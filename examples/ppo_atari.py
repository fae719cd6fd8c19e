"""End-to-end PPO on the Atari-like env (paper §4.2 / Figure 6).

Quickstart — the full classic ALE pipeline, entirely on device:

    PYTHONPATH=src python examples/ppo_atari.py --total-steps 100000

The default task is ``PongClassic-v5``: the env renders native
210x160x3 RGB screens through the batched Pallas render kernel, and the
engine fuses the classic DQN preprocessing — ``Grayscale`` ->
``Resize(84, 84)`` (the ``kernels/image`` Pallas family) ->
``FrameStack(4)`` -> ``RewardClip`` — into its jitted recv
(``core/transforms.py``), so PPO trains on the stacked 4x84x84 stream
with zero Python wrappers and no pixel ever leaving the device — the
EnvPool §3.4 placement plus CuLE's on-accelerator preprocessing
argument.  Any registered task works; presets come from the registry
(``repro.make`` applies the task's default transform pipeline), and
``--raw`` drops the preset to train on the env's raw observations.

Default settings mirror the paper's CleanRL Atari config (Table 3, N=8);
``--tuned`` switches to the high-throughput Figure-6 settings (N=64,
larger batch, fewer epochs) that trade sample efficiency for wall-clock.

    PYTHONPATH=src python examples/ppo_atari.py --task Pong-v5  # 84x84 direct
    PYTHONPATH=src python examples/ppo_atari.py --tuned
"""

import argparse
import json

import repro
from repro.launch.compile_cache import enable_compile_cache
from repro.rl.ppo import PPOConfig, train_device


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default="PongClassic-v5",
                    help="registered task; the default runs the RGB "
                         "render + Grayscale/Resize classic pipeline")
    ap.add_argument("--total-steps", type=int, default=100_000)
    ap.add_argument("--num-envs", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=None)
    ap.add_argument("--tuned", action="store_true",
                    help="paper Fig.6 high-throughput settings (N=64)")
    ap.add_argument("--num-steps", type=int, default=128,
                    help="rollout length per iteration (smaller = faster "
                         "smoke runs on CPU)")
    ap.add_argument("--raw", action="store_true",
                    help="drop the task's preset pipeline and train on "
                         "raw observations")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-json", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    if args.tuned:
        num_envs, batch = 64, 64
        cfg = PPOConfig(total_steps=args.total_steps,
                        num_steps=args.num_steps,
                        minibatches=4, epochs=2, lr=8e-4, ent_coef=0.01,
                        vf_clip=False)
    else:
        num_envs = args.num_envs
        batch = args.batch_size or num_envs
        cfg = PPOConfig(total_steps=args.total_steps,
                        num_steps=args.num_steps,
                        minibatches=4, epochs=4, lr=2.5e-4)

    # the registry preset IS the preprocessing config: for
    # PongClassic-v5 that's Grayscale -> Resize(84,84) -> FrameStack(4)
    # -> RewardClip, all fused into the engine's jitted recv
    kw = {"transforms": []} if args.raw else {}
    pool = repro.make(args.task, num_envs=num_envs, batch_size=batch,
                      engine="device", **kw)
    print(f"[ppo_atari] task={args.task} obs_spec="
          f"{pool.spec.obs_spec.shape} pipeline="
          f"{[type(t).__name__ for t in pool.pipeline.transforms]}",
          flush=True)

    def log(rec):
        print(json.dumps({k: (round(v, 3) if isinstance(v, float) else v)
                          for k, v in rec.items()}), flush=True)

    state, net, hist = train_device(pool, cfg, seed=args.seed, log_fn=log)
    if args.out_json:
        with open(args.out_json, "w") as f:
            json.dump(hist, f)


if __name__ == "__main__":
    main()
