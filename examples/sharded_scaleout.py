"""Multi-device scale-out demo: the ``device-sharded`` engine over a mesh.

The paper's headline numbers (1M FPS Atari, 3M FPS MuJoCo, §4.1) come
from saturating all available hardware; here the same engine shards its
``PoolState`` across every visible device with ``shard_map`` and the
rollout stays device-resident end to end.

Run on CPU with simulated devices (the flag must be set before jax
imports, which is why this script sets it at the very top):

    PYTHONPATH=src python examples/sharded_scaleout.py --shards 4
"""

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--envs-per-shard", type=int, default=16)
    ap.add_argument("--task", default="TokenCopy-v0")
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args()

    if "jax" in sys.modules:
        raise RuntimeError("set the device count before importing jax")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.shards}"
        ).strip()

    import jax

    from repro.core.registry import make
    from repro.core.xla_loop import build_random_collect_fn, frames_per_batch

    print(f"devices: {jax.devices()}")
    for shards in (1, args.shards):
        n = args.envs_per_shard * shards
        pool = make(args.task, num_envs=n, engine="device-sharded",
                    num_shards=shards)
        collect = build_random_collect_fn(pool, num_steps=args.steps)
        ps, ts = pool.reset(jax.random.PRNGKey(0))
        ps, ts, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(1))
        jax.block_until_ready(traj.reward)          # warmup + compile
        t0 = time.time()
        ps, ts, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(2))
        frames = float(traj.step_cost.sum())
        dt = time.time() - t0
        print(f"mesh={shards}  envs={n:4d}  "
              f"{frames / dt:>12,.0f} steps/s  "
              f"(~{frames_per_batch(pool) * args.steps} frames/collect)")


if __name__ == "__main__":
    main()
