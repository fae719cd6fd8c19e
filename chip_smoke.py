"""Bring-up smoke of the system's main paths on TPU chips.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip mesh paths only

One chip runs four phases, in this order, in this one process:

  1. kernel parity: every Pallas kernel of the main paths (``env_step``
     masked, ``grayscale``, ``resize``, ``crop``, ``pong_render``,
     ``decode_attention``) compiled for the chip against its
     ``reference`` backend — the integer image family bitwise, the float
     kernels within ``ENV_TOL`` / ``DECODE_TOL`` — with the kernel
     (``tpu_custom_call``) asserted in each compiled program;
  2. the Atari PPO trainer: ``repro.make("PongClassic-v5")`` on the
     ``device`` engine through ``rl/ppo.py::train_device`` with the
     Nature CNN and CleanRL's ``PPOConfig``, ``ATARI_UPDATES`` updates;
  3. the MuJoCo async pool: ``Ant-v3`` with N=4096, M=1024 through
     ``build_random_collect_fn``, on the Pallas ``env_step``;
  4. the decode server: ``DecodePool`` greedy tokens with the compiled
     ``decode_attention`` equal to the same pool on the ``reference``
     attention.

``--chips 4`` runs only what exists across chips: ``train_device`` over a
four-shard ``device-sharded`` PongClassic-v5 pool, a scripted sync
rollout at mesh 4 against mesh 1, and an ``AntSkew-v3`` collect under the
``hierarchical`` schedule, whose ``(D, C)`` all_gather crosses the
interconnect.

Every phase prints its findings on its own ``[phase]`` lines.  The last
line of standard output is ``{"ok": true, "device": {...}}``, printed
only when every phase passed; with no TPU, or with any failure, the
script exits non-zero without it.  The compile cache is
``launch/compile_cache.py``'s.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# W1 size: the ROADMAP's Atari PPO cell.  Compiled ahead of time for a
# v5e, its whole fused train_step needs 11.8 GB of the chip's 16 GB.
ATARI_ENVS = 1024
ATARI_UPDATES = 3
ANT_ENVS, ANT_BATCH, ANT_STEPS = 4096, 1024, 64
# float kernels: largest |pallas - reference| allowed (f32, HIGHEST)
ENV_TOL = 1e-4
DECODE_TOL = 1e-4
KERNEL = "tpu_custom_call"     # a Pallas kernel in a compiled TPU program


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def require_kernels(text: str, what: str, at_least: int = 1) -> int:
    n = text.count(KERNEL)
    require(n >= at_least, f"{what}: {n} {KERNEL} in the compiled program, "
                           f"expected at least {at_least}")
    return n


# --------------------------------------------------------------------- #
# phase 1: kernel parity
# --------------------------------------------------------------------- #
def kernel_parity(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.backend import resolve_backend
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.env_step.ops import env_multi_step
    from repro.kernels.image import ops as image

    kernel = resolve_backend("auto")

    def run(name, fn, *args, **kw):
        """(kernel output, reference output); asserts the kernel is in
        the compiled program."""
        out = {}
        for backend in (kernel, "reference"):
            f = jax.jit(lambda *a, b=backend: fn(*a, backend=b, **kw))
            compiled = f.lower(*args).compile()
            if backend == kernel:
                require_kernels(compiled.as_text(), name)
            out[backend] = jax.device_get(compiled(*args))
        return out[kernel], out["reference"]

    def bitwise(name, fn, *args, **kw):
        got, want = run(name, fn, *args, **kw)
        require(got.shape == want.shape and got.dtype == want.dtype,
                f"{name}: {got.shape}/{got.dtype} vs {want.shape}/{want.dtype}")
        diff = int(np.count_nonzero(got != want))
        report("parity", kernel=name, shape=tuple(got.shape), bitwise=diff == 0,
               differing=diff)
        require(diff == 0, f"{name}: {diff} elements differ from reference")

    def close(name, tol, fn, *args, **kw):
        got, want = run(name, fn, *args, **kw)
        got, want = jax.tree.leaves(got), jax.tree.leaves(want)
        err = max(float(np.max(np.abs(g - w))) for g, w in zip(got, want))
        finite = all(bool(np.isfinite(g).all()) for g in got)
        report("parity", kernel=name, shape=tuple(got[0].shape),
               max_abs_err=err, tol=tol)
        require(finite and err <= tol, f"{name}: max |err| {err} > {tol}")

    ks = jax.random.split(jax.random.PRNGKey(seed), 12)
    # env_step, masked, at the Ant-v3 shape: contacts active (low torso)
    n = ANT_ENVS
    state = (jax.random.normal(ks[0], (n, 28)) * 0.3).at[:, 2].set(0.3)
    action = jax.random.uniform(ks[1], (n, 8), minval=-1, maxval=1)
    cost = jax.random.randint(ks[2], (n,), 0, 10)
    r0 = jax.random.normal(ks[3], (n,))
    with jax.default_matmul_precision("highest"):
        close("env_step_masked", ENV_TOL, env_multi_step, state, action, cost,
              r0, max_cost=9, block_n=256)

    # the PongClassic-v5 image family at W1's N
    n = ATARI_ENVS
    rgb = jax.random.randint(ks[4], (n, 210, 160, 3), 0, 256, jnp.int32
                             ).astype(jnp.uint8)
    gray = rgb[..., 0]
    bitwise("grayscale", image.grayscale, rgb)
    bitwise("resize", lambda x, backend: image.resize(x, 84, 84,
                                                      backend=backend), gray)
    bitwise("crop", lambda x, backend: image.crop(x, 34, 0, 160, 160,
                                                  backend=backend), gray)
    pos = [jax.random.uniform(k, (n,), minval=0.0, maxval=84.0)
           for k in jax.random.split(ks[5], 4)]
    bitwise("pong_render", image.pong_render, *pos)

    # decode_attention: a serving cache (B=32, T=1024) and the LM
    # policy's default shape (rl/policy_lm.py: H=4, Hkv=2, hd=16, T=64)
    for b, h, hkv, t, d, bt in ((32, 8, 2, 1024, 64, 512),
                                (64, 4, 2, 64, 16, 64)):
        kq, kk, kv, kl = jax.random.split(ks[6 + (t == 64)], 4)
        q = jax.random.normal(kq, (b, h, d))
        k = jax.random.normal(kk, (b, hkv, t, d))
        v = jax.random.normal(kv, (b, hkv, t, d))
        lengths = jax.random.randint(kl, (b,), 1, t + 1)
        with jax.default_matmul_precision("highest"):
            close(f"decode_attention_T{t}", DECODE_TOL, decode_attention,
                  q, k, v, lengths, block_t=bt)


# --------------------------------------------------------------------- #
# phase 2: the Atari PPO trainer
# --------------------------------------------------------------------- #
def _finite_history(hist: list[dict], what: str) -> None:
    import math

    for rec in hist:
        bad = {k: v for k, v in rec.items()
               if isinstance(v, float) and not math.isfinite(v)}
        require(not bad, f"{what}: non-finite {bad} at iter {rec['iter']}")


def _compiled_train_step(pool, cfg):
    """AOT-compile train_device's fused collect+update program at this
    pool's shapes."""
    import jax
    import jax.numpy as jnp

    from repro.rl.nets import ActorCritic
    from repro.rl.ppo import PPOState, make_ppo_update, make_train_step

    net = ActorCritic(pool.spec)
    opt, update = make_ppo_update(net, cfg, 1)
    state = jax.eval_shape(
        lambda k: (lambda p: PPOState(p, opt.init(p), jnp.int32(0)))(
            net.init(k)), jax.random.PRNGKey(0))
    ps, ts = jax.eval_shape(pool.reset, jax.random.PRNGKey(0))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    step = make_train_step(pool, cfg, net, update)
    return step.lower(state, ps, ts, key, key).compile()


def atari_ppo(seed: int, num_envs: int, updates: int, engine: str,
              **pool_kw) -> list[dict]:
    import jax

    import repro
    from repro.launch.compile_cache import CompileCounter
    from repro.rl.ppo import PPOConfig, train_device

    pool = repro.make("PongClassic-v5", num_envs=num_envs, engine=engine,
                      seed=seed, **pool_kw)
    cfg = PPOConfig(total_steps=updates * PPOConfig.num_steps * num_envs)
    with CompileCounter() as cc:
        t0 = time.perf_counter()
        compiled = _compiled_train_step(pool, cfg)
        aot_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    kernels = require_kernels(compiled.as_text(), "train_step", at_least=3)
    report("atari", num_envs=num_envs, engine=engine, shards=pool.num_shards,
           num_steps=cfg.num_steps, epochs=cfg.epochs,
           minibatches=cfg.minibatches, kernels_in_train_step=kernels,
           aot_compile_s=round(aot_s, 3), argument_bytes=mem.argument_size_in_bytes,
           temp_bytes=mem.temp_size_in_bytes, **cc.summary())
    hist: list[dict] = []
    with CompileCounter() as cc:
        train_device(pool, cfg, seed=seed, log_fn=hist.append)
    require(len(hist) == updates, f"{len(hist)} updates, expected {updates}")
    _finite_history(hist, "train_device")
    t = [h["time_s"] for h in hist]
    stats = jax.devices()[0].memory_stats() or {}
    report("atari", updates=len(hist), first_update_s=round(t[0], 3),
           s_per_update_after_warmup=(t[-1] - t[0]) / (len(t) - 1),
           peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"),
           bytes_limit=stats.get("bytes_limit", "not reported"),
           loss=hist[-1]["loss"], mean_return=hist[-1]["mean_return"],
           episodes=sum(h["episodes"] for h in hist),
           train_device_compile=cc.summary())
    return hist


# --------------------------------------------------------------------- #
# phase 3: the MuJoCo async pool
# --------------------------------------------------------------------- #
def ant_collect(seed: int, task: str, num_envs: int, batch_size: int,
                steps: int, engine: str = "device", **pool_kw):
    import jax
    import numpy as np

    import repro
    from repro.core.xla_loop import build_random_collect_fn

    pool = repro.make(task, num_envs=num_envs, batch_size=batch_size,
                      engine=engine, seed=seed, **pool_kw)
    collect = build_random_collect_fn(pool, num_steps=steps)
    k0, k1 = jax.random.split(jax.random.PRNGKey(seed))
    ps, ts = pool.reset(k0)
    compiled = collect.lower(ps, None, ts, k1).compile()
    text = compiled.as_text()
    require_kernels(text, f"{task} collect")
    ps, ts, traj, _ = compiled(ps, None, ts, k1)
    ids = np.asarray(traj.env_id)
    rew = np.asarray(traj.reward)
    served = np.bincount(ids.ravel(), minlength=num_envs)
    require(ids.shape == (steps, batch_size), f"served block {ids.shape}")
    require(ids.min() >= 0 and ids.max() < num_envs,
            f"env_id out of [0, {num_envs}): {ids.min()}..{ids.max()}")
    require(served.min() > 0,
            f"{int((served == 0).sum())} lanes never served in {steps} recvs")
    require(bool(np.isfinite(rew).all()), "non-finite rewards")
    report("mujoco", task=task, engine=engine, num_envs=num_envs,
           batch_size=batch_size, steps=steps, schedule=pool.scheduler.name,
           served_min=int(served.min()), served_max=int(served.max()),
           reward_mean=float(rew.mean()), frames=int(traj.step_cost.sum()))
    return pool, ps, text


# --------------------------------------------------------------------- #
# phase 4: the decode server
# --------------------------------------------------------------------- #
def decode_server(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.specs import ArraySpec, EnvSpec
    from repro.rl.policy_lm import LMPolicy, default_policy_config
    from repro.serving import DecodePool

    vocab, max_len, lanes, n_req = 256, 64, 8, 16
    spec = EnvSpec(
        name="serve-lm",
        obs_spec=ArraySpec((2,), jnp.int32, 0, vocab - 1),
        act_spec=ArraySpec((), jnp.int32, 0, vocab - 1),
        max_episode_steps=max_len,
    )
    rng = np.random.default_rng(seed)
    prompts = [list(rng.integers(0, vocab, rng.integers(4, 17)))
               for _ in range(n_req)]
    budgets = [int(rng.choice([8, 32])) for _ in range(n_req)]
    cfg = default_policy_config(vocab, max_len)
    params = LMPolicy(spec, cfg, max_len=max_len).init(
        jax.random.PRNGKey(seed))
    outs = {}
    # f32 at the highest matmul precision on both sides, so that a
    # greedy argmax compares the attention kernels and not the MXU passes
    with jax.default_matmul_precision("highest"):
        for backend in ("auto", "reference"):
            policy = LMPolicy(spec, cfg, max_len=max_len, backend=backend)
            if backend == "auto":
                z = policy.init_lanes(lanes)
                toks = jnp.zeros((lanes,), jnp.int32)
                text = jax.jit(policy.decode_step).lower(
                    params, toks, z.k, z.v, z.length).compile().as_text()
                require_kernels(text, "decode_step", at_least=cfg.n_layers)
            outs[backend], stats = DecodePool(
                policy, num_lanes=lanes, max_new=32).serve(
                    params, prompts, max_new=budgets)
            require([len(o) for o in outs[backend]] == budgets,
                    f"{backend}: token counts differ from the budgets")
    same = outs["auto"] == outs["reference"]
    report("decode", requests=n_req, lanes=lanes, tokens=stats.total_tokens,
           decode_steps=stats.decode_steps, greedy_equal_reference=same)
    require(same, "greedy tokens with the compiled decode_attention differ "
                  "from the reference attention")


# --------------------------------------------------------------------- #
# --chips 4: the mesh paths
# --------------------------------------------------------------------- #
def check_shard_placement(pool, ps) -> None:
    """Each shard's PoolState rows sit on their own device."""
    import jax

    for leaf in jax.tree.leaves(ps):
        if leaf.ndim == 0 or leaf.shape[0] != pool.num_envs:
            continue
        shards = leaf.addressable_shards
        devices = {s.device for s in shards}
        rows = {s.index[0].start for s in shards}
        require(len(shards) == pool.num_shards == len(devices) == len(rows),
                f"{len(shards)} shards of a per-lane leaf on "
                f"{len(devices)} devices, {len(rows)} row blocks")


def mesh_rollout(seed: int, shards: int, num_envs: int, steps: int):
    """Scripted sync rollout of PongClassic-v5 over ``shards`` chips,
    each step's block in env_id order."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro

    pool = repro.make("PongClassic-v5", num_envs=num_envs,
                      engine="device-sharded", num_shards=shards, seed=seed)
    ps, ts = pool.reset(jax.random.PRNGKey(seed))
    ps = pool.device_put(ps)
    check_shard_placement(pool, ps)
    step = jax.jit(pool.step)
    rec = {"ids": [], "rew": [], "done": [], "obs": []}
    for t in range(steps):
        i = np.asarray(ts.env_id)
        order = np.argsort(i)
        for k, x in (("ids", i), ("rew", ts.reward), ("done", ts.done),
                     ("obs", ts.obs)):
            rec[k].append(np.asarray(x)[order])
        a = jnp.asarray(((i * 3 + t) % 6).astype(np.int32))
        ps, ts = step(ps, a, ts.env_id)
    return {k: np.stack(v) for k, v in rec.items()}


def four_chips(seed: int) -> None:
    import jax
    import numpy as np

    d = 4
    require(len(jax.devices()) >= d, f"{len(jax.devices())} devices, need {d}")
    # train_device over four shards: W1's CleanRL shapes at 64 lanes/chip
    hist = atari_ppo(seed, num_envs=64 * d, updates=2,
                     engine="device-sharded", num_shards=d)
    report("mesh", part="train_device", shards=d, updates=len(hist),
           loss=hist[-1]["loss"])

    one = mesh_rollout(seed, 1, num_envs=16 * d, steps=16)
    four = mesh_rollout(seed, d, num_envs=16 * d, steps=16)
    equal = {k: bool(np.array_equal(one[k], four[k])) for k in one}
    report("mesh", part="sync_rollout_mesh4_vs_mesh1", steps=16,
           num_envs=16 * d, **equal)
    require(all(equal.values()), f"mesh 4 differs from mesh 1: {equal}")

    pool, ps, text = ant_collect(seed, "AntSkew-v3", num_envs=ANT_ENVS,
                                 batch_size=ANT_BATCH, steps=32,
                                 engine="device-sharded", num_shards=d,
                                 schedule="hierarchical")
    check_shard_placement(pool, ps)
    gathers = text.count("all-gather")
    report("mesh", part="antskew_hierarchical", shards=d,
           all_gathers=gathers)
    require(gathers > 0, "no all-gather in the hierarchical collect")


# --------------------------------------------------------------------- #
def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh paths only, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.kernels.backend import resolve_backend
    from repro.launch.compile_cache import CompileCounter, enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform!r})",
              file=sys.stderr)
        return 2
    if resolve_backend("auto") != "pallas":
        print(f"chip_smoke: kernel backend 'auto' resolves to "
              f"{resolve_backend('auto')!r}, not 'pallas'", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    report("setup", platform=dev.platform, kind=dev.device_kind.replace(" ", "_"),
           count=len(jax.devices()), jax=jax.__version__, cache_dir=cache_dir)
    t0 = time.perf_counter()
    with CompileCounter() as cc:
        if args.chips == 4:
            four_chips(args.seed)
        else:
            kernel_parity(args.seed)
            atari_ppo(args.seed, ATARI_ENVS, ATARI_UPDATES, "device")
            ant_collect(args.seed, "Ant-v3", ANT_ENVS, ANT_BATCH, ANT_STEPS)
            decode_server(args.seed)
    report("compile", wall_s=round(time.perf_counter() - t0, 3),
           **cc.summary())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
