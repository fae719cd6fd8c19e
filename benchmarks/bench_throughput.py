"""Paper Table 1 / Figure 3: pure environment simulation throughput.

Engines × {AtariLike Pong (FPS = steps x frameskip 4), MujocoLike Ant
(FPS = physics substeps, base 5)} × num_envs, random actions (paper §4.1).
This container has few CPU cores, so host-engine numbers play the paper's
"Laptop" column role; the device engine is the TPU-native contribution.

``--ab`` benchmarks the batched-native hot path against the forced
vmap-lifting adapter on MujocoLike Ant (the CI regression guard for the
batched-env rewrite); every mode writes its rows to
``BENCH_throughput.json`` at the repo root.

``--mesh D`` benchmarks the multi-device scale-out instead: the
ShardedDeviceEnvPool on the token env, weak scaling (fixed envs per
shard, the paper's §4.1 protocol — more hardware hosts more envs),
reporting aggregate FPS at mesh=1 vs mesh=D.  On CPU CI the mesh is
simulated with ``XLA_FLAGS=--xla_force_host_platform_device_count`` —
set *before* jax import, which is why this module only imports jax
inside functions.

``--schedule`` A/Bs the async scheduling policies (``core/scheduler.py``:
fifo vs sjf vs hierarchical) on the long-tail-skew workload
(``TokenSkew-v0``: 25% of episodes carry an 8x decode-cost multiplier)
on the sharded engine at ``--mesh`` shards (default 4), writing the
``BENCH_schedule.json`` artifact; ``--min-schedule-ratio`` gates CI on
best(sjf, hierarchical)/fifo FPS.

``--resident`` A/Bs the device-resident collect loop (the donated
``lax.scan`` over the mesh engine — ``PoolState`` never leaves the
mesh) against the per-step host-driven recv loop (one jitted step
dispatch per env step, batch materialized on the host each step) at
mesh 1 and ``--mesh`` D, writing ``BENCH_resident.json``;
``--min-resident-ratio`` gates CI on resident/host-driven FPS at
mesh=D — the acceptance check that the PPO-style scan loop keeps its
zero-host-round-trip advantage.

``--pipelined`` A/Bs the pipelined collect/train driver
(``rl/ppo.py::train_pipelined``: collect scan and learner update as two
concurrently-dispatched programs, rollout one policy step stale,
V-trace corrected) against the fused-serial ``train_device`` (one XLA
program, collect and update serialized — and the update replicated
across every mesh shard) at mesh 1 and ``--mesh`` D, reporting steady-
state wall-clock per update; ``--min-pipelined-ratio`` gates CI on
fused/pipelined time per update at mesh=D.  Both drivers train the
same TokenEnv policy, and the summary records each side's final
``mean_return`` so reward parity under the lag correction is visible
in the artifact.  Writes ``BENCH_pipelined.json``.

``--transforms`` A/Bs the in-engine transform pipeline
(``core/transforms.py``, fused into the jitted recv) against the
classic python-wrapper placement (raw pool + the numpy mirror applied
host-side each step) on ``PongStack-v5`` — the EnvPool §3.4 claim that
preprocessing belongs inside the engine.  Both sides run the identical
step loop and materialize the final observations on the host; only the
transform placement differs.  Writes ``BENCH_transforms.json``;
``--min-transform-ratio`` gates CI on in-engine/wrapper FPS.

``--image`` is the same placement A/B on the IMAGE pipeline
(``PongClassic-v5``: native 210x160 RGB render -> Grayscale -> Resize
(84,84) -> FrameStack(4) -> RewardClip, the ALE preprocessing stack).
In-engine, grayscale+resize run as the ``kernels/image`` family fused
into the jitted recv next to the batched render; the wrapper side
ships full RGB screens to the host and runs the bitwise-identical
numpy mirrors per step.  Writes ``BENCH_image.json``;
``--min-image-ratio`` gates CI on in-engine/wrapper FPS.

``--decode`` benches the LLM-policy decode path (``rl/policy_lm.py``):
(a) the KV-cached one-token-per-recv ``decode_step`` (per-lane static
cache + ``kernels/decode_attention`` over ragged lengths) against the
full-recompute no-cache forward over each lane's token history — the
per-token cost a cache-less policy server pays — at N=32 on
``TokenCopy-v0``; and (b) continuous batching (the engine's auto-reset
keeps every served lane a live request) against run-to-completion
static batches (lanes idle behind the batch's longest episode) on the
ragged-generation-length mix ``TokenRagged-v0``.  Both sides of (b)
run the IDENTICAL compiled program, so the ratio is pure utilization:
useful tokens per lane-slot under each admission discipline.  Writes
``BENCH_decode.json``; ``--min-decode-cached-ratio`` /
``--min-decode-cb-ratio`` gate CI.

``--obs`` A/Bs the in-graph telemetry overhead (``obs/telemetry.py``):
the device sync hot loop (the resident random-collect scan) with the
``PoolState`` counters on (``obs=True``, the default) vs off
(``obs=False`` — zero telemetry leaves, the exact pre-telemetry XLA
program).  Best-of-iters FPS per side so 2-core CI timer noise doesn't
masquerade as overhead; the summary embeds the instrumented pool's
``stats()`` snapshot and its ``MetricsRegistry`` export.  Writes
``BENCH_obs.json``; ``--min-obs-ratio`` gates CI on obs-on/obs-off FPS
(the acceptance bound is 0.97 — instrumentation costs <= 3% of the hot
loop).

``--multihost`` A/Bs the multi-process topology on loopback
(``launch/mesh.py::initialize_multihost``, gloo collectives): (a) WEAK
SCALING — aggregate random-collect FPS of 2 processes (global mesh
spanning both) vs 1 process at the same per-process shard count, the
cross-host analogue of ``--mesh``; and (b) DISAGGREGATION —
``rl/ppo.py::train_disaggregated`` (env shards on one process, the
learner update on another, params handed back by host broadcast each
iteration) vs the colocated single-process ``train_pipelined`` at the
same sizes.  Each rank runs in a fresh subprocess via the hidden
``--mh-worker`` entry so the set-before-import device-count dance stays
per-process.  Writes ``BENCH_multihost.json``;
``--min-multihost-ratio`` / ``--min-disagg-ratio`` gate CI (the
acceptance bounds — 1.5x weak scaling, 1.0x disaggregation — assume
>= 2 host cores; scripts/ci.sh derives honest floors from nproc).

Every artifact carries a shared ``meta`` header (git commit, jax
version + platform, device count, resolved kernel backend, host core
count) so BENCH_*.json files are comparable across machines/commits.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bench_meta() -> dict:
    """Shared metadata header stamped into every BENCH_*.json artifact:
    enough provenance to compare numbers across machines and commits.
    jax is imported lazily — this runs after the benches, so the mesh
    env-var dance in main() has already happened."""
    import subprocess

    import jax

    try:
        commit = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=ROOT, capture_output=True,
            text=True, timeout=10,
        ).stdout.strip() or None
    except Exception:
        commit = None
    from repro.kernels.backend import resolve_backend

    from repro.launch.mesh import multihost_info

    return {
        "git_commit": commit,
        "jax_version": jax.__version__,
        "jax_platform": jax.default_backend(),
        "device_count": jax.device_count(),
        "kernel_backend": resolve_backend("auto"),
        "host_cpu_count": os.cpu_count(),
        # multi-host provenance (launch/mesh.py): single-process runs
        # report the backfill defaults {1, 0, None}, so pre-multihost
        # artifacts and multi-host ones stay comparable field-for-field
        **multihost_info(),
    }


def fps_unit(task: str) -> str:
    if "Pong" in task:
        return "frames"
    if "Token" in task:
        return "tokens"
    return "physics-steps"


def bench_device(task: str, num_envs: int, batch_size: int, mode: str,
                 steps: int = 60, iters: int = 3,
                 batched: bool | None = None) -> float:
    import jax

    from repro.core.device_pool import DeviceEnvPool
    from repro.core.registry import _jax_env
    from repro.core.xla_loop import build_random_collect_fn

    env = _jax_env(task)
    pool = DeviceEnvPool(env, num_envs, batch_size, mode=mode,
                         batched=batched)
    collect = build_random_collect_fn(pool, num_steps=steps)
    ps, ts = pool.reset(jax.random.PRNGKey(0))
    ps, ts, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(1))
    jax.block_until_ready(traj.reward)
    frames = 0.0
    t0 = time.time()
    for i in range(iters):
        ps, ts, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(2 + i))
        frames += float(traj.step_cost.sum())
    jax.block_until_ready(traj.reward)
    return frames / (time.time() - t0)


def bench_host(task: str, engine: str, num_envs: int, batch_size: int | None,
               steps: int = 30, num_threads: int | None = None) -> float:
    import repro

    pool = repro.make(task, engine=engine, num_envs=num_envs,
                      batch_size=batch_size, num_threads=num_threads)
    rng = np.random.default_rng(0)
    spec = pool.spec
    try:
        if hasattr(pool, "async_reset"):
            pool.async_reset()
            out = pool.recv()
        else:
            out = pool.reset()
        M = getattr(pool, "batch_size", num_envs)
        # warmup
        for _ in range(3):
            acts = spec.act_spec.sample(rng, (M,))
            out = pool.step(acts, out["env_id"])
        frames = 0.0
        t0 = time.time()
        for _ in range(steps):
            acts = spec.act_spec.sample(rng, (M,))
            out = pool.step(acts, out["env_id"])
            frames += float(np.sum(out["step_cost"]))
        dt = time.time() - t0
        return frames / dt
    finally:
        pool.close() if hasattr(pool, "close") else None


def run(csv_rows: list[str]) -> None:
    tasks = ["Pong-v5", "Ant-v3"]
    for task in tasks:
        rows = []
        # host engines (paper Table 1 baselines)
        for engine, n, m in [("forloop", 8, None), ("thread", 8, 8),
                             ("thread", 16, 8)]:
            tag = f"{engine}{'-async' if m and m < n else ''}"
            try:
                fps = bench_host(task, engine, n, m)
                rows.append((f"{tag}_N{n}", fps))
            except Exception as e:  # pragma: no cover
                rows.append((f"{tag}_N{n}", float("nan")))
        # device engines
        for mode, n, m in [("sync", 64, 64), ("async", 64, 32),
                           ("async", 128, 32), ("masked", 64, 32)]:
            fps = bench_device(task, n, m, mode)
            rows.append((f"device-{mode}_N{n}_M{m}", fps))
        best = max(r[1] for r in rows if np.isfinite(r[1]))
        for name, fps in rows:
            csv_rows.append(
                f"throughput_{task}_{name},{1e6/max(fps,1e-9):.3f},"
                f"{fps:.0f} {fps_unit(task)}/s"
            )
        csv_rows.append(
            f"throughput_{task}_BEST,{1e6/best:.3f},{best:.0f} {fps_unit(task)}/s"
        )


def bench_sharded(task: str, envs_per_shard: int, shards: int,
                  steps: int = 40, iters: int = 3) -> float:
    """Aggregate FPS of a ShardedDeviceEnvPool rollout (weak scaling)."""
    import jax

    from repro.core.registry import make
    from repro.core.xla_loop import build_random_collect_fn

    pool = make(task, num_envs=envs_per_shard * shards,
                engine="device-sharded", num_shards=shards)
    collect = build_random_collect_fn(pool, num_steps=steps)
    ps, ts = pool.reset(jax.random.PRNGKey(0))
    ps, ts, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(1))  # warmup
    jax.block_until_ready(traj.reward)
    frames = 0.0
    t0 = time.time()
    for i in range(iters):
        ps, ts, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(2 + i))
        frames += float(traj.step_cost.sum())
    jax.block_until_ready(traj.reward)
    return frames / (time.time() - t0)


def run_mesh(mesh: int, task: str = "TokenCopy-v0", envs_per_shard: int = 16,
             steps: int = 40, iters: int = 3) -> list[str]:
    """Single-vs-multi-shard FPS table (the scale-out acceptance check)."""
    rows: list[str] = []
    fps1 = bench_sharded(task, envs_per_shard, 1, steps, iters)
    fpsD = bench_sharded(task, envs_per_shard, mesh, steps, iters)
    unit = fps_unit(task)
    rows.append(f"sharded_{task}_mesh1_N{envs_per_shard},"
                f"{1e6/max(fps1,1e-9):.3f},{fps1:.0f} {unit}/s")
    rows.append(f"sharded_{task}_mesh{mesh}_N{envs_per_shard * mesh},"
                f"{1e6/max(fpsD,1e-9):.3f},{fpsD:.0f} {unit}/s")
    rows.append(f"sharded_{task}_SPEEDUP,{fpsD / max(fps1, 1e-9):.2f},"
                f"mesh{mesh} vs mesh1 aggregate")
    return rows


def bench_schedule(task: str, schedule: str, envs_per_shard: int, shards: int,
                   batch_frac: int = 4, steps: int = 60, iters: int = 3
                   ) -> float:
    """Aggregate FPS of an async sharded rollout under one scheduling
    policy (N = envs_per_shard * shards, M = N / batch_frac)."""
    import jax

    from repro.core.registry import make
    from repro.core.xla_loop import build_random_collect_fn

    n = envs_per_shard * shards
    pool = make(task, num_envs=n, batch_size=max(n // batch_frac, shards),
                engine="device-sharded", num_shards=shards, schedule=schedule)
    collect = build_random_collect_fn(pool, num_steps=steps)
    ps, ts = pool.reset(jax.random.PRNGKey(0))
    ps, ts, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(1))  # warmup
    jax.block_until_ready(traj.reward)
    frames = 0.0
    t0 = time.time()
    for i in range(iters):
        ps, ts, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(2 + i))
        frames += float(traj.step_cost.sum())
    jax.block_until_ready(traj.reward)
    return frames / (time.time() - t0)


def run_schedule(mesh: int, task: str = "TokenSkew-v0",
                 envs_per_shard: int = 16, steps: int = 60, iters: int = 3
                 ) -> tuple[list[str], dict]:
    """Scheduling-policy A/B on the long-tail-skew workload: fifo vs
    sjf vs hierarchical on the sharded engine at mesh=D.  The win comes
    from cost-homogeneous recv blocks: the fused multi-substep pads each
    block to its max cost, so mixing one heavy lane into a cheap block
    multiplies its latency (paper Fig. 2a, per shard)."""
    rows: list[str] = []
    unit = fps_unit(task)
    fps: dict[str, float] = {}
    for schedule in ("fifo", "sjf", "hierarchical"):
        f = bench_schedule(task, schedule, envs_per_shard, mesh,
                           steps=steps, iters=iters)
        fps[schedule] = f
        rows.append(
            f"schedule_{task}_{schedule}_mesh{mesh},"
            f"{1e6/max(f,1e-9):.3f},{f:.0f} {unit}/s"
        )
    best = max("sjf", "hierarchical", key=lambda s: fps[s])
    ratio = fps[best] / max(fps["fifo"], 1e-9)
    rows.append(
        f"schedule_{task}_BEST_RATIO,{ratio:.3f},{best}/fifo FPS at mesh{mesh}"
    )
    summary = {
        "task": task,
        "mesh": mesh,
        "envs_per_shard": envs_per_shard,
        "fps": fps,
        "best": best,
        "best_over_fifo": ratio,
    }
    return rows, summary


def bench_resident_pair(task: str, envs_per_shard: int, shards: int,
                        steps: int = 40, iters: int = 3
                        ) -> tuple[float, float]:
    """(resident FPS, host-driven FPS) for one mesh size: the SAME pool
    and random policy driven by the donated device-resident scan vs the
    per-step host-materializing loop (``build_stepwise_collect_fn``)."""
    import jax

    from repro.core.registry import make
    from repro.core.xla_loop import (
        build_collect_fn,
        build_stepwise_collect_fn,
    )

    pool = make(task, num_envs=envs_per_shard * shards,
                engine="device-sharded", num_shards=shards)
    spec = pool.spec

    def policy(params, obs, key):
        del params, obs
        return spec.act_spec.sample_jax(key, (pool.batch_size,))

    out = {}
    for tag, build in (("resident", build_collect_fn),
                       ("host", build_stepwise_collect_fn)):
        collect = build(pool, policy, num_steps=steps)
        ps, ts = pool.reset(jax.random.PRNGKey(0))
        ps, ts, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(1))
        jax.block_until_ready(traj.reward)
        frames = 0.0
        t0 = time.time()
        for i in range(iters):
            ps, ts, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(2 + i))
            frames += float(np.asarray(traj.step_cost).sum())
        jax.block_until_ready(traj.reward)
        out[tag] = frames / (time.time() - t0)
    return out["resident"], out["host"]


def run_resident(mesh: int, task: str = "TokenCopy-v0",
                 envs_per_shard: int = 16, steps: int = 40, iters: int = 3
                 ) -> tuple[list[str], dict]:
    """Device-resident vs host-driven collect A/B at mesh 1 and D (see
    --resident).  The resident loop is what ``rl/ppo.train_device``
    runs; the gate pins that its zero-host-round-trip structure keeps
    paying off on the multi-device mesh."""
    rows: list[str] = []
    unit = fps_unit(task)
    fps: dict[str, dict[str, float]] = {}
    for d in sorted({1, mesh}):
        res, host = bench_resident_pair(task, envs_per_shard, d,
                                        steps=steps, iters=iters)
        fps[str(d)] = {"resident": res, "host_driven": host,
                       "ratio": res / max(host, 1e-9)}
        rows.append(f"resident_{task}_scan_mesh{d},"
                    f"{1e6/max(res,1e-9):.3f},{res:.0f} {unit}/s")
        rows.append(f"resident_{task}_hostdriven_mesh{d},"
                    f"{1e6/max(host,1e-9):.3f},{host:.0f} {unit}/s")
        rows.append(f"resident_{task}_RATIO_mesh{d},"
                    f"{fps[str(d)]['ratio']:.3f},resident/host-driven FPS")
    summary = {
        "task": task,
        "mesh": mesh,
        "envs_per_shard": envs_per_shard,
        "fps": fps,
        "gate_ratio": fps[str(mesh)]["ratio"],
    }
    return rows, summary


def bench_train_driver(task: str, pipelined: bool, envs_per_shard: int,
                       shards: int, num_steps: int = 16, iters: int = 5,
                       ) -> tuple[float, float]:
    """(steady-state seconds per update, final mean_return) for one
    training driver: the fused-serial ``train_device`` program or the
    pipelined two-program driver, same task/policy/sizes.  The first
    iteration (compile) is excluded from the timing."""
    import jax

    from repro.core.registry import make
    from repro.rl.ppo import PPOConfig, train_device, train_pipelined

    n = envs_per_shard * shards
    pool = make(task, num_envs=n, engine="device-sharded",
                num_shards=shards)
    cfg = PPOConfig(total_steps=n * num_steps * iters, num_steps=num_steps,
                    minibatches=4, epochs=4)
    train = train_pipelined if pipelined else train_device
    _, _, hist = train(pool, cfg, seed=0, hidden=(64, 64))
    if len(hist) < 2:
        raise RuntimeError("need >= 2 iterations to time steady state")
    per_update = (hist[-1]["time_s"] - hist[0]["time_s"]) / (len(hist) - 1)
    return per_update, hist[-1]["mean_return"]


def run_pipelined(mesh: int, task: str = "TokenCopy-v0",
                  envs_per_shard: int = 16, num_steps: int = 16,
                  iters: int = 5) -> tuple[list[str], dict]:
    """Pipelined vs fused-serial training A/B at mesh 1 and D (see
    --pipelined).  At mesh=D the fused program pays the PPO epochs D
    times (replicated across every shard) and serializes them after the
    collect scan; the pipelined driver pays them once on the learner
    device while the env mesh collects the next rollout behind the
    stale params — the gate pins that structural win."""
    rows: list[str] = []
    out: dict[str, dict[str, float]] = {}
    for d in sorted({1, mesh}):
        fused_s, fused_ret = bench_train_driver(
            task, False, envs_per_shard, d, num_steps, iters)
        pipe_s, pipe_ret = bench_train_driver(
            task, True, envs_per_shard, d, num_steps, iters)
        ratio = fused_s / max(pipe_s, 1e-9)
        out[str(d)] = {
            "fused_s_per_update": fused_s,
            "pipelined_s_per_update": pipe_s,
            "speedup": ratio,
            "fused_mean_return": fused_ret,
            "pipelined_mean_return": pipe_ret,
        }
        rows.append(f"pipelined_{task}_fused_mesh{d},"
                    f"{fused_s * 1e3:.1f},ms/update fused-serial")
        rows.append(f"pipelined_{task}_pipelined_mesh{d},"
                    f"{pipe_s * 1e3:.1f},ms/update pipelined+vtrace")
        rows.append(f"pipelined_{task}_SPEEDUP_mesh{d},{ratio:.3f},"
                    f"fused/pipelined wall-clock per update")
    summary = {
        "task": task,
        "mesh": mesh,
        "envs_per_shard": envs_per_shard,
        "num_steps": num_steps,
        "per_mesh": out,
        "gate_ratio": out[str(mesh)]["speedup"],
    }
    return rows, summary


def bench_transform_placement(task: str, num_envs: int, steps: int,
                              iters: int, wrapper: bool) -> float:
    """FPS of one preprocessing placement: ``wrapper=False`` runs the
    task's preset pipeline in-engine (fused into the jitted recv);
    ``wrapper=True`` runs the raw pool and applies the IDENTICAL
    pipeline host-side through the numpy mirror after every step (the
    gym-style wrapper placement the paper argues against)."""
    import jax
    import jax.numpy as jnp

    from repro.core.registry import default_transforms, make
    from repro.core.transforms import TransformPipeline

    if wrapper:
        pool = make(task, num_envs=num_envs, transforms=[])
        pipe = TransformPipeline(default_transforms(task), pool.spec)
        tf_state = pipe.np_init(num_envs)
    else:
        pool = make(task, num_envs=num_envs)
    step = jax.jit(pool.step)
    rng = np.random.default_rng(0)
    act_spec = pool.spec.act_spec

    def run_steps(ps, ts, n_steps):
        frames = 0.0
        tf = tf_state if wrapper else None
        for _ in range(n_steps):
            a = jnp.asarray(act_spec.sample(rng, (num_envs,)))
            ps, ts = step(ps, a, ts.env_id)
            # both placements deliver the transformed batch to the host
            # (the consumer's view); only where the transform runs moves
            out = {
                "obs": np.asarray(ts.obs),
                "reward": np.asarray(ts.reward),
                "done": np.asarray(ts.done),
                "terminated": np.asarray(ts.terminated),
                "env_id": np.asarray(ts.env_id),
            }
            if wrapper:
                tf, out = pipe.np_apply(tf, out)
            frames += float(np.sum(np.asarray(ts.step_cost)))
        return ps, ts, frames

    ps, ts = pool.reset(jax.random.PRNGKey(0))
    ps, ts, _ = run_steps(ps, ts, 2)          # warmup / compile
    t0 = time.time()
    frames = 0.0
    for _ in range(iters):
        ps, ts, f = run_steps(ps, ts, steps)
        frames += f
    return frames / (time.time() - t0)


def run_transforms(task: str = "PongStack-v5", num_envs: int = 32,
                   steps: int = 30, iters: int = 3,
                   prefix: str = "transforms") -> tuple[list[str], dict]:
    """In-engine vs python-wrapper preprocessing A/B (see --transforms
    and --image; the harness is task-generic, only the preset differs)."""
    fps_wrap = bench_transform_placement(task, num_envs, steps, iters,
                                         wrapper=True)
    fps_eng = bench_transform_placement(task, num_envs, steps, iters,
                                        wrapper=False)
    ratio = fps_eng / max(fps_wrap, 1e-9)
    unit = fps_unit(task)
    rows = [
        f"{prefix}_{task}_wrapper_N{num_envs},"
        f"{1e6/max(fps_wrap,1e-9):.3f},{fps_wrap:.0f} {unit}/s",
        f"{prefix}_{task}_inengine_N{num_envs},"
        f"{1e6/max(fps_eng,1e-9):.3f},{fps_eng:.0f} {unit}/s",
        f"{prefix}_{task}_RATIO,{ratio:.3f},in-engine/wrapper FPS",
    ]
    summary = {
        "task": task,
        "num_envs": num_envs,
        "wrapper_fps": fps_wrap,
        "inengine_fps": fps_eng,
        "ratio": ratio,
    }
    return rows, summary


def run_ab(task: str = "Ant-v3", num_envs: int = 64, steps: int = 40,
           iters: int = 3) -> tuple[list[str], dict]:
    """Batched-native vs forced-vmap A/B on the same sync pool — the
    hot-path regression guard for the batched-env rewrite.  On TPU the
    batched side is the compiled Pallas kernel; on CPU it is the fused
    masked-loop path (same jaxpr as vmap by design, so the guard bounds
    engine-level overhead rather than kernel speedup)."""
    fps_vmap = bench_device(task, num_envs, num_envs, "sync",
                            steps=steps, iters=iters, batched=False)
    fps_bat = bench_device(task, num_envs, num_envs, "sync",
                           steps=steps, iters=iters, batched=None)
    ratio = fps_bat / max(fps_vmap, 1e-9)
    unit = fps_unit(task)
    rows = [
        f"ab_{task}_vmap_N{num_envs},{1e6/max(fps_vmap,1e-9):.3f},"
        f"{fps_vmap:.0f} {unit}/s",
        f"ab_{task}_batched_N{num_envs},{1e6/max(fps_bat,1e-9):.3f},"
        f"{fps_bat:.0f} {unit}/s",
        f"ab_{task}_RATIO,{ratio:.3f},batched/vmap FPS",
    ]
    summary = {
        "task": task,
        "num_envs": num_envs,
        "vmap_fps": fps_vmap,
        "batched_fps": fps_bat,
        "ratio": ratio,
    }
    return rows, summary


def bench_lm_collect(task: str, num_envs: int, steps: int, iters: int,
                     cached: bool) -> tuple[float, np.ndarray]:
    """(tokens/s, done stream (steps*iters, N)) for the LM-policy collect
    loop — ``cached=True`` runs the KV-cached one-token-per-recv
    ``decode_step``; ``cached=False`` re-runs the full no-cache forward
    over each lane's history every step (the cache-less baseline).  One
    recv serves one token per lane, so tokens = steps * N."""
    import jax

    from repro.core.registry import make
    from repro.rl.policy_lm import LMPolicy, build_lm_collect_fn

    pool = make(task, num_envs=num_envs)
    policy = LMPolicy(pool.spec)
    params = policy.place_params(policy.init(jax.random.PRNGKey(0)), pool)
    collect = build_lm_collect_fn(pool, policy, steps, cached=cached)
    ps, ts = pool.reset(jax.random.PRNGKey(1))
    lanes = policy.init_lanes(num_envs)
    # two warmups: the first compiles for reset-fresh inputs, the second
    # for the self-feeding steady state the timed loop actually runs
    # (the carry layouts differ, so one call would leave the recompile
    # inside the timing)
    for w in (2, 3):
        ps, lanes, ts, traj, _ = collect(ps, lanes, params, ts,
                                         jax.random.PRNGKey(w))
    jax.block_until_ready(traj.reward)
    dones = []
    t0 = time.time()
    for i in range(iters):
        ps, lanes, ts, traj, _ = collect(ps, lanes, params, ts,
                                         jax.random.PRNGKey(4 + i))
        # sync emission order is priority-based, so serve-slot columns
        # mix lanes across steps — scatter back to lane order by env_id
        d, ids = np.asarray(traj.done), np.asarray(traj.env_id)
        lane_done = np.zeros_like(d)
        np.put_along_axis(lane_done, ids, d, axis=1)
        dones.append(lane_done)
    jax.block_until_ready(traj.reward)
    dt = time.time() - t0
    return steps * num_envs * iters / dt, np.concatenate(dones, axis=0)


def _rtc_useful(done: np.ndarray) -> tuple[int, int]:
    """(useful tokens, lane-slots spent) under run-to-completion static
    batching, replayed from the engine's done stream.  ``done[t, lane]``
    marks the obs at step t as the FIRST of a fresh episode, i.e. the
    lane's request completed at step t.  A round starts with every lane
    fresh; each lane contributes tokens until its first completion, then
    idles until the slowest lane finishes; only completed rounds count."""
    S, M = done.shape
    useful, slots, t0 = 0, 0, 0
    while True:
        finish = []
        for lane in range(M):
            nxt = np.flatnonzero(done[t0 + 1:, lane])
            if nxt.size == 0:
                finish = None
                break
            finish.append(t0 + 1 + int(nxt[0]))
        if finish is None:
            break
        end = max(finish)
        useful += sum(f - t0 for f in finish)
        slots += (end - t0) * M
        t0 = end
    return useful, slots


def run_decode(num_envs: int = 32, steps: int = 48, iters: int = 3,
               cb_steps: int = 64, task_cached: str = "TokenCopy-v0",
               task_cb: str = "TokenRagged-v0") -> tuple[list[str], dict]:
    """LLM-policy decode-path A/B (see --decode): (a) KV-cached
    decode_step vs full-recompute forward, tokens/s at N=num_envs; (b)
    continuous batching vs run-to-completion static batches on the
    ragged-length mix — the identical compiled program replayed under
    the RTC admission discipline via the done stream, so the ratio is
    pure lane utilization."""
    rows: list[str] = []
    fps_cached, _ = bench_lm_collect(task_cached, num_envs, steps, iters,
                                     cached=True)
    fps_full, _ = bench_lm_collect(task_cached, num_envs, steps, iters,
                                   cached=False)
    cached_ratio = fps_cached / max(fps_full, 1e-9)
    rows += [
        f"decode_{task_cached}_cached_N{num_envs},"
        f"{1e6/max(fps_cached,1e-9):.3f},{fps_cached:.0f} tokens/s",
        f"decode_{task_cached}_fullrecompute_N{num_envs},"
        f"{1e6/max(fps_full,1e-9):.3f},{fps_full:.0f} tokens/s",
        f"decode_CACHED_RATIO,{cached_ratio:.3f},"
        f"cached/full-recompute tokens-per-s at N={num_envs}",
    ]
    fps_cont, done = bench_lm_collect(task_cb, num_envs, cb_steps, iters,
                                      cached=True)
    useful, slots = _rtc_useful(done)
    util = useful / slots if slots else 1.0
    fps_rtc = fps_cont * util  # same wall-clock, fewer useful tokens
    cb_ratio = 1.0 / max(util, 1e-9)
    rows += [
        f"decode_{task_cb}_continuous_N{num_envs},"
        f"{1e6/max(fps_cont,1e-9):.3f},{fps_cont:.0f} useful tokens/s",
        f"decode_{task_cb}_runtocompletion_N{num_envs},"
        f"{1e6/max(fps_rtc,1e-9):.3f},{fps_rtc:.0f} useful tokens/s",
        f"decode_CB_RATIO,{cb_ratio:.3f},"
        f"continuous/run-to-completion useful tokens-per-s",
    ]
    summary = {
        "num_envs": num_envs,
        "task_cached": task_cached,
        "cached_tok_s": fps_cached,
        "full_recompute_tok_s": fps_full,
        "cached_over_full": cached_ratio,
        "task_cb": task_cb,
        "continuous_tok_s": fps_cont,
        "rtc_tok_s": fps_rtc,
        "rtc_utilization": util,
        "rtc_useful_tokens": useful,
        "rtc_lane_slots": slots,
        "continuous_over_rtc": cb_ratio,
    }
    return rows, summary


def run_obs(task: str = "TokenCopy-v0", num_envs: int = 64,
            steps: int = 40, iters: int = 3) -> tuple[list[str], dict]:
    """Telemetry-overhead A/B (--obs): the device sync hot loop with
    in-graph counters on vs off.  Same resident collect program both
    sides; ``obs=False`` drops every telemetry leaf, so the off side IS
    the pre-telemetry program.  The two sides' timed iterations are
    INTERLEAVED (on, off, on, off, ...) and each side keeps its best —
    sequential phases would let slow CPU-frequency/load drift on the
    shared CI box bias the ratio by far more than the effect under
    measurement."""
    import jax

    from repro.core.device_pool import DeviceEnvPool
    from repro.core.registry import _jax_env
    from repro.core.xla_loop import build_random_collect_fn
    from repro.obs.metrics import MetricsRegistry, publish_pool_stats
    from repro.obs.telemetry import stats_to_jsonable

    def make_side(obs: bool):
        env = _jax_env(task)
        pool = DeviceEnvPool(env, num_envs, num_envs, mode="sync", obs=obs)
        collect = build_random_collect_fn(pool, num_steps=steps)
        ps, ts = pool.reset(jax.random.PRNGKey(0))
        ps, ts, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(1))
        jax.block_until_ready(traj.reward)
        return {"pool": pool, "collect": collect, "ps": ps, "ts": ts,
                "best": 0.0}

    sides = {True: make_side(True), False: make_side(False)}
    for i in range(iters):
        for obs in (True, False):
            s = sides[obs]
            t0 = time.time()
            s["ps"], s["ts"], traj, _ = s["collect"](
                s["ps"], None, s["ts"], jax.random.PRNGKey(2 + i))
            jax.block_until_ready(traj.reward)
            s["best"] = max(s["best"], float(traj.step_cost.sum())
                            / (time.time() - t0))
    fps_obs, fps_off = sides[True]["best"], sides[False]["best"]
    pool, ps = sides[True]["pool"], sides[True]["ps"]
    ratio = fps_obs / max(fps_off, 1e-9)
    # the instrumented side's own counters prove the telemetry ran and
    # land in the artifact through the unified registry
    stats = pool.stats(ps)
    registry = MetricsRegistry()
    publish_pool_stats(registry, stats, engine="device", task=task)
    rows = [
        f"obs_{task}_on_N{num_envs},{1e6/max(fps_obs,1e-9):.3f},"
        f"{fps_obs:.0f} {fps_unit(task)}/s",
        f"obs_{task}_off_N{num_envs},{1e6/max(fps_off,1e-9):.3f},"
        f"{fps_off:.0f} {fps_unit(task)}/s",
        f"obs_RATIO,{ratio:.3f},obs-on/obs-off FPS (best of {iters})",
    ]
    summary = {
        "task": task,
        "num_envs": num_envs,
        "steps": steps,
        "iters": iters,
        "fps_obs_on": fps_obs,
        "fps_obs_off": fps_off,
        "ratio": ratio,
        "stats": stats_to_jsonable(stats),
        "metrics": registry.snapshot(),
    }
    return rows, summary


# --------------------------------------------------------------------- #
# multi-host loopback bench (--multihost): weak scaling + disaggregation
# --------------------------------------------------------------------- #
def _mh_worker(cfg: dict) -> int:
    """Worker entry (--mh-worker): one process of a multihost bench run.

    Joins the loopback ``jax.distributed`` job (or simulates devices
    solo), runs the requested measurement, prints one JSON line.  Fresh
    interpreter per worker — the parent never imports jax before
    spawning these.
    """
    from repro.launch import mesh as launch_mesh

    if cfg["procs"] > 1:
        launch_mesh.initialize_multihost(
            f"127.0.0.1:{cfg['port']}", num_processes=cfg["procs"],
            process_id=cfg["pid"], local_device_count=cfg["local_devices"])
    else:
        launch_mesh.force_host_device_count(cfg["local_devices"])
    import jax

    from repro.core.registry import make

    if cfg["kind"] == "collect":
        from repro.core.xla_loop import build_random_collect_fn

        shards = cfg["procs"] * cfg["local_devices"]
        n = shards * cfg["envs_per_shard"]
        pool = make(cfg["task"], num_envs=n, engine="device-sharded",
                    num_shards=shards, seed=0)
        collect = build_random_collect_fn(pool, num_steps=cfg["steps"])
        key = lambda s: pool.put_replicated(  # noqa: E731
            np.asarray(jax.random.PRNGKey(s)))
        ps, ts = pool.reset(key(0))
        ps = pool.device_put(ps)
        ps, ts, traj, _ = collect(ps, None, ts, key(1))
        jax.block_until_ready(traj.reward)
        frames = 0.0
        t0 = time.time()
        for i in range(cfg["iters"]):
            ps, ts, traj, _ = collect(ps, None, ts, key(2 + i))
            frames += float(traj.step_cost.sum())
        jax.block_until_ready(traj.reward)
        out = {"fps": frames / (time.time() - t0), "frames": frames,
               "shards": shards, "num_envs": n}
    else:  # train: colocated pipelined vs disaggregated
        from repro.rl.ppo import (
            PPOConfig, train_disaggregated, train_pipelined,
        )

        n = cfg["envs_per_shard"]
        pool = make(cfg["task"], num_envs=n, engine="device-sharded",
                    num_shards=1, seed=0)
        pcfg = PPOConfig(
            total_steps=n * cfg["num_steps"] * cfg["iters"],
            num_steps=cfg["num_steps"], minibatches=4, epochs=4)
        train = train_disaggregated if cfg["procs"] > 1 else train_pipelined
        _, _, hist = train(pool, pcfg, seed=0, hidden=(64, 64))
        if len(hist) < 4:
            raise RuntimeError("need >= 4 iterations to time steady state")
        # median interval: the jit compiles land in one early interval
        # (collect in the prologue, update in hist[0]->hist[1]) and would
        # otherwise dominate a mean at smoke sizes
        t = [h["time_s"] for h in hist]
        diffs = sorted(b - a for a, b in zip(t, t[1:]))
        out = {
            "s_per_update": diffs[len(diffs) // 2],
            "mean_return": hist[-1]["mean_return"],
            "iters": len(hist),
        }
    print(json.dumps(dict(out, pid=cfg.get("pid", 0))), flush=True)
    return 0


def _mh_spawn(configs: list[dict], timeout: float = 600.0) -> list[dict]:
    """Run one worker subprocess per config (concurrently — they are the
    ranks of one loopback job) and return their JSON results.

    The ranks pin themselves to simulated CPU devices, so their numbers
    are CPU numbers: on an accelerator host this refuses rather than
    report them."""
    import socket
    import subprocess

    import jax

    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"--multihost runs its ranks on simulated CPU devices; this "
            f"host's default backend is {jax.default_backend()!r}, whose "
            f"chip one process holds at a time. Run it under "
            f"JAX_PLATFORMS=cpu.")

    if len(configs) > 1:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        for i, c in enumerate(configs):
            c.update(port=port, pid=i, procs=len(configs))
    else:
        configs[0].update(pid=0, procs=1)
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--mh-worker", json.dumps(c)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for c in configs
    ]
    results = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            if p.returncode != 0:
                raise RuntimeError(f"multihost worker failed:\n{err[-2000:]}")
            lines = [ln for ln in out.splitlines() if ln.startswith("{")]
            results.append(json.loads(lines[-1]))
    finally:
        for p in procs:
            p.kill()
    return results


def run_multihost(task: str, envs_per_shard: int, local_devices: int,
                  steps: int, iters: int, num_steps: int, train_iters: int,
                  ) -> tuple[list[str], dict]:
    """The --multihost A/B pair (ROADMAP #1 acceptance):

      * WEAK SCALING — aggregate random-collect FPS of 2 loopback
        processes (mesh = 2 x local_devices, gloo collectives) vs ONE
        process at the same per-process shard count.  With >= 2 real
        cores the fifo hot path has no cross-process rendezvous, so
        aggregate FPS should approach 2x (the >= 1.5x acceptance
        floor); on a 1-core container both topologies time-share one
        core and the honest expectation is parity.
      * DISAGGREGATION — per-update wall-clock of
        ``train_disaggregated`` (env process + learner process) vs the
        colocated single-process ``train_pipelined`` at the same sizes.
        With >= 2 cores the learner's PPO epochs overlap env stepping
        across processes (the >= 1.0x acceptance floor); on 1 core the
        two broadcasts per iteration are pure overhead.
    """
    base = {"task": task, "envs_per_shard": envs_per_shard,
            "local_devices": local_devices, "steps": steps, "iters": iters}
    solo = _mh_spawn([dict(base, kind="collect")])[0]
    pair = _mh_spawn([dict(base, kind="collect") for _ in range(2)])
    scaling = pair[0]["fps"] / max(solo["fps"], 1e-9)

    tbase = {"task": task, "envs_per_shard": envs_per_shard,
             "local_devices": 1, "num_steps": num_steps,
             "iters": train_iters}
    colo = _mh_spawn([dict(tbase, kind="train")])[0]
    disagg = _mh_spawn([dict(tbase, kind="train") for _ in range(2)])
    dratio = colo["s_per_update"] / max(disagg[0]["s_per_update"], 1e-9)

    rows = [
        f"multihost_collect_1proc,{solo['fps']:.0f},"
        f"aggregate FPS 1 proc x {solo['shards']} shards",
        f"multihost_collect_2proc,{pair[0]['fps']:.0f},"
        f"aggregate FPS 2 procs x {local_devices} shards (gloo loopback)",
        f"multihost_WEAK_SCALING,{scaling:.3f},"
        "2proc/1proc aggregate FPS at equal per-process shards",
        f"multihost_train_colocated,{colo['s_per_update'] * 1e3:.1f},"
        "ms/update train_pipelined (1 proc)",
        f"multihost_train_disagg,{disagg[0]['s_per_update'] * 1e3:.1f},"
        "ms/update train_disaggregated (env proc + learner proc)",
        f"multihost_DISAGG_RATIO,{dratio:.3f},"
        "colocated/disaggregated wall-clock per update",
    ]
    summary = {
        "task": task,
        "local_devices_per_process": local_devices,
        "envs_per_shard": envs_per_shard,
        "collect": {"solo": solo, "two_process": pair},
        "weak_scaling": scaling,
        "train": {"colocated": colo, "disaggregated": disagg},
        "disagg_ratio": dratio,
        "host_cpu_count": os.cpu_count(),
    }
    return rows, summary


def write_json(rows: list[str], extra: dict | None = None,
               path: str | None = None) -> str:
    """Persist the bench rows (and any mode-specific summary) as the
    BENCH_throughput.json artifact."""
    path = path or os.path.join(ROOT, "BENCH_throughput.json")
    payload = {
        "benchmark": "throughput",
        "meta": bench_meta(),
        "rows": [
            dict(zip(("name", "us_per_unit", "note"), r.split(",", 2)))
            for r in rows
        ],
    }
    if extra:
        payload.update(extra)
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return path


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mesh", type=int, default=0,
                    help="benchmark ShardedDeviceEnvPool at this mesh size "
                         "(0 = run the full engine table instead)")
    ap.add_argument("--ab", action="store_true",
                    help="batched-native vs vmap-lifted A/B on MujocoLike")
    ap.add_argument("--schedule", action="store_true",
                    help="scheduling-policy A/B (fifo/sjf/hierarchical) on "
                         "the long-tail-skew workload; uses --mesh shards "
                         "(default 4); writes BENCH_schedule.json")
    ap.add_argument("--min-schedule-ratio", type=float, default=0.0,
                    help="fail (exit 1) if best(sjf,hierarchical)/fifo FPS "
                         "drops below this (CI gate)")
    ap.add_argument("--resident", action="store_true",
                    help="device-resident scan vs per-step host-driven "
                         "collect A/B at mesh 1 and --mesh (default 4); "
                         "writes BENCH_resident.json")
    ap.add_argument("--min-resident-ratio", type=float, default=0.0,
                    help="fail (exit 1) if resident/host-driven FPS at "
                         "mesh=D drops below this (CI gate)")
    ap.add_argument("--pipelined", action="store_true",
                    help="pipelined vs fused-serial collect/train A/B "
                         "(rl/ppo.py: train_pipelined vs train_device) at "
                         "mesh 1 and --mesh (default 4); writes "
                         "BENCH_pipelined.json")
    ap.add_argument("--min-pipelined-ratio", type=float, default=0.0,
                    help="fail (exit 1) if fused/pipelined wall-clock per "
                         "update at mesh=D drops below this (CI gate)")
    ap.add_argument("--transforms", action="store_true",
                    help="in-engine transform pipeline vs python-wrapper "
                         "A/B on PongStack-v5; writes BENCH_transforms.json")
    ap.add_argument("--image", action="store_true",
                    help="in-engine vs python-wrapper IMAGE-pipeline "
                         "A/B on PongClassic-v5 (RGB render + Pallas "
                         "grayscale/resize family); writes "
                         "BENCH_image.json")
    ap.add_argument("--min-image-ratio", type=float, default=0.0,
                    help="fail (exit 1) if in-engine/wrapper FPS on the "
                         "image pipeline is below this")
    ap.add_argument("--min-transform-ratio", type=float, default=0.0,
                    help="fail (exit 1) if in-engine/wrapper FPS drops "
                         "below this (CI gate)")
    ap.add_argument("--decode", action="store_true",
                    help="LLM-policy decode-path A/B (rl/policy_lm.py): "
                         "KV-cached decode_step vs full-recompute forward "
                         "at N=32, and continuous batching vs "
                         "run-to-completion static batches on "
                         "TokenRagged-v0; writes BENCH_decode.json")
    ap.add_argument("--min-decode-cached-ratio", type=float, default=0.0,
                    help="fail (exit 1) if cached/full-recompute "
                         "tokens-per-s drops below this (CI gate)")
    ap.add_argument("--min-decode-cb-ratio", type=float, default=0.0,
                    help="fail (exit 1) if continuous/run-to-completion "
                         "useful-tokens-per-s drops below this (CI gate)")
    ap.add_argument("--obs", action="store_true",
                    help="in-graph telemetry overhead A/B "
                         "(obs/telemetry.py): device sync hot loop with "
                         "PoolState counters on vs off; writes "
                         "BENCH_obs.json")
    ap.add_argument("--min-obs-ratio", type=float, default=0.0,
                    help="fail (exit 1) if obs-on/obs-off FPS drops "
                         "below this (CI gate; acceptance bound 0.97)")
    ap.add_argument("--multihost", action="store_true",
                    help="multi-process loopback A/B (launch/mesh.py + "
                         "rl/ppo.py::train_disaggregated): 2-process "
                         "weak-scaling collect FPS vs 1 process, and "
                         "disaggregated env/learner per-update wall vs "
                         "colocated train_pipelined; writes "
                         "BENCH_multihost.json")
    ap.add_argument("--min-multihost-ratio", type=float, default=0.0,
                    help="fail (exit 1) if 2proc/1proc aggregate FPS "
                         "drops below this (CI gate; acceptance bound "
                         "1.5 on >= 2 cores)")
    ap.add_argument("--min-disagg-ratio", type=float, default=0.0,
                    help="fail (exit 1) if colocated/disaggregated "
                         "per-update wall ratio drops below this (CI "
                         "gate; acceptance bound 1.0 on >= 2 cores)")
    ap.add_argument("--mh-worker", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--task", default="TokenCopy-v0")
    ap.add_argument("--envs-per-shard", type=int, default=16)
    ap.add_argument("--num-envs", type=int, default=64)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--min-ab-ratio", type=float, default=0.0,
                    help="fail (exit 1) if batched/vmap FPS ratio drops "
                         "below this (CI regression gate)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for the CI smoke (~2s)")
    ap.add_argument("--json", default=None,
                    help="output path (default: <repo>/BENCH_throughput.json)")
    args = ap.parse_args(argv)

    if args.mh_worker:  # one rank of a --multihost run (fresh process)
        return _mh_worker(json.loads(args.mh_worker))

    rows: list[str] = []
    extra: dict = {}
    if args.mesh or args.schedule or args.resident or args.pipelined:
        mesh = args.mesh or 4
        # must precede ANY jax import in this process
        if "jax" in sys.modules:
            raise RuntimeError(
                "--mesh/--schedule/--resident/--pipelined require jax to "
                "not be imported yet"
            )
        # shared set-before-import helper (launch/mesh.py); an inherited
        # count flag (e.g. from a driving harness) wins
        if "host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                              ""):
            from repro.launch.mesh import force_host_device_count

            force_host_device_count(mesh, platform=None)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.pipelined:
        if args.smoke:
            args.envs_per_shard, args.steps, args.iters = 16, 16, 4
        rows, summary = run_pipelined(mesh, args.task, args.envs_per_shard,
                                      args.steps, args.iters)
        extra = {"mode": "pipelined", "pipelined": summary}
        if args.json is None:
            args.json = os.path.join(ROOT, "BENCH_pipelined.json")
    elif args.resident:
        if args.smoke:
            args.envs_per_shard, args.steps, args.iters = 16, 16, 1
        rows, summary = run_resident(mesh, args.task, args.envs_per_shard,
                                     args.steps, args.iters)
        extra = {"mode": "resident", "resident": summary}
        if args.json is None:
            args.json = os.path.join(ROOT, "BENCH_resident.json")
    elif args.schedule:
        task = args.task if args.task != "TokenCopy-v0" else "TokenSkew-v0"
        if args.smoke:
            args.envs_per_shard, args.steps, args.iters = 16, 24, 1
        rows, summary = run_schedule(mesh, task, args.envs_per_shard,
                                     args.steps, args.iters)
        extra = {"mode": "schedule", "schedule": summary}
        if args.json is None:
            args.json = os.path.join(ROOT, "BENCH_schedule.json")
    elif args.mesh:
        if args.smoke:
            args.envs_per_shard, args.steps, args.iters = 16, 10, 1
        rows = run_mesh(args.mesh, args.task, args.envs_per_shard,
                        args.steps, args.iters)
        extra = {"mode": "mesh", "mesh": args.mesh}
    elif args.multihost:
        if args.smoke:
            mh = dict(envs_per_shard=16, local_devices=2, steps=16,
                      iters=2, num_steps=16, train_iters=4)
        else:
            mh = dict(envs_per_shard=args.envs_per_shard, local_devices=2,
                      steps=args.steps, iters=max(args.iters, 2),
                      num_steps=16, train_iters=6)
        rows, summary = run_multihost(args.task, **mh)
        extra = {"mode": "multihost", "multihost": summary}
        if args.json is None:
            args.json = os.path.join(ROOT, "BENCH_multihost.json")
    elif args.obs:
        if args.smoke:
            # more, shorter iters: best-of keeps the ratio honest on
            # noisy 2-core CI without stretching the smoke budget
            args.steps, args.iters = 24, 4
        rows, summary = run_obs(args.task, args.num_envs, args.steps,
                                args.iters)
        extra = {"mode": "obs", "obs": summary}
        if args.json is None:
            args.json = os.path.join(ROOT, "BENCH_obs.json")
    elif args.decode:
        # the gate is pinned at N=32 (the acceptance sizes), so --smoke
        # only trims steps/iters; the cb stream still needs to span a
        # few run-to-completion rounds (episode lengths 8/32)
        steps, iters, cb_steps = (24, 1, 72) if args.smoke else (48, 3, 64)
        rows, summary = run_decode(num_envs=32, steps=steps, iters=iters,
                                   cb_steps=cb_steps)
        extra = {"mode": "decode", "decode": summary}
        if args.json is None:
            args.json = os.path.join(ROOT, "BENCH_decode.json")
    elif args.image:
        if args.smoke:
            # N=64 for the same reason as --transforms; fewer steps —
            # every wrapper step ships N full 210x160x3 screens to the
            # host, so the gap shows up fast
            args.num_envs, args.steps, args.iters = 64, 10, 2
        task = args.task if args.task != "TokenCopy-v0" else "PongClassic-v5"
        rows, summary = run_transforms(task, args.num_envs, args.steps,
                                       args.iters, prefix="image")
        extra = {"mode": "image", "image": summary}
        if args.json is None:
            args.json = os.path.join(ROOT, "BENCH_image.json")
    elif args.transforms:
        if args.smoke:
            # N=64 so the placement gap (numpy wrapper copies scale
            # with N, the fused XLA path amortizes) dominates 2-core
            # timer noise; at N=16 the ratio flirts with the 1.0 gate
            args.num_envs, args.steps, args.iters = 64, 20, 2
        task = args.task if args.task != "TokenCopy-v0" else "PongStack-v5"
        rows, summary = run_transforms(task, args.num_envs, args.steps,
                                       args.iters)
        extra = {"mode": "transforms", "transforms": summary}
        if args.json is None:
            args.json = os.path.join(ROOT, "BENCH_transforms.json")
    elif args.ab:
        if args.smoke:
            args.num_envs, args.steps, args.iters = 32, 10, 1
        task = args.task if args.task != "TokenCopy-v0" else "Ant-v3"
        rows, summary = run_ab(task, args.num_envs, args.steps, args.iters)
        extra = {"mode": "ab", "ab": summary}
    else:
        run(rows)
        extra = {"mode": "table"}
    print("\n".join(rows))
    path = write_json(rows, extra, args.json)
    print(f"[bench] wrote {path}")
    # gate only when the A/B branch actually ran (--mesh wins over --ab)
    if extra.get("mode") == "ab" and args.min_ab_ratio > 0:
        ratio = extra["ab"]["ratio"]
        if ratio < args.min_ab_ratio:
            print(f"[bench] FAIL: batched/vmap ratio {ratio:.3f} < "
                  f"{args.min_ab_ratio}")
            return 1
        print(f"[bench] ratio {ratio:.3f} >= {args.min_ab_ratio} OK")
    if extra.get("mode") == "pipelined" and args.min_pipelined_ratio > 0:
        ratio = extra["pipelined"]["gate_ratio"]
        d = extra["pipelined"]["mesh"]
        if ratio < args.min_pipelined_ratio:
            print(f"[bench] FAIL: fused/pipelined per-update ratio "
                  f"{ratio:.3f} < {args.min_pipelined_ratio} at mesh={d}")
            return 1
        print(f"[bench] fused/pipelined per-update ratio {ratio:.3f} >= "
              f"{args.min_pipelined_ratio} at mesh={d} OK")
    if extra.get("mode") == "resident" and args.min_resident_ratio > 0:
        ratio = extra["resident"]["gate_ratio"]
        d = extra["resident"]["mesh"]
        if ratio < args.min_resident_ratio:
            print(f"[bench] FAIL: resident/host-driven ratio {ratio:.3f} "
                  f"< {args.min_resident_ratio} at mesh={d}")
            return 1
        print(f"[bench] resident/host-driven ratio {ratio:.3f} >= "
              f"{args.min_resident_ratio} at mesh={d} OK")
    if extra.get("mode") == "schedule" and args.min_schedule_ratio > 0:
        ratio = extra["schedule"]["best_over_fifo"]
        best = extra["schedule"]["best"]
        if ratio < args.min_schedule_ratio:
            print(f"[bench] FAIL: {best}/fifo ratio {ratio:.3f} < "
                  f"{args.min_schedule_ratio}")
            return 1
        print(f"[bench] {best}/fifo ratio {ratio:.3f} >= "
              f"{args.min_schedule_ratio} OK")
    if extra.get("mode") == "image" and args.min_image_ratio > 0:
        ratio = extra["image"]["ratio"]
        if ratio < args.min_image_ratio:
            print(f"[bench] FAIL: image in-engine/wrapper ratio "
                  f"{ratio:.3f} < {args.min_image_ratio}")
            return 1
        print(f"[bench] image in-engine/wrapper ratio {ratio:.3f} >= "
              f"{args.min_image_ratio} OK")
    if extra.get("mode") == "decode":
        if args.min_decode_cached_ratio > 0:
            ratio = extra["decode"]["cached_over_full"]
            if ratio < args.min_decode_cached_ratio:
                print(f"[bench] FAIL: cached/full-recompute ratio "
                      f"{ratio:.3f} < {args.min_decode_cached_ratio}")
                return 1
            print(f"[bench] cached/full-recompute ratio {ratio:.3f} >= "
                  f"{args.min_decode_cached_ratio} OK")
        if args.min_decode_cb_ratio > 0:
            ratio = extra["decode"]["continuous_over_rtc"]
            if ratio < args.min_decode_cb_ratio:
                print(f"[bench] FAIL: continuous/run-to-completion ratio "
                      f"{ratio:.3f} < {args.min_decode_cb_ratio}")
                return 1
            print(f"[bench] continuous/run-to-completion ratio "
                  f"{ratio:.3f} >= {args.min_decode_cb_ratio} OK")
    if extra.get("mode") == "obs" and args.min_obs_ratio > 0:
        ratio = extra["obs"]["ratio"]
        if ratio < args.min_obs_ratio:
            print(f"[bench] FAIL: obs-on/obs-off ratio {ratio:.3f} < "
                  f"{args.min_obs_ratio}")
            return 1
        print(f"[bench] obs-on/obs-off ratio {ratio:.3f} >= "
              f"{args.min_obs_ratio} OK")
    if extra.get("mode") == "multihost":
        if args.min_multihost_ratio > 0:
            ratio = extra["multihost"]["weak_scaling"]
            if ratio < args.min_multihost_ratio:
                print(f"[bench] FAIL: 2proc/1proc weak-scaling FPS ratio "
                      f"{ratio:.3f} < {args.min_multihost_ratio}")
                return 1
            print(f"[bench] 2proc/1proc weak-scaling FPS ratio "
                  f"{ratio:.3f} >= {args.min_multihost_ratio} OK")
        if args.min_disagg_ratio > 0:
            ratio = extra["multihost"]["disagg_ratio"]
            if ratio < args.min_disagg_ratio:
                print(f"[bench] FAIL: colocated/disaggregated per-update "
                      f"ratio {ratio:.3f} < {args.min_disagg_ratio}")
                return 1
            print(f"[bench] colocated/disaggregated per-update ratio "
                  f"{ratio:.3f} >= {args.min_disagg_ratio} OK")
    if extra.get("mode") == "transforms" and args.min_transform_ratio > 0:
        ratio = extra["transforms"]["ratio"]
        if ratio < args.min_transform_ratio:
            print(f"[bench] FAIL: in-engine/wrapper ratio {ratio:.3f} < "
                  f"{args.min_transform_ratio}")
            return 1
        print(f"[bench] in-engine/wrapper ratio {ratio:.3f} >= "
              f"{args.min_transform_ratio} OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
