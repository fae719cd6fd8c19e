"""Regenerate tests/golden_fifo_streams.npz — the pre-refactor reference
streams that ``schedule="fifo"`` must reproduce bitwise.

Captured ONCE from the engines as they stood before the scheduler
subsystem extraction (PR 3); rerunning this script after behavioral
changes would just bless the new behavior, so only regenerate it when
the conformance contract itself is deliberately being moved.

Usage: PYTHONPATH=src python tests/_golden_gen.py
"""

import os
import sys

import numpy as np

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.registry import make  # noqa: E402

SEED = 0
STEPS = 12
VOCAB = 256
TASK = "TokenCopy-v0"


def policy(ids: np.ndarray, t: int) -> np.ndarray:
    return ((ids.astype(np.int64) * 7 + t) % VOCAB).astype(np.int32)


def device_stream(engine: str, n: int, m: int | None, **kw):
    pool = make(TASK, num_envs=n, batch_size=m, engine=engine, seed=SEED, **kw)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    ids, rew, done, obs = [], [], [], []
    for t in range(STEPS):
        i = np.asarray(ts.env_id)
        ps, ts = step(ps, jnp.asarray(policy(i, t)), ts.env_id)
        ids.append(np.asarray(ts.env_id))
        rew.append(np.asarray(ts.reward))
        done.append(np.asarray(ts.done))
        obs.append(np.asarray(ts.obs))
    return map(np.stack, (ids, rew, done, obs))


def thread_stream(n: int):
    """Thread engine with M == N; each batch sorted by env_id (block
    composition order is timing-dependent, per-env streams are not)."""
    pool = make(TASK, num_envs=n, engine="thread", seed=SEED, num_threads=2)
    try:
        pool.async_reset()
        out = pool.recv()
        ids, rew, done = [], [], []
        for t in range(STEPS):
            i = np.asarray(out["env_id"])
            out = pool.step(policy(i, t), i)
            o = np.argsort(np.asarray(out["env_id"]))
            ids.append(np.asarray(out["env_id"])[o])
            rew.append(np.asarray(out["reward"])[o])
            done.append(np.asarray(out["done"])[o])
        return map(np.stack, (ids, rew, done))
    finally:
        pool.close()


def atari_stream(steps: int = 32, n: int = 4):
    """Golden streams for tests/golden_atari_stream.npz.

    ``ids/rew/done/cost`` were captured from the PRE-transform-pipeline
    ``AtariLike`` (intra-step frame buffer, stacked obs in the env) and
    pin that the raw-frame refactor left dynamics/rng bitwise-unchanged;
    ``obs_stack`` pins the default in-engine ``FrameStack(4)`` pipeline
    output as of the transform-subsystem PR.  Regenerating this file
    just blesses new behavior — don't, unless the contract moves.
    """
    pool = make("Pong-v5", num_envs=n, seed=SEED)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    ids, rew, done, cost, obs = [], [], [], [], []
    for t in range(steps):
        i = np.asarray(ts.env_id)
        a = jnp.asarray(((i * 3 + t) % 6).astype(np.int32))
        ps, ts = step(ps, a, ts.env_id)
        ids.append(np.asarray(ts.env_id))
        rew.append(np.asarray(ts.reward))
        done.append(np.asarray(ts.done))
        cost.append(np.asarray(ts.step_cost))
        obs.append(np.asarray(ts.obs))
    return map(np.stack, (ids, rew, done, cost, obs))


def main() -> None:
    data = {}
    for tag, engine, n, m, kw in [
        ("device_sync", "device", 8, None, {}),
        ("device_async", "device", 8, 4, {}),
        ("sharded_async", "device-sharded", 8, 4, {"num_shards": 1}),
    ]:
        i, r, d, o = device_stream(engine, n, m, **kw)
        data[f"{tag}_ids"], data[f"{tag}_rew"] = i, r
        data[f"{tag}_done"], data[f"{tag}_obs"] = d, o
    i, r, d = thread_stream(8)
    data["thread_ids"], data["thread_rew"], data["thread_done"] = i, r, d

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "golden_fifo_streams.npz")
    np.savez_compressed(out, **data)
    print(f"wrote {out}: " + ", ".join(sorted(data)))

    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "golden_atari_stream.npz")
    if os.path.exists(out) and "--force-atari" not in sys.argv:
        # ids/rew/done/cost were captured from the PRE-transform-pipeline
        # engine — rewriting them from current code would re-bless
        # whatever the current dynamics produce and void the pin
        print(f"kept {out} (pre-refactor capture; --force-atari overwrites)")
        return
    i, r, d, c, o = atari_stream()
    atari = {"ids": i, "rew": r, "done": d, "cost": c, "obs_stack": o}
    np.savez_compressed(out, **atari)
    print(f"wrote {out}: " + ", ".join(sorted(atari)))


if __name__ == "__main__":
    main()
