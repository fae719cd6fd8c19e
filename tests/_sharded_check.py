"""Subprocess helper for tests/test_sharded_pool.py.

The tier-1 suite runs on ONE device (conftest harness contract), so the
multi-device assertions run here, in a fresh interpreter that forces D
simulated host devices before jax locks the platform.  Prints one JSON
object; the parent test asserts on it.

Usage: python tests/_sharded_check.py [D]
"""

import json
import sys

from repro.launch.mesh import force_host_device_count

D = int(sys.argv[1]) if len(sys.argv) > 1 else 4
force_host_device_count(D)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.registry import make  # noqa: E402
from repro.core.engine import MeshEnvPool  # noqa: E402
from repro.core.xla_loop import build_random_collect_fn  # noqa: E402

STEPS = 8
N_PER_SHARD = 4


def sync_rollout(task: str, shards: int):
    """Deterministic scripted rollout; returns stacked per-step arrays."""
    pool = make(task, num_envs=N_PER_SHARD * D, engine="device-sharded",
                num_shards=shards)
    env = pool.env
    hi = int(env.spec.act_spec.maximum) if env.spec.act_spec.maximum else 1
    ps, ts = pool.reset(jax.random.PRNGKey(0))
    step = jax.jit(pool.step)
    obs, rew, done, ids = [], [], [], []
    for t in range(STEPS):
        a = ((ts.env_id * 7 + t) % (hi + 1)).astype(env.spec.act_spec.dtype)
        ps, ts = step(ps, a, ts.env_id)
        obs.append(np.asarray(ts.obs))
        rew.append(np.asarray(ts.reward))
        done.append(np.asarray(ts.done))
        ids.append(np.asarray(ts.env_id))
    return map(np.stack, (obs, rew, done, ids))


def main() -> dict:
    res: dict = {"devices": len(jax.devices()), "mesh": D}

    # 1) shard-count invariance: sync rollouts bitwise-equal at mesh 1 vs D
    for task in ("TokenCopy-v0", "CartPole-v1"):
        o1, r1, d1, i1 = sync_rollout(task, 1)
        oD, rD, dD, iD = sync_rollout(task, D)
        res[f"equal_{task}"] = bool(
            np.array_equal(o1, oD) and np.array_equal(r1, rD)
            and np.array_equal(d1, dD) and np.array_equal(i1, iD)
        )

    # 2) jitted lax.scan rollout across the mesh
    pool = make("TokenCopy-v0", num_envs=4 * D, engine="device-sharded",
                num_shards=D)
    collect = build_random_collect_fn(pool, num_steps=6)
    ps, ts = pool.reset(jax.random.PRNGKey(1))
    ps, ts, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(2))
    res["scan_shape_ok"] = bool(traj.reward.shape == (6, 4 * D))
    res["scan_finite"] = bool(np.isfinite(np.asarray(traj.reward)).all())

    # 3) async mode across shards: every batch has M unique global ids
    pool = make("TokenCopy-v0", num_envs=4 * D, batch_size=2 * D,
                engine="device-sharded", num_shards=D)
    ps, ts = pool.reset(jax.random.PRNGKey(3))
    uniq = True
    for t in range(6):
        ids = np.asarray(ts.env_id)
        uniq &= len(set(ids.tolist())) == 2 * D
        a = ((ts.env_id + t) % 256).astype(jnp.int32)
        ps, ts = pool.step(ps, a, ts.env_id)
    res["async_unique_ids"] = bool(uniq)

    # 4) divisibility validation needs a real multi-device mesh
    try:
        env = pool.env
        MeshEnvPool(env, num_envs=D + 1, mesh=D)
        res["divisibility_raises"] = False
    except ValueError:
        res["divisibility_raises"] = True

    # 5) hierarchical schedule on the skew workload: deterministic at
    #    every mesh size in {1, 2, D}, batches stay M unique global ids,
    #    and no lane starves once the initial READY drain is consumed
    #    (the overdue band's guarantee — pure sjf would fail this)
    def hier_rollout(shards: int, steps: int = 24):
        pool = make("TokenSkew-v0", num_envs=16, batch_size=8,
                    engine="device-sharded", num_shards=shards,
                    schedule="hierarchical")
        ps, ts = pool.reset(jax.random.PRNGKey(5))
        step = jax.jit(pool.step)
        ids_all, rews = [], []
        served_late: set[int] = set()
        uniq = True
        for t in range(steps):
            ids = np.asarray(ts.env_id)
            uniq &= len(set(ids.tolist())) == 8
            if t >= 2:  # past the init drain: scheduling, not reset, serves
                served_late.update(ids.tolist())
            a = ((ts.env_id * 7 + t) % 256).astype(jnp.int32)
            ps, ts = step(ps, a, ts.env_id)
            ids_all.append(ids)
            rews.append(np.asarray(ts.reward))
        return np.stack(ids_all), np.stack(rews), uniq, served_late

    det = uniq_ok = no_starve = True
    for d in sorted({1, 2, D}):
        i1, r1, u1, s1 = hier_rollout(d)
        i2, r2, u2, s2 = hier_rollout(d)
        det &= np.array_equal(i1, i2) and np.array_equal(r1, r2)
        uniq_ok &= u1 and u2
        no_starve &= s1 == set(range(16))
    res["hier_deterministic"] = bool(det)
    res["hier_unique_ids"] = bool(uniq_ok)
    res["hier_no_starvation"] = bool(no_starve)

    # 6) mesh-elastic transform-state restore: NormalizeObs moments
    #    checkpointed at mesh 1 restore onto the mesh-D pool (global
    #    entries re-broadcast to D identical shard copies, per-lane
    #    rows passed through) and vice versa
    import tempfile

    from repro.checkpoint import CheckpointStore

    def norm_pool(shards):
        pool = make("AntNorm-v3", num_envs=8, engine="device-sharded",
                    num_shards=shards)
        ps, ts = pool.reset(jax.random.PRNGKey(7))
        step = jax.jit(pool.step)
        for t in range(2):
            i = np.asarray(ts.env_id)
            a = jnp.asarray(np.sin(i[:, None] * 0.7 + t + np.arange(8)),
                            jnp.float32)
            ps, ts = step(ps, a, ts.env_id)
        return pool, ps

    store = CheckpointStore(tempfile.mkdtemp())
    ok = True
    for d_src, d_dst in ((1, D), (D, 1)):
        src_pool, src_ps = norm_pool(d_src)
        src_pool.save_transform_state(store, d_src, src_ps)
        dst_pool, dst_ps = norm_pool(d_dst)
        dst_ps = dst_pool.restore_transform_state(store, d_src, dst_ps)
        src_c = jax.tree.map(np.asarray, src_pool._tf_canonical(src_ps.tf_state))
        dst_m = jax.tree.map(np.asarray, dst_ps.tf_state[0])
        for k in ("count", "mean", "m2"):
            ok &= dst_m[k].shape[0] == d_dst
            for s in range(d_dst):          # every shard copy == source
                ok &= bool(np.array_equal(src_c[0][k], dst_m[k][s]))
    res["tf_restore_elastic"] = bool(ok)
    return res


if __name__ == "__main__":
    print(json.dumps(main()))
