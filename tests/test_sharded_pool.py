"""The ``device-sharded`` engine: single-shard equivalence in-process, multi-shard
invariance via a subprocess with simulated host devices (the tier-1
process itself must see ONE device — conftest harness contract)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import MeshEnvPool, make_env_mesh
from repro.core.xla_loop import build_random_collect_fn
from repro.envs.classic import CartPole
from repro.envs.token_env import TokenEnv

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))


def scripted_rollout(pool, env, steps=10, seed=0):
    ps, ts = pool.reset(jax.random.PRNGKey(seed))
    step = jax.jit(pool.step)
    recs = []
    for t in range(steps):
        hi = int(env.spec.act_spec.maximum or 1)
        a = ((ts.env_id * 7 + t) % (hi + 1)).astype(env.spec.act_spec.dtype)
        ps, ts = step(ps, a, ts.env_id)
        order = np.argsort(np.asarray(ts.env_id))
        recs.append((
            np.asarray(ts.env_id)[order],
            np.asarray(ts.reward)[order],
            np.asarray(ts.obs)[order],
        ))
    return recs


def test_mesh1_matches_plain_device_pool():
    """An explicit D=1 mesh must be a bitwise no-op vs the default
    one-device pool (sync)."""
    env = TokenEnv()
    plain = MeshEnvPool(env, 8, 8, mode="sync")
    sharded = MeshEnvPool(env, 8, mesh=1)
    for (i1, r1, o1), (i2, r2, o2) in zip(
        scripted_rollout(plain, env), scripted_rollout(sharded, env)
    ):
        np.testing.assert_array_equal(i1, i2)
        np.testing.assert_array_equal(r1, r2)
        np.testing.assert_array_equal(o1, o2)


def test_sync_output_is_env_id_ordered():
    """Sharded sync batches are emitted in env-id order (the property
    that makes rollouts independent of per-shard top-k cost ordering)."""
    pool = MeshEnvPool(TokenEnv(), 8, mesh=1)
    ps, ts = pool.reset(jax.random.PRNGKey(0))
    step = jax.jit(pool.step)
    for t in range(3):
        np.testing.assert_array_equal(np.asarray(ts.env_id), np.arange(8))
        a = ((ts.env_id + t) % 256).astype(jnp.int32)
        ps, ts = step(ps, a, ts.env_id)


def test_async_mode_unique_ids():
    pool = MeshEnvPool(CartPole(), 8, batch_size=4, mesh=1)
    assert pool.mode == "async"
    ps, ts = pool.reset(jax.random.PRNGKey(0))
    step = jax.jit(pool.step)
    served = []
    for t in range(6):
        ids = np.asarray(ts.env_id)
        assert len(set(ids.tolist())) == 4, ids
        served.extend(ids.tolist())
        a = ((ts.env_id + t) % 2).astype(jnp.int32)
        ps, ts = step(ps, a, ts.env_id)
    assert set(served) == set(range(8))  # aging: nobody starves


def test_scan_rollout_under_jit():
    """The whole collect loop lowers into one lax.scan over the pool."""
    pool = MeshEnvPool(TokenEnv(), 8, mesh=1)
    collect = build_random_collect_fn(pool, num_steps=7)
    ps, ts = pool.reset(jax.random.PRNGKey(0))
    ps, ts, traj, acts = collect(ps, None, ts, jax.random.PRNGKey(1))
    assert traj.reward.shape == (7, 8)
    assert acts.shape[0] == 7
    assert np.isfinite(np.asarray(traj.reward)).all()


def test_xla_handle_api():
    pool = MeshEnvPool(CartPole(), 4, batch_size=2, mesh=1)
    handle, recv, send, step = pool.xla()
    ps, ts = recv(handle)
    assert ts.env_id.shape == (2,)
    ps = send(ps, jnp.zeros(2, jnp.int32), ts.env_id)
    ps, ts = recv(ps)
    assert ts.env_id.shape == (2,)


def test_validation_errors():
    env = CartPole()
    with pytest.raises(ValueError):
        make_env_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError):
        MeshEnvPool(env, 4, batch_size=8, mesh=1)


def test_multi_shard_invariance_subprocess():
    """Mesh of 1 vs 4 simulated host devices: bitwise-equal sync rollouts,
    scan smoke, async uniqueness, divisibility validation."""
    script = os.path.join(ROOT, "tests", "_sharded_check.py")
    p = subprocess.run([sys.executable, script, "4"], env=ENV,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout[p.stdout.index("{"):])
    assert res["devices"] == 4
    assert res["equal_TokenCopy-v0"], res
    assert res["equal_CartPole-v1"], res
    assert res["scan_shape_ok"] and res["scan_finite"], res
    assert res["async_unique_ids"], res
    assert res["divisibility_raises"], res
    # hierarchical scheduler: mesh-size-deterministic at mesh∈{1,2,4},
    # unique batches, overdue band prevents starvation
    assert res["hier_deterministic"], res
    assert res["hier_unique_ids"], res
    assert res["hier_no_starvation"], res
    # NormalizeObs moments checkpointed at mesh 1 restore onto mesh D
    # (and back): global entries re-broadcast to identical shard copies
    assert res["tf_restore_elastic"], res
