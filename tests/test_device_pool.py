"""MeshEnvPool semantics: the paper's engine invariants, TPU-native."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.engine import MeshEnvPool
from repro.envs.classic import CartPole
from repro.envs.mujoco_like import MujocoLike


def roll(pool, steps=30, seed=0):
    env = pool.env
    ps, ts = pool.reset(jax.random.PRNGKey(seed))
    step = jax.jit(pool.step)
    seen = []
    for i in range(steps):
        a = env.sample_actions(jax.random.PRNGKey(1000 + i), pool.batch_size)
        ps, ts = step(ps, a, ts.env_id)
        seen.append(np.asarray(ts.env_id))
    return ps, ts, np.concatenate(seen)


def test_sync_equals_direct_vmap():
    """sync pool over N must equal directly vmapped env stepping."""
    env = CartPole()
    pool = MeshEnvPool(env, 4, 4, mode="sync")
    ps = pool.init(jax.random.PRNGKey(0))

    # manual reference: same seeds -> same init states
    rng, sub = jax.random.split(jax.random.PRNGKey(0))
    keys = jax.random.split(sub, 4)
    ref_states = jax.vmap(env.init_state)(keys)

    acts = env.sample_actions(jax.random.PRNGKey(7), 4)
    ps2, ts = pool.step(ps, acts, jnp.arange(4))
    ref_states, ref_ts = env.v_step(ref_states, acts)
    np.testing.assert_allclose(
        np.asarray(ts.obs), np.asarray(ref_ts.obs), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(ts.reward), np.asarray(ref_ts.reward), rtol=1e-6
    )


@pytest.mark.parametrize("mode,N,M", [
    ("sync", 8, 8), ("async", 8, 4), ("async", 16, 4),
])
def test_batch_shape_and_ids(mode, N, M):
    pool = MeshEnvPool(MujocoLike(), N, M, mode=mode)
    ps, ts = pool.reset(jax.random.PRNGKey(0))
    assert ts.env_id.shape == (M,)
    assert len(set(np.asarray(ts.env_id).tolist())) == M  # distinct envs
    ps, ts2 = pool.step(
        ps, pool.env.sample_actions(jax.random.PRNGKey(1), M), ts.env_id
    )
    assert ts2.reward.shape == (M,)
    assert np.all(np.asarray(ts2.step_cost) >= 0)


def test_no_starvation_async():
    """Aging must guarantee every env is served (paper §3.3 long-tail)."""
    pool = MeshEnvPool(MujocoLike(), 16, 4, mode="async", aging=1.0)
    _, _, seen = roll(pool, steps=60)
    counts = np.bincount(seen, minlength=16)
    assert counts.min() > 0, counts
    # fairness: no env should dominate more than ~4x the median
    assert counts.max() <= max(4 * np.median(counts), 8), counts


def test_async_m_equals_n_matches_sync():
    env = CartPole()
    sync = MeshEnvPool(env, 6, 6, mode="sync")
    asy = MeshEnvPool(env, 6, 6, mode="async")
    ps_s, ts_s = sync.reset(jax.random.PRNGKey(3))
    ps_a, ts_a = asy.reset(jax.random.PRNGKey(3))
    for i in range(10):
        a = env.sample_actions(jax.random.PRNGKey(i), 6)
        # align by env_id ordering
        order_s = np.argsort(np.asarray(ts_s.env_id))
        order_a = np.argsort(np.asarray(ts_a.env_id))
        ps_s, ts_s = sync.step(ps_s, a[order_s], ts_s.env_id[order_s])
        ps_a, ts_a = asy.step(ps_a, a[order_a], ts_a.env_id[order_a])
        np.testing.assert_allclose(
            np.sort(np.asarray(ts_s.reward)), np.sort(np.asarray(ts_a.reward)),
            rtol=1e-6,
        )


def test_env_id_routing():
    """Actions must be applied to the env they were addressed to: stepping
    env k twice with the same action from the same state is deterministic,
    regardless of batch position."""
    env = CartPole()
    pool = MeshEnvPool(env, 8, 4, mode="async")
    ps, ts = pool.reset(jax.random.PRNGKey(0))
    # send actions labeled by env_id; observation for env k must evolve by
    # env k's dynamics (check obs corresponds to stored env state)
    a = env.sample_actions(jax.random.PRNGKey(5), 4)
    ps2, ts2 = pool.step(ps, a, ts.env_id)
    env_states = pool.lane_view(ps2).env_states
    for j, env_id in enumerate(np.asarray(ts2.env_id)):
        state_j = jax.tree.map(lambda x: x[env_id], env_states)
        np.testing.assert_allclose(
            np.asarray(env.observe(state_j)), np.asarray(ts2.obs[j]), rtol=1e-6
        )


def test_xla_handle_api():
    pool = MeshEnvPool(CartPole(), 4, 2, mode="async")
    handle, recv, send, step = pool.xla()
    ps, ts = recv(handle)
    assert ts.env_id.shape == (2,)
    ps = send(ps, jnp.zeros(2, jnp.int32), ts.env_id)
    ps, ts = recv(ps)
    assert ts.env_id.shape == (2,)


def test_validation_errors():
    env = CartPole()
    with pytest.raises(ValueError):
        MeshEnvPool(env, 4, 8)
    with pytest.raises(ValueError):
        MeshEnvPool(env, 4, 2, mode="sync")
    with pytest.raises(ValueError):
        MeshEnvPool(env, 4, 4, mode="weird")


# the event-driven tick ablation's mode, which the engine no longer has
TICK_MODE = "masked"


@pytest.mark.parametrize("build", [
    lambda: repro.make("CartPole-v1", num_envs=4, batch_size=2,
                       engine=f"device-{TICK_MODE}"),
    lambda: MeshEnvPool(CartPole(), 4, 2, mode=TICK_MODE),
], ids=["make-engine", "pool-mode"])
def test_tick_mode_is_rejected(build):
    """The device engine has one recv path: neither the engine name nor
    the mode of the tick ablation builds a pool."""
    with pytest.raises(ValueError, match=TICK_MODE):
        build()


def test_sync_blocks_come_in_lane_order_at_one_shard():
    """A sync recv serves every lane, in env-id order at every mesh size
    (gym's and EnvPool's sync ``step`` contract), though Pong's step cost
    varies (+2 frames after a point, +3 on a reset) and so reorders the
    scheduler's priority from one step to the next."""
    import repro

    n = 16
    pool = repro.make("PongClassic-v5", num_envs=n, engine="device", seed=3)
    assert pool.num_shards == 1 and pool.mode == "sync"
    ps, ts = pool.reset(jax.random.PRNGKey(3))
    step = jax.jit(pool.step)
    costs = []
    for t in range(60):
        np.testing.assert_array_equal(ts.env_id, np.arange(n), f"step {t}")
        a = jnp.asarray((np.arange(n) * 5 + t) % 6, jnp.int32)
        ps, ts = step(ps, a, ts.env_id)
        costs.append(np.asarray(ts.step_cost))
    # the costs did vary within steps: priority order alone would differ
    assert sum(len(set(c)) > 1 for c in costs) >= 10
