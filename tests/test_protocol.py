"""core/protocol.py: ONE `EnvPool` contract over all five engines, and
the drivers (dm_api / xla_loop / PPO) running unchanged across them."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.protocol import EnvPool, FunctionalEnvPool, bind, is_functional
from repro.core.xla_loop import build_collect_fn, collect_init

TASK = "TokenCopy-v0"
SEED = 0


# --------------------------------------------------------------------- #
# protocol conformance: all five engines
# --------------------------------------------------------------------- #
def _make(engine, n=4, m=None):
    kwargs = {}
    if engine == "thread":
        kwargs["num_threads"] = 2
    if engine == "subprocess":
        kwargs["num_threads"] = 1
    if engine == "device-sharded":
        kwargs["num_shards"] = 1
    return repro.make(TASK, num_envs=n, batch_size=m, engine=engine,
                      seed=SEED, **kwargs)


@pytest.mark.parametrize("engine,functional", [
    ("device", True),
    ("device-sharded", True),
    ("thread", False),
    ("forloop", False),
    ("subprocess", False),
])
def test_all_six_engines_satisfy_envpool_protocol(engine, functional):
    pool = _make(engine, 4)
    try:
        assert isinstance(pool, EnvPool), engine
        assert is_functional(pool) == functional, engine
        if functional:
            assert isinstance(pool, FunctionalEnvPool)
        # the spec triple every engine must carry (paper §3.4)
        assert pool.num_envs == 4
        assert pool.batch_size == 4
        assert pool.spec.obs_spec.shape
    finally:
        if hasattr(pool, "close"):
            pool.close()


@pytest.mark.parametrize("engine", ["device", "device-sharded", "thread",
                                    "forloop"])
def test_bind_uniform_driver_loop(engine):
    """bind() gives the same reset/step TimeStep loop over any engine."""
    pool = _make(engine, 4)
    h = bind(pool, key=jax.random.PRNGKey(SEED))
    try:
        ts = h.reset()
        assert np.asarray(ts.env_id).shape == (4,)
        for t in range(3):
            a = ((np.asarray(ts.env_id) * 7 + t) % 256).astype(np.int32)
            ts = h.step(jnp.asarray(a), ts.env_id)
            assert np.asarray(ts.reward).shape == (4,)
    finally:
        h.close()


def test_bound_send_recv_roundtrip():
    pool = _make("device", 4)
    h = bind(pool, key=jax.random.PRNGKey(SEED))
    ts = h.reset()
    h.send(jnp.zeros(4, jnp.int32), ts.env_id)
    ts = h.recv()
    assert np.asarray(ts.env_id).shape == (4,)


# --------------------------------------------------------------------- #
# drivers unchanged over device / device-sharded / thread (acceptance)
# --------------------------------------------------------------------- #
def _scripted_policy(params, obs, key):
    del params, key
    # deterministic from the observation -> identical across engines
    return (jnp.sum(jnp.asarray(obs), axis=-1) % 256).astype(jnp.int32)


@pytest.mark.parametrize("engine", ["device", "device-sharded", "thread"])
def test_collect_fn_runs_over_engine(engine):
    pool = _make(engine, 4)
    try:
        collect = build_collect_fn(pool, _scripted_policy, num_steps=5,
                                   donate=False)
        carry, ts = collect_init(pool, jax.random.PRNGKey(SEED))
        carry, ts, traj, acts = collect(carry, None, ts, jax.random.PRNGKey(1))
        assert np.asarray(traj.reward).shape == (5, 4)
        assert np.asarray(acts).shape[:2] == (5, 4)
    finally:
        if hasattr(pool, "close"):
            pool.close()


def test_collect_fn_identical_rewards_device_vs_thread():
    """Same scripted policy through the SAME driver over two engines
    must give identical reward streams (sorted by env id per step)."""
    streams = {}
    for engine in ("device", "thread"):
        pool = _make(engine, 4)
        try:
            collect = build_collect_fn(pool, _scripted_policy, num_steps=6,
                                       donate=False)
            carry, ts = collect_init(pool, jax.random.PRNGKey(SEED))
            _, _, traj, _ = collect(carry, None, ts, jax.random.PRNGKey(1))
            ids = np.asarray(traj.env_id)
            rew = np.asarray(traj.reward)
            streams[engine] = np.stack(
                [r[np.argsort(i)] for r, i in zip(rew, ids)]
            )
        finally:
            if hasattr(pool, "close"):
                pool.close()
    np.testing.assert_array_equal(streams["device"], streams["thread"])


@pytest.mark.parametrize("engine", ["device", "device-sharded", "thread"])
def test_ppo_train_dispatches_over_engine(engine):
    from repro.rl.ppo import PPOConfig, train

    kwargs = ({"num_threads": 2} if engine == "thread"
              else {"num_shards": 1} if engine == "device-sharded" else {})
    pool = repro.make("CartPole-v1", num_envs=4, engine=engine, seed=SEED,
                      **kwargs)
    try:
        cfg = PPOConfig(total_steps=4 * 8 * 2, num_steps=8, minibatches=2,
                        epochs=1)
        state, net, hist = train(pool, cfg, seed=0, hidden=(16,))
        assert len(hist) >= 1
        assert np.isfinite(hist[-1]["loss"])
    finally:
        if hasattr(pool, "close"):
            pool.close()


# --------------------------------------------------------------------- #
# dm_api: engine-agnostic + FIRST emitted after auto-reset (satellite)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("engine", ["device", "device-sharded", "thread"])
def test_dm_env_first_last_roundtrip(engine):
    """TokenCopy episodes end after exactly ep_len steps: the LAST batch
    must be followed by a FIRST batch for the same envs."""
    ep_len = 4
    pool = repro.make(TASK, num_envs=4, engine=engine, seed=SEED,
                      ep_len=ep_len,
                      **({"num_threads": 2} if engine == "thread" else
                         {"num_shards": 1} if engine == "device-sharded" else {}))
    dm = repro.DmEnv(pool)
    try:
        ts = dm.reset(jax.random.PRNGKey(SEED))
        assert bool(np.all(np.asarray(ts.first())))           # reset batch
        assert np.all(np.asarray(ts.reward) == 0.0)
        phases = []
        for t in range(2 * ep_len):
            acts = jnp.zeros(4, jnp.int32)
            ts = dm.step(acts, ts.observation.env_id)
            ids = np.asarray(ts.observation.env_id)
            # batches arrive in completion order on host engines:
            # realign every step to env-id order before stacking lanes
            phases.append(np.asarray(ts.step_type)[np.argsort(ids)].copy())
        phases = np.stack(phases)                             # (T, 4)
        for lane in range(4):
            col = phases[:, lane].tolist()
            assert 2 in col, col                              # a LAST happened
            last_at = col.index(2)
            assert col[:last_at] == [1] * last_at, col        # MIDs before
            # the very next served step opens the new episode
            assert col[last_at + 1] == 0, col
            # and the episode after that proceeds with MIDs until next LAST
            if last_at + 2 < len(col):
                assert col[last_at + 2] in (1, 2), col
    finally:
        if hasattr(pool, "close"):
            pool.close()


def test_dm_first_has_full_discount():
    pool = repro.make(TASK, num_envs=2, engine="device", seed=SEED, ep_len=2)
    dm = repro.DmEnv(pool, gamma=0.9)
    ts = dm.reset(jax.random.PRNGKey(0))
    for _ in range(2):
        ts = dm.step(jnp.zeros(2, jnp.int32), ts.observation.env_id)
    assert bool(np.all(np.asarray(ts.last())))
    ts = dm.step(jnp.zeros(2, jnp.int32), ts.observation.env_id)
    assert bool(np.all(np.asarray(ts.first())))
    np.testing.assert_allclose(np.asarray(ts.discount), 1.0)


# --------------------------------------------------------------------- #
# xla(seed) satellite
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("maker", [
    lambda: repro.make(TASK, num_envs=4, engine="device", seed=SEED),
    lambda: repro.make(TASK, num_envs=4, engine="device-sharded",
                       num_shards=1, seed=SEED),
])
def test_xla_handle_is_seedable(maker):
    pool = maker()
    h0a, *_ = pool.xla()                       # default — old behavior
    h0b, *_ = pool.xla(seed=0)
    h7, *_ = pool.xla(seed=7)
    hk, *_ = pool.xla(key=jax.random.PRNGKey(7))
    t0a = jax.tree.leaves(pool.lane_view(h0a).env_states)[0]
    t0b = jax.tree.leaves(pool.lane_view(h0b).env_states)[0]
    t7 = jax.tree.leaves(pool.lane_view(h7).env_states)[0]
    tk = jax.tree.leaves(pool.lane_view(hk).env_states)[0]
    np.testing.assert_array_equal(np.asarray(t0a), np.asarray(t0b))
    np.testing.assert_array_equal(np.asarray(t7), np.asarray(tk))
    assert not np.array_equal(np.asarray(t0a), np.asarray(t7))


# --------------------------------------------------------------------- #
# ThreadEnvPool lifecycle satellites
# --------------------------------------------------------------------- #
def test_thread_pool_close_is_idempotent_and_concurrent():
    pool = repro.make("CartPole-v1", engine="thread", num_envs=4,
                      batch_size=4, num_threads=2)
    errs = []

    def closer():
        try:
            pool.close()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=closer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    pool.close()  # and again, explicitly


def test_thread_pool_partial_reset_raises():
    pool = repro.make("CartPole-v1", engine="thread", num_envs=4,
                      batch_size=2, num_threads=2)
    try:
        with pytest.raises(RuntimeError, match="partial batch"):
            pool.reset()
    finally:
        pool.close()


def test_forloop_send_recv_protocol():
    pool = repro.make("CartPole-v1", engine="forloop", num_envs=3)
    pool.async_reset()
    out = pool.recv()
    assert out["obs"].shape[0] == 3
    pool.send(np.zeros(3, np.int64), out["env_id"])
    out = pool.recv()
    assert out["reward"].shape == (3,)
    with pytest.raises(RuntimeError):
        pool.recv()                            # nothing pending
