"""RL substrate: GAE vs numpy oracle (hypothesis), PPO smoke."""

import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 container has no hypothesis
    from _propshim import given, settings, strategies as st

from repro.rl.gae import gae


def gae_numpy(rewards, values, dones, last_values, gamma, lam):
    T, N = rewards.shape
    adv = np.zeros((T, N))
    next_adv = np.zeros(N)
    next_val = last_values
    for t in reversed(range(T)):
        nd = 1.0 - dones[t]
        delta = rewards[t] + gamma * next_val * nd - values[t]
        next_adv = delta + gamma * lam * nd * next_adv
        adv[t] = next_adv
        next_val = values[t]
    return adv, adv + values


@given(
    T=st.integers(1, 20),
    N=st.integers(1, 4),
    gamma=st.floats(0.5, 0.999),
    lam=st.floats(0.5, 1.0),
    seed=st.integers(0, 100),
)
@settings(max_examples=25, deadline=None)
def test_gae_matches_numpy(T, N, gamma, lam, seed):
    rng = np.random.default_rng(seed)
    rewards = rng.normal(size=(T, N)).astype(np.float32)
    values = rng.normal(size=(T, N)).astype(np.float32)
    dones = (rng.random((T, N)) < 0.2)
    last_values = rng.normal(size=N).astype(np.float32)
    adv, ret = gae(jnp.asarray(rewards), jnp.asarray(values),
                   jnp.asarray(dones), jnp.asarray(last_values), gamma, lam)
    adv_np, ret_np = gae_numpy(rewards, values, dones.astype(np.float32),
                               last_values, gamma, lam)
    np.testing.assert_allclose(adv, adv_np, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(ret, ret_np, atol=1e-4, rtol=1e-4)


def test_gae_terminal_cuts_bootstrap():
    """After done=1, no value flows backward across the boundary."""
    rewards = jnp.array([[1.0], [0.0]])
    values = jnp.array([[0.0], [100.0]])
    dones = jnp.array([[True], [False]])
    last = jnp.array([100.0])
    adv, _ = gae(rewards, values, dones, last, gamma=0.99, lam=0.95)
    # step 0 advantage must see only its own reward (episode ended)
    np.testing.assert_allclose(adv[0, 0], 1.0, atol=1e-5)


def vtrace_numpy(blogp, tlogp, rewards, values, dones, bv,
                 gamma, lam, rho_clip, c_clip):
    T, N = rewards.shape
    nd = 1.0 - dones
    ratio = np.exp(tlogp - blogp)
    rho = np.minimum(ratio, rho_clip)
    c = lam * np.minimum(ratio, c_clip)
    vnext = np.concatenate([values[1:], bv[None]], axis=0)
    delta = rho * (rewards + gamma * vnext * nd - values)
    acc = np.zeros(N)
    dv = np.zeros((T, N))
    for t in reversed(range(T)):
        acc = delta[t] + gamma * nd[t] * c[t] * acc
        dv[t] = acc
    vs = values + dv
    vs_next = np.concatenate([vs[1:], bv[None]], axis=0)
    pg = rho * (rewards + gamma * vs_next * nd - values)
    return vs, pg


@given(
    T=st.integers(1, 20),
    N=st.integers(1, 4),
    gamma=st.floats(0.5, 0.999),
    lam=st.floats(0.5, 1.0),
    rho_clip=st.floats(0.5, 2.0),
    c_clip=st.floats(0.5, 2.0),
    seed=st.integers(0, 100),
)
@settings(max_examples=25, deadline=None)
def test_vtrace_matches_numpy(T, N, gamma, lam, rho_clip, c_clip, seed):
    from repro.rl.vtrace import vtrace

    rng = np.random.default_rng(seed)
    blogp = rng.normal(scale=0.5, size=(T, N)).astype(np.float32)
    tlogp = blogp + rng.normal(scale=0.3, size=(T, N)).astype(np.float32)
    rewards = rng.normal(size=(T, N)).astype(np.float32)
    values = rng.normal(size=(T, N)).astype(np.float32)
    dones = (rng.random((T, N)) < 0.2)
    bv = rng.normal(size=N).astype(np.float32)
    out = vtrace(jnp.asarray(blogp), jnp.asarray(tlogp), jnp.asarray(rewards),
                 jnp.asarray(values), jnp.asarray(dones), jnp.asarray(bv),
                 gamma=gamma, lam=lam, rho_clip=rho_clip, c_clip=c_clip)
    vs_np, pg_np = vtrace_numpy(blogp, tlogp, rewards, values,
                                dones.astype(np.float32), bv,
                                gamma, lam, rho_clip, c_clip)
    np.testing.assert_allclose(out.vs, vs_np, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(out.pg_advantages, pg_np, atol=1e-4, rtol=1e-4)


@given(
    T=st.integers(1, 20),
    N=st.integers(1, 4),
    gamma=st.floats(0.5, 0.999),
    lam=st.floats(0.5, 1.0),
    seed=st.integers(0, 100),
)
@settings(max_examples=25, deadline=None)
def test_vtrace_reduces_to_gae_on_policy(T, N, gamma, lam, seed):
    """behavior == target and inactive clip thresholds => ``vs - values``
    is EXACTLY the GAE(lam) advantage (the docstring contract that makes
    the pipelined path a strict generalization of the fused one)."""
    from repro.rl.vtrace import vtrace

    rng = np.random.default_rng(seed)
    logp = jnp.asarray(rng.normal(size=(T, N)).astype(np.float32))
    rewards = jnp.asarray(rng.normal(size=(T, N)).astype(np.float32))
    values = jnp.asarray(rng.normal(size=(T, N)).astype(np.float32))
    dones = jnp.asarray(rng.random((T, N)) < 0.2)
    bv = jnp.asarray(rng.normal(size=N).astype(np.float32))
    out = vtrace(logp, logp, rewards, values, dones, bv,
                 gamma=gamma, lam=lam, rho_clip=10.0, c_clip=10.0)
    adv, ret = gae(rewards, values, dones, bv, gamma, lam)
    np.testing.assert_allclose(out.vs - values, adv, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(out.vs, ret, atol=1e-4, rtol=1e-4)


def test_vtrace_on_policy_lam1_pg_adv_is_gae():
    """With lam=1 on-policy, the policy-gradient advantages also collapse
    to the GAE advantages (bootstrapped through vs_{t+1})."""
    from repro.rl.vtrace import vtrace

    rng = np.random.default_rng(3)
    logp = jnp.asarray(rng.normal(size=(12, 3)).astype(np.float32))
    rewards = jnp.asarray(rng.normal(size=(12, 3)).astype(np.float32))
    values = jnp.asarray(rng.normal(size=(12, 3)).astype(np.float32))
    dones = jnp.asarray(rng.random((12, 3)) < 0.2)
    bv = jnp.asarray(rng.normal(size=3).astype(np.float32))
    out = vtrace(logp, logp, rewards, values, dones, bv,
                 gamma=0.97, lam=1.0, rho_clip=10.0, c_clip=10.0)
    adv, _ = gae(rewards, values, dones, bv, 0.97, 1.0)
    np.testing.assert_allclose(out.pg_advantages, adv, atol=1e-4, rtol=1e-4)


def test_mean_return_finite_on_zero_episode_iteration():
    """TokenEnv episodes last 32 steps; with num_steps=8 the first
    iteration completes ZERO episodes.  mean_return must stay a plain
    finite float (carry-forward / 0.0), never NaN, and the history must
    stay JSON-serializable (the Fig-4 artifact contract)."""
    import json

    from repro.core.registry import make
    from repro.rl.ppo import PPOConfig, train_device

    pool = make("TokenCopy-v0", num_envs=8, engine="device-sharded",
                num_shards=1, ep_len=32, vocab=8)
    cfg = PPOConfig(total_steps=8 * 8 * 3, num_steps=8, minibatches=2,
                    epochs=2, lr=3e-4)
    _, _, hist = train_device(pool, cfg, seed=0, hidden=(32, 32))
    assert len(hist) == 3
    for h in hist:
        assert isinstance(h["mean_return"], float)
        assert np.isfinite(h["mean_return"]), hist
    # iteration 1 sees no completed episode: the recorded value is the
    # documented fallback (0.0, nothing earlier to carry forward)
    assert hist[0]["mean_return"] == 0.0
    json.dumps(hist)  # must not choke on jnp scalars / NaN


def test_train_pipelined_smoke():
    """The double-buffered driver runs end to end at mesh=1: collect
    stays one policy step stale, metrics stay finite, and the V-trace
    update path exercises rho_behavior accounting."""
    from repro.core.registry import make
    from repro.rl.ppo import PPOConfig, train_pipelined

    pool = make("TokenCopy-v0", num_envs=8, engine="device-sharded",
                num_shards=1, ep_len=8, vocab=8, ctx_len=16)
    cfg = PPOConfig(total_steps=8 * 8 * 4, num_steps=8, minibatches=2,
                    epochs=2, lr=3e-4)
    _, _, hist = train_pipelined(pool, cfg, seed=0, hidden=(32, 32))
    assert len(hist) == 4
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert all(np.isfinite(h["mean_return"]) for h in hist)
    assert all(np.isfinite(h["rho_behavior"]) for h in hist)


def test_train_host_pipelined_smoke():
    """Appendix-D queues on the hot path: actor thread streams batches
    into the StateBufferQueue while the learner drains blocks.  Must run
    to completion (no deadlock against the bounded ring), produce finite
    metrics, and report the actor_wait/train/other profile buckets."""
    from repro.core.registry import make
    from repro.rl.ppo import PPOConfig, train_host_pipelined

    pool = make("TokenCopy-v0", num_envs=8, engine="thread",
                num_threads=2, ep_len=8, vocab=8, ctx_len=16)
    try:
        cfg = PPOConfig(total_steps=8 * 8 * 3, num_steps=8, minibatches=2,
                        epochs=2, lr=3e-4)
        _, _, hist, prof = train_host_pipelined(pool, cfg=cfg, seed=0,
                                                hidden=(32, 32))
    finally:
        pool.close()
    assert len(hist) == 3
    assert all(np.isfinite(h["loss"]) for h in hist)
    assert all(np.isfinite(h["mean_return"]) for h in hist)
    assert set(prof) == {"actor_wait", "train", "other"}
    assert all(v >= 0 for v in prof.values())


def test_ppo_improves_cartpole():
    """Short-budget learning trend on CartPole (device pool, sync)."""
    from repro.core.engine import MeshEnvPool
    from repro.envs.classic import CartPole
    from repro.rl.ppo import PPOConfig, train_device

    pool = MeshEnvPool(CartPole(), 16, 16, mode="sync")
    cfg = PPOConfig(total_steps=30_000, num_steps=64, minibatches=4,
                    epochs=4, lr=1e-3)
    _, _, hist = train_device(pool, cfg, seed=1, hidden=(64, 64))
    early = np.nanmean([h["mean_return"] for h in hist[:5]])
    late = np.nanmean([h["mean_return"] for h in hist[-5:]])
    assert late > early + 20, (early, late)


def test_ppo_async_pool_runs():
    """PPO over the ASYNC pool (the paper's headline mode) trains without
    error and routes env_ids correctly."""
    from repro.core.engine import MeshEnvPool
    from repro.envs.mujoco_like import MujocoLike
    from repro.rl.ppo import PPOConfig, train_device

    pool = MeshEnvPool(MujocoLike(), 16, 8, mode="async")
    cfg = PPOConfig(total_steps=4_000, num_steps=32, minibatches=2,
                    epochs=2, lr=3e-4)
    _, _, hist = train_device(pool, cfg, seed=0, hidden=(32, 32))
    assert len(hist) >= 10
    assert all(np.isfinite(h["loss"]) for h in hist)


def test_train_step_rows_stay_with_their_lanes():
    """``make_train_step`` stacks step t's served rows (obs, actions,
    values) with step t+1's (rewards, dones): on a sync pool row i is
    lane i at every step, so GAE runs down each lane's own column.  The
    pool here reports, as each row's reward, its env id and step cost."""
    import repro
    from repro.rl.nets import ActorCritic
    from repro.rl.ppo import PPOConfig, make_ppo_update, make_train_step

    n, steps = 16, 40
    pool = repro.make("PongClassic-v5", num_envs=n, seed=5)

    class LaneTagged:
        def step(self, ps, a, ids):
            ps, ts = pool.step(ps, a, ids)
            tag = ts.env_id * 16 + ts.step_cost
            return ps, ts.replace(reward=tag.astype(jnp.float32))

    cfg = PPOConfig(num_steps=steps, epochs=1, minibatches=1, clip=0.1)
    net = ActorCritic(pool.spec)
    opt, update = make_ppo_update(net, cfg, 1)
    params = net.init(jax.random.PRNGKey(0))
    from repro.rl.ppo import PPOState

    state = PPOState(params, opt.init(params), jnp.int32(0))
    ps, ts = pool.reset(jax.random.PRNGKey(5))
    np.testing.assert_array_equal(ts.env_id, np.arange(n))
    step = make_train_step(LaneTagged(), cfg, net, update)
    k = jax.random.PRNGKey(1)
    _, _, ts, _, record = step(state, ps, ts, k, k)
    tag = np.asarray(record["rewards"]).astype(np.int64)
    np.testing.assert_array_equal(tag // 16, np.tile(np.arange(n), (steps, 1)))
    np.testing.assert_array_equal(ts.env_id, np.arange(n))
    assert sum(len(set(row)) > 1 for row in tag % 16) >= 5
    assert record["obs_sum"].shape == (steps, 1)      # one row in 16
