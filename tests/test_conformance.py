"""Engine conformance: the same scripted policy through every engine via
``make()`` must produce identical reward/done streams (EnvPool's promise
that the engine is an execution detail, not a semantics change).

Uses TokenEnv: episodes are exactly ``ep_len`` steps, so short rollouts
never hit auto-reset and rewards depend only on (init key, actions) —
which ``make()`` aligns across engines via shared per-env init keys.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.registry import make

from _lanes import by_lane

TASK = "TokenCopy-v0"
N = 8
STEPS = 10
SEED = 0
VOCAB = 256


def policy(env_ids: np.ndarray, t: int) -> np.ndarray:
    """Deterministic per-(env, step) action — engine-independent."""
    return ((env_ids.astype(np.int64) * 7 + t) % VOCAB).astype(np.int32)


def by_id(ids, *arrays):
    order = np.argsort(ids)
    return tuple(np.asarray(a)[order] for a in arrays)


def run_host_engine(engine: str):
    pool = make(TASK, num_envs=N, engine=engine, seed=SEED)
    try:
        if hasattr(pool, "async_reset"):
            pool.async_reset()
            out = pool.recv()
        else:
            out = pool.reset()
        recs = []
        for t in range(STEPS):
            ids = np.asarray(out["env_id"])
            out = pool.step(policy(ids, t), ids)
            recs.append(by_id(np.asarray(out["env_id"]),
                              out["reward"], out["done"]))
        return recs
    finally:
        if hasattr(pool, "close"):
            pool.close()


def run_device_engine(engine: str):
    pool = make(TASK, num_envs=N, engine=engine, seed=SEED)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    recs = []
    for t in range(STEPS):
        ids = np.asarray(ts.env_id)
        a = jnp.asarray(policy(ids, t))
        ps, ts = step(ps, a, ts.env_id)
        recs.append(by_id(np.asarray(ts.env_id), ts.reward, ts.done))
    return recs


def test_all_engines_identical_rewards_and_dones():
    """forloop == thread == device(sync) == device-sharded, step for step."""
    ref = run_device_engine("device")
    for engine, runner in [
        ("device-sharded", run_device_engine),
        ("forloop", run_host_engine),
        ("thread", run_host_engine),
    ]:
        got = runner(engine)
        for t, ((r_ref, d_ref), (r_got, d_got)) in enumerate(zip(ref, got)):
            np.testing.assert_allclose(
                r_ref, r_got, rtol=0, atol=0,
                err_msg=f"{engine} reward diverges at step {t}",
            )
            np.testing.assert_array_equal(
                d_ref, d_got, err_msg=f"{engine} done diverges at step {t}"
            )


@pytest.mark.parametrize("engine", ["device", "device-sharded"])
def test_async_batches_have_unique_ids(engine):
    """Every recv batch is M distinct envs (paper §3.2 batch contract)."""
    pool = make(TASK, num_envs=16, batch_size=4, engine=engine, seed=SEED)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    for t in range(8):
        ids = np.asarray(ts.env_id).tolist()
        assert len(set(ids)) == 4, ids
        a = jnp.asarray(policy(np.asarray(ts.env_id), t))
        ps, ts = step(ps, a, ts.env_id)


@pytest.mark.parametrize("engine", ["device", "device-sharded"])
def test_async_serves_everyone_once_before_twice(engine):
    """Under aging, the first N/M batches cover all N envs exactly once —
    the soft-FIFO guarantee that replaces the StateBufferQueue's hard one."""
    N_, M = 16, 4
    pool = make(TASK, num_envs=N_, batch_size=M, engine=engine, seed=SEED)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    served = list(np.asarray(ts.env_id))          # reset = first batch
    for t in range(N_ // M - 1):
        a = jnp.asarray(policy(np.asarray(ts.env_id), t))
        ps, ts = step(ps, a, ts.env_id)
        served.extend(np.asarray(ts.env_id).tolist())
    assert sorted(served) == list(range(N_)), served


def test_make_rejects_unknown_engine():
    with pytest.raises(ValueError):
        make(TASK, num_envs=4, engine="gpu-cluster")


def test_make_rejects_bad_schedules():
    with pytest.raises(ValueError):
        make(TASK, num_envs=4, schedule="random")
    with pytest.raises(ValueError):
        # hierarchical is the cross-shard policy
        make(TASK, num_envs=4, batch_size=2, engine="device",
             schedule="hierarchical")
    with pytest.raises(ValueError):
        # sync baselines have no selection freedom
        make(TASK, num_envs=4, engine="forloop", schedule="sjf")
    with pytest.raises(ValueError):
        # hierarchical has no host mirror (single queue = single shard)
        make(TASK, num_envs=4, batch_size=2, engine="thread",
             schedule="hierarchical")


# --------------------------------------------------------------------- #
# schedule="fifo" must be bitwise-identical to the PRE-refactor engines:
# golden streams captured before the scheduler extraction (PR 3) by
# tests/_golden_gen.py — regenerating them just blesses new behavior, so
# don't, unless the conformance contract itself is deliberately moved.
# --------------------------------------------------------------------- #
GOLDEN = np.load(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "golden_fifo_streams.npz")
)
G_STEPS = 12


def golden_device_stream(engine, n, m, **kw):
    pool = make(TASK, num_envs=n, batch_size=m, engine=engine, seed=SEED, **kw)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    recs = []
    for t in range(G_STEPS):
        ids = np.asarray(ts.env_id)
        ps, ts = step(ps, jnp.asarray(policy(ids, t)), ts.env_id)
        recs.append((np.asarray(ts.env_id), np.asarray(ts.reward),
                     np.asarray(ts.done), np.asarray(ts.obs)))
    return [np.stack(x) for x in zip(*recs)]


@pytest.mark.parametrize("tag,engine,n,m,kw", [
    ("device_sync", "device", 8, None, {}),
    ("device_async", "device", 8, 4, {}),
    ("sharded_async", "device-sharded", 8, 4, {"num_shards": 1}),
])
def test_fifo_bitwise_matches_pre_refactor_golden(tag, engine, n, m, kw):
    ids, rew, done, obs = golden_device_stream(engine, n, m, **kw)
    np.testing.assert_array_equal(ids, GOLDEN[f"{tag}_ids"])
    np.testing.assert_array_equal(rew, GOLDEN[f"{tag}_rew"])
    np.testing.assert_array_equal(done, GOLDEN[f"{tag}_done"])
    np.testing.assert_array_equal(obs, GOLDEN[f"{tag}_obs"])


@pytest.mark.parametrize("tag,n,m", [
    ("device_sync", 8, None),
    ("device_async", 8, 4),
])
def test_unified_engine_mesh1_sharded_matches_device_goldens(tag, n, m):
    """The engine-unification contract: ``device-sharded`` at mesh 1 is
    the SAME class over the SAME degenerate mesh as ``device``, so it
    must reproduce the device goldens bitwise — including sync emission
    order (env-id order at every mesh size)."""
    ids, rew, done, obs = golden_device_stream(
        "device-sharded", n, m, num_shards=1
    )
    np.testing.assert_array_equal(ids, GOLDEN[f"{tag}_ids"])
    np.testing.assert_array_equal(rew, GOLDEN[f"{tag}_rew"])
    np.testing.assert_array_equal(done, GOLDEN[f"{tag}_done"])
    np.testing.assert_array_equal(obs, GOLDEN[f"{tag}_obs"])


def test_unified_engine_mesh1_sharded_matches_atari_golden():
    """Same unification check on the second golden: the default-pipeline
    Pong stream (FrameStack(4) fused in-engine, variable frameskip
    cost).  Compared lane by lane: the golden's blocks came in priority
    order (not env-id-sorted at steps 8 and 20), a sync block now comes
    in env-id order at every mesh size."""
    golden = np.load(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "golden_atari_stream.npz")
    )
    pool = make("Pong-v5", num_envs=4, engine="device-sharded",
                num_shards=1, seed=SEED)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    recs = []
    for t in range(32):
        i = np.asarray(ts.env_id)
        a = jnp.asarray(((i * 3 + t) % 6).astype(np.int32))
        ps, ts = step(ps, a, ts.env_id)
        recs.append((np.asarray(ts.env_id), np.asarray(ts.reward),
                     np.asarray(ts.done), np.asarray(ts.step_cost),
                     np.asarray(ts.obs)))
    got = by_lane(*(np.stack(x) for x in zip(*recs)))
    want = by_lane(*(golden[k] for k in ("ids", "rew", "done", "cost",
                                         "obs_stack")))
    for name, g, w in zip(("ids", "rew", "done", "cost", "obs"), got, want):
        np.testing.assert_array_equal(g, w, err_msg=name)


def test_scanned_collect_donates_pool_state():
    """The device-resident collect contract: the donated ``lax.scan``
    must hand the PoolState SoA buffers to XLA (donate_argnums) instead
    of retaining stale copies — every input leaf is invalidated by the
    call, so the rollout carries exactly one live PoolState."""
    from repro.core.xla_loop import build_random_collect_fn

    pool = make(TASK, num_envs=N, seed=SEED)
    collect = build_random_collect_fn(pool, num_steps=4)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    stale = jax.tree.leaves(ps)
    ps2, ts2, traj, _ = collect(ps, None, ts, jax.random.PRNGKey(1))
    assert all(leaf.is_deleted() for leaf in stale), (
        "scanned collect retained stale PoolState buffers"
    )
    # the returned state is live and usable (the buffers were reused,
    # not lost) — one more step must run off it
    ps3, ts3 = jax.jit(pool.step)(
        ps2, jnp.zeros((N,), jnp.int32), ts2.env_id
    )
    assert np.isfinite(np.asarray(ts3.reward)).all()


def test_fifo_thread_matches_pre_refactor_golden():
    """Thread engine (M == N, batches env-id-sorted: block composition
    is timing-dependent, per-env streams are not)."""
    pool = make(TASK, num_envs=8, engine="thread", seed=SEED, num_threads=2)
    try:
        pool.async_reset()
        out = pool.recv()
        for t in range(G_STEPS):
            ids = np.asarray(out["env_id"])
            out = pool.step(policy(ids, t), ids)
            o = np.argsort(np.asarray(out["env_id"]))
            np.testing.assert_array_equal(
                np.asarray(out["env_id"])[o], GOLDEN["thread_ids"][t])
            np.testing.assert_array_equal(
                np.asarray(out["reward"])[o], GOLDEN["thread_rew"][t])
            np.testing.assert_array_equal(
                np.asarray(out["done"])[o], GOLDEN["thread_done"][t])
    finally:
        pool.close()


# --------------------------------------------------------------------- #
# non-default schedules: serving order changes, trajectories don't
# --------------------------------------------------------------------- #
def test_sjf_schedule_serves_cost_homogeneous_blocks():
    """On the skew workload sjf must keep serving valid unique batches
    and (unlike fifo) keep heavy lanes out of cheap blocks while cheap
    work exists."""
    pool = make("TokenSkew-v0", num_envs=8, batch_size=4, engine="device",
                seed=SEED, schedule="sjf")
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    for t in range(10):
        ids = np.asarray(ts.env_id)
        assert len(set(ids.tolist())) == 4, ids
        ps, ts = step(ps, jnp.asarray(policy(ids, t)), ts.env_id)


def test_schedule_does_not_change_per_env_trajectories():
    """The policy only reorders service: per-env (reward, done) streams
    under sjf must equal the fifo streams, serve-for-serve."""

    def run(schedule):
        pool = make("TokenSkew-v0", num_envs=8, batch_size=4,
                    engine="device", seed=SEED, schedule=schedule)
        ps, ts = pool.reset(jax.random.PRNGKey(SEED))
        step = jax.jit(pool.step)
        counts = np.zeros(8, int)
        streams: dict[int, list] = {i: [] for i in range(8)}
        for _ in range(16):
            ids = np.asarray(ts.env_id)
            rew = np.asarray(ts.reward)
            for j, e in enumerate(ids):
                streams[int(e)].append(rew[j])
            a = jnp.asarray((counts[ids] * 7 + ids) % VOCAB, jnp.int32)
            counts[ids] += 1
            ps, ts = step(ps, a, ts.env_id)
        return streams

    sf, ss = run("fifo"), run("sjf")
    compared = 0
    for e in range(8):
        n = min(len(sf[e]), len(ss[e]))
        compared += n
        np.testing.assert_array_equal(
            np.asarray(sf[e][:n]), np.asarray(ss[e][:n]),
            err_msg=f"env {e} trajectory diverges across schedules",
        )
    assert compared > 0


# --------------------------------------------------------------------- #
# batched-native vs vmap-lifted: the hot-path rewrite must be invisible
# --------------------------------------------------------------------- #
ANT = "Ant-v3"   # MujocoLike: the Pallas-kernel-backed batched env


def ant_rollout(engine, batched, steps=25, n=8, m=None, num_shards=None):
    """Scripted continuous-action rollout; returns per-step
    (env_id-sorted) ids/rewards/obs/dones."""
    kwargs = {"num_shards": num_shards} if num_shards else {}
    pool = make(ANT, num_envs=n, batch_size=m, engine=engine,
                seed=SEED, batched=batched, **kwargs)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    recs = []
    for t in range(steps):
        ids = np.asarray(ts.env_id)
        # deterministic per-(env, step) continuous action in [-1, 1]
        a = jnp.asarray(
            np.sin(ids[:, None] * 0.7 + t * 0.3 + np.arange(8)[None, :]),
            jnp.float32,
        )
        ps, ts = step(ps, a, ts.env_id)
        order = np.argsort(np.asarray(ts.env_id))
        recs.append((
            np.asarray(ts.env_id)[order],
            np.asarray(ts.reward)[order],
            np.asarray(ts.obs)[order],
            np.asarray(ts.done)[order],
            np.asarray(ts.step_cost)[order],
        ))
    return recs


@pytest.mark.parametrize("engine,m,shards", [
    ("device", None, None),           # sync
    ("device", 4, None),              # async top-M
    ("device-sharded", None, 1),      # shard_map body
])
def test_batched_native_matches_vmap_lifted(engine, m, shards):
    """The Pallas-backed batched MujocoLike path must be BITWISE
    identical to the generic vmap-lifting adapter in every device mode
    (the acceptance contract of the batched-native rewrite)."""
    native = ant_rollout(engine, batched=None, m=m, num_shards=shards)
    vmapped = ant_rollout(engine, batched=False, m=m, num_shards=shards)
    costs = set()
    for t, (nat, vm) in enumerate(zip(native, vmapped)):
        for name, a, b in zip(("env_id", "reward", "obs", "done", "cost"),
                              nat, vm):
            np.testing.assert_array_equal(
                a, b, err_msg=f"{engine} {name} diverges at step {t}"
            )
        costs.update(nat[4].tolist())
    assert len(costs) > 1, f"rollout never exercised variable cost: {costs}"


def test_batched_native_ant_matches_host_per_lane_engine():
    """Cross-family: the kernel-backed device path against the
    per-lane JittedHostEnv thread engine (exact reward/done streams)."""
    dev = ant_rollout("device", batched=None, steps=10, n=4)

    pool = make(ANT, num_envs=4, engine="thread", seed=SEED, num_threads=2)
    try:
        pool.async_reset()
        out = pool.recv()
        recs = []
        for t in range(10):
            ids = np.asarray(out["env_id"])
            a = np.sin(ids[:, None] * 0.7 + t * 0.3 +
                       np.arange(8)[None, :]).astype(np.float32)
            out = pool.step(a, ids)
            order = np.argsort(np.asarray(out["env_id"]))
            recs.append((np.asarray(out["env_id"])[order],
                         np.asarray(out["reward"])[order],
                         np.asarray(out["done"])[order]))
    finally:
        pool.close()

    for t, ((di, dr, _, dd, _), (hi, hr, hd)) in enumerate(zip(dev, recs)):
        np.testing.assert_array_equal(di, hi, err_msg=f"ids step {t}")
        np.testing.assert_array_equal(dr, hr, err_msg=f"reward step {t}")
        np.testing.assert_array_equal(dd, hd, err_msg=f"done step {t}")
