"""In-engine transform pipeline (core/transforms.py): spec transformers,
per-transform semantics, engine conformance (device / device-sharded /
thread / forloop, bitwise for the deterministic transforms), the Atari
golden pins, and the NormalizeObs moment invariants."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.registry import default_transforms, make
from repro.core.scheduler import get_scheduler
from repro.core.specs import TimeStep
from repro.core.transforms import (
    EpisodicLife,
    FrameStack,
    NormalizeObs,
    ObsCast,
    RewardClip,
    TransformPipeline,
)
from repro.envs.token_env import TokenEnv

from _lanes import by_lane

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0


def token_spec():
    return TokenEnv().spec


def block_ts(obs, reward=None, done=None):
    m = obs.shape[0]
    z = jnp.zeros((m,), jnp.float32)
    f = jnp.zeros((m,), jnp.bool_)
    return TimeStep(
        obs=jnp.asarray(obs),
        reward=z if reward is None else jnp.asarray(reward),
        done=f if done is None else jnp.asarray(done),
        terminated=f if done is None else jnp.asarray(done),
        truncated=f,
        env_id=jnp.arange(m, dtype=jnp.int32),
        episode_return=z,
        episode_length=jnp.zeros((m,), jnp.int32),
        step_cost=jnp.ones((m,), jnp.int32),
    )


# --------------------------------------------------------------------- #
# spec transformers: pool.spec stays truthful
# --------------------------------------------------------------------- #
def test_spec_transformers():
    spec = token_spec()                      # obs (64,) int32
    p = TransformPipeline(
        [FrameStack(3), ObsCast(np.float32, scale=0.5, offset=1.0)], spec
    )
    assert p.out_spec.obs_spec.shape == (3, 64)
    assert np.dtype(p.out_spec.obs_spec.dtype) == np.float32
    assert p.out_spec.obs_spec.minimum == 1.0           # 0 * 0.5 + 1
    assert p.out_spec.obs_spec.maximum == 255 * 0.5 + 1
    assert p.out_spec.act_spec is spec.act_spec         # never transformed

    n = TransformPipeline([NormalizeObs(clip=5.0)], spec)
    assert np.dtype(n.out_spec.obs_spec.dtype) == np.float32
    assert n.out_spec.obs_spec.minimum == -5.0
    assert n.out_spec.obs_spec.maximum == 5.0


def test_pipeline_rejects_non_transforms():
    with pytest.raises(TypeError):
        TransformPipeline(["frame_stack"], token_spec())


def test_make_spec_reflects_transforms():
    pool = make("Pong-v5", num_envs=2)                  # default stack
    assert pool.spec.obs_spec.shape == (4, 84, 84)
    assert pool.raw_spec.obs_spec.shape == (84, 84)
    raw = make("Pong-v5", num_envs=2, transforms=[])    # explicit raw
    assert raw.spec.obs_spec.shape == (84, 84)
    assert default_transforms("Pong-v5")[0].k == 4


def test_presets_registered():
    pong = make("PongStack-v5", num_envs=2)
    assert pong.spec.obs_spec.shape == (4, 84, 84)
    assert [type(t).__name__ for t in pong.pipeline.transforms] == [
        "FrameStack", "RewardClip"
    ]
    ant = make("AntNorm-v3", num_envs=2)
    assert np.dtype(ant.spec.obs_spec.dtype) == np.float32
    assert type(ant.pipeline.transforms[0]).__name__ == "NormalizeObs"


# --------------------------------------------------------------------- #
# per-transform semantics (pure functions on one block)
# --------------------------------------------------------------------- #
def test_frame_stack_push_reset_fresh():
    spec = token_spec()
    t = FrameStack(3)
    state = t.init(spec, 2)
    obs1 = jnp.arange(2 * 64, dtype=jnp.int32).reshape(2, 64)
    # first serve: fresh lanes broadcast
    state, ts = t.apply(state, block_ts(obs1), spec)
    out = np.asarray(ts.obs)
    assert out.shape == (2, 3, 64)
    np.testing.assert_array_equal(out[:, 0], out[:, 2])
    # second serve: push (oldest first)
    obs2 = obs1 + 1000
    state, ts = t.apply(state, block_ts(obs2), spec)
    out = np.asarray(ts.obs)
    np.testing.assert_array_equal(out[:, 2], np.asarray(obs2))
    np.testing.assert_array_equal(out[:, 1], np.asarray(obs1))
    # done lane restarts its stack from the (post-autoreset) first obs
    obs3 = obs1 + 5000
    done = jnp.asarray([True, False])
    state, ts = t.apply(state, block_ts(obs3, done=done), spec)
    out = np.asarray(ts.obs)
    np.testing.assert_array_equal(out[0, 0], np.asarray(obs3)[0])
    np.testing.assert_array_equal(out[0, 1], np.asarray(obs3)[0])
    np.testing.assert_array_equal(out[1, 1], np.asarray(obs2)[1])


def test_reward_clip_and_episodic_life():
    spec = token_spec()
    rc = RewardClip()
    _, ts = rc.apply((), block_ts(jnp.zeros((3, 64)),
                                  reward=jnp.asarray([-2.5, 0.5, 3.0])),
                     spec)
    np.testing.assert_array_equal(np.asarray(ts.reward), [-1.0, 0.5, 1.0])

    el = EpisodicLife()
    _, ts = el.apply((), block_ts(jnp.zeros((3, 64)),
                                  reward=jnp.asarray([-1.0, 0.0, 1.0])),
                     spec)
    np.testing.assert_array_equal(np.asarray(ts.done), [True, False, False])
    np.testing.assert_array_equal(np.asarray(ts.terminated),
                                  [True, False, False])
    # clip BEFORE life in a pipeline still sees the negative reward
    p = TransformPipeline([EpisodicLife(), RewardClip()], spec)
    st, ts = p.apply(p.init(4), block_ts(
        jnp.zeros((4, 64)), reward=jnp.asarray([-3.0, -0.5, 0.0, 2.0])))
    assert np.asarray(ts.done)[:2].all() and not np.asarray(ts.done)[2:].any()
    np.testing.assert_array_equal(np.asarray(ts.reward), [-1, -0.5, 0, 1])


def test_normalize_obs_moments_match_manual():
    spec = token_spec()
    t = NormalizeObs(clip=None)
    state = t.init(spec, 4)
    rng = np.random.default_rng(0)
    xs = [rng.normal(size=(4, 64)).astype(np.float32) for _ in range(3)]
    for x in xs:
        state, ts = t.apply(state, block_ts(jnp.asarray(x)), spec)
    cat = np.concatenate(xs, axis=0)
    np.testing.assert_allclose(np.asarray(state["mean"]), cat.mean(0),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(state["m2"]) / float(state["count"]),
                               cat.var(0), rtol=1e-4, atol=1e-6)
    # the last block was normalized by the running moments incl. itself
    expect = (xs[-1] - cat.mean(0)) / np.sqrt(cat.var(0) + 1e-8)
    np.testing.assert_allclose(np.asarray(ts.obs), expect,
                               rtol=1e-3, atol=1e-4)


# --------------------------------------------------------------------- #
# Atari golden pins: dynamics bitwise-unchanged by the raw-frame
# refactor; the default in-engine stack output is pinned
# --------------------------------------------------------------------- #
GOLDEN_ATARI = np.load(os.path.join(HERE, "golden_atari_stream.npz"))


def atari_default_stream(steps=32, n=4):
    pool = make("Pong-v5", num_envs=n, seed=SEED)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    recs = []
    for t in range(steps):
        i = np.asarray(ts.env_id)
        a = jnp.asarray(((i * 3 + t) % 6).astype(np.int32))
        ps, ts = step(ps, a, ts.env_id)
        recs.append((np.asarray(ts.env_id), np.asarray(ts.reward),
                     np.asarray(ts.done), np.asarray(ts.step_cost),
                     np.asarray(ts.obs)))
    return [np.stack(x) for x in zip(*recs)]


def test_atari_golden_stream():
    """reward/done/cost captured PRE-refactor (stacked-in-env AtariLike)
    must be bitwise-reproduced by the raw-frame env + in-engine
    FrameStack default; the stacked obs is pinned against the golden
    captured when the pipeline shipped.  Compared lane by lane: the
    golden's one-shard sync blocks came in priority order, a sync block
    now comes in env-id order."""
    got = by_lane(*atari_default_stream())
    want = by_lane(*(GOLDEN_ATARI[k] for k in ("ids", "rew", "done", "cost",
                                               "obs_stack")))
    for name, g, w in zip(("ids", "rew", "done", "cost", "obs"), got, want):
        np.testing.assert_array_equal(g, w, err_msg=name)


def test_in_engine_stack_equals_python_wrapper():
    """The EnvPool claim itself: the in-engine pipeline must emit
    exactly what a host-side Python wrapper stack over the raw stream
    would — preprocessing placement changes cost, never semantics."""
    raw_pool = make("Pong-v5", num_envs=4, seed=SEED, transforms=[])
    wrapper = TransformPipeline(
        [FrameStack(4)], raw_pool.spec
    )
    tf_state = wrapper.np_init(4)
    ps, ts = raw_pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(raw_pool.step)
    stacked = []
    for t in range(8):
        i = np.asarray(ts.env_id)
        out = {"obs": np.asarray(ts.obs), "done": np.asarray(ts.done),
               "env_id": i}
        tf_state, out = wrapper.np_apply(tf_state, out)
        stacked.append(out["obs"][np.argsort(i)])
        a = jnp.asarray(((i * 3 + t) % 6).astype(np.int32))
        ps, ts = step(ps, a, ts.env_id)

    pool = make("Pong-v5", num_envs=4, seed=SEED)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    for t in range(8):
        i = np.asarray(ts.env_id)
        np.testing.assert_array_equal(
            np.asarray(ts.obs)[np.argsort(i)], stacked[t],
            err_msg=f"in-engine vs wrapper stack diverges at step {t}",
        )
        a = jnp.asarray(((i * 3 + t) % 6).astype(np.int32))
        ps, ts = step(ps, a, ts.env_id)


# --------------------------------------------------------------------- #
# engine conformance: transformed streams bitwise across engines
# --------------------------------------------------------------------- #
PIPE = [FrameStack(4), RewardClip(), ObsCast(np.float32, scale=1 / 255)]


def pong_device(engine, steps=5, n=4, **kw):
    pool = make("Pong-v5", num_envs=n, engine=engine, seed=SEED,
                transforms=PIPE, **kw)
    assert pool.spec.obs_spec.shape == (4, 84, 84)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    recs = []
    for t in range(steps):
        i = np.asarray(ts.env_id)
        o = np.argsort(i)
        recs.append((i[o], np.asarray(ts.reward)[o], np.asarray(ts.obs)[o],
                     np.asarray(ts.done)[o]))
        ps, ts = step(ps, jnp.asarray(((i * 3 + t) % 6).astype(np.int32)),
                      ts.env_id)
    return recs


def pong_host(engine, steps=5, n=4, **kw):
    pool = make("Pong-v5", num_envs=n, engine=engine, seed=SEED,
                transforms=PIPE, **kw)
    assert pool.spec.obs_spec.shape == (4, 84, 84)
    try:
        if hasattr(pool, "async_reset"):
            pool.async_reset()
            out = pool.recv()
        else:
            out = pool.reset()
        recs = []
        for t in range(steps):
            i = np.asarray(out["env_id"])
            o = np.argsort(i)
            recs.append((i[o], np.asarray(out["reward"])[o],
                         np.asarray(out["obs"])[o],
                         np.asarray(out["done"])[o]))
            out = pool.step(((i * 3 + t) % 6).astype(np.int32), i)
        return recs
    finally:
        if hasattr(pool, "close"):
            pool.close()


def test_transformed_streams_bitwise_across_engines():
    """device == device-sharded == thread == forloop, step for step,
    bitwise — the deterministic transforms (stack/clip/cast) preserve
    engine conformance exactly (numpy mirror == fused device path)."""
    ref = pong_device("device")
    for engine, run in [
        ("device-sharded", lambda: pong_device("device-sharded",
                                               num_shards=1)),
        ("thread", lambda: pong_host("thread", num_threads=2)),
        ("forloop", lambda: pong_host("forloop")),
    ]:
        got = run()
        for t, (a, b) in enumerate(zip(ref, got)):
            for name, x, y in zip(("ids", "reward", "obs", "done"), a, b):
                np.testing.assert_array_equal(
                    x, y, err_msg=f"{engine} {name} diverges at step {t}"
                )


def test_async_and_masked_transformed_streams_match_sync():
    """Per-lane transform state must follow each lane through async
    serving: per-env transformed streams under async == sync."""
    tfs = [FrameStack(2), ObsCast(np.float32, scale=0.5)]

    def run(engine, m):
        pool = make("TokenCopy-v0", num_envs=8, batch_size=m, engine=engine,
                    seed=SEED, transforms=tfs)
        ps, ts = pool.reset(jax.random.PRNGKey(SEED))
        step = jax.jit(pool.step)
        counts = np.zeros(8, int)
        streams: dict[int, list] = {i: [] for i in range(8)}
        for _ in range(16):
            ids = np.asarray(ts.env_id)
            obs = np.asarray(ts.obs)
            rew = np.asarray(ts.reward)
            for j, e in enumerate(ids):
                streams[int(e)].append((rew[j], obs[j]))
            a = jnp.asarray((counts[ids] * 7 + ids) % 256, jnp.int32)
            counts[ids] += 1
            ps, ts = step(ps, a, ts.env_id)
        return streams

    sync = run("device", None)
    streams = run("device", 4)
    compared = 0
    for e in range(8):
        n = min(len(sync[e]), len(streams[e]))
        compared += n
        for k in range(n):
            np.testing.assert_array_equal(
                sync[e][k][0], streams[e][k][0],
                err_msg=f"async reward stream env {e} serve {k}")
            np.testing.assert_array_equal(
                sync[e][k][1], streams[e][k][1],
                err_msg=f"async obs stream env {e} serve {k}")
    assert compared > 0


def test_normalize_obs_device_vs_thread():
    """NormalizeObs streams agree across device and host engines to f32
    reduction-order tolerance (the only non-bitwise transform)."""

    def dev(steps=5):
        pool = make("AntNorm-v3", num_envs=4, seed=SEED)
        ps, ts = pool.reset(jax.random.PRNGKey(SEED))
        step = jax.jit(pool.step)
        recs, variances = [], []
        for t in range(steps):
            i = np.asarray(ts.env_id)
            recs.append(np.asarray(ts.obs)[np.argsort(i)])
            # the running variance that normalized THIS block (the
            # moments on ps include the block, per the apply contract)
            # — identifies the degenerate dims whose normalizer is
            # sqrt(eps)-sized at this step
            m = jax.tree.map(np.asarray, ps.tf_state[0])
            variances.append(np.maximum(m["m2"][0] / m["count"][0], 0.0))
            a = jnp.asarray(np.sin(i[:, None] * 0.7 + t * 0.3
                                   + np.arange(8)[None, :]), jnp.float32)
            ps, ts = step(ps, a, ts.env_id)
        return recs, variances

    def host(steps=5):
        pool = make("AntNorm-v3", num_envs=4, engine="thread", seed=SEED,
                    num_threads=2)
        try:
            pool.async_reset()
            out = pool.recv()
            recs = []
            for t in range(steps):
                i = np.asarray(out["env_id"])
                recs.append(np.asarray(out["obs"])[np.argsort(i)])
                a = np.sin(i[:, None] * 0.7 + t * 0.3
                           + np.arange(8)[None, :]).astype(np.float32)
                out = pool.step(a, i)
            return recs
        finally:
            pool.close()

    recs, variances = dev()
    # well-conditioned dims keep the tight tolerance; degenerate dims
    # (running variance ~ 0 at that step, so the normalizer is
    # sqrt(eps)-sized and a single f32 reassociation ulp in m2 — jit
    # fusion vs the numpy mirror's op order — amplifies ~1e4x into the
    # output) get a proportionally looser absolute bound
    checked_loose = False
    for t, (a, b, var) in enumerate(zip(recs, host(), variances)):
        tight = var > 1e-6
        assert tight.any()
        checked_loose |= bool((~tight).any())
        np.testing.assert_allclose(a[:, tight], b[:, tight],
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=f"step {t} (well-conditioned)")
        np.testing.assert_allclose(a[:, ~tight], b[:, ~tight],
                                   rtol=1e-4, atol=1e-3,
                                   err_msg=f"step {t} (degenerate-var)")
    assert checked_loose   # the degenerate regime was actually exercised


def test_transform_mesh_conformance_subprocess():
    """Mesh sizes {1, 2, 4}: transformed Pong streams bitwise-identical,
    NormalizeObs moments mesh-size-invariant, shard copies identical
    (runs in a subprocess with 4 simulated host devices)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_transform_mesh_check.py"), "4"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ,
             "PYTHONPATH": os.path.join(os.path.dirname(HERE), "src")},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4
    assert res["pong_stream_bitwise_all_meshes"]
    assert res["classic_stream_bitwise_all_meshes"]
    assert res["norm_shard_copies_identical"]
    assert res["norm_moments_mesh_invariant"]
    assert res["norm_stream_mesh_close"]


# --------------------------------------------------------------------- #
# satellites: sched_patience plumbing + thread cost EMA
# --------------------------------------------------------------------- #
def test_sched_patience_threads_through_make():
    pool = make("TokenSkew-v0", num_envs=8, batch_size=4,
                engine="device-sharded", num_shards=1,
                schedule="hierarchical", sched_patience=2.5)
    assert pool.scheduler.patience == 2.5
    # still serves valid unique batches
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    for t in range(6):
        ids = np.asarray(ts.env_id)
        assert len(set(ids.tolist())) == 4
        ps, ts = step(ps, jnp.asarray((ids * 7 + t) % 256, jnp.int32),
                      ts.env_id)
    with pytest.raises(ValueError):
        get_scheduler("fifo", patience=0.0)


def test_thread_cost_ema():
    from repro.core.host_pool import ThreadEnvPool

    with pytest.raises(ValueError):
        make("TokenCopy-v0", num_envs=2, engine="thread",
             cost_ema_alpha=0.0)

    # alpha=1.0 (default): estimator == last observed cost, the classic
    pool = make("TokenCopy-v0", num_envs=4, engine="thread", seed=SEED,
                num_threads=2, schedule="sjf")
    try:
        pool.async_reset()
        out = pool.recv()
        for t in range(3):
            ids = np.asarray(out["env_id"])
            out = pool.step(((ids * 7 + t) % 256).astype(np.int32), ids)
        ids = np.asarray(out["env_id"])
        np.testing.assert_array_equal(
            pool._est_cost[ids], np.maximum(out["step_cost"], 1))
    finally:
        pool.close()

    # alpha=0.5: estimator is the EMA of observed costs
    pool = make("TokenCopy-v0", num_envs=4, engine="thread", seed=SEED,
                num_threads=2, schedule="sjf", cost_ema_alpha=0.5)
    try:
        expect = np.ones(4, np.float32)
        pool.async_reset()
        out = pool.recv()
        ids = np.asarray(out["env_id"])
        expect[ids] = 0.5 * np.maximum(out["step_cost"], 1) + 0.5 * expect[ids]
        for t in range(3):
            ids = np.asarray(out["env_id"])
            out = pool.step(((ids * 7 + t) % 256).astype(np.int32), ids)
            ids = np.asarray(out["env_id"])
            expect[ids] = (0.5 * np.maximum(out["step_cost"], 1)
                           + 0.5 * expect[ids])
        np.testing.assert_allclose(pool._est_cost, expect, rtol=1e-6)
    finally:
        pool.close()
