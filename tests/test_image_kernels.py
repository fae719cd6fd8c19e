"""kernels/image: backend tri-identity (pallas-interpret == reference ==
jnp/vmap fallback, bitwise, incl. odd/non-divisible sizes), the numpy
mirrors, the Grayscale/Resize/Crop transforms, the batched Atari RGB
render, and the PongClassic-v5 golden dynamics + engine conformance."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.registry import make
from repro.core.transforms import (
    Crop,
    FrameStack,
    Grayscale,
    Resize,
    TransformPipeline,
)
from repro.envs.atari_like import AtariLike, AtariLikeBatch
from repro.kernels.backend import BACKENDS, resolve_backend
from repro.kernels.image import kernel, ops, ref

from _lanes import by_lane

SEED = 0
# every off-TPU backend must agree bitwise in DIRECT calls (auto
# resolves to vmap here; keep it in the sweep so the default is pinned)
SWEEP = ("reference", "pallas-interpret", "vmap", "auto")


def rand_u8(rng, shape):
    return rng.integers(0, 256, shape, np.uint8)


# --------------------------------------------------------------------- #
# shared backend machinery (satellite: stated once, consumed twice)
# --------------------------------------------------------------------- #
def test_shared_backend_module():
    from repro.kernels import backend as shared
    from repro.kernels.env_step import ops as env_ops

    # env_step re-exports the single shared implementation
    assert env_ops.resolve_backend is shared.resolve_backend
    assert env_ops.BACKENDS is shared.BACKENDS
    assert ops.resolve_backend is shared.resolve_backend
    assert resolve_backend("reference") == "reference"
    assert resolve_backend() in ("pallas", "vmap")
    with pytest.raises(ValueError):
        resolve_backend("cuda")
    assert set(SWEEP) <= set(BACKENDS)


# --------------------------------------------------------------------- #
# grayscale
# --------------------------------------------------------------------- #
def test_grayscale_backends_bitwise():
    rng = np.random.default_rng(SEED)
    for shape in ((5, 37, 29, 3), (3, 210, 160, 3), (1, 1, 1, 3)):
        rgb = jnp.asarray(rand_u8(rng, shape))
        outs = [np.asarray(ops.grayscale(rgb, backend=b)) for b in SWEEP]
        for b, o in zip(SWEEP[1:], outs[1:]):
            np.testing.assert_array_equal(outs[0], o, err_msg=f"{b} {shape}")
        # numpy mirror (the host-engine path) is bitwise too
        np.testing.assert_array_equal(
            outs[0], ref.grayscale_np(np.asarray(rgb))
        )
        assert outs[0].dtype == np.uint8 and outs[0].shape == shape[:-1]


def test_grayscale_fixed_point_properties():
    # coefficients sum to exactly 2^15: flat fields are preserved
    assert ref.GRAY_R + ref.GRAY_G + ref.GRAY_B == 1 << ref.GRAY_SHIFT
    for v in (0, 1, 77, 254, 255):
        flat = jnp.full((2, 4, 6, 3), v, jnp.uint8)
        assert np.all(np.asarray(ops.grayscale(flat, backend="reference"))
                      == v)


# --------------------------------------------------------------------- #
# resize
# --------------------------------------------------------------------- #
RESIZE_CASES = [
    (210, 160, 84, 84),   # the classic ALE downsample
    (37, 29, 17, 13),     # odd sizes, non-divisible edge rows
    (10, 7, 3, 5),        # non-divisible down + up in one call
    (8, 8, 16, 16),       # pure upsample
]


@pytest.mark.parametrize("method", ref.RESIZE_METHODS)
def test_resize_backends_bitwise(method):
    rng = np.random.default_rng(SEED)
    for h, w, oh, ow in RESIZE_CASES:
        img = jnp.asarray(rand_u8(rng, (3, h, w)))
        outs = [
            np.asarray(ops.resize(img, oh, ow, method, backend=b))
            for b in SWEEP
        ]
        for b, o in zip(SWEEP[1:], outs[1:]):
            np.testing.assert_array_equal(
                outs[0], o, err_msg=f"{method} {b} {(h, w, oh, ow)}"
            )
        np.testing.assert_array_equal(
            outs[0], ref.resize_np(np.asarray(img), oh, ow, method)
        )
        assert outs[0].shape == (3, oh, ow) and outs[0].dtype == np.uint8


@pytest.mark.parametrize("method", ref.RESIZE_METHODS)
def test_resize_weight_rows_sum_exact(method):
    for in_s, out_s in ((210, 84), (160, 84), (29, 13), (7, 5), (8, 16)):
        wm = ref.resize_weights(in_s, out_s, method)
        assert wm.shape == (out_s, in_s)
        np.testing.assert_array_equal(
            wm.sum(axis=1), np.full(out_s, 1 << ref.RESIZE_SHIFT)
        )
        assert (wm >= 0).all()
    # exact row sums mean flat fields pass through every backend exactly
    flat = jnp.full((2, 33, 21), 77, jnp.uint8)
    for b in SWEEP:
        assert np.all(np.asarray(ops.resize(flat, 9, 6, method, backend=b))
                      == 77)


def test_resize_rejects_bad_method():
    with pytest.raises(ValueError):
        ref.resize_weights(10, 5, "lanczos")


# --------------------------------------------------------------------- #
# crop
# --------------------------------------------------------------------- #
def test_crop_backends_bitwise():
    rng = np.random.default_rng(SEED)
    img = jnp.asarray(rand_u8(rng, (4, 31, 23)))
    outs = [
        np.asarray(ops.crop(img, 5, 2, 17, 19, backend=b)) for b in SWEEP
    ]
    for b, o in zip(SWEEP[1:], outs[1:]):
        np.testing.assert_array_equal(outs[0], o, err_msg=b)
    np.testing.assert_array_equal(
        outs[0], np.asarray(img)[:, 5:22, 2:21]
    )
    with pytest.raises(ValueError):
        ops.crop(img, 20, 2, 17, 19)


# --------------------------------------------------------------------- #
# the batched Pong RGB render
# --------------------------------------------------------------------- #
def test_pong_render_backends_bitwise():
    rng = np.random.default_rng(SEED)
    n = 6
    bx = rng.uniform(0, 84, n).astype(np.float32)
    by = rng.uniform(0, 84, n).astype(np.float32)
    py = rng.uniform(6, 78, n).astype(np.float32)
    ey = rng.uniform(6, 78, n).astype(np.float32)
    outs = [
        np.asarray(ops.pong_render(bx, by, py, ey, backend=b))
        for b in SWEEP
    ]
    for b, o in zip(SWEEP[1:], outs[1:]):
        np.testing.assert_array_equal(outs[0], o, err_msg=b)
    # the batched render == vmap of the per-lane observe form, bitwise
    per_lane = jax.vmap(ref.pong_render_reference)(bx, by, py, ey)
    np.testing.assert_array_equal(outs[0], np.asarray(per_lane))
    assert outs[0].shape == (n, ref.RGB_H, ref.RGB_W, 3)
    # background + all three sprite colors actually appear
    px = outs[0].reshape(-1, 3)
    for color in (ref.PONG_BG, ref.PONG_PLAYER, ref.PONG_ENEMY,
                  ref.PONG_BALL):
        assert (px == np.array(color)).all(axis=1).any(), color


def _direct_call(name, rng, n):
    """(the kernel's direct call, its arguments, ref.py's answer)."""
    img = jnp.asarray(rand_u8(rng, (n, 37, 29)))
    if name == "grayscale":
        rgb = jnp.asarray(rand_u8(rng, (n, 37, 29, 3)))
        return (lambda x: kernel.grayscale_batch(x, block_n=4), (rgb,),
                ref.grayscale_reference(rgb))
    if name == "resize":
        return (lambda x: kernel.resize_batch(x, 17, 13), (img,),
                ref.resize_reference(img, 17, 13, "area"))
    if name == "crop":
        return (lambda x: kernel.crop_batch(x, 5, 2, 17, 19, block_n=4),
                (img,), ref.crop_reference(img, 5, 2, 17, 19))
    state = [rng.uniform(lo, hi, n).astype(np.float32)
             for lo, hi in ((0, 84), (0, 84), (6, 78), (6, 78))]
    return (lambda *v: kernel.pong_render_batch(*v, block_n=4), state,
            ref.pong_render_reference(*state))


@pytest.mark.parametrize("name", ["crop", "grayscale", "pong_render",
                                  "resize"])
def test_kernel_direct_call_is_uint8(name):
    """Called directly in interpret mode, at a batch (5) that is no
    multiple of the block (4), each kernel reads and writes its images
    as uint8 at its own boundary and returns ref.py's answer bitwise."""
    fn, args, want = _direct_call(name, np.random.default_rng(SEED), 5)
    got = fn(*args)
    assert got.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    (call,) = [e for e in jax.make_jaxpr(fn)(*args).eqns
               if e.primitive.name == "pallas_call"]
    images = [v.aval for v in call.invars + call.outvars
              if v.aval.ndim == 3 and v.aval.shape[-1] > 1]
    assert images and {a.dtype for a in images} == {np.dtype(np.uint8)}


def test_atari_rgb_pipeline_composes():
    """RGB screen -> grayscale -> area resize to 84x84: the full classic
    path through direct kernel calls, every backend bitwise."""
    rng = np.random.default_rng(SEED)
    n = 4
    bx = rng.uniform(0, 84, n).astype(np.float32)
    by = rng.uniform(0, 84, n).astype(np.float32)
    py = rng.uniform(6, 78, n).astype(np.float32)
    ey = rng.uniform(6, 78, n).astype(np.float32)
    outs = []
    for b in ("reference", "pallas-interpret"):
        screens = ops.pong_render(bx, by, py, ey, backend=b)
        gray = ops.grayscale(screens, backend=b)
        outs.append(np.asarray(ops.resize(gray, 84, 84, "area", backend=b)))
    np.testing.assert_array_equal(outs[0], outs[1])
    assert outs[0].shape == (n, 84, 84)


# --------------------------------------------------------------------- #
# transforms: spec rules + device path == numpy mirror
# --------------------------------------------------------------------- #
def test_image_transform_spec_rules():
    spec = AtariLike(obs_mode="rgb").spec
    p = TransformPipeline(
        [Grayscale(), Resize(84, 84), FrameStack(4)], spec
    )
    assert p.out_spec.obs_spec.shape == (4, 84, 84)
    assert np.dtype(p.out_spec.obs_spec.dtype) == np.uint8
    c = TransformPipeline([Grayscale(), Crop(25, 0, 160, 160)], spec)
    assert c.out_spec.obs_spec.shape == (160, 160)
    # rule violations surface at construction, not at trace time
    gray_spec = AtariLike().spec                     # (84, 84) already
    with pytest.raises(ValueError):
        TransformPipeline([Grayscale()], gray_spec)  # no channel dim
    with pytest.raises(ValueError):
        TransformPipeline([Crop(80, 0, 10, 10)], gray_spec)  # OOB window
    with pytest.raises(ValueError):
        Resize(84, 84, method="lanczos")


def test_image_transforms_np_mirror_bitwise():
    from repro.core.specs import TimeStep

    rng = np.random.default_rng(SEED)
    m = 3
    spec = AtariLike(obs_mode="rgb").spec
    obs = rand_u8(rng, (m,) + spec.obs_spec.shape)
    z = jnp.zeros((m,), jnp.float32)
    f = jnp.zeros((m,), jnp.bool_)
    ts = TimeStep(obs=jnp.asarray(obs), reward=z, done=f, terminated=f,
                  truncated=f, env_id=jnp.arange(m, dtype=jnp.int32),
                  episode_return=z, episode_length=jnp.zeros((m,), jnp.int32),
                  step_cost=jnp.ones((m,), jnp.int32))
    pipe = TransformPipeline([Grayscale(), Resize(84, 84)], spec)
    blk, out_ts = pipe.apply(pipe.init(m), ts)
    tf = pipe.np_init(m)
    out = {"obs": obs, "reward": np.zeros(m, np.float32),
           "done": np.zeros(m, bool), "terminated": np.zeros(m, bool),
           "env_id": np.arange(m)}
    tf, out = pipe.np_apply(tf, out)
    np.testing.assert_array_equal(np.asarray(out_ts.obs), out["obs"])
    assert out["obs"].shape == (m, 84, 84)


# --------------------------------------------------------------------- #
# AtariLikeBatch: the fused render is the native batched view
# --------------------------------------------------------------------- #
def test_atari_batch_native_render_bitwise():
    env = AtariLike(obs_mode="rgb")
    benv = env.as_batch()
    assert isinstance(benv, AtariLikeBatch)
    keys = jax.random.split(jax.random.PRNGKey(SEED), 5)
    states = benv.v_init_state(keys)
    for backend in ("vmap", "reference", "pallas-interpret"):
        b = AtariLikeBatch(env, backend=backend)
        np.testing.assert_array_equal(
            np.asarray(b.v_observe(states)),
            np.asarray(jax.vmap(env.observe)(states)),
            err_msg=backend,
        )
    # gray84 mode keeps the generic vmap observe (classic path untouched)
    g = AtariLike().as_batch()
    gs = g.v_init_state(keys)
    assert np.asarray(g.v_observe(gs)).shape == (5, 84, 84)


# --------------------------------------------------------------------- #
# the golden pin: only the observation path changed
# --------------------------------------------------------------------- #
GOLDEN = np.load(__file__.replace("test_image_kernels.py",
                                  "golden_atari_stream.npz"))


def classic_stream(steps=32, n=4, engine="device", **kw):
    pool = make("PongClassic-v5", num_envs=n, seed=SEED, engine=engine, **kw)
    assert pool.spec.obs_spec.shape == (4, 84, 84)
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


def test_classic_pipeline_golden_dynamics():
    """The RGB render + in-engine image pipeline must reproduce the
    golden reward/done/cost streams bitwise: rendering is observe-only,
    so upgrading the observation path cannot perturb dynamics.  Compared
    lane by lane: the golden's one-shard sync blocks came in priority
    order, a sync block now comes in env-id order."""
    ids, rew, done, cost, obs = classic_stream()
    got = by_lane(ids, rew, done, cost)
    want = by_lane(*(GOLDEN[k] for k in ("ids", "rew", "done", "cost")))
    for name, g, w in zip(("ids", "rew", "done", "cost"), got, want):
        np.testing.assert_array_equal(g, w, err_msg=name)
    assert obs.shape == (32, 4, 4, 84, 84) and obs.dtype == np.uint8
    # the processed screen is not degenerate: sprites survive the
    # grayscale+resize (more than one luma level per frame)
    assert len(np.unique(obs[-1])) > 1


# --------------------------------------------------------------------- #
# engine conformance: device / sharded / thread / forloop, bitwise
# (mesh sizes {2, 4} run in tests/test_transforms.py's subprocess
# check — classic_stream_bitwise_all_meshes)
# --------------------------------------------------------------------- #
def classic_device_stream(engine, steps=5, n=4, **kw):
    """Pre-step recording (first record is the reset serve), matching
    the host pools' recv-first protocol below."""
    pool = make("PongClassic-v5", num_envs=n, seed=SEED, engine=engine, **kw)
    assert pool.spec.obs_spec.shape == (4, 84, 84)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    recs = []
    for t in range(steps):
        i = np.asarray(ts.env_id)
        o = np.argsort(i)
        recs.append((i[o], np.asarray(ts.reward)[o],
                     np.asarray(ts.done)[o], np.asarray(ts.obs)[o]))
        ps, ts = step(ps, jnp.asarray(((i * 3 + t) % 6).astype(np.int32)),
                      ts.env_id)
    return [np.stack(x) for x in zip(*recs)]


def classic_host_stream(engine, steps=5, n=4, **kw):
    pool = make("PongClassic-v5", num_envs=n, seed=SEED, engine=engine, **kw)
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
                         np.asarray(out["done"])[o],
                         np.asarray(out["obs"])[o]))
            out = pool.step(((i * 3 + t) % 6).astype(np.int32), i)
        return [np.stack(x) for x in zip(*recs)]
    finally:
        if hasattr(pool, "close"):
            pool.close()


def test_classic_streams_bitwise_across_engines():
    """Grayscale/Resize streams: device == device-sharded == thread ==
    forloop, step for step, bitwise — the integer fixed-point image ops
    keep the numpy mirror exactly equal to the fused device path."""
    steps = 5
    refs = classic_device_stream("device", steps=steps)
    for engine, run in [
        ("device-sharded",
         lambda: classic_device_stream("device-sharded", steps=steps,
                                       num_shards=1)),
        ("thread", lambda: classic_host_stream("thread", steps=steps,
                                               num_threads=2)),
        ("forloop", lambda: classic_host_stream("forloop", steps=steps)),
    ]:
        got = run()
        for name, x, y in zip(("ids", "rew", "done", "obs"), refs, got):
            np.testing.assert_array_equal(
                x, y, err_msg=f"{engine} {name} diverges"
            )
