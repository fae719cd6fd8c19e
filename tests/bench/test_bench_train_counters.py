"""The ``train`` cells' operation and byte counters (``bench/metrics``)
against XLA's ``cost_analysis()`` of the policy's forward pass and the
image kernels' array shapes, at a small size on the CPU, and their
readers on a synthetic trace."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import catalog  # noqa: E402
from bench.lib.trace import Event, Trace  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_cnn_flop_counter_matches_xla():
    import repro
    from repro.rl.nets import ActorCritic

    mfu = catalog.metric("mfu_pct.train")
    pool = repro.make("PongClassic-v5", num_envs=2, seed=0)
    net = ActorCritic(pool.spec)
    params = net.init(jax.random.PRNGKey(0))
    b = 8
    obs = jnp.zeros((b,) + pool.spec.obs_spec.shape, jnp.uint8)
    c = jax.jit(net.forward).lower(params, obs).compile().cost_analysis()
    c = c[0] if isinstance(c, list) else c
    want = mfu.cnn_forward_flops(pool.spec.obs_spec.shape, net.act_dim)
    # XLA also counts the bias adds, ReLUs and the 1/255 scaling
    assert want == pytest.approx(c["flops"] / b, rel=0.02)
    assert want == 2 * (20 * 20 * 32 * 8 * 8 * 4 + 9 * 9 * 64 * 4 * 4 * 32
                        + 7 * 7 * 64 * 3 * 3 * 64 + 3136 * 512 + 512 * 7)


def test_image_byte_count_matches_the_kernels_shapes():
    from repro.kernels.image import ops

    img = catalog.metric("image_kernels_roofline.train")
    n = 4
    xs = jax.ShapeDtypeStruct((n,), jnp.float32)
    rgb = jax.eval_shape(lambda a, b, c, d: ops.pong_render(a, b, c, d),
                         xs, xs, xs, xs)
    gray = jax.eval_shape(lambda x: ops.grayscale(x), rgb)
    small = jax.eval_shape(lambda x: ops.resize(x, 84, 84, "area"), gray)
    size = lambda s: int(np.prod(s.shape)) * s.dtype.itemsize  # noqa: E731
    moved = size(rgb) + (size(rgb) + size(gray)) + (size(gray) + size(small))
    assert img.LANE_BYTES * n == moved


def _trace(names_us, window_us):
    ops = [Event(f"{name}.1", t0 * 1e3, dur * 1e3)
           for name, t0, dur in names_us]
    return Trace(ops={"/device:TPU:0": ops}, modules={}, host=[],
                 window=(0.0, window_us * 1e3))


def test_train_readers_on_a_synthetic_trace():
    img = catalog.metric("image_kernels_roofline.train")
    mfu = catalog.metric("mfu_pct.train")
    idle = catalog.metric("device_idle_pct.train")
    tr = _trace([("pong_render", 0, 100), ("grayscale", 100, 50),
                 ("resize", 150, 50), ("convolution", 200, 600)], 1000)
    counts = {"updates": 2, "served": 1000, "num_steps": 8, "num_envs": 4,
              "epochs": 4, "obs_shape": (4, 84, 84), "num_actions": 6,
              "peaks": PEAKS}
    assert img.read(tr, counts) == pytest.approx(
        100 * 1000 * img.LANE_BYTES / 819e9 / 200e-6)
    per_update = (32 + 4 + 3 * 4 * 32) * mfu.cnn_forward_flops((4, 84, 84), 6)
    assert mfu.read(tr, counts) == pytest.approx(
        100 * 2 * per_update / (1e-3 * 197e12))
    assert idle.read(tr, counts) == pytest.approx(20.0)
    assert img.read(tr, dict(counts, served=0)) is None
    assert mfu.read(tr, dict(counts, updates=0)) is None
