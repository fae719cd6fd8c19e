"""Batched image preprocessing as Pallas TPU kernels.

The CuLE/EnvPool argument made concrete: the classic Atari observation
path (native 210 x 160 RGB render -> grayscale -> 84 x 84) runs over the
whole served SoA block as fused kernels, so frames never leave the
accelerator between the emulator and the agent.

All math is the integer fixed-point definition from ``ref.py`` (see its
module docstring for the exactness argument): grayscale is int32 VPU
arithmetic over per-channel planes; resize is two small f32 matmuls per
image (MXU-friendly, integer-exact because every product and partial
sum stays below 2^24) with integer rounding shifts between; the render
is compares/selects over broadcasted iota grids.  Interpret mode
(``interpret=True``) validates every kernel on CPU bitwise against the
jnp reference; TPU is the lowering target.

Layout notes: channel planes are split OUTSIDE the kernels (a minor dim
of 3 tiles terribly on the VPU; W = 160/84 in the lane dim is fine), and
kernels carry int32/f32 — the uint8 casts live in ``ops.py`` so the
stored dtypes stay tiling-friendly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.image.ref import (
    GRAY_B,
    GRAY_G,
    GRAY_R,
    GRAY_SHIFT,
    RESIZE_SHIFT,
    RGB_H,
    RGB_W,
    _pong_plane_values,
    resize_weights,
)


def _pad_batch(x: jnp.ndarray, block_n: int) -> jnp.ndarray:
    """Pad the leading dim up to a multiple of ``block_n``."""
    n = x.shape[0]
    pad = (-n) % block_n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x


# ---------------------------------------------------------------------- #
# grayscale
# ---------------------------------------------------------------------- #
def _grayscale_kernel(r_ref, g_ref, b_ref, o_ref):
    y = (GRAY_R * r_ref[...] + GRAY_G * g_ref[...] + GRAY_B * b_ref[...]
         + (1 << (GRAY_SHIFT - 1))) >> GRAY_SHIFT
    o_ref[...] = y.astype(o_ref.dtype)


def grayscale_batch(rgb: jnp.ndarray, *, block_n: int = 4,
                    interpret: bool = True) -> jnp.ndarray:
    """(N, H, W, 3) uint8 -> (N, H, W) uint8 via the Pallas luma kernel.

    Four int32 planes of ``block_n`` 210 x 160 screens, double-buffered,
    must fit the 16 MiB of scoped VMEM: 8 needs 16.7 MiB, 4 fits."""
    n, h, w = rgb.shape[0], rgb.shape[1], rgb.shape[2]
    block_n = max(1, min(block_n, n))
    planes = [
        _pad_batch(rgb[..., c].astype(jnp.int32), block_n) for c in range(3)
    ]
    np_ = planes[0].shape[0]
    spec = pl.BlockSpec((block_n, h, w), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        _grayscale_kernel,
        grid=(np_ // block_n,),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((np_, h, w), jnp.int32),
        interpret=interpret,
    )(*planes)
    return out[:n].astype(jnp.uint8)


# ---------------------------------------------------------------------- #
# resize (separable fixed-point matmuls; one image per grid step)
# ---------------------------------------------------------------------- #
def _resize_kernel(x_ref, a_ref, bt_ref, o_ref):
    hp = lax.Precision.HIGHEST
    x = x_ref[0].astype(jnp.float32)              # (H, W)
    t = jnp.dot(a_ref[...], x, precision=hp)      # (out_h, W)
    t = ((t.astype(jnp.int32) + (1 << (RESIZE_SHIFT - 1))) >> RESIZE_SHIFT
         ).astype(jnp.float32)
    o = jnp.dot(t, bt_ref[...], precision=hp)     # (out_h, out_w)
    o = (o.astype(jnp.int32) + (1 << (RESIZE_SHIFT - 1))) >> RESIZE_SHIFT
    o_ref[...] = o[None].astype(o_ref.dtype)


def resize_batch(img: jnp.ndarray, out_h: int, out_w: int,
                 method: str = "area", *,
                 interpret: bool = True) -> jnp.ndarray:
    """(N, H, W) uint8 -> (N, out_h, out_w) uint8 via the Pallas
    separable-resample kernel (ref.py's weight matrices)."""
    n, h, w = img.shape
    a = jnp.asarray(resize_weights(h, out_h, method), jnp.float32)
    bt = jnp.asarray(resize_weights(w, out_w, method).T, jnp.float32)
    out = pl.pallas_call(
        _resize_kernel,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h, w), lambda i: (i, 0, 0)),
            pl.BlockSpec((out_h, h), lambda i: (0, 0)),
            pl.BlockSpec((w, out_w), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, out_h, out_w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, out_h, out_w), jnp.int32),
        interpret=interpret,
    )(img.astype(jnp.int32), a, bt)
    return out.astype(jnp.uint8)


# ---------------------------------------------------------------------- #
# crop (static window copy)
# ---------------------------------------------------------------------- #
def _crop_kernel(x_ref, o_ref, *, top: int, left: int, height: int,
                 width: int):
    o_ref[...] = x_ref[:, top:top + height, left:left + width]


def crop_batch(img: jnp.ndarray, top: int, left: int, height: int,
               width: int, *, block_n: int = 8,
               interpret: bool = True) -> jnp.ndarray:
    """(N, H, W) uint8 -> (N, height, width) uint8 static-window crop."""
    n, h, w = img.shape
    block_n = max(1, min(block_n, n))
    x = _pad_batch(img.astype(jnp.int32), block_n)
    out = pl.pallas_call(
        functools.partial(_crop_kernel, top=top, left=left,
                          height=height, width=width),
        grid=(x.shape[0] // block_n,),
        in_specs=[pl.BlockSpec((block_n, h, w), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((block_n, height, width),
                               lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], height, width),
                                       jnp.int32),
        interpret=interpret,
    )(x)
    return out[:n].astype(jnp.uint8)


# ---------------------------------------------------------------------- #
# the batched Pong RGB render (one fused render per served block)
# ---------------------------------------------------------------------- #
def _render_kernel(bx_ref, by_ref, py_ref, ey_ref, r_ref, g_ref, b_ref):
    bn = r_ref.shape[0]
    # Mosaic builds integer iotas only; the f32 grid is exact
    ys = lax.broadcasted_iota(jnp.int32, (bn, RGB_H, RGB_W), 1
                              ).astype(jnp.float32)
    xs = lax.broadcasted_iota(jnp.int32, (bn, RGB_H, RGB_W), 2
                              ).astype(jnp.float32)
    r, g, b = _pong_plane_values(
        ys, xs, bx_ref[...], by_ref[...], py_ref[...], ey_ref[...],
    )
    r_ref[...] = r.astype(r_ref.dtype)
    g_ref[...] = g.astype(g_ref.dtype)
    b_ref[...] = b.astype(b_ref.dtype)


def pong_render_batch(ball_x: jnp.ndarray, ball_y: jnp.ndarray,
                      paddle_y: jnp.ndarray, enemy_y: jnp.ndarray, *,
                      block_n: int = 8,
                      interpret: bool = True) -> jnp.ndarray:
    """(N,) game-state scalars -> (N, 210, 160, 3) uint8: the whole
    served block's screens in one fused render."""
    n = ball_x.shape[0]
    block_n = max(1, min(block_n, n))
    # the per-lane scalars enter as (N, 1, 1): a block whose two minor
    # dims are the array's own tiles for any block_n, where a rank-1
    # (block_n,) block must be a multiple of 128
    ins = [
        _pad_batch(jnp.asarray(v, jnp.float32), block_n)[:, None, None]
        for v in (ball_x, ball_y, paddle_y, enemy_y)
    ]
    np_ = ins[0].shape[0]
    sspec = pl.BlockSpec((block_n, 1, 1), lambda i: (i, 0, 0))
    pspec = pl.BlockSpec((block_n, RGB_H, RGB_W), lambda i: (i, 0, 0))
    shape = jax.ShapeDtypeStruct((np_, RGB_H, RGB_W), jnp.int32)
    r, g, b = pl.pallas_call(
        _render_kernel,
        grid=(np_ // block_n,),
        in_specs=[sspec] * 4,
        out_specs=[pspec] * 3,
        out_shape=[shape] * 3,
        interpret=interpret,
    )(*ins)
    return jnp.stack([r[:n], g[:n], b[:n]], axis=-1).astype(jnp.uint8)
