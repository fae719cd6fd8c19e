"""jit'd public wrappers for the batched image kernels.

Backend selection follows the shared rule in ``kernels/backend.py``:
``auto`` is the compiled Pallas kernel on TPU and the jnp fallback
everywhere else; ``pallas-interpret`` and ``reference`` stay explicitly
selectable for kernel cross-checks.  Because this family's math is pure
integer fixed-point (``ref.py``), the ``vmap`` fallback and the packed
``reference`` are the SAME jnp form — there is no float-fusion ulp gap
for a structurally different body to expose, so all backends are
bit-identical (pinned by tests/test_image_kernels.py), not just the
direct-call pairs.

Every op accepts arbitrary leading batch dims over the image dims and
preserves them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import (           # noqa: F401
    BACKENDS,
    default_backend,
    resolve_backend,
)
from repro.kernels.image.kernel import (
    crop_batch,
    grayscale_batch,
    pong_render_batch,
    resize_batch,
)
from repro.kernels.image.ref import (
    check_crop,
    crop_reference,
    grayscale_reference,
    pong_render_reference,
    resize_reference,
)


def _use_kernel(backend: str) -> bool:
    return resolve_backend(backend) in ("pallas", "pallas-interpret")


def _interpret(backend: str) -> bool:
    return resolve_backend(backend) == "pallas-interpret"


def _flatten_to(x: jnp.ndarray, image_ndim: int):
    """Collapse leading batch dims so the kernel sees (N, *image)."""
    lead = x.shape[:x.ndim - image_ndim]
    flat = x.reshape((-1,) + x.shape[x.ndim - image_ndim:])
    return flat, lead


@functools.partial(jax.jit, static_argnames=("backend", "block_n"))
def grayscale(rgb: jnp.ndarray, *, backend: str = "auto",
              block_n: int = 8) -> jnp.ndarray:
    """(..., H, W, 3) uint8 RGB -> (..., H, W) uint8 ALE luma."""
    if rgb.ndim < 3 or rgb.shape[-1] != 3:
        raise ValueError(f"grayscale wants (..., H, W, 3); got {rgb.shape}")
    if not _use_kernel(backend):
        return grayscale_reference(rgb)
    flat, lead = _flatten_to(rgb, 3)
    out = grayscale_batch(flat, block_n=block_n,
                          interpret=_interpret(backend))
    return out.reshape(lead + out.shape[1:])


@functools.partial(
    jax.jit, static_argnames=("out_h", "out_w", "method", "backend")
)
def resize(img: jnp.ndarray, out_h: int, out_w: int,
           method: str = "area", *, backend: str = "auto") -> jnp.ndarray:
    """(..., H, W) uint8 -> (..., out_h, out_w) uint8 fixed-point
    resampling (``area`` or ``bilinear``)."""
    if img.ndim < 2:
        raise ValueError(f"resize wants (..., H, W); got {img.shape}")
    if not _use_kernel(backend):
        return resize_reference(img, out_h, out_w, method)
    flat, lead = _flatten_to(img, 2)
    out = resize_batch(flat, out_h, out_w, method,
                       interpret=_interpret(backend))
    return out.reshape(lead + out.shape[1:])


@functools.partial(
    jax.jit,
    static_argnames=("top", "left", "height", "width", "backend", "block_n"),
)
def crop(img: jnp.ndarray, top: int, left: int, height: int, width: int,
         *, backend: str = "auto", block_n: int = 8) -> jnp.ndarray:
    """Static-window crop of the trailing (H, W) dims."""
    if img.ndim < 2:
        raise ValueError(f"crop wants (..., H, W); got {img.shape}")
    check_crop(img.shape[-2], img.shape[-1], top, left, height, width)
    if not _use_kernel(backend):
        return crop_reference(img, top, left, height, width)
    flat, lead = _flatten_to(img, 2)
    out = crop_batch(flat, top, left, height, width, block_n=block_n,
                     interpret=_interpret(backend))
    return out.reshape(lead + out.shape[1:])


@functools.partial(jax.jit, static_argnames=("backend", "block_n"))
def pong_render(ball_x: jnp.ndarray, ball_y: jnp.ndarray,
                paddle_y: jnp.ndarray, enemy_y: jnp.ndarray, *,
                backend: str = "auto", block_n: int = 8) -> jnp.ndarray:
    """(N,) game-state scalars -> (N, 210, 160, 3) uint8 native screens
    (one fused render over the served block — AtariLikeBatch's
    ``v_observe``)."""
    if not _use_kernel(backend):
        return pong_render_reference(ball_x, ball_y, paddle_y, enemy_y)
    return pong_render_batch(
        jnp.asarray(ball_x, jnp.float32), jnp.asarray(ball_y, jnp.float32),
        jnp.asarray(paddle_y, jnp.float32), jnp.asarray(enemy_y, jnp.float32),
        block_n=block_n, interpret=_interpret(backend),
    )


__all__ = [
    "BACKENDS",
    "crop",
    "default_backend",
    "grayscale",
    "pong_render",
    "resize",
    "resolve_backend",
]
