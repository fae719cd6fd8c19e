"""jit'd public wrapper for the minibatch observation kernel.

Backend selection follows ``kernels/backend.py``: the Pallas kernel for
``pallas`` and ``pallas-interpret``, ``ref.py``'s jnp form otherwise.
Every backend gives the same bits (tests/test_minibatch_obs.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import resolve_backend
from repro.kernels.minibatch_obs.kernel import minibatch_obs_batch
from repro.kernels.minibatch_obs.ref import minibatch_obs_reference


def uses_kernel(backend: str = "auto") -> bool:
    return resolve_backend(backend) in ("pallas", "pallas-interpret")


def supports(shape: tuple[int, ...], dtype) -> bool:
    """Whether the kernel takes a rollout of ``(B, *shape)`` stacks:
    uint8 ``(C, H, W)`` with C even (two channels per output word)."""
    return (jnp.dtype(dtype) == jnp.uint8 and len(shape) == 3
            and shape[0] % 2 == 0)


@functools.partial(jax.jit, static_argnames=("backend",))
def minibatch_obs(obs: jnp.ndarray, idx: jnp.ndarray, *,
                  backend: str = "auto") -> jnp.ndarray:
    """(B, C, H, W) uint8 rollout, (mb,) row indices -> (H, W, C, mb)
    bfloat16: ``obs[idx] / 255`` rounded to bfloat16, batch-minor, the
    operand of a Nature-CNN conv1 that takes ``HWCN``."""
    if not supports(obs.shape[1:], obs.dtype) or idx.ndim != 1:
        raise ValueError("minibatch_obs wants a (B, C, H, W) uint8 rollout "
                         f"with C even and (mb,) indices; got {obs.dtype}"
                         f"{list(obs.shape)} and {list(idx.shape)}")
    if not uses_kernel(backend):
        return minibatch_obs_reference(obs, idx)
    return minibatch_obs_batch(
        obs, idx, interpret=resolve_backend(backend) == "pallas-interpret")


__all__ = ["minibatch_obs", "supports", "uses_kernel"]
