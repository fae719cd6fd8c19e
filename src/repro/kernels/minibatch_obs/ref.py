"""Pure-jnp oracle for the minibatch observation kernel.

A PPO minibatch step feeds the Nature CNN ``obs[idx] / 255``.  On a TPU
the convolutions hold their activations batch-minor, and at
``Precision.DEFAULT`` conv1 rounds its operand to bfloat16, so the
operand XLA builds from a uint8 rollout is a gather, a convert and a
relayout copy.  This is that operand written out: ``(H, W, C, mb)``
bfloat16, element ``[h, w, c, j]`` the bfloat16 rounding of
``float32(obs[idx[j], c, h, w]) / 255``.
"""

from __future__ import annotations

import jax.numpy as jnp


def minibatch_obs_reference(obs: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """(B, C, H, W) uint8, (mb,) int32 -> (H, W, C, mb) bfloat16."""
    x = obs[idx].astype(jnp.float32) / 255.0
    return jnp.transpose(x, (2, 3, 1, 0)).astype(jnp.bfloat16)
