"""Pallas TPU kernels for the compute hot-spots (DESIGN.md §3):
  flash_attention/  train/prefill attention (online-softmax K/V sweep)
  decode_attention/ flash-decoding (KV-chunk partials + tiny combine)
  env_step/         the paper's env-execution hot loop on the VPU
  image/            batched image preprocessing (grayscale / resize /
                    crop) + the Atari RGB render — the CuLE argument
  minibatch_obs/    a PPO minibatch's conv1 operand: gather, scale to
                    bfloat16, batch-minor layout in one pass

Each has kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper),
ref.py (pure-jnp oracle).  backend.py states the shared TPU/fallback
selection rule once (BACKENDS / default_backend / resolve_backend).
Validated in interpret mode on CPU; TPU is the lowering target.

The public ops of every family are re-exported here so consumers (the
LM policy decode path, transforms, benchmarks) import them uniformly:

    from repro.kernels import decode_attention, flash_attention, ...
"""

from repro.kernels.backend import BACKENDS, default_backend, resolve_backend
from repro.kernels.decode_attention.ops import (
    decode_attention,
    decode_attention_reference,
)
from repro.kernels.env_step.ops import env_multi_step, env_step
from repro.kernels.flash_attention.ops import flash_attention, mha_reference
from repro.kernels.image.ops import crop, grayscale, pong_render, resize
from repro.kernels.minibatch_obs.ops import minibatch_obs

__all__ = [
    "BACKENDS",
    "crop",
    "decode_attention",
    "decode_attention_reference",
    "default_backend",
    "env_multi_step",
    "env_step",
    "flash_attention",
    "grayscale",
    "mha_reference",
    "minibatch_obs",
    "pong_render",
    "resize",
    "resolve_backend",
]
