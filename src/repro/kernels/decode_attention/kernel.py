"""Flash-decoding Pallas TPU kernel: one query token vs a long KV cache.

Decode attention is memory-bound (the whole cache is read once per token),
so the adaptation target is *bandwidth parallelism*, not MXU utilization:
the cache's sequence axis is split into chunks, each grid step produces a
partial (max, sumexp, weighted-V) triple, and a cheap second pass combines
them — the same split that lets the sharding layer place cache chunks on
different chips ("kv_seq" -> model axis) and combine with one tiny
all-reduce instead of gathering the cache.

Grid: (B, Hkv, T/block_t).  Each step processes all G = H/Hkv query heads
of its kv head against one cache chunk: q-tile (G, D) stays in VREGs, the
(block_t, D) K/V tiles stream through VMEM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(
    q_ref, k_ref, v_ref, len_ref, m_ref, l_ref, acc_ref, *,
    block_t: int, sm_scale: float,
):
    b = pl.program_id(0)
    ti = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale      # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                 # (block_t, D)
    v = v_ref[0, 0].astype(jnp.float32)
    G = q.shape[0]

    s = q @ k.T                                          # (G, block_t)
    t_pos = ti * block_t + lax.broadcasted_iota(jnp.int32, (G, block_t), 1)
    valid = t_pos < len_ref[b]
    s = jnp.where(valid, s, NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)               # (G, 1)
    p = jnp.exp(s - m)
    p = jnp.where(valid, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = p @ v                                          # (G, D)

    m_ref[0, 0, 0] = m
    l_ref[0, 0, 0] = l
    acc_ref[0, 0, 0] = acc


def decode_attention_fwd(
    q: jnp.ndarray,        # (B, H, D)
    k: jnp.ndarray,        # (B, Hkv, T, D)
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # (B,)
    *,
    sm_scale: float | None = None,
    block_t: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    B, H, D = q.shape
    _, Hkv, T, _ = k.shape
    G = H // Hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    block_t = min(block_t, T)
    if T % block_t:
        raise ValueError(f"T={T} % block_t={block_t}")
    n_chunks = T // block_t

    grid = (B, Hkv, n_chunks)
    qg = q.reshape(B, Hkv, G, D)
    lengths = lengths.astype(jnp.int32)

    kernel = functools.partial(_decode_kernel, block_t=block_t, sm_scale=scale)
    # lengths sit whole in SMEM (read as a scalar per batch row); the
    # per-chunk (max, sumexp) partials are (G, 1) columns, so every block's
    # two minor dims are the array's own
    m, l, acc = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, t: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_t, D), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, block_t, D), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, G, 1), lambda b, h, t: (b, h, t, 0, 0)),
            pl.BlockSpec((1, 1, 1, G, 1), lambda b, h, t: (b, h, t, 0, 0)),
            pl.BlockSpec((1, 1, 1, G, D), lambda b, h, t: (b, h, t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, n_chunks, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, n_chunks, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, n_chunks, G, D), jnp.float32),
        ],
        interpret=interpret,
    )(qg, k, v, lengths)
    m, l = m[..., 0], l[..., 0]

    # pass 2: combine partials (tiny; runs in XLA — or across shards as an
    # all-reduce when the cache is kv_seq-sharded)
    m_glob = jnp.max(m, axis=2, keepdims=True)               # (B,Hkv,1,G)
    w = jnp.exp(m - m_glob)
    l_glob = jnp.sum(l * w, axis=2)                          # (B,Hkv,G)
    o = jnp.sum(acc * w[..., None], axis=2) / jnp.maximum(
        l_glob, 1e-30
    )[..., None]
    return o.reshape(B, H, D).astype(q.dtype)
