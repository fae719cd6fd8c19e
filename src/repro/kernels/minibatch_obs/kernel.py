"""The minibatch observation operand as one Pallas TPU kernel.

``ref.py`` states the operand.  XLA builds it in three passes over HBM
(a gather of uint8 stacks, a convert to bfloat16, a relayout to
batch-minor); this kernel reads each gathered stack once and writes the
operand once.

One grid step serves a block of 128 minibatch rows, which become the
lane dimension of the output:

  * The rows' stacks are DMA'd by index (``idx`` is scalar-prefetched to
    SMEM) into one of two VMEM slots: the next block's copies start
    before this block's work, so they overlap it.
  * Viewed as int32, each stack word holds four consecutive uint8 rows
    of one plane (the (8, 128)(4, 1) tiling of uint8).  One strided load
    takes the same word from every stack of the block: a ``(128, 128)``
    matrix with the rows on sublanes.
  * Each byte is widened, scaled by 1/255 in float32 and rounded to
    bfloat16 in integer arithmetic (Mosaic casts neither uint8 to
    bfloat16 nor extracts 8-bit scalars).  Two channels pack into one
    32-bit word, the (2, 1) packing of the bfloat16 output, and a 2-D
    transpose puts the rows on lanes.
  * The output block is ``(H, W, C, 128)``, tiled (4, 128)(2, 1): the
    layout XLA gives conv1's operand, so nothing relayouts it.

Compiled, each stack is copied as whole (8, 128) tiles, padding
included: Mosaic slices an HBM ref only at tile boundaries, and every
tile of the stack lies inside its buffer.  Interpret mode has no tiles
and copies the logical window.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILE, LANES = 8, 128        # a uint8 plane's tile in HBM
INV_255 = 1.0 / 255.0


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _bf16_bits(word: jnp.ndarray, byte: int, high: bool) -> jnp.ndarray:
    """Byte ``byte`` of each int32 ``word``, over 255 in float32, as the
    bits of the nearest bfloat16: in the low half of an int32, or with
    ``high`` in the high half.

    Adding half a bfloat16 ulp and truncating is round-to-nearest-even
    here because no tie occurs: for 0 < u < 255, u / 255 in binary is
    u's 8 bits repeated, so the bits below a bfloat16's 8 significant
    ones start with the same 8 bits rotated to a leading 1, at least
    0x80 of 0xFF, above the half; 0 and 255 / 255 = 1 are exact."""
    if byte == 3:
        u = lax.shift_right_logical(word, 24)
    else:
        u = (word >> (8 * byte)) & 0xFF if byte else word & 0xFF
    b = pltpu.bitcast(u.astype(jnp.float32) * INV_255, jnp.int32) + 0x8000
    return b & -0x10000 if high else lax.shift_right_logical(b, 16)


def _store_pairs(o_ref, h, p: int, words: jnp.ndarray, interpret: bool):
    """Row ``h``, channels ``2p`` and ``2p + 1`` of the ``(H, W, C, 128)``
    bfloat16 output block from ``(W, 128)`` int32 words that hold them
    in their low and high halves.  Compiled, through an
    int32 view of the block, in which row ``w * C / 2 + p`` holds pair
    ``p`` of column ``w``: strided stores of 8 rows each.  Interpret mode
    stores through no view; it splits the words into their bfloat16
    halves (the same bits)."""
    h_out, w_out, c_out, block_n = o_ref.shape
    if interpret:
        pair = pltpu.bitcast(words, jnp.bfloat16).reshape(w_out, 2, block_n)
        o_ref[h, :, pl.ds(2 * p, 2), :] = pair
        return
    rows = o_ref.bitcast(jnp.int32).reshape(h_out, w_out * c_out // 2,
                                             block_n)
    rows[h, pl.ds(p, w_out, stride=c_out // 2), :] = words


def _kernel(idx_ref, x_hbm, o_ref, buf, sem, *, n_blocks: int,
            window: tuple[int, int], interpret: bool):
    i = pl.program_id(0)
    slot = i % 2
    h_out, w_out, c_out, block_n = o_ref.shape
    rows, lanes = window

    def copies(blk, slot, act):
        def one(j, carry):
            row = idx_ref[blk * block_n + j]
            act(pltpu.make_async_copy(
                x_hbm.at[row, :, pl.ds(0, rows), pl.ds(0, lanes)],
                buf.at[slot, j, :, pl.ds(0, rows), pl.ds(0, lanes)],
                sem.at[slot]))
            return carry
        lax.fori_loop(0, block_n, one, 0)

    start = lambda c: c.start()      # noqa: E731
    wait = lambda c: c.wait()        # noqa: E731

    @pl.when(i == 0)
    def _():
        copies(0, 0, start)

    @pl.when(i + 1 < n_blocks)
    def _():
        copies(i + 1, 1 - slot, start)

    copies(i, slot, wait)

    # int32 row q of stack j, channel c holds uint8 rows 4q..4q+3 of the
    # plane, row 4q + k in byte k; one strided load takes it from every
    # stack of the block
    hp, wp = buf.shape[3:]
    stride = c_out * hp // 4
    x32 = buf.at[slot].bitcast(jnp.int32).reshape(block_n * stride, wp)

    def word_rows(q, ks):
        for p in range(c_out // 2):
            lo, hi = (x32[pl.ds(c * hp // 4 + q, block_n, stride=stride), :]
                      for c in (2 * p, 2 * p + 1))
            for k in ks:
                pair = _bf16_bits(lo, k, False) | _bf16_bits(hi, k, True)
                _store_pairs(o_ref, 4 * q + k, p, pair.T[:w_out], interpret)

    def body(q, carry):
        word_rows(q, range(4))
        return carry

    lax.fori_loop(0, h_out // 4, body, 0)
    if h_out % 4:
        word_rows(h_out // 4, range(h_out % 4))


def minibatch_obs_batch(obs: jnp.ndarray, idx: jnp.ndarray, *,
                        interpret: bool = True) -> jnp.ndarray:
    """(B, C, H, W) uint8, (mb,) int32 -> (H, W, C, mb) bfloat16 via the
    Pallas kernel, 128 rows per grid step; ``mb`` need not be a multiple
    of 128.

    VMEM holds two slots of 128 tile-padded stacks and two output
    blocks: at Pong's 4 x 84 x 84, 2 x 5.77 MB and 2 x 7.23 MB of the 32
    MiB asked for.  A 256-row block would take 52 MB, and Mosaic does not
    lower its strided stores on a v5e."""
    b, c, h, w = obs.shape
    mb = idx.shape[0]
    n_blocks = -(-mb // LANES)
    idx = jnp.pad(idx.astype(jnp.int32), (0, n_blocks * LANES - mb))
    hp, wp = _round_up(h, ROW_TILE), _round_up(w, LANES)
    window = (h, w) if interpret else (hp, wp)
    return pl.pallas_call(
        functools.partial(_kernel, n_blocks=n_blocks, window=window,
                          interpret=interpret),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((h, w, c, LANES),
                                   lambda i, idx: (0, 0, 0, i)),
            scratch_shapes=[pltpu.VMEM((2, LANES, c, hp, wp), jnp.uint8),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((h, w, c, mb), jnp.bfloat16),
        # the next block's copies are started by this one: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=32 * 2 ** 20),
        interpret=interpret,
        name="minibatch_obs",
    )(idx, obs)
