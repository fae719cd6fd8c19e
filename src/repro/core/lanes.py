"""Lane-major row table: the engine's per-lane leaves in one 32-bit slab.

Every recv moves the rows of M lanes out of the pool's state and back.
Kept as one ``(N, ...)`` array per leaf, that is one gather and one
scatter per leaf, and on the chip each of those costs a fixed few
microseconds whatever its width.  ``LaneLayout`` lays a lane's leaves
side by side in one ``(N, W) uint32`` row, so a block of lanes moves in
one gather and one scatter, and the leaves are sliced back out with
static column slices and bitcasts.

The layout is static, built once from the shapes and dtypes of the
leaves.  Each element takes one 32-bit word: a 32-bit leaf is bitcast,
``bool`` is stored as 0/1, and an 8- or 16-bit leaf is bitcast to its
unsigned integer and zero-extended.  Pack then unpack is the identity
bit for bit, NaN payloads and negative zeros included.  A leaf of any
other width (64-bit, or a typed PRNG key: store its key data) is refused
when the layout is built.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclasses.dataclass(frozen=True)
class Column:
    """One leaf's place in a lane row."""

    dtype: Any                # the leaf's dtype
    shape: tuple[int, ...]    # the leaf's shape less the lane dim
    offset: int               # first slab column
    words: int                # 32-bit words per lane, one per element


def _bits(dtype) -> int:
    return 8 * np.dtype(dtype).itemsize


def _to_words(c: Column, x: jnp.ndarray) -> jnp.ndarray:
    x = x.astype(c.dtype)
    if c.dtype == jnp.bool_:
        w = x.astype(jnp.uint32)
    else:
        w = lax.bitcast_convert_type(x, jnp.dtype(f"uint{_bits(c.dtype)}"))
        w = w.astype(jnp.uint32)
    return w.reshape(x.shape[0], c.words)


def _from_words(c: Column, w: jnp.ndarray) -> jnp.ndarray:
    w = w.reshape((w.shape[0],) + c.shape)
    if c.dtype == jnp.bool_:
        return w != 0
    w = w.astype(jnp.dtype(f"uint{_bits(c.dtype)}"))
    return lax.bitcast_convert_type(w, c.dtype)


class LaneLayout:
    """Static column layout of a pytree of per-lane leaves.

    ``rows``: arrays or ``jax.ShapeDtypeStruct``s with a leading lane
    dim (its size is ignored), e.g. ``jax.eval_shape`` of an init.
    ``words``: the slab's width, 32-bit words per lane.
    """

    def __init__(self, rows: Any):
        leaves, self.treedef = jax.tree_util.tree_flatten(rows)
        cols, offset = [], 0
        for leaf in leaves:
            dtype = leaf.dtype
            if (jnp.issubdtype(dtype, jax.dtypes.prng_key)
                    or _bits(dtype) not in (8, 16, 32)):
                raise TypeError(
                    f"a lane row leaf of dtype {dtype} does not fit 32-bit "
                    "words; store typed PRNG keys by their key data")
            shape = tuple(leaf.shape[1:])
            cols.append(Column(dtype, shape, offset, math.prod(shape)))
            offset += cols[-1].words
        self.columns = tuple(cols)
        self.words = offset

    def pack(self, rows: Any) -> jnp.ndarray:
        """The slab ``(m, words) uint32`` of the row tree ``rows``.

        The leaves are lane-minor on TPU: stacking their words as
        ``(words, m)`` and transposing once is cheaper than a
        concatenate that transposes each narrow leaf (on a v5e, at
        m=1024, Ant-v3's 50 words: 21.1 against 46.4 us per writeback).
        """
        xs = self.treedef.flatten_up_to(rows)
        return jnp.concatenate(
            [_to_words(c, x).T for c, x in zip(self.columns, xs)], axis=0).T

    def unpack(self, slab: jnp.ndarray) -> Any:
        """The row tree back from ``pack``'s slab, bit for bit."""
        return self.treedef.unflatten([
            _from_words(c, slab[:, c.offset:c.offset + c.words])
            for c in self.columns])

    def gather(self, slab: jnp.ndarray, idx: jnp.ndarray) -> Any:
        """The row tree of lanes ``idx``: one row gather of the slab."""
        return self.unpack(slab[idx])

    def scatter(self, slab: jnp.ndarray, idx: jnp.ndarray, rows: Any
                ) -> jnp.ndarray:
        """Write the row tree ``rows`` to lanes ``idx``: one scatter of
        whole slab rows.  To change a few fields, gather the rows,
        replace those fields and scatter them back: a scatter of a few
        columns compiles to a serial loop on TPU, a row scatter does
        not."""
        return slab.at[idx].set(self.pack(rows))


__all__ = ["Column", "LaneLayout"]
