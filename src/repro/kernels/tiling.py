"""Tiling helpers shared by the Pallas tick kernels.

Mosaic takes a block whose last two dims are multiples of (8, 128) or equal
to the array's.  The kernels stream their event axis in ``K_TILE`` rows
and their segment axis in lane tiles of up to ``S_TILE``; scalar
parameters travel as one ``(1, 128)`` lane row, which stays a legal block
when ``jax.vmap`` (the sweep row axis, or a batched ``lax.switch`` branch)
prepends a row dimension to it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

K_TILE = 128
S_TILE = 2048  # segment lanes per grid step (a multiple of 128)
LANES = 128


def seg_tiling(n_segments: int) -> tuple[int, int]:
    """(lanes per segment tile, padded segment count) for ``n_segments``:
    one lane-aligned tile when the segments fit in ``S_TILE``, else
    ``S_TILE``-wide tiles."""
    st = min(S_TILE, pl.cdiv(max(n_segments, 1), LANES) * LANES)
    return st, pl.cdiv(max(n_segments, 1), st) * st


def pad_ids(seg: jax.Array, n_segments: int, kp: int) -> jax.Array:
    """``seg`` as int32 padded to ``kp``, with every out-of-range id (and
    the padding) mapped to -1, which matches no segment lane."""
    seg = seg.astype(jnp.int32)
    seg = jnp.where((seg >= 0) & (seg < n_segments), seg, -1)
    return jnp.full((kp,), -1, jnp.int32).at[: seg.shape[0]].set(seg)


def within_tile_rank(col: jax.Array, row: jax.Array) -> jax.Array:
    """(T, 1) count of strictly-earlier lanes of the tile holding the same
    id: ``col`` is the tile's ids as a column, ``row`` the same ids as a
    row.  Mosaic has no cumsum; this pairwise compare is exact."""
    t = col.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    same = (col == row) & (j < i)
    return jnp.sum(same.astype(jnp.int32), axis=1, keepdims=True)


def scalar_row(*vals) -> jax.Array:
    """int32 scalars packed into the first lanes of a ``(1, 128)`` row;
    a kernel reads scalar ``i`` as the ``(1, 1)`` slice ``[:, i:i + 1]``."""
    assert len(vals) <= LANES
    packed = jnp.stack([jnp.asarray(v, jnp.int32) for v in vals])
    return jnp.zeros((1, LANES), jnp.int32).at[0, : len(vals)].set(packed)


SCALAR_ROW_SPEC = pl.BlockSpec((1, LANES), lambda *_: (0, 0))
