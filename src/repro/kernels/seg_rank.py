"""Pallas TPU kernel: batched FIFO segment rank (tiled histogram scan).

``rank_i = #{j < i : seg_j == seg_i}`` — the stable within-segment rank the
netsim engine uses twice per tick: ranking same-connection ACK events for
the exact ``feedback_rounds`` replay, and ranking same-target arrivals for
FIFO enqueue positions (engine.py §1/§4).

The pure-jnp engine formulation is the O(K²) pairwise compare+reduce; this
kernel is the O(K·S) *tiled sort-free scan*: a running per-segment
histogram stays resident in VMEM scratch while K streams through in
``K_TILE``-sized chunks.  Each element's rank is the histogram count of its
segment so far plus its within-tile rank, a ``(K_TILE, K_TILE)`` pairwise
compare masked to the strictly-earlier lanes (Mosaic has no cumsum).
The segment axis is tiled too (``tiling.S_TILE`` lanes per grid step), so
the one-hot block stays ``(K_TILE, S_TILE)`` at any segment count —
10⁵-conn scale mode included.

Batching: the kernel body is written per row; under ``jax.vmap`` (the
sweep/fleet (scenario, seed) row axis) the ``pallas_call`` batching rule
prepends a row grid dimension, so one launch covers the whole bucket.

Out-of-range segment ids (``seg < 0`` or ``seg >= S``, the engine's
sentinel/padding convention) get rank 0 and never touch the histogram.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import K_TILE, pad_ids, seg_tiling, within_tile_rank


def _seg_rank_kernel(
    seg_ref,  # (K_TILE, 1) int32 segment id (-1: out of range, rank 0)
    seg_row_ref,  # (1, K_TILE) the same ids as a row
    o_rank_ref,  # (K_TILE, 1) int32, accumulated over the segment tiles
    hist_ref,  # (n_s, S_TILE) int32 scratch: running per-segment counts
):
    k, s = pl.program_id(0), pl.program_id(1)
    st = hist_ref.shape[1]
    seg = seg_ref[...]  # (T, 1)

    @pl.when(s == 0)
    def _within():
        o_rank_ref[...] = jnp.where(
            seg >= 0, within_tile_rank(seg, seg_row_ref[...]), 0
        )

    @pl.when(k == 0)
    def _init():
        hist_ref[pl.ds(s, 1), :] = jnp.zeros((1, st), jnp.int32)

    hist = hist_ref[pl.ds(s, 1), :]  # (1, S_TILE) counts before this tile
    lane = jax.lax.broadcasted_iota(jnp.int32, (seg.shape[0], st), 1) + s * st
    onehot = (lane == seg).astype(jnp.int32)  # (T, S_TILE)
    o_rank_ref[...] += jnp.sum(hist * onehot, axis=1, keepdims=True)
    hist_ref[pl.ds(s, 1), :] = hist + jnp.sum(onehot, axis=0, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("n_segments", "interpret")
)
def seg_rank_pallas(
    seg: jax.Array,  # (K,) int32; entries outside [0, n_segments) rank as 0
    n_segments: int,
    *,
    interpret: bool,
) -> jax.Array:
    """FIFO rank of each element within its segment, stable in input order.

    Bit-identical to ``repro.kernels.ref.seg_rank_ref`` (and to the
    engine's pairwise/sort jnp formulations) for every ``seg`` in
    ``[0, n_segments)``; ``n_segments`` only has to bound the ids whose
    ranks are consumed.
    """
    K = seg.shape[0]
    S = int(n_segments)
    st, sp = seg_tiling(S)
    KP = pl.cdiv(K, K_TILE) * K_TILE
    seg_p = pad_ids(seg, S, KP)
    rank = pl.pallas_call(
        _seg_rank_kernel,
        grid=(KP // K_TILE, sp // st),
        in_specs=[
            pl.BlockSpec((K_TILE, 1), lambda k, s: (k, 0)),
            pl.BlockSpec((1, K_TILE), lambda k, s: (0, k)),
        ],
        out_specs=pl.BlockSpec((K_TILE, 1), lambda k, s: (k, 0)),
        out_shape=jax.ShapeDtypeStruct((KP, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((sp // st, st), jnp.int32)],
        interpret=interpret,
    )(seg_p.reshape(KP, 1), seg_p.reshape(1, KP))
    return rank.reshape(KP)[:K]
