"""Pallas TPU kernel: batched multi-field segment sum.

``out[f, s] = sum_k vals[f, k] * (seg[k] == s)`` — the per-connection /
per-round event aggregation the netsim tick is built on (inflight and
retransmit accounting, NACK counts, delivery/coalescing bookkeeping,
injection window updates: engine.py §1/§2/§3/§5).  The engine's jnp
formulation is a stacked scatter-add.

Kernel shape: the grid walks segment tiles (``tiling.S_TILE`` lanes)
and, inside each, streams the K event axis through in ``K_TILE`` chunks;
the ``(F, S_TILE)`` accumulator block stays resident in VMEM across the K
chunks.  Each chunk reduces its one-hot ``(T, S_TILE)`` against every
value column as a stack of F int32 masked reductions, so the sums are
exact for the whole int32 range and arbitrarily large event batches or
segment counts never materialize a ``(K, S)`` intermediate.

Batching: written per row; under ``jax.vmap`` (the sweep/fleet
(scenario, seed) row axis) the ``pallas_call`` batching rule prepends a
row grid dimension — one launch per bucket tick, not one per row.

Out-of-range segment ids (``seg < 0`` or ``seg >= S``) contribute to no
bucket — the engine's sentinel convention (events of padded rows aggregate
to the ``NC`` sentinel column, which callers slice off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import K_TILE, pad_ids, seg_tiling


def _seg_sum_kernel(
    seg_ref,  # (K_TILE, 1) int32 segment id (-1: no-op)
    vals_ref,  # (K_TILE, F) int32 value columns
    o_sum_ref,  # (F, S_TILE) int32 accumulator (carried across K tiles)
):
    s, k = pl.program_id(0), pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_sum_ref[...] = jnp.zeros_like(o_sum_ref)

    F, st = o_sum_ref.shape
    seg = seg_ref[...]  # (T, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (seg.shape[0], st), 1) + s * st
    onehot = lane == seg  # (T, S_TILE); all-false rows for id -1
    vals = vals_ref[...]  # (T, F)
    o_sum_ref[...] += jnp.concatenate(
        [
            jnp.sum(jnp.where(onehot, vals[:, f : f + 1], 0), axis=0,
                    keepdims=True)
            for f in range(F)
        ],
        axis=0,
    )


@functools.partial(
    jax.jit, static_argnames=("n_segments", "interpret")
)
def seg_sum_pallas(
    seg: jax.Array,  # (K,) int32; entries outside [0, n_segments) drop
    vals: jax.Array,  # (F, K) int32 stacked fields
    n_segments: int,
    *,
    interpret: bool,
) -> jax.Array:
    """Segment-sum ``F`` stacked int32 fields into ``n_segments`` buckets.

    Returns ``(F, n_segments)`` int32.  Integer addition is associative and
    commutative (wrapping included), so the result is bit-identical to the
    dense one-hot reduction (``repro.kernels.ref.seg_sum_ref``) and to the
    engine's jnp scatter-add for any accumulation order.
    """
    K = seg.shape[0]
    F = vals.shape[0]
    S = int(n_segments)
    st, sp = seg_tiling(S)
    KP = pl.cdiv(K, K_TILE) * K_TILE
    seg_p = pad_ids(seg, S, KP)
    vals_p = jnp.zeros((KP, F), jnp.int32).at[:K].set(
        vals.astype(jnp.int32).T
    )
    out = pl.pallas_call(
        _seg_sum_kernel,
        grid=(sp // st, KP // K_TILE),
        in_specs=[
            pl.BlockSpec((K_TILE, 1), lambda s, k: (k, 0)),
            pl.BlockSpec((K_TILE, F), lambda s, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((F, st), lambda s, k: (0, s)),
        out_shape=jax.ShapeDtypeStruct((F, sp), jnp.int32),
        interpret=interpret,
    )(seg_p.reshape(KP, 1), vals_p)
    return out[:, :S]
