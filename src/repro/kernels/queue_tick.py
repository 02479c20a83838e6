"""Pallas TPU kernel: one switch tick (serve + multi-enqueue + RED/ECN).

The recycled balls-into-bins inner loop (§5.1) and the netsim's
service/arrival steps fused for a single switch: every non-empty served
queue drains one packet, then a batch of K arrivals is enqueued with FIFO
ranking, tail-drop and RED marking.

TPU mapping (DESIGN.md §3.2): the per-arrival "which queue" histogram is a
one-hot (K_TILE x Q) comparison — lane-parallel over Q (queues on the
128-lane axis), sequential-grid-accumulated over K tiles so arbitrarily
large arrival batches stream through VMEM while the running
queue-occupancy block stays resident.  An arrival's rank among the tile's
earlier same-queue arrivals is a (K_TILE x K_TILE) pairwise compare (the
same one ``seg_rank`` uses; Mosaic has no cumsum).

Outputs: new queue lengths, per-arrival accept flag, RED mark flag, and the
insert position (used by callers to place payload slots).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import (
    K_TILE, SCALAR_ROW_SPEC, pad_ids, scalar_row, within_tile_rank,
)


def _queue_tick_kernel(
    target_ref,  # (K_TILE, 1) int32 arrival target queue (-1: no-op)
    target_row_ref,  # (1, K_TILE) the same targets as a row
    u_ref,  # (K_TILE, 1) float32 uniform for RED
    qlen_ref,  # (1, Q) int32 lengths at tick start
    serve_ref,  # (1, Q) int32 0/1 service mask
    params_ref,  # (1, 128) scalar row: [capacity, kmin, kmax]
    o_qlen_ref,  # (1, Q) int32 running lengths (accumulated over K tiles)
    o_accept_ref,  # (K_TILE, 1) int32
    o_mark_ref,  # (K_TILE, 1) int32
    o_pos_ref,  # (K_TILE, 1) int32
):
    params = params_ref[...]
    cap, kmin, kmax = params[:, 0:1], params[:, 1:2], params[:, 2:3]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        q0 = qlen_ref[...]
        served = jnp.where((q0 > 0) & (serve_ref[...] == 1), 1, 0)
        o_qlen_ref[...] = q0 - served

    qlen = o_qlen_ref[...]  # (1, Q) running occupancy
    Q = qlen.shape[1]
    target = target_ref[...]  # (T, 1)
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (target.shape[0], Q), 1)
        == target
    ).astype(jnp.int32)  # (T, Q)
    is_real = jnp.sum(onehot, axis=1, keepdims=True) > 0  # 0 <= target < Q
    base = jnp.sum(qlen * onehot, axis=1, keepdims=True)  # qlen[target]
    # arrivals before me in this tile, same queue
    my_rank = jnp.where(
        is_real, within_tile_rank(target, target_row_ref[...]), 0
    )
    pos = base + my_rank
    accept = is_real & (pos < cap)
    ramp = (pos - kmin).astype(jnp.float32) / jnp.maximum(
        (kmax - kmin).astype(jnp.float32), 1.0
    )
    mark = accept & (u_ref[...] < jnp.clip(ramp, 0.0, 1.0))

    counts = jnp.sum(jnp.where(accept, onehot, 0), axis=0, keepdims=True)
    o_qlen_ref[...] = qlen + counts
    o_accept_ref[...] = accept.astype(jnp.int32)
    o_mark_ref[...] = mark.astype(jnp.int32)
    o_pos_ref[...] = pos


@functools.partial(jax.jit, static_argnames=("interpret",))
def queue_tick_pallas(
    target: jax.Array,  # (K,) int32; entries outside [0, Q) are no-ops
    u: jax.Array,  # (K,) float32
    qlen: jax.Array,  # (Q,) int32
    serve: jax.Array,  # (Q,) int32/bool
    capacity,
    kmin,
    kmax,
    *,
    interpret: bool,
):
    K = target.shape[0]
    Q = qlen.shape[0]
    KP = pl.cdiv(K, K_TILE) * K_TILE
    # whole tiles, no-op targets (-1) in the padding: no tile reads past K
    target_p = pad_ids(target, Q, KP)
    u_p = jnp.zeros((KP,), jnp.float32).at[:K].set(u.astype(jnp.float32))
    kcol = pl.BlockSpec((K_TILE, 1), lambda i: (i, 0))
    krow = pl.BlockSpec((1, K_TILE), lambda i: (0, i))
    qrow = pl.BlockSpec((1, Q), lambda i: (0, 0))
    out = pl.pallas_call(
        _queue_tick_kernel,
        grid=(KP // K_TILE,),
        in_specs=[
            kcol, krow, kcol, qrow, qrow, SCALAR_ROW_SPEC
        ],
        out_specs=(qrow, kcol, kcol, kcol),
        out_shape=(
            jax.ShapeDtypeStruct((1, Q), jnp.int32),
            jax.ShapeDtypeStruct((KP, 1), jnp.int32),
            jax.ShapeDtypeStruct((KP, 1), jnp.int32),
            jax.ShapeDtypeStruct((KP, 1), jnp.int32),
        ),
        interpret=interpret,
    )(
        target_p.reshape(KP, 1),
        target_p.reshape(1, KP),
        u_p.reshape(KP, 1),
        qlen.reshape(1, Q).astype(jnp.int32),
        serve.reshape(1, Q).astype(jnp.int32),
        scalar_row(capacity, kmin, kmax),
    )
    new_qlen, accept, mark, pos = out
    return (
        new_qlen.reshape(Q),
        accept.reshape(KP)[:K].astype(jnp.bool_),
        mark.reshape(KP)[:K].astype(jnp.bool_),
        pos.reshape(KP)[:K],
    )
