"""Pallas TPU kernel: fused per-tick REPS connection-state update.

This is the NIC datapath hot spot of the paper, restructured for a vector
machine (DESIGN.md §3.2): one kernel invocation applies, for a tile of
connections at once, the paper's Algorithm 1 (onAck + onFailureDetection)
followed by Algorithm 2 (onSend/getNextEV) — branch-free selects over the
8-lane circular buffers held in VMEM.

Layout: per grid step a (CONN_TILE, 8) int32 block of buffer state plus
(CONN_TILE, 1) per-connection scalars.  8 is the buffer depth (paper §3.1);
CONN_TILE=128 keeps a step's working set « VMEM while filling VREG lanes.

The pure-jnp oracle is `repro.kernels.ref.reps_tick_ref`, itself pinned to
`repro.core.reps` (which tests pin to the paper's scalar pseudocode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import SCALAR_ROW_SPEC, scalar_row

CONN_TILE = 128
BUF = 8  # paper buffer depth


def _reps_tick_kernel(
    # state
    buf_ev_ref, buf_valid_ref, head_ref, num_valid_ref, explore_ref,
    freezing_ref, exit_freeze_ref, n_cached_ref,
    # events
    ack_mask_ref, ack_ev_ref, ack_ecn_ref, timeout_mask_ref, send_mask_ref,
    rand_ev_ref,
    # scalars
    params_ref,  # (1, 128) scalar row: [now, num_pkts_bdp, freezing_timeout]
    # outputs
    o_buf_ev_ref, o_buf_valid_ref, o_head_ref, o_num_valid_ref,
    o_explore_ref, o_freezing_ref, o_exit_freeze_ref, o_n_cached_ref,
    o_ev_ref,
):
    params = params_ref[...]
    now, bdp, freeze_to = params[:, 0:1], params[:, 1:2], params[:, 2:3]

    buf_ev = buf_ev_ref[...]
    buf_valid = buf_valid_ref[...]  # int32 0/1
    head = head_ref[...]  # (T,1)
    num_valid = num_valid_ref[...]
    explore_ctr = explore_ref[...]
    freezing = freezing_ref[...]  # int32 0/1
    exit_freeze = exit_freeze_ref[...]
    n_cached = n_cached_ref[...]

    lane = jax.lax.broadcasted_iota(jnp.int32, buf_ev.shape, 1)  # (T,8)

    # ---- Algorithm 1: onAck -------------------------------------------
    ack = ack_mask_ref[...]
    cache = (ack == 1) & (ack_ecn_ref[...] == 0)
    at_head = lane == head  # (T,8)
    slot_valid = jnp.sum(jnp.where(at_head, buf_valid, 0), axis=1, keepdims=True)
    num_valid = jnp.where(cache & (slot_valid == 0), num_valid + 1, num_valid)
    wr = cache & at_head
    buf_ev = jnp.where(wr, ack_ev_ref[...], buf_ev)
    buf_valid = jnp.where(wr, 1, buf_valid)
    head = jnp.where(cache, (head + 1) % BUF, head)
    n_cached = jnp.where(cache, n_cached + 1, n_cached)
    exit_now = cache & (freezing == 1) & (now > exit_freeze)
    freezing = jnp.where(exit_now, 0, freezing)
    explore_ctr = jnp.where(exit_now, bdp, explore_ctr)

    # ---- Algorithm 1: onFailureDetection -------------------------------
    enter = (timeout_mask_ref[...] == 1) & (freezing == 0) & (explore_ctr == 0)
    freezing = jnp.where(enter, 1, freezing)
    exit_freeze = jnp.where(enter, now + freeze_to, exit_freeze)

    # ---- Algorithm 2: onSend / getNextEV --------------------------------
    send = send_mask_ref[...] == 1
    is_empty = n_cached == 0
    explore = send & (
        is_empty | ((num_valid == 0) & (freezing == 0)) | (explore_ctr > 0)
    )
    recycle = send & ~explore
    pop_valid = recycle & (num_valid > 0)
    reuse = recycle & (num_valid == 0)
    offset = jnp.where(pop_valid, (head - num_valid) % BUF, head)  # (T,1)
    at_off = lane == offset
    picked = jnp.sum(jnp.where(at_off, buf_ev, 0), axis=1, keepdims=True)
    ev = jnp.where(recycle, picked, rand_ev_ref[...])
    buf_valid = jnp.where(pop_valid & at_off, 0, buf_valid)
    num_valid = jnp.where(pop_valid, num_valid - 1, num_valid)
    head = jnp.where(reuse, (head + 1) % BUF, head)
    explore_ctr = jnp.where(
        explore, jnp.maximum(explore_ctr - 1, 0), explore_ctr
    )

    o_buf_ev_ref[...] = buf_ev
    o_buf_valid_ref[...] = buf_valid
    o_head_ref[...] = head
    o_num_valid_ref[...] = num_valid
    o_explore_ref[...] = explore_ctr
    o_freezing_ref[...] = freezing
    o_exit_freeze_ref[...] = exit_freeze
    o_n_cached_ref[...] = n_cached
    o_ev_ref[...] = ev


@functools.partial(jax.jit, static_argnames=("interpret",))
def reps_tick_pallas(
    buf_ev, buf_valid, head, num_valid, explore, freezing, exit_freeze,
    n_cached, ack_mask, ack_ev, ack_ecn, timeout_mask, send_mask, rand_ev,
    now, num_pkts_bdp, freezing_timeout, *, interpret: bool,
):
    """All per-conn inputs are (N,) int32 (masks 0/1); buffers (N, 8) int32.

    Returns the updated state tuple + chosen EVs, same shapes.
    """
    N = buf_ev.shape[0]
    assert buf_ev.shape == (N, BUF)
    col = lambda x: x.reshape(N, 1).astype(jnp.int32)

    grid = (pl.cdiv(N, CONN_TILE),)
    buf_spec = pl.BlockSpec((CONN_TILE, BUF), lambda i: (i, 0))
    col_spec = pl.BlockSpec((CONN_TILE, 1), lambda i: (i, 0))
    out_shapes = (
        jax.ShapeDtypeStruct((N, BUF), jnp.int32),  # buf_ev
        jax.ShapeDtypeStruct((N, BUF), jnp.int32),  # buf_valid
        *[jax.ShapeDtypeStruct((N, 1), jnp.int32) for _ in range(7)],
    )
    outs = pl.pallas_call(
        _reps_tick_kernel,
        grid=grid,
        in_specs=[buf_spec, buf_spec] + [col_spec] * 12 + [SCALAR_ROW_SPEC],
        out_specs=(buf_spec, buf_spec) + (col_spec,) * 7,
        out_shape=out_shapes,
        interpret=interpret,
    )(
        buf_ev.astype(jnp.int32),
        buf_valid.astype(jnp.int32),
        col(head), col(num_valid), col(explore), col(freezing),
        col(exit_freeze), col(n_cached),
        col(ack_mask), col(ack_ev), col(ack_ecn), col(timeout_mask),
        col(send_mask), col(rand_ev),
        scalar_row(now, num_pkts_bdp, freezing_timeout),
    )
    (
        o_buf_ev, o_buf_valid, o_head, o_num_valid, o_explore, o_freezing,
        o_exit_freeze, o_n_cached, o_ev,
    ) = outs
    flat = lambda x: x.reshape(N)
    return (
        o_buf_ev,
        o_buf_valid,
        flat(o_head),
        flat(o_num_valid),
        flat(o_explore),
        flat(o_freezing),
        flat(o_exit_freeze),
        flat(o_n_cached),
        flat(o_ev),
    )
