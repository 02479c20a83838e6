"""Jit'd public wrappers for the Pallas kernels.

Each wrapper stages both modes of its kernel with
``jax.lax.platform_dependent``, and JAX lowers only the branch of the
platform the program is compiled for: a program lowered for a TPU (the
attached chip, or a described one in an ahead-of-time compile) lowers the
kernel to Mosaic, and one lowered for any other platform runs it with
``interpret=True`` — the kernel body executed by XLA, which is the parity
reference the tests check against the pure-jnp oracles in
``repro.kernels.ref``.  No TPU program ever holds an interpreted kernel.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ecmp_hash as _eh
from repro.kernels import queue_tick as _qt
from repro.kernels import reps_update as _ru
from repro.kernels import seg_rank as _sr
from repro.kernels import seg_sum as _ss


def _by_platform(kernel, *args, **static):
    """``kernel(*args, **static)`` compiled by Mosaic when lowered for a
    TPU, interpreted on every other platform."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel, interpret=False, **static),
        default=functools.partial(kernel, interpret=True, **static),
    )


def ecmp_hash(flow, ev, salt, nports):
    """(R,128) int32 tiles -> ECMP port choice per element."""
    return _by_platform(_eh.ecmp_hash_pallas, flow, ev, salt, nports)


def reps_tick(*args):
    """Fused REPS per-tick update; see repro.kernels.reps_update."""
    return _by_platform(_ru.reps_tick_pallas, *args)


def queue_tick(*args):
    """One switch tick: serve + enqueue + RED; see repro.kernels.queue_tick."""
    return _by_platform(_qt.queue_tick_pallas, *args)


def seg_rank(seg, n_segments):
    """(K,) int32 -> stable FIFO rank within each segment; see
    repro.kernels.seg_rank (batched over sweep rows via vmap)."""
    return _by_platform(_sr.seg_rank_pallas, seg, n_segments=n_segments)


def seg_sum(seg, vals, n_segments):
    """(K,), (F, K) int32 -> (F, n_segments) stacked segment sums; see
    repro.kernels.seg_sum (batched over sweep rows via vmap)."""
    return _by_platform(_ss.seg_sum_pallas, seg, vals, n_segments=n_segments)
