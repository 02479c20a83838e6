"""Pallas TPU kernel: batched ECMP mixing hash (flow, EV, salt) -> port.

The switch datapath hashes every packet header; in the vectorized simulator
this is a wide elementwise u32 mix — a pure VPU kernel.  Inputs are tiled
(ROWS x 128) int32 blocks resident in VMEM; lanes are the 128-wide vector
dimension of the TPU VPU, rows are sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiling import LANES, SCALAR_ROW_SPEC, scalar_row

ROW_TILE = 8  # one (8, 128) VREG per block step


def _mix_kernel(flow_ref, ev_ref, salt_ref, nports_ref, out_ref):
    flow = flow_ref[...].astype(jnp.uint32)
    ev = ev_ref[...].astype(jnp.uint32)
    salt = salt_ref[...].astype(jnp.uint32)
    x = (
        flow * jnp.uint32(0x9E3779B1)
        ^ ev * jnp.uint32(0x85EBCA77)
        ^ salt * jnp.uint32(0xC2B2AE3D)
    )
    # murmur3 finalizer
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    nports = nports_ref[:, 0:1].astype(jnp.uint32)  # (1, 1)
    out_ref[...] = (x % nports).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ecmp_hash_pallas(
    flow: jax.Array,  # (R, 128) int32
    ev: jax.Array,
    salt: jax.Array,
    nports: jax.Array,  # () int32
    *,
    interpret: bool,
) -> jax.Array:
    R = flow.shape[0]
    assert flow.shape[1] == LANES and flow.shape == ev.shape == salt.shape
    grid = (pl.cdiv(R, ROW_TILE),)
    spec = pl.BlockSpec((ROW_TILE, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _mix_kernel,
        grid=grid,
        in_specs=[spec, spec, spec, SCALAR_ROW_SPEC],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((R, LANES), jnp.int32),
        interpret=interpret,
    )(flow, ev, salt, scalar_row(nports))
