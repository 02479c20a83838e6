"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

Every Pallas tick kernel must lower to Mosaic and pass the TPU compiler at
the engine's figure shapes (``FATTREE_128``: 128 hosts, 384 queues, 128
conns) and, for the segment primitives, at scale-mode shapes (10⁵ conns),
both as called by the serial engine and batched over sweep rows by
``jax.vmap``.  One serial simulator program with every kernel on must
compile with its kernels as Mosaic custom calls: ``repro.kernels.ops``
picks Mosaic from the platform the program is lowered for, so a program
lowered for the described chip holds no interpreted kernel even though
this process's default backend is the CPU.

The topology is described inside a module fixture, not at import (one
process may load the TPU library at a time), and the
persistent compilation cache is off for this module: entries compiled for
a described chip could never be read back.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

ROWS = 2  # sweep rows for the vmapped compiles
NH, NC = 128, 128  # FATTREE_128 hosts; permutation conns
SCALE_NC = 100_000  # benchmarks/scale_smoke.py conns


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _n_queues():
    from repro.configs.arcane_paper import FATTREE_128
    from repro.netsim import Topology

    return Topology.build(FATTREE_128).n_queues


def _kernel_call(name):
    """(fn, per-row arg shapes) for one kernel at one shape."""
    if name == "seg_rank/figure":  # feedback ACK ranks: K = NH, S = NC + 1
        return lambda s: ops.seg_rank(s, NC + 1), [((NH,), jnp.int32)]
    if name == "seg_rank/scale":
        return lambda s: ops.seg_rank(s, SCALE_NC + 1), [((NH,), jnp.int32)]
    if name == "seg_sum/figure":  # feedback table: 5 fields, 3 x (NC + 1)
        return (lambda s, v: ops.seg_sum(s, v, 3 * (NC + 1)),
                [((NH,), jnp.int32), ((5, NH), jnp.int32)])
    if name == "seg_sum/scale":
        return (lambda s, v: ops.seg_sum(s, v, 3 * (SCALE_NC + 1)),
                [((NH,), jnp.int32), ((5, NH), jnp.int32)])
    if name == "queue_tick/figure":  # arrivals: K = NQ + NH, Q = NQ
        nq = _n_queues()
        return (lambda t, u, q, s: ops.queue_tick(t, u, q, s, 85, 17, 68),
                [((nq + NH,), jnp.int32), ((nq + NH,), jnp.float32),
                 ((nq,), jnp.int32), ((nq,), jnp.int32)])
    if name == "reps_tick/figure":
        return (lambda *a: ops.reps_tick(*a, 32, 800),
                [((NC, 8), jnp.int32)] * 2 + [((NC,), jnp.int32)] * 12
                + [((), jnp.int32)])
    if name == "ecmp_hash/figure":
        return (lambda f, e, s, n: ops.ecmp_hash(f, e, s, n),
                [((8, 128), jnp.int32)] * 3 + [((), jnp.int32)])
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "seg_rank/figure", "seg_rank/scale", "seg_sum/figure", "seg_sum/scale",
    "queue_tick/figure", "reps_tick/figure", "ecmp_hash/figure",
])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_call(name)
    plain = [_sds(one_chip, s, d) for s, d in shapes]
    rows = [_sds(one_chip, (ROWS,) + s, d) for s, d in shapes]
    for f, args in ((fn, plain), (jax.vmap(fn), rows)):
        compiled = jax.jit(f).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text(), name


def test_serial_simulator_program_holds_mosaic_kernels(one_chip):
    """A few ticks of ``Simulator._run`` with every kernel on: seg_rank,
    seg_sum, queue_tick and the REPS update all lower to Mosaic."""
    from repro.configs.arcane_paper import FATTREE_128
    from repro.core import make_lb
    from repro.netsim import Simulator, workloads

    cfg = FATTREE_128.replace(
        kernels_backend="pallas", arrivals_backend="pallas"
    )
    sim = Simulator(cfg, workloads.permutation(NH, 64, seed=1),
                    make_lb("reps", backend="pallas"))
    state = jax.tree_util.tree_map(
        lambda x: _sds(one_chip, x.shape, x.dtype),
        jax.eval_shape(sim.init_state),
    )
    compiled = jax.jit(lambda st: sim._run(4, st)).lower(state).compile()
    mosaic = [
        line for line in compiled.as_text().splitlines()
        if "tpu_custom_call" in line
    ]
    for kernel in ("seg_rank_pallas", "seg_sum_pallas", "queue_tick_pallas",
                   "reps_tick_pallas"):
        assert any(kernel in line for line in mosaic), kernel


@pytest.fixture(scope="module")
def sweep_chunk_text(one_chip):
    """Compiled HLO text of one bucket's summary-mode chunk program (the
    sweep path every figure grid runs: ECMP/OPS/REPS rows under a vmapped
    LB switch, with the telemetry fold) for the described chip."""
    from repro.configs.arcane_paper import FATTREE_128
    from repro.netsim import SweepCase, SweepEngine, TelemetrySpec, workloads

    cfg = FATTREE_128.replace(
        kernels_backend="pallas", arrivals_backend="pallas"
    )
    wl = workloads.permutation(NH, 16, seed=1)
    cases = [
        SweepCase(f"p/{lb}", wl, lb, 64, seeds=(0, 1), lb_kwargs=dict(
            evs_size=cfg.evs_size,
            **({"backend": "pallas"} if lb == "reps" else {}),
        ))
        for lb in ("ecmp", "ops", "reps")
    ]
    eng = SweepEngine(cfg, cases, devices=1, kernels_backend="pallas")
    assert len(eng.buckets) == 1
    bucket, spec = eng.buckets[0], TelemetrySpec.default()
    args = (
        eng.bucket_carry(bucket, "summary", spec), bucket.keys, bucket.scn,
        jnp.asarray(bucket.horizons), jnp.zeros((), jnp.int32),
    )
    fn = eng._make_chunk_fn(bucket.program, 8, "summary", spec)
    return fn.lower(*jax.tree_util.tree_map(
        lambda x: _sds(one_chip, x.shape, x.dtype), args
    )).compile().as_text()


def test_sweep_chunk_program_holds_mosaic_kernels(sweep_chunk_text):
    """The chunk program compiles with its kernels batched over rows."""
    assert "tpu_custom_call" in sweep_chunk_text


def test_sweep_chunk_program_keeps_tick_scopes(sweep_chunk_text):
    """Every tick stage's ``jax.named_scope`` survives the TPU compile in
    the ops' metadata, where a profiler trace's ops are mapped to stages;
    the Mosaic kernels keep their wrappers' names inside their stages."""
    op_names = re.findall(r'op_name="([^"]*)"', sweep_chunk_text)

    def stages_of(ops):  # the innermost tick.* scope of each op
        found = (re.findall(r"(?<![\w.])tick\.[a-z_]+", op) for op in ops)
        return {f[-1] for f in found if f}

    stages = stages_of(op_names)
    assert stages == {
        "tick.feedback", "tick.rto", "tick.service", "tick.arrivals",
        "tick.injection", "tick.freelist", "tick.lb", "tick.telemetry",
    }
    kernels = {
        kernel: stages_of(op for op in op_names if f"jit({kernel})" in op)
        for kernel in ("seg_rank_pallas", "seg_sum_pallas",
                       "queue_tick_pallas", "reps_tick_pallas")
    }
    assert kernels["queue_tick_pallas"] == {"tick.arrivals"}
    assert "tick.feedback" in kernels["seg_rank_pallas"]
    assert {"tick.feedback", "tick.rto", "tick.service",
            "tick.injection"} <= kernels["seg_sum_pallas"]
    assert kernels["reps_tick_pallas"] == {"tick.lb"}
