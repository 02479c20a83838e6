"""Every tick stage the simulator names with ``jax.named_scope("tick.<stage>")``
reaches the compiled program's HLO metadata, where a profiler trace's ops
are mapped to stages (``bench/stage_trace.py``): the dense path as the
sweep engine compiles a summary-mode chunk (with a frozen-horizon row and
the ``SwitchLB`` branches' ``lb.<variant>`` scopes), and the sparse
scale-mode tick with its per-connection exchange over a conn axis."""
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.arcane_paper import FATTREE_32_CI
from repro.core import make_lb
from repro.distrib.sharding import CONN_AXIS, SWEEP_AXIS
from repro.netsim import (
    Simulator, SweepCase, SweepEngine, TelemetrySpec, workloads,
)

STAGES = {"tick.feedback", "tick.rto", "tick.service", "tick.arrivals",
          "tick.injection", "tick.freelist", "tick.lb"}


def _scopes(text: str, prefix: str) -> set:
    return set(re.findall(rf"(?<![\w.]){prefix}\.[a-z_]+", text))


def test_dense_chunk_program_names_every_stage():
    cfg = FATTREE_32_CI
    wl = workloads.permutation(32, 8, seed=1)
    kw = {"evs_size": cfg.evs_size}
    cases = [SweepCase(f"p/{lb}", wl, lb, ticks, seeds=(0,), lb_kwargs=kw)
             for lb, ticks in (("ecmp", 40), ("ops", 40), ("reps", 30))]
    eng = SweepEngine(cfg, cases, devices=1)
    assert len(eng.buckets) == 1
    bucket, spec = eng.buckets[0], TelemetrySpec.default()
    assert bucket.program.masked  # rows of two horizons: the freeze runs
    args = (eng.bucket_carry(bucket, "summary", spec), bucket.keys,
            bucket.scn, jnp.asarray(bucket.horizons), jnp.zeros((), jnp.int32))
    fn = eng._make_chunk_fn(bucket.program, 4, "summary", spec)
    text = fn.lower(*args).compile().as_text()
    assert _scopes(text, "tick") == STAGES | {"tick.telemetry", "tick.freeze"}
    # ECMP's EV is fixed per connection: its branch may leave no op
    assert ({"lb.ops", "lb.reps"} <= _scopes(text, "lb")
            <= {"lb.ecmp", "lb.ops", "lb.reps"})


def test_sparse_conn_sharded_tick_names_every_stage():
    cfg = FATTREE_32_CI.replace(conn_sharding=True)
    sim = Simulator(cfg, workloads.permutation(32, 8, seed=1),
                    make_lb("reps", evs_size=cfg.evs_size))
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                (SWEEP_AXIS, CONN_AXIS))
    step = jax.shard_map(
        lambda st, t: sim.step_scenario(st, t, sim.base_key, sim.scn,
                                        conn_axis=CONN_AXIS)[0],
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
    )
    text = jax.jit(step).lower(
        sim.init_state(), jnp.zeros((), jnp.int32)
    ).compile().as_text()
    assert _scopes(text, "tick") == STAGES | {"tick.active_set",
                                               "tick.conn_exchange"}
