"""The split of device busy time by tick stage (``bench/stage_trace.py``):
the innermost-op rule on made-up events, the scope matcher on the op
paths the trace carries, the staging of fusions with no path from the
HLO the trace carries, and a small trace recorded on a TPU v5e
(``tools/record_stage_trace.py``: the fig07 cell cut to the CPU tests'
size, one 2-tick chunk and its quiescence poll)."""
import pathlib
import shutil
import tempfile
import types

import pytest

from bench import stage_trace, trace_reduce

TRACE = pathlib.Path(__file__).resolve().parent / "testdata" / "v5e_stages.xplane.pb"
STAGES = {"tick.feedback", "tick.rto", "tick.service", "tick.arrivals",
          "tick.injection", "tick.freelist", "tick.lb", "tick.telemetry"}


def test_innermost_op_takes_each_instant():
    # a while [0, 100) holding ops of two stages, a kernel inside the
    # second; two ops that start together: the shorter is inside
    events = [(0.0, 100.0, "while"), (10.0, 30.0, "a"), (40.0, 70.0, "b"),
              (50.0, 60.0, "k"), (200.0, 210.0, "x"), (200.0, 205.0, "y")]
    got = stage_trace.innermost_split(events)
    assert got == {"while": 50.0, "a": 20.0, "b": 20.0, "k": 10.0,
                   "x": 5.0, "y": 5.0}
    # an op that outlasts its neighbour keeps the time after it ends
    assert stage_trace.innermost_split(
        [(0.0, 10.0, "p"), (5.0, 20.0, "q")]) == {"p": 5.0, "q": 15.0}


@pytest.mark.parametrize("op_path,stage,lb", [
    ("jit(body)/while/body/closed_call/vmap(tick.feedback)/add:",
     "tick.feedback", None),
    ("jit(body)/while/body/vmap(tick.rto)/tick.lb/cond/branch_1_fun/lb.ops/"
     "random_bits", "tick.lb", "lb.ops"),
    ("jit(f)/vmap(tick.b)/while/body/closed_call/tick.service/cos",
     "tick.service", None),
    ("jit(body)/shard_map/while/body/vmap(tick.conn_exchange)/all_gather:",
     "tick.conn_exchange", None),
    ("jit(body)/while/body/vmap(tick.freelist)/tick.active_set/sort",
     "tick.active_set", None),
    ("jit(body)/while/body/vmap(tick.arrivals)/jit(queue_tick_pallas)/"
     "pallas_call", "tick.arrivals", None),
    ("jit(body)/while/body/vmap(reps_tick.3)/add", None, None),
    ("jit(body)/while:", None, None),
])
def test_innermost_scope_of_op_path(op_path, stage, lb):
    assert stage_trace.innermost(op_path) == stage
    assert stage_trace.innermost(op_path, stage_trace.LB) == lb


HLO = """HloModule jit_body, is_scheduled=true

%fused_rto (p.1: s32[8]) -> s32[8] {
  %p.1 = s32[8]{0} parameter(0)
  %c.1 = s32[]{:T(128)} constant(0), metadata={op_name="jit(body)/while/body/vmap(tick.service)/broadcast_in_dim"}
  ROOT %dus.1 = s32[8]{0} dynamic-update-slice(%p.1, %p.1, %c.1), metadata={op_name="jit(body)/while/body/vmap(tick.rto)/tick.lb/lb.reps/scatter"}
}

%fused_vote (p.2: s32[8]) -> s32[8] {
  %p.2 = s32[8]{0} parameter(0)
  %a.2 = s32[8]{0} add(%p.2, %p.2), metadata={op_name="jit(body)/while/body/vmap(tick.freelist)/add"}
  %m.2 = s32[8]{0} multiply(%a.2, %p.2), metadata={op_name="jit(body)/while/body/vmap(tick.freelist)/tick.active_set/mul"}
  %s.2 = s32[8]{0} subtract(%a.2, %m.2), metadata={op_name="jit(body)/while/body/vmap(tick.freelist)/sub"}
  ROOT %r.2 = s32[8]{0} select(%s.2, %a.2, %m.2)
}

%fused_consts (p.3: s32[8]) -> s32[8] {
  %p.3 = s32[8]{0} parameter(0)
  %c.3 = s32[8]{0} constant({0,0,0,0,0,0,0,0}), metadata={op_name="jit(body)/while/body/vmap(tick.rto)/broadcast_in_dim"}
  ROOT %r.3 = s32[8]{0} add(%p.3, %c.3)
}

ENTRY %main.9 (x.9: s32[8]) -> s32[8] {
  %x.9 = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%x.9), kind=kLoop, calls=%fused_rto
  %fusion.2 = s32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_vote
  %fusion.3 = s32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_consts
  ROOT %fusion.4 = s32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%fused_rto, metadata={op_name="jit(body)/while/body/vmap(tick.injection)/add"}
}
"""


def test_fusions_without_a_path_take_their_computations_stage():
    """A fusion with no stage of its own takes its root's path; with an
    unscoped root, that of the stage most of its instructions carry; with
    only a shared constant, none.  A fusion with a stage keeps it."""
    got = stage_trace.hlo_paths(HLO)
    assert got == {
        "fusion.1": "jit(body)/while/body/vmap(tick.rto)/tick.lb/lb.reps/"
                    "scatter",
        "fusion.2": "jit(body)/while/body/vmap(tick.freelist)/add",
    }
    assert stage_trace.innermost(got["fusion.1"], stage_trace.LB) == "lb.reps"


def test_split_by_stage():
    """Each op's innermost time goes to its path's stage; ops with no
    stage (the scan, a copy, another program's op) to ``unscoped``; the
    ``lb.*`` variants split ``tick.lb``'s time."""
    ops = {0: [("while.1", 0.0, 100.0), ("fusion.1", 10.0, 20.0),
               ("fusion.2", 40.0, 30.0), ("reps_tick.3", 50.0, 10.0),
               ("copy.4", 80.0, 10.0), ("poll.5", 150.0, 10.0)]}
    paths = {0: {
        "while.1": "jit(body)/while:",
        "fusion.1": "jit(body)/while/body/vmap(tick.rto)/gather:",
        "fusion.2": "jit(body)/while/body/vmap(tick.service)/scatter:",
        "reps_tick.3": "jit(body)/while/body/vmap(tick.feedback)/tick.lb/"
                       "lb.reps/jit(reps_tick_pallas)/pallas_call:",
        "poll.5": "jit(f)/and:",
    }}
    s = stage_trace.split(ops, (5.0, 155.0), paths)
    d = s.devices[0]
    assert d.stage_ns == {"unscoped": 35.0 + 10.0 + 5.0, "tick.rto": 20.0,
                          "tick.service": 20.0, "tick.lb": 10.0}
    assert d.lb_ns == {"lb.reps": 10.0}
    assert d.busy_ns == 100.0 == sum(d.stage_ns.values())
    assert s.ran("tick.rto") and not s.ran("tick.freeze")
    summary = trace_reduce.summarize(
        {0: [(n, a, dur) for n, a, dur in ops[0]]},
        [(trace_reduce.WINDOW_SPAN, 5.0, 155.0)])
    assert summary.devices[0].busy_ns == d.busy_ns


def test_recorded_v5e_stages_sum_to_busy_time():
    s = stage_trace.reduce(str(TRACE))
    summary = trace_reduce.reduce(str(TRACE))
    assert s.window == summary.window
    assert set(s.devices) == set(summary.devices) == {0}
    for dev, d in s.devices.items():
        assert abs(sum(d.stage_ns.values()) - d.busy_ns) <= 1.0
        assert abs(d.busy_ns - summary.devices[dev].busy_ns) <= 1.0
    assert STAGES <= set(s.names("stage_ns"))
    assert {"lb.ops", "lb.reps"} <= set(s.names("lb_ns"))
    paths = stage_trace.op_paths(str(TRACE))[0]
    kernels = [p for n, p in paths.items() if "queue_tick_pallas" in n]
    assert kernels and all(stage_trace.innermost(p) == "tick.arrivals"
                           for p in kernels)
    # this fusion has no tf_op path: its stage comes from the trace's HLO
    (fused,) = [p for n, p in paths.items()
                if trace_reduce.op_name(n) == "select_select_fusion.79"]
    assert stage_trace.innermost(fused) == "tick.freelist"


def test_readers_find_the_trace_behind_the_reduced_one(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    target = tmp_path / "bench_trace_1" / "plugins" / "profile" / "t"
    target.mkdir(parents=True)
    shutil.copy(TRACE, target / "host.xplane.pb")
    window = types.SimpleNamespace(traced={"ticks": 2, "rows": 6})
    ctx = {"trace": trace_reduce.reduce(str(TRACE)), "window": window}
    s = stage_trace.reduce(str(TRACE))
    want = 1e6 * s.stage_s("tick.rto") / 12
    assert stage_trace.us_per_row_tick(ctx, "tick.rto") == pytest.approx(want)
    assert stage_trace.us_per_row_tick(ctx, "tick.active_set") is None
    ctx["trace"] = types.SimpleNamespace(window=(0.0, 1.0))  # another run's
    assert stage_trace.us_per_row_tick(ctx, "tick.rto") is None
