"""The reduction from a profiler trace to busy, idle, kernel and collective
time, on made-up events and on a small trace recorded on a TPU v5e."""
import pathlib

import pytest

from bench import trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "testdata"
TRACE = DATA / "v5e_small.xplane.pb"  # two Pallas kernels, a matmul, spans
HLO = DATA / "v5e_small.hlo.txt"  # its program's custom-call lines


def test_summarize_made_up_events():
    spans = [("bench.traced", 100.0, 1100.0), ("bench.chunk", 100.0, 600.0),
             ("bench.poll", 600.0, 1100.0), ("bench.unrelated", 2000.0, 2100.0)]
    ops = {
        0: [("fusion.1", 50.0, 100.0),  # starts before the window
            ("seg_rank_pallas.1", 200.0, 100.0),
            ("fusion.2", 250.0, 150.0),  # overlaps the kernel
            ("all-gather-start.3", 700.0, 100.0),
            ("fusion.1", 1050.0, 200.0)],  # ends after the window
        1: [("fusion.1", 100.0, 1000.0)],
    }
    s = trace_reduce.summarize(ops, spans, kernels={"seg_rank_pallas.1"})
    assert s.window_ns == 1000.0
    d0 = s.devices[0]
    # busy: [100,150) + [200,400) + [700,800) + [1050,1100)
    assert d0.busy_ns == 50 + 200 + 100 + 50
    assert d0.kernel_ns == 100 and d0.collective_ns == 100
    assert d0.kernel_by == {"seg_rank": 100}
    assert s.devices[1].busy_ns == 1000
    assert s.busy_s == pytest.approx((400 + 1000) / 2 / 1e9)
    gaps = s.idle_gaps(3)
    assert gaps == [["bench.chunk", pytest.approx(300 / 1e9)],  # [400,700)
                    ["bench.poll", pytest.approx(250 / 1e9)],  # [800,1050)
                    ["bench.chunk", pytest.approx(50 / 1e9)]]  # [150,200)
    assert [n for n, _ in s.top_ops(1)] == ["fusion.1"]
    assert [sp[0] for sp in s.spans] == ["bench.chunk", "bench.poll"]


def test_op_name_is_the_instruction_name():
    assert trace_reduce.op_name(
        "%queue_tick_pallas.10 = (s32[6,1,384]{2,1,0}, s32[6,512,1]{2,1,0}) "
        'custom-call(s32[6,512,1] %x), custom_call_target="tpu_custom_call"'
    ) == "queue_tick_pallas.10"
    assert trace_reduce.op_name("fusion.3") == "fusion.3"
    assert trace_reduce.kernel_of("queue_tick_pallas.10") == "queue_tick"
    assert trace_reduce.kernel_of("reps_tick_pallas") == "reps_tick"


def test_program_ops_names_kernels_and_collectives():
    hlo = """
  %seg_rank_pallas.1 = s32[256,1]{1,0} custom-call(%copy.2), custom_call_target="tpu_custom_call", backend_config={}
  %all-gather.4 = s32[512]{0} all-gather(%p.1), replica_groups={{0,1}}, dimensions={0}
  ROOT %all-reduce-start.2 = s32[4]{0} all-reduce-start(%x), to_apply=%add
  %fusion.3 = s32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation
"""
    kernels, collectives = trace_reduce.program_ops([hlo])
    assert kernels == {"seg_rank_pallas.1"}
    assert collectives == {"all-gather.4", "all-reduce-start.2"}


def test_summarize_needs_the_window_span():
    with pytest.raises(ValueError, match="bench.traced"):
        trace_reduce.summarize({0: []}, [("bench.chunk", 0.0, 1.0)])


def test_recorded_v5e_trace():
    kernels, collectives = trace_reduce.program_ops([HLO.read_text()])
    assert len(kernels) == 2 and not collectives
    s = trace_reduce.reduce(str(TRACE), kernels, collectives)
    assert list(s.devices) == [0]
    d = s.devices[0]
    assert 0 < d.busy_ns <= s.window_ns
    assert d.kernel_ns > 0 and d.collective_ns == 0
    assert set(d.kernel_by) == {"seg_rank", "seg_sum"}
    assert sum(d.kernel_by.values()) == pytest.approx(d.kernel_ns)
    assert {n for n, _, _ in s.spans} >= {"bench.chunk", "bench.poll"}
    assert all(name.startswith("bench.") for name, _ in s.idle_gaps())
