"""The reader of ``route.us_per_row_tick``: which ops are the next hop's.

A fusion is the route's where more than half of its staged instructions
hold a ``route.*`` scope (constants aside), whatever its own path names; on
a trace of a program without the scope (``testdata/v5e_stages.xplane.pb``,
recorded before the scope was added) the reader reads nothing and does not
raise.  That the compiled chunk program holds such a fusion is in
``tests/test_tpu_compile.py``.
"""
import pathlib
import shutil
import tempfile
import types

from bench import extensions, trace_reduce

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE = ROOT / "bench" / "testdata" / "v5e_stages.xplane.pb"
ARR = "jit(body)/while/body/closed_call/vmap(tick.arrivals)"
HLO = f"""HloModule jit_body, is_scheduled=true

%fused_route (p.1: s32[8]) -> s32[8] {{
  %p.1 = s32[8]{{0}} parameter(0)
  %c.1 = s32[8]{{0}} constant({{0,0,0,0,0,0,0,0}}), metadata={{op_name="{ARR}/broadcast_in_dim"}}
  %a.1 = s32[8]{{0}} add(%p.1, %p.1), metadata={{op_name="{ARR}/route.next_hop/add"}}
  %m.1 = s32[8]{{0}} multiply(%a.1, %p.1), metadata={{op_name="{ARR}/route.next_hop/mul"}}
  ROOT %g.1 = s32[8]{{0}} or(%m.1, %c.1), metadata={{op_name="{ARR}/gather"}}
}}

%fused_half (p.2: s32[8]) -> s32[8] {{
  %p.2 = s32[8]{{0}} parameter(0)
  %a.2 = s32[8]{{0}} add(%p.2, %p.2), metadata={{op_name="{ARR}/route.next_hop/add"}}
  ROOT %s.2 = s32[8]{{0}} subtract(%a.2, %p.2), metadata={{op_name="{ARR}/sub"}}
}}

ENTRY %main.9 (x.9: s32[8]) -> s32[8] {{
  %x.9 = s32[8]{{0}} parameter(0)
  %fusion.1 = s32[8]{{0}} fusion(%x.9), kind=kLoop, calls=%fused_route, metadata={{op_name="{ARR}/gather"}}
  ROOT %fusion.2 = s32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused_half, metadata={{op_name="{ARR}/sub"}}
}}
"""


def reader():
    return extensions.find(ROOT, "metrics", "route.us_per_row_tick")


def test_a_fusion_mostly_of_the_route_is_the_routes():
    """Two route instructions of three staged ones (the constant does not
    count): the fusion is the route's, under its own path; one of two is
    not more than half."""
    assert reader().routed_fusions(HLO) == {("fusion.1", f"{ARR}/gather")}


def test_a_trace_without_the_scope_reads_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    target = tmp_path / "bench_trace_1" / "plugins" / "profile" / "t"
    target.mkdir(parents=True)
    shutil.copy(TRACE, target / "host.xplane.pb")
    window = types.SimpleNamespace(traced={"ticks": 2, "rows": 6})
    ctx = {"trace": trace_reduce.reduce(str(TRACE)), "window": window}
    r = reader()
    assert len(list(r._modules(str(TRACE)))) == 3  # the trace's programs
    assert r.read(ctx) is None
