"""Device time of the next-hop decision of arriving packets (the ``route.*``
scopes: ``route.next_hop`` inside ``tick.arrivals``) per simulated row-tick
in the traced chunk (us), averaged over the chips, over the row-ticks
``tick.device_us_per_row_tick`` divides by.

An op is the route's where its path (``stage_trace.op_paths``) holds a
``route.*`` scope, or where it is a fusion most of whose staged
instructions do: ``stage_trace``'s rule for a fusion without a stage of
its own, applied to the route.  The TPU compiler fuses the next-hop
arithmetic with the index of the gather that reads its result, and names
the fusion after that gather: one fusion a tick, most of whose
instructions are the route's.  Each op's time is its innermost time
(``stage_trace.split``).  Nothing to read where no such op ran (a program
without the scope)."""
import glob
import os
import re
import tempfile

from bench import stage_trace, trace_reduce

ROUTE = re.compile(r"(?:^|[/(])(route\.[A-Za-z0-9_]+)(?=[)/:]|$)")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)")
_NAME = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+)\s*=")
_CALLS = re.compile(r"\bcalls=%([^\s,)]+)")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CONSTANT = re.compile(r"=\s*\S+\s+constant\(")


def routed_fusions(text: str) -> set:
    """``{(instruction, its own op path)}`` of an HLO module's text: the
    instructions that call a computation more than half of whose staged
    instructions (a ``tick.*`` path, constants aside) hold a ``route.*``
    scope."""
    staged, routed, calls = {}, {}, []
    comp = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _HEADER.match(line)
            comp = m.group(1) if m and line.rstrip().endswith("{") else None
            continue
        m = _NAME.match(line)
        if comp is None or not m:
            continue
        op = _OP_NAME.search(line)
        path = op.group(1) if op else ""
        if stage_trace.innermost(path) and not _CONSTANT.search(line):
            staged[comp] = staged.get(comp, 0) + 1
            routed[comp] = routed.get(comp, 0) + bool(ROUTE.search(path))
        called = _CALLS.search(line)
        if called:
            calls.append((m.group(1), path, called.group(1)))
    return {(name, path) for name, path, comp in calls
            if 2 * routed.get(comp, 0) > staged.get(comp, 0)}


def _modules(path: str):
    """The HLO text of each program the trace at ``path`` holds (plane
    ``/host:metadata``, one ``Hlo Proto`` stat per program; the fields
    as ``stage_trace.op_paths`` reads them)."""
    with open(path, "rb") as f:
        b = f.read()
    fields, text = stage_trace._fields, stage_trace._text
    for field, plane in fields(b, 0, len(b)):
        name = next((text(b, v) for f2, v in fields(b, *plane) if f2 == 2),
                    "") if field == 1 else ""
        if name != stage_trace.HLO_PLANE:
            continue
        want = {k for k, n in stage_trace._stat_names(b, plane).items()
                if n == stage_trace.HLO_PROTO_STAT}
        for _, _, stats in stage_trace._event_metadata(b, plane):
            for key, stat in stats:
                if key in want and 6 in stat:
                    proto = b[stat[6][0]:stat[6][1]]
                    a, z = dict(fields(proto, 0, len(proto)))[1]
                    yield stage_trace.hlo_text(proto[a:z])


def _trace_path(ctx):
    """The newest ``bench_trace_*`` profile whose window is the reduced
    trace's (the one ``stage_trace.find`` splits)."""
    pattern = os.path.join(tempfile.gettempdir(), "bench_trace_*", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        if stage_trace.reduce(path).window == tuple(ctx["trace"].window):
            return path
    return None


def read(ctx):
    path = _trace_path(ctx)
    if path is None:
        return None
    split = stage_trace.reduce(path)
    fused = set().union(*(routed_fusions(t) for t in _modules(path)))
    ns = 0.0
    for dev, paths in stage_trace.op_paths(path).items():
        # a path in the trace is the op's ``op_name`` and ``:`` its type
        routed = {trace_reduce.op_name(name) for name, p in paths.items()
                  if ROUTE.search(p) or (trace_reduce.op_name(name),
                                         p.rsplit(":", 1)[0]) in fused}
        d = split.devices.get(dev)
        if d is not None:
            ns += sum(t for (op, _), t in d.op_ns.items() if op in routed)
    if ns <= 0:
        return None
    traced = ctx["window"].traced
    return ns / len(split.devices) / 1e3 / (traced["ticks"] * traced["rows"])
