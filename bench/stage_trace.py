"""Device time per tick stage, from the profiler trace of a traced run.

* **Stages.**  The simulator runs each stage of its tick under a
  ``jax.named_scope("tick.<stage>")`` (and each ``SwitchLB`` branch under
  ``lb.<variant>``).  The scopes reach each compiled op's metadata
  ``op_name``, which the profiler copies into the trace as the op's
  ``tf_op`` stat (in the device plane's event metadata), a path such as
  ``jit(body)/while/body/closed_call/vmap(tick.rto)/tick.lb/lb.reps/...:``.
  An op's stage is the innermost ``tick.*`` of that path.
* **Fusions with no path.**  A fusion the compiler made may carry no
  metadata of its own while its fused instructions do.  The trace holds
  each program's optimized HLO too (plane ``/host:metadata``, one
  ``Hlo Proto`` per program, named by the ``program_id`` stat of each
  device op): such a fusion takes the path of its fused computation's
  root if that has a stage, else of the stage most common among its
  instructions.  Ops with no stage after that are ``unscoped``: the
  chunk's scan itself, copies the compiler makes, the quiescence poll's
  program, and ops whose lowering kept no path (``jnp.cumsum`` on the
  TPU: ``reduce_window_sum``).
* **Split.**  Ops nest on the ``XLA Ops`` line (a ``while`` holds its
  body's ops).  Inside the traced window (host span ``bench.traced``)
  every instant a device is busy goes to the innermost op running then,
  the latest to start: a ``while`` is charged only for the time none of
  its body's ops cover.  Each op's time goes to its stage, or to
  ``unscoped``, so the stage times of a device sum to its busy time
  (``trace_reduce``'s union of op intervals).
* **The trace.**  The harness hands its readers the reduced trace
  (``ctx["trace"]``) but not the file; ``find`` takes the newest
  ``bench_trace_*`` profile in the temporary directory (where
  ``bench/run.py`` writes it) whose ``bench.traced`` window is that one.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import glob
import heapq
import os
import re
import tempfile

from bench import trace_reduce

UNSCOPED = "unscoped"
LB_STAGE = "tick.lb"  # the stage whose time the ``lb.*`` scopes split
STAGE = re.compile(r"(?:^|[/(])(tick\.[A-Za-z0-9_]+)(?=[)/:]|$)")
LB = re.compile(r"(?:^|[/(])(lb\.[A-Za-z0-9_]+)(?=[)/:]|$)")
OP_PATH_STAT = "tf_op"
PROGRAM_STAT = "program_id"
HLO_PROTO_STAT = "Hlo Proto"
HLO_PLANE = "/host:metadata"


def innermost(op_path: str, pattern=STAGE) -> str | None:
    """The innermost scope ``pattern`` matches in an op's path:
    ``jit(body)/while/body/vmap(tick.rto)/tick.lb/select_n`` -> ``tick.lb``."""
    found = pattern.findall(op_path)
    return found[-1] if found else None


def innermost_split(events) -> dict:
    """``{key: ns}``: every instant covered by some ``(start, end, key)``
    event goes to the innermost event then (the latest start; of two that
    start together, the one that ends first).  The values sum to the
    length of the union of the events."""
    out = collections.defaultdict(float)
    active: list = []  # heap of (-start, end, seq, key)
    t = float("-inf")

    def run_to(limit):
        nonlocal t
        while active and t < limit:
            _, end, _, key = active[0]
            if end <= t:
                heapq.heappop(active)
                continue
            stop = min(end, limit)
            out[key] += stop - t
            t = stop

    events = sorted(events, key=lambda e: (e[0], -e[1]))
    for seq, (a, b, key) in enumerate(events):
        run_to(a)
        t = max(t, a)
        heapq.heappush(active, (-a, b, seq, key))
    run_to(float("inf"))
    return dict(out)


# ---------------------------------------------------------------------------
# The ops' paths.  ``ProfileData`` gives events but not their metadata's
# stats, so the device planes' event metadata is read from the serialized
# ``XSpace`` (tsl/profiler/protobuf/xplane.proto) by a wire-format walk.
# ---------------------------------------------------------------------------
def _varint(b: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes, i: int, j: int):
    """``(field, value)`` of the message ``b[i:j]``; a length-delimited
    value is its ``(start, end)``."""
    while i < j:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(b: bytes, span: tuple) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _stat_names(b: bytes, plane: tuple) -> dict:
    """``{stat metadata id: name}`` of a plane (map entries: key 1, value
    2; XStatMetadata name 2)."""
    out = {}
    for field, v in _fields(b, *plane):
        if field == 5:
            entry = dict(_fields(b, *v))
            out[entry[1]] = _text(b, dict(_fields(b, *entry[2]))[2])
    return out


def _event_metadata(b: bytes, plane: tuple):
    """``(id, name, [(stat name id, XStat fields)])`` per event metadata
    of a plane (map entries: key 1, value 2; XEventMetadata name 2, stats
    5)."""
    for field, v in _fields(b, *plane):
        if field != 4:
            continue
        entry = dict(_fields(b, *v))
        name, stats = None, []
        for f2, v2 in _fields(b, *entry[2]):
            if f2 == 2:
                name = _text(b, v2)
            elif f2 == 5:
                stat = dict(_fields(b, *v2))
                stats.append((stat.get(1), stat))
        yield entry.get(1), name, stats


def op_paths(path: str) -> dict:
    """``{device: {op event name: its op path}}`` of the trace at ``path``:
    the op's ``tf_op`` stat, or, where that has no stage, the path
    ``hlo_paths`` finds for the instruction in its program's HLO.  Fields:
    XSpace.planes 1; XPlane name 2, event_metadata 4, stat_metadata 5;
    XStat metadata_id 1, uint64_value 3, str_value 5, bytes_value 6,
    ref_value 7.  A name that two ops of a device share with different
    paths is left out (those ops count as unscoped)."""
    with open(path, "rb") as f:
        b = f.read()
    planes = []
    for field, plane in _fields(b, 0, len(b)):
        if field == 1:
            name = next((_text(b, v) for f2, v in _fields(b, *plane)
                         if f2 == 2), "")
            planes.append((name, plane))
    hlo = {}  # program id -> {instruction: path}, parsed when first asked
    protos = {}
    for name, plane in planes:
        if name == HLO_PLANE:
            want = {k for k, n in _stat_names(b, plane).items()
                    if n == HLO_PROTO_STAT}
            # one event metadata per program, keyed by its program id
            for program, _, stats in _event_metadata(b, plane):
                for key, stat in stats:
                    if key in want and 6 in stat:
                        proto = b[stat[6][0]:stat[6][1]]
                        # HloProto.hlo_module 1
                        a, z = dict(_fields(proto, 0, len(proto)))[1]
                        protos[program] = proto[a:z]

    def hlo_path(program, op):
        if program not in hlo:
            hlo[program] = (hlo_paths(hlo_text(protos[program]))
                            if program in protos else {})
        return hlo[program].get(trace_reduce.op_name(op))

    out = {}
    for name, plane in planes:
        m = trace_reduce.DEVICE_PLANE.match(name)
        if not m:
            continue
        names = _stat_names(b, plane)
        paths, clash = {}, set()
        for _, op, stats in _event_metadata(b, plane):
            op_path = program = None
            for key, stat in stats:
                if names.get(key) == OP_PATH_STAT:
                    op_path = (_text(b, stat[5]) if 5 in stat
                               else names.get(stat.get(7)))
                elif names.get(key) == PROGRAM_STAT:
                    program = stat.get(3)
            if op is None:
                continue
            if innermost(op_path or "") is None and program is not None:
                op_path = hlo_path(program, op) or op_path
            if op_path is None:
                continue
            if paths.setdefault(op, op_path) != op_path:
                clash.add(op)
        out[int(m.group(1))] = {k: v for k, v in paths.items()
                                if k not in clash}
    return out


def hlo_text(module: bytes) -> str:
    """The text of a serialized ``HloModuleProto``, with each instruction's
    metadata."""
    from jax._src.lib import _jax

    options = _jax.HloPrintOptions()
    options.print_metadata = True
    options.print_backend_config = False
    return _jax.HloModule.from_serialized_hlo_module_proto(module).to_string(
        options)


_HEADER = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)")
_NAME = re.compile(r"^\s+(ROOT\s+)?%([^\s=]+)\s*=")
_CALLS = re.compile(r"\bcalls=%([^\s,)]+)")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CONSTANT = re.compile(r"=\s*\S+\s+constant\(")


def hlo_paths(text: str) -> dict:
    """``{instruction: op path}`` for the instructions of an HLO module's
    text that call a computation (``calls=``: fusions, async ops) and whose
    own ``op_name`` has no stage: the path of the called computation's root
    if that has a stage, else the first path of the stage most common
    among the computation's instructions.  Constants do not count: the
    compiler shares one constant among the stages that use its value.
    Instructions with no stage either way are left out."""
    comps = {}  # computation -> the paths of its staged instructions
    roots = {}  # computation -> its root's path
    calls = {}  # instruction -> the computation it calls
    comp = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _HEADER.match(line)
            comp = m.group(1) if m and line.rstrip().endswith("{") else None
            if comp is not None:
                comps[comp] = []
            continue
        m = _NAME.match(line)
        if comp is None or not m:
            continue
        op = _OP_NAME.search(line)
        op_path = op.group(1) if op else ""
        if innermost(op_path) and not _CONSTANT.search(line):
            comps[comp].append(op_path)
        if m.group(1):
            roots[comp] = op_path
        called = _CALLS.search(line)
        if called and not innermost(op_path):
            calls[m.group(2)] = called.group(1)
    out = {}
    for fusion, comp in calls.items():
        if innermost(roots.get(comp, "")):
            out[fusion] = roots[comp]
        elif comps.get(comp):
            stages = collections.Counter(innermost(p) for p in comps[comp])
            top = stages.most_common(1)[0][0]
            out[fusion] = next(p for p in comps[comp] if innermost(p) == top)
    return out


# ---------------------------------------------------------------------------
# The split
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DeviceStages:
    """One device's busy time inside the window, split by what ran."""

    busy_ns: float = 0.0
    stage_ns: dict = dataclasses.field(default_factory=dict)  # stage -> ns
    lb_ns: dict = dataclasses.field(default_factory=dict)  # tick.lb by lb.*
    op_ns: dict = dataclasses.field(default_factory=dict)  # (op, stage) -> ns


@dataclasses.dataclass
class StageSplit:
    window: tuple  # (start_ns, end_ns) of ``bench.traced``
    devices: dict  # device id -> DeviceStages

    def mean_s(self, ns_of) -> float:
        """``ns_of(device)`` in seconds, averaged over the devices."""
        ns = sum(ns_of(d) for d in self.devices.values())
        return ns / len(self.devices) / 1e9

    def stage_s(self, stage: str) -> float:
        return self.mean_s(lambda d: d.stage_ns.get(stage, 0.0))

    def ran(self, stage: str) -> bool:
        return any(stage in d.stage_ns for d in self.devices.values())

    def names(self, field: str) -> list:
        """The stages (``"stage_ns"``) or variants (``"lb_ns"``) seen."""
        return sorted({k for d in self.devices.values()
                       for k in getattr(d, field)})


def read(path: str):
    """``({device: [(op event name, start_ns, duration_ns)]}, window)`` of
    the trace at ``path``: ``trace_reduce.read``'s ops, with the events'
    whole names (the instruction text ``op_paths`` is keyed by), and the
    first ``bench.traced`` span."""
    from jax.profiler import ProfileData

    ops, window = {}, None
    for plane in ProfileData.from_file(path).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            evs = ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    evs.extend((e.name, float(e.start_ns),
                                float(e.duration_ns)) for e in line.events)
        elif plane.name.startswith("/host:") and window is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace_reduce.WINDOW_SPAN and window is None:
                        a = float(e.start_ns)
                        window = (a, a + float(e.duration_ns))
    if window is None:
        raise ValueError(f"no {trace_reduce.WINDOW_SPAN!r} host span")
    return ops, window


def split(ops: dict, window: tuple, paths: dict) -> StageSplit:
    """Split each device's busy time in ``window`` by tick stage (see the
    module docstring): ``ops`` as ``read`` returns them, ``paths`` as
    ``op_paths`` does."""
    w0, w1 = window
    out = {}
    for dev, evs in sorted(ops.items()):
        known = paths.get(dev, {})
        events = []
        for name, a, dur in evs:
            a, b = max(a, w0), min(a + dur, w1)
            if b > a:
                events.append((a, b, name))
        if not events:
            continue
        d = DeviceStages()
        for name, ns in innermost_split(events).items():
            op_path = known.get(name, "")
            stage = innermost(op_path) or UNSCOPED
            d.busy_ns += ns
            d.stage_ns[stage] = d.stage_ns.get(stage, 0.0) + ns
            key = (trace_reduce.op_name(name), stage)
            d.op_ns[key] = d.op_ns.get(key, 0.0) + ns
            lb = innermost(op_path, LB)
            if lb and stage == LB_STAGE:
                d.lb_ns[lb] = d.lb_ns.get(lb, 0.0) + ns
        out[dev] = d
    return StageSplit((w0, w1), out)


@functools.lru_cache(maxsize=2)
def reduce(path: str) -> StageSplit:
    """The split of the trace at ``path``."""
    return split(*read(path), op_paths(path))


def find(ctx) -> StageSplit | None:
    """The split of the trace behind ``ctx["trace"]``: the newest
    ``bench_trace_*`` profile in the temporary directory whose window is
    the reduced trace's; ``None`` where there is none."""
    pattern = os.path.join(tempfile.gettempdir(), "bench_trace_*", "**",
                           "*.xplane.pb")
    for path in sorted(glob.glob(pattern, recursive=True),
                       key=os.path.getmtime, reverse=True):
        s = reduce(path)
        if s.window == tuple(ctx["trace"].window):
            return s if s.devices else None
    return None


def us_per_row_tick(ctx, stage: str) -> float | None:
    """``stage``'s device time per simulated row-tick in the traced chunk
    (us), averaged over the chips, over the row-ticks that
    ``tick.device_us_per_row_tick`` divides by; ``None`` where no op of
    the stage ran or the trace is not found."""
    s = find(ctx)
    if s is None or not s.ran(stage):
        return None
    traced = ctx["window"].traced
    return 1e6 * s.stage_s(stage) / (traced["ticks"] * traced["rows"])
