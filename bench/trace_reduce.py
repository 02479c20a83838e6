"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

* Device planes are the planes named ``/device:TPU:<n>``; their operations
  are the events of the line ``XLA Ops`` (the TensorCore's ops; the DMA
  copies on ``Async XLA Ops`` overlap them and are not counted).  An event
  is named by its HLO instruction text, ``%<name> = <shape> <op>(...)``;
  the reduction keeps ``<name>``.  Busy time is the union of the op
  intervals inside the traced window, idle time the rest.
* Operations are put in categories by their HLO instruction name, which
  the trace's op events carry: the Mosaic kernels (Pallas
  ``tpu_custom_call``s) and the collectives (all-gather, all-reduce,
  reduce-scatter, all-to-all, collective-permute, and their async halves)
  are named from the compiled program's HLO text (``program_ops``).  A
  Mosaic op is named after its jitted wrapper (``seg_rank_pallas.3``), so
  kernel time is also split by kernel (``kernel_of``).
* Host spans are the host events whose name starts with ``bench.`` (the
  harness's ``jax.profiler.TraceAnnotation``s).  The window is the span
  ``bench.traced``; each idle gap of a device is attributed to the
  innermost ``bench.*`` span under its midpoint.

``ProfileData`` puts host and device events on one clock (nanoseconds).
"""
from __future__ import annotations

import collections
import dataclasses
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.traced"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?(\.\d+)?$"
)


@dataclasses.dataclass
class DeviceOps:
    """One device's operations inside the window."""

    busy_ns: float = 0.0  # union of op intervals
    kernel_ns: float = 0.0  # Mosaic kernels (sum of durations)
    kernel_by: dict = dataclasses.field(default_factory=dict)  # kernel -> ns
    collective_ns: float = 0.0
    op_ns: dict = dataclasses.field(default_factory=dict)  # name -> sum
    gaps: list = dataclasses.field(default_factory=list)  # (start, end)


@dataclasses.dataclass
class TraceSummary:
    window_ns: float
    window: tuple  # (start_ns, end_ns)
    devices: dict  # device id -> DeviceOps
    spans: list  # (name, start_ns, end_ns) host spans in the window

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the devices."""
        return float(np.mean([d.busy_ns for d in self.devices.values()])) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` device operations that took most time, in seconds
        averaged over the devices."""
        tot = collections.Counter()
        for d in self.devices.values():
            tot.update(d.op_ns)
        k = len(self.devices)
        return [[name, ns / k / 1e9] for name, ns in tot.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest idle gaps of any device, each named by the
        innermost ``bench.*`` host span under its midpoint."""
        gaps = sorted(
            (g for d in self.devices.values() for g in d.gaps),
            key=lambda g: g[0] - g[1],
        )[:n]
        return [[self.span_at((a + b) / 2), (b - a) / 1e9] for a, b in gaps]

    def span_at(self, t: float) -> str:
        best = None
        for name, a, b in self.spans:
            if a <= t <= b and (best is None or b - a < best[2] - best[1]):
                best = (name, a, b)
        return best[0] if best else "none"


KERNEL_NAME = re.compile(r"^(.*?)(?:_pallas)?(?:\.\d+)?$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=")
_COLLECTIVE_OP = re.compile(
    r"\s(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\("
)


def op_name(text: str) -> str:
    """``%fusion.7 = s32[8] fusion(...)`` -> ``fusion.7``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def kernel_of(name: str) -> str:
    """A Mosaic op's kernel, from the name of its jitted wrapper:
    ``queue_tick_pallas.10`` -> ``queue_tick``."""
    return KERNEL_NAME.match(name).group(1)


def program_ops(hlo_texts) -> tuple[set, set]:
    """Names of the Mosaic kernel and collective instructions in compiled
    HLO text: ``(kernels, collectives)``."""
    kernels, collectives = set(), set()
    for text in hlo_texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if not m:
                continue
            if 'custom_call_target="tpu_custom_call"' in line:
                kernels.add(m.group(1))
            elif _COLLECTIVE_OP.search(line):
                collectives.add(m.group(1))
    return kernels, collectives


def _union(intervals: list) -> tuple[float, list]:
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals in order."""
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def read(path: str):
    """Device operations and ``bench.*`` host spans of the trace at ``path``:
    ``({device: [(name, start_ns, duration_ns)]}, [(name, start_ns,
    end_ns)])``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = []
    ops = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = ops.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        evs.append((op_name(e.name), float(e.start_ns),
                                    float(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        a = float(e.start_ns)
                        spans.append((e.name, a, a + float(e.duration_ns)))
    return ops, spans


def reduce(path: str, kernels=frozenset(), collectives=frozenset()):
    """Read the trace at ``path`` and reduce it (see the module docstring)."""
    return summarize(*read(path), kernels, collectives)


def summarize(ops: dict, spans: list, kernels=frozenset(),
              collectives=frozenset()) -> TraceSummary:
    """Reduce device operations and host spans (as ``read`` returns them)
    to the window's busy, idle, kernel and collective time; ``kernels`` and
    ``collectives`` name the ops of each category (``program_ops``)."""
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} host span in the trace")
    w0, w1 = win[0][1], win[0][2]
    devices = {}
    for dev, evs in sorted(ops.items()):
        if not evs:
            continue
        d = DeviceOps()
        ivs = []
        for name, a, dur in evs:
            b = a + dur
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            d.op_ns[name] = d.op_ns.get(name, 0.0) + (b - a)
            if name in collectives or COLLECTIVE.match(name):
                d.collective_ns += b - a
            elif name in kernels:
                d.kernel_ns += b - a
                k = kernel_of(name)
                d.kernel_by[k] = d.kernel_by.get(k, 0.0) + (b - a)
        d.busy_ns, merged = _union(ivs)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        d.gaps = [
            (edges[i], edges[i + 1])
            for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        ]
        devices[dev] = d
    if not devices:
        raise ValueError(f"no device operations on a {OPS_LINE!r} line")
    inside = [s for s in spans
              if s[2] > w0 and s[1] < w1 and s[0] != WINDOW_SPAN]
    return TraceSummary(w1 - w0, (w0, w1), devices, inside)
