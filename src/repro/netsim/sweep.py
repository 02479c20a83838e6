"""Sweep engine: shape-bucketed multi-scenario fleets in a few compiled calls.

The paper's headline figures sweep workloads × load balancers × seeds ×
failure schedules; serially that costs one trace + compile + scan per cell.
This module batches *heterogeneous* cells instead:

  1. **Quantization** — cells are described by their padded static shapes
     ``(ticks, adaptive, NC, MSG, F, W)``: conn counts and message-bitmap
     widths round up to powers of two, failure schedules drop events that
     are provably dead before the horizon (``failures.truncate_dead``) and
     pad to the bucket max, watch lists pad to the bucket max.  Within a
     bucket every cell compiles to the *same* jaxpr, so the whole bucket is
     one ``lax.scan``.
  2. **Cost-aware packing** (``pack``) — pure, host-side, inspectable:

     * *merge*: shape groups whose padded union costs at most
       ``PackerConfig.waste_budget`` more than the sum of their native
       costs fuse into one bucket (greedy lowest-waste pair first).  The
       cost model (``est_row_tick_cost``) is a gather/scatter footprint
       proxy: packet-table slots + per-conn bitmaps + event one-hots +
       schedule/watch rows, times the tick horizon.  Merging may fuse
       *different tick horizons*: the bucket scans to the max and each row
       freezes bit-exactly at its own horizon (see 4).
     * *split*: groups larger than ``max_rows_per_bucket`` rows split into
       equal-capacity sub-buckets (cells stay atomic).  Sub-buckets of one
       group share padded shapes *and* padded row count, so they reuse one
       compiled program — splitting bounds device memory, not compiles.
     * *device alignment*: bucket rows pad to a multiple of the sweep mesh
       so ``shard_map`` assigns every device the same row count (rows of a
       bucket cost the same, so equal rows ⇒ balanced cost).

     The resulting ``PackPlan`` (→ ``SweepEngine.plan``) is a dataclass
     tree that tests and benchmarks assert on: cell→bucket coverage,
     per-bucket ``merge_waste``, pad rows, device row assignment.
  3. **Neutral padding** — padded conns never start (start tick 2^29),
     padded failure rows are inert (start == end == 0; semantics and the
     never-resurrect invariant live on ``FailureSchedule``), and the
     derived static sizes a padded table would perturb are pinned via
     ``SimConfig.msg_slots`` / ``conns_per_host`` / ``failure_slots`` so
     the *serial reference* (``serial_sim``) builds bit-identical shapes.
     Every sweep row is bit-identical to ``Simulator.run`` on that
     reference (tests/test_sweep.py, tests/test_figure_parity.py).
  4. **Per-row horizons** — when a bucket fuses cells with different tick
     horizons, each row carries its own horizon and the scan body freezes
     the row's carry once ``tick >= horizon`` (a ``where`` on every state
     leaf; skipped entirely for homogeneous buckets).  A frozen row is
     bit-identical to stopping its serial run at that tick.
  5. **LB dispatch** — cells that differ only in load balancer share the
     bucket through ``SwitchLB``: one ``lax.switch`` branch index per row
     selects the variant, so ECMP/OPS/REPS columns cost one compilation.
     In-network adaptive LBs change the routing function (a static
     property) and never merge with endpoint LBs.
  6. **(scenario, seed) vmap + device sharding** — rows are the product of
     cells and seeds; ``Simulator.step_scenario`` vmaps over the row axis
     and, when more than one device is visible, rows shard across a 1-D
     ``shard_map`` mesh (CPU CI materializes devices with
     ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
  7. **Donated chunked execution** — the scan carry is donated per chunk
     and trace chunks stream to the host; ``collect="none"`` drops trace
     emission entirely (the fast path benchmarks use), and quiescence
     early exit skips post-fixed-point chunks without changing any
     reported metric.
  8. **Telemetry sketch channels** — ``collect="summary"`` folds a
     ``TelemetrySpec`` (repro.netsim.telemetry) into the scan: each row
     carries ONE stacked int32 sketch vector (FCT/qlen histograms,
     windowed link utilization, recovery trackers, exact counters) updated
     by pure ``(carry, probe) -> carry`` reducers.  Host traffic drops
     from O(rows × ticks) to O(rows × bins), and — because reducers are
     no-ops on quiescent ticks — summary collection composes with
     ``early_exit=True``, which raw trace streaming cannot.

Example (one compiled call per shape bucket, not per cell):

    cases = [SweepCase(f"fig02/{w}/{lb}", wl, lb, ticks=4000)
             for w, wl in wls.items() for lb in ("ecmp", "ops", "reps")]
    eng = SweepEngine(cfg, cases)
    print(eng.plan.describe())
    result = eng.run()
    for name, summaries in result.summaries().items(): ...
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.load_balancers import SwitchLB, make_lb
from repro.distrib.sharding import (
    CONN_AXIS, SWEEP_AXIS, resolve_kernels_backend, sweep_conn_mesh,
    sweep_mesh,
)
from repro.netsim.config import SimConfig
from repro.netsim.engine import (
    FailureSchedule, ScenarioArrays, Simulator, SimState, Workload,
)
from repro.netsim.failures import truncate_dead
from repro.netsim.metrics import RunSummary, summarize, summarize_sketch
from repro.netsim.telemetry import TelemetrySpec
from repro.netsim.tracer import TraceSpec
from repro.utils.spans import span

# padded conns start here: far beyond any sweep horizon, still well inside
# int32 so `now >= start` arithmetic cannot wrap.
NEVER_TICK = 2**29


def _pow2(n: int) -> int:
    return int(2 ** np.ceil(np.log2(max(int(n), 1))))


@dataclasses.dataclass(frozen=True)
class SweepCase:
    """One cell of a sweep grid: a scenario structure plus its seeds."""

    name: str
    workload: Workload
    lb: str  # load-balancer registry name
    ticks: int
    lb_kwargs: dict = dataclasses.field(default_factory=dict)
    failures: FailureSchedule | None = None
    watch_queues: Any = None  # None = topology default
    seeds: tuple[int, ...] = (0,)


# ---------------------------------------------------------------------------
# Cost-aware bucket packer.  Pure host-side planning over quantized cell
# shapes — no jax, no Simulator construction — so property tests can hammer
# it with random grids (tests/test_sweep.py).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PackerConfig:
    """Knobs for ``pack``.

    * ``max_rows_per_bucket`` — split threshold: a bucket's (cells × seeds)
      row count beyond this splits into sub-buckets sharing one compiled
      program.  A single cell larger than the threshold stays atomic (one
      oversized bucket).
    * ``waste_budget`` — max fractional padded-cost overhead a merged
      bucket may carry over the sum of its members' native costs
      (``BucketPlan.merge_waste``).  0 disables all padding-for-merging
      but still fuses bit-identical shapes.
    * ``merge`` — disable to reproduce pure shape quantization (one bucket
      per distinct quantized shape, the pre-packer behavior).
    """

    max_rows_per_bucket: int = 1024
    waste_budget: float = 0.25
    merge: bool = True


@dataclasses.dataclass(frozen=True)
class CellShape:
    """What the packer sees of a cell: quantized static shapes + row count.

    ``nc``/``msg``/``f``/``w`` are the cell's *own* padded sizes (pow2
    conns, pow2 message bitmap, live failure rows, watch rows); ``rows`` is
    its seed count.  Merging never mutates a CellShape — native costs are
    always measured on these original shapes.

    ``nc_exact`` is the unquantized conn count.  Grouping and cost compare
    the pow2 ``nc`` (so near-sized cells land together), but the bucket is
    finally sized to the *max exact* conn count of its members: conn
    padding is visible to spraying LBs through their per-conn random draw
    shapes (jax threefry pairs counter i with i + n/2, so a (480,) draw and
    a (512,) draw differ everywhere), and shrink-to-fit keeps the largest
    cell of every bucket — and any solo-shape figure column — bit-identical
    to a *raw* unpadded serial run, not just to the padded reference.
    """

    name: str
    ticks: int
    adaptive: bool
    nc: int
    msg: int
    f: int
    w: int
    rows: int
    nc_exact: int = 0  # 0 = same as nc

    @property
    def key(self) -> tuple:
        return (self.ticks, self.adaptive, self.nc, self.msg, self.f, self.w)


def est_row_tick_cost(
    cfg: SimConfig, nc: int, msg: int, f: int, w: int
) -> float:
    """Estimated cost of one row-tick at the given padded shapes.

    The tick body is gather/scatter-bound (engine.py header), so the proxy
    counts array footprint touched per tick rather than FLOPs: the packed
    packet table (NP slots, pow2 of conns × max cwnd + host slack), the
    per-conn message bitmaps (NC × MSG, touched via event scatters at ~1/8
    density), the feedback/delivery segment tables (MAX_EV ≈ 3·NH events ×
    NC+1 segments), and the linear schedule/watch rows.  Only *relative*
    cost matters — the packer compares merged vs native sums of this
    estimate (or of the measured-cost model, see ``measured_costs_from_bench``).
    """
    np_slots = _pow2(nc * cfg.max_cwnd_pkts + 4 * cfg.n_hosts + 64)
    max_ev = 3 * cfg.n_hosts
    return float(np_slots + nc * msg / 8.0 + max_ev * (nc + 1) / 8.0 + f + w)


def measured_costs_from_bench(path_or_rows) -> dict:
    """Harvest the packer's measured-cost feedback from a benchmark file.

    Args:
        path_or_rows: path to a ``BENCH_netsim.json`` (or its already-loaded
            ``rows`` dict).  The PackPlan-keyed ``{fig}/bucket/*`` rows that
            ``benchmarks/common.figure_grid`` emits carry ``bucket_key =
            [ticks, adaptive, nc, msg, f, w]`` next to the *measured*
            ``measured_row_tick_us`` wall-clock of that bucket's scan.

    Returns:
        ``{(adaptive, pow2(nc), msg, f, w): mean measured_row_tick_us}`` —
        the per-row-tick cost is horizon-independent, so ``ticks`` is
        dropped; ``nc`` quantizes to the pow2 grouping grid because bucket
        keys record the shrink-to-fit *exact* conn count while the packer's
        merge decisions compare pow2-quantized shapes.  Multiple samples of
        one shape (several figures / sub-buckets) average.  Missing or
        malformed files yield ``{}`` (the packer then falls back to
        ``est_row_tick_cost`` everywhere).
    """
    rows = path_or_rows
    if not isinstance(rows, dict):
        import json

        try:
            with open(path_or_rows) as fh:
                rows = json.load(fh).get("rows", {})
        except (OSError, ValueError, AttributeError):
            return {}
    acc: dict[tuple, list] = {}
    if not isinstance(rows, dict):
        return {}
    for name, rec in rows.items():
        if "/bucket/" not in str(name) or not isinstance(rec, dict):
            continue
        key = rec.get("bucket_key")
        us = rec.get("measured_row_tick_us")
        try:
            _t, ad, nc, msg, f, w = key
            k = (bool(ad), _pow2(nc), int(msg), int(f), int(w))
            us = float(us)
        except (TypeError, ValueError):  # malformed row: skip, don't abort
            continue
        if us > 0:
            acc.setdefault(k, []).append(us)
    return {k: sum(v) / len(v) for k, v in acc.items()}


def _cost_model(cfg: SimConfig, measured: dict | None):
    """Per-row-tick cost function for ``pack``: measured µs where a shape
    was benchmarked, the footprint estimate *calibrated to µs* elsewhere
    (scale = median measured/estimate ratio over the measured keys, so
    mixing the two inside one merge comparison stays unit-consistent).
    Deterministic: pure arithmetic over the sorted measured dict."""
    if not measured:
        return lambda ad, nc, msg, f, w: est_row_tick_cost(cfg, nc, msg, f, w)
    ratios = sorted(
        us / max(est_row_tick_cost(cfg, *k[1:]), 1e-9)
        for k, us in measured.items()
    )
    scale = ratios[len(ratios) // 2]

    def cost(ad, nc, msg, f, w):
        hit = measured.get((ad, nc, msg, f, w))
        if hit is None:
            hit = measured.get((ad, _pow2(nc), msg, f, w))
        if hit is not None:
            return hit
        return scale * est_row_tick_cost(cfg, nc, msg, f, w)

    return cost


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """One planned bucket: a set of cells sharing padded shapes + horizon.

    ``key = (ticks, adaptive, nc, msg, f, w)`` is the padded union shape;
    ``group`` identifies the split family — buckets with equal ``group``
    share padded shapes *and* ``n_padded_rows`` and therefore one compiled
    program.  ``native_cost`` sums the members' costs at their own
    quantized shapes/horizons, so ``merge_waste`` isolates the padding
    overhead the packer accepted to fuse them.
    """

    key: tuple
    cells: tuple[str, ...]
    group: int
    n_rows: int
    n_padded_rows: int
    n_devices: int
    est_row_cost: float  # one padded row over the full bucket horizon
    native_cost: float

    @property
    def ticks(self) -> int:
        return self.key[0]

    @property
    def est_cost(self) -> float:
        return self.n_rows * self.est_row_cost

    @property
    def merge_waste(self) -> float:
        """Fractional padded-cost overhead from shape/horizon merging
        (row padding excluded — see ``pad_rows``)."""
        return self.est_cost / max(self.native_cost, 1e-9) - 1.0

    @property
    def pad_rows(self) -> int:
        return self.n_padded_rows - self.n_rows

    @property
    def device_rows(self) -> tuple[int, ...]:
        """Rows per mesh device (shard_map splits the padded row axis
        evenly; rows of one bucket cost the same, so equal rows ⇒ balanced
        estimated tick cost)."""
        per = self.n_padded_rows // self.n_devices
        return (per,) * self.n_devices


@dataclasses.dataclass(frozen=True)
class PackPlan:
    """The packer's full output — inspect via ``SweepEngine.plan``.

    A pure host-side dataclass tree (no jax arrays): ``buckets`` is the
    ordered tuple of :class:`BucketPlan` rows the engine will materialize,
    ``n_devices`` the mesh width every bucket's rows were padded for, and
    ``packer`` the :class:`PackerConfig` that produced the plan.

    Invariants (property-tested): cells covered exactly once across
    ``buckets``; per split-group aggregate ``merge_waste`` ≤ the packer's
    budget (``group_merge_waste()``); every ``n_padded_rows`` divisible by
    ``n_devices``.  Plans are deterministic in (cfg, shapes, packer,
    n_devices, measured_costs) — replanning with identical inputs yields
    an identical (``==``) plan, which is what lets benchmark files key
    rows by plan shape.  ``describe()`` renders the human-readable form.
    """

    buckets: tuple[BucketPlan, ...]
    n_devices: int
    packer: PackerConfig

    @property
    def n_cells(self) -> int:
        return sum(len(b.cells) for b in self.buckets)

    @property
    def n_rows(self) -> int:
        return sum(b.n_rows for b in self.buckets)

    @property
    def n_padded_rows(self) -> int:
        return sum(b.n_padded_rows for b in self.buckets)

    @property
    def n_groups(self) -> int:
        return len({b.group for b in self.buckets})

    @property
    def merge_waste(self) -> float:
        native = sum(b.native_cost for b in self.buckets)
        est = sum(b.est_cost for b in self.buckets)
        return est / max(native, 1e-9) - 1.0

    def group_merge_waste(self) -> dict[int, float]:
        """Per split-group aggregate waste — the level the budget is
        enforced at (an individual sub-bucket holding only the group's
        shortest-horizon cells can sit above it)."""
        est: dict[int, float] = {}
        native: dict[int, float] = {}
        for b in self.buckets:
            est[b.group] = est.get(b.group, 0.0) + b.est_cost
            native[b.group] = native.get(b.group, 0.0) + b.native_cost
        return {
            g: est[g] / max(native[g], 1e-9) - 1.0 for g in est
        }

    def describe(self) -> str:
        lines = [
            f"PackPlan: {self.n_cells} cells -> {len(self.buckets)} buckets "
            f"({self.n_groups} compiled programs, {self.n_devices} devices, "
            f"waste {self.merge_waste:+.1%})"
        ]
        for b in self.buckets:
            t, ad, nc, msg, f, w = b.key
            lines.append(
                f"  g{b.group} ticks={t} adaptive={int(ad)} NC={nc} MSG={msg} "
                f"F={f} W={w} rows={b.n_rows}+{b.pad_rows}pad "
                f"waste={b.merge_waste:+.1%} cells={list(b.cells)}"
            )
        return "\n".join(lines)


@dataclasses.dataclass
class _Group:
    shapes: list[CellShape]

    def key(self) -> tuple:
        ks = [s.key for s in self.shapes]
        return (
            max(k[0] for k in ks), ks[0][1], max(k[2] for k in ks),
            max(k[3] for k in ks), max(k[4] for k in ks),
            max(k[5] for k in ks),
        )

    def fit_key(self) -> tuple:
        """The bucket's final key: NC shrunk to the members' max *exact*
        conn count (see CellShape.nc_exact) — quantized NC is a grouping /
        cost artifact, not a shape the scan has to pay (or perturb RNG
        streams) for."""
        k = self.key()
        nc_fit = max(max(s.nc_exact or s.nc, 1) for s in self.shapes)
        return (k[0], k[1], nc_fit, *k[3:])

    def rows(self) -> int:
        return sum(s.rows for s in self.shapes)


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def pack(
    cfg: SimConfig,
    shapes: Sequence[CellShape],
    packer: PackerConfig = PackerConfig(),
    n_devices: int = 1,
    measured_costs: dict | None = None,
) -> PackPlan:
    """Plan buckets for quantized cell shapes (pure; deterministic).

    Args:
        cfg: the sweep's base :class:`SimConfig` (only static sizing fields
            feed the cost model).
        shapes: one :class:`CellShape` per cell — quantized padded shapes
            plus the cell's seed-row count.  Names must be unique.
        packer: merge/split knobs, see :class:`PackerConfig`.
        n_devices: sweep mesh size; bucket rows pad to a multiple of it.
        measured_costs: optional ``{(adaptive, nc, msg, f, w): µs}`` map of
            *measured* per-row-tick wall-clock (the PackPlan-keyed
            ``{fig}/bucket/*`` rows of ``BENCH_netsim.json`` — build it with
            :func:`measured_costs_from_bench`).  Where a candidate shape was
            benchmarked its measured cost replaces the footprint estimate in
            every merge comparison; unbenchmarked shapes fall back to the
            estimate calibrated to µs (median measured/estimate ratio), so
            the two are unit-compatible.  ``None``/``{}`` = pure estimate.

    Returns:
        A :class:`PackPlan` — a pure dataclass tree (no jax arrays) that
        ``SweepEngine`` materializes and that tests/benchmarks assert on.

    Invariants (property-tested in tests/test_sweep.py):
      * every cell lands in exactly one bucket;
      * ``n_rows <= max(max_rows_per_bucket, largest cell) + n_devices - 1``
        for every bucket (cells are atomic; capacities are device-rounded);
      * aggregate ``merge_waste <= waste_budget`` for every split group
        (``PackPlan.group_merge_waste`` — the merge decision's level; a
        single sub-bucket of a heterogeneous group can sit above it) under
        whichever cost model (estimated or measured) planned it;
      * ``n_padded_rows`` is a multiple of ``n_devices`` and every device
        is assigned exactly ``n_padded_rows / n_devices`` rows;
      * planning is deterministic: identical inputs (including the
        ``measured_costs`` dict) reproduce the identical plan.

    Note on bit-parity: the plan decides each bucket's padded conn count
    (shrink-to-fit to its members' max *exact* conn count).  Conn padding
    is RNG-visible to spraying load balancers — jax threefry draws are
    **not prefix-stable** (a ``(480,)`` uniform draw shares no prefix with
    a ``(512,)`` draw), so two plans that bucket a cell differently can
    both be *self*-consistent yet produce different per-cell streams.
    Every plan is bit-identical to its own ``serial_sim`` reference; only
    cells whose exact conn count equals their bucket's fit size are
    additionally bit-identical to a *raw* unpadded run.
    """
    assert n_devices >= 1
    assert shapes, "need at least one cell"
    names = [s.name for s in shapes]
    assert len(set(names)) == len(names), "cell names must be unique"
    cost_fn = _cost_model(cfg, measured_costs)

    def _cell_cost(s: CellShape) -> float:
        return s.rows * s.ticks * cost_fn(s.adaptive, s.nc, s.msg, s.f, s.w)

    # 1. exact-shape grouping (insertion order kept for determinism)
    by_key: dict[tuple, _Group] = {}
    for s in shapes:
        by_key.setdefault(s.key, _Group(shapes=[])).shapes.append(s)
    groups = list(by_key.values())

    def native(g: _Group) -> float:
        return sum(_cell_cost(s) for s in g.shapes)

    def est(key: tuple, rows: int) -> float:
        t, ad, nc, msg, f, w = key
        return rows * t * cost_fn(ad, nc, msg, f, w)

    # 2. greedy lowest-waste pairwise merging under the budget.  Group
    #    key/rows/native are additive under merge, so they are memoized and
    #    updated incrementally — the pair search is O(1) per pair instead
    #    of re-summing per-cell costs.
    keys = [g.key() for g in groups]
    rows = [g.rows() for g in groups]
    natives = [native(g) for g in groups]

    def merged_key(a: tuple, b: tuple) -> tuple:
        return (
            max(a[0], b[0]), a[1], max(a[2], b[2]), max(a[3], b[3]),
            max(a[4], b[4]), max(a[5], b[5]),
        )

    while packer.merge and len(groups) > 1:
        best = None  # (waste, i, j)
        for i in range(len(groups)):
            for j in range(i + 1, len(groups)):
                if keys[i][1] != keys[j][1]:
                    continue  # adaptive routing is a static property
                k = merged_key(keys[i], keys[j])
                waste = est(k, rows[i] + rows[j]) / max(
                    natives[i] + natives[j], 1e-9
                ) - 1.0
                if waste <= packer.waste_budget and (
                    best is None or waste < best[0] - 1e-12
                ):
                    best = (waste, i, j)
        if best is None:
            break
        _, i, j = best
        groups[i] = _Group(shapes=groups[i].shapes + groups[j].shapes)
        keys[i] = merged_key(keys[i], keys[j])
        rows[i] += rows[j]
        natives[i] += natives[j]
        del groups[j], keys[j], rows[j], natives[j]

    # 3. split oversized groups into equal-capacity sub-buckets that share
    #    one compiled program (same shapes AND same padded row count)
    buckets: list[BucketPlan] = []
    for gid, g in enumerate(groups):
        key = g.fit_key()
        total = g.rows()
        max_cell = max(s.rows for s in g.shapes)
        threshold = max(packer.max_rows_per_bucket, max_cell)
        n_sub = -(-total // threshold)
        target = max(-(-total // n_sub), max_cell)
        cap = _pad_to(target, n_devices)
        if n_sub == 1:
            order = list(g.shapes)  # keep submission order
        else:
            order = sorted(g.shapes, key=lambda s: (-s.rows, s.name))
        bins: list[list[CellShape]] = []
        fill: list[int] = []
        for s in order:
            for b_i, used in enumerate(fill):
                if used + s.rows <= cap:
                    bins[b_i].append(s)
                    fill[b_i] += s.rows
                    break
            else:
                bins.append([s])
                fill.append(s.rows)
        shared_pad = (
            _pad_to(max(fill), n_devices) if len(bins) > 1 else None
        )
        row_cost = key[0] * cost_fn(key[1], *key[2:])
        for cells, used in zip(bins, fill):
            buckets.append(
                BucketPlan(
                    key=key,
                    cells=tuple(s.name for s in cells),
                    group=gid,
                    n_rows=used,
                    n_padded_rows=(
                        shared_pad
                        if shared_pad is not None
                        else _pad_to(used, n_devices)
                    ),
                    n_devices=n_devices,
                    est_row_cost=row_cost,
                    native_cost=sum(_cell_cost(s) for s in cells),
                )
            )
    return PackPlan(
        buckets=tuple(buckets), n_devices=n_devices, packer=packer
    )


# ---------------------------------------------------------------------------
# Engine-side materialization of a plan.
# ---------------------------------------------------------------------------


def _canon_lb_kwargs(case: SweepCase, cfg: SimConfig) -> dict:
    """LB kwargs with harness defaults resolved — keying on the raw kwargs
    would give `{}` and `{"evs_size": cfg.evs_size}` distinct SwitchLB
    branches, and every redundant branch costs a full extra LB evaluation
    per tick under the vmapped switch."""
    kw = dict(case.lb_kwargs)
    kw.setdefault("evs_size", cfg.evs_size)
    return kw


def _variant_key(case: SweepCase, cfg: SimConfig) -> tuple:
    return (case.lb, tuple(sorted(_canon_lb_kwargs(case, cfg).items())))


def _pad_workload(wl: Workload, nc: int, n_hosts: int) -> Workload:
    """Pad the conn table to ``nc`` rows with inert connections: they never
    start and depend on nothing.  Pad conns fill the *least-loaded* hosts
    first, so whenever the padding fits into existing per-host slack the
    conns_per_host pin equals the unpadded auto width — and the padded row
    stays bit-identical to a raw (unpinned) serial run, not just to the
    pinned serial reference."""
    extra = nc - wl.n_conns
    if extra == 0:
        return wl
    assert extra > 0
    counts = np.bincount(
        wl.src.astype(np.int64), minlength=n_hosts
    ).astype(np.int64)
    pad_src = np.empty((extra,), np.int32)
    for i in range(extra):
        h = int(np.argmin(counts))  # stable: lowest host id wins ties
        pad_src[i] = h
        counts[h] += 1
    return Workload(
        src=np.concatenate([wl.src.astype(np.int32), pad_src]),
        dst=np.concatenate(
            [wl.dst.astype(np.int32), (pad_src + 1) % n_hosts]
        ).astype(np.int32),
        msg_pkts=np.concatenate(
            [wl.msg_pkts.astype(np.int32), np.ones((extra,), np.int32)]
        ),
        start=np.concatenate(
            [wl.start.astype(np.int32), np.full((extra,), NEVER_TICK, np.int32)]
        ),
        dep=np.concatenate(
            [wl.dep.astype(np.int32), np.full((extra,), -1, np.int32)]
        ),
        name=wl.name,
    )


def _host_conns(wl: Workload, n_hosts: int, cph: int) -> np.ndarray:
    """host -> local conn table, same layout the engine builds (-1 padded)."""
    hc = np.full((n_hosts, cph), -1, np.int32)
    fill = np.zeros((n_hosts,), np.int32)
    for c in range(wl.n_conns):
        h = int(wl.src[c])
        hc[h, fill[h]] = c
        fill[h] += 1
    return hc


def _pad_watch(watch: np.ndarray, w: int) -> np.ndarray:
    watch = np.asarray(watch, np.int32)
    extra = w - len(watch)
    assert extra >= 0
    if extra == 0:
        return watch
    fill = watch[-1] if len(watch) else 0
    return np.concatenate([watch, np.full((extra,), fill, np.int32)])


@dataclasses.dataclass
class _Cell:
    case: SweepCase
    padded_wl: Workload
    padded_fs: FailureSchedule
    padded_watch: np.ndarray
    branch: int
    rows: list[int] = dataclasses.field(default_factory=list)  # per seed


@dataclasses.dataclass
class _Program:
    """One compiled scan family: all sub-buckets of a split group share it
    (identical padded shapes, padded row count, SwitchLB variant set)."""

    group: int
    cfg: SimConfig  # shape-pinned bucket config
    lb: SwitchLB
    sim: Simulator
    sim_ticks: int  # the group's bucket horizon (max member horizon)
    masked: bool  # rows carry heterogeneous horizons
    variant_order: list  # one (lb, kwargs) key per SwitchLB branch
    padded_wls: dict  # cell name -> group-padded Workload
    chunk_fns: dict = dataclasses.field(default_factory=dict)
    quiescent_fn: Any = None
    tel_progs: dict = dataclasses.field(default_factory=dict)  # spec -> prog
    trc_progs: dict = dataclasses.field(default_factory=dict)  # TraceSpec -> prog


@dataclasses.dataclass
class _Bucket:
    plan: BucketPlan
    program: _Program
    cells: list[_Cell]
    n_rows: int
    # stacked per-row inputs
    keys: jax.Array  # (R, key)
    scn: ScenarioArrays  # leaves (R, ...)
    branch_idx: np.ndarray  # (R,)
    horizons: np.ndarray  # (R,) per-row tick horizon
    # filled by run()
    final_state: Any = None  # host-side SimState, leaves (R, ...)
    traces: Any = None  # host-side TickTrace, leaves (ticks, R, ...) or None
    telemetry: Any = None  # host-side (R, size) int32 sketch carries or None
    tel_prog: Any = None  # TelemetryProgram that owns `telemetry`'s layout
    trace_rows: Any = None  # host-side (R, size) int32 flight-ring carries
    trc_prog: Any = None  # TracerProgram that owns `trace_rows`'s layout
    exec_wall_s: float = 0.0
    compile_wall_s: float = 0.0
    ticks_run: int = 0  # == ticks unless early exit fired sooner

    # compat accessors (benchmarks read these off result buckets)
    @property
    def key(self) -> tuple:
        return self.plan.key

    @property
    def ticks(self) -> int:
        return self.plan.ticks

    @property
    def cfg(self) -> SimConfig:
        return self.program.cfg

    @property
    def lb(self) -> SwitchLB:
        return self.program.lb

    @property
    def sim(self) -> Simulator:
        return self.program.sim


class SweepResult:
    """Per-cell access to a finished sweep (all arrays already on host)."""

    def __init__(self, engine: "SweepEngine"):
        self._engine = engine
        self.buckets = engine.buckets
        self.plan = engine.plan
        self.exec_wall_s = sum(b.exec_wall_s for b in self.buckets)
        self.compile_wall_s = sum(b.compile_wall_s for b in self.buckets)

    def _find(self, name: str) -> tuple[_Bucket, _Cell]:
        for b in self.buckets:
            for c in b.cells:
                if c.case.name == name:
                    return b, c
        raise KeyError(name)

    def state_for(self, name: str, seed_idx: int = 0) -> SimState:
        b, c = self._find(name)
        row = c.rows[seed_idx]
        return jax.tree_util.tree_map(lambda x: x[row], b.final_state)

    def trace_for(self, name: str, seed_idx: int = 0):
        b, c = self._find(name)
        assert b.traces is not None, "run with collect='full' to keep traces"
        row = c.rows[seed_idx]
        # rows of a horizon-merged bucket freeze at their own horizon; the
        # trace past it is that frozen state re-observed, so expose only
        # the cell's own window.
        return jax.tree_util.tree_map(
            lambda x: x[: c.case.ticks, row], b.traces
        )

    def flight_for(self, name: str, seed_idx: int = 0, since: int = 0) -> dict:
        """Decoded flight-recorder events for one cell row (run with a
        ``trace=TraceSpec(...)``): ``{seq, tick, code, value, cursor, lost,
        first_drop_tick, first_redeliver_tick}`` in push order — see
        ``tracer.TracerProgram.decode_row``."""
        b, c = self._find(name)
        if b.trace_rows is None:
            raise ValueError(
                "no flight-recorder events were collected for this sweep; "
                "run with trace=TraceSpec(...)"
            )
        return b.trc_prog.decode_row(b.trace_rows[c.rows[seed_idx]], since)

    def telemetry_for(self, name: str, seed_idx: int = 0) -> dict:
        """Finalized sketch channels for one cell row.

        Args:
            name: the cell's ``SweepCase.name``.
            seed_idx: index into the cell's ``seeds`` tuple (not the seed
                value itself).

        Returns:
            ``{channel key: finalized dict}`` as produced by each channel's
            ``finalize`` — e.g. ``["fct"]["counts"]``,
            ``["recovery"]["recovery_us"]`` for the default spec.
            Finalization uses the cell's *own* horizon (rows of a
            horizon-merged bucket froze bit-exactly there), so the result
            is identical whether or not the cell shared its bucket.

        Raises:
            ValueError: if the sweep did not run with
                ``collect="summary"`` (no sketches were carried).
            KeyError: unknown cell name.
        """
        b, c = self._find(name)
        if b.telemetry is None:
            raise ValueError(
                "no telemetry sketches were collected for this sweep; "
                "run with collect='summary'"
            )
        return b.tel_prog.finalize_row(
            b.telemetry[c.rows[seed_idx]], c.case.ticks
        )

    def summaries(self, source: str = "auto") -> dict[str, list[RunSummary]]:
        """Per-cell summaries (one per seed).

        ``source="state"`` builds them from each bucket's host-side final
        state; ``"sketch"`` from the telemetry sketches (summary mode) —
        bit-identical counters/completions/runtime/mean, p99 to bin
        resolution; ``"auto"`` prefers sketches when they were collected
        with the channels a RunSummary needs (custom specs without them
        fall back to the state path, which is always available).
        """
        from repro.netsim.telemetry import SUMMARY_CHANNEL_KEYS

        assert source in ("auto", "state", "sketch"), source
        out: dict[str, list[RunSummary]] = {}
        for b in self.buckets:
            sketch = (
                b.telemetry is not None
                and SUMMARY_CHANNEL_KEYS <= b.tel_prog.channel_keys
                if source == "auto"
                else source == "sketch"
            )
            for c in b.cells:
                variant = b.lb.variants[c.branch]
                if sketch:
                    if b.telemetry is None:
                        raise ValueError(
                            "no telemetry sketches were collected; run "
                            "with collect='summary' for sketch summaries"
                        )
                    out[c.case.name] = [
                        summarize_sketch(
                            b.tel_prog.finalize_row(
                                b.telemetry[row], c.case.ticks
                            ),
                            name=c.case.name,
                            lb_name=variant.name,
                            n_conns=c.case.workload.n_conns,
                        )
                        for row in c.rows
                    ]
                else:
                    out[c.case.name] = [
                        summarize(
                            b.sim,
                            jax.tree_util.tree_map(
                                lambda x, r=row: x[r], b.final_state
                            ),
                            name=c.case.name,
                            lb_name=variant.name,
                            n_conns=c.case.workload.n_conns,
                            conn_start=c.padded_wl.start,
                        )
                        for row in c.rows
                    ]
        return out


class SweepEngine:
    """Packs a list of SweepCases into cost-aware buckets and runs each as
    one compiled, row-sharded, donated-carry scan.

    ``kernels_backend`` pins the engine's segment-rank/segment-sum hot-spot
    backend (``SimConfig.kernels_backend``) for every bucket program:
    ``None`` keeps the config's own setting, ``"auto"`` resolves against
    the sweep mesh's platform (compiled Pallas kernels on TPU, the jnp
    formulations elsewhere), ``"pallas"`` forces the kernels — compiled on
    TPU, ``interpret=True`` elsewhere (slow; the bit-parity reference CI
    runs).  ``measured_costs`` feeds the packer's measured-cost model, see
    ``pack``/``measured_costs_from_bench``.
    """

    def __init__(
        self,
        cfg: SimConfig,
        cases: Sequence[SweepCase],
        devices: int | str | None = "auto",
        min_conn_bucket: int = 8,
        packer: PackerConfig | None = None,
        kernels_backend: str | None = None,
        measured_costs: dict | None = None,
        min_failure_slots: int = 0,
        conn_devices: int = 1,
    ):
        # ``min_failure_slots`` floors every cell's quantized failure-row
        # count (pow2-rounded like the natural size): headroom for the soak
        # runtime's live injection (SoakRunner.inject re-materializes the
        # padded schedule into the reserved inert rows without a shape
        # change), and the knob that makes an injected run and its
        # statically-scheduled equivalent plan identical buckets.
        #
        # ``conn_devices`` > 1 (scale mode) shards the *connection* state
        # axis over the minor axis of a 2-D (rows, conns) mesh — requires
        # the cfg to opt in via ``conn_sharding=True``; ``devices`` then
        # bounds the total device count and rows take the rest.  Bit-parity
        # contract: a conn-sharded row is bit-identical to its unsharded
        # ``serial_sim`` reference (tests/test_scale_mode.py).
        self.min_failure_slots = int(min_failure_slots)
        self.cfg = cfg
        self.cases = list(cases)
        assert self.cases, "need at least one case"
        self.conn_devices = max(1, int(conn_devices))
        if self.conn_devices > 1:
            if not cfg.conn_sharding:
                raise ValueError(
                    "conn_devices > 1 requires SimConfig.conn_sharding=True "
                    "(the scale mode is opt-in; see ARCHITECTURE.md §10)"
                )
            self.mesh = sweep_conn_mesh(
                self.conn_devices,
                None if devices in ("auto", None) else int(devices),
            )
        elif devices == "auto":
            self.mesh = sweep_mesh()
        elif devices in (None, 1):
            self.mesh = None
        else:
            self.mesh = sweep_mesh(int(devices))
        self.n_devices = (
            self.mesh.shape[SWEEP_AXIS] if self.mesh is not None else 1
        )
        # resolve the backend (incl. the config's own "auto") against the
        # row mesh's platform, ONE shared rule for every layer
        resolved = resolve_kernels_backend(
            kernels_backend or cfg.kernels_backend, self.mesh
        )
        if resolved != cfg.kernels_backend:
            self.cfg = cfg = cfg.replace(kernels_backend=resolved)
        self.kernels_backend = resolved
        self.min_conn_bucket = min_conn_bucket
        self.packer = packer or PackerConfig()
        self._default_watch_arr = self._default_watch()
        self.plan = pack(
            cfg,
            [self._quantize(c) for c in self.cases],
            self.packer,
            self.n_devices,
            measured_costs=measured_costs,
        )
        self.programs: dict[int, _Program] = {}
        self.buckets = self._build_buckets()

    # ------------------------------------------------------------------
    def _default_watch(self) -> np.ndarray:
        from repro.netsim.topology import Topology

        topo = Topology.build(self.cfg)
        return np.asarray(
            topo.t0_up_queues(0)[: self.cfg.n_watch_queues], np.int32
        )

    def _watch_for(self, case: SweepCase) -> np.ndarray:
        if case.watch_queues is None:
            return self._default_watch_arr
        return np.asarray(case.watch_queues, np.int32)

    def _live_failures(self, case: SweepCase) -> FailureSchedule:
        return truncate_dead(
            case.failures or FailureSchedule.none(), case.ticks
        )

    def _quantize(self, case: SweepCase) -> CellShape:
        cfg = self.cfg
        variant = make_lb(case.lb, **_canon_lb_kwargs(case, cfg))
        wl = case.workload
        msg_max = int(wl.msg_pkts.max()) if wl.n_conns else 1
        # conn-sharded buckets need conn counts divisible by the conn mesh
        # axis, so the shrink-to-fit exact size rounds up to it (inert pad
        # conns, same neutral-padding contract as bucket-size padding)
        nc_exact = _pad_to(max(wl.n_conns, 1), self.conn_devices)
        return CellShape(
            name=case.name,
            ticks=case.ticks,
            adaptive=variant.switch_adaptive,
            nc=_pow2(max(wl.n_conns, self.min_conn_bucket)),
            msg=int(min(cfg.max_msg_pkts, max(_pow2(max(msg_max, 2)), 2))),
            f=_pow2(
                max(len(self._live_failures(case)), 1, self.min_failure_slots)
            ),
            w=_pow2(max(len(self._watch_for(case)), 1)),
            rows=len(case.seeds),
            nc_exact=nc_exact,
        )

    # ------------------------------------------------------------------
    def _build_buckets(self) -> list[_Bucket]:
        cfg = self.cfg
        by_name = {c.name: c for c in self.cases}
        # group-level shape/variant context (shared by all sub-buckets)
        group_cases: dict[int, list[SweepCase]] = {}
        for bp in self.plan.buckets:
            group_cases.setdefault(bp.group, []).extend(
                by_name[n] for n in bp.cells
            )
        for gid, members in group_cases.items():
            self.programs[gid] = self._build_program(
                gid,
                next(bp for bp in self.plan.buckets if bp.group == gid),
                members,
            )
        return [self._build_bucket(bp, by_name) for bp in self.plan.buckets]

    def _build_program(
        self, gid: int, bp: BucketPlan, members: list[SweepCase]
    ) -> _Program:
        ticks_b, _adaptive, nc_b, msg_b, f_b, _w_b = bp.key
        cfg = self.cfg

        # one SwitchLB branch per distinct (lb name, kwargs) spec
        variant_order: list[tuple] = []
        variants = []
        for case in members:
            vk = _variant_key(case, cfg)
            if vk not in variant_order:
                variant_order.append(vk)
                variants.append(
                    make_lb(case.lb, **_canon_lb_kwargs(case, cfg))
                )

        # pin the derived static sizes the padded tables would otherwise
        # perturb, so serial references share bit-identical shapes
        cph_b = 1
        padded_wls = {}
        for case in members:
            pwl = _pad_workload(case.workload, nc_b, cfg.n_hosts)
            padded_wls[case.name] = pwl
            counts = np.bincount(pwl.src, minlength=cfg.n_hosts)
            cph_b = max(cph_b, int(counts.max()))
        cfg_b = cfg.replace(
            msg_slots=msg_b, conns_per_host=cph_b, failure_slots=f_b
        )

        lb = SwitchLB(variants)
        first = members[0]
        sim = Simulator(
            cfg_b,
            padded_wls[first.name],
            lb,
            failures=self._live_failures(first).pad_to(f_b),
            watch_queues=_pad_watch(self._watch_for(first), bp.key[5]),
            seed=int(first.seeds[0]),
        )
        return _Program(
            group=gid,
            cfg=cfg_b,
            lb=lb,
            sim=sim,
            sim_ticks=ticks_b,
            masked=any(case.ticks < ticks_b for case in members),
            variant_order=variant_order,
            padded_wls=padded_wls,
        )

    def _build_bucket(
        self, bp: BucketPlan, by_name: dict[str, SweepCase]
    ) -> _Bucket:
        f_b, w_b = bp.key[4], bp.key[5]
        prog = self.programs[bp.group]
        cfg = self.cfg

        cells: list[_Cell] = []
        for name in bp.cells:
            case = by_name[name]
            cells.append(
                _Cell(
                    case=case,
                    padded_wl=prog.padded_wls[name],
                    padded_fs=self._live_failures(case).pad_to(f_b),
                    padded_watch=_pad_watch(self._watch_for(case), w_b),
                    branch=prog.variant_order.index(
                        _variant_key(case, cfg)
                    ),
                )
            )

        # rows = cells × seeds, padded to the planned row count by
        # repeating row 0 (discarded on output)
        row_cells: list[tuple[_Cell, int]] = []
        for c in cells:
            for s in c.case.seeds:
                c.rows.append(len(row_cells))
                row_cells.append((c, int(s)))
        n_rows = len(row_cells)
        assert n_rows == bp.n_rows, (n_rows, bp)
        row_cells += [row_cells[0]] * (bp.n_padded_rows - n_rows)

        cph_b = prog.cfg.conns_per_host

        def stack(field_of):
            return jnp.asarray(np.stack([field_of(c, s) for c, s in row_cells]))

        scn = ScenarioArrays(
            conn_src=stack(lambda c, s: c.padded_wl.src.astype(np.int32)),
            conn_dst=stack(lambda c, s: c.padded_wl.dst.astype(np.int32)),
            conn_msg=stack(lambda c, s: c.padded_wl.msg_pkts.astype(np.int32)),
            conn_start=stack(lambda c, s: c.padded_wl.start.astype(np.int32)),
            conn_dep=stack(lambda c, s: c.padded_wl.dep.astype(np.int32)),
            host_conns=stack(
                lambda c, s: _host_conns(c.padded_wl, cfg.n_hosts, cph_b)
            ),
            watch=stack(lambda c, s: c.padded_watch),
            f_queue=stack(lambda c, s: c.padded_fs.queue.astype(np.int32)),
            f_start=stack(lambda c, s: c.padded_fs.start.astype(np.int32)),
            f_end=stack(lambda c, s: c.padded_fs.end.astype(np.int32)),
            f_kind=stack(lambda c, s: c.padded_fs.kind.astype(np.int32)),
            f_param=stack(lambda c, s: c.padded_fs.param.astype(np.int32)),
        )
        keys = jnp.stack([jax.random.PRNGKey(s) for _, s in row_cells])
        branch_idx = np.asarray([c.branch for c, _ in row_cells], np.int32)
        horizons = np.asarray(
            [c.case.ticks for c, _ in row_cells], np.int32
        )
        return _Bucket(
            plan=bp, program=prog, cells=cells, n_rows=n_rows,
            keys=keys, scn=scn, branch_idx=branch_idx, horizons=horizons,
        )

    # ------------------------------------------------------------------
    def serial_sim(self, name: str, seed: int | None = None) -> Simulator:
        """The serial reference for a cell: a plain Simulator built on the
        same padded scenario and shape-pinned config the sweep row ran —
        ``serial_sim(name).run(case.ticks)`` is bit-identical to the sweep
        row (which froze at exactly that horizon in a merged bucket)."""
        for b in self.buckets:
            for c in b.cells:
                if c.case.name == name:
                    lb = make_lb(
                        c.case.lb, **_canon_lb_kwargs(c.case, self.cfg)
                    )
                    return Simulator(
                        b.cfg,
                        c.padded_wl,
                        lb,
                        failures=c.padded_fs,
                        watch_queues=c.padded_watch,
                        seed=int(c.case.seeds[0] if seed is None else seed),
                    )
        raise KeyError(name)

    # ------------------------------------------------------------------
    def _init_states(self, bucket: _Bucket) -> SimState:
        states = jax.vmap(bucket.sim.init_state)(bucket.keys)
        _, variant_states = states.lb_state
        return states._replace(
            lb_state=(jnp.asarray(bucket.branch_idx), variant_states)
        )

    def _tel_prog(self, prog: _Program, spec: TelemetrySpec):
        """The program's TelemetryProgram for a spec (built once; shapes and
        window strides derive from the group's bucket horizon)."""
        if spec not in prog.tel_progs:
            prog.tel_progs[spec] = spec.build(prog.sim, prog.sim_ticks)
        return prog.tel_progs[spec]

    def _trc_prog(self, prog: _Program, trace: TraceSpec):
        """The program's TracerProgram for a TraceSpec (built once)."""
        if trace not in prog.trc_progs:
            prog.trc_progs[trace] = trace.build(prog.sim, prog.sim_ticks)
        return prog.trc_progs[trace]

    def _make_chunk_fn(
        self, prog: _Program, n: int, collect: str,
        spec: TelemetrySpec | None = None, trace: TraceSpec | None = None,
    ):
        """Compiled runner for one chunk of ``n`` ticks: carries donated
        states (plus the stacked telemetry sketches in summary mode, plus
        the flight-recorder rings when tracing), returns (carry,
        traces-or-None).  Shared by every bucket of the program's split
        group (same shapes, same padded rows)."""
        sim = prog.sim
        full = collect == "full"
        summary = collect == "summary"
        masked = prog.masked
        ca = CONN_AXIS if self.conn_devices > 1 else None
        if ca is not None and summary:
            raise ValueError(
                "collect='summary' is incompatible with conn_devices > 1: "
                "telemetry reducers consume full-width per-conn probe "
                "vectors (done_now, fct), which are shard-local under conn "
                "sharding.  Use collect='none' or 'full'."
            )
        if summary and trace is not None:
            vstep = jax.vmap(
                lambda st, t, k, sc: sim.step_events(st, t, k, sc, conn_axis=ca),
                in_axes=(0, None, 0, 0),
            )
            tel_update = jax.vmap(self._tel_prog(prog, spec).update)
            trc_update = jax.vmap(self._trc_prog(prog, trace).update)
        elif summary:
            vstep = jax.vmap(
                lambda st, t, k, sc: sim.step_probe(st, t, k, sc, conn_axis=ca),
                in_axes=(0, None, 0, 0),
            )
            tel_update = jax.vmap(self._tel_prog(prog, spec).update)
        else:
            vstep = jax.vmap(
                lambda st, t, k, sc: sim.step_scenario(st, t, k, sc, conn_axis=ca),
                in_axes=(0, None, 0, 0),
            )

        def freeze(live, new, old):
            # freeze rows past their own horizon: bit-identical to stopping
            # that row's serial run at `horizon` ticks
            return jax.tree_util.tree_map(
                lambda nw, od: jnp.where(
                    live.reshape((-1,) + (1,) * (nw.ndim - 1)), nw, od
                ),
                new,
                old,
            )

        def body(carry, keys, scn, horizon, t0):
            def tick(carry, t):
                if summary and trace is not None:
                    states, tel, trc = carry
                    new_states, probe, events = vstep(states, t, keys, scn)
                    with jax.named_scope("tick.telemetry"):
                        new_carry = (
                            new_states,
                            tel_update(tel, probe),
                            trc_update(trc, probe, events),
                        )
                    tr = None
                elif summary:
                    states, tel = carry
                    new_states, probe = vstep(states, t, keys, scn)
                    with jax.named_scope("tick.telemetry"):
                        new_carry = (new_states, tel_update(tel, probe))
                    tr = None
                else:
                    new_carry, tr = vstep(carry, t, keys, scn)
                if masked:
                    with jax.named_scope("tick.freeze"):
                        new_carry = freeze(t < horizon, new_carry, carry)
                return new_carry, (tr if full else None)

            ticks = t0 + jnp.arange(n, dtype=jnp.int32)
            return jax.lax.scan(tick, carry, ticks)

        if self.mesh is not None:
            if ca is None:
                carry_spec = P(SWEEP_AXIS)
                scn_spec = P(SWEEP_AXIS)
            else:
                # per-leaf specs: per-conn leaves shard (rows, conns), the
                # rest (packet table, queues, LB state, stats) replicate
                # over the conn axis — matching step_scenario's conn_axis
                # contract (gather at entry / slice at exit keeps them
                # device-invariant along CONN_AXIS).
                carry_spec = self._conn_state_specs()
                scn_spec = self._conn_scn_specs()
            body = jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=(
                    carry_spec, P(SWEEP_AXIS), scn_spec,
                    P(SWEEP_AXIS), P(),
                ),
                out_specs=(
                    carry_spec, P(None, SWEEP_AXIS) if full else P()
                ),
                check_vma=False,
            )
        return jax.jit(body, donate_argnums=(0,))

    def _conn_state_specs(self) -> SimState:
        row, conn = P(SWEEP_AXIS), P(SWEEP_AXIS, CONN_AXIS)
        return SimState(
            pkt=row, qbuf=row, q_head=row, q_len=row, q_served=row,
            c_inflight=conn, c_next_new=conn, c_delivered=conn,
            c_rx_pending=conn, c_done=conn, c_done_tick=conn,
            c_rtx_count=conn, c_rtx=conn, c_rcv=conn, c_cwnd=conn,
            c_alpha=conn, h_rr=row, lb_state=row, fl=row, fl_head=row,
            fl_count=row, s_stats=row, as_idx=row, as_count=row,
        )

    def _conn_scn_specs(self) -> ScenarioArrays:
        row, conn = P(SWEEP_AXIS), P(SWEEP_AXIS, CONN_AXIS)
        return ScenarioArrays(
            conn_src=conn, conn_dst=conn, conn_msg=conn, conn_start=conn,
            conn_dep=conn, host_conns=row, watch=row, f_queue=row,
            f_start=row, f_end=row, f_kind=row, f_param=row,
        )

    def _make_quiescent_fn(self, prog: _Program):
        """Per-row fixed-point detector.  A row is quiescent when no packet
        slot is allocated (covers FLYING/QUEUED/ACK/NACK/LOST_WAIT — every
        live state holds a slot until consumed) and no connection that can
        still start within the row's horizon has work left — or when the
        row is already past its horizon (frozen).  Once all rows hold,
        every later tick is a no-op for packet/conn/stat state, so the
        remaining scan chunks can be skipped without changing any reported
        result (only time-keeping LB internals, e.g. PLB epoch clocks,
        would have kept advancing).
        """
        NP = prog.sim.NP

        def f(states: SimState, scn: ScenarioArrays, horizon, offset):
            no_pkts = states.fl_count == NP  # (R,)
            dep = jnp.clip(scn.conn_dep, 0, scn.conn_src.shape[-1] - 1)
            dep_ok = (scn.conn_dep < 0) | jnp.take_along_axis(
                states.c_done, dep, axis=-1
            )
            startable = (scn.conn_start < horizon[:, None]) & dep_ok
            has_work = (states.c_rtx_count > 0) | (
                states.c_next_new < scn.conn_msg
            )
            active = startable & ~states.c_done & has_work
            quiet = no_pkts & ~jnp.any(active, axis=-1)
            return jnp.all(quiet | (offset >= horizon))

        return jax.jit(f)

    def run(
        self,
        collect: str = "none",
        chunk: int | None = None,
        early_exit: bool = False,
        telemetry: TelemetrySpec | None = None,
        trace: TraceSpec | None = None,
    ) -> SweepResult:
        """Execute every bucket.  The three-mode ``collect`` contract:

        * ``"none"``    — no per-tick output (fastest; state summaries
          only).  Early-exit compatible.
        * ``"summary"`` — on-device sketch channels (``telemetry`` spec,
          default ``TelemetrySpec.default()``) reduced inside the scan;
          O(bins) host bytes per row.  Early-exit compatible: reducers are
          no-ops on quiescent ticks, so skipping them is bit-invisible.
        * ``"full"``    — raw TickTrace streams fetched chunk-by-chunk;
          O(ticks) host bytes per row.  Incompatible with ``early_exit``.

        ``chunk`` bounds how many ticks of trace live on device at once
        (defaults to the whole run in one chunk).  ``early_exit`` stops a
        bucket at the first chunk boundary where every row has reached its
        fixed point (see _make_quiescent_fn); all reported metrics are
        bit-identical to running the full horizon.

        ``trace`` (a ``tracer.TraceSpec``, summary mode only) additionally
        carries the flight-recorder ring per row; decoded events come back
        via ``SweepResult.flight_for``.  Tracing is observation-only: every
        state / telemetry array is bit-identical with it on or off.
        """
        if collect not in ("none", "summary", "full"):
            raise ValueError(
                f"collect must be 'none', 'summary' or 'full', got "
                f"{collect!r}"
            )
        if trace is not None and collect != "summary":
            raise ValueError(
                "trace=TraceSpec(...) requires collect='summary' (the "
                "flight recorder rides the telemetry carry contract)"
            )
        if early_exit and collect == "full":
            raise ValueError(
                "early_exit=True cannot be combined with collect='full': "
                "raw trace streams would be truncated at the quiescence "
                "point.  Use collect='summary' (on-device sketch channels "
                "keep figure fidelity and are early-exit safe) or "
                "collect='none', or run the full horizon with "
                "early_exit=False."
            )
        if telemetry is not None and collect != "summary":
            raise ValueError(
                "a telemetry spec only applies to collect='summary'"
            )
        spec = (
            (telemetry or TelemetrySpec.default())
            if collect == "summary"
            else None
        )
        for bucket in self.buckets:
            self._run_bucket(bucket, collect, chunk, early_exit, spec, trace)
        return SweepResult(self)

    # ------------------------------------------------------------------
    # Chunked carry in/out — the resumable building blocks the batch path
    # below AND the soak runtime (repro.netsim.soak) drive: a bucket's
    # execution is ``carry = bucket_carry(...)`` followed by any sequence
    # of ``run_chunk`` calls whose (t0, n) windows tile ``[0, ticks)``, and
    # the result is bit-identical regardless of how the windows are cut —
    # which is exactly what lets a checkpointed carry resume at any chunk
    # boundary and replay the remaining windows.
    # ------------------------------------------------------------------
    def bucket_carry(
        self, bucket: _Bucket, collect: str = "none",
        spec: TelemetrySpec | None = None, trace: TraceSpec | None = None,
    ):
        """The bucket's t=0 scan carry: vmapped per-row init states, plus
        the stacked telemetry sketch carry in summary mode, plus the
        flight-recorder ring carry when tracing."""
        carry = self._init_states(bucket)
        if collect == "summary":
            tel_prog = self._tel_prog(bucket.program, spec)
            tel0 = jnp.tile(
                tel_prog.init()[None], (bucket.plan.n_padded_rows, 1)
            )
            if trace is not None:
                trc_prog = self._trc_prog(bucket.program, trace)
                trc0 = jnp.tile(
                    trc_prog.init()[None], (bucket.plan.n_padded_rows, 1)
                )
                carry = (carry, tel0, trc0)
            else:
                carry = (carry, tel0)
        return carry

    def chunk_runner(
        self, bucket: _Bucket, n: int, collect: str = "none",
        spec: TelemetrySpec | None = None, example_carry=None,
        trace: TraceSpec | None = None,
    ):
        """The compiled ``(carry, keys, scn, horizons, t0) -> (carry,
        traces)`` executable for an ``n``-tick chunk.  AOT-compiled once
        per (n, collect, spec, trace) and shared by every sub-bucket of the
        program's split group (same shapes, same padded rows); the carry is
        donated on call.  ``example_carry`` supplies lowering shapes (a
        fresh ``bucket_carry`` is built when omitted)."""
        prog = bucket.program
        ck = (n, collect, spec, trace)
        if ck not in prog.chunk_fns:
            if example_carry is None:
                example_carry = self.bucket_carry(bucket, collect, spec, trace)
            fn = self._make_chunk_fn(prog, n, collect, spec, trace)
            prog.chunk_fns[ck] = fn.lower(
                example_carry, bucket.keys, bucket.scn,
                jnp.asarray(bucket.horizons), jnp.zeros((), jnp.int32),
            ).compile()
        return prog.chunk_fns[ck]

    def run_chunk(
        self, bucket: _Bucket, carry, t0: int, n: int,
        collect: str = "none", spec: TelemetrySpec | None = None,
        trace: TraceSpec | None = None,
    ):
        """Advance one bucket's carry over ticks ``[t0, t0 + n)``.  Returns
        ``(carry, traces)``; ``carry`` is donated (the passed-in buffers
        are invalid afterwards — checkpoint via ``jax.device_get`` *before*
        calling).  Rows whose own horizon lies inside the window freeze
        bit-exactly there (heterogeneous buckets), so driving a bucket to
        its horizon in any chunking yields identical results."""
        fn = self.chunk_runner(
            bucket, n, collect, spec, example_carry=carry, trace=trace
        )
        return fn(
            carry, bucket.keys, bucket.scn, jnp.asarray(bucket.horizons),
            jnp.asarray(t0, jnp.int32),
        )

    def finalize_bucket(
        self, bucket: _Bucket, carry, collect: str, ticks_run: int,
        trace_chunks=None, spec: TelemetrySpec | None = None,
        trace: TraceSpec | None = None,
    ):
        """Publish a finished carry onto the bucket (one host transfer):
        ``final_state`` / ``telemetry`` / ``traces`` / ``trace_rows`` as
        ``SweepResult`` expects, pad rows dropped."""
        summary = collect == "summary"
        host = jax.device_get(carry)  # one transfer for the bucket
        keep = bucket.n_rows
        host_state = host[0] if summary else host
        bucket.final_state = jax.tree_util.tree_map(
            lambda x: x[:keep], host_state
        )
        bucket.ticks_run = ticks_run
        if summary:
            bucket.telemetry = host[1][:keep]
            bucket.tel_prog = self._tel_prog(bucket.program, spec)
            if trace is not None:
                bucket.trace_rows = host[2][:keep]
                bucket.trc_prog = self._trc_prog(bucket.program, trace)
        if collect == "full" and trace_chunks:
            bucket.traces = jax.tree_util.tree_map(
                lambda *xs: np.concatenate(xs, axis=0)[:, :keep],
                *trace_chunks,
            )

    def _run_bucket(
        self, bucket: _Bucket, collect: str, chunk: int | None,
        early_exit: bool = False, spec: TelemetrySpec | None = None,
        trace: TraceSpec | None = None,
    ):
        prog = bucket.program
        ticks = bucket.ticks
        summary = collect == "summary"
        if chunk is None:
            # early exit needs chunk boundaries to act on
            chunk = max(64, ticks // 8) if early_exit else ticks
        chunk = max(1, min(chunk, ticks))
        sizes = [chunk] * (ticks // chunk)
        if ticks % chunk:
            sizes.append(ticks % chunk)

        with span("sweep.bucket.compile") as compiling:
            carry = self.bucket_carry(bucket, collect, spec, trace)
            # AOT-compile each distinct chunk length (usually 1-2) untimed;
            # sub-buckets of a split group share the compiled executables.
            for n in sorted(set(sizes)):
                self.chunk_runner(
                    bucket, n, collect, spec, example_carry=carry, trace=trace
                )
            if early_exit and prog.quiescent_fn is None:
                prog.quiescent_fn = self._make_quiescent_fn(prog)
            quiescent = prog.quiescent_fn if early_exit else None
            jax.block_until_ready(jax.tree_util.tree_leaves(carry)[0])
        bucket.compile_wall_s = compiling.seconds

        trace_chunks = []
        offset = 0
        with span("sweep.bucket.exec") as executing:
            for n in sizes:
                carry, traces = self.run_chunk(
                    bucket, carry, offset, n, collect, spec, trace
                )
                offset += n
                if collect == "full":
                    # stream this chunk to host so the device never holds more
                    # than `chunk` ticks of trace
                    trace_chunks.append(jax.device_get(traces))
                states = carry[0] if summary else carry
                if quiescent is not None and offset < ticks and bool(
                    quiescent(
                        states, bucket.scn, jnp.asarray(bucket.horizons),
                        jnp.asarray(offset, jnp.int32),
                    )
                ):
                    break
            states = carry[0] if summary else carry
            jax.block_until_ready(states.c_done)
        bucket.exec_wall_s = executing.seconds
        self.finalize_bucket(
            bucket, carry, collect, offset, trace_chunks, spec, trace
        )
