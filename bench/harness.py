"""The harness: a cell from its files, the measured window, the result line.

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/``) and a
traffic mix (``bench/traffic/``); ``load_cell`` finds both by name, and
``bench/metrics/<metric>.py`` holds the reader of each per-layer metric.
Nothing here names a cell, so a later cell needs only new files and entries.

The window drives the sweep path as a user's grid runs it, through
``SweepEngine``'s resumable API (the one the soak runtime drives):
``bucket_carry``, then ``run_chunk`` in the traffic's ``chunk_ticks``, the
engine's quiescence poll at every chunk boundary, ``finalize_bucket`` when
a bucket reaches its horizon or quiesces, then the same grid again from its
initial state.  The window ends at the first chunk boundary after
``seconds``.  Host spans ``bench.chunk`` / ``bench.poll`` / ``bench.regrid``
name what the host is doing, so a trace can attribute device idle time.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import generate, kernel_work, reference, trace_reduce
from repro.netsim.config import SimConfig
from repro.netsim.sweep import SweepCase, SweepEngine
from repro.netsim.telemetry import TelemetrySpec

ROOT = pathlib.Path(__file__).resolve().parents[1]
ST_UNPROC, ST_ALLOC_FAIL = 6, 7  # s_stats slots of lost simulator work


@dataclasses.dataclass
class CellSpec:
    """One cell of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries this cell reports without a trace
    per_layer: list  # metric entries this cell reports with a trace


def load_cell(name: str, root: pathlib.Path = ROOT) -> CellSpec:
    """The cell ``name`` of ``BENCHMARK.json`` with its files read."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell_from_files(name, int(cell["chips"]), cfg_entry["file"],
                           cell["traffic"], bench, root)


def cell_from_files(name: str, chips: int, config_file: str, traffic: str,
                    bench: dict, root: pathlib.Path = ROOT) -> CellSpec:
    """A cell from its configuration file and traffic mix; it reports the
    metrics of ``bench`` (a ``BENCHMARK.json``) that list it or no cell."""

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return CellSpec(
        name=name,
        chips=chips,
        config=json.loads((root / config_file).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{traffic}.json").read_text()
        ),
        end_to_end=mine(bench["end_to_end"]),
        per_layer=mine(bench["per_layer"]),
    )


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# The grid
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Grid:
    spec: CellSpec
    cfg: SimConfig
    inputs: generate.Inputs
    cases: list
    engine: SweepEngine
    collect: str
    tel_spec: TelemetrySpec | None
    chunk: int
    early_exit: bool
    quiescent: dict = dataclasses.field(default_factory=dict)  # group -> fn

    def sizes(self, bucket) -> list[int]:
        ticks = bucket.ticks
        n = max(1, min(self.chunk, ticks))
        return [n] * (ticks // n) + ([ticks % n] if ticks % n else [])


def build_grid(spec: CellSpec, seed: int) -> Grid:
    """The cell's engine and inputs for ``seed`` (nothing compiled yet)."""
    cfg = SimConfig(**spec.config["sim"])
    tr = spec.traffic
    inputs = generate.build(spec.config, tr, cfg, seed)
    cases = [
        SweepCase(
            f"{spec.name}/{lb['lb']}", inputs.workload, lb["lb"], tr["ticks"],
            lb_kwargs={"evs_size": cfg.evs_size, **lb["kwargs"]},
            failures=inputs.failures, seeds=inputs.row_seeds,
        )
        for lb in tr["lbs"]
    ]
    conn_devices = spec.chips if cfg.conn_sharding else 1
    engine = SweepEngine(cfg, cases, devices=spec.chips,
                         conn_devices=conn_devices)
    collect = tr["collect"]
    return Grid(
        spec=spec, cfg=cfg, inputs=inputs, cases=cases,
        engine=engine, collect=collect,
        tel_spec=TelemetrySpec.default() if collect == "summary" else None,
        chunk=int(tr["chunk_ticks"]), early_exit=bool(tr["early_exit"]),
    )


def warm_up(grid: Grid):
    """Compile (or load from the cache) every chunk length and quiescence
    poll the window uses, and run each poll once.  Returns the first
    bucket's initial carry."""
    eng = grid.engine
    first = None
    for bucket in eng.buckets:
        carry = eng.bucket_carry(bucket, grid.collect, grid.tel_spec)
        for n in sorted(set(grid.sizes(bucket))):
            fn = eng.chunk_runner(bucket, n, grid.collect, grid.tel_spec,
                                  example_carry=carry)
        # On a mesh the fresh carry sits on one device: place it as the
        # chunk program takes it, and poll it as the program hands it on,
        # so that neither reshards nor compiles inside the window.
        carry = _placed(carry, fn.input_shardings[0][0])
        prog = bucket.program
        if grid.early_exit and prog.group not in grid.quiescent:
            grid.quiescent[prog.group] = eng._make_quiescent_fn(prog)
        if grid.early_exit:
            _poll(grid, bucket, _placed(carry, fn.output_shardings[0]), 0)
        if first is None:
            first = carry
    jax.block_until_ready(first)
    return first


def _placed(tree, shardings):
    """``tree`` on ``shardings``; unchanged (and uncommitted, as a chunk
    hands it on on one device) where it is there already."""
    leaves = jax.tree_util.tree_leaves(tree)
    if all(x.sharding == s for x, s in
           zip(leaves, jax.tree_util.tree_leaves(shardings))):
        return tree
    return jax.device_put(tree, shardings)


def _states(grid: Grid, carry):
    return carry[0] if grid.collect == "summary" else carry


def _poll(grid: Grid, bucket, carry, offset: int) -> bool:
    fn = grid.quiescent[bucket.program.group]
    return bool(fn(_states(grid, carry), bucket.scn,
                   jnp.asarray(bucket.horizons),
                   jnp.asarray(offset, jnp.int32)))


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Snapshot:
    """A bucket's rows as the window left them: finished or in flight."""

    ticks: int  # ticks the rows advanced (a frozen row stopped earlier)
    state: object  # host SimState, leaves (rows, ...)
    telemetry: object  # host (rows, size) int32 or None


@dataclasses.dataclass
class Window:
    seconds: float
    row_ticks: int  # simulated row-ticks credited to the window
    rows_started: int
    rows_lost: int  # rows whose state reports lost simulator work
    chunks: int
    compiles: int  # backend compiles inside the window (should be 0)
    snapshots: dict  # bucket index -> the latest Snapshot
    traced: dict | None = None  # the traced chunk: ticks, rows


class _CompileCounter:
    """Counts XLA backend compiles while ``on`` (one listener per process)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    _instance = None

    def __init__(self):
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    @classmethod
    def get(cls):
        if cls._instance is None:
            cls._instance = cls()
        cls._instance.n = 0
        return cls._instance

    def _listen(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.n += 1


def lb_rows(bucket) -> dict:
    """Real rows of a bucket per load balancer."""
    out: dict = {}
    for cell in bucket.cells:
        out[cell.case.lb] = out.get(cell.case.lb, 0) + len(cell.rows)
    return out


def lost_rows(state, n_rows: int) -> int:
    """Rows whose state reports lost simulator work (unprocessed events or
    failed packet-slot allocations)."""
    s = np.asarray(state.s_stats)[:n_rows]
    return int(np.sum((s[:, ST_UNPROC] > 0) | (s[:, ST_ALLOC_FAIL] > 0)))


def run_window(grid: Grid, carry, seconds: float, trace_dir: str | None = None,
               chunk_hook=None) -> Window:
    """Drive the grid for ``seconds`` (to the next chunk boundary).

    With ``trace_dir`` the window's second chunk with its poll (the first
    when ``seconds`` is 0) runs under the profiler, inside the host span
    ``bench.traced``, and the window lasts at least that long.
    ``chunk_hook(carry, bucket, t0, n) -> carry``, when given, replaces
    ``run_chunk`` (the tests plant faults with it)."""
    eng = grid.engine
    run_chunk = chunk_hook or (
        lambda c, b, t0, n: eng.run_chunk(b, c, t0, n, grid.collect,
                                          grid.tel_spec)[0]
    )
    buckets = eng.buckets
    snapshots: dict = {}
    credit = lost = chunks = 0
    rows_started = buckets[0].n_rows
    bi, offset, k = 0, 0, 0
    sizes = grid.sizes(buckets[0])
    trace_at = None if trace_dir is None else (1 if seconds > 0 else 0)
    traced = None
    counter = _CompileCounter.get()
    counter.on = True
    t0 = time.perf_counter()
    while True:
        bucket = buckets[bi]
        n = sizes[k]
        if chunks == trace_at:
            jax.profiler.start_trace(trace_dir)
            span = TraceAnnotation("bench.traced")
            span.__enter__()
            traced = {"ticks": n, "rows": bucket.n_rows,
                      "lb_rows": lb_rows(bucket)}
        with TraceAnnotation("bench.chunk"):
            carry = run_chunk(carry, bucket, offset, n)
        offset += n
        k += 1
        chunks += 1
        with TraceAnnotation("bench.poll"):
            if grid.early_exit and offset < bucket.ticks:
                done = _poll(grid, bucket, carry, offset)
            else:
                jax.block_until_ready(_states(grid, carry).c_done)
                done = offset >= bucket.ticks
        if done:
            with TraceAnnotation("bench.regrid"):
                eng.finalize_bucket(bucket, carry, grid.collect, offset,
                                    spec=grid.tel_spec)
                snapshots[bi] = Snapshot(offset, bucket.final_state,
                                         bucket.telemetry)
                credit += int(np.sum(bucket.horizons[: bucket.n_rows]))
                lost += lost_rows(bucket.final_state, bucket.n_rows)
                bi = (bi + 1) % len(buckets)
                bucket = buckets[bi]
                carry = eng.bucket_carry(bucket, grid.collect, grid.tel_spec)
                jax.block_until_ready(carry)
                rows_started += bucket.n_rows
                sizes = grid.sizes(bucket)
                offset, k = 0, 0
        if chunks - 1 == trace_at:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        if time.perf_counter() - t0 >= seconds and (
            trace_at is None or chunks > trace_at
        ):
            break
    elapsed = time.perf_counter() - t0
    counter.on = False
    if offset:
        credit += int(np.sum(np.minimum(bucket.horizons[: bucket.n_rows],
                                        offset)))
        host = jax.device_get(carry)
        st = jax.tree_util.tree_map(lambda x: x[: bucket.n_rows],
                                    _states(grid, host))
        lost += lost_rows(st, bucket.n_rows)
        snapshots[bi] = Snapshot(
            offset, st,
            host[1][: bucket.n_rows] if grid.collect == "summary" else None,
        )
    return Window(elapsed, credit, rows_started, lost, chunks, counter.n,
                  snapshots, traced)


# ---------------------------------------------------------------------------
# Correctness, device
# ---------------------------------------------------------------------------
def row_index(grid: Grid) -> dict:
    """bucket index -> [(row, case, seed index, SwitchLB branch), ...] for
    the rows the check compares (one sampled seed per LB cell)."""
    out = {}
    pick = dict(zip((c.name for c in grid.cases), grid.inputs.sample))
    for bi, bucket in enumerate(grid.engine.buckets):
        for cell in bucket.cells:
            si = pick[cell.case.name]
            out.setdefault(bi, []).append(
                (cell.rows[si], cell.case, si, cell.branch)
            )
    return out


def device_record(devices, peak_bytes: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak_bytes)}


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run(spec: CellSpec, seed: int, seconds: float, trace: bool,
        t_start: float, devices, trace_dir: str | None = None,
        chunk_hook=None, log=print) -> dict:
    """One run of a cell: set-up, window, check.  Returns the result line
    (a dict whose last key is ``checks``)."""
    t_build = time.time()
    grid = build_grid(spec, seed)
    t_warm = time.time()
    carry = warm_up(grid)
    setup_s = time.time() - t_start
    log(f"setup_s={setup_s:.3f} (start {t_build - t_start:.3f}, build "
        f"{t_warm - t_build:.3f}, warm-up {t_start + setup_s - t_warm:.3f}) "
        f"buckets={len(grid.engine.buckets)} "
        f"rows={[b.n_rows for b in grid.engine.buckets]}")
    window = run_window(grid, carry, seconds,
                        trace_dir=trace_dir if trace else None,
                        chunk_hook=chunk_hook)
    del carry
    log(f"window: {window.seconds:.3f} s, {window.chunks} chunks, "
        f"{window.row_ticks} row-ticks, compiles inside: {window.compiles}")
    used = devices[: spec.chips]
    peak = memory_peak(used)
    index = row_index(grid)
    n_conns = grid.inputs.workload.n_conns
    op_names = trace_reduce.program_ops(
        fn.as_text() for prog in grid.engine.programs.values()
        for fn in prog.chunk_fns.values()
    ) if trace else None
    inputs, cfg, collect, chunk = grid.inputs, grid.cfg, grid.collect, grid.chunk
    del grid
    gc.collect()

    t_ref = time.time()
    numbers, compared = reference.check(
        cfg, inputs, index, window.snapshots, collect, chunk,
        spec.traffic["ticks"],
    )
    log(f"reference: {compared} rows in {time.time() - t_ref:.3f} s")
    correct = compared > 0 and all(
        v["value"] <= v["limit"] for v in numbers.values()
    )
    out = {
        "correct": bool(correct),
        "attempted": window.rows_started,
        "failed": window.rows_lost,
    }
    if trace:
        ctx = {
            "trace": None, "window": window,
            "kernel_work": kernel_work.bucket_tick(
                cfg, n_conns, window.traced["lb_rows"]),
            "device_kind": devices[0].device_kind, "n_devices": spec.chips,
        }
        metrics, breakdown, dev_extra = traced_metrics(spec, ctx, trace_dir,
                                                       op_names)
    else:
        metrics = {
            "row_ticks_per_s": {"value": window.row_ticks / window.seconds,
                                "unit": "row-ticks/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        metrics = {m["name"]: metrics[m["name"]] for m in spec.end_to_end}
        breakdown, dev_extra = None, {}
    out["metrics"] = metrics
    out["device"] = {**device_record(used, peak), **dev_extra}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["window"] = {"seconds": window.seconds, "chunks": window.chunks,
                     "row_ticks": window.row_ticks,
                     "compiles": window.compiles}
    out["checks"] = numbers
    return out


def traced_metrics(spec: CellSpec, ctx: dict, trace_dir: str, op_names):
    """Per-layer metrics from the traced chunk (each reader may return
    ``None``, and its metric is then left out).  ``op_names``: the
    program's kernel and collective op names (``program_ops``)."""
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    summary = trace_reduce.reduce(paths[-1], *op_names)
    ctx["trace"] = summary
    metrics = {}
    for m in spec.per_layer:
        value = metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {"device_ops": summary.top_ops(10),
                 "idle_gaps": summary.idle_gaps(10)}
    extra = {"busy_s": summary.busy_s, "window_s": summary.window_s}
    return metrics, breakdown, extra
