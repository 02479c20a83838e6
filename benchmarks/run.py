"""Benchmark harness: one module per paper figure/table.

Prints ``name,us_per_call,derived`` CSV rows and writes a machine-readable
``BENCH_netsim.json`` (name -> us_per_call / derived / ticks-per-sec where
applicable) so perf trajectory is tracked across PRs.

BENCH_FULL=1 switches to paper-scale constants.  Select subsets with
BENCH_ONLY=fig02,fig13.  BENCH_SMOKE=1 shrinks figure mains to CI-smoke
subsets; BENCH_SEEDS=N runs netsim scenarios as N-seed vmapped fleets.
``--collect {none,summary,full}`` (or BENCH_COLLECT) picks the sweep
collection mode figure grids run under: "summary" (default) folds
on-device telemetry sketch channels into the scans
(repro.netsim.telemetry) and builds figure metrics from the sketches,
"none" keeps state-built summaries only, "full" streams raw traces as a
parity reference and forgoes quiescence early exit.
``--trace N`` (or BENCH_TRACE) folds the on-device flight recorder into
summary-mode grids with an N-slot ring; rows are stamped with their trace
context and CI throughput gates only compare trace-off rows.
"""
import argparse
import json
import os
import platform
import sys
import time

MODULES = [
    "table1_footprint",
    "scale_smoke",  # no-op unless BENCH_SCALE_CONNS is set (scale-smoke CI)
    "fig13_balls_bins",
    "fig16_evs_imbalance",
    "fig17_coalesced_bins",
    "fig01_tornado_micro",
    "fig03_asym_micro",
    "fig05_background",
    "fig06_failures_micro",
    "fig09_fpga_analogue",
    "fig15_forced_freezing",
    "fig18_three_tier",
    "fig11_ack_coalescing",
    "fig12_evs_cc",
    "fig04_asym_macro",
    "fig07_failures_macro",
    "fig08_extreme",
    "fig19_incremental",
    "fig02_symmetric",
    "arena",
    "reps_channels_bench",
]

JSON_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_netsim.json")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--collect",
        choices=["none", "summary", "full"],
        default=os.environ.get("BENCH_COLLECT", "summary"),
        help="sweep collection mode for figure grids (default: "
        "BENCH_COLLECT or 'summary')",
    )
    ap.add_argument(
        "--trace",
        type=int,
        default=int(os.environ.get("BENCH_TRACE", "0")),
        help="flight-recorder ring size for summary-mode figure grids "
        "(0 = off, the default; also BENCH_TRACE).  Observation-only: "
        "metrics are bit-identical either way; rows are stamped with the "
        "trace context so CI throughput gates skip traced rows.",
    )
    args = ap.parse_args(argv)
    if args.trace < 0:
        ap.error(f"--trace must be >= 0, got {args.trace}")
    if args.collect not in ("none", "summary", "full"):
        # argparse validates `choices` only for flag-provided values, not
        # for the BENCH_COLLECT-derived default
        ap.error(f"invalid BENCH_COLLECT {args.collect!r} "
                 "(choose from none, summary, full)")
    # benchmarks.common reads the env at import; set it before importing so
    # the flag plumbs through figure_grid and into every row's context stamp.
    # Programmatic callers may have imported benchmarks.common already — its
    # COLLECT global is read at call time, so patch it too.
    os.environ["BENCH_COLLECT"] = args.collect
    os.environ["BENCH_TRACE"] = str(args.trace)
    if "benchmarks.common" in sys.modules:
        sys.modules["benchmarks.common"].COLLECT = args.collect
        sys.modules["benchmarks.common"].TRACE = args.trace
    from benchmarks.common import COLLECT, FULL, SEEDS, SMOKE, TRACE, Rows
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    only = os.environ.get("BENCH_ONLY")
    selected = MODULES
    if only:
        keys = [k.strip() for k in only.split(",")]
        selected = [m for m in MODULES if any(m.startswith(k) for k in keys)]
    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    records: dict[str, dict] = {}
    for mod_name in selected:
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
        try:
            result = mod.main()
        except Exception as e:  # noqa: BLE001
            failed.append((mod_name, repr(e)))
            print(f"{mod_name},0,ERROR={e!r}", flush=True)
            continue
        if isinstance(result, Rows):
            for rec in result.records:
                records[rec["name"]] = {k: v for k, v in rec.items() if k != "name"}
    wall = time.time() - t0
    print(f"# total_wall_s={wall:.0f} failed={len(failed)}")
    modules = list(selected)
    if only and os.path.exists(JSON_PATH):
        # Subset run: merge into the existing baseline instead of erasing
        # rows for modules that were not selected — BENCH_netsim.json is
        # the cross-PR perf trajectory, each row keeps its latest sample.
        # meta must then describe the *merged* file, not just this run:
        # modules become the union, and full_scale/smoke/seeds are derived
        # from the per-row context stamps (mixed runs are marked "mixed").
        try:
            with open(JSON_PATH) as f:
                prev = json.load(f)
            # {fig}/bucket/* row names encode the PackPlan's bucketing, so a
            # replan (packer/grid change) can retire names a plain key merge
            # would carry forever: drop every stale bucket row of a figure
            # this run re-planned (its fresh bucket rows are in `records`).
            replanned = {
                n.split("/bucket/")[0] for n in records if "/bucket/" in n
            }
            prev_rows = {
                k: v
                for k, v in prev.get("rows", {}).items()
                if not (
                    "/bucket/" in k and k.split("/bucket/")[0] in replanned
                )
            }
            records = {**prev_rows, **records}
            modules = sorted(set(prev.get("meta", {}).get("modules", [])) | set(selected))
        except (json.JSONDecodeError, OSError):
            pass

    def _row_consensus(key, default):
        # rows without a context stamp (pre-stamp legacy merges) must not
        # be backfilled with the current run's flag — that would launder a
        # mixed file into a unanimous one; treat "absent" as its own value.
        vals = {rec.get(key) for rec in records.values()}
        if len(vals) != 1:
            return "mixed"
        v = vals.pop()
        return default if v is None else v

    payload = {
        "meta": {
            "full_scale": _row_consensus("full_scale", FULL),
            "smoke": _row_consensus("smoke", SMOKE),
            "seeds": _row_consensus("seeds", SEEDS),
            "collect": _row_consensus("collect", COLLECT),
            "trace": _row_consensus("trace", TRACE),
            "modules": modules,
            # figures that ran as sweep batches (figure_grid emits one
            # aggregate row per figure; CI gates these)
            "sweep_totals": sorted(
                k for k in records if k.endswith("/sweep_total")
            ),
            "failed": [m for m, _ in failed],
            "total_wall_s": wall,
            "platform": platform.platform(),
            "python": platform.python_version(),
        },
        "rows": records,
    }
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# wrote {JSON_PATH} ({len(records)} rows)")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
