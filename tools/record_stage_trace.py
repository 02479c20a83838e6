"""Record the small TPU trace that ``bench/test_bench_stages.py`` reads.

    python tools/record_stage_trace.py [out_dir]   # default bench/testdata/

Runs on a TPU host.  Drives the fig07 cell cut to the CPU tests' size
(``bench.tiny``) in 2-tick chunks through the harness's own window, with
the profiler on for the first chunk and its quiescence poll, and writes
the trace (device ops with their op paths, the programs' HLO, the
``bench.*`` host spans) as ``v5e_stages.xplane.pb``.
"""
import glob
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402

from bench import harness  # noqa: E402
from bench.tiny import shrink  # noqa: E402


def main(out: pathlib.Path) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    spec = shrink(harness.load_cell("fig07_perm.mixed_lb", ROOT))
    spec.traffic.update(ticks=20, chunk_ticks=2)
    grid = harness.build_grid(spec, 2147483659)
    carry = harness.warm_up(grid)
    trace_dir = tempfile.mkdtemp(prefix="stage_trace_")
    try:
        harness.run_window(grid, carry, 0.0, trace_dir=trace_dir)
        (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        out.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, out / "v5e_stages.xplane.pb")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"wrote {out}/v5e_stages.xplane.pb")


if __name__ == "__main__":
    main(pathlib.Path(sys.argv[1] if len(sys.argv) > 1 else
                      ROOT / "bench" / "testdata"))
