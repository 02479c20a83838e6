"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Builds the cell's grid from its files
(``BENCHMARK.json``, ``bench/configs/``, ``bench/traffic/``), warms up every
program the window uses (that is ``setup_s``), drives the sweep path for
``--seconds`` (to the next chunk boundary), then compares sampled rows with
the serial reference.  The last line of standard output is the result as
one JSON object; the numbers compared, each beside its limit, are the last
lines of standard error.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.

JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the program takes the cache directory it is given
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # The TPU runtime maps a host staging buffer when it starts.  Without
    # transparent hugepages the default size takes 6-14 s to map on a v5e
    # host and varies from run to run; 256 MiB maps in 1-2 s.  The
    # window moves a few bytes to the host per chunk.
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(256 << 20))
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    t_jax = time.time()

    from bench import harness
    from repro.utils.compile_cache import enable_compile_cache

    spec = harness.load_cell(args.workload, ROOT)
    t_imports = time.time()
    devices = jax.devices()
    t_devices = time.time()
    if devices[0].platform != "tpu":
        log(f"JAX found no TPU (platform {devices[0].platform!r})")
        return 2
    if len(devices) < spec.chips:
        log(f"{spec.name} needs {spec.chips} chips, found {len(devices)}")
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log(f"{spec.name}: seed {args.seed}, {args.seconds} s, trace "
        f"{args.trace}, {len(devices)} x {devices[0].device_kind}; import "
        f"jax {t_jax - T_START:.3f} s, the rest {t_imports - t_jax:.3f} s, "
        f"devices {t_devices - t_imports:.3f} s")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        out = harness.run(spec, args.seed, args.seconds, bool(args.trace),
                          T_START, devices, trace_dir=trace_dir, log=log)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"correct={out['correct']} attempted={out['attempted']} "
        f"failed={out['failed']}")
    for name, v in out["checks"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
