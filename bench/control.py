"""Read the control of a cell on the chip: the numbers ``correct`` compares,
with the reference computed in the nearest precision below the
configuration's (float state in bfloat16) in the program's place.

    python3 bench/control.py --workload <cell> --ticks <t> --seeds <n> ...

For each seed it builds the cell's inputs and the rows the check samples,
runs the reference and the control to ``--ticks`` (what a run's window
reaches), and prints one JSON line per seed.  The control must fail every
seed; the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--ticks", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness, reference
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    spec = harness.load_cell(args.workload, ROOT)
    failed = 0
    for seed in args.seeds:
        grid = harness.build_grid(spec, seed)
        numbers, rows = reference.control(
            grid.cfg, grid.inputs, harness.row_index(grid), args.ticks,
            grid.collect, grid.chunk, spec.traffic["ticks"], model=grid.model,
        )
        ok = rows > 0 and all(v["value"] <= v["limit"]
                              for v in numbers.values())
        failed += not ok
        print(json.dumps({"workload": spec.name, "seed": seed,
                          "ticks": args.ticks, "rows": rows,
                          "control_correct": ok, "checks": numbers,
                          "device": jax.devices()[0].device_kind}),
              flush=True)
        del grid
    return 0 if failed == len(args.seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
