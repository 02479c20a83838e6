"""CPU rehearsals of the benchmark's command and of its four-chip cell.

* The command exits non-zero, with no result line, where JAX finds no TPU,
  and in a directory that holds only ``BENCHMARK.json`` and ``bench/``.
* The conn-sharded scale cell runs its window and check through the
  harness's functions on four virtual CPU devices, at a tiny size, with
  nothing compiled inside the window, and comes out correct; with the exchange between chips left out (every
  ``all_gather`` of the tick replaced by copies of the local shard) it
  comes out not correct, and so it does with its state left unchanged.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

ROOT = pathlib.Path(__file__).resolve().parents[1]

FOUR_DEVICES = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, time
    from unittest import mock
    import jax, jax.numpy as jnp
    from bench import harness, reference
    from bench.tiny import shrink

    def cell():
        return shrink(harness.load_cell("scale_1e5.conn4"), chips=4)

    seed = 2**31 + 99
    out = harness.run(cell(), seed, 0.0, False, time.time(), jax.devices(),
                      log=lambda m: None)

    def no_exchange(x, axis_name, axis=0, tiled=False):
        n = jax.lax.psum(1, axis_name)
        return jnp.concatenate([x] * n, axis=axis)

    spec = cell()
    spec.traffic["chunk_ticks"] = spec.traffic["ticks"]  # conns of every shard start
    with mock.patch("jax.lax.all_gather", no_exchange):
        grid = harness.build_grid(spec, seed)
        carry = harness.warm_up(grid)
    window = harness.run_window(grid, carry, 0.0)
    numbers, rows = reference.check(
        grid.cfg, grid.inputs, harness.row_index(grid), window.snapshots,
        grid.collect, grid.chunk, grid.spec.traffic["ticks"])
    faulty = rows > 0 and all(v["value"] <= v["limit"] for v in numbers.values())
    carry = grid.engine.bucket_carry(grid.engine.buckets[0], grid.collect,
                                     grid.tel_spec)
    window = harness.run_window(grid, carry, 0.0,
                                chunk_hook=lambda c, b, t0, n: c)
    still, _ = reference.check(
        grid.cfg, grid.inputs, harness.row_index(grid), window.snapshots,
        grid.collect, grid.chunk, grid.spec.traffic["ticks"])
    print(json.dumps({"correct": out["correct"], "devices": out["device"]["count"],
                      "compiles": out["window"]["compiles"],
                      "checks": out["checks"], "faulty_correct": faulty,
                      "faulty_checks": numbers, "unchanged_checks": still}))
    """
)


def env():
    e = dict(os.environ)
    e["PYTHONPATH"] = f"{ROOT / 'src'}{os.pathsep}{ROOT}"
    e["JAX_PLATFORMS"] = "cpu"
    return e


def run_cli(cwd):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fig07_perm.mixed_lb",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300, env=env(),
    )


def test_command_refuses_a_machine_without_a_tpu():
    r = run_cli(ROOT)
    assert r.returncode != 0 and r.stdout.strip() == "", r.stderr[-2000:]
    assert "no TPU" in r.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    e = env()
    e["PYTHONPATH"] = ""
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fig07_perm.mixed_lb",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300, env=e,
    )
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_conn_sharded_cell_on_four_devices():
    r = subprocess.run([sys.executable, "-c", FOUR_DEVICES], cwd=ROOT,
                       capture_output=True, text=True, timeout=600, env=env())
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    assert out["compiles"] == 0  # nothing compiles inside the window
    assert out["correct"], out["checks"]
    assert not out["faulty_correct"], out["faulty_checks"]
    assert out["unchanged_checks"]["state_mismatch"]["value"] > 0
