"""``BENCHMARK.json`` keeps the contract's shape, and a cell is data.

A throwaway cell made of files alone (a configuration, a traffic mix, a
per-layer metric reader and entries in a copy of ``BENCHMARK.json``) is
found by the harness, and its grid builds, with no edit to a file the
benchmark already has.
"""
import json
import pathlib
import re
import shutil

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_shape():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and 1 <= b["run_seconds"] <= 51
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
    cells = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert len(w["why"]) <= 200
        cells.add(w["name"])
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names) and "setup_s" in names
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
    for name in list(configs) + list(cells):
        assert NAME.match(name)


def test_a_cell_made_of_files_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/fattree128.json").read_text())
    cfg["sim"].update(n_hosts=32, hosts_per_tor=8, uplinks_per_tor=8)
    (tmp_path / "bench/configs/throwaway.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/throwaway_mix.json").write_text(json.dumps({
        "generator": "permutation", "msg_pkts": 16,
        "lbs": [{"lb": "reps", "kwargs": {}}], "seeds_per_lb": 3,
        "ticks": 200, "chunk_ticks": 50, "collect": "summary",
        "early_exit": True,
    }))
    (tmp_path / "bench/metrics/throwaway.rows.py").write_text(
        "def read(ctx):\n    return float(ctx['window'].traced['rows'])\n")
    b["configs"].append({"name": "throwaway", "source": "test",
                         "file": "bench/configs/throwaway.json",
                         "reduced": ["n_hosts"], "why": "test"})
    b["workloads"].append({"name": "throwaway.cell", "config": "throwaway",
                           "traffic": "throwaway_mix", "chips": 1,
                           "why": "test"})
    b["per_layer"].append({"name": "throwaway.rows", "unit": "rows",
                           "better": "higher", "source": "program_counter",
                           "layer": "sweep", "moves": "row_ticks_per_s",
                           "workloads": ["throwaway.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    spec = harness.load_cell("throwaway.cell", tmp_path)
    assert spec.config["sim"]["n_hosts"] == 32
    assert [m["name"] for m in spec.per_layer][-1] == "throwaway.rows"
    grid = harness.build_grid(spec, 123)
    assert [b.n_rows for b in grid.engine.buckets] == [3]
    assert grid.inputs.workload.n_conns == 32
    read = harness.metric_reader("throwaway.rows", tmp_path)

    class W:
        traced = {"rows": 3}
    assert read({"window": W}) == 3.0
    # the cells of the benchmark itself are untouched by the copy
    assert "throwaway.cell" not in {
        w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]}
