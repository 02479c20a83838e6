"""``BENCHMARK.json`` keeps the contract's shape, and a cell is data.

A throwaway cell made of files alone (a configuration, a traffic mix, a
per-layer metric reader and entries in a copy of ``BENCHMARK.json``) is
found by the harness, and its grid builds, with no edit to a file the
benchmark already has.  So is a throwaway deployment that brings its own
generator kind, failure regime and reference extension: it runs, its check
passes, and a planted fault fails it.
"""
import json
import pathlib
import re
import shutil
import textwrap
import types

import numpy as np
import pytest

from bench import generate, harness, plainsim, reference
from repro.netsim.config import SimConfig

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


def copy_of_bench(tmp_path) -> dict:
    """``bench/`` copied under ``tmp_path``; returns ``BENCHMARK.json``."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_a_cell_made_of_files_alone(tmp_path):
    b = copy_of_bench(tmp_path)
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


# A deployment of its own: the program's ring all-reduce (``netsim.workloads.
# ring_allreduce``, its arithmetic copied) over 8 hosts drawn from the seed,
# one spine's downlinks down from tick 60, and a reference extension that
# writes the next-hop rule out per hop and compares one more number.
RING = """
import numpy as np

from repro.netsim.engine import Workload


def workload(cfg, traffic, seed):
    p = traffic["hosts"]
    hosts = np.random.RandomState(seed).choice(cfg.n_hosts, p, replace=False)
    chunk = max(1, traffic["total_msg_pkts"] // p)
    src, dst, msg, start, dep = [], [], [], [], []
    conn_id = {}
    for r in range(2 * (p - 1)):
        for i in range(p):
            conn_id[(i, r)] = len(src)
            src.append(hosts[i])
            dst.append(hosts[(i + 1) % p])
            msg.append(chunk)
            start.append(0)
            dep.append(-1 if r == 0 else conn_id[((i - 1) % p, r - 1)])
    a = lambda x: np.asarray(x, np.int32)  # noqa: E731
    return Workload(src=a(src), dst=a(dst), msg_pkts=a(msg), start=a(start),
                    dep=a(dep), name="ring_allreduce")
"""
SPINE_DOWN = """
import numpy as np

from bench.generate import FOREVER
from repro.netsim.engine import FailureSchedule


def schedule(cfg, params, seed):
    T, U = cfg.n_hosts // cfg.hosts_per_tor, cfg.uplinks_per_tor
    spine = np.random.RandomState(seed).randint(U)
    q = (T * U + spine * T + np.arange(T)).astype(np.int32)
    return FailureSchedule(queue=q, start=np.full(T, params["start"], np.int32),
                           end=np.full(T, FOREVER, np.int32),
                           kind=np.zeros(T, np.int32))
"""
PER_HOP = """
import jax.numpy as jnp
import numpy as np

from bench import plainsim


class Row(plainsim.Row):
    def _next_queue(self, scn, first_hop, cur, conn, ev):
        sh = self.sh
        s_tor, d_tor = scn["src"][conn] // sh.H, scn["dst"][conn] // sh.H
        host_down = sh.down_base + scn["dst"][conn]
        uplink = s_tor * sh.U + plainsim.path_hash(conn, ev, s_tor, sh.U)
        spine_down = sh.T * sh.U + (cur % sh.U) * sh.T + d_tor
        leaving = first_hop & (s_tor != d_tor)
        on_uplink = ~first_hop & (cur < sh.T * sh.U)
        return jnp.where(leaving, uplink,
                         jnp.where(on_uplink, spine_down, host_down))


def view(st):
    return {**plainsim.view(st), "n_done": np.sum(np.asarray(st["done"]))}


def program_view(state, lb_state):
    return {"n_done": np.sum(np.asarray(state.c_done))}
"""


def deployment(tmp_path):
    """A throwaway cell whose kind, regime and reference are new files."""
    b = copy_of_bench(tmp_path)
    new = {"generators/ring_allreduce.py": RING,
           "failure_regimes/spine_down.py": SPINE_DOWN,
           "references/per_hop.py": PER_HOP}
    for path, text in new.items():
        (tmp_path / "bench" / path).parent.mkdir(exist_ok=True)
        (tmp_path / "bench" / path).write_text(textwrap.dedent(text))
    cfg = json.loads((ROOT / "bench/configs/fattree128.json").read_text())
    cfg["sim"].update(n_hosts=32, hosts_per_tor=8, uplinks_per_tor=8,
                      evs_size=256, queue_capacity=48, init_cwnd_pkts=40,
                      max_cwnd_pkts=80, rto_ticks=400, max_msg_pkts=512,
                      hop_latency_ticks=4, ack_delay_ticks=8)
    cfg.update(failures={"kind": "spine_down", "start": 60},
               reference="per_hop")
    (tmp_path / "bench/configs/ring32.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/ring8.json").write_text(json.dumps({
        "generator": "ring_allreduce", "hosts": 8, "total_msg_pkts": 64,
        "lbs": [{"lb": "reps", "kwargs": {}}], "seeds_per_lb": 2,
        "ticks": 500, "chunk_ticks": 500, "collect": "summary",
        "early_exit": True,
    }))
    b["configs"].append({"name": "ring32", "source": "test",
                         "file": "bench/configs/ring32.json",
                         "reduced": ["n_hosts"], "why": "test"})
    b["workloads"].append({"name": "ring32.ring8", "config": "ring32",
                           "traffic": "ring8", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return harness.load_cell("ring32.ring8", tmp_path)


def passes(numbers):
    return all(v["value"] <= v["limit"] for v in numbers.values())


def test_a_deployment_made_of_new_files_alone(tmp_path):
    before = {p: p.read_bytes() for p in (ROOT / "bench").rglob("*.py")}
    spec = deployment(tmp_path)
    grid = harness.build_grid(spec, 2**31 + 7)
    inputs, model = grid.inputs, grid.model
    assert issubclass(model.Row, plainsim.Row)
    assert model.Row is not plainsim.Row
    wl, fs = inputs.workload, inputs.failures
    assert wl.n_conns == 8 * 14 and int(np.sum(wl.dep >= 0)) == 8 * 13
    spines = set((fs.queue - 4 * 8) // 4)  # T * U, then T queues a spine
    assert len(fs.queue) == 4 and len(spines) == 1

    def check(model, window):
        numbers, rows = reference.check(
            grid.cfg, inputs, harness.row_index(grid), window.snapshots,
            grid.collect, grid.chunk, spec.traffic["ticks"], model=model)
        assert rows == 1
        return numbers

    carry = harness.warm_up(grid)
    window = harness.run_window(grid, carry, 0.0)
    assert window.compiles == 0
    numbers = check(model, window)
    assert passes(numbers), numbers
    # rounds finished in dependency order, and the failure was met
    (_, snap), = window.snapshots
    st = snap.state
    row = harness.row_index(grid)[0][0][0]
    done, tick = np.asarray(st.c_done[row]), np.asarray(st.c_done_tick[row])
    gated = done & (wl.dep >= 0)
    assert gated.sum() >= 8
    assert np.all(tick[gated] > tick[wl.dep[gated]])
    assert int(st.s_stats[row][1]) > 0  # drops on the failed links

    # the extension's view drops one field: the check fails
    short = types.SimpleNamespace(
        Row=model.Row, program_view=model.program_view,
        view=lambda s: {k: v for k, v in model.view(s).items() if k != "rr"})
    numbers = check(short, window)
    assert not passes(numbers)
    # the program's state left unchanged: the check fails
    carry = grid.engine.bucket_carry(grid.engine.buckets[0], grid.collect,
                                     grid.tel_spec)
    window = harness.run_window(grid, carry, 0.0,
                                chunk_hook=lambda c, b, t0, n: c)
    assert not passes(check(model, window))
    # no file of the benchmark changed
    assert before == {p: p.read_bytes()
                      for p in (ROOT / "bench").rglob("*.py")}


@pytest.mark.parametrize("where", ["generators", "failure_regimes",
                                   "references"])
def test_an_unknown_kind_names_the_file_it_looked_for(where):
    spec = harness.load_cell("fig07_perm.mixed_lb")
    config, traffic = dict(spec.config), dict(spec.traffic)
    if where == "generators":
        traffic["generator"] = "nowhere"
    elif where == "failure_regimes":
        config["failures"] = {**config["failures"], "kind": "nowhere"}
    else:
        config["reference"] = "nowhere"
    path = ROOT / "bench" / where / "nowhere.py"
    with pytest.raises(ValueError, match=re.escape(str(path))):
        generate.build(config, traffic, SimConfig(**config["sim"]), 1)
        reference.model_of(config)
