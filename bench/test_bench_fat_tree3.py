"""The 3-tier fat-tree deployment (``fattree_k8``) and its check.

At k = 4 (16 hosts: 4 pods of 2 ToRs and 2 aggs, 2 hosts a ToR, 4 cores)
on the CPU, the cell ``fattree_k8_perm.mixed_lb`` is built from a copy of
its files: the configuration with the regime ``down_uplinks_every_tier``
and the reference ``bench/references/fat_tree3.py``.  The program's window
against that reference is correct, and not vacuously so: packets were lost
on failed links, a link is down at each choice hop, and packets were served
on the cores' downlinks.  Each fault fails the check: the program's state
left unchanged, the reference's second-hop salt altered, one field dropped
from the view, and the control (the reference with its float state in
bfloat16).  At k = 8 the regime takes 6 ToR-to-agg and 6 agg-to-core links
down; the cell's generated inputs equal the digests in
``testdata/fattree_k8_inputs_sha256.json``, and its workload is fig07's:

    PYTHONPATH=src:. python bench/test_bench_fat_tree3.py --record
"""
import json
import pathlib
import shutil
import sys
import types

import numpy as np
import pytest

from bench import extensions, harness, plainsim, reference
from bench.test_bench_inputs import digests
from bench.tiny import shrink
from repro.netsim.config import SimConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]
CELL = "fattree_k8_perm.mixed_lb"
SEED = 2**31 + 3170  # a seed past 32 signed bits
K4 = dict(n_hosts=16, hosts_per_tor=2, uplinks_per_tor=2, tors_per_pod=2,
          aggs_per_pod=2, agg_uplinks=2)
SEEDS = (0, 1, 3140000011)
DIGESTS = pathlib.Path(__file__).resolve().parent / "testdata" / \
    "fattree_k8_inputs_sha256.json"


@pytest.fixture(scope="module")
def warm(tmp_path_factory):
    """The k = 4 cell from a copy of its files, warm, with its sound
    window."""
    root = tmp_path_factory.mktemp("k4")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    spec = shrink(harness.load_cell(CELL, root))
    spec.config["sim"].update(K4)
    grid = harness.build_grid(spec, SEED)
    harness.warm_up(grid)
    return grid, window_of(grid)


def window_of(grid, hook=None):
    """The window over the whole horizon (failures start at tick 150): a
    clock that reads one second a chunk, for as many seconds as the
    bucket has chunks."""
    eng = grid.engine
    bucket = eng.buckets[0]
    step = hook or (lambda c, b, t0, n: eng.run_chunk(
        b, c, t0, n, grid.collect, grid.tel_spec)[0])
    ran = []

    def counted(c, b, t0, n):
        ran.append(n)
        return step(c, b, t0, n)

    carry = eng.bucket_carry(bucket, grid.collect, grid.tel_spec)
    return harness.run_window(grid, carry, len(grid.sizes(bucket)),
                              chunk_hook=counted, clock=lambda: len(ran))


def numbers_of(grid, window, model=None):
    numbers, rows = reference.check(
        grid.cfg, grid.inputs, harness.row_index(grid), window.snapshots,
        grid.collect, grid.chunk, grid.spec.traffic["ticks"],
        model=model or grid.model)
    assert rows == 3  # one sampled row per LB cell
    return numbers


def passes(numbers):
    return all(v["value"] <= v["limit"] for v in numbers.values())


def test_sound_window_is_correct_and_meets_both_tiers(warm):
    grid, window = warm
    assert grid.model.Row is not plainsim.Row
    assert issubclass(grid.model.Row, plainsim.Row)
    assert window.compiles == 0
    numbers = numbers_of(grid, window)
    assert passes(numbers), numbers
    # a failed link at each choice hop
    cfg = grid.cfg
    tor_ups = cfg.n_tors * cfg.aggs_per_pod
    agg_ups = cfg.n_pods * cfg.aggs_per_pod * cfg.agg_uplinks
    q = np.sort(grid.inputs.failures.queue)
    assert len(q) == 2 and q[0] < tor_ups <= q[1] < tor_ups + agg_ups
    # packets lost on them, and packets served down from the cores
    (_, snap), = window.snapshots
    assert snap.finalized and snap.ticks == grid.spec.traffic["ticks"]
    st = snap.state
    assert int(np.sum(np.asarray(st.s_stats)[:, 1])) > 0  # drops_fail
    core_down = slice(tor_ups + agg_ups, tor_ups + 2 * agg_ups)
    assert int(np.sum(np.asarray(st.q_served)[:, core_down])) > 0


def test_state_left_unchanged_fails(warm):
    grid, _ = warm
    window = window_of(grid, hook=lambda c, b, t0, n: c)
    assert not passes(numbers_of(grid, window))


def test_second_hop_salt_altered_fails(warm, monkeypatch):
    grid, window = warm
    monkeypatch.setattr(grid.model, "AGG_SALT", grid.model.AGG_SALT + 1)
    numbers = numbers_of(grid, window)
    assert numbers["state_mismatch"]["value"] > 0


def test_a_view_field_dropped_fails(warm):
    grid, window = warm
    model = grid.model
    short = types.SimpleNamespace(
        Row=model.Row,
        view=lambda s: {k: v for k, v in model.view(s).items()
                        if k != "q_served"})
    assert not passes(numbers_of(grid, window, short))


def test_control_fails(warm):
    grid, window = warm
    numbers, rows = reference.control(
        grid.cfg, grid.inputs, harness.row_index(grid),
        grid.spec.traffic["ticks"], grid.collect, grid.chunk,
        grid.spec.traffic["ticks"], model=grid.model)
    assert rows == 3 and not passes(numbers)
    assert numbers["state_mismatch"]["value"] > 0


def test_k8_regime_takes_six_links_down_at_each_choice_hop():
    config = json.loads((ROOT / "bench/configs/fattree_k8.json").read_text())
    cfg = SimConfig(**config["sim"])
    regime = extensions.find(ROOT, "failure_regimes",
                             config["failures"]["kind"])
    fs = regime.schedule(cfg, config["failures"], 12345)
    q = np.asarray(fs.queue)
    assert len(set(q.tolist())) == 12
    assert np.sum(q < 128) == 6  # ToR -> agg: 32 ToRs x 4 aggs
    assert np.sum((q >= 128) & (q < 256)) == 6  # agg -> core: 32 x 4
    assert np.all(fs.start == 150) and np.all(fs.kind == 0)
    assert np.all(fs.end > 10**9)
    two_tier = json.loads(
        (ROOT / "bench/configs/fattree128.json").read_text())["sim"]
    with pytest.raises(ValueError, match="3-tier"):
        regime.schedule(SimConfig(**two_tier), config["failures"], 1)


@pytest.mark.parametrize("seed", SEEDS)
def test_inputs_are_the_recorded_ones(seed):
    want = json.loads(DIGESTS.read_text())[str(seed)]
    assert digests(CELL, seed) == want


@pytest.mark.parametrize("seed", SEEDS)
def test_workload_is_fig07s(seed):
    mine, fig07 = digests(CELL, seed), digests("fig07_perm.mixed_lb", seed)
    for k in ("src", "dst", "msg_pkts", "start", "dep", "row_seeds",
              "sample"):
        assert mine[k] == fig07[k], k
    assert mine["failures.queue"] != fig07["failures.queue"]


if __name__ == "__main__" and sys.argv[1:] == ["--record"]:
    DIGESTS.write_text(json.dumps({str(s): digests(CELL, s) for s in SEEDS},
                                  indent=1, sort_keys=True) + "\n")
