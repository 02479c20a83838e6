"""The kernels' algorithmic work, counted from the paper fabric's shapes,
and only when a roofline reader asks for it."""
import types

import pytest

from bench import harness, kernel_work
from repro.netsim.config import SimConfig

NH = NC = 128  # hosts; a permutation has one conn per host
NQ = 3 * NH  # ToR uplinks, spine downlinks and ToR-to-host links
CFG = SimConfig(n_hosts=128, hosts_per_tor=16, uplinks_per_tor=16)


def by_kernel(work):
    out = {}
    for w in work:
        out.setdefault(w.kernel, []).append(w.bytes)
    return out


def test_counted_bytes_equal_a_hand_count():
    assert kernel_work.n_queues(CFG) == NQ
    by = by_kernel(kernel_work.row_tick(NH, NC, NQ, 2, "reps"))
    # feedback FIFO rank: NH ACK conn ids in, NH ranks out
    assert by["seg_rank"] == [4 * (NH + NH)]
    # feedback table: ids + 5 fields of NH ACKs in, 5 x 3 rounds x (NC+1)
    # out; then RTO (2 fields), delivery (4) and injection (2) into NC+1
    assert by["seg_sum"] == [4 * (NH + 5 * NH + 5 * 3 * (NC + 1)),
                             4 * (NH + 2 * NH + 2 * (NC + 1)),
                             4 * (NH + 4 * NH + 4 * (NC + 1)),
                             4 * (NH + 2 * NH + 2 * (NC + 1))]
    # arrivals: NQ + NH targets and uniforms and NQ lengths in; NQ lengths,
    # accept and mark flags (1 byte) and positions out
    k = NQ + NH
    assert by["queue_tick"] == [8 * k + 4 * NQ + 4 * NQ + 6 * k]
    # REPS, per conn: an ACK step reads 24 and writes 22 bytes, a timeout
    # step 6 and 5, a send step 26 and 17; plus the tick (4 bytes) each
    assert by["reps_tick"] == [46 * NC + 4] * 2 + [11 * NC + 4, 43 * NC + 4]


def test_reps_work_only_on_reps_rows():
    one = {w.kernel: w for w in kernel_work.row_tick(NH, NC, NQ, 2, "ecmp")}
    assert "reps_tick" not in one
    per = kernel_work.bucket_tick(CFG, NC, {"ecmp": 2, "ops": 2, "reps": 2})
    reps = [w for w in kernel_work.row_tick(NH, NC, NQ, 2, "reps")
            if w.kernel == "reps_tick"]
    assert per["reps_tick"].bytes == 2 * sum(w.bytes for w in reps)
    assert per["seg_rank"].bytes == 6 * 8 * NH
    assert per["seg_sum"].ops == 6 * (5 + 2 + 4 + 2) * NH


def test_least_time_is_bound_by_hbm():
    per = kernel_work.bucket_tick(CFG, NC, {"reps": 1})
    for w in per.values():
        assert w.ops < w.bytes
        assert kernel_work.least_time_s(w, "TPU v5 lite") == pytest.approx(
            w.bytes / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        kernel_work.peaks("TPU v0 imaginary")


def test_a_traced_run_counts_kernel_work_only_for_a_roofline_reader():
    window = types.SimpleNamespace(
        traced={"ticks": 10, "rows": 2, "lb_rows": {"reps": 2}})
    three_tier = SimConfig(n_hosts=128, hosts_per_tor=16, tiers=3,
                           tors_per_pod=2, aggs_per_pod=4, agg_uplinks=4)
    ctx = harness.trace_context(three_tier, NC, window, "TPU v5 lite", 1)
    with pytest.raises(NotImplementedError, match="2-tier"):
        ctx["kernel_work"]()  # asked for by a roofline reader alone
    ctx = harness.trace_context(CFG, NC, window, "TPU v5 lite", 1)
    work = ctx["kernel_work"]()
    assert work == kernel_work.bucket_tick(CFG, NC, {"reps": 2})
    assert ctx["kernel_work"]() is work  # counted once
    ctx["trace"] = types.SimpleNamespace(devices={
        0: types.SimpleNamespace(kernel_by={"seg_rank": 2e6})})
    want = kernel_work.least_time_s(work["seg_rank"] * 10, "TPU v5 lite")
    assert kernel_work.roofline(ctx, "seg_rank") == pytest.approx(
        100 * want / 2e-3)
