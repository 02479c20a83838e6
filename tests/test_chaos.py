"""Chaos engine (repro.netsim.chaos): invariants, campaign, shrinking.

The contract under test:

* **Scenario plumbing** — generated scenarios are a pure function of the
  campaign seed, cover every fault archetype across the first cycle, and
  round-trip through their JSON artifact encoding.
* **Green path** — REPS survives a generated scenario (invariants all
  hold, including the kill/resume bit-parity check on scenario 0).
* **Teeth** — the known-bad fixture (ecmp under a permanent half-fabric
  outage) violates deterministically; the same faults under REPS do not.
* **Shrinking** — a violating scenario shrinks to a smaller one that
  still violates, and the emitted artifact replays bit-exactly (digest
  equality), which is the repro contract the CI job uploads.
* **Checker sensitivity** — the invariant monitor flags corrupted
  carries (conservation / monotonicity), not just macro outcomes.
"""
import dataclasses
import json

from repro.netsim import chaos
from repro.netsim.chaos import (
    ARCHETYPES, ChaosCampaign, ChaosFault, ChaosInvariants, ChaosScenario,
    known_bad_scenario, record_digest,
)


def _small_campaign(**kw):
    c = ChaosCampaign(seed=11, budget_s=1.0, min_scenarios=1,
                      max_scenarios=1, **kw)
    # lighter messages keep a test-scale run in CI budget; the horizon
    # must stay at full scale (fault windows need rto + chunk slack)
    c.MSG_PKTS = 24
    return c


def test_generate_is_deterministic_and_covers_archetypes():
    c = _small_campaign()
    a = [c.generate(i) for i in range(len(ARCHETYPES))]
    b = [c.generate(i) for i in range(len(ARCHETYPES))]
    assert a == b
    primaries = [s.faults[0].archetype for s in a]
    assert primaries[0] == "link_down"
    assert primaries[1] == "link_degraded"
    assert primaries[2] == "link_flapping"
    assert primaries[3] == "gray_loss"
    assert primaries[4] in ("switch_down", "switch_degraded", "spine_down")


def test_scenario_round_trips_through_json():
    s = known_bad_scenario()
    blob = json.dumps(s.to_dict(), sort_keys=True)
    assert ChaosScenario.from_dict(json.loads(blob)) == s


def test_reps_survives_generated_scenario_with_resume_parity():
    c = _small_campaign()
    s = c.generate(0)  # resume_check=True: includes kill/resume parity
    assert s.resume_check
    violations, record = c.run_scenario(s)
    assert violations == []
    assert record["summaries"][s.name][0]["completed"] == 32


def test_known_bad_fixture_violates_and_reps_does_not():
    c = ChaosCampaign(seed=1)
    bad = known_bad_scenario(ticks=640, chunk=160)
    violations, _ = c.run_scenario(bad)
    assert violations, "ecmp under half-fabric outage must violate"
    assert {v.invariant for v in violations} == {"completion"}
    # the control needs the full fixture horizon: REPS rides out up to two
    # 400-tick RTO rounds before every retransmit lands on the live half
    good = dataclasses.replace(
        known_bad_scenario(), name="chaos/control/reps", lb="reps"
    )
    assert c.run_scenario(good)[0] == []


def test_shrink_produces_smaller_bit_exact_replayable_repro(tmp_path):
    c = ChaosCampaign(seed=1)
    # start from an already-small violating scenario so the greedy loop
    # converges in a handful of runs: under jax's default random stream,
    # ECMP hashes a conn of ToR 0's first 8 onto spine 1, where it
    # livelocks
    seedling = dataclasses.replace(
        known_bad_scenario(ticks=320, chunk=160),
        faults=(ChaosFault("spine_down", tor=0, spine=1, start=8,
                           end=chaos.failures.FOREVER),),
        msg_pkts=6, n_conns=8,
    )
    violations, _ = c.run_scenario(seedling)
    assert violations
    minimal, mv, mrec = c.shrink(seedling)
    assert mv, "shrunken scenario must still violate"
    assert (
        minimal.n_conns < 8 or minimal.msg_pkts < 6
    ), f"shrink made no progress: {minimal}"
    artifact = c.make_artifact(minimal, mv, mrec)
    path = tmp_path / "repro.json"
    path.write_text(json.dumps(artifact, sort_keys=True))
    loaded = json.loads(path.read_text())
    rv, bit_exact = c.replay(loaded)
    assert rv and bit_exact, "artifact replay must reproduce bit-exactly"
    assert "chaos_campaign" in loaded["repro"]


def test_monitor_flags_corrupted_carry():
    """Feed the checker a deliberately corrupted state: conservation and
    monotone invariants must fire (the checker is not outcome-only)."""
    import jax

    c = _small_campaign()
    s = dataclasses.replace(c.generate(0), resume_check=False,
                            faults=(), name="chaos/corrupt")
    runner = c._runner(s)
    inv = ChaosInvariants(no_progress_window=10**9)
    mon = inv.monitor(runner)
    runner.advance(s.chunk)
    assert mon.boundary() == []
    # corrupt: free-list count off by one + rewind a stats counter
    states, tel = runner.carries[0]
    states = states._replace(
        fl_count=states.fl_count + 1,
        s_stats=states.s_stats.at[:, :].set(0),
    )
    runner.carries[0] = (states, tel)
    got = {v.invariant for v in mon.boundary()}
    assert "conservation" in got
    assert "monotone" in got


def test_invariants_recovery_bound_fires_on_tight_budget():
    """A genuine recovery that exceeds an artificially tight bound is
    reported — the bound is a real parameter, not decoration."""
    c = ChaosCampaign(
        seed=2,
        invariants=ChaosInvariants(
            no_progress_window=10**9, recovery_bound_ticks=1,
            require_completion=False,
        ),
    )
    c.MSG_PKTS = 24
    s = dataclasses.replace(
        c.generate(0), resume_check=False, name="chaos/tightrec",
        faults=(ChaosFault("link_down", tor=0, spine=0, start=8, end=200),),
    )
    violations, _ = c.run_scenario(s)
    if any(v.invariant == "recovery" for v in violations):
        return  # drop happened and the 1-tick bound fired, as intended
    # the fault window may have dropped nothing for this seed; then the
    # invariant correctly stays silent — but the scenario must have run
    assert violations == []
