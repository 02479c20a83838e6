"""RTO fires only at ``send_tick + rto_ticks``.

The tick's RTO stage looks only at the packets sent exactly ``rto_ticks``
ago (at most one per host) and checks their connections afterwards.  That
equals the full-table rule "live, not orphaned, ``now - send >= rto_ticks``,
connection not done" because a packet older than ``rto_ticks`` was checked
at that tick and kept only for a done connection, which stays done.  These
tests hold the engine to that:

* (a) at every tick no live, unorphaned packet of an unfinished connection
  is older than ``rto_ticks``, at most NH are exactly that old, and the
  timeouts counted are those candidates less the done-connection skips;
* (b) a LOST_WAIT packet whose connection is done never fires and stays as
  it was;
* (c) the final state and stats are bit-identical to the recorded ones
  (``data/rto_exact_tick_digests.json``, from the full-table rule).

To record the digests from the code on ``PYTHONPATH``::

    PYTHONPATH=src python tests/test_rto_exact_tick.py --record
"""
import functools
import hashlib
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_lb
from repro.netsim import SimConfig, Simulator, failures, workloads
from repro.netsim.engine import (
    FLYING, LOST_WAIT, PCONN, PORPH, PS, PSEND, PSEQ, QUEUED, ST_TIMEOUTS,
)

DIGESTS = pathlib.Path(__file__).parent / "data" / "rto_exact_tick_digests.json"

NH = 16
BASE = SimConfig(
    n_hosts=NH, hosts_per_tor=4, uplinks_per_tor=4, evs_size=256,
    queue_capacity=16, init_cwnd_pkts=16, max_cwnd_pkts=32, rto_ticks=60,
    max_msg_pkts=64,
)
TICKS = 700
DOWN = failures.random_down_uplinks(BASE, 0.25, start=40, end=2**30, seed=1)
PERM = workloads.permutation(NH, msg_pkts=48, seed=3)
# name -> (config, LB, failures, workload): ECMP collisions alone, trimming,
# a quarter of the ToR uplinks down, an incast whose queues outlast the RTO
# (retransmitted copies still queued when their connection finishes), and
# scale mode's sparse active set
CASES = {
    "dense": (BASE, "ecmp", None, PERM),
    "dense_trim": (BASE.replace(trimming=True), "ecmp", DOWN, PERM),
    "dense_down": (BASE, "ops", DOWN, PERM),
    "incast": (BASE.replace(queue_capacity=48, rto_ticks=40), "reps", None,
               workloads.incast(NH, 12, 16)),
    "scale": (BASE.replace(conn_sharding=True), "reps", DOWN, PERM),
}


def build(case: str) -> Simulator:
    cfg, lb, fs, wl = CASES[case]
    return Simulator(cfg, wl, make_lb(lb, evs_size=cfg.evs_size),
                     failures=fs, seed=5)


def run_checked(sim: Simulator, state, t0: int, n: int):
    """Advance ``state`` over ticks ``[t0, t0 + n)``; per tick, read from the
    state the RTO stage sees: live unorphaned packets of unfinished
    connections older than ``rto_ticks``, the candidates (exactly that old),
    those of done connections, and the timeouts the tick counted."""
    NC = sim.wl.n_conns
    rto = sim.cfg.rto_ticks

    def body(st, t):
        p = st.pkt
        live = (p[PS] == FLYING) | (p[PS] == QUEUED) | (p[PS] == LOST_WAIT)
        base = live & (p[PORPH] != 1)
        done = st.c_done[jnp.clip(p[PCONN], 0, NC - 1)]
        age = t - p[PSEND]
        cand = base & (age == rto)
        new, _ = sim._step(st, t, sim.base_key)
        seen = jnp.stack([
            jnp.sum(base & ~done & (age > rto)),
            jnp.sum(cand),
            jnp.sum(cand & done),
            new.s_stats[ST_TIMEOUTS] - st.s_stats[ST_TIMEOUTS],
        ])
        return new, seen

    ticks = jnp.arange(t0, t0 + n, dtype=jnp.int32)
    st, seen = jax.jit(lambda s: jax.lax.scan(body, s, ticks))(state)
    return st, np.asarray(seen)


@functools.lru_cache(maxsize=None)
def checked_run(case: str):
    sim = build(case)
    st, seen = run_checked(sim, sim.init_state(), 0, TICKS)
    return sim, st, seen


def digest(state) -> dict:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(state):
        a = np.asarray(leaf)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return {"state_sha256": h.hexdigest(),
            "s_stats": np.asarray(state.s_stats).tolist()}


@pytest.mark.parametrize("case", list(CASES))
def test_rto_candidates_are_exactly_rto_ticks_old(case):
    sim, _, seen = checked_run(case)
    over, cand, skip, fired = seen.T
    assert not over.any(), np.flatnonzero(over)[:10]
    assert cand.max() <= sim.NH
    np.testing.assert_array_equal(fired, cand - skip)
    assert fired.sum() > 0  # the stage fires in every case


def test_done_connection_skips_happen():
    """A retransmitted copy still queued when its connection finishes is a
    candidate at its RTO tick and is skipped by the done-check."""
    assert checked_run("incast")[2][:, 2].sum() > 0


@pytest.mark.parametrize("case", ["dense", "scale"])
def test_lost_wait_packet_of_done_connection_never_fires(case):
    sim, st, _ = checked_run(case)
    done = np.flatnonzero(np.asarray(st.c_done))
    assert done.size
    conn = int(done[0])
    # allocate a slot as injection does and leave a lost packet of the done
    # connection in it, sent 3 ticks before the run resumes
    NP = sim.NP
    slot = int(st.fl[int(st.fl_head)])
    row = np.zeros(st.pkt.shape[0], np.int32)
    row[PS], row[PCONN], row[PSEQ], row[PSEND] = LOST_WAIT, conn, 0, TICKS - 3
    st = st._replace(
        pkt=st.pkt.at[:, slot].set(jnp.asarray(row)),
        fl_head=(st.fl_head + 1) % NP,
        fl_count=st.fl_count - 1,
    )
    if sim.A:
        st = st._replace(
            as_idx=jnp.sort(jnp.concatenate([st.as_idx, jnp.asarray([slot])]))[: sim.A],
            as_count=st.as_count + 1,
        )
    n = sim.cfg.rto_ticks + 20
    end, seen = run_checked(sim, st, TICKS, n)
    np.testing.assert_array_equal(np.asarray(end.pkt[:, slot]), row)
    # its RTO tick came and went: it was a candidate, skipped, never fired
    assert seen[sim.cfg.rto_ticks - 3, 2] >= 1
    np.testing.assert_array_equal(seen[:, 3], seen[:, 1] - seen[:, 2])
    assert int(end.c_inflight[conn]) == int(st.c_inflight[conn])
    assert int(end.c_rtx_count[conn]) == int(st.c_rtx_count[conn])


@pytest.mark.parametrize("case", list(CASES))
def test_bit_identical_to_full_table_rule(case):
    _, st, _ = checked_run(case)
    want = json.loads(DIGESTS.read_text())[case]
    assert digest(st) == want


if __name__ == "__main__" and sys.argv[1:] == ["--record"]:
    DIGESTS.parent.mkdir(exist_ok=True)
    DIGESTS.write_text(json.dumps(
        {c: digest(checked_run(c)[1]) for c in CASES}, indent=1) + "\n")
    print(DIGESTS.read_text())
