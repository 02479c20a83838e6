"""The arithmetic 3-tier router on the k = 8 fat tree walks valid paths.

``Topology.next_queue`` is the only code that maps one EV to two hash
choices (ToR -> agg, then agg -> core).  On the canonical k = 8 tree (8 pods
of 4 ToRs and 4 aggs, 4 hosts a ToR, 16 cores: 128 hosts, 1:1 at every
tier), for every ordered pair of distinct hosts and 64 EVs, each packet's
walk must be an up/down path of 1, 3 or 5 queues (same ToR, same pod,
across pods) that ends at the destination's host downlink, where each queue
leaves the switch the previous one entered.  Both choice hops must reach
every port.  The walks themselves must equal the digest in
``data/route_3tier_digests.json``, recorded on the router before its dead
first assignment of ``core`` was deleted:

    PYTHONPATH=src python tests/test_route_three_tier.py --record
"""
import hashlib
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.netsim.config import SimConfig
from repro.netsim.topology import Topology

CFG = SimConfig(n_hosts=128, hosts_per_tor=4, tiers=3, tors_per_pod=4,
                aggs_per_pod=4, agg_uplinks=4)
N_EVS = 64
HOPS = 5
DIGESTS = pathlib.Path(__file__).resolve().parent / "data" / \
    "route_3tier_digests.json"


def lanes():
    """(flow, ev, src, dst) for every ordered pair of distinct hosts and
    ``N_EVS`` EVs; a pair's flow id is its index."""
    nh = CFG.n_hosts
    src, dst = np.divmod(np.arange(nh * nh), nh)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    flow = np.arange(len(src))
    ev = (np.arange(N_EVS) * 1031) % CFG.evs_size
    rep = lambda x: np.repeat(x, N_EVS).astype(np.int32)  # noqa: E731
    return (rep(flow), np.tile(ev, len(src)).astype(np.int32), rep(src),
            rep(dst))


def walks(topo):
    """(HOPS, lanes) queue ids of each lane's walk, -1 after it arrived."""
    flow, ev, src, dst = (jnp.asarray(x) for x in lanes())
    q_len = jnp.zeros((topo.n_queues,), jnp.int32)

    @jax.jit
    def walk():
        cur = jnp.zeros_like(src)
        at = jnp.ones(src.shape, bool)
        live = jnp.ones(src.shape, bool)
        out = []
        for _ in range(HOPS):
            nxt = topo.next_queue(at, cur, flow, ev, src, dst, q_len,
                                  adaptive=False)
            out.append(jnp.where(live, nxt, -1))
            live = live & ~topo.is_final_hop(nxt)
            cur, at = nxt, jnp.zeros_like(at)
        return jnp.stack(out)

    return np.asarray(walk())


def digest(hops) -> str:
    a = np.ascontiguousarray(hops, np.int32)
    h = hashlib.sha256(f"{a.dtype.str}{a.shape}".encode())
    h.update(a.tobytes())
    return h.hexdigest()


def links(topo):
    """Each queue's (from switch, to switch), switches named as tuples,
    from the fabric's definition (Al-Fares et al., Sec. 3): ToR t holds
    queue (t, a) up to agg a of its pod; agg (p, a) holds queue u up to
    core a*U2 + u, and one down to each ToR of its pod; core c holds one
    down to agg c // U2 of each pod; ToR t one down to each of its hosts."""
    c = CFG
    A, U2, Tp, H = c.aggs_per_pod, c.agg_uplinks, c.tors_per_pod, \
        c.hosts_per_tor
    P, T = c.n_pods, c.n_tors
    out = []
    for t in range(T):
        out += [(("tor", t), ("agg", t // Tp, a)) for a in range(A)]
    for p in range(P):
        for a in range(A):
            out += [(("agg", p, a), ("core", a * U2 + u)) for u in range(U2)]
    for core in range(A * U2):
        out += [(("core", core), ("agg", p, core // U2)) for p in range(P)]
    for p in range(P):
        for a in range(A):
            out += [(("agg", p, a), ("tor", p * Tp + tl)) for tl in range(Tp)]
    for t in range(T):
        out += [(("tor", t), ("host", t * H + h)) for h in range(H)]
    assert len(out) == topo.n_queues
    return out


def test_every_walk_is_an_up_down_path_to_the_destination():
    topo = Topology.build(CFG)
    hops = walks(topo)
    _, _, src, dst = lanes()
    link = links(topo)
    H, Tp = CFG.hosts_per_tor, CFG.tors_per_pod
    n = np.sum(hops >= 0, axis=0)
    same_tor = src // H == dst // H
    same_pod = src // (H * Tp) == dst // (H * Tp)
    assert np.array_equal(n, np.where(same_tor, 1, np.where(same_pod, 3, 5)))
    last = hops[n - 1, np.arange(hops.shape[1])]
    assert np.array_equal(last, topo.t0_down_base + dst)
    # each queue leaves the switch the one before it entered, up then down
    kinds = {1: ("tor", "host"), 3: ("tor", "agg", "tor", "host"),
             5: ("tor", "agg", "core", "agg", "tor", "host")}
    for length, want in kinds.items():
        sel = np.flatnonzero(n == length)
        paths = {tuple(hops[:length, i]) + (int(src[i]) // H,) for i in sel}
        for *qs, tor in paths:
            at = [("tor", tor)]
            for q in qs:
                frm, to = link[q]
                assert frm == at[-1], (qs, tor)
                at.append(to)
            assert tuple(sw[0] for sw in at) == want, (qs, tor)
    # both choice hops reach every port
    cross = n == 5
    assert set(hops[0, cross] % CFG.aggs_per_pod) == set(
        range(CFG.aggs_per_pod))
    agg_up = hops[1, cross] - topo.agg_up_base
    assert set(agg_up % CFG.agg_uplinks) == set(range(CFG.agg_uplinks))


def test_walks_equal_the_recorded_ones():
    want = json.loads(DIGESTS.read_text())["k8"]
    assert digest(walks(Topology.build(CFG))) == want


if __name__ == "__main__" and sys.argv[1:] == ["--record"]:
    DIGESTS.write_text(json.dumps(
        {"k8": digest(walks(Topology.build(CFG)))}, indent=1) + "\n")
