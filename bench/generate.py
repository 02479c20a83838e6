"""The one traffic generator: a cell's inputs from its data files and ``--seed``.

A traffic file (``bench/traffic/<name>.json``) names a ``generator`` and its
parameters; a configuration file (``bench/configs/<name>.json``) gives the
fabric and its failure regime.  Everything random is drawn here from the
seed: the permutation, the failed links, the row seeds and the sample of
rows that the correctness check compares.  The simulator receives only the
resulting ``Workload`` and ``FailureSchedule`` arrays.

The arithmetic is a copy of the program's own generators
(``netsim.workloads.permutation``, ``netsim.failures.random_down_uplinks``
and ``benchmarks/scale_smoke.scale_workload``), kept here so that no later
change to the program can move the yardstick.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.netsim.engine import FailureSchedule, Workload
from repro.netsim.topology import Topology

FOREVER = 2**30  # a failure window that never closes (netsim.failures)


@dataclasses.dataclass(frozen=True)
class Inputs:
    workload: Workload
    failures: FailureSchedule | None
    row_seeds: tuple[int, ...]  # one PRNG seed per row of each LB cell
    sample: tuple[int, ...]  # per LB cell, the seed index the check compares


def _rng(seed: int, stream: int) -> np.random.Generator:
    """Any whole number (negative or past 2**63) seeds a distinct stream."""
    s = int(seed)
    words = [(s >> (32 * i)) & 0xFFFFFFFF for i in range(3)]
    return np.random.default_rng([stream, int(s < 0), *words])


def permutation(n_hosts: int, msg_pkts: int, seed: int) -> Workload:
    """Random derangement: each host sends to and receives from exactly one."""
    rng = np.random.RandomState(seed)
    while True:
        perm = rng.permutation(n_hosts)
        if not np.any(perm == np.arange(n_hosts)):
            break
    n = n_hosts
    return Workload(
        src=np.arange(n, dtype=np.int32),
        dst=perm.astype(np.int32),
        msg_pkts=np.full((n,), msg_pkts, np.int32),
        start=np.zeros((n,), np.int32),
        dep=np.full((n,), -1, np.int32),
        name="permutation",
    )


def staggered(n_hosts: int, n_conns: int, msg_pkts: int, stagger: int,
              seed: int) -> Workload:
    """``n_conns`` messages spread round-robin over hosts, each host starting
    one every ``stagger`` ticks (the scale smoke row).  The seed relabels
    the hosts: every seed has the same sizes and start ticks."""
    i = np.arange(n_conns, dtype=np.int64)
    src = i % n_hosts
    r = i // n_hosts  # per-host conn rank
    dst = (src + 1 + r % (n_hosts - 1)) % n_hosts
    relabel = np.random.RandomState(seed).permutation(n_hosts)
    return Workload(
        src=relabel[src].astype(np.int32),
        dst=relabel[dst].astype(np.int32),
        msg_pkts=np.full((n_conns,), msg_pkts, np.int32),
        start=(r * stagger).astype(np.int32),
        dep=np.full((n_conns,), -1, np.int32),
        name=f"staggered{n_conns}",
    )


def down_uplinks(cfg, fraction: float, start: int, seed: int) -> FailureSchedule:
    """A random ``fraction`` of the ToR uplinks down from ``start`` on."""
    topo = Topology.build(cfg)
    ups = np.concatenate([topo.t0_up_queues(t) for t in range(cfg.n_tors)])
    k = max(1, int(round(fraction * len(ups))))
    q = np.random.RandomState(seed).choice(ups, k, replace=False)
    q = q.astype(np.int32)
    return FailureSchedule(
        queue=q,
        start=np.full(q.shape, start, np.int32),
        end=np.full(q.shape, FOREVER, np.int32),
        kind=np.zeros(q.shape, np.int32),
    )


def build(config: dict, traffic: dict, cfg, seed: int) -> Inputs:
    """The cell's inputs for ``seed`` (``cfg`` is the config's SimConfig)."""
    rng = _rng(seed, 0)
    wl_seed, fail_seed = (int(x) for x in rng.integers(0, 2**31 - 1, 2))
    row_seeds = tuple(
        int(x) for x in rng.integers(0, 2**31 - 1, traffic["seeds_per_lb"])
    )
    kind = traffic["generator"]
    if kind == "permutation":
        wl = permutation(cfg.n_hosts, traffic["msg_pkts"], wl_seed)
    elif kind == "staggered":
        wl = staggered(cfg.n_hosts, traffic["n_conns"], traffic["msg_pkts"],
                       traffic["stagger_ticks"], wl_seed)
    else:
        raise ValueError(f"unknown traffic generator {kind!r}")
    fail = config.get("failures")
    fs = None
    if fail:
        if fail["kind"] != "down_uplinks":
            raise ValueError(f"unknown failure regime {fail['kind']!r}")
        fs = down_uplinks(cfg, fail["fraction"], fail["start"], fail_seed)
    pick = _rng(seed, 1)
    sample = tuple(
        int(pick.integers(traffic["seeds_per_lb"])) for _ in traffic["lbs"]
    )
    return Inputs(wl, fs, row_seeds, sample)
