"""``n_conns`` messages spread round-robin over hosts, each host starting one
every ``stagger_ticks`` ticks (a copy of the scale smoke row,
``benchmarks/scale_smoke.scale_workload``).  The seed relabels the hosts:
every seed has the same sizes and start ticks.

    {"generator": "staggered", "n_conns": <n>, "stagger_ticks": <ticks>,
     "msg_pkts": <packets per message>, ...}
"""
import numpy as np

from repro.netsim.engine import Workload


def workload(cfg, traffic: dict, seed: int) -> Workload:
    n_hosts, n_conns = cfg.n_hosts, traffic["n_conns"]
    msg_pkts, stagger = traffic["msg_pkts"], traffic["stagger_ticks"]
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
