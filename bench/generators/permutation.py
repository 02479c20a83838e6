"""A random derangement: each host sends one message of ``msg_pkts`` packets
to exactly one other host and receives exactly one, all from tick 0 (a copy
of ``netsim.workloads.permutation``).

    {"generator": "permutation", "msg_pkts": <packets per message>, ...}
"""
import numpy as np

from repro.netsim.engine import Workload


def workload(cfg, traffic: dict, seed: int) -> Workload:
    n_hosts, msg_pkts = cfg.n_hosts, traffic["msg_pkts"]
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
