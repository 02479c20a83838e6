"""A random ``fraction`` of the ToR uplinks down from tick ``start`` on, for
good (a copy of ``netsim.failures.random_down_uplinks``).

    "failures": {"kind": "down_uplinks", "fraction": <share>, "start": <tick>}
"""
import numpy as np

from bench.generate import FOREVER
from repro.netsim.engine import FailureSchedule
from repro.netsim.topology import Topology


def schedule(cfg, params: dict, seed: int) -> FailureSchedule:
    fraction, start = params["fraction"], params["start"]
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
