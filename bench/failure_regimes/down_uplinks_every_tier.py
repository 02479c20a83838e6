"""A random ``fraction`` of the uplinks at each choice hop of a 3-tier fat
tree down from tick ``start`` on, for good: first of the ToR-to-agg uplinks,
then of the agg-to-core uplinks, from one draw of the seed.

    "failures": {"kind": "down_uplinks_every_tier", "fraction": <share>,
                 "start": <tick>}

A 2-tier tree has one choice hop: its regime is ``down_uplinks``.
"""
import numpy as np

from bench.generate import FOREVER
from repro.netsim.engine import FailureSchedule
from repro.netsim.topology import Topology


def schedule(cfg, params: dict, seed: int) -> FailureSchedule:
    if cfg.tiers != 3 or cfg.fabric:
        raise ValueError("down_uplinks_every_tier takes the 3-tier fat tree")
    fraction, start = params["fraction"], params["start"]
    topo = Topology.build(cfg)
    rng = np.random.RandomState(seed)
    tor_ups = np.concatenate([topo.t0_up_queues(t) for t in range(cfg.n_tors)])
    agg_ups = np.arange(topo.agg_up_base, topo.core_down_base)
    q = np.concatenate([
        rng.choice(ups, max(1, int(round(fraction * len(ups)))), replace=False)
        for ups in (tor_ups, agg_ups)
    ]).astype(np.int32)
    return FailureSchedule(
        queue=q,
        start=np.full(q.shape, start, np.int32),
        end=np.full(q.shape, FOREVER, np.int32),
        kind=np.zeros(q.shape, np.int32),
    )
