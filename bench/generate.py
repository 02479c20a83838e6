"""The one traffic generator: a cell's inputs from its data files and ``--seed``.

A traffic file (``bench/traffic/<name>.json``) names a ``generator`` kind and
its parameters; a configuration file (``bench/configs/<name>.json``) gives the
fabric and its failure regime.  Each kind and regime is a module of its own,
found by name (``bench/extensions.py``).  Everything random is drawn here
from the seed, in a fixed order: the generator's seed, the failure regime's,
the row seeds, then the sample of rows that the correctness check compares.
The simulator receives only the resulting ``Workload`` and
``FailureSchedule`` arrays.

The generators and regimes are copies of the program's own arithmetic, kept
under ``bench/`` so that no later change to the program can move the
yardstick.
"""
from __future__ import annotations

import dataclasses
import pathlib

import numpy as np

from bench import extensions
from repro.netsim.engine import FailureSchedule, Workload

ROOT = pathlib.Path(__file__).resolve().parents[1]
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


def build(config: dict, traffic: dict, cfg, seed: int,
          root: pathlib.Path = ROOT) -> Inputs:
    """The cell's inputs for ``seed`` (``cfg`` is the config's SimConfig;
    the kinds and regimes are looked up under ``root``)."""
    rng = _rng(seed, 0)
    wl_seed, fail_seed = (int(x) for x in rng.integers(0, 2**31 - 1, 2))
    row_seeds = tuple(
        int(x) for x in rng.integers(0, 2**31 - 1, traffic["seeds_per_lb"])
    )
    gen = extensions.find(root, "generators", traffic["generator"])
    wl = gen.workload(cfg, traffic, wl_seed)
    fail = config.get("failures")
    fs = None
    if fail:
        regime = extensions.find(root, "failure_regimes", fail["kind"])
        fs = regime.schedule(cfg, fail, fail_seed)
    pick = _rng(seed, 1)
    sample = tuple(
        int(pick.integers(traffic["seeds_per_lb"])) for _ in traffic["lbs"]
    )
    return Inputs(wl, fs, row_seeds, sample)
