"""The generated inputs of the benchmark's cells do not move.

``generate.build`` finds each generator kind and failure regime by name
(``bench/generators/``, ``bench/failure_regimes/``).  The arrays it gives the
fig07 and scale cells must be those that the generator gave before that
lookup, bit for bit: the workload, the failed links, the row seeds and the
sample the check compares, on three seeds.  The digests in
``testdata/inputs_sha256.json`` were recorded with the generator as it was
before the lookup:

    PYTHONPATH=src:. python <this file> --record    (from that checkout)
"""
import hashlib
import json
import pathlib
import sys

import numpy as np
import pytest

from bench import generate, harness
from repro.netsim.config import SimConfig

CELLS = ("fig07_perm.mixed_lb", "scale_1e5.conn4")
SEEDS = (0, 1, 3140000011)
DIGESTS = pathlib.Path(__file__).resolve().parent / "testdata" / \
    "inputs_sha256.json"


def _sha(a) -> str:
    a = np.ascontiguousarray(a)
    h = hashlib.sha256(f"{a.dtype.str}{a.shape}".encode())
    h.update(a.tobytes())
    return h.hexdigest()


def digests(cell: str, seed: int) -> dict:
    spec = harness.load_cell(cell)
    inputs = generate.build(spec.config, spec.traffic,
                            SimConfig(**spec.config["sim"]), seed)
    wl, fs = inputs.workload, inputs.failures
    out = {k: _sha(getattr(wl, k))
           for k in ("src", "dst", "msg_pkts", "start", "dep")}
    if fs is not None:
        out.update({f"failures.{k}": _sha(getattr(fs, k))
                    for k in ("queue", "start", "end", "kind")})
    out["row_seeds"] = _sha(np.asarray(inputs.row_seeds, np.int64))
    out["sample"] = _sha(np.asarray(inputs.sample, np.int64))
    return out


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", CELLS)
def test_inputs_are_the_recorded_ones(cell, seed):
    want = json.loads(DIGESTS.read_text())[cell][str(seed)]
    assert digests(cell, seed) == want


if __name__ == "__main__" and sys.argv[1:] == ["--record"]:
    DIGESTS.write_text(json.dumps(
        {c: {str(s): digests(c, s) for s in SEEDS} for c in CELLS},
        indent=1, sort_keys=True) + "\n")
