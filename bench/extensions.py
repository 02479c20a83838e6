"""The benchmark's extension points: a module of ``bench/`` found by name.

A cell's files name the code they need, and each name is a file:

* a traffic file's ``generator`` kind: ``bench/generators/<kind>.py``, with
  ``workload(cfg, traffic, seed) -> Workload``;
* a configuration's ``failures["kind"]``: ``bench/failure_regimes/<kind>.py``,
  with ``schedule(cfg, params, seed) -> FailureSchedule``;
* a configuration's optional ``reference``: ``bench/references/<name>.py``,
  the reference model of ``correct`` in ``plainsim``'s place (``Row``,
  ``view`` and, optionally, ``program_view``: see ``bench/reference.py``);
* a per-layer metric of ``BENCHMARK.json``: ``bench/metrics/<name>.py``, with
  ``read(ctx)``.

So a new deployment, traffic mix or metric is new files; it edits none that
are there.
"""
from __future__ import annotations

import importlib.util
import pathlib
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def find(root: pathlib.Path, folder: str, name: str):
    """The module ``<root>/bench/<folder>/<name>.py``; a name that is no
    such file raises, naming the path it looked for."""
    path = pathlib.Path(root) / "bench" / folder / f"{name}.py"
    if not (isinstance(name, str) and NAME.match(name)) or not path.is_file():
        raise ValueError(f"no {folder} module {name!r}: looked for {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{folder}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
