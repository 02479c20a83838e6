"""The chip benchmark of this repository: ``python3 bench/run.py``.

``BENCHMARK.json`` at the root lists its configurations, cells and metrics;
this package holds everything it measures with (see ``harness``).
"""
