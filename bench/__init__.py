"""The on-chip benchmark of the LIDC repository: ``python3 bench/run.py``.

Everything that belongs to one model configuration, one traffic mix or
one per-layer metric is a file of its own under this directory, found by
the name that ``BENCHMARK.json`` gives it.
"""
