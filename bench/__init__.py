"""On-chip benchmark of DSBP serving: cells, traffic, metrics, reference.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``; everything a cell names (its
configuration, traffic mix, limits and per-layer metric readers) is found
by name under this directory.
"""
