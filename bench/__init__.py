"""On-chip benchmark of the paper's pipeline served as a solver service.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything a configuration, a traffic
mix or a metric needs lives in a file of its own under this directory and
is found by the name ``BENCHMARK.json`` gives it (see ``harness.py``).
"""
