"""The chip benchmark: one data-driven harness over configurations, traffic
mixes, cells and per-layer metric readers, each a file of its own.

Run from the root of a checkout::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
