"""Run one benchmark cell on the chip and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program (``src/repro``). The
cell, its configuration, its traffic mix and the per-layer metric readers are
found by name under ``bench/``; see ``bench/loader.py``. JAX's persistent
compilation cache is kept at ``<checkout>/.jax_cache``, a fixed path, so that
only a cell's first run in a checkout compiles.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT                     # import bench as a package, not its files
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"

if __name__ == "__main__":
    from bench import harness

    sys.exit(harness.main(t_start=T_START))
