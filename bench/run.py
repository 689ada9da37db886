"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload set2_knn.saturate --seed 7 --seconds 10 --trace 0

The cell, its configuration and its traffic mix are named in
``BENCHMARK.json``.  Exits non-zero, printing no result, unless JAX's first
device is a TPU and there are as many chips as the cell asks for.  The last
line of standard output is one JSON object; the numbers that decided
``correct`` are the last lines of standard error.
"""

import pathlib
import sys

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    from bench.harness import main

    raise SystemExit(main())
