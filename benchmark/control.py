#!/usr/bin/env python3
"""The control: one cell run with a fault planted in its timed path.

    python3 benchmark/control.py --fault accept_all_signatures \
        --workload <cell> --seed <n> --seconds <s>

Prints the run's result line like run.py; `correct` must come out
false. The driver's runs never call this (benchmark/faults.py).
"""

import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    sys.path.insert(0, ROOT)
    from benchmark import faults, harness

    argv = sys.argv[1:]
    i = argv.index("--fault")
    name = argv.pop(i + 1)
    argv.pop(i)
    return harness.main(argv, t_start=T_START, fault=faults.FAULTS[name])


if __name__ == "__main__":
    raise SystemExit(main())
