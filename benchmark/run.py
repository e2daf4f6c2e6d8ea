#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

See benchmark/harness.py. Exits non-zero, printing no result, when jax
finds no TPU or fewer chips than the cell asks for, or when the frame
pool runs dry before the window closes.
"""

import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    sys.path.insert(0, ROOT)
    try:
        import corda_tpu  # noqa: F401
        from benchmark import harness
    except ImportError as e:
        print(f"benchmark: the system under test is not here ({e})",
              file=sys.stderr)
        return 2
    return harness.main(t_start=T_START)


if __name__ == "__main__":
    raise SystemExit(main())
