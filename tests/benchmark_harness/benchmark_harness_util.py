"""Helpers of the benchmark harness tests (tiny CPU runs)."""

from __future__ import annotations

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# a few hundred frames, 32-signature chunks, a 2,048-state store; the
# native codec is whatever the suite already built
TINY = {
    "config": {"committed_states": 2048, "pool_per_s": 1500},
    "shape": {"issue_fanout": 64},
    "notary": {"verifier_batch_sizes": [32], "max_batch": 256},
    "traffic": {"warmup_frames": 64, "rate_per_s": 150},
    "workers": 1,
    "build_native": False,
}
SEED = 2**31 + 22


def run_cell(root, cell, *, seconds=2.0, trace=0, overrides=None,
             fault=None, seed=SEED):
    """harness.run on the CPU at the tiny size; returns its result."""
    from benchmark import harness

    args = harness.parse_args([
        "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
        "--trace", str(trace),
    ])
    merged = dict(TINY)
    for k, v in (overrides or {}).items():
        merged[k] = dict(TINY[k], **v) if isinstance(v, dict) else v
    return harness.run(
        args, time.monotonic(), root=root, allow_cpu=True,
        overrides=merged, fault=fault,
        verbose=lambda msg: None,
    )
