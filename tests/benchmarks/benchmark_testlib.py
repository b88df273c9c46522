"""Shared by the benchmark's tests: put ``benchmarks/`` on the path the way
``run.py`` does, and load its modules. No JAX topology call, no engine
import at import time."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def rehearse_args(workload, seed=11, seconds=0.2, trace=0):
    import argparse

    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, rehearse=True)
