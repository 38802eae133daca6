"""Benchmark entry: one run of one cell, one JSON line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Makes the cell's weights and traffic from `--seed`, serves the traffic
on one process's chip through `Engine.generate_continuous`, and prints
as its last line of standard output

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown",] "checks"}

with the cell's end-to-end metrics (`--trace 0`) or its per-layer
metrics (`--trace 1`, read from a profiler trace of the window). The
numbers compared for `correct` also go, each beside its limit, to the
last lines of standard error. Without a TPU, or with fewer chips than
the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    import harness
    try:
        line = harness.run(a.workload, a.seed, a.seconds, bool(a.trace),
                           t_start=T_START)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
