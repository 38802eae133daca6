"""Readings that set a cell's limits: the program's sound runs and the
control, one process, several seeds.

    python3 bench/control.py --workload <name> --seeds 11,12,13 [--out f]

For each seed: weights from the seed, one job of the cell's traffic
served through the cell's engine (the timed path, at the cell's own
load), the same sample `check.run` draws, and two readings over it:

  program  `check.numbers` of the gaps of the served tokens below the
           reference's best, and the verdict `harness.judge` gives
  control  the same numbers and verdict for the tokens that the
           reference computed in float8 (one precision below the
           configuration's bfloat16) ranks first, at the same positions

The program's readings set the lower end of each compared number's
limit and the control's the upper end; the control's verdict is the one
a run's `correct` would read with the control in the program's place
(bench/tests/test_control.py holds the same comparison at a test size).
Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def readings(c, eng, seed: int, make_params) -> dict:
    """One seed's readings and verdicts: the served job judged by
    `harness.judge` as a run judges it, and the control judged by the
    same call with the reference one precision down."""
    import harness
    import traffic
    eng.params = make_params(seed)
    reqs = traffic.job(c.mix, c.cell["job_requests"],
                       c.config["vocab_size"], seed, 0)
    rs, res = harness.serve_job(eng, reqs)
    by_uid = {r.uid: r for r in res.results}
    served = [(q, by_uid[q.uid]) for q in rs]
    out = {"seed": seed}
    for who, prec in (("program", "f32"), ("control", "fp8")):
        got, checks, ok = harness.judge(c, eng.params, served, seed, prec)
        out[f"{who}.correct"] = ok
        out[f"{who}.tokens"] = got["tokens"]
        for k in ("max_logit_gap", "mean_logit_gap"):
            out[f"{who}.{k}"] = got[k]
        out[f"{who}.checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    import harness
    import jax
    import traffic
    import weights
    from repro.utils import init_compile_cache
    init_compile_cache()
    if jax.devices()[0].platform != "tpu":
        print("control readings are taken on the TPU", file=sys.stderr)
        return 2
    c = harness.load_cell(a.workload)
    seeds = [int(s) for s in a.seeds.split(",")]
    eng = harness.engine(c, weights.make(c.config, seeds[0]))
    harness.serve_job(eng, traffic.warmup_job(c.mix, c.config["vocab_size"],
                                              seeds[0]))
    out = []
    for s in seeds:
        t = time.perf_counter()
        r = readings(c, eng, s, lambda k: weights.make(c.config, k))
        r["seconds"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
        out.append(r)
    if a.out:
        with open(a.out, "w") as f:
            json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
