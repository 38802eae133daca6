"""The engine's own spans on the profiler's clock, and a probe that reads
them on the chip.

`repro.obs.Tracer` stamps its events with `time.perf_counter`; the
profiler's host events carry a clock of their own. `Tracer.anchor`,
given `jax.profiler.TraceAnnotation`, reads perf_counter inside an
annotation named `obs_anchor`: that annotation's start in the loaded
trace less the reading is the one offset that `mapped` adds to every
tracer event. `leaves` keeps the engine lane's spans that hold no other
(`admit`, `dispatch`, `fetch`, `record`, `setup`, `finish`) as host
events named `obs/<name>`, the form in which `tracing.breakdown` labels
an idle gap by the host event that overlaps it most.

    python3 bench/engine_trace.py --workload <name> --seed <n> --seconds <s>

serves the cell's traffic in one process on the chip. After the cell's
warm-up it runs one profiled window of `--seconds` with the engine
tracer on (the first window after the warm-up, as in the harness's
traced run), then windows with the profiler off and the tracer off, on,
on, off, off, on (the tracer's cost in `output_tok_s` and
`itl_p95_ms`). Of the profiled window it reads every per-layer metric
of the cell, the breakdown with the engine's leaf spans among the host
events (`breakdown`) and with them alone (`engine_breakdown`), each
leaf phase's summed seconds, the host time per loop iteration from the
spans (`loop_host_ms_spans`, beside the reader's), and a clock check:
a tracer span and a profiler annotation around the same 20 ms sleep,
before and after the jobs, with the distance of their starts and ends
in microseconds. It prints one JSON line.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

ANCHOR = "obs_anchor"
CHECKS = ("obs_check_before", "obs_check_after")
# adjacent phases share one perf_counter reading as a boundary; mapped to
# nanoseconds they may overlap by a rounding error, far under this
SLACK_NS = 100.0


def mapped(tr: dict, tracer, name: str = ANCHOR) -> list:
    """Every complete event of `tracer` as [name, tid, start_ns, dur_ns,
    args] on the clock of the loaded trace `tr`."""
    import tracing
    off = tracing.span(tr, name)[0] - tracer.anchors[name] * 1e9
    return [[n, tid, ts * 1e9 + off, dur * 1e9, args]
            for ph, n, tid, ts, dur, args in tracer.events() if ph == "X"]


def leaves(events: list, t0: float, t1: float) -> list:
    """The engine lane's (tid 0) spans that hold no other engine-lane
    span and overlap [t0, t1], as host events [`obs/<name>`, start_ns,
    dur_ns]. Spans on the lane nest, so sorted by start (longest first)
    a span holds another exactly when the next one starts inside it (by
    more than `SLACK_NS`)."""
    eng = sorted((e for e in events if e[1] == 0),
                 key=lambda e: (e[2], -e[3]))
    out = []
    for i, (n, _, s, d, _) in enumerate(eng):
        if i + 1 < len(eng) and eng[i + 1][2] < s + d - SLACK_NS:
            continue
        if s < t1 and s + d > t0:
            out.append([f"obs/{n}", s, d])
    return out


def loop_host_ms(events: list, t0: float, t1: float):
    """Mean over the `step` spans inside [t0, t1] of the step's duration
    less its `fetch` children's: the loop's host self time per
    iteration, from the spans."""
    steps = sorted((e for e in events if e[0] == "step" and e[1] == 0
                    and t0 <= e[2] and e[2] + e[3] <= t1),
                   key=lambda e: e[2])
    fetch = sorted((e for e in events if e[0] == "fetch" and e[1] == 0),
                   key=lambda e: e[2])
    if not steps:
        return None
    host, j = 0.0, 0
    for _, _, s, d, _ in steps:
        while j < len(fetch) and fetch[j][2] < s:
            j += 1
        host += d
        while (j < len(fetch)
               and fetch[j][2] + fetch[j][3] <= s + d + SLACK_NS):
            host -= fetch[j][3]
            j += 1
    return host / len(steps) / 1e6


def _read(name: str, ctx):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def _window(eng, c, ctx, seed: int, seconds: float) -> None:
    """Back-to-back jobs as the harness runs them, into `ctx`."""
    import harness
    import traffic
    n, vocab = c.cell["job_requests"], c.config["vocab_size"]
    t0 = time.perf_counter()
    while True:
        reqs = traffic.job(c.mix, n, vocab, seed, len(ctx.jobs))
        ctx.jobs.append(harness.serve_job(eng, reqs))
        if time.perf_counter() - t0 >= seconds:
            break
    ctx.window_s = time.perf_counter() - t0


def probe(name: str, seed: int, seconds: float, *, root: str = ROOT,
          require_tpu: bool = True) -> dict:
    """The measurements the module docstring lists, as a dict.
    `require_tpu=False` lets a test drive it on the CPU, where the
    trace holds no device and the device readings are left out."""
    import shutil
    import tempfile

    import jax
    import harness
    import tracing
    import traffic
    import weights
    from repro.obs import NULL_TRACER, Tracer
    from repro.utils import init_compile_cache

    c = harness.load_cell(name, root)
    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        raise SystemExit(f"{name} needs a TPU; JAX found {dev.platform}")
    init_compile_cache()
    params = weights.make(c, seed)
    eng = harness.engine(c, params)
    harness.serve_job(eng, traffic.warmup_job(c.mix, c.config["vocab_size"],
                                              seed))
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "tracer_off": [], "tracer_on": []}
    tracer = Tracer()
    eng.trace = tracer
    ctx = harness.Ctx(cell=c, seed=seed)
    if require_tpu:
        import peaks
        ctx.peaks = peaks.peaks_for(dev.device_kind)

    def check(label):
        with jax.profiler.TraceAnnotation(label):
            with tracer.span(label):
                time.sleep(0.02)

    log_dir = tempfile.mkdtemp(prefix="engine-trace-")
    jax.profiler.start_trace(log_dir, profiler_options=tracing.options())
    tracer.anchor(jax.profiler.TraceAnnotation, ANCHOR)
    check(CHECKS[0])
    with jax.profiler.TraceAnnotation("window"):
        _window(eng, c, ctx, seed, seconds)
    check(CHECKS[1])
    jax.profiler.stop_trace()
    ctx.trace = tracing.load(log_dir)
    shutil.rmtree(log_dir, ignore_errors=True)
    ctx.span = t0, t1 = tracing.span(ctx.trace, "window")
    events = mapped(ctx.trace, tracer)

    out["dropped_events"] = tracer.dropped
    out["clock_check_us"] = {}
    for label in CHECKS:
        a0, a1 = tracing.span(ctx.trace, label)
        _, _, s, d, _ = next(e for e in events if e[0] == label)
        out["clock_check_us"][label] = {"start": (s - a0) / 1e3,
                                        "end": (s + d - a1) / 1e3}
    obs = leaves(events, t0, t1)
    phase_s: dict = {}
    for n, s, d in obs:
        phase_s[n] = phase_s.get(n, 0.0) + d / 1e9
    out["phase_s"] = phase_s
    out["loop_host_ms_spans"] = loop_host_ms(events, t0, t1)
    if ctx.trace["devices"]:
        busy = tracing.busy_ns(ctx.trace["devices"][0], t0, t1)
        ctx.device = {"count": 1, "busy_s": busy / 1e9,
                      "window_s": (t1 - t0) / 1e9}
        out["metrics"] = {m["name"]: _read(m["name"], ctx)
                          for m in harness.metrics_for(c, True)}
        out["breakdown"] = tracing.breakdown(
            {**ctx.trace, "host": ctx.trace["host"] + obs}, t0, t1)
        out["engine_breakdown"] = tracing.breakdown(
            {**ctx.trace, "host": obs}, t0, t1)
    else:
        out["metrics"] = {m: _read(m, ctx) for m in
                          ("slot_starved_pct", "slot_prefill_pct",
                           "loop_host_ms", "slot_occupancy_pct")}

    for on in (False, True, True, False, False, True):
        eng.trace = Tracer() if on else NULL_TRACER
        ctx = harness.Ctx(cell=c, seed=seed)
        _window(eng, c, ctx, seed, seconds)
        out["tracer_on" if on else "tracer_off"].append(
            {m: _read(m, ctx) for m in ("output_tok_s", "itl_p95_ms")})

    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    try:
        out = probe(a.workload, a.seed, a.seconds)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    sys.exit(main())
