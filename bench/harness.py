"""One benchmark run: set-up, the measured window, the check, the line.

Everything that belongs to one configuration, traffic mix, cell or
metric is a file found by name:

    BENCHMARK.json               the cells, metrics and their bounds
    bench/configs/<config>.json  sizes, source, cuts, the reference's name
    bench/refs/<reference>.py    the plain reference of that architecture
    bench/archs/<reference>.py   the program side of it: its `ModelConfig`,
                                 weight tree and work counts
    bench/traffic/<mix>.json     parameters for `traffic.py`
    bench/cells/<workload>.json  engine settings, job size, check limits
    bench/metrics/<metric>.py    `read(ctx)` -> number, or None

The window runs back-to-back batch jobs: each job is `job_requests`
requests from the mix, submitted together to one
`Engine.generate_continuous` call; jobs run until `seconds` have passed,
and the window spans the first job's call to the last job's return.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

import numpy as np  # noqa: E402


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod     # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict          # the workload's entry in BENCHMARK.json
    config: dict         # bench/configs/<config>.json
    cell: dict           # bench/cells/<name>.json
    mix: dict            # bench/traffic/<traffic>.json
    bench: dict          # the whole BENCHMARK.json
    root: str = ROOT     # the checkout it was found in

    @functools.cached_property
    def arch(self):
        """The configuration's architecture module, loaded once."""
        return arch(self.config["reference"], self.root)


def arch(name: str, root: str = ROOT):
    """`bench/archs/<name>.py`: `program_config(conf)`,
    `weight_shapes(conf)` and `work(conf, engine)` of an architecture."""
    return _module(os.path.join(root, "bench", "archs", f"{name}.py"),
                   "arch_" + name)


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _json(root, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    bdir = os.path.join(root, "bench")
    return Cell(name=name, entry=entry,
                config=_json(root, conf["file"]),
                cell=_json(bdir, "cells", f"{name}.json"),
                mix=_json(bdir, "traffic", f"{entry['traffic']}.json"),
                bench=bench, root=root)


def metrics_for(c: Cell, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    e2e = [m for m in c.bench["end_to_end"]
           if c.name in m.get("workloads", [c.name])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in c.bench["per_layer"]
            if (c.name in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def policy(eng: dict):
    from repro.core.policy import presets
    return presets(budget=eng.get("budget", 0),
                   window=eng.get("window", 128))[eng["policy"]]


def engine(c: Cell, params):
    """The cell's `Engine` over `params`, Pallas kernels on."""
    from repro.serving import Engine
    e = dict(c.cell["engine"])
    kw = {k: e[k] for k in ("slots", "paged", "block_len", "block_growth",
                            "chunked_prefill", "chunk_len") if k in e}
    return Engine(c.arch.program_config(c.config), params, policy(e),
                  buckets=c.mix["prompt"]["buckets"],
                  max_new=c.mix["output"]["max"], use_kernels=True, **kw)


def counts_shape(c: Cell):
    """The work object `counts` sums for this cell."""
    return c.arch.work(c.config, c.cell["engine"])


# ---------------------------------------------------------------------------
# The run


@dataclasses.dataclass
class Ctx:
    """What a metric reader may read."""
    cell: Cell
    seed: int
    setup_s: float = 0.0
    window_s: float = 0.0
    jobs: list = dataclasses.field(default_factory=list)  # (reqs, result)
    trace: dict = None
    span: tuple = None         # traced window on the trace clock (ns)
    peaks: dict = None
    device: dict = None

    def served(self) -> list:
        """(request, its RequestResult) of every request in the window."""
        out = []
        for reqs, res in self.jobs:
            by_uid = {r.uid: r for r in res.results}
            out += [(q, by_uid[q.uid]) for q in reqs]
        return out

    def counts_reqs(self) -> list:
        import counts
        return [counts.Served(len(q.tokens), r.n_tokens)
                for q, r in self.served()]


def serve_job(eng, reqs):
    from repro.serving import Request
    rs = [Request(tokens=r.tokens, max_new=r.max_new) for r in reqs]
    return rs, eng.generate_continuous(rs)


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, root: str = ROOT,
        log=print, keep: dict = None) -> dict:
    """One run of cell `name`. Returns the result line as a dict (the
    caller prints it). Raises before any work where the device does not
    fit the cell; `require_tpu=False` lets a test drive the rest of a run
    on the CPU. `keep` receives the run's `Ctx` under "ctx"."""
    import jax
    import traffic
    import weights
    from repro.utils import init_compile_cache

    c = load_cell(name, root)
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu"
                        or len(devs) < c.entry["chips"]):
        raise SystemExit(f"{name} needs {c.entry['chips']} TPU chip(s); "
                         f"JAX found {len(devs)} {dev.platform} device(s)")
    init_compile_cache()
    ctx = Ctx(cell=c, seed=seed)
    if keep is not None:
        keep["ctx"] = ctx
    if require_tpu:
        import peaks
        ctx.peaks = peaks.peaks_for(dev.device_kind)

    params = weights.make(c, seed)
    eng = engine(c, params)
    vocab = c.config["vocab_size"]
    serve_job(eng, traffic.warmup_job(c.mix, vocab, seed))
    ctx.setup_s = time.perf_counter() - t_start
    log(f"setup_s={ctx.setup_s:.3f}", file=sys.stderr)

    n = c.cell["job_requests"]
    log_dir = None
    if trace:
        import tempfile

        import tracing
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(log_dir,
                                 profiler_options=tracing.options())
    compiles = _count_compiles()
    job_s = []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        while True:
            reqs = traffic.job(c.mix, n, vocab, seed, len(ctx.jobs))
            t_j = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"job{len(ctx.jobs)}"):
                rs, res = serve_job(eng, reqs)
            job_s.append(time.perf_counter() - t_j)
            ctx.jobs.append((rs, res))
            if time.perf_counter() - t0 >= seconds:
                break
    ctx.window_s = time.perf_counter() - t0
    n_compiles = compiles()
    if trace:
        jax.profiler.stop_trace()
    log(f"window_s={ctx.window_s:.3f} jobs={len(ctx.jobs)} "
        f"job_s={[round(t, 2) for t in job_s]} "
        f"compiles_in_window={n_compiles}", file=sys.stderr)

    stats = dev.memory_stats() or {}
    ctx.device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devs),
                  "memory_peak_bytes": int(stats.get("peak_bytes_in_use",
                                                     0))}
    if trace:
        import shutil
        ctx.trace = tracing.load(log_dir)
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx.span = tracing.span(ctx.trace, "window")
        t_a, t_b = ctx.span
        busy = [tracing.busy_ns(d, t_a, t_b) for d in ctx.trace["devices"]]
        ctx.device["busy_s"] = float(np.mean(busy)) / 1e9 if busy else 0.0
        ctx.device["window_s"] = (t_b - t_a) / 1e9

    served = ctx.served()
    line = {"correct": False, "attempted": len(served),
            "failed": n_failed(served)}

    metrics = {}
    for m in metrics_for(c, trace):
        v = _module(os.path.join(HERE, "metrics", f"{m['name']}.py"),
                    "metric_" + m["name"].replace(".", "_")).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = ctx.device
    if trace:
        line["breakdown"] = tracing.breakdown(ctx.trace, *ctx.span)

    # the check runs once the window is measured and the program's state
    # is gone: only the weights (made here, not by the program) stay
    del eng
    t_c = time.perf_counter()
    readings, checks, line["correct"] = judge(c, params, served, seed)
    log(f"check_s={time.perf_counter() - t_c:.3f} readings={readings}",
        file=sys.stderr)
    line["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}={v['value']!r} limit={v['limit']!r}",
            file=sys.stderr)
    return line


def n_failed(served: list) -> int:
    """Requests that failed or were served fewer tokens than asked."""
    return sum(1 for q, r in served
               if r.finish_reason == "failed" or r.n_tokens != q.max_new)


def judge(c: Cell, params, served: list, seed: int,
          precision: str = "f32") -> tuple:
    """(readings, checks, correct) of the served requests: the numbers
    `check.run` compares (the reference at `precision`; "fp8" is the
    control) and the failed requests, each beside its limit, and the
    verdict over them. `bench/control.py` judges the control with this
    same call."""
    import check
    readings, checks = check.run(c, params, served, seed, precision)
    checks["failed_requests"] = {"value": n_failed(served), "limit": 0}
    return readings, checks, all(v["value"] <= v["limit"]
                                 for v in checks.values())


def _count_compiles():
    """Start counting XLA compilations; the returned call gives the
    count since."""
    import jax
    n = [0]

    def on(event, *a, **k):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    start = n[0]
    return lambda: n[0] - start
