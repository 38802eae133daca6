"""The engine's spans on the profiler's clock (`engine_trace.mapped`), the
readers of the engine's own counters and spans, and the chip probe end to
end on the CPU."""
import time

import engine_trace
import harness
import pytest
import tracing

SEED = 2**31 + 23
CELL = "tiny.full.tinymix"


def _read(name, ctx):
    return engine_trace._read(name, ctx)


def test_tracer_span_maps_onto_a_profiler_annotation(tmp_path):
    """A tracer span and a profiler annotation around the same 20 ms
    sleep agree, once mapped through the anchor, within 1 ms."""
    import jax
    from repro.obs import Tracer
    tr_obs = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    tr_obs.anchor(jax.profiler.TraceAnnotation)
    time.sleep(0.01)
    with jax.profiler.TraceAnnotation("sleep"):
        with tr_obs.span("sleep"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    tr = tracing.load(str(tmp_path))
    a0, a1 = tracing.span(tr, "sleep")
    (_, _, s, d, _), = [e for e in engine_trace.mapped(tr, tr_obs)
                        if e[0] == "sleep"]
    assert abs(s - a0) < 1e6 and abs(s + d - a1) < 1e6
    assert a1 - a0 >= 20e6


@pytest.fixture(scope="module")
def traced_job(tiny_root):
    """One job of the tiny chunked cell served with a live tracer."""
    import traffic
    import weights
    from repro.obs import Tracer
    c = harness.load_cell(CELL, tiny_root)
    eng = harness.engine(c, weights.make(c.config, SEED))
    eng.trace = tracer = Tracer()
    reqs = traffic.job(c.mix, c.cell["job_requests"],
                       c.config["vocab_size"], SEED, 0)
    ctx = harness.Ctx(cell=c, seed=SEED)
    ctx.jobs.append(harness.serve_job(eng, reqs))
    return ctx, tracer


def test_step_spans_hold_the_leaf_phases(traced_job):
    ctx, tracer = traced_job
    ev = [[n, tid, ts, dur, a] for ph, n, tid, ts, dur, a
          in tracer.events() if ph == "X"]
    steps = [e for e in ev if e[0] == "step"]
    phases = [e for e in ev if e[0] in ("admit", "dispatch", "fetch",
                                        "record")]
    (res,) = [r for _, r in ctx.jobs]
    assert len(steps) == res.loop_iters > 2
    inside = {i: set() for i in range(len(steps))}
    for n, _, s, d, _ in phases:
        (i,) = [i for i, st in enumerate(steps)
                if st[2] <= s and s + d <= st[2] + st[3]]
        inside[i].add(n)
    # the first iteration has nothing to fetch yet, the last breaks out
    # before dispatching; every other one runs all four phases
    assert all({"admit", "dispatch"} <= inside[i] for i in inside)
    for i in range(1, len(steps) - 1):
        assert inside[i] == {"admit", "dispatch", "fetch", "record"}, i
    # the engine lane's leaves are every phase plus the job's set-up and
    # finish; the slot lanes' admission spans carry the request's uid
    # (in nanoseconds, moved as the mapping onto a trace's clock moves
    # them: adjacent phases share a boundary that rounding may blur)
    ns = [[n, tid, s * 1e9 + 8.7e12, d * 1e9, a] for n, tid, s, d, a in ev]
    leaf: dict = {}
    for n, _, _ in engine_trace.leaves(ns, 0, float("inf")):
        leaf[n] = leaf.get(n, 0) + 1
    assert set(leaf) == {"obs/admit", "obs/dispatch", "obs/fetch",
                         "obs/record", "obs/setup", "obs/finish"}
    assert leaf["obs/dispatch"] == len(steps)
    assert sum(leaf.values()) == len(phases) + 2
    uids = {r.uid for r in res.results}
    chunks = [e for e in ev if e[0] == "prefill_chunk"]
    assert chunks and {e[4]["uid"] for e in chunks} == uids


def test_readers_of_the_engine_counters(traced_job):
    ctx, tracer = traced_job
    (res,) = [r for _, r in ctx.jobs]
    starved = _read("slot_starved_pct", ctx)
    prefill = _read("slot_prefill_pct", ctx)
    occ = _read("slot_occupancy_pct", ctx)
    # 3 requests on 2 slots: the second and third stream in while a
    # slot decodes, and the job's tail drains (with one admission in
    # flight and a single free slot at a time, none starves here)
    assert prefill > 0 and res.drain_share > 0 and starved == 0
    assert occ + prefill + starved + 100 * res.drain_share == (
        pytest.approx(100.0))
    host = _read("loop_host_ms", ctx)
    ev = [[n, tid, ts * 1e9, dur * 1e9, a] for ph, n, tid, ts, dur, a
          in tracer.events() if ph == "X"]
    spans = engine_trace.loop_host_ms(ev, 0, float("inf"))
    assert 0 < host and spans == pytest.approx(host, rel=0.05)


def test_chunk_step_reader_on_a_synthetic_trace():
    ctx = harness.Ctx(cell=None, seed=0)
    ctx.span = (0, 1000)
    ctx.trace = {"devices": [{"name": "/device:TPU:0", "ops": [],
                              "modules": [["jit__chunk_step(3)", 10, 200],
                                          ["jit__step(1)", 300, 50],
                                          ["jit__chunk_step(3)", 400, 400],
                                          ["jit__finalize(4)", 900, 9]]}],
                 "host": []}
    assert _read("chunk_step_ms", ctx) == pytest.approx(300e-6)
    assert _read("decode_step_ms", ctx) == pytest.approx(50e-6)
    ctx.trace["devices"][0]["modules"] = [["jit__lambda(2)", 10, 200]]
    assert _read("chunk_step_ms", ctx) is None


def test_readers_return_none_without_the_counters():
    """A program that does not count slot states or time its loop (the
    parent of these readers) reads None, and nothing raises."""
    class Old:
        decode_steps = 10
        occupancy = 0.5

    ctx = harness.Ctx(cell=None, seed=0)
    ctx.jobs = [([], Old())]
    for m in ("slot_starved_pct", "slot_prefill_pct", "loop_host_ms"):
        assert _read(m, ctx) is None
    assert _read("slot_occupancy_pct", ctx) == pytest.approx(50.0)


def test_probe_end_to_end_on_the_cpu(tiny_root):
    out = engine_trace.probe(CELL, SEED, 0.1, root=tiny_root,
                             require_tpu=False)
    assert len(out["tracer_on"]) == len(out["tracer_off"]) == 3
    assert all(r["output_tok_s"] > 0 for r in out["tracer_on"])
    assert out["dropped_events"] == 0
    for check in out["clock_check_us"].values():
        assert abs(check["start"]) < 1000 and abs(check["end"]) < 1000
    assert {"obs/admit", "obs/fetch", "obs/setup"} <= set(out["phase_s"])
    m = out["metrics"]
    assert all(m[k] is not None for k in m), m
    assert out["loop_host_ms_spans"] == pytest.approx(m["loop_host_ms"],
                                                      rel=0.05)
