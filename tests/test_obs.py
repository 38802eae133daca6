"""Observability (repro.obs): zero-sync tracing + metrics registry.
Tier-2 (own CI job); the pinned contracts:

  * Tracer ring bounds: overflow drops the *oldest* events, counts the
    drops, and export stays valid (a long run keeps its tail);
  * Chrome ``trace_event`` schema: ``ph``/``ts``/``pid``/``tid`` parse,
    ``X`` events carry ``dur``, instants are thread-scoped, and ``M``
    metadata names every lane that carried an event;
  * telemetry is invisible to decoding: trace-on greedy streams are
    bit-identical to trace-off across full/kivi2 x dense/paged, plain
    AND speculative loops — the `Span` seam always times, only the
    emit is conditional, so reported seconds match too;
  * a forced-preemption + tiering run's exported trace contains the
    preempt -> spill -> restore chain in causal timestamp order;
  * Metrics: get-or-create typing, histogram bucketing, and the one
    serialized schema `serve.py --metrics-json` writes.
"""
import json
import time

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core.policy import presets
from repro.nn import model as M
from repro.obs import (NULL_TRACER, Metrics, NullMetrics, NullTracer,
                       Phases, Tracer, write_metrics_json)
from repro.serving import Engine, Request

# ---------------------------------------------------------------------------
# Tracer units: ring bounds, span seam, Chrome export schema
# ---------------------------------------------------------------------------


def test_ring_drops_oldest_and_counts():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4 and tr.dropped == 6
    assert [e[1] for e in tr.events()] == ["e6", "e7", "e8", "e9"]
    assert tr.to_chrome()["otherData"]["dropped_events"] == 6


def test_tracer_validation():
    with pytest.raises(ValueError):
        Tracer(0)


def test_span_times_even_on_null_tracer():
    """The single timing seam: a NullTracer span measures identically
    and only skips the emit — trace-off reported seconds must not
    change when tracing turns on."""
    nt = NullTracer()
    with nt.span("phase") as sp:
        time.sleep(0.002)
    assert sp.elapsed >= 0.002
    assert not nt and len(nt) == 0 and nt.events() == []


def test_span_emits_complete_event():
    tr = Tracer()
    with tr.span("prefill", tid=3, args=dict(uid=7)) as sp:
        pass
    (ph, name, tid, ts, dur, args), = tr.events()
    assert ph == "X" and name == "prefill" and tid == 3
    assert args == dict(uid=7)
    assert ts == sp.t0 and abs(dur - sp.elapsed) < 1e-9


def test_chrome_export_schema(tmp_path):
    tr = Tracer(pid=7, process_name="obs-test")
    tr.instant("tick", tid=2, args=dict(a=1))
    tr.complete("phase", tr.now())
    tr.counter("pool", dict(free=3, active=1))
    path = tr.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    data = [e for e in evs if e["ph"] != "M"]
    named = {(m["name"], m["tid"]): m["args"] for m in meta}
    assert named[("process_name", 0)]["name"] == "obs-test"
    assert named[("thread_name", 0)]["name"] == "engine"
    assert named[("thread_name", 2)]["name"] == "slot 1"
    assert named[("thread_sort_index", 2)]["sort_index"] == 2
    assert [e["ph"] for e in data] == ["i", "X", "C"]
    for e in data:
        assert e["pid"] == 7 and isinstance(e["tid"], int)
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
    inst, comp, ctr = data
    assert inst["s"] == "t" and inst["args"] == dict(a=1)
    assert comp["dur"] >= 0
    assert ctr["args"] == dict(free=3, active=1)


def test_phases_time_always_and_emit_only_when_tracing():
    """`Phases` sums every phase's seconds with tracing off as on; a
    live tracer also gets one complete event per phase, contiguous."""
    for trace in (NULL_TRACER, Tracer()):
        ph = Phases(trace)
        assert ph.to("admit") is None
        time.sleep(0.002)
        assert ph.to("fetch") == "admit"
        time.sleep(0.002)
        prev = ph.to("fetch")           # a wait nested in a phase
        assert prev == "fetch" and ph.to(prev) == "fetch"
        ph.to(None)
        assert ph.totals["admit"] >= 0.002 and ph.totals["fetch"] >= 0.002
        evs = trace.events()
        if trace:
            assert [e[1] for e in evs] == ["admit", "fetch", "fetch",
                                           "fetch"]
            for a, b in zip(evs, evs[1:]):
                assert a[3] + a[4] == pytest.approx(b[3], abs=1e-9)
        else:
            assert evs == []


def test_anchor_reads_the_clock_inside_the_annotation():
    """`anchor` reads perf_counter inside the caller's context manager,
    which is what ties the tracer's clock to the annotation's."""
    import contextlib
    seen = []

    @contextlib.contextmanager
    def annotation(name):
        seen.append((name, time.perf_counter()))
        yield
        seen.append((name, time.perf_counter()))

    tr = Tracer()
    t = tr.anchor(annotation)
    assert [n for n, _ in seen] == ["obs_anchor", "obs_anchor"]
    assert seen[0][1] <= t <= seen[1][1] and tr.anchors == {"obs_anchor": t}


# ---------------------------------------------------------------------------
# Metrics units
# ---------------------------------------------------------------------------


def test_metrics_registry_and_snapshot():
    mx = Metrics()
    mx.counter("a").inc()
    mx.counter("a").inc(2)              # get-or-create: same instrument
    mx.gauge("b").set(0.5)
    h = mx.histogram("h", bounds=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    snap = mx.snapshot()
    assert list(snap) == sorted(snap)
    assert snap["a"] == 3 and snap["b"] == 0.5
    hs = snap["h"]
    assert hs["count"] == 3 and hs["min"] == 0.05 and hs["max"] == 5.0
    assert hs["buckets"] == [[0.1, 1], [1.0, 1], ["inf", 1]]
    with pytest.raises(TypeError):      # no silent type shadowing
        mx.gauge("a")


def test_histogram_bounds_must_ascend():
    with pytest.raises(ValueError):
        Metrics().histogram("h", bounds=(1.0, 0.5))


def test_write_metrics_json(tmp_path):
    mx = Metrics()
    mx.counter("x").inc(4)
    p = tmp_path / "m.json"
    payload = write_metrics_json(mx, str(p), extra={"run": "t"})
    doc = json.loads(p.read_text())
    assert doc == payload
    assert doc["schema"] == "repro.obs.metrics/1"
    assert doc["metrics"]["x"] == 4 and doc["run"] == "t"


def test_null_objects_are_falsy_noops():
    assert not NullTracer() and not NullMetrics() and not NULL_TRACER
    nm = NullMetrics()
    nm.counter("c").inc()
    nm.gauge("g").set(1.0)
    nm.histogram("h").observe(2.0)
    assert nm.snapshot() == {} and len(nm) == 0


# ---------------------------------------------------------------------------
# End to end: telemetry is invisible to decoding
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("paper-llama-7b"), num_layers=2)
    params = M.init_params(jax.random.key(0), cfg)
    return cfg, params


def _requests(cfg, n, seed=0, size=32, max_new=10):
    rng = np.random.default_rng(seed)
    return [Request(tokens=rng.integers(0, cfg.vocab_size,
                                        size=size).astype(np.int32),
                    max_new=max_new) for _ in range(n)]


def _tokens(res):
    return [r.tokens.tolist() for r in sorted(res.results,
                                              key=lambda r: r.uid)]


@pytest.mark.parametrize("pname,paged", [
    ("full", False), ("full", True), ("kivi2", False), ("kivi2", True),
])
def test_trace_on_streams_bit_identical(small_model, pname, paged):
    cfg, params = small_model
    pol = presets(budget=32, window=8)[pname]
    kw = dict(prompt_len=32, max_new=10, slots=2, buckets=(32,), seed=0)
    if paged:
        kw.update(paged=True, block_len=8)
    reqs = lambda: _requests(cfg, 3, seed=1)
    off = Engine(cfg, params, pol, **kw).generate_continuous(reqs())
    tr, mx = Tracer(), Metrics()
    on = Engine(cfg, params, pol, tracer=tr, metrics=mx,
                **kw).generate_continuous(reqs())
    assert _tokens(on) == _tokens(off)
    names = {e[1] for e in tr.events()}
    assert {"submit", "admit", "first_token", "prefill", "step",
            "request", "setup", "finish", "slots"} <= names
    assert mx.counter("engine.loop_iters").value == on.loop_iters > 0
    assert mx.histogram("request.ttft_s").count == 3
    snap = mx.snapshot()
    assert snap["requests.completed"] == 3 and snap["requests.failed"] == 0
    # 3 requests on 2 slots: one waits while both slots decode, and a
    # slot frees before the last request does
    slot_steps = on.decode_steps * 2
    assert snap["slots.drain_steps"] > 0
    assert (snap["slots.prefill_steps"] + snap["slots.starved_steps"]
            + snap["slots.drain_steps"]) == pytest.approx(
                slot_steps * (1 - on.occupancy))
    assert on.drain_share == snap["slots.drain_steps"] / slot_steps
    if paged:
        assert "pool" in names          # per-iteration counter track


def test_trace_on_chunked_streams_bit_identical(small_model):
    """Chunked admission with the tracer on: the same streams, every
    iteration's `step` span holds its leaf phases, and the loop's host
    seconds from the spans (steps less their fetches) are the result's
    own (`loop_seconds - fetch_seconds`)."""
    cfg, params = small_model
    pol = presets(budget=32, window=8)["full"]
    kw = dict(prompt_len=32, max_new=10, slots=2, buckets=(32,), seed=0,
              chunked_prefill=True, chunk_len=16)
    reqs = lambda: _requests(cfg, 3, seed=1)
    off = Engine(cfg, params, pol, **kw).generate_continuous(reqs())
    tr = Tracer()
    on = Engine(cfg, params, pol, tracer=tr, **kw).generate_continuous(
        reqs())
    assert _tokens(on) == _tokens(off)
    assert on.decode_steps == off.decode_steps
    assert (on.prefill_share, on.starved_share, on.drain_share) == (
        off.prefill_share, off.starved_share, off.drain_share)
    evs = [e for e in tr.events() if e[0] == "X" and e[2] == 0]
    steps = [e for e in evs if e[1] == "step"]
    assert len(steps) == on.loop_iters
    fetch = 0.0
    for e in evs:
        if e[1] in ("admit", "dispatch", "fetch", "record"):
            assert any(s[3] <= e[3] and e[3] + e[4] <= s[3] + s[4]
                       for s in steps), e
            fetch += e[4] if e[1] == "fetch" else 0.0
    assert fetch == pytest.approx(on.fetch_seconds, rel=1e-6)
    assert sum(s[4] for s in steps) == pytest.approx(on.loop_seconds,
                                                     rel=0.05)
    assert {e[1] for e in evs} >= {"setup", "finish", "admit", "dispatch",
                                   "fetch", "record"}
    chunk_uids = {e[5]["uid"] for e in tr.events()
                  if e[1] == "prefill_chunk"}
    assert chunk_uids == {r.uid for r in on.results}


@pytest.mark.parametrize("pname,paged", [("full", False), ("kivi2", True)])
def test_trace_on_speculative_streams_bit_identical(small_model, pname,
                                                    paged):
    cfg, params = small_model
    pol = presets(budget=32, window=8)[pname]
    kw = dict(prompt_len=32, max_new=10, slots=2, buckets=(32,), seed=0,
              block_len=8, speculative=True, gamma=3, draft_policy="same")
    if paged:
        kw.update(paged=True)
    reqs = lambda: _requests(cfg, 3, seed=1)
    off = Engine(cfg, params, pol, **kw).generate_continuous(reqs())
    tr, mx = Tracer(), Metrics()
    on = Engine(cfg, params, pol, tracer=tr, metrics=mx,
                **kw).generate_continuous(reqs())
    assert _tokens(on) == _tokens(off)
    names = {e[1] for e in tr.events()}
    assert {"submit", "round", "draft_prefill", "request"} <= names
    assert mx.counter("spec.rounds").value > 0
    assert mx.gauge("spec.accept_rate").value > 0.0


def test_preemption_tiering_trace_causal_order(small_model, tmp_path):
    """The post-mortem the tracer exists for: a forced-preemption +
    tiering run exports a Chrome trace whose preempt -> spill ->
    restore chain appears in causal timestamp order, alongside the
    request lifecycle spans."""
    cfg, params = small_model
    pol = presets(budget=32, window=8)["kivi2"]
    kw = dict(prompt_len=32, max_new=10, slots=2, buckets=(32,), seed=0,
              paged=True, block_len=8)
    tr = Tracer()
    eng = Engine(cfg, params, pol, preempt_at=((3, 0), (5, 1)),
                 tiering=True, tracer=tr, **kw)
    res = eng.generate_continuous(_requests(cfg, 3, seed=1))
    assert res.tier["n_spills"] >= 1 and res.tier["n_fetches"] >= 1
    with open(tr.export(str(tmp_path / "trace.json"))) as f:
        doc = json.load(f)
    evs = [e for e in doc["traceEvents"] if e["ph"] != "M"]

    def first_ts(name, ph):
        hits = [e["ts"] for e in evs if e["name"] == name and e["ph"] == ph]
        assert hits, f"no {name!r}/{ph} events in the exported trace"
        return min(hits)

    t_spill = first_ts("spill", "i")
    t_preempt = first_ts("preempt", "i")
    t_restore = first_ts("restore", "X")
    t_fetch = first_ts("fetch", "i")
    # preempt-to-host snapshots the victim's blocks *before* the
    # scheduler releases its ids, and the ticketed continuation fetches
    # them back inside its restore span
    assert t_spill <= t_preempt <= t_restore <= t_fetch
    assert first_ts("request", "X") >= 0
