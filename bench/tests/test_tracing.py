"""The reduction from a profiler trace to busy/idle time, kernel sums and
the breakdown, on a small recorded trace (`data/trace_small.json`, cut
from a chip run of the kivi2 cell) and on hand-made events; and the
loader on a trace recorded here."""
import gzip
import json
import os

import pytest
import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _tr(ops, modules=(), host=()):
    return {"devices": [{"name": "/device:TPU:0", "ops": list(ops),
                         "modules": list(modules)}],
            "host": list(host)}


def test_busy_is_the_union_clipped_to_the_window():
    tr = _tr([["a", 0, 10], ["b", 5, 10], ["c", 30, 10], ["d", 95, 20]])
    dev = tr["devices"][0]
    # [0,15] U [30,40] U [95,100] inside the window [0,100]
    assert tracing.busy_ns(dev, 0, 100) == 15 + 10 + 5
    assert tracing.merged(dev["ops"], 0, 100).tolist() == [
        [0, 15], [30, 40], [95, 100]]


def test_kernel_sums_match_by_name():
    ops = [["decode_attn_paged_pallas.3", 0, 7],
           ["decode_attn_paged_pallas.3", 20, 9],
           ["fusion.12", 40, 5], ["flash_prefill_chunk_pallas.1", 50, 11]]
    t, n = tracing.summed_ns(ops, r"decode_attn(_paged)?_pallas(\.\d+)?$",
                             0, 100)
    assert (t, n) == (16, 2)
    t, n = tracing.summed_ns(ops, r"flash_prefill(_chunk)?_pallas", 0, 100)
    assert (t, n) == (11, 1)


def test_breakdown_orders_ops_and_labels_gaps():
    tr = _tr([["x", 0, 10], ["y", 10, 30], ["x", 60, 10]],
             host=[["window", 0, 100], ["job0", 0, 100],
                   ["PjitFunction(_step)", 38, 30], ["sample", 70, 5]])
    b = tracing.breakdown(tr, 0, 100)
    assert b["device_ops"] == [["y", 30e-9], ["x", 20e-9]]
    # gaps [40,60] (20 ns) and [70,100] (30 ns), longest first
    assert b["idle_gaps"][0] == ["sample", 30e-9]
    assert b["idle_gaps"][1] == ["PjitFunction(_step)", 20e-9]


def test_recorded_chip_trace():
    path = os.path.join(DATA, "trace_small.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded trace")
    with gzip.open(path, "rt") as f:
        tr = json.load(f)
    t0, t1 = tracing.span(tr, "window")
    dev = tr["devices"][0]
    busy = tracing.busy_ns(dev, t0, t1)
    assert 0 < busy <= t1 - t0
    kt, kn = tracing.summed_ns(dev["ops"],
                               r"decode_attn(_paged)?_pallas(\.\d+)?$",
                               t0, t1)
    assert kn > 0 and 0 < kt < busy
    mt, mn = tracing.summed_ns(dev["modules"], r"jit__step\b", t0, t1)
    assert mn > 0 and kt < mt
    b = tracing.breakdown(tr, t0, t1)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10


def test_load_reads_a_recorded_xplane(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = tracing.load(str(tmp_path))
    t0, t1 = tracing.span(tr, "window")
    assert t1 > t0
    assert tr["devices"] == []          # no TPU plane on the CPU
