"""Cells, mixes, configurations and metrics are files found by name: a
new one needs no edit of an existing file of the harness."""
import json
import os

import harness


def test_new_mix_and_metric_are_found_by_name(tiny_root, tmp_path):
    import shutil
    root = str(tmp_path / "co")
    shutil.copytree(tiny_root, root)
    b = os.path.join(root, "bench")
    # a new mix: a data file only
    with open(os.path.join(b, "traffic", "tinyburst.json"), "w") as f:
        json.dump({"prompt": {"buckets": [128], "median": 100, "sigma": 0.1},
                   "output": {"median": 4, "sigma": 0.1, "min": 3,
                              "max": 5}}, f)
    # a new cell using it, and a new per-layer metric with its reader
    shutil.copy(os.path.join(b, "cells", "tiny.full.tinymix.json"),
                os.path.join(b, "cells", "tiny.full.tinyburst.json"))
    with open(os.path.join(b, "metrics", "jobs_run.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx.jobs)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "tiny.full.tinyburst",
                               "config": "tiny", "traffic": "tinyburst",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "jobs_run", "unit": "jobs",
                               "better": "higher", "source":
                               "program_counter", "layer": "scheduler",
                               "moves": "output_tok_s",
                               "workloads": ["tiny.full.tinyburst"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    c = harness.load_cell("tiny.full.tinyburst", root)
    assert c.mix["prompt"]["buckets"] == [128]
    # its own metric, and every per-layer metric that lists no cells
    # (those read in every cell), without an edit of their entries
    assert {m["name"] for m in harness.metrics_for(c, True)} == {
        "jobs_run", "device_idle_pct", "slot_occupancy_pct", "mfu_pct"}
    assert {m["name"] for m in harness.metrics_for(c, False)} == {
        "setup_s", "output_tok_s", "itl_p95_ms"}
    mod = harness._module(os.path.join(b, "metrics", "jobs_run.py"), "m")
    assert mod.read(harness.Ctx(cell=c, seed=0, jobs=[1, 2])) == 2


def test_every_metric_has_its_reader():
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           f"{m['name']}.py")), m["name"]
    for w in bench["workloads"]:
        c = harness.load_cell(w["name"])
        assert c.config["name"] == w["config"]
        for kind in ("refs", "archs"):
            assert os.path.exists(os.path.join(
                harness.HERE, kind, f"{c.config['reference']}.py"))


PROBE_ARCH = '''"""Test architecture: the dense decoder, each call recorded, and a
layer stack that a token meets with EXTRA more parameters."""
import os

import harness

EXTRA = 1000
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
dense = harness.arch("dense_decoder", ROOT)
calls = []


def program_config(conf):
    calls.append("program_config")
    return dense.program_config(conf)


def weight_shapes(conf):
    calls.append("weight_shapes")
    return dense.weight_shapes(conf)


class Work:
    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def block_params(self):
        return self.inner.block_params() + EXTRA


def work(conf, engine):
    calls.append("work")
    return Work(dense.work(conf, engine))
'''

PROBE_REF = '''"""Test reference: the dense decoder's."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "probe_ref_dense",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "dense_decoder.py"))
_dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_dense)
served_logits = _dense.served_logits
'''


def _files(root):
    out = {}
    for d, _, names in os.walk(root):
        for n in names:
            p = os.path.join(d, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = f.read()
    return out


def test_new_architecture_is_found_by_name(tiny_root, tmp_path):
    """An architecture comes with new files and new entries only: its
    program side, its reference, its configuration and a cell, with no
    edit of a file or an entry that is there."""
    import shutil
    import time

    import counts
    root = str(tmp_path / "co")
    shutil.copytree(tiny_root, root)
    b = os.path.join(root, "bench")
    before = _files(b)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    old = json.loads(json.dumps(bench))

    with open(os.path.join(b, "archs", "probe.py"), "w") as f:
        f.write(PROBE_ARCH)
    with open(os.path.join(b, "refs", "probe.py"), "w") as f:
        f.write(PROBE_REF)
    with open(os.path.join(b, "configs", "tiny.json")) as f:
        conf = json.load(f)
    with open(os.path.join(b, "configs", "probe.json"), "w") as f:
        json.dump({**conf, "name": "probe", "reference": "probe"}, f)
    shutil.copy(os.path.join(b, "cells", "tiny.full.tinymix.json"),
                os.path.join(b, "cells", "probe.full.tinymix.json"))
    bench["configs"].append({"name": "probe", "source": "test",
                             "file": "bench/configs/probe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "probe.full.tinymix",
                               "config": "probe", "traffic": "tinymix",
                               "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    after = _files(b)
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == sorted(
        os.path.join(*p) for p in (
            ("archs", "probe.py"), ("refs", "probe.py"),
            ("configs", "probe.json"),
            ("cells", "probe.full.tinymix.json")))
    for key, was in old.items():     # entries appended, none edited
        got = bench[key]
        assert (got[:len(was)] if isinstance(was, list) else got) == was

    keep = {}
    line = harness.run("probe.full.tinymix", 2**31 + 29, 0.5, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       root=root, log=lambda *a, **k: None, keep=keep)
    assert line["correct"] is True, line
    ctx = keep["ctx"]
    probe = ctx.cell.arch
    assert probe.__file__ == os.path.join(b, "archs", "probe.py")
    assert probe.calls == ["weight_shapes", "program_config"]

    w = harness.counts_shape(ctx.cell)
    assert probe.calls[-1] == "work" and isinstance(w, probe.Work)
    reqs = ctx.counts_reqs()
    tok = sum(r.prompt + r.served - 1 for r in reqs)
    dense = probe.dense.work(conf, ctx.cell.cell["engine"])
    flops = counts.model_flops(reqs, w)
    assert flops == counts.model_flops(reqs, dense) + 2 * probe.EXTRA * tok

    # the MFU reader counts through the probe's work object
    ctx.peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    n = len(probe.calls)
    mfu = harness._module(os.path.join(b, "metrics", "mfu_pct.py"),
                          "metric_mfu_pct").read(ctx)
    assert probe.calls[n:] == ["work"]
    assert mfu == 100.0 * flops / (ctx.window_s * 1e12
                                   * ctx.device["count"])
