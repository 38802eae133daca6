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
        assert os.path.exists(os.path.join(
            harness.HERE, "refs", f"{c.config['reference']}.py"))
