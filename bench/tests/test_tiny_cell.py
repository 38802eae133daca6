"""One tiny-width cell end to end on the CPU (kernels in interpret mode),
and the measurement path refusing to run off the TPU."""
import json
import os
import subprocess
import sys
import time

import harness
import pytest

SEED = 2**31 + 11


@pytest.mark.parametrize("cell", ["tiny.kivi2.tinymix", "tiny.full.tinymix"])
def test_tiny_cell_end_to_end(tiny_root, cell):
    keep = {}
    line = harness.run(cell, SEED, 0.5, False, t_start=time.perf_counter(),
                       require_tpu=False, root=tiny_root,
                       log=lambda *a, **k: None, keep=keep)
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert set(line["metrics"]) == {"setup_s", "output_tok_s", "itl_p95_ms"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "checks"
    assert line["checks"] and all(v["value"] <= v["limit"]
                                  for v in line["checks"].values())
    # every served request came from the cell's mix
    for q, r in keep["ctx"].served():
        assert len(q.tokens) in (128, 256) and r.n_tokens == q.max_new


def test_refused_off_the_tpu(tiny_root):
    with pytest.raises(SystemExit):
        harness.run("tiny.full.tinymix", SEED, 0.5, False,
                    t_start=time.perf_counter(), root=tiny_root)


def test_run_py_exits_nonzero_without_tpu_and_prints_nothing():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "granite-8b-12l.full.longdoc", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode == 2 and p.stdout.strip() == ""


def test_run_py_without_the_program_prints_nothing(tmp_path):
    import shutil
    shutil.copytree(harness.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "granite-8b-12l.full.longdoc", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    json.loads(open(tmp_path / "BENCHMARK.json").read())
