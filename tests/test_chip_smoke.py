"""`chip_smoke.py`, the one-chip proof that the serving path runs, off the
chip: it refuses to report without a TPU, and its phases and checks run
end to end at a tiny width with the kernels in interpret mode (every
check but the compiled program's `tpu_custom_call`)."""
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_config, reduced
from repro.nn import model as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_smoke(script: str, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-checkout", "alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, alone):
    """Without a TPU, and alone in a directory without the repo, the
    script exits non-zero and never prints the ok line."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    p = _run_smoke(str(script), tmp_path)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_chip_smoke_phases_pass_in_interpret_mode():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke as cs
    finally:
        sys.path.remove(ROOT)
    cfg = reduced(get_config("minicpm-2b"), num_heads=4, num_kv_heads=4,
                  dtype=jnp.bfloat16)
    params = M.init_params(jax.random.key(0), cfg)
    sz = cs.Sizes(prompt_len=64, max_new=16, shared_prefix=32, window=8,
                  kivi_budget=64, h2o_budget=32)
    recs = cs.run_phases(cfg, params, sz, kind="cpu", custom_call=False)
    by = {r["phase"]: r for r in recs}
    assert list(by) == ["full", "kivi2", "h2o", "preempt", "spec"]
    assert by["preempt"]["identical"] and by["preempt"]["preemptions"]
    assert by["kivi2"]["warm_hits"] and by["kivi2"]["cow_copies"]
    for r in recs:
        assert r["max_logit_diff"] <= cs.LOGIT_RTOL * max(r["logit_scale"],
                                                          1.0)
