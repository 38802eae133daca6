"""How `correct` separates, at a test size: sound runs pass the limit,
the control (the reference one precision down, float8 for bfloat16)
fails it, and a run whose timed path is broken underneath reads
`correct` false — a token altered where the decode step produces it, and
a decode step that returns its cache unchanged."""
import time

import control
import harness
import pytest
import traffic
import weights

SEEDS = (1, 2, 3)


def _cell(root, name):
    return harness.load_cell(name, root)


@pytest.mark.parametrize("name", ["tiny.full.tinymix", "tiny.kivi2.tinymix"])
def test_control_fails_the_limit_sound_runs_pass(tiny_root, name):
    c = _cell(tiny_root, name)
    eng = harness.engine(c, weights.make(c, SEEDS[0]))
    got = [control.readings(c, eng, s, lambda k: weights.make(c, k))
           for s in SEEDS]
    for number, limit in c.cell["check"]["limits"].items():
        assert all(r[f"program.{number}"] <= limit for r in got), got
        assert all(r[f"control.{number}"] > limit for r in got), got
    # the verdict a run would give, by the run's own comparison
    assert all(r["program.correct"] is True for r in got), got
    assert all(r["control.correct"] is False for r in got), got


def _broken_step(kind):
    from repro.nn import model as M
    real = M.decode_step

    def step(params, cfg, cache, token, spec, **kw):
        logits, new = real(params, cfg, cache, token, spec, **kw)
        if kind == "state_unchanged":
            return logits, cache
        import jax.numpy as jnp
        wrong = (jnp.argmax(logits, -1) + 1) % logits.shape[-1]
        return logits.at[jnp.arange(logits.shape[0]), wrong].add(1e4), new
    return step


@pytest.mark.parametrize("name", ["tiny.full.tinymix", "tiny.kivi2.tinymix"])
@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_broken_timed_path_reads_incorrect(tiny_root, monkeypatch, fault,
                                           name):
    from repro.nn import model as M
    monkeypatch.setattr(M, "decode_step", _broken_step(fault))
    line = harness.run(name, 2**31 + 3, 0.5, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       root=tiny_root, log=lambda *a, **k: None)
    assert line["correct"] is False
    assert any(v["value"] > v["limit"] for v in line["checks"].values())
