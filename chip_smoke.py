"""Smoke run of the serving path on one TPU chip, at minicpm-2b's width.

    python chip_smoke.py

Builds minicpm-2b (40 layers, d_model 2304, 36 MHA heads of 64, vocab
122,753) with bf16 weights drawn from `jax.random.key(0)` — random weights
are the point: nothing is downloaded — and serves a few 512-token prompts
for 32 new tokens each on 4 slots through `Engine.generate_continuous`,
with the Pallas kernels compiled (`use_kernels=True`), in five phases:

  full     dense store: flash prefill + the bf16 decode kernel
  kivi2    paged, lazy growth, prefix sharing (256-token shared prefix):
           the paged quantized kernel, block adoption, copy-on-write
  h2o      dense: the kernel's mass output + the XLA scored prefill
  preempt  kivi2 paged with forced preemption and host tiering: spill and
           restore; streams must equal an unpreempted run
  spec     kivi2 self-speculative (window:64 drafter, gamma 4): the verify
           kernel; token agreement with plain decode is printed

Each phase fails the run unless every request completes with its full
length, two runs of it give the same streams, the engine's compiled decode
step (the verify step in `spec`) holds a `tpu_custom_call`, and one
decode step over a cache filled the way admissions fill it gives finite
logits that match the oracle path (`use_kernels=False`, same cache)
within LOGIT_RTOL of the logits' largest magnitude. The times printed
are smoke timings, not benchmark numbers. The last line of a passing run
is the JSON object `{"ok": true, "device": {...}}`; without a TPU the
script exits 1 before running anything.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.core import cache as kvcache  # noqa: E402
from repro.core import paging  # noqa: E402
from repro.core.policy import presets  # noqa: E402
from repro.nn import model as M  # noqa: E402
from repro.serving import Engine, Request  # noqa: E402
from repro.utils import init_compile_cache  # noqa: E402

# Kernel and oracle decode logits may differ by bf16 rounding carried
# through every layer; a kernel that reads the wrong block or mask is off
# by the logits' own magnitude.
LOGIT_RTOL = 5e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    prompt_len: int = 512
    max_new: int = 32
    slots: int = 4
    n_requests: int = 6
    shared_prefix: int = 256
    window: int = 16            # kivi2 ring = quant group; h2o ring
    kivi_budget: int = 512      # main store rows: decode evicts, so
                                # shared blocks are copied on write
    h2o_budget: int = 256
    preempt_at: tuple = ((3, 0), (5, 1))


def _requests(cfg, sz: Sizes, seed: int, shared: int = 0):
    rng = np.random.default_rng(seed)
    head = rng.integers(0, cfg.vocab_size, size=shared)
    return [np.concatenate([head, rng.integers(
        0, cfg.vocab_size, size=sz.prompt_len - shared)]).astype(np.int32)
        for _ in range(sz.n_requests)]


def _serve(eng, prompts, sz: Sizes):
    """One `generate_continuous` run; returns (streams, wall seconds)."""
    reqs = [Request(tokens=p, max_new=sz.max_new) for p in prompts]
    t0 = time.perf_counter()
    res = eng.generate_continuous(reqs)
    wall = time.perf_counter() - t0
    bad = [(r.uid, r.finish_reason, r.n_tokens) for r in res.results
           if r.finish_reason == "failed" or r.n_tokens != sz.max_new]
    if len(res.results) != len(reqs) or bad:
        raise AssertionError(f"incomplete requests: {bad}")
    return [r.tokens.tolist() for r in res.results], wall, res


def _probe(eng, prompts):
    """The engine's live-cache layout with one prompt admitted per slot
    (batch-1 prefill, then the same insert an admission runs), plus each
    slot's next token."""
    cfg, spec = eng.cfg, eng.spec
    lb = jnp.asarray(eng.layer_budgets, jnp.int32)
    cache = M.init_cache(cfg, spec, eng.slots, eng.prompt_len + eng.max_new,
                         layer_budgets=lb, paged=eng.paged,
                         block_len=eng.block_len,
                         pool_blocks=eng.pool_blocks)
    prefill = jax.jit(lambda p, t: M.prefill(p, cfg, {"tokens": t}, spec,
                                             layer_budgets=lb))
    if eng.paged:
        insert = jax.jit(lambda c, pc, s, ids: c._replace(
            attn=paging.insert_request_paged(c.attn, s, pc.attn, ids,
                                             batch_axis=2)))
    else:
        insert = jax.jit(lambda c, pc, s: c._replace(
            attn=kvcache.insert_request(c.attn, s, pc.attn, batch_axis=2)))
    toks = []
    for s in range(eng.slots):
        logits, pc = prefill(eng.params, jnp.asarray(prompts[s][None]))
        toks.append(jnp.argmax(logits, -1).astype(jnp.int32))
        if eng.paged:
            n = eng.n_max_blocks
            cache = insert(cache, pc, jnp.int32(s),
                           jnp.arange(s * n, (s + 1) * n, dtype=jnp.int32))
        else:
            cache = insert(cache, pc, jnp.int32(s))
    return cache, jnp.concatenate(toks)[:, None]


def _decode_parity(eng, prompts):
    """Max |kernel - oracle| decode logits over one step of a probe cache,
    the oracle logits' largest magnitude, and whether both are finite."""
    cache, tok = _probe(eng, prompts)
    out = {}
    for name, flag in (("kernel", True), ("oracle", False)):
        cfg = dataclasses.replace(eng.cfg, use_kernels=flag)
        step = jax.jit(lambda p, c, t, cfg=cfg: M.decode_step(
            p, cfg, c, t, eng.spec)[0])
        out[name] = np.asarray(jax.device_get(step(eng.params, cache, tok)),
                               np.float32)
    finite = bool(np.isfinite(out["kernel"]).all()
                  and np.isfinite(out["oracle"]).all())
    diff = float(np.max(np.abs(out["kernel"] - out["oracle"])))
    return diff, float(np.max(np.abs(out["oracle"]))), finite, cache, tok


def _step_text(eng, cache, tok) -> str:
    """Compiled text of the engine's jitted step: the verify step for a
    speculative engine, the decode step otherwise."""
    key = jax.random.key(0)
    if eng.speculative:
        toks = jnp.zeros((eng.slots, eng.gamma + 1), jnp.int32)
        vl = jnp.full((eng.slots,), eng.gamma + 1, jnp.int32)
        lowered = eng._verify.lower(eng.params, cache, toks, vl, key)
    else:
        lowered = eng._decode.lower(eng.params, cache, tok, key)
    return lowered.compile().as_text()


def run_phases(cfg, params, sz: Sizes = Sizes(), *, kind: str = "",
               custom_call: bool = True) -> list:
    """Run every phase; raises on the first failed check. Returns one
    record per phase. `custom_call=False` skips the `tpu_custom_call`
    check, for a run of the same phases in interpret mode."""
    pol_k = presets(budget=sz.kivi_budget, window=sz.window)["kivi2"]
    pol_h = presets(budget=sz.h2o_budget, window=sz.window)["h2o"]
    pol_f = presets(budget=sz.kivi_budget, window=sz.window)["full"]
    base = dict(prompt_len=sz.prompt_len, max_new=sz.max_new,
                slots=sz.slots, use_kernels=True)
    plain = _requests(cfg, sz, seed=1)
    shared = _requests(cfg, sz, seed=2, shared=sz.shared_prefix)
    paged = dict(paged=True, block_len=sz.window)
    phases = [
        ("full", pol_f, {}, plain, None),
        ("kivi2", pol_k, dict(paged, block_growth="lazy",
                              prefix_sharing=True), shared, None),
        ("h2o", pol_h, {}, plain, None),
        ("preempt", pol_k, dict(paged, preemption=True, tiering=True,
                                preempt_at=sz.preempt_at), plain, paged),
        ("spec", pol_k, dict(speculative=True, draft_policy="window:64",
                             gamma=4), plain, {}),
    ]
    records = []
    for name, pol, kw, prompts, ref_kw in phases:
        eng = Engine(cfg, params, pol, **base, **kw)
        streams, cold, res = _serve(eng, prompts, sz)
        again, warm, _ = _serve(eng, prompts, sz)
        if again != streams:
            raise AssertionError(f"{name}: two runs gave different streams")
        rec = dict(phase=name, kind=kind, compile_s=cold - warm,
                   wall_s=warm)
        if ref_kw is not None:
            ref, _, _ = _serve(Engine(cfg, params, pol, **base, **ref_kw),
                               prompts, sz)
            n = sum(len(s) for s in ref)
            rec["agree"] = sum(a == b for s, r in zip(streams, ref)
                               for a, b in zip(s, r)) / n
            rec["identical"] = streams == ref
            if name == "preempt":
                pre = sum(r.n_preemptions for r in res.results)
                rec["preemptions"] = pre
                rec["spills"] = res.tier["n_spills"]
                if not rec["identical"] or not pre:
                    raise AssertionError(
                        f"preempt: streams identical={rec['identical']} "
                        f"after {pre} preemptions")
            if res.spec is not None:
                rec["acceptance"] = res.spec.acceptance_rate
        if res.prefix is not None:
            rec["warm_hits"] = res.prefix["warm_hits"]
            rec["cow_copies"] = res.prefix["cow_copies"]
            if not (rec["warm_hits"] and rec["cow_copies"]):
                raise AssertionError(f"{name}: no prefix adoption or "
                                     f"copy-on-write: {res.prefix}")
        diff, scale, finite, cache, tok = _decode_parity(eng, prompts)
        rec.update(max_logit_diff=diff, logit_scale=scale)
        if not finite:
            raise AssertionError(f"{name}: non-finite decode logits")
        if not diff <= LOGIT_RTOL * max(scale, 1.0):
            raise AssertionError(
                f"{name}: kernel vs oracle logits differ by {diff} "
                f"(scale {scale}, rtol {LOGIT_RTOL})")
        if custom_call:
            rec["custom_call"] = "tpu_custom_call" in _step_text(eng, cache,
                                                                  tok)
            if not rec["custom_call"]:
                raise AssertionError(
                    f"{name}: no tpu_custom_call in the compiled step")
        print(" ".join(f"{k}={v}" for k, v in rec.items()), flush=True)
        records.append(rec)
        del eng, cache
    return records


def main() -> int:
    init_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    cfg = get_config("minicpm-2b")
    t0 = time.perf_counter()
    params = jax.jit(M.init_params, static_argnums=1)(jax.random.key(0), cfg)
    jax.block_until_ready(params)
    print(f"minicpm-2b random bf16 weights on {dev.device_kind}: "
          f"{sum(x.size for x in jax.tree.leaves(params)):,} params in "
          f"{time.perf_counter() - t0:.1f}s")
    print("per-phase compile_s / wall_s are smoke timings, not benchmark "
          "numbers (compile_s = cold run minus warm run)")
    run_phases(cfg, params, kind=dev.device_kind)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
