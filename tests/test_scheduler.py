"""Continuous-batching lifecycle: scheduler bookkeeping, per-slot cache
surgery (insert_request / reset_slot), and end-to-end early-exit +
slot-reuse correctness against the wave-based reference path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced
from repro.core import cache as C
from repro.core import paging as P
from repro.core.cache import CacheSpec
from repro.core.policy import presets
from repro.nn import model as M
from repro.serving import Engine, Request, Scheduler


# ---------------------------------------------------------------------------
# Scheduler unit tests (pure python, fake clock)
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _req(L, max_new=4, eos=None):
    return Request(tokens=np.zeros(L, np.int32), max_new=max_new, eos_id=eos)


def test_scheduler_fifo_and_buckets():
    sched = Scheduler((128, 32, 64), n_slots=2, clock=_FakeClock())
    assert sched.buckets == (32, 64, 128)
    r1, r2, r3 = _req(32), _req(128), _req(64)
    for r in (r1, r2, r3):
        sched.submit(r)
    assert sched.pending == 3
    assert sched.admit_next(0).uid == r1.uid        # FIFO
    assert sched.admit_next(1).uid == r2.uid
    assert sched.free_slots() == []
    with pytest.raises(ValueError):
        sched.admit_next(0)                          # occupied
    with pytest.raises(ValueError):
        sched.submit(_req(33))                       # no such bucket


def test_scheduler_lifecycle_eos_and_length():
    sched = Scheduler((16,), n_slots=1, clock=_FakeClock())
    sched.submit(_req(16, max_new=3, eos=7))
    sched.submit(_req(16, max_new=2))
    sched.admit_next(0)
    assert sched.record_token(0, 5) is None
    assert sched.record_token(0, 7) == "eos"         # before max_new
    res = sched.retire(0, "eos")
    assert res.finish_reason == "eos"
    np.testing.assert_array_equal(res.tokens, [5, 7])
    assert res.ttft_s > 0 and res.total_s >= res.ttft_s

    sched.admit_next(0)
    assert sched.record_token(0, 7) is None          # eos_id=None: ignored
    assert sched.record_token(0, 9) == "length"
    sched.retire(0, "length")
    assert sched.all_done()
    assert [r.n_tokens for r in sched.results] == [2, 2]


def test_scheduler_occupancy_accounting():
    sched = Scheduler((16,), n_slots=2, clock=_FakeClock())
    sched.submit(_req(16))
    sched.admit_next(0)
    sched.note_decode_step()                         # 1 of 2 slots active
    sched.note_decode_step()
    assert sched.occupancy == pytest.approx(0.5)


def test_scheduler_slot_state_counts():
    """Every slot-step of a scripted admit/prefill/retire sequence is
    active, prefilling, starved (free, queue waiting) or drain (free,
    queue empty), counted exactly."""
    sched = Scheduler((16,), n_slots=3, clock=_FakeClock())
    for _ in range(3):
        sched.submit(_req(16, max_new=4))
    sched.admit_next(0)
    sched.begin_prefill(1)
    sched.note_decode_step()        # active 0, prefilling 1, starved 2
    sched.note_decode_step()
    sched.finish_prefill(1)
    sched.begin_prefill(2)          # queue now empty
    sched.note_decode_step()        # active 0 1, prefilling 2
    sched.finish_prefill(2)
    sched.retire(0, "length")
    sched.note_decode_step()        # active 1 2, drain 0
    sched.retire(1, "length")
    sched.note_decode_step()        # active 2, drain 0 1
    assert sched.decode_steps == 5
    assert (sched.prefill_slot_steps, sched.starved_slot_steps,
            sched.drain_slot_steps) == (3, 2, 3)
    assert sched.occupancy == pytest.approx(7 / 15)
    assert sched.prefill_share == pytest.approx(3 / 15)
    assert sched.starved_share == pytest.approx(2 / 15)
    assert sched.drain_share == pytest.approx(3 / 15)
    assert (sched.occupancy + sched.prefill_share + sched.starved_share
            + sched.drain_share) == pytest.approx(1.0)


def test_scheduler_chunked_admission_interleaves_resident_decode():
    """PREFILLING lifecycle (fake clock): while slot 1 streams its
    prompt chunks, the resident slot 0 keeps recording tokens — and the
    admitted request's TTFT clocks at its *real* first token, after the
    whole chunked prefill."""
    clock = _FakeClock()
    sched = Scheduler((16,), n_slots=2, clock=clock)
    sched.submit(_req(16, max_new=8))
    sched.submit(_req(16, max_new=2))
    sched.admit_next(0)
    sched.record_token(0, 1)                         # slot 0 resident

    req = sched.begin_prefill(1)                     # chunked admission
    assert req is not None
    assert sched.prefilling_slots() == [1]
    assert sched.active_slots() == [0]               # not active yet
    t_prefill_start = clock.t
    with pytest.raises(ValueError):
        sched.record_token(1, 5)                     # no tokens mid-prefill
    resident_times = []
    for tok in (2, 3, 4):                            # 3 chunks stream...
        sched.note_decode_step()
        sched.record_token(0, tok)                   # ...decode continues
        resident_times.append(clock.t)
    sched.finish_prefill(1)
    assert sched.prefilling_slots() == []
    assert sched.record_token(1, 9) is None          # first real token
    for t in (5, 6, 7, 8):
        sched.record_token(0, t)
    assert sched.record_token(1, 9) is not None      # max_new=4? no: 2nd
    res1 = sched.retire(1, "length")
    res0 = sched.retire(0, "length")
    # resident tokens were recorded strictly inside the admission window
    assert all(t > t_prefill_start for t in resident_times)
    assert res0.token_times.shape == (8,)
    # TTFT spans the whole chunked prefill (submit -> real first token)
    assert res1.ttft_s > (resident_times[-1] - t_prefill_start)
    np.testing.assert_array_equal(res0.tokens, [1, 2, 3, 4, 5, 6, 7, 8])


def test_scheduler_fail_head_and_failed_retire():
    clock = _FakeClock()
    sched = Scheduler((16,), n_slots=1, clock=clock)
    sched.submit(_req(16, max_new=4))
    sched.submit(_req(16, max_new=4))
    res = sched.fail_head()
    assert res.finish_reason == "failed" and res.slot == -1
    assert res.n_tokens == 0 and res.ttft_s == 0.0 and res.total_s > 0
    assert sched.pending == 1
    # a PREFILLING slot can also be retired as failed (no tokens yet)
    sched.begin_prefill(0)
    res2 = sched.retire(0, "failed")
    assert res2.finish_reason == "failed" and res2.ttft_s == 0.0
    assert sched.all_done()


def test_scheduler_preempt_requeues_with_prefix():
    """Preemption folds emitted tokens into the continuation prefix,
    releases blocks, requeues at the queue FRONT; on re-admission the
    length budget and the retired result count the prefix."""
    alloc = P.BlockAllocator(8)
    sched = Scheduler((16,), n_slots=2, clock=_FakeClock(),
                      allocator=alloc, block_need=lambda r: 2)
    r1 = _req(16, max_new=6)
    r2 = _req(16, max_new=6)
    sched.submit(r1)
    sched.submit(r2)
    assert sched.admit_next(0) is r1 and alloc.used == 2
    sched.record_token(0, 7)
    sched.record_token(0, 8)
    assert sched.preempt(0) is r1
    assert alloc.used == 0 and sched.active_slots() == []
    assert list(r1.emitted_prefix) == [7, 8]
    assert r1.n_preemptions == 1 and sched.n_preemptions == 1
    assert len(r1.token_times_prefix) == 2
    assert sched.pending == 2
    assert sched.admit_next(1) is r1         # continuation jumps r2
    for t in (9, 10, 11):
        assert sched.record_token(1, t) is None
    assert sched.record_token(1, 12) == "length"   # 2 prefix + 4 = 6
    res = sched.retire(1, "length")
    assert res.tokens.tolist() == [7, 8, 9, 10, 11, 12]
    assert res.n_preemptions == 1
    assert res.token_times.shape == (6,)
    assert res.ttft_s > 0                    # first-token time carried


def test_scheduler_preempt_guards():
    sched = Scheduler((16,), n_slots=2, clock=_FakeClock())
    with pytest.raises(ValueError):
        sched.preempt(0)                     # empty slot
    sched.submit(_req(16))
    sched.begin_prefill(0)
    with pytest.raises(ValueError, match="prefilling"):
        sched.preempt(0)                     # cancel, don't preempt


def test_scheduler_preempt_victim_policy():
    """Lowest progress fraction loses; ties break youngest-admitted
    first; prefilling and excluded slots are never victims."""
    sched = Scheduler((16,), n_slots=3, clock=_FakeClock())
    a = _req(16, max_new=4)
    b = _req(16, max_new=4)
    c = _req(16, max_new=8)
    for r in (a, b, c):
        sched.submit(r)
    assert sched.admit_next(0) is a
    assert sched.admit_next(1) is b
    assert sched.admit_next(2) is c
    for s in (0, 1, 2):
        sched.record_token(s, 1)
    assert sched.preempt_victim() == 2               # 1/8 < 1/4
    assert sched.preempt_victim(exclude=(2,)) == 1   # tie: b younger
    assert sched.preempt_victim(exclude=(1, 2)) == 0
    assert sched.preempt_victim(exclude=(0, 1, 2)) is None
    # a continuation's prefix counts as progress
    sched.preempt(2)
    assert sched.preempt_victim() in (0, 1)


def test_scheduler_note_retry_counts():
    sched = Scheduler((16,), n_slots=1, clock=_FakeClock())
    assert sched.note_retry() == 0           # empty queue: no-op
    sched.submit(_req(16))
    assert sched.note_retry() == 1
    assert sched.note_retry() == 2
    assert sched.n_retries == 2
    res = sched.fail_head()
    assert res.n_retries == 2                # surfaced on the result


def test_scheduler_replace_blocks_and_occupied():
    alloc = P.BlockAllocator(8)
    sched = Scheduler((16,), n_slots=2, clock=_FakeClock(),
                      allocator=alloc, block_need=lambda r: 4)
    sched.submit(_req(16))
    sched.admit_next(0)
    ids = sched.slot_blocks(0)
    keep = [ids[2], ids[0]]                  # degraded table order
    dropped = sched.replace_blocks(0, keep)
    assert sorted(dropped) == sorted(set(ids) - set(keep))
    assert sched.slot_blocks(0) == keep and alloc.used == 2
    assert sched.occupied_blocks() == {0: keep}
    with pytest.raises(AssertionError):
        sched.replace_blocks(0, [99])        # not a subset of the grant
    # occupied_blocks censuses PREFILLING holders too (audit input)
    sched.submit(_req(16))
    sched.begin_prefill(1)
    occ = sched.occupied_blocks()
    assert set(occ) == {0, 1} and occ[1] == sched.slot_blocks(1)


# ---------------------------------------------------------------------------
# Per-slot cache surgery
# ---------------------------------------------------------------------------


_DENSE = CacheSpec(budget=16, sinks=2, policy="h2o", window=0, group=1,
                   recent_protect=4)
_QUANT = CacheSpec(budget=16, sinks=2, policy="streaming", window=4, group=4,
                   bits=4)


@pytest.mark.parametrize("spec", [_DENSE, _QUANT], ids=["dense", "quant"])
def test_insert_request_and_reset_slot(spec):
    n_layers, B, H, D, S_p = 2, 3, 2, 8, 32
    stacked = C.stacked_kv(spec, n_layers, B, S_p, H, D, jnp.float32)

    ks = jax.random.split(jax.random.key(0), 3)
    k = jax.random.normal(ks[0], (1, S_p, H, D), jnp.float32)
    v = jax.random.normal(ks[1], (1, S_p, H, D), jnp.float32)
    mass = jax.random.uniform(ks[2], (1, S_p))
    one = C.compress_prompt(spec, k, v, mass, dtype=jnp.float32)
    pref = jax.tree.map(lambda x: jnp.stack([x] * n_layers), one)

    ins = C.insert_request(stacked, 1, pref, batch_axis=1)
    for f in C.LayerKV._fields:
        got, want = getattr(ins, f), getattr(pref, f)
        if f == "budget":
            # per-layer state shared by all slots: untouched by surgery
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(stacked.budget))
            continue
        np.testing.assert_array_equal(np.asarray(got[:, 1]),
                                      np.asarray(want[:, 0]), err_msg=f)
        # neighbouring slots untouched (still the init state)
        np.testing.assert_array_equal(np.asarray(got[:, 0]),
                                      np.asarray(getattr(stacked, f)[:, 0]),
                                      err_msg=f)

    # reset returns the slot to the fresh init state; neighbours keep theirs
    ins2 = C.insert_request(ins, 2, pref, batch_axis=1)
    back = C.reset_slot(ins2, 1, batch_axis=1)
    for f in C.LayerKV._fields:
        if f == "budget":
            continue
        np.testing.assert_array_equal(np.asarray(getattr(back, f)[:, 1]),
                                      np.asarray(getattr(stacked, f)[:, 1]),
                                      err_msg=f)
        np.testing.assert_array_equal(np.asarray(getattr(back, f)[:, 2]),
                                      np.asarray(getattr(ins2, f)[:, 2]),
                                      err_msg=f)
    assert int(back.length[0, 1]) == 0
    assert int(back.rlen[0, 1]) == 0
    assert bool((np.asarray(back.slot_pos[:, 1]) == -1).all())


# ---------------------------------------------------------------------------
# End-to-end: continuous == wave prefix, early exit frees slots cleanly
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def small_model():
    cfg = reduced(get_config("paper-llama-7b"), num_layers=2)
    params = M.init_params(jax.random.key(0), cfg)
    return cfg, params


def _prompts(cfg, n, L, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=(n, L)).astype(np.int32)


@pytest.mark.parametrize("pname", ["h2o", "kivi2"])
def test_continuous_matches_wave_with_early_exit(small_model, pname):
    """A request hitting EOS at step t produces tokens identical to the
    wave-based path up to t, and the freed slot's next occupant (requests
    3/4 reuse slots of 0..2) is unaffected by stale cache contents —
    across dense (h2o) and quantized (kivi2) specs."""
    cfg, params = small_model
    L, NEW, n = 32, 8, 5
    prompts = _prompts(cfg, n, L, seed=1)
    pol = presets(budget=32, window=8)[pname]

    wave = Engine(cfg, params, pol, prompt_len=L, max_new=NEW,
                  slots=2).generate(prompts).tokens

    eng = Engine(cfg, params, pol, prompt_len=L, max_new=NEW, slots=2)
    eos = int(wave[2, 3])            # force request 2 to exit early
    reqs = [Request(tokens=prompts[i], max_new=NEW,
                    eos_id=(eos if i == 2 else None)) for i in range(n)]
    res = eng.generate_continuous(reqs)

    assert len(res.results) == n
    for i, r in enumerate(res.results):
        np.testing.assert_array_equal(
            r.tokens, wave[i][:r.n_tokens],
            err_msg=f"{pname} request {i} diverged from wave path")
    early = res.results[2]
    assert early.finish_reason == "eos"
    # stops at the *first* occurrence of the eos value, eos included
    first = int(np.argmax(wave[2] == eos))
    assert early.n_tokens == first + 1
    others = [r for i, r in enumerate(res.results) if i != 2]
    assert all(r.finish_reason == "length" and r.n_tokens == NEW
               for r in others)
    # 5 requests through 2 slots: reuse actually happened
    assert len({r.slot for r in res.results}) <= 2
    assert res.decode_tokens > 0 and res.occupancy > 0


def test_continuous_multibucket_matches_wave(small_model):
    """Mixed 32/64-token prompts through one engine: every request matches
    its own-bucket wave reference (bucketed prefills are exact)."""
    cfg, params = small_model
    NEW = 6
    pol = presets(budget=32, window=8)["h2o"]
    p32 = _prompts(cfg, 2, 32, seed=2)
    p64 = _prompts(cfg, 2, 64, seed=3)
    ref = {}
    for L, ps in ((32, p32), (64, p64)):
        ref[L] = Engine(cfg, params, pol, prompt_len=L, max_new=NEW,
                        slots=2).generate(ps).tokens

    eng = Engine(cfg, params, pol, max_new=NEW, slots=2, buckets=(32, 64))
    reqs = [Request(tokens=p32[0], max_new=NEW),
            Request(tokens=p64[0], max_new=NEW),
            Request(tokens=p32[1], max_new=NEW),
            Request(tokens=p64[1], max_new=NEW)]
    res = eng.generate_continuous(reqs)
    np.testing.assert_array_equal(res.results[0].tokens, ref[32][0])
    np.testing.assert_array_equal(res.results[1].tokens, ref[64][0])
    np.testing.assert_array_equal(res.results[2].tokens, ref[32][1])
    np.testing.assert_array_equal(res.results[3].tokens, ref[64][1])
    assert {r.bucket for r in res.results} == {32, 64}


def test_continuous_rejects_oversized_request(small_model):
    cfg, params = small_model
    pol = presets(budget=32, window=8)["h2o"]
    eng = Engine(cfg, params, pol, prompt_len=32, max_new=4, slots=2)
    with pytest.raises(ValueError):
        eng.generate_continuous(
            [Request(tokens=np.zeros(32, np.int32), max_new=99)])
    with pytest.raises(ValueError):
        eng.generate_continuous(
            [Request(tokens=np.zeros(7, np.int32), max_new=2)])
    with pytest.raises(ValueError):
        # override buckets can't exceed what the cache was sized for
        eng.generate_continuous(
            [Request(tokens=np.zeros(64, np.int32), max_new=2)],
            buckets=(64,))
