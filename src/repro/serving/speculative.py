"""Self-speculative decoding over compressed KV caches.

Per-token decode is weight-bandwidth-bound: every step moves all
parameters to produce one token per slot. The survey's hybrid direction
(§5/§7) pairs compression with complementary speedups, and a compressed
cache is not just smaller — it is a cheap *drafter*. This module runs
TriForce-style self-speculation inside the continuous-batching engine:

  * **draft** — the same weights decode gamma tokens per slot against a
    much cheaper cache view (`--draft-policy`): a sliding-window
    attention view over an uncompressed store (`window:N`), a quantized
    KIVI ring at a tiny budget (`kivi2:B:W` / `kivi4` / `int8`), or
    `same` (a clone of the target spec — the acceptance-rate ceiling,
    for sanity runs). The drafter owns a second, per-slot cache over the
    same weights; drafting is ordinary `decode_step`s on it.
  * **verify** — ONE rectangular forward (`nn.model.verify_step`) scores
    the whole segment (last committed token + drafts) against the real
    budgeted cache: `cache.append_segment` appends the segment (bit-equal
    to sequential appends), `nn.attention.verify_attention` attends every
    row in one pass over the cache (the flash_prefill_chunk segment×cache
    grid on the kernel path), and greedy acceptance reduces rejection
    sampling to match-and-truncate: the longest draft prefix matching the
    target's argmax commits, plus the bonus/correction token.
  * **rollback** — rejected rows are un-appended (`cache.truncate_rows`)
    inside the same verify step; under lazy block growth the engine
    returns no-longer-covered pool blocks to the free list.

**Exactness.** Greedy speculative streams are bit-identical to
non-speculative decode (full/h2o/kivi2 × dense/paged) because every
verify sub-step reproduces the decode step it replaces exactly. The one
obligation that makes rollback trivial is the **depth cap**: a slot may
draft at most as many tokens as its cache can append *without firing an
eviction or a quantized ring flush* (`CacheMirror.headroom_after_feeds`)
— the committed first token may evict/flush (it is never rolled back),
the draft rows may not. Consequences per store:

  * uncompressed (`full`): headroom is the remaining decode budget —
    near-full speculation depth for the whole request;
  * quantized rings (`kivi*`): headroom cycles with the ring — after a
    flush step the ring reopens `window - 1` draft rows, so speculation
    proceeds in ring-sized bursts with one plain (flushing) step between;
  * dense compressed at budget (`h2o` post-fill): headroom is 0 — every
    step degrades to a plain single-token verify, and the stream equality
    contract holds trivially. (Exact speculation through mid-segment
    evictions would need an undo log for evicted rows; see README.)

The per-slot headroom/row arithmetic is mirrored host-side
(`CacheMirror`): flush and eviction timing depend only on append counts,
never on values, so the engine decides depths and lazy block grants
without device syncs.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import paging as paging_lib
from repro.core.cache import CacheSpec
from repro.obs import Phases
from repro.serving.scheduler import Request

Array = jax.Array


# ---------------------------------------------------------------------------
# Draft-policy resolution
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class DraftPolicy:
    """Resolved drafter: a (possibly modified) model config + cache spec
    the drafter decodes with. Same weights either way."""
    name: str
    cfg: Any
    spec: CacheSpec


def resolve_draft_policy(policy: str, cfg, base_spec: CacheSpec,
                         prompt_len: int, max_new: int) -> DraftPolicy:
    """Parse a `--draft-policy` string.

    * ``window:N`` — sliding-window attention view (window N) over an
      *uncompressed* store: cheapest attention reads, always has append
      headroom (a latency drafter, not a memory drafter — the draft
      store holds the full stream).
    * ``kivi2[:budget[:window]]`` (also kivi4 / int8) — quantized KIVI
      ring at a tiny budget: a true compressed-memory drafter whose ring
      headroom cycles like the target's.
    * ``same`` — clone of the target spec (acceptance ceiling; the
      drafter computes exactly what the verifier does).
    """
    parts = policy.split(":")
    kind = parts[0]
    if kind == "same":
        return DraftPolicy("same", cfg, base_spec)
    if kind == "window":
        win = int(parts[1]) if len(parts) > 1 else 64
        if win < 1:
            raise ValueError(f"draft window must be >= 1, got {win}")
        dcfg = dataclasses.replace(cfg, sliding_window=win)
        spec = CacheSpec(budget=prompt_len + max_new, policy="none",
                         sinks=base_spec.sinks)
        return DraftPolicy(f"window:{win}", dcfg, spec)
    bits = {"kivi2": 2, "kivi4": 4, "int8": 8}.get(kind)
    if bits is None:
        raise ValueError(
            f"unknown draft policy {policy!r} (want window:N, "
            f"kivi2[:budget[:window]], kivi4[...], int8[...], or same)")
    window = int(parts[2]) if len(parts) > 2 else (base_spec.window or 16)
    budget = int(parts[1]) if len(parts) > 1 else (base_spec.budget or 64)
    budget = max(-(-budget // window) * window, window)   # group-aligned
    spec = CacheSpec(budget=budget, window=window, bits=bits, group=window,
                     policy="streaming", sinks=base_spec.sinks)
    return DraftPolicy(f"{kind}:{budget}:{window}", cfg, spec)


# ---------------------------------------------------------------------------
# Host-side cache mirror
# ---------------------------------------------------------------------------


class CacheMirror:
    """Host replica of the per-slot cache-growth state (per-layer main
    store `length`, ring `rlen`, absolute `pos`). Append/flush/eviction
    *timing* depends only on counts — `append_token` flushes iff
    ``rlen >= window`` and evicts iff ``length >= cap`` — so the engine
    can compute speculative depth caps and lazy block coverage without
    fetching device state. The mirror is advanced by the engine for
    every append/truncate it causes and re-derived from scratch at each
    admission (`compress_prompt`'s arithmetic)."""

    def __init__(self, spec: CacheSpec, layer_budgets, S_phys: int,
                 n_slots: int):
        self.spec = spec
        self.S = int(S_phys)
        lb = np.minimum(np.asarray(layer_budgets, np.int64).reshape(-1),
                        self.S)
        if spec.quantized:
            G = spec.group
            self.cap_rows = (lb // G) * G      # flush grows whole groups
        else:
            self.cap_rows = lb                 # append evicts at min(lb, S)
        self.length = np.zeros((n_slots, lb.size), np.int64)
        self.rlen = np.zeros(n_slots, np.int64)
        self.pos = np.zeros(n_slots, np.int64)

    def admit(self, slot: int, prompt_len: int) -> None:
        """Replicate `compress_prompt`'s post-admission state."""
        spec, S, W = self.spec, self.S, self.spec.window
        if S >= prompt_len and not spec.quantized and W == 0:
            self.length[slot] = prompt_len     # verbatim-placement branch
        else:
            n_main = max(min(S, prompt_len - W), 0)
            self.length[slot] = np.minimum(n_main, self.cap_rows)
        self.rlen[slot] = W
        self.pos[slot] = prompt_len

    def reset(self, slot: int) -> None:
        self.length[slot] = 0
        self.rlen[slot] = 0
        self.pos[slot] = 0

    def snapshot(self, slot: int) -> dict:
        """The slot's mirror row, detached — rides a host-tier slot
        snapshot so a spill-preempted request resumes with the exact
        eviction/ring state it left with."""
        return dict(length=self.length[slot].copy(),
                    rlen=int(self.rlen[slot]), pos=int(self.pos[slot]))

    def restore(self, slot: int, snap: dict) -> None:
        self.length[slot] = snap["length"]
        self.rlen[slot] = snap["rlen"]
        self.pos[slot] = snap["pos"]

    def _sim(self, slot: int, n: int):
        """(length, rlen) after n more appends."""
        ln = self.length[slot].copy()
        rl = int(self.rlen[slot])
        W = self.spec.window
        for _ in range(n):
            if self.spec.quantized:
                if rl >= W:
                    ln = np.minimum(ln + W, self.cap_rows)
                    rl = 0
                rl += 1
            else:
                ln = np.minimum(ln + 1, self.cap_rows)
        return ln, rl

    def append(self, slot: int, n: int = 1) -> None:
        self.length[slot], self.rlen[slot] = self._sim(slot, n)
        self.pos[slot] += n

    def truncate(self, slot: int, n: int) -> None:
        """Mirror of `cache.truncate_rows` (headroom contract: the
        undone appends were fresh in every layer)."""
        if n <= 0:
            return
        if self.spec.quantized:
            self.rlen[slot] -= n
        else:
            self.length[slot] -= n
        self.pos[slot] -= n

    def drop_rows(self, slot: int, n: int) -> None:
        """Mirror of pressure degradation (`degrade_slot_groups`): the
        slot lost `n` of its oldest flushed main-store rows in every
        layer. Ring state and absolute position are untouched — the
        drop rewrites history, not the append cursor."""
        if n <= 0:
            return
        self.length[slot] = np.maximum(self.length[slot] - n, 0)

    def headroom_after_feeds(self, slot: int, n: int) -> int:
        """Appends guaranteed eviction/flush-free after `n` more appends
        land — the speculative depth budget for rollbackable rows."""
        ln, rl = self._sim(slot, n)
        if self.spec.quantized:
            return int(self.spec.window - rl)
        return int(np.min(self.cap_rows - ln))

    def rows_after_feeds(self, slot: int, n: int) -> int:
        """Max main-store rows any layer uses after `n` more appends —
        the paged block-coverage target (the table is shared across
        layers, so coverage follows the widest layer)."""
        ln, _ = self._sim(slot, n)
        return int(ln.max())


# ---------------------------------------------------------------------------
# Acceptance accounting
# ---------------------------------------------------------------------------


@dataclass
class SpecStats:
    """Draft/verify accounting for one `generate_continuous` run."""
    rounds: int = 0             # engine loop iterations that dispatched
    verify_steps: int = 0       # slot-steps verified with >= 1 draft
    plain_steps: int = 0        # slot-steps with no drafts (depth cap 0)
    drafted: int = 0            # draft tokens proposed
    accepted: int = 0           # draft tokens accepted by the verifier
    committed: int = 0          # tokens committed by drafted verify steps
    draft_policy: str = ""
    gamma: int = 0

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / max(self.drafted, 1)

    @property
    def committed_per_verify_step(self) -> float:
        return self.committed / max(self.verify_steps, 1)

    def describe(self) -> str:
        return (f"spec[{self.draft_policy} gamma={self.gamma}]: "
                f"{self.verify_steps} verify + {self.plain_steps} plain "
                f"slot-steps, acceptance {self.acceptance_rate:.2f} "
                f"({self.accepted}/{self.drafted}), "
                f"{self.committed_per_verify_step:.2f} committed/verify")


# ---------------------------------------------------------------------------
# The draft/verify serving loop
# ---------------------------------------------------------------------------


@dataclass
class _SlotSpecState:
    """Per-slot host state of the speculative lifecycle."""
    stream: List[int] = field(default_factory=list)   # prompt + committed
    fed: int = 0            # stream tokens whose KV the draft cache holds
    # recompute-on-resume: committed tokens still to re-feed through the
    # target cache (outputs discarded). While nonempty the slot drafts
    # nothing (gamma forced 0) — replay rounds are plain re-decodes.
    replay: List[int] = field(default_factory=list)
    # True from continuation admit until the first post-replay round:
    # that round also runs plain (gamma 0) so its single append stays
    # inside the admission's resume reserve — it always completes and
    # commits >= 1 new token, which is what makes preemption converge.
    resumed: bool = False


def generate_continuous_spec(eng, requests: Sequence[Union[Request,
                                                           np.ndarray]], *,
                             buckets: Optional[Sequence[int]] = None):
    """Speculative twin of `Engine.generate_continuous` (dispatched from
    it when the engine was built with ``speculative=True``). Synchronous
    rounds — drafting needs each round's committed tokens on the host —
    of: admit (monolithic, or one chunked-prefill step) -> draft ->
    grant blocks (lazy paged) -> verify/commit/rollback -> record.
    """
    from repro.nn import model as M
    from repro.serving.scheduler import Scheduler

    cfg = eng.cfg
    gamma = eng.gamma
    stats = SpecStats(draft_policy=eng.draft.name, gamma=gamma)

    if eng.paged:
        eng.block_allocator = paging_lib.BlockAllocator(
            eng.pool_blocks, fault_plan=eng.fault_plan, tracer=eng.trace)
        sched = Scheduler(buckets or eng.buckets, eng.slots,
                          allocator=eng.block_allocator,
                          block_need=eng._request_blocks,
                          admission_order=eng.admission_order,
                          tracer=eng.trace)
    else:
        sched = Scheduler(buckets or eng.buckets, eng.slots,
                          admission_order=eng.admission_order,
                          tracer=eng.trace)
    for r in requests:
        if not isinstance(r, Request):
            r = Request(tokens=r, max_new=eng.max_new)
        if r.max_new > eng.max_new:
            raise ValueError(f"request max_new {r.max_new} exceeds engine "
                             f"headroom {eng.max_new}")
        sched.submit(r)

    max_len = eng.prompt_len + eng.max_new
    cache = M.init_cache(cfg, eng.spec, eng.slots, max_len,
                         layer_budgets=jnp.asarray(eng.layer_budgets,
                                                   jnp.int32),
                         paged=eng.paged, block_len=eng.block_len,
                         pool_blocks=eng.pool_blocks)
    dcache = M.init_cache(eng.draft.cfg, eng.draft.spec, eng.slots,
                          max_len,
                          layer_budgets=jnp.asarray(eng.draft_layer_budgets,
                                                    jnp.int32))
    tmirror = CacheMirror(eng.spec, eng.layer_budgets, eng._S_phys,
                          eng.slots)
    dmirror = CacheMirror(eng.draft.spec, eng.draft_layer_budgets,
                          eng.draft.spec.main_store_len(max_len), eng.slots)
    slot_state: List[_SlotSpecState] = [_SlotSpecState()
                                        for _ in range(eng.slots)]
    lb = jnp.asarray(eng.layer_budgets)
    dlb = jnp.asarray(eng.draft_layer_budgets)
    prefill_s = 0.0
    decode_tokens = 0
    clean = set(range(eng.slots))
    # token fetches are timed always (the result's fetch seconds) and
    # emitted as `fetch` spans under a live tracer
    phases = Phases(eng.trace)

    def reset_slot(i: int) -> None:
        nonlocal cache, dcache
        cache = eng._reset(cache, jnp.int32(i))
        dcache = eng._reset_draft(dcache, jnp.int32(i))
        tmirror.reset(i)
        dmirror.reset(i)
        slot_state[i] = _SlotSpecState()
        clean.add(i)

    def replaying() -> List[int]:
        """Slots mid-resume — never preemption victims (convergence: a
        victim must have recorded progress since its last preemption)."""
        return [i for i, st in enumerate(slot_state) if st.replay]

    def spec_preempt(i: int) -> None:
        """Preempt slot `i`: requeue prompt + committed as a continuation
        and drop all of its device state (target AND drafter — the
        drafter re-prefills at re-admission, so no draft row survives).
        Unlike the plain loop there is never a pending token to fold:
        every committed token was recorded synchronously."""
        sched.preempt(i)
        reset_slot(i)

    def admit_draft(slot: int, req: Request, key) -> None:
        """Prefill + insert the drafter's cache for a just-admitted
        request (the drafter sees the same prompt under its own spec)."""
        nonlocal dcache, prefill_s
        with eng.trace.span("draft_prefill", tid=slot + 1,
                            args=dict(uid=req.uid)) as sp:
            _, dpc = eng._draft_prefill(
                eng.params, {"tokens": jnp.asarray(req.tokens[None])},
                dlb, key)
            dcache = eng._insert_draft(dcache, dpc, jnp.int32(slot))
        prefill_s += sp.elapsed
        dmirror.admit(slot, len(req.tokens))
        slot_state[slot] = _SlotSpecState(stream=list(map(int, req.tokens)),
                                          fed=len(req.tokens))

    def record(slot: int, tok: int, *, count: bool = True) -> bool:
        """Record one committed token; True if the slot retired.
        count=False for a request's prefill-produced first token — the
        plain loop's decode_tokens excludes those, and the benchmark
        compares the two loops' tok/s."""
        nonlocal decode_tokens
        if count:
            decode_tokens += 1
        slot_state[slot].stream.append(int(tok))
        reason = sched.record_token(slot, int(tok))
        if reason is not None:
            sched.retire(slot, reason)
            reset_slot(slot)
            return True
        return False

    def admit_into(slot: int, ladder: bool = False) -> bool:
        """Monolithic admission (target + draft caches). Mirrors the
        engine's plain-loop admission, extended with the drafter.
        `ladder=True` (round-top sweep only — never mid-round, where a
        victim reset would corrupt in-flight per-round state) lets a
        refused admission preempt a victim for its blocks."""
        nonlocal cache, prefill_s
        while True:
            req = sched.admit_next(slot)
            if req is None:
                if eng.paged and sched.pending:
                    tries = sched.note_retry()
                    if (ladder and eng.preemption
                            and tries > eng.preempt_patience):
                        v = sched.preempt_victim(
                            exclude=(slot, *replaying()))
                        if v is not None:
                            spec_preempt(v)
                            continue
                    if (not sched.active_slots()
                            and not sched.prefilling_slots()):
                        # transient injected refusals get a bounded
                        # retry window before the head is declared
                        # truly unservable
                        if tries <= eng.fail_patience:
                            continue
                        sched.fail_head()
                        continue
                if slot not in clean:
                    reset_slot(slot)
                return False
            eng.key, k1 = jax.random.split(eng.key)
            with eng.trace.span("prefill", tid=slot + 1,
                                args=dict(uid=req.uid)) as sp:
                logits, pc = eng._prefill(
                    eng.params, {"tokens": jnp.asarray(req.tokens[None])},
                    lb, k1)
                tok = eng.sampler(logits, k1)
                if eng.paged:
                    ids = np.full(eng.n_max_blocks, -1, np.int32)
                    got = sched.slot_blocks(slot)
                    ids[:len(got)] = got
                    cache = eng._insert(cache, pc, jnp.int32(slot),
                                        jnp.asarray(ids), jnp.int32(0))
                else:
                    cache = eng._insert(cache, pc, jnp.int32(slot))
                clean.discard(slot)
                tmirror.admit(slot, len(req.tokens))
            prefill_s += sp.elapsed
            admit_draft(slot, req, k1)
            if req.emitted_prefix:
                # preempted continuation: the prompt's KV was just
                # re-prefilled; the already-recorded tokens re-enter
                # through plain replay rounds (all but the last fed with
                # outputs discarded; the last fed token's output is the
                # first NEW token). The prefill's sample is discarded —
                # the first emitted token is already in the prefix.
                st = slot_state[slot]
                st.stream = (list(map(int, req.tokens))
                             + [int(t) for t in req.emitted_prefix])
                st.replay = [int(t) for t in req.emitted_prefix[:-1]]
                st.resumed = True
                return True
            prev = phases.to("fetch")
            # kvlint: ok(host-sync: admission prefill's first token — once per admitted request, not per round)
            tok_i = int(jax.device_get(tok)[0])
            phases.to(prev)
            if not record(slot, tok_i, count=False):
                return True
            # 1-token request: retired immediately, refill the slot

    def grow_blocks_for(slot: int, n_appends: int) -> bool:
        """Lazy paged growth: make the slot's table cover the rows the
        next `n_appends` appends can touch. False = pool starved."""
        nonlocal cache
        if not (eng.paged and eng.lazy_blocks):
            return True
        rows = tmirror.rows_after_feeds(slot, n_appends)
        need = paging_lib.request_blocks_prefix(eng.spec, eng._S_phys,
                                                rows, eng.block_len)
        have = len(sched.slot_blocks(slot))
        if need <= have:
            return True
        if not sched.grant_blocks(slot, need - have):
            return False
        ids = sched.slot_blocks(slot)[have:]
        cache = eng._grow_tbl(cache, jnp.int32(slot), jnp.int32(have),
                              jnp.asarray(ids, jnp.int32))
        return True

    def shrink_blocks_for(slot: int) -> None:
        """Rollback's free-list return: release table entries beyond the
        post-truncate row coverage."""
        nonlocal cache
        if not (eng.paged and eng.lazy_blocks):
            return
        rows = tmirror.rows_after_feeds(slot, 0)
        need = paging_lib.request_blocks_prefix(eng.spec, eng._S_phys,
                                                rows, eng.block_len)
        have = len(sched.slot_blocks(slot))
        if have > need:
            sched.release_blocks(slot, have - need)
            cache = eng._clear_tbl(cache, jnp.int32(slot), jnp.int32(need))

    # chunked-prefill interleave state (at most one admission in flight)
    adm = None
    preempt_due = list(eng.preempt_at)   # forced (round, slot) pairs

    if not eng.chunked_prefill:
        for i in range(eng.slots):
            admit_into(i)

    # per-round telemetry: host mirrors only
    trace = eng.trace
    n_iters = 0
    loop_t0 = time.perf_counter()
    prefill_at_loop = prefill_s
    fetch_at_loop = phases.totals.get("fetch", 0.0)
    while True:
        it_t0 = time.perf_counter()
        n_iters += 1
        if trace:
            trace.counter("slots", dict(
                active=len(sched.active_slots()),
                prefilling=len(sched.prefilling_slots()),
                free=len(sched.free_slots())))
        if eng.chunked_prefill and adm is None:
            adm, dt0 = eng._start_admission_timed(sched)
            prefill_s += dt0
        active = sched.active_slots()
        if eng.chunked_prefill and adm is not None:
            cache, adm, first, dt = eng._advance_chunked_admission(
                adm, sched, cache, lb, run_all=not active)
            prefill_s += dt
            if first is not None:
                slot0, ftok = first
                clean.discard(slot0)
                req0 = sched.slot_request(slot0)
                tmirror.admit(slot0, len(req0.tokens))
                eng.key, kd = jax.random.split(eng.key)
                admit_draft(slot0, req0, kd)
                if req0.emitted_prefix:
                    # chunk-admitted continuation: discard the sampled
                    # first token, replay the recorded prefix instead
                    st0 = slot_state[slot0]
                    st0.stream = (list(map(int, req0.tokens))
                                  + [int(t) for t in req0.emitted_prefix])
                    st0.replay = [int(t) for t in req0.emitted_prefix[:-1]]
                    st0.resumed = True
                else:
                    phases.to("fetch")
                    # kvlint: ok(host-sync: chunk-admitted first token — once per admission, not per round)
                    tok0 = int(jax.device_get(ftok)[0])
                    phases.to(None)
                    record(slot0, tok0, count=False)
                active = sched.active_slots()
        if preempt_due:
            # forced preemptions (tests): fire at the given dispatch round
            due = [p for p in preempt_due if p[0] == stats.rounds]
            if due:
                preempt_due = [p for p in preempt_due
                               if p[0] != stats.rounds]
                for _, s in due:
                    if s in sched.active_slots():
                        spec_preempt(s)
                active = sched.active_slots()
        if (eng.preemption and adm is not None
                and adm.stalls > eng.preempt_patience):
            # chunk-admission grant stalled past patience: escalate to
            # the ladder (never the admission's own slot or a replayer)
            v = sched.preempt_victim(exclude=(adm.slot, *replaying()))
            if v is not None:
                spec_preempt(v)
                adm.stalls = 0
        if (eng.preemption and not eng.chunked_prefill and sched.pending):
            # admission retry sweep: a refused head may fit now, or may
            # claim a victim through the ladder
            for i in sched.free_slots():
                if not sched.pending or not admit_into(i, ladder=True):
                    break
            active = sched.active_slots()
        if (eng.audit_every and stats.rounds
                and stats.rounds % eng.audit_every == 0):
            eng._run_audit(sched, cache)
            if trace:
                trace.instant("audit", args=dict(round=stats.rounds))
        if not active:
            if sched.pending or adm is not None:
                if not eng.chunked_prefill:
                    for i in sched.free_slots():
                        admit_into(i)
                continue
            break

        # --- per-slot speculation depth (host mirrors, no device sync) --
        gam: Dict[int, int] = {}
        for s in active:
            if slot_state[s].replay:
                gam[s] = 0      # mid-resume: plain replay rounds only
                continue
            if slot_state[s].resumed:
                # first post-replay round: plain, so its one append is
                # inside the admission's resume reserve — guaranteed to
                # complete and commit the first new token
                slot_state[s].resumed = False
                gam[s] = 0
                continue
            st = sched.slot_request(s)
            remaining = st.max_new - len(slot_state[s].stream) + len(st.tokens)
            g = min(gamma,
                    tmirror.headroom_after_feeds(s, 1),
                    dmirror.headroom_after_feeds(
                        s, len(slot_state[s].stream) - slot_state[s].fed) + 1,
                    max(remaining - 1, 0))
            gam[s] = max(int(g), 0)

        # --- draft phase: chained decode_steps on the drafter cache ----
        drafts: Dict[int, List[int]] = {s: [] for s in active}
        participating = [s for s in active if gam[s] >= 1]
        while True:
            feed = np.zeros(eng.slots, np.int32)
            mask = np.zeros(eng.slots, bool)
            want_out = np.zeros(eng.slots, bool)
            for s in participating:
                st = slot_state[s]
                if st.fed < len(st.stream):
                    feed[s] = st.stream[st.fed]       # catch-up / chain head
                    mask[s] = True
                    want_out[s] = st.fed == len(st.stream) - 1
                elif len(drafts[s]) < gam[s]:
                    feed[s] = drafts[s][-1]
                    mask[s] = True
                    want_out[s] = True
            if not mask.any():
                break
            eng.key, kd = jax.random.split(eng.key)
            tok_dev, dcache = eng._draft_decode(
                eng.params, dcache, jnp.asarray(feed)[:, None],
                jnp.asarray(mask), kd)
            phases.to("fetch")
            # kvlint: ok(host-sync: draft tokens feed the host-built verify batch — draft rounds are synchronous by design)
            toks = np.asarray(tok_dev)
            phases.to(None)
            for s in participating:
                if not mask[s]:
                    continue
                st = slot_state[s]
                if st.fed < len(st.stream):
                    st.fed += 1
                dmirror.append(s, 1)
                if want_out[s] and len(drafts[s]) < gam[s]:
                    drafts[s].append(int(toks[s]))

        # --- lazy paged: cover the verify appends; starved slots fall
        # back to a plain step, then to an oom retire -------------------
        for s in list(active):
            if s not in active:     # preempted as an earlier slot's victim
                continue
            if grow_blocks_for(s, 1 + gam[s]):
                continue
            if gam[s] > 0 and grow_blocks_for(s, 1):
                gam[s] = 0
                continue
            gam[s] = 0
            # transient injected refusals: each retry is a fresh alloc
            granted = False
            for _ in range(eng.fail_patience):
                if grow_blocks_for(s, 1):
                    granted = True
                    break
            if not granted and eng.preemption:
                # the ladder: free victims' blocks until the grant fits
                while not granted:
                    v = sched.preempt_victim(exclude=(s, *replaying()))
                    if v is None:
                        break
                    spec_preempt(v)
                    if v in active:
                        active.remove(v)
                    gam.pop(v, None)
                    granted = grow_blocks_for(s, 1)
            if granted:
                continue
            if eng.preemption and (len(sched.active_slots()) > 1
                                   or sched.prefilling_slots()):
                # other work holds blocks that will free: requeue this
                # slot instead of failing it
                spec_preempt(s)
            else:
                sched.retire(s, "oom")
                reset_slot(s)
            active.remove(s)
            gam.pop(s, None)
        if not active:
            continue

        # --- all-plain round (every slot's depth cap is 0, e.g. a dense
        # compressed store at budget): the single-token decode jit is
        # the same computation as a valid_len=1 verify at a fraction of
        # the width — don't pay (gamma+1)x FLOPs to commit one token
        if all(gam[s] == 0 for s in active):
            # a pool-starved round may have downgraded gam AFTER the
            # draft phase: the drafter's phantom chain rows must roll
            # back here too (nothing was verified, nothing is kept)
            m_vec = np.zeros(eng.slots, np.int32)
            for s in active:
                m_vec[s] = max(len(drafts.get(s, ())) - 1, 0)
                dmirror.truncate(s, int(m_vec[s]))
            if m_vec.any():
                dcache = eng._truncate_draft(dcache, jnp.asarray(m_vec))
            feed = np.zeros(eng.slots, np.int32)
            for s in active:
                st = slot_state[s]
                # mid-resume: re-feed the next recorded token (its output
                # is a re-derivation, discarded); past the replay queue
                # the last stream token's output is the first new one
                feed[s] = st.replay[0] if st.replay else st.stream[-1]
            eng.key, kp = jax.random.split(eng.key)
            tok_dev, cache = eng._decode(eng.params, cache,
                                         jnp.asarray(feed)[:, None], kp)
            sched.note_decode_step()
            stats.rounds += 1
            if trace:
                trace.complete("round", it_t0,
                               args=dict(kind="plain", active=len(active)))
            phases.to("fetch")
            # kvlint: ok(host-sync: plain-decode fallback round — the token builds the next feed host-side)
            toks = np.asarray(tok_dev)
            phases.to(None)
            for s in active:
                st = slot_state[s]
                tmirror.append(s, 1)
                if st.replay:
                    st.replay.pop(0)    # replay row landed; output unused
                    continue
                stats.plain_steps += 1
                if record(s, int(toks[s])) and sched.pending \
                        and not eng.chunked_prefill:
                    for i in sched.free_slots():
                        if not sched.pending or not admit_into(i):
                            break
            continue

        # --- verify: one rectangular forward, commit + rollback inside -
        tokens = np.zeros((eng.slots, gamma + 1), np.int32)
        valid = np.zeros(eng.slots, np.int32)
        for s in active:
            st = slot_state[s]
            tokens[s, 0] = st.replay[0] if st.replay else st.stream[-1]
            for i, d in enumerate(drafts[s][:gam[s]]):
                tokens[s, 1 + i] = d
            valid[s] = 1 + min(gam[s], len(drafts[s]))
        eng.key, kv = jax.random.split(eng.key)
        y_dev, acc_dev, cache = eng._verify(
            eng.params, cache, jnp.asarray(tokens), jnp.asarray(valid), kv)
        sched.note_decode_step()
        stats.rounds += 1
        if trace:
            trace.complete("round", it_t0,
                           args=dict(kind="verify", active=len(active)))
        phases.to("fetch")
        # kvlint: ok(host-sync: verify results drive host-side acceptance mirroring — the round is synchronous by design)
        y = np.asarray(y_dev)
        # kvlint: ok(host-sync: verify results drive host-side acceptance mirroring — the round is synchronous by design)
        acc = np.asarray(acc_dev)
        phases.to(None)

        # device-side acceptance/rollback already happened inside
        # verify_step; mirror it host-side and roll the drafter back
        m_vec = np.zeros(eng.slots, np.int32)
        for s in active:
            g = int(valid[s]) - 1
            a = int(acc[s])
            tmirror.append(s, int(valid[s]))
            tmirror.truncate(s, g - a)
            # drafter rollback: drop draft-cache rows beyond the accepted
            # prefix. `fed_draft` counts chain rows the drafter actually
            # appended (drafts produced minus the last, which was never
            # fed) — NOT the verify depth: a pool-starved round may have
            # downgraded gam to 0 after drafting, and those phantom rows
            # must still be rolled back or every later catch-up feed
            # lands at shifted positions and acceptance collapses.
            st = slot_state[s]
            fed_draft = max(len(drafts.get(s, ())) - 1, 0)
            keep_draft = min(a, fed_draft)
            m_vec[s] = fed_draft - keep_draft
            dmirror.truncate(s, int(m_vec[s]))
            st.fed += keep_draft
            if g >= 1:
                stats.verify_steps += 1
                stats.drafted += g
                stats.accepted += a
            elif not st.replay:
                stats.plain_steps += 1
        if m_vec.any():
            dcache = eng._truncate_draft(dcache, jnp.asarray(m_vec))

        for s in active:
            g = int(valid[s]) - 1
            a = int(acc[s])
            st = slot_state[s]
            if st.replay:
                st.replay.pop(0)        # replay row committed (valid=1);
                continue                # the re-derived output is unused
            retired = False
            for i in range(a + 1):
                if g >= 1:
                    stats.committed += 1
                if record(s, int(y[s, i])):
                    retired = True
                    break
            if not retired:
                shrink_blocks_for(s)
            if retired or not sched.pending:
                continue
            if not eng.chunked_prefill:
                for i in sched.free_slots():
                    if not sched.pending or not admit_into(i):
                        break

    loop_s = time.perf_counter() - loop_t0
    decode_s = loop_s - (prefill_s - prefill_at_loop)
    if eng.paged:
        eng._run_audit(sched)    # every pool block accounted for, or raise
    return eng._continuous_result(
        sched, cache, prefill_s=prefill_s, decode_s=decode_s,
        decode_tokens=decode_tokens, loop_iters=n_iters, loop_s=loop_s,
        fetch_s=phases.totals.get("fetch", 0.0) - fetch_at_loop,
        spec_stats=stats)
