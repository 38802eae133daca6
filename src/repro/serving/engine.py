"""Batched serving engine with first-class cache compression.

Two decode disciplines over the same compiled model functions (static
shapes — TPU discipline):

  * **Wave-based** (`generate`): requests are grouped into waves of
    `slots` sequences of one `prompt_len` bucket; each wave is one
    compiled prefill + N compiled decode steps. Simple, but padded slots
    burn full decode steps, finished sequences cannot exit early, and
    slots are never reused across waves.

  * **Continuous** (`generate_continuous`): one persistent `slots`-wide
    stacked cache that requests are admitted into and retired from
    *individually*. Prompts are bucketed (one compiled prefill per bucket
    length), a finished sequence (EOS / max-new) frees its slot
    mid-decode via per-slot cache surgery (`core.cache.insert_request` /
    `reset_slot`), and the next queued request is prefilled straight into
    the freed batch position — no recompilation, no reallocation. This is
    what converts a compression policy's capacity win (more live
    sequences per byte) into throughput. With ``chunked_prefill=True``
    admissions stream their prompt in ``chunk_len``-token segments
    interleaved one bounded step per decode step (segment / compress /
    insert), so a long prompt never stalls resident slots' decode —
    with greedy token streams bit-identical to monolithic admission
    (the canonical mass fold in `nn.attention` plus the full-precision
    admission scratch in `nn.model` make the compressed cache the same
    bits either way). With ``paged=True`` the
    persistent cache is the block-table substrate (`core.paging`): one
    physical pool shared across slots, block-aware admission (a request
    is admitted only when the free list covers its budgeted length), and
    blocks recycled on retire — so short, compressed and full-precision
    requests charge the pool only what they use.

The compression policy is plumbed end-to-end either way: prompt
compression at prefill, budgeted eviction / quantized ring flushes at
decode, layer budgets from the policy's allocator. Reports the survey's
comparison axes: decode step time, logical + physical cache bytes,
compression ratio vs full cache, and (continuous) TTFT / per-token
latency / slot occupancy.
"""
from __future__ import annotations

import dataclasses
import math
import time
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import budgets as budgets_lib
from repro.core import cache as kvcache
from repro.core import paging as paging_lib
from repro.core.cache import CacheSpec, cache_logical_bytes_per_layer
from repro.core.policy import CompressionPolicy
from repro.nn import model as M
from repro.nn import moe as moe_lib
from repro.nn.attention import MASS_GROUP
from repro.obs import NULL_METRICS, NULL_TRACER, Phases
from repro.serving import cacheblend as cacheblend_lib
from repro.serving import prefix as prefix_lib
from repro.serving import sampler as sampler_lib
from repro.serving import speculative as spec_lib
from repro.serving.scheduler import Request, RequestResult, Scheduler
from repro.utils import tree_bytes


@dataclass
class GenerationResult:
    tokens: np.ndarray            # [n_requests, max_new]
    prefill_seconds: float
    decode_seconds: float
    decode_tokens_per_s: float
    cache_physical_bytes: int
    cache_logical_bytes: float
    full_cache_bytes: float
    compression_ratio: float
    policy_name: str


@dataclass
class ContinuousGenerationResult:
    results: List[RequestResult]  # sorted by uid; per-request tokens + latency
    prefill_seconds: float
    decode_seconds: float
    decode_steps: int
    decode_tokens: int            # useful tokens produced by decode steps
    decode_tokens_per_s: float
    occupancy: float              # mean active-slot fraction per decode step
    # the rest of each step's slots: mid-admission (PREFILLING), free
    # while requests waited (starved), free with the queue empty (drain);
    # the four shares sum to 1
    prefill_share: float
    starved_share: float
    drain_share: float
    ttft_mean_s: float
    cache_physical_bytes: int     # dense: resident slots-wide footprint;
                                  # paged: peak allocated-block + metadata
                                  # bytes (real pool usage, not reserve)
    cache_logical_bytes: float
    full_cache_bytes: float
    compression_ratio: float
    policy_name: str
    # the host loop: iterations, their wall seconds, and the seconds of
    # those spent blocked in token fetches (the rest is host self time)
    loop_iters: int = 0
    loop_seconds: float = 0.0
    fetch_seconds: float = 0.0
    # decode steps that updated a dense store in place
    # (`model.decode_in_place`); 0 for quantized, paged and speculative
    inplace_steps: int = 0
    # drop-free MoE routing over the run's prompt segments and the
    # decode rows of slots serving a request (MoE models only; empty
    # slots' rows are left out): assignments, experts_hit (experts with
    # at least one assignment, summed over layer calls), calls (MoE layer
    # calls) and max_expert_load (the busiest expert's assignments over
    # the mean, averaged over calls)
    moe: Optional[dict] = None
    latent_store_bytes: int = 0   # MLA: bytes of the dense latent store
    pool_blocks: int = 0          # paged runs only: reserved pool size,
    pool_block_bytes: int = 0     # bytes one block pins across layers,
    pool_peak_blocks: int = 0     # high-water allocated blocks
    spec: Optional[spec_lib.SpecStats] = None  # speculative runs only
    prefix: Optional[dict] = None  # prefix-sharing runs only: warm/cold
                                   # hits + prefill seconds, CoW copies,
                                   # near-hits, index churn
    tier: Optional[dict] = None    # tiering runs only: spill/fetch counts,
                                   # bytes moved, fetch stalls, host-tier
                                   # capacity + pressure-controller stats

    def tokens_for(self, uid: int) -> np.ndarray:
        for r in self.results:
            if r.uid == uid:
                return r.tokens
        raise KeyError(uid)

    def failed(self) -> List[RequestResult]:
        """Requests retired without being served (e.g. a paged pool too
        small for their budgeted length). Their completed peers' results
        are preserved alongside."""
        return [r for r in self.results if r.finish_reason == "failed"]

    def paged_bytes_per_seq(self, slots: int) -> float:
        """Physical bytes one live request pins under paging: its peak
        allocated blocks plus its share of the per-slot metadata. The
        single source of truth for capacity accounting (inverse of the
        `cache_physical_bytes = metadata + peak * block_bytes` report);
        meaningful for single-request paged runs."""
        blocks = self.pool_peak_blocks * self.pool_block_bytes
        return blocks + (self.cache_physical_bytes - blocks) / slots


@dataclass
class _ChunkedAdmission:
    """One in-flight chunked admission (at most one per engine loop):
    the PREFILLING slot, its device-side scratch, and the MASS_GROUP-
    aligned prompt segments still to stream."""
    slot: int
    uid: int                       # the admitted request's
    st: Any                        # M.PrefillState scratch (device)
    segs: List[np.ndarray]
    starts: List[int]
    key: Any
    total_blocks: int = 0          # paged: full grant target
    granted: int = 0
    next_i: int = 0
    last_logits: Any = None        # device logits of the last segment run
    pc: Any = None                 # finalized batch-1 cache awaiting insert
    restore_m: int = 0             # prefix rows restored from the index
    n_adopt: int = 0               # pool blocks adopted read-only
    direct: bool = False           # prefill-direct: segments write the pool
    blend: bool = False            # near-hit CacheBlend admission
    secs: float = 0.0              # accumulated prefill seconds
    stalls: int = 0                # consecutive refused block grants —
                                   # the preemption ladder's trigger


class Engine:
    def __init__(self, cfg, params, policy: CompressionPolicy, *,
                 prompt_len: Optional[int] = None, max_new: int,
                 slots: int = 4, buckets: Optional[Sequence[int]] = None,
                 sampler: Callable = sampler_lib.greedy,
                 allocator_signal: Optional[dict] = None, seed: int = 0,
                 use_kernels: Optional[bool] = None,
                 paged: bool = False, block_len: int = 16,
                 pool_blocks: Optional[int] = None,
                 chunked_prefill: bool = False, chunk_len: int = 64,
                 block_growth: str = "eager",
                 admission_order: str = "fifo",
                 speculative: bool = False, gamma: int = 4,
                 draft_policy: str = "window:64",
                 prefix_sharing: bool = False, near_hit: float = 0.0,
                 preemption: bool = False, preempt_patience: int = 2,
                 fail_patience: int = 3,
                 degrade: bool = False, degrade_high: float = 0.85,
                 degrade_low: float = 0.60, degrade_keep_groups: int = 2,
                 tiering: bool = False, host_blocks: Optional[int] = None,
                 fault_plan: Optional[paging_lib.FaultPlan] = None,
                 audit_every: int = 0,
                 preempt_at: Sequence[Sequence[int]] = (),
                 tracer=None, metrics=None):
        if prompt_len is None and not buckets:
            raise ValueError("need prompt_len and/or buckets")
        if use_kernels is not None:
            # fused Pallas decode/prefill vs the materialize oracle; None
            # keeps the config's auto policy (kernels on TPU only)
            cfg = dataclasses.replace(cfg, use_kernels=use_kernels)
        self.buckets = (tuple(sorted({int(b) for b in buckets}))
                        if buckets else (int(prompt_len),))
        if prompt_len is None:
            prompt_len = max(self.buckets)
        if max(self.buckets) > prompt_len:
            raise ValueError(f"bucket {max(self.buckets)} exceeds "
                             f"prompt_len {prompt_len}")
        self.cfg, self.params, self.policy = cfg, params, policy
        self.prompt_len, self.max_new, self.slots = prompt_len, max_new, slots
        self.sampler = sampler
        self.key = jax.random.key(seed)
        # observability (repro/obs): both default to falsy no-ops, so
        # every emit site below is one truthiness check when telemetry
        # is off. Zero-sync contract: only host-side values ever reach
        # the tracer/metrics — kvlint's host-sync rule enforces it.
        self.trace = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else NULL_METRICS

        spec = policy.spec
        if cfg.is_mla and spec.compressed:
            raise ValueError(
                f"policy {policy.name!r} compresses the cache, and a latent "
                f"(MLA) store supports no quantized or evicting policy: "
                f"serve it with the full policy")
        if cfg.is_mla and paged:
            raise ValueError("a paged latent (MLA) pool is not supported: "
                             "serve latent attention with paged=False")
        if not spec.compressed:
            # uncompressed baseline still needs decode headroom (sized for
            # the largest bucket so every bucket shares one cache shape)
            spec = CacheSpec(budget=prompt_len + max_new, policy="none",
                             sinks=spec.sinks)
        self.spec = spec

        # --- paged block-table cache (continuous batching only) ---------
        # One physical pool per layer + a per-slot block table; requests
        # only pin the blocks their budgeted length needs, and retired
        # blocks recycle through the free-list (core/paging.py). Default
        # pool sizing is capacity parity with the dense layout
        # (slots * S / block_len); size it smaller to realize the
        # capacity win (admission then refuses what doesn't fit).
        self.paged = bool(paged)
        self._S_phys = self.spec.main_store_len(prompt_len + max_new)
        self.block_len = paging_lib.resolve_block_len(
            self.spec, self._S_phys, block_len) if paged else 0
        self.n_max_blocks = (self._S_phys // self.block_len) if paged else 0
        self.pool_blocks = (
            int(pool_blocks) if (paged and pool_blocks)
            else slots * self.n_max_blocks if paged else 0)
        self.block_allocator: Optional[paging_lib.BlockAllocator] = None

        # --- lazy decode-block growth (paged + continuous only) ---------
        # Admission reserves only prompt coverage; decode blocks are
        # granted as `pos` crosses block boundaries (a speculative
        # rollback below a boundary returns blocks to the free list).
        # A slot whose growth the pool cannot cover retires "oom" —
        # admission control only guarantees prompt coverage, so an
        # over-committed pool surfaces as per-request oom, never as a
        # corrupted batch.
        if block_growth not in ("eager", "lazy"):
            raise ValueError(f"unknown block_growth {block_growth!r}")
        if block_growth == "lazy" and not paged:
            raise ValueError("block_growth='lazy' requires paged=True")
        self.lazy_blocks = block_growth == "lazy"
        self.admission_order = admission_order

        # --- KV tiering: host-RAM block tier under the pool -------------
        # A `paging.HostTier` holds spilled block payloads (async,
        # double-buffered device<->host copies; core/paging.py). Cold
        # sources, in ladder order: refcount-1 prefix-index blocks are
        # *demoted* instead of LRU-freed (warm hits survive pool churn,
        # paged back on adoption), stalled admissions' granted-but-
        # unwritten scratch blocks are stripped, and preempted slots
        # snapshot to host — restored on re-admission instead of
        # recomputed. Fetch always lands blocks device-resident before
        # attention, so kernels never see the tier.
        self.tiering = bool(tiering)
        if self.tiering and not self.paged:
            raise ValueError("tiering spills paged pool blocks; it "
                             "requires paged=True")
        if self.tiering and speculative:
            raise ValueError("tiering + speculative is unsupported (the "
                             "draft cache holds no block tables to spill)")
        if host_blocks is not None and not self.tiering:
            raise ValueError("host_blocks requires tiering=True")
        self.host_blocks = (int(host_blocks) if host_blocks
                            else self.pool_blocks if self.tiering else 0)
        self.host_tier: Optional[paging_lib.HostTier] = None
        self.tier_pressure = None
        self._tier_aux: dict = {}     # tier handle -> host mirror snapshots
        self._adm_live = None         # mid-advance cache (reclaim reads it)
        self._tier_stripped = 0       # stalled-admission grants reclaimed

        # --- chunked prefill (continuous batching only) -----------------
        # Long-prompt admissions stream in `chunk_len`-token segments
        # interleaved between decode steps, so resident slots keep
        # emitting tokens while a prompt loads (nn/model.py chunked-
        # prefill section). chunk_len snaps to the canonical mass group
        # so chunked and monolithic admissions fold attention mass in
        # the same association chain (bit-identical greedy streams).
        self.chunked_prefill = bool(chunked_prefill)

        # --- cross-request prefix sharing (paged + continuous only) -----
        # A radix index over the pool (serving/prefix.py) lets admissions
        # that share a prompt prefix map the same physical blocks read-
        # only (refcounted) and prefill only their suffix; a shared block
        # is un-shared copy-on-write the moment its slot would mutate it.
        # Sharing reuses the chunked-prefill machinery (suffix streaming
        # is a chunked prefill starting at a nonzero offset), so every
        # admission under sharing goes through it — streams stay
        # bit-identical per the chunked == monolithic contract.
        self.prefix_sharing = bool(prefix_sharing)
        self.near_hit = float(near_hit)
        if self.prefix_sharing:
            if not paged:
                raise ValueError("prefix_sharing requires paged=True")
            if speculative:
                raise ValueError(
                    "prefix_sharing + speculative is unsupported (the "
                    "draft cache holds no block tables to share)")
        if self.near_hit:
            if not self.prefix_sharing:
                raise ValueError("near_hit requires prefix_sharing=True")
            if not 0.0 < self.near_hit <= 1.0:
                raise ValueError(
                    f"near_hit is a recompute fraction in (0, 1], "
                    f"got {self.near_hit}")
        self._share_state: Optional[dict] = None  # live only during a
                                                  # sharing-enabled run

        self.chunk_len = 0
        if self.chunked_prefill or self.prefix_sharing:
            M._check_chunkable(cfg)
            self.chunk_len = max(MASS_GROUP,
                                 int(chunk_len) - int(chunk_len) % MASS_GROUP)
            bad = [b for b in self.buckets if b % MASS_GROUP]
            if bad:
                raise ValueError(
                    f"chunked prefill needs MASS_GROUP({MASS_GROUP})-"
                    f"aligned prompt buckets, got {bad}")

        n_attn = cfg.num_attn_layers()
        alloc = budgets_lib.ALLOCATORS[policy.allocator]
        kw = dict(policy.allocator_kwargs)
        kw.setdefault("multiple", spec.group if spec.quantized else 1)
        if policy.allocator == "squeeze":
            kw.setdefault("cos_sim", (allocator_signal or {}).get(
                "cos_sim", np.linspace(0.6, 0.95, n_attn)))
        if policy.allocator == "zigzag":
            kw.setdefault("uncertainty", (allocator_signal or {}).get(
                "uncertainty", np.ones(n_attn)))
        self.layer_budgets = np.minimum(
            alloc(n_attn, spec.budget, **kw),
            spec.main_store_len(prompt_len))

        # every program is a named function: the profiler's "XLA Modules"
        # line shows `jit__<name>`, which the benchmark's trace readers
        # match (`jit__step` is the decode step, `jit__chunk_step` a
        # prompt chunk)
        def _prefill(p, b, lb, k):
            return M.prefill(p, cfg, b, self.spec, layer_budgets=lb, key=k)

        self._prefill = jax.jit(_prefill)

        def _step(p, cache, tok, k, live=None):
            logits, cache = M.decode_step(p, cfg, cache, tok, self.spec,
                                          key=k, live=live)
            nxt = self.sampler(logits, k)
            return nxt, cache
        # donate the live cache through decode and slot surgery so XLA
        # aliases it in place instead of copying every leaf per step /
        # admission (donation is unimplemented on cpu and only warns there)
        dn = jax.default_backend() != "cpu"
        self._decode = jax.jit(_step, donate_argnums=(1,) if dn else ())
        self._live = None    # (slots, mask) of the last `_live_rows`

        # per-slot cache surgery (continuous batching): one compile each,
        # `slot` is a traced operand so every slot index reuses it
        # an insert also adds the prompt's MoE counters to the running ones
        def _insert(cache: M.ModelCache, pc: M.ModelCache, slot):
            attn = (kvcache.insert_request(cache.attn, slot, pc.attn,
                                           batch_axis=2)
                    if cache.attn is not None else None)
            ssm = (kvcache.insert_request_tree(cache.ssm, slot, pc.ssm,
                                              batch_axis=2)
                   if cache.ssm is not None else None)
            return cache._replace(attn=attn, ssm=ssm,
                                  moe=moe_lib.add_stats(cache.moe, pc.moe))

        def _insert_paged(cache: M.ModelCache, pc: M.ModelCache, slot, ids,
                          n_skip, *, pool_write: bool = True):
            # prefill always builds the dense batch-1 view; the insert
            # scatters its rows into the slot's freshly granted blocks.
            # `n_skip` leading table entries are adopted shared-prefix
            # blocks: the table maps them, the pool write skips them
            # (their rows are already resident and referenced elsewhere)
            attn = (paging_lib.insert_request_paged(
                        cache.attn, slot, pc.attn, ids, batch_axis=2,
                        n_skip=n_skip, pool_write=pool_write)
                    if cache.attn is not None else None)
            ssm = (kvcache.insert_request_tree(cache.ssm, slot, pc.ssm,
                                              batch_axis=2)
                   if cache.ssm is not None else None)
            return cache._replace(attn=attn, ssm=ssm,
                                  moe=moe_lib.add_stats(cache.moe, pc.moe))

        def _reset(cache: M.ModelCache, slot):
            if self.paged:
                attn = (paging_lib.reset_slot_paged(cache.attn, slot,
                                                    batch_axis=2)
                        if cache.attn is not None else None)
            else:
                attn = (kvcache.reset_slot(cache.attn, slot, batch_axis=2)
                        if cache.attn is not None else None)
            ssm = (kvcache.reset_slot_tree(cache.ssm, slot, batch_axis=2)
                   if cache.ssm is not None else None)
            return cache._replace(attn=attn, ssm=ssm)

        if self.paged:
            self._insert = jax.jit(_insert_paged,
                                   donate_argnums=(0,) if dn else ())
        else:
            self._insert = jax.jit(_insert, donate_argnums=(0,) if dn else ())
        self._reset = jax.jit(_reset, donate_argnums=(0,) if dn else ())

        if self.chunked_prefill or self.prefix_sharing:
            # one compile per segment *length* (the offset is traced):
            # <= 2 shapes per bucket (chunk_len + a ragged tail)
            def _chunk_step(p, st, toks, c0):
                return M.prefill_chunk(p, cfg, st, toks, c0, self.spec)

            def _finalize(st, lb2, k):
                return M.prefill_finalize(cfg, st, self.spec,
                                          layer_budgets=lb2, key=k)

            self._chunk_step = jax.jit(_chunk_step,
                                       donate_argnums=(1,) if dn else ())
            self._finalize = jax.jit(_finalize)

        if self.paged and (self.chunked_prefill or self.prefix_sharing):
            # prefill-direct (no-selection policies keep every prompt row
            # verbatim): each chunk's K/V rows stream straight into the
            # slot's granted pool blocks as they are computed, and the
            # insert writes metadata only — no end-of-prefill bulk scatter
            def _write_rows(c, rows, ks, vs):
                return c._replace(
                    attn=paging_lib.write_prefill_rows(c.attn, rows, ks, vs,
                                                       batch_axis=2))

            def _insert_meta(cache, pc, slot, ids, n_skip):
                return _insert_paged(cache, pc, slot, ids, n_skip,
                                     pool_write=False)

            def _finalize_meta(st, lb2):
                return M.prefill_finalize_meta(cfg, st, self.spec,
                                               layer_budgets=lb2)

            self._write_rows = jax.jit(_write_rows,
                                       donate_argnums=(0,) if dn else ())
            self._insert_meta = jax.jit(_insert_meta,
                                        donate_argnums=(0,) if dn else ())
            self._finalize_meta = jax.jit(_finalize_meta)

        if self.paged and self.prefix_sharing:
            # copy-on-write un-share: duplicate the rows of the adopted
            # blocks into the slot's fresh exclusive blocks (the table
            # rewrite itself reuses `_grow_tbl` at offset 0)
            def _copy_blocks(c, src, dst):
                return c._replace(
                    attn=paging_lib.copy_pool_blocks(c.attn, src, dst,
                                                     batch_axis=2))

            self._copy_blocks = jax.jit(_copy_blocks,
                                        donate_argnums=(0,) if dn else ())

        if self.paged and self.tiering:
            # device halves of the tier's swap path. The gathers are NOT
            # donated (the live cache survives a spill); the scatters
            # are (a fetch rewrites the pool in place). Payloads round-
            # trip host RAM bit-identically — pools hold integer codes /
            # raw floats, nothing is re-encoded on either copy.
            def _gather_blocks(c, ids):
                return paging_lib.gather_pool_blocks(c.attn, ids,
                                                     batch_axis=2)

            def _scatter_blocks(c, ids, payload):
                return c._replace(
                    attn=paging_lib.scatter_pool_blocks(c.attn, ids, payload,
                                                        batch_axis=2))

            def _gather_meta(c, slot):
                return paging_lib.gather_slot_meta(c.attn, slot,
                                                   batch_axis=2)

            def _restore_meta(c, slot, payload):
                return c._replace(
                    attn=paging_lib.scatter_slot_meta(c.attn, slot, payload,
                                                      batch_axis=2))

            # kvlint: ok(jit-donate: spill gather is read-only — the live cache must survive until the host copy lands)
            self._gather_blocks = jax.jit(_gather_blocks)
            self._scatter_blocks = jax.jit(_scatter_blocks,
                                           donate_argnums=(0,) if dn else ())
            # kvlint: ok(jit-donate: spill gather is read-only — the live cache must survive until the host copy lands)
            self._gather_meta = jax.jit(_gather_meta)
            self._restore_meta = jax.jit(_restore_meta,
                                         donate_argnums=(0,) if dn else ())

        if self.paged and (self.lazy_blocks or self.prefix_sharing
                           or self.tiering):
            # device half of lazy growth/rollback: write freshly granted
            # ids into a slot's table row / unmap released entries
            def _grow_tbl(c, slot, j0, ids):
                return c._replace(
                    attn=paging_lib.write_block_table(c.attn, slot, j0, ids,
                                                      batch_axis=2))

            def _clear_tbl(c, slot, j0):
                return c._replace(
                    attn=paging_lib.clear_block_table_from(c.attn, slot, j0,
                                                           batch_axis=2))

            self._grow_tbl = jax.jit(_grow_tbl,
                                     donate_argnums=(0,) if dn else ())
            self._clear_tbl = jax.jit(_clear_tbl,
                                      donate_argnums=(0,) if dn else ())

        # --- speculative decoding (continuous only) ---------------------
        # Draft/verify loop in serving/speculative.py: a second cache
        # over the same weights drafts against a cheap view; the verify
        # step scores the whole segment against the real cache in one
        # rectangular forward, committing via append_segment and rolling
        # rejects back via truncate_rows.
        self.speculative = bool(speculative)
        self.gamma = int(gamma)
        if self.speculative:
            if gamma < 1:
                raise ValueError(f"gamma must be >= 1, got {gamma}")
            if sampler is not sampler_lib.greedy:
                raise ValueError(
                    "speculative decoding requires the greedy sampler "
                    "(acceptance is exact match-and-truncate under argmax)")
            M._check_speculable(cfg)
            self.draft = spec_lib.resolve_draft_policy(
                draft_policy, cfg, self.spec, prompt_len, max_new)
            dS = self.draft.spec.main_store_len(prompt_len + max_new)
            self.draft_layer_budgets = np.minimum(
                budgets_lib.ALLOCATORS["uniform"](
                    n_attn, self.draft.spec.budget or dS,
                    multiple=(self.draft.spec.group
                              if self.draft.spec.quantized else 1)),
                dS)
            dcfg, dspec = self.draft.cfg, self.draft.spec

            def _verify(p, c, toks, vl, k):
                return M.verify_step(p, cfg, c, toks, vl, self.spec, key=k)

            def _draft_prefill(p, b, lb2, k):
                return M.prefill(p, dcfg, b, dspec, layer_budgets=lb2,
                                 key=k)

            self._verify = jax.jit(_verify, donate_argnums=(1,) if dn else ())
            self._draft_prefill = jax.jit(_draft_prefill)

            def _dstep(p, dc, tok, mask, k):
                logits, dc = M.decode_step(p, dcfg, dc, tok, dspec, key=k,
                                           append_mask=mask)
                return jnp.argmax(logits, -1).astype(jnp.int32), dc

            self._draft_decode = jax.jit(
                _dstep, donate_argnums=(1,) if dn else ())

            def _insert_draft(dc, pc, slot):
                return dc._replace(
                    attn=kvcache.insert_request(dc.attn, slot, pc.attn,
                                                batch_axis=2))

            def _reset_draft(dc, slot):
                return dc._replace(
                    attn=kvcache.reset_slot(dc.attn, slot, batch_axis=2))

            def _truncate_draft(dc, m):
                return dc._replace(
                    attn=kvcache.truncate_rows(dc.attn, dspec, m))

            self._insert_draft = jax.jit(_insert_draft,
                                         donate_argnums=(0,) if dn else ())
            self._reset_draft = jax.jit(_reset_draft,
                                        donate_argnums=(0,) if dn else ())
            self._truncate_draft = jax.jit(_truncate_draft,
                                           donate_argnums=(0,) if dn else ())

        # --- overload ladder: degrade -> preempt -> fail ----------------
        # Preemption: when an admission or a lazy-growth boundary can't
        # get blocks, evict the lowest-progress resident slot (through
        # `Scheduler.preempt`) and requeue it as a continuation — its
        # re-admission re-prefills the prompt and *replays* the emitted
        # tokens through the normal decode path, so resumed greedy
        # streams are bit-identical to unpreempted runs. `preempt_at`
        # ((step, slot) pairs) forces preemptions deterministically for
        # that bit-identity test. Degradation (PressureController in
        # serving/adaptive.py) sits below preemption: above a high-water
        # mark resident quantized slots are evicted down first
        # (`paging.degrade_slot_groups`). `fault_plan` + `audit_every`
        # are the proof harness: injected allocator faults, and
        # allocator-vs-table-vs-index invariant audits during the run.
        self.preempt_at = tuple((int(k), int(s)) for k, s in preempt_at)
        self.preemption = bool(preemption) or bool(self.preempt_at)
        self.preempt_patience = int(preempt_patience)
        self.fail_patience = max(int(fail_patience), 1)
        self.fault_plan = fault_plan
        self.audit_every = int(audit_every)
        self.last_audit: Optional[dict] = None
        if fault_plan is not None and not self.paged:
            raise ValueError("fault_plan injects BlockAllocator faults; "
                             "it requires paged=True")
        if self.audit_every and not self.paged:
            raise ValueError("audit_every audits the paged pool; it "
                             "requires paged=True")
        self.pressure = None
        if degrade:
            if not (self.paged and self.lazy_blocks):
                raise ValueError(
                    "degrade requires paged=True with block_growth="
                    "'lazy': lazy growth grants a block before every "
                    "dispatch, which is what guarantees a post-degrade "
                    "ring flush always lands in a mapped table entry")
            if not self.spec.quantized or self.spec.track_scores():
                raise ValueError(
                    "degrade drops whole flushed groups of a quantized "
                    "streaming store (kivi*); score-carrying or "
                    "unquantized policies have no group structure to "
                    "evict down")
            if self.speculative:
                raise ValueError(
                    "degrade + speculative is unsupported (the drafter's "
                    "host mirror cannot track pressure evictions)")
            # adaptive.py imports Engine at module level; import the
            # controller lazily to keep the cycle one-directional
            from repro.serving.adaptive import PressureController
            self.pressure = PressureController(
                high_water=degrade_high, low_water=degrade_low,
                keep_groups=degrade_keep_groups, tracer=self.trace)

            def _degrade(c, slot, n):
                return c._replace(
                    attn=paging_lib.degrade_slot_groups(
                        c.attn, self.spec, slot, n, batch_axis=2))

            self._degrade_op = jax.jit(_degrade,
                                       donate_argnums=(0,) if dn else ())

    # ------------------------------------------------------------------
    def _run_audit(self, sched, cache=None) -> dict:
        """Pool invariant audit (`core.paging.audit_pool`): allocator
        refcounts vs every occupied slot's grant list vs the prefix
        index; passing `cache` adds the device block-table cross-check
        for active slots. Raises `PoolAuditError` on any violation; the
        report lands on `self.last_audit` for post-run inspection."""
        index_blocks = ()
        if self._share_state is not None:
            index_blocks = self._share_state["index"].block_ids()
        tier_holders: List[int] = []
        if self.host_tier is not None:
            if self._share_state is not None:
                tier_holders += self._share_state["index"].host_handles()
            tier_holders += sched.queued_tickets()
        report = paging_lib.audit_pool(
            self.block_allocator, sched.occupied_blocks(), index_blocks,
            block_tbl=(cache.attn.block_tbl if cache is not None else None),
            tbl_slots=sched.active_slots(),
            host_tier=self.host_tier, tier_holders=tier_holders)
        self.last_audit = report
        return report

    # ------------------------------------------------------------------
    def _request_blocks(self, req: Request) -> int:
        """Pool blocks an admission must reserve. Eager growth covers
        the request's whole budgeted length (prompt + decode headroom +
        quantization slack); lazy growth covers only the prompt — decode
        blocks are granted as `pos` advances. Under preemption, lazy
        admission additionally covers the continuation's replay rows
        plus the first new append: a resumed slot must never starve
        mid-replay (a mid-replay self-preempt discards the recompute
        and commits nothing — with two such slots trading the pool the
        loop never converges), and covering one row past the prefix
        guarantees every resume commits >= 1 new token before it can be
        preempted again."""
        if self.lazy_blocks:
            rows = len(req.tokens) + len(req.emitted_prefix)
            if self.preemption:
                rows += 1
            base = paging_lib.request_blocks_prefix(
                self.spec, self._S_phys, rows, self.block_len)
        else:
            base = paging_lib.request_blocks(
                self.spec, self._S_phys, len(req.tokens), req.max_new,
                self.block_len)
        if req.tier_ticket is not None:
            # a spill-preempted continuation restores its snapshot into
            # freshly granted ids — the grant must cover the snapshot
            # AND the recompute path (a refused fetch falls back to
            # replay, which needs its normal coverage)
            return max(req.tier_blocks, base)
        return base

    def _drop_ticket(self, req: Request) -> None:
        """Abandon a queued continuation's host snapshot; it will resume
        by recompute-on-resume replay instead."""
        if req.tier_ticket is not None and self.host_tier is not None:
            self.host_tier.drop(req.tier_ticket)
            self._tier_aux.pop(req.tier_ticket, None)
            req.tier_ticket = None
            req.tier_blocks = 0

    # ------------------------------------------------------------------
    # Prefix sharing: eligibility + host-side copy-on-write trigger
    # ------------------------------------------------------------------
    def _share_retained(self, bucket: int) -> int:
        """Leading prompt rows of a `bucket`-length admission whose final
        cache rows are *blockwise deterministic and position-ordered* —
        the shareable prefix. Position order is what lets pool block b be
        mapped verbatim by any request whose tokens agree on rows
        [b*block_len, (b+1)*block_len). Returns 0 when this spec cannot
        share: score-carrying eviction (h2o/nacl/keyformer) orders rows
        data-dependently, and a budget too small to retain the whole
        pre-window prompt drops rows mid-prefix."""
        spec = self.spec
        if spec.policy not in ("none", "streaming") or spec.track_scores():
            return 0
        min_lb = int(np.min(self.layer_budgets))
        if spec.window == 0:
            # verbatim prefill branch: every prompt row kept in place
            if spec.quantized:
                return 0
            ok = spec.main_store_len(bucket) >= bucket and min_lb >= bucket
            return bucket if ok else 0
        # streaming selection: rows [0, bucket-window) land position-
        # ordered in the main store when the store covers them all
        # (earliest-index top-k tie-break; see tests/test_prefix.py)
        n_main = bucket - spec.window
        if n_main <= 0 or spec.main_store_len(bucket) < n_main:
            return 0
        cap = ((min_lb // spec.group) * spec.group if spec.quantized
               else min_lb)
        return n_main if cap >= n_main else 0

    def _verbatim_ok(self, bucket: int) -> bool:
        """True when prefill keeps every prompt row verbatim (no
        selection, no quantization, no ring) — the prefill-direct case:
        chunk K/V rows can stream straight into pool blocks and the
        insert writes metadata only (`prefill_finalize_meta`)."""
        s = self.spec
        return (not s.quantized and s.window == 0
                and s.main_store_len(bucket) >= bucket)

    def _cow_due(self, mirror, slot: int) -> bool:
        """Host-side trigger: could this slot's next append mutate rows
        below its adopted shared prefix? Appends and non-evicting ring
        flushes only ever write at/above the slot's own length — past
        the adopted coverage by construction — so the only mutation that
        can reach a shared block is an evict-at-cap flush. Quantized
        rings flush nothing until the ring is full."""
        if self.spec.quantized and int(mirror.rlen[slot]) < self.spec.window:
            return False
        return bool(np.any(mirror.length[slot] >= mirror.cap_rows))

    # ------------------------------------------------------------------
    def _logical_bytes_per_seq(self) -> float:
        """Per-sequence logical cache bytes under the layer budgets."""
        n = self.prompt_len + self.max_new
        if self.cfg.is_mla:       # one bf16 latent row a position
            per = self.spec.main_store_len(n) * self.cfg.latent_dim * 2
        else:
            per = cache_logical_bytes_per_layer(
                self.spec, n, self.cfg.num_kv_heads, self.cfg.head_dim)
        return sum(per * (lb / max(self.spec.budget, 1))
                   for lb in self.layer_budgets)

    # ------------------------------------------------------------------
    def generate(self, prompts: np.ndarray,
                 src_embeds: Optional[np.ndarray] = None) -> GenerationResult:
        """prompts: [n, prompt_len] int32 (exact bucket length)."""
        if self.paged:
            raise ValueError(
                "the wave path decodes straight off the prefill cache "
                "(dense by construction); build a dense engine for "
                "generate(), paged applies to generate_continuous()")
        if self.speculative:
            raise ValueError(
                "speculative decoding lives in the continuous engine "
                "(per-slot draft state); use generate_continuous()")
        n, L = prompts.shape
        assert L == self.prompt_len, (L, self.prompt_len)
        outs = np.zeros((n, self.max_new), np.int32)
        prefill_s = decode_s = 0.0
        phys = logical = 0.0

        for w0 in range(0, n, self.slots):
            w1 = min(w0 + self.slots, n)
            wave = prompts[w0:w1]
            pad = self.slots - (w1 - w0)
            if pad:
                wave = np.concatenate([wave, np.repeat(wave[-1:], pad, 0)], 0)
            batch = {"tokens": jnp.asarray(wave)}
            if self.cfg.is_encoder_decoder:
                se = (src_embeds[w0:w1] if src_embeds is not None else
                      np.zeros((w1 - w0, max(L // 4, 16), self.cfg.d_model),
                               np.float32))
                if pad:
                    se = np.concatenate([se, np.repeat(se[-1:], pad, 0)], 0)
                batch["src_embeds"] = jnp.asarray(se)

            self.key, k1 = jax.random.split(self.key)
            with self.trace.span("wave_prefill",
                                 args=dict(wave=w0 // self.slots)) as sp:
                logits, cache = self._prefill(
                    self.params, batch, jnp.asarray(self.layer_budgets), k1)
                # kvlint: ok(host-sync: prefill timing fence — once per wave, before the decode loop starts)
                logits.block_until_ready()
            prefill_s += sp.elapsed

            tok = self.sampler(logits, k1)[:, None]
            # kvlint: ok(host-sync: first-token fetch off the prefill — once per wave, not per step)
            outs[w0:w1, 0] = np.asarray(tok)[: w1 - w0, 0]
            sp = self.trace.span("wave_decode",
                                 args=dict(wave=w0 // self.slots))
            sp.__enter__()
            # Double-buffered decode (same discipline as the continuous
            # path): step t+1 is dispatched from step t's device-side
            # tokens before the host fetches step t, so the per-step
            # host sync pipelines behind the next dispatch instead of
            # serializing every step. Token streams are unchanged — the
            # compute chain is identical, only the fetch moves.
            pend_tok = None
            pend_t = 0
            for t in range(1, self.max_new):
                self.key, k2 = jax.random.split(self.key)
                tok_dev, cache = self._decode(
                    self.params, cache, tok, k2,
                    self._live_rows(range(w1 - w0)))
                tok = tok_dev[:, None]
                if pend_tok is not None:
                    # kvlint: ok(host-sync: the pipelined fetch — step t-1's tokens land behind step t's dispatch)
                    outs[w0:w1, pend_t] = np.asarray(pend_tok)[: w1 - w0]
                pend_tok, pend_t = tok_dev, t
            if pend_tok is not None:
                # kvlint: ok(host-sync: loop epilogue — drains the final pending token once per wave)
                outs[w0:w1, pend_t] = np.asarray(pend_tok)[: w1 - w0]
            # kvlint: ok(host-sync: decode timing fence — once per wave, after the loop exits)
            jax.block_until_ready(cache)
            sp.__exit__()
            decode_s += sp.elapsed
            # accumulate across waves, normalized to the wave's *real*
            # request count (a padded final wave must not bill phantom
            # sequences at `slots` each)
            active = w1 - w0
            phys += tree_bytes(cache) * active / self.slots
            logical += self._logical_bytes_per_seq() * active
        full = (self.cfg.kv_bytes_per_token() *
                (self.prompt_len + self.max_new) * n)
        total_decode_tokens = n * (self.max_new - 1)
        return GenerationResult(
            tokens=outs,
            prefill_seconds=prefill_s,
            decode_seconds=decode_s,
            decode_tokens_per_s=total_decode_tokens / max(decode_s, 1e-9),
            cache_physical_bytes=int(phys),
            cache_logical_bytes=float(logical),
            full_cache_bytes=float(full),
            compression_ratio=float(full / max(logical, 1.0)),
            policy_name=self.policy.name,
        )

    # ------------------------------------------------------------------
    def _continuous_result(self, sched, cache, *, prefill_s: float,
                           decode_s: float, decode_tokens: int,
                           loop_iters: int, loop_s: float, fetch_s: float,
                           inplace_steps: int = 0,
                           spec_stats=None) -> "ContinuousGenerationResult":
        """Post-run accounting shared by the plain and speculative
        continuous loops (bytes, ratios, latency aggregates) — one copy
        so the spec-vs-plain comparisons the benchmark asserts on can
        never drift apart."""
        if self.paged:
            # real pool usage, not the reserved worst case: bytes of the
            # blocks the run actually pinned at its high-water mark,
            # plus the dense metadata/ring leaves
            per_block = paging_lib.bytes_per_block(cache.attn)
            meta = tree_bytes(cache) - paging_lib.pool_bytes(cache.attn)
            peak = self.block_allocator.peak_used
            phys = meta + peak * per_block
            pool_stats = dict(pool_blocks=self.pool_blocks,
                              pool_block_bytes=per_block,
                              pool_peak_blocks=peak)
        else:
            phys = tree_bytes(cache)
            pool_stats = {}
        logical = self._logical_bytes_per_seq() * self.slots
        full = (self.cfg.kv_bytes_per_token() *
                (self.prompt_len + self.max_new) * self.slots)
        results = sorted(sched.results, key=lambda r: r.uid)
        ttfts = [r.ttft_s for r in results if r.finish_reason != "failed"]
        prefix_stats = None
        if self._share_state is not None:
            prefix_stats = dict(self._share_state["stats"])
            prefix_stats["index_blocks"] = len(self._share_state["index"])
        tier_stats = None
        if self.host_tier is not None:
            tier_stats = dict(self.host_tier.stats)
            tier_stats.update(
                host_blocks=self.host_tier.capacity_blocks,
                host_entries=len(self.host_tier.handles()),
                host_resident=self.host_tier.resident_blocks,
                n_spills=sched.n_spills, n_fetches=sched.n_fetches,
                bytes_moved=sched.bytes_moved,
                fetch_stall_s=sched.fetch_stall_s,
                grants_stripped=self._tier_stripped,
                # transport compression: what one block costs to move vs
                # what it would cost as fp16 (the offload baseline)
                block_bytes=paging_lib.bytes_per_block(cache.attn),
                fp16_block_bytes=paging_lib.block_fp16_bytes(
                    cache.attn, self.spec))
            if self.tier_pressure is not None:
                tier_stats["pressure"] = dict(self.tier_pressure.stats)
        moe = None
        if cache.moe is not None:
            st = jax.device_get(cache.moe)      # once, at run end
            moe = dict(assignments=int(st.assignments),
                       experts_hit=int(st.experts_hit),
                       calls=int(st.calls),
                       max_expert_load=float(st.load_ratio)
                       / max(int(st.calls), 1))
        latent = (int(cache.attn.k.nbytes)
                  if cache.attn is not None and kvcache.is_latent(cache.attn)
                  else 0)
        res = ContinuousGenerationResult(
            results=results,
            prefill_seconds=prefill_s,
            decode_seconds=decode_s,
            decode_steps=sched.decode_steps,
            decode_tokens=decode_tokens,
            decode_tokens_per_s=decode_tokens / max(decode_s, 1e-9),
            occupancy=sched.occupancy,
            prefill_share=sched.prefill_share,
            starved_share=sched.starved_share,
            drain_share=sched.drain_share,
            ttft_mean_s=float(np.mean(ttfts)) if ttfts else 0.0,
            cache_physical_bytes=int(phys),
            cache_logical_bytes=float(logical),
            full_cache_bytes=float(full),
            compression_ratio=float(full / max(logical, 1.0)),
            policy_name=self.policy.name,
            loop_iters=loop_iters,
            loop_seconds=loop_s,
            fetch_seconds=fetch_s,
            inplace_steps=inplace_steps,
            moe=moe,
            latent_store_bytes=latent,
            spec=spec_stats,
            prefix=prefix_stats,
            tier=tier_stats,
            **pool_stats,
        )
        self._publish_metrics(sched, res)
        return res

    def _live_rows(self, slots):
        """[slots] bool on the device, True at the slots a decode step
        serves, for the MoE counters to count those rows alone; kept
        until the set changes. None for a dense model (nothing reads
        it, so its decode program takes no such operand)."""
        if not self.cfg.is_moe:
            return None
        got = tuple(sorted(slots))
        if self._live is None or self._live[0] != got:
            m = np.zeros(self.slots, bool)
            m[list(got)] = True
            self._live = (got, jnp.asarray(m))
        return self._live[1]

    def _publish_metrics(self, sched, res) -> None:
        """End-of-run aggregates into the metrics registry (no-op under
        the default `NULL_METRICS`). Gauges carry run-level rates,
        counters event totals, histograms per-request latency
        distributions — the snapshot `serve.py --metrics-json`
        serializes."""
        mx = self.metrics
        if not mx:
            return
        mx.gauge("run.prefill_s").set(res.prefill_seconds)
        mx.gauge("run.decode_s").set(res.decode_seconds)
        mx.gauge("run.decode_tok_s").set(res.decode_tokens_per_s)
        mx.gauge("run.occupancy").set(res.occupancy)
        mx.gauge("run.ttft_mean_s").set(res.ttft_mean_s)
        mx.gauge("run.compression_ratio").set(res.compression_ratio)
        mx.gauge("cache.physical_bytes").set(res.cache_physical_bytes)
        mx.gauge("cache.logical_bytes").set(res.cache_logical_bytes)
        mx.counter("engine.decode_steps").inc(res.decode_steps)
        mx.counter("engine.decode_tokens").inc(res.decode_tokens)
        mx.counter("engine.loop_iters").inc(res.loop_iters)
        mx.counter("decode.inplace_steps").inc(res.inplace_steps)
        if res.moe is not None:
            mx.counter("moe.assignments").inc(res.moe["assignments"])
            mx.counter("moe.experts_hit").inc(res.moe["experts_hit"])
            mx.counter("moe.calls").inc(res.moe["calls"])
            mx.gauge("moe.max_expert_load").set(res.moe["max_expert_load"])
        if res.latent_store_bytes:
            mx.gauge("mla.latent_store_bytes").set(res.latent_store_bytes)
        mx.counter("slots.prefill_steps").inc(sched.prefill_slot_steps)
        mx.counter("slots.starved_steps").inc(sched.starved_slot_steps)
        mx.counter("slots.drain_steps").inc(sched.drain_slot_steps)
        mx.counter("sched.preemptions").inc(sched.n_preemptions)
        mx.counter("sched.retries").inc(sched.n_retries)
        h_ttft = mx.histogram("request.ttft_s")
        h_gap = mx.histogram("request.inter_token_s")
        n_done = n_failed = 0
        for r in res.results:
            if r.finish_reason == "failed":
                n_failed += 1
                continue
            n_done += 1
            h_ttft.observe(r.ttft_s)
            for gap in np.diff(r.token_times):
                h_gap.observe(float(gap))
        mx.counter("requests.completed").inc(n_done)
        mx.counter("requests.failed").inc(n_failed)
        if res.tier is not None:
            mx.counter("tier.spills").inc(res.tier["n_spills"])
            mx.counter("tier.fetches").inc(res.tier["n_fetches"])
            mx.counter("tier.bytes_moved").inc(res.tier["bytes_moved"])
            mx.gauge("tier.fetch_stall_s").set(res.tier["fetch_stall_s"])
        if self.pressure is not None:
            mx.counter("pressure.degrades").inc(
                self.pressure.stats["degrades"])
            mx.counter("pressure.blocks_dropped").inc(
                self.pressure.stats["blocks_dropped"])
        if res.prefix is not None:
            mx.counter("prefix.warm_hits").inc(res.prefix["warm_hits"])
            mx.counter("prefix.cold").inc(res.prefix["cold"])
            mx.counter("prefix.near_hits").inc(res.prefix["near_hits"])
            mx.counter("prefix.cow_copies").inc(res.prefix["cow_copies"])
        if res.spec is not None:
            mx.gauge("spec.accept_rate").set(res.spec.acceptance_rate)
            mx.counter("spec.rounds").inc(res.spec.rounds)

    # ------------------------------------------------------------------
    # Chunked admission (shared by the plain continuous loop and the
    # speculative loop): at most one admission in flight, advanced one
    # bounded step — a prompt segment, the compress, or the insert —
    # per decode step, so a long prompt never stalls resident decode.
    # ------------------------------------------------------------------
    def _start_admission_timed(self, sched):
        """Start a chunked admission under the prefill timing seam.
        Both continuous loops route through this: the start step can do
        real prefill work (a scratch restore, or a full CacheBlend
        forward for a near-hit), so its seconds belong to ``prefill_s``
        — before this seam the plain loop silently billed blend
        admissions to decode while the speculative loop (which never
        blends) did not, so the two loops' reported decode seconds were
        not comparable. Returns (admission-or-None, seconds)."""
        t0 = time.perf_counter()
        adm = self._start_chunked_admission(sched)
        return adm, time.perf_counter() - t0

    def _start_chunked_admission(self, sched) -> Optional[_ChunkedAdmission]:
        """Begin a chunked admission into the first free slot; heads
        that can never fit the pool fail immediately. Under prefix
        sharing the admission first consults the radix index: an exact
        block-aligned prefix hit adopts the matched pool blocks read-only
        and streams only the suffix; a near-hit (same template, edited
        middle) routes through CacheBlend's selective recompute."""
        share = self._share_state
        while sched.pending:
            free = sched.free_slots()
            if not free:
                return None
            req = sched.head_request()
            if self.host_tier is not None and req.tier_ticket is not None:
                # a spill-preempted continuation is restored by the
                # loop-top ticket path, never streamed through chunked
                # admission; later requests stay FIFO-blocked behind it
                return None
            total = self._request_blocks(req) if self.paged else 0
            if self.paged and total > self.pool_blocks:
                sched.fail_head()
                continue
            slot = free[0]
            self.key, k1 = jax.random.split(self.key)
            L = len(req.tokens)
            C = self.chunk_len
            m = 0
            adopt_ids: List[int] = []
            pieces: List[tuple] = []
            if share is not None and self._share_retained(L):
                ids, pcs = share["index"].match(req.tokens)
                m_exact = len(ids) * self.block_len
                if (share["near_ok"] and m_exact * 2 < L
                        and share["index"].near_overlap(req.tokens) >= 0.8):
                    adm = self._start_blend_admission(
                        sched, slot, req, total, k1, m_exact)
                    if adm is not None:
                        return adm
                # restore length: full matched blocks, snapped down to the
                # resume alignment (chunked prefill folds attention mass
                # per MASS_GROUP), capped so >= 1 suffix token remains to
                # produce the first-token logits
                m = min(m_exact, L - 1)
                m -= m % share["align"]
                if m > 0:
                    retained = self._share_retained(L)
                    n_adopt = min(m // self.block_len,
                                  retained // self.block_len)
                    adopt_ids = ids[:n_adopt]
                    pieces = pcs[:m // self.block_len]
            sched.begin_prefill(slot)
            if adopt_ids:
                sched.adopt_blocks(slot, adopt_ids)
            if m > 0:
                st = self._restore_scratch(L, m, pieces)
                starts = list(range(m, L, C))
            else:
                st = M.init_prefill_state(self.cfg, L)
                starts = list(range(0, L, C))
            adm = _ChunkedAdmission(
                slot=slot, uid=req.uid, st=st,
                segs=[req.tokens[s:s + C] for s in starts],
                starts=starts, key=k1, total_blocks=total,
                granted=len(adopt_ids), restore_m=m,
                n_adopt=len(adopt_ids))
            adm.direct = self.paged and self._verbatim_ok(L)
            return adm
        return None

    def _start_blend_admission(self, sched, slot, req, total, k1,
                               m_exact: int):
        """Near-hit admission: CacheBlend recomputes only the high-
        KV-deviation tokens past the exact prefix and reuses the rest
        from a full forward's cheap substitute (serving/cacheblend.py),
        then the K/V tensors are compressed into a regular batch-1 cache
        (`prefill_from_kv`). Approximate for recompute_frac < 1, so the
        result is never ingested into the index. Returns None when the
        exact prefix is too short to anchor the blend."""
        if m_exact < self.block_len:
            return None
        with self.trace.span("blend_prefill", tid=slot + 1,
                             args=dict(uid=req.uid, m=m_exact)) as sp:
            logits, (ks, vs), _ = cacheblend_lib.blend_prefill(
                self.params, self.cfg, jnp.asarray(req.tokens[None]),
                [0, m_exact], recompute_frac=self.near_hit)
            pc = M.prefill_from_kv(
                self.cfg, self.spec, ks, vs,
                layer_budgets=jnp.asarray(self.layer_budgets), key=k1)
            sched.begin_prefill(slot)
            adm = _ChunkedAdmission(
                slot=slot, uid=req.uid, st=None, segs=[], starts=[], key=k1,
                total_blocks=total, next_i=1, last_logits=logits, pc=pc,
                blend=True)
        adm.secs = sp.elapsed
        self._share_state["stats"]["near_hits"] += 1
        if self.trace:
            self.trace.instant("prefix_near_hit", tid=slot + 1,
                               args=dict(uid=req.uid))
        return adm

    def _restore_scratch(self, L: int, m: int, pieces) -> M.PrefillState:
        """Rebuild a prefill scratch whose first `m` rows are the indexed
        prefix's host pieces — block b of K/V rows + attention mass —
        so `prefill_chunk` can resume at offset m with only the suffix.
        Bit-identical to streaming the whole prompt: rows [0, m) of the
        ingesting run's final scratch are exactly what this prompt's own
        chunks would have produced (within-segment causality + the
        canonical mass fold make scratch rows segmentation-invariant)."""
        empty = M.init_prefill_state(self.cfg, L)
        k = np.zeros(empty.k.shape, np.asarray(pieces[0][0]).dtype)
        v = np.zeros_like(k)
        mass = np.zeros(empty.mass.shape, np.float32)
        bl = self.block_len
        for b, (pk, pv, pm) in enumerate(pieces):
            k[..., b * bl:(b + 1) * bl, :, :] = pk
            v[..., b * bl:(b + 1) * bl, :, :] = pv
            mass[..., b * bl:(b + 1) * bl] = pm
        return M.PrefillState(jnp.asarray(k), jnp.asarray(v),
                              jnp.asarray(mass))

    def _note_inserted(self, sched, adm: _ChunkedAdmission, share) -> None:
        """Post-insert sharing bookkeeping: ingest the admission's
        retained full blocks into the radix index (exact admissions only
        — a blend cache is approximate), remember the prompt for
        near-hit detection, admit the host row mirror, and record which
        leading blocks this slot maps read-only (the CoW watch set)."""
        slot = adm.slot
        req = sched.slot_request(slot)
        L = len(req.tokens)
        n_ing = 0
        if not adm.blend:
            n_ing = self._share_retained(L) // self.block_len
            if n_ing > 0:
                bl = self.block_len
                # host copies of the final scratch rows, block-sliced:
                # the index outlives the (donated) device scratch
                k = np.asarray(adm.st.k)
                v = np.asarray(adm.st.v)
                ms = np.asarray(adm.st.mass)
                pieces = [(k[..., b * bl:(b + 1) * bl, :, :],
                           v[..., b * bl:(b + 1) * bl, :, :],
                           ms[..., b * bl:(b + 1) * bl])
                          for b in range(n_ing)]
                share["stats"]["ingested_blocks"] += share["index"].ingest(
                    req.tokens, sched.slot_blocks(slot)[:n_ing], pieces,
                    self.block_allocator)
        share["index"].note_prompt(req.tokens)
        share["mirror"].admit(slot, L)
        # CoW watch set: every leading block the index now references —
        # adopted blocks AND the slot's own freshly ingested ones (the
        # index holds a ref either way, so an evict flush into them
        # would corrupt the cached prefix for every later adopter)
        n_watch = max(adm.n_adopt, n_ing)
        if n_watch > 0:
            share["upto"][slot] = n_watch
        if adm.n_adopt > 0:
            share["stats"]["warm_hits"] += 1
            if self.trace:
                self.trace.instant("prefix_warm_hit", tid=slot + 1,
                                   args=dict(uid=req.uid,
                                             blocks=adm.n_adopt))
        elif not adm.blend:
            share["stats"]["cold"] += 1
            if self.trace:
                self.trace.instant("prefix_cold", tid=slot + 1,
                                   args=dict(uid=req.uid))

    def _note_adm_stall(self, adm: _ChunkedAdmission, sched
                        ) -> Optional[_ChunkedAdmission]:
        """A block grant for the in-flight admission was refused. With
        resident work the admission just stalls (the decode loop's
        ladder may preempt a victim once `stalls` passes the patience).
        With *nothing* active this used to be provably impossible
        (total <= pool_blocks and nothing else holds blocks) and still
        raises absent injected faults / preemption; under either, a lone
        admission can genuinely starve — cancel it as "failed" after a
        bounded retry window instead of spinning forever."""
        adm.stalls += 1
        if not sched.active_slots():
            if self.fault_plan is None and not self.preemption:
                raise RuntimeError(
                    "chunked admission stalled with no active slots "
                    "(allocator invariant violated)")
            if adm.stalls > self.preempt_patience + self.fail_patience + 8:
                sched.retire(adm.slot, "failed")
                return None
        return adm

    def _advance_chunked_admission(self, adm: _ChunkedAdmission, sched,
                                   cache, lb, *, run_all: bool):
        """Advance the in-flight admission by one interleave step: a
        prompt segment, the finalize (compress), or the insert + first-
        token sample. Finalize and insert are separate steps — each
        costs work proportional to the prompt/cache, so lumping them
        (or a segment) together would itself become the resident stall
        chunked prefill removes. Returns (cache, adm-or-None, first,
        seconds): `first` is (slot, first_token_device) once the slot
        goes ACTIVE. `run_all` drains everything back-to-back — used
        when no resident slot is decoding, so there is nothing to
        stall."""
        if adm is None:
            return cache, None, None, 0.0
        sp = self.trace.span("prefill_chunk", tid=adm.slot + 1,
                             args=dict(uid=adm.uid))
        sp.__enter__()
        first = None
        cur = adm
        # a block grant below can trigger the scheduler's reclaim, whose
        # tiering half gathers pool blocks — publish the in-progress
        # cache so it never dispatches against a donated stale buffer
        self._adm_live = cache
        while adm is not None:
            i = adm.next_i
            if i == len(adm.segs):        # compress the scratch
                adm.pc = (self._finalize_meta(adm.st, lb) if adm.direct
                          else self._finalize(adm.st, lb, adm.key))
                adm.next_i += 1
                if run_all:
                    continue
                break
            if i == len(adm.segs) + 1:    # insert + first token
                # the full grant must be in place before the insert
                # scatters (decode headroom + quantization slack under
                # eager growth; prompt coverage under lazy)
                if self.paged and adm.total_blocks > adm.granted:
                    if not sched.grant_blocks(
                            adm.slot, adm.total_blocks - adm.granted):
                        adm = self._note_adm_stall(adm, sched)
                        break  # stall until a retire frees blocks
                    adm.granted = adm.total_blocks
                    adm.stalls = 0
                tok = self.sampler(adm.last_logits, adm.key)
                slot = adm.slot
                if self.paged:
                    ids = np.full(self.n_max_blocks, -1, np.int32)
                    got = sched.slot_blocks(slot)
                    ids[:len(got)] = got
                    ins = self._insert_meta if adm.direct else self._insert
                    cache = ins(cache, adm.pc, jnp.int32(slot),
                                jnp.asarray(ids), jnp.int32(adm.n_adopt))
                else:
                    cache = self._insert(cache, adm.pc, jnp.int32(slot))
                share = self._share_state
                if share is not None:
                    self._note_inserted(sched, adm, share)
                sched.finish_prefill(slot)
                first = (slot, tok)
                adm = None
                break
            if self.paged:
                # chunk-wise grants: pin only the blocks the rows
                # streamed so far need
                c1 = adm.starts[i] + len(adm.segs[i])
                target = min(
                    adm.total_blocks, paging_lib.request_blocks_prefix(
                        self.spec, self._S_phys, c1, self.block_len))
                if target > adm.granted:
                    if not sched.grant_blocks(adm.slot,
                                              target - adm.granted):
                        adm = self._note_adm_stall(adm, sched)
                        break  # stall until a retire frees blocks
                    adm.granted = target
                    adm.stalls = 0
            adm.last_logits, adm.st = self._chunk_step(
                self.params, adm.st, jnp.asarray(adm.segs[i][None]),
                jnp.int32(adm.starts[i]))
            if adm.direct:
                # prefill-direct: this segment's exact K/V rows go
                # straight into the slot's granted blocks (metadata-only
                # insert later); restored prefix rows are already
                # resident, so only the suffix ever hits the pool
                c0a = adm.starts[i]
                c1a = c0a + len(adm.segs[i])
                got = sched.slot_blocks(adm.slot)
                bl = self.block_len
                rows = np.asarray(
                    [got[t // bl] * bl + t % bl for t in range(c0a, c1a)],
                    np.int32)
                cache = self._write_rows(
                    cache, jnp.asarray(rows),
                    adm.st.k[:, :, :, c0a:c1a], adm.st.v[:, :, :, c0a:c1a])
                self._adm_live = cache
            adm.next_i += 1
            if not run_all:
                break
        self._adm_live = None
        sp.__exit__()
        dt = sp.elapsed
        cur.secs += dt
        if first is not None and self._share_state is not None:
            stats = self._share_state["stats"]
            warm = cur.restore_m > 0 or cur.blend
            stats["warm_prefill_s" if warm else
                  "cold_prefill_s"].append(cur.secs)
        return cache, adm, first, dt

    # ------------------------------------------------------------------
    # Continuous batching
    # ------------------------------------------------------------------
    def generate_continuous(
        self, requests: Sequence[Union[Request, np.ndarray]], *,
        buckets: Optional[Sequence[int]] = None,
    ) -> ContinuousGenerationResult:
        """Serve `requests` through one persistent `slots`-wide cache.

        Each request is prefilled at its prompt bucket (batch 1, one
        compiled prefill per bucket length) and scattered into a free
        batch slot; every decode step advances all occupied slots at
        once; a request hitting its `eos_id` or `max_new` retires
        immediately and its slot is handed to the next queued request.
        Bare arrays are wrapped as `Request(tokens, max_new=self.max_new)`.

        Decoder-only archs (the survey's subject). MoE layers route
        drop-free here (`moe.moe_apply_dropfree`): a token's output
        depends on that token alone, so co-resident slots never perturb
        each other.
        """
        if self.cfg.is_encoder_decoder:
            raise NotImplementedError(
                "continuous batching is decoder-only for now (enc-dec "
                "requests carry per-request cross memory)")
        if buckets and max(int(b) for b in buckets) > self.prompt_len:
            # the cache/spec were sized for prompt_len at construction; a
            # longer bucket would silently truncate prompts via the
            # compression path instead of erroring
            raise ValueError(
                f"bucket {max(int(b) for b in buckets)} exceeds engine "
                f"prompt_len {self.prompt_len}")
        if buckets and (self.chunked_prefill or self.prefix_sharing):
            bad = [int(b) for b in buckets if int(b) % MASS_GROUP]
            if bad:
                raise ValueError(
                    f"chunked prefill needs MASS_GROUP({MASS_GROUP})-"
                    f"aligned prompt buckets, got {bad}")
        if self.speculative:
            # draft/verify loop (serving/speculative.py): synchronous
            # rounds — drafting needs each round's committed tokens
            return spec_lib.generate_continuous_spec(self, requests,
                                                     buckets=buckets)
        t_setup = time.perf_counter()
        # the loop's leaf phases (admit, dispatch, fetch, record): timed
        # always — the result's fetch seconds — and emitted on the
        # engine lane under a live tracer
        phases = Phases(self.trace)
        if self.paged:
            # fresh free list per run (the cache is rebuilt below too);
            # kept on self for post-run inspection (peak usage)
            self.block_allocator = paging_lib.BlockAllocator(
                self.pool_blocks, fault_plan=self.fault_plan,
                tracer=self.trace)
            sched = Scheduler(buckets or self.buckets, self.slots,
                              allocator=self.block_allocator,
                              block_need=self._request_blocks,
                              admission_order=self.admission_order,
                              tracer=self.trace)
        else:
            sched = Scheduler(buckets or self.buckets, self.slots,
                              admission_order=self.admission_order,
                              tracer=self.trace)
        for r in requests:
            if not isinstance(r, Request):
                r = Request(tokens=r, max_new=self.max_new)
            if r.max_new > self.max_new:
                raise ValueError(
                    f"request max_new {r.max_new} exceeds engine headroom "
                    f"{self.max_new}")
            sched.submit(r)

        # KV tiering: fresh host tier + its own pressure controller per
        # run (same watermarks as degradation — the spill rung engages
        # at the same pressure, one rung earlier in the ladder)
        tier: Optional[paging_lib.HostTier] = None
        tier_ctrl = None
        self._tier_aux = {}
        self._tier_stripped = 0
        if self.tiering:
            tier = paging_lib.HostTier(self.host_blocks,
                                       fault_plan=self.fault_plan,
                                       tracer=self.trace)
            from repro.serving.adaptive import PressureController
            tier_ctrl = PressureController(high_water=0.85, low_water=0.60,
                                           tracer=self.trace)
        self.host_tier = tier
        self.tier_pressure = tier_ctrl

        # sharing routes every admission through the chunked machinery
        # (a warm hit is a chunked prefill resumed at the match offset);
        # the chunked == monolithic bit-identity contract keeps streams
        # unchanged for runs that never hit the index
        use_adm = self.chunked_prefill or (self.paged and self.prefix_sharing)
        self._share_state = None
        if self.paged and self.prefix_sharing:
            index = prefix_lib.PrefixIndex(
                self.block_len,
                align=math.lcm(self.block_len, MASS_GROUP),
                tracer=self.trace)
            self._share_state = dict(
                index=index,
                mirror=spec_lib.CacheMirror(
                    self.spec, self.layer_budgets, self._S_phys,
                    self.slots),
                upto={},            # slot -> leading blocks mapped shared
                align=math.lcm(self.block_len, MASS_GROUP),
                near_ok=(self.near_hit > 0
                         and self.spec.policy == "none"
                         and M.sb_layout(self.cfg)[0] == 1),
                stats=dict(warm_hits=0, cold=0, near_hits=0, cow_copies=0,
                           ingested_blocks=0, evicted_blocks=0,
                           warm_prefill_s=[], cold_prefill_s=[]),
            )

            def _reclaim(shortfall: int) -> None:
                # under tiering, cold index blocks demote to host first
                # (warm hits survive the churn); only what the tier
                # can't absorb is LRU-freed outright
                if tier is not None:
                    shortfall -= demote_index_blocks(shortfall)
                if shortfall <= 0:
                    return
                freed = index.evict(shortfall, self.block_allocator)
                self._share_state["stats"]["evicted_blocks"] += len(freed)
                sched.release(-1, freed)

            sched.reclaim = _reclaim
            if tier is not None:
                # tier-aware admission: free + spillable-cold coverage
                # (the scheduler's second reclaim pass converts it)
                sched.spillable = lambda: min(
                    index.spillable(self.block_allocator),
                    tier.free_blocks)

        def share_retire(slot_idx: int) -> None:
            if self._share_state is not None:
                self._share_state["upto"].pop(slot_idx, None)
                self._share_state["mirror"].reset(slot_idx)

        cache = M.init_cache(
            self.cfg, self.spec, self.slots, self.prompt_len + self.max_new,
            layer_budgets=jnp.asarray(self.layer_budgets, jnp.int32),
            paged=self.paged, block_len=self.block_len,
            pool_blocks=self.pool_blocks)
        next_tok = np.zeros(self.slots, np.int32)
        prefill_s = decode_s = 0.0
        decode_tokens = 0
        lb = jnp.asarray(self.layer_budgets)
        # slots known to hold the empty-cache state (the init above):
        # admission refusals reset a slot at most once, not per retry
        clean_slots = set(range(self.slots))
        # lazy block growth: host mirror of per-slot row usage (append/
        # flush timing depends only on counts, so no device sync needed
        # to decide a grant)
        lazy_mirror = (spec_lib.CacheMirror(
            self.spec, self.layer_budgets, self._S_phys, self.slots)
            if (self.paged and self.lazy_blocks) else None)
        # Pipeline + preemption state, declared before the initial fill:
        # admissions may preempt (the ladder below), and `preempt_slot`
        # reads the in-flight token buffers.
        pending = None                          # (tok_dev, valid slots)
        first_pending = None                    # (slot, first-token dev)
        replay: dict = {}     # slot -> committed tokens still to re-feed
        step_idx = 0                            # dispatches so far
        preempt_due = list(self.preempt_at)     # forced (step, slot) pairs

        def preempt_slot(s: int) -> bool:
            """Preempt slot `s`: fold its committed-but-unfetched token
            (a decode token riding `pending` or a chunk-admitted first
            token riding `first_pending`) into the record, then requeue
            prompt + emitted as a continuation and clear the slot. If
            that folded token *finished* the request it retires instead
            (nothing left to resume) — blocks are freed either way.
            Returns True when the slot was preempted (vs retired)."""
            nonlocal cache, pending, first_pending, decode_tokens
            reason = None
            if pending is not None and s in pending[1]:
                ptok, pvalid = pending
                decode_tokens += 1
                reason = sched.record_token(s, int(np.asarray(ptok)[s]))
                pvalid.remove(s)
            elif first_pending is not None and first_pending[0] == s:
                reason = sched.record_token(
                    s, int(jax.device_get(first_pending[1])[0]))
                first_pending = None
            if reason is not None:
                sched.retire(s, reason)
            else:
                # preempt-to-host: snapshot blocks + slot meta before
                # `preempt` releases the ids; the ticketed continuation
                # restores instead of recomputing. Tier off / host full
                # / nothing emitted yet -> recompute-on-resume as before.
                h = spill_slot(s)
                req = sched.preempt(s)
                if h is not None:
                    req.tier_ticket = h
                    req.tier_blocks = self._tier_aux[h]["n"]
            share_retire(s)
            cache = self._reset(cache, jnp.int32(s))
            clean_slots.add(s)
            if lazy_mirror is not None:
                lazy_mirror.reset(s)
            replay.pop(s, None)
            return reason is None

        def degrade_tick() -> None:
            """First rung of the ladder: above the controller's high-water
            mark, evict resident quantized slots down (drop their oldest
            flushed non-sink groups) until the requested shortfall is
            freed — reversible quality loss instead of preemption."""
            nonlocal cache
            ctrl = self.pressure
            shortfall = ctrl.shortfall(self.block_allocator)
            if shortfall <= 0:
                return
            G = self.spec.group
            share = self._share_state
            for s in sched.active_slots():
                if shortfall <= 0:
                    break
                if s in replay:
                    continue    # mid-resume recompute: keep it exact
                if share is not None and share["upto"].get(s):
                    continue    # leading blocks shared read-only
                lens = lazy_mirror.length[s]
                if int(lens.min()) != int(lens.max()):
                    continue    # one shared table permutation per layer
                n = min(int(lens[0]) // G - ctrl.keep_groups, shortfall)
                if n <= 0:
                    continue
                cache = self._degrade_op(cache, jnp.int32(s), jnp.int32(n))
                # kvlint: ok(host-sync: pressure-driven degrade is a rare event — the table read is off the steady-state step)
                tbl = np.asarray(jax.device_get(cache.attn.block_tbl))
                row = tbl.reshape(-1, tbl.shape[-2], tbl.shape[-1])[0, s]
                dropped = sched.replace_blocks(
                    s, [int(b) for b in row if b >= 0])
                lazy_mirror.drop_rows(s, len(dropped) * G)
                if share is not None:
                    share["mirror"].drop_rows(s, len(dropped) * G)
                ctrl.note_degrade(len(dropped))
                shortfall -= len(dropped)

        # --- KV tiering closures (all no-ops with tiering off) ----------
        def _live_cache():
            """Buffer a tier gather may dispatch against. A block grant
            inside `_advance_chunked_admission` can reclaim -> demote
            while the closure `cache` is a donated stale buffer; the
            admission publishes its in-progress cache for that window."""
            return self._adm_live if self._adm_live is not None else cache

        def demote_index_blocks(shortfall: int) -> int:
            """Cold source (a): prefix-cache blocks past their last
            adopter (refcount 1) demote to host LRU-first instead of
            being LRU-freed — a later warm hit pages them back
            (`promote_for_head`) rather than re-prefilling. Returns the
            number of device blocks freed."""
            share = self._share_state
            if tier is None or share is None:
                return 0
            index = share["index"]
            freed = 0
            while freed < shortfall:
                node = index.demote_candidate(self.block_allocator)
                if node is None:
                    break
                payload = self._gather_blocks(
                    _live_cache(), jnp.asarray([node.block_id], jnp.int32))
                h = tier.begin_spill(payload, 1)
                if h is None:
                    break                       # host tier full
                bid = node.block_id
                index.mark_host(node, h)
                sched.release(-1, [bid])
                sched.note_swap(-1, spills=1,
                                bytes_moved=tier.nbytes_of(h))
                freed += 1
            if freed and tier_ctrl is not None:
                tier_ctrl.note_spill(freed)
            return freed

        def spill_tick() -> None:
            """The ladder's new first rung, ahead of degradation: above
            the tier controller's high-water mark, demote cold index
            blocks, then strip granted-but-unwritten blocks from a
            stalled PREFILLING admission (its scratch holds the rows, so
            the blocks carry no data yet and the grant loop simply
            re-requests them once pressure clears)."""
            shortfall = tier_ctrl.shortfall(self.block_allocator)
            if shortfall <= 0:
                return
            shortfall -= demote_index_blocks(shortfall)
            if (shortfall > 0 and adm is not None and not adm.direct
                    and not adm.blend and adm.stalls > 0
                    and adm.granted > adm.n_adopt):
                n_strip = min(shortfall, adm.granted - adm.n_adopt)
                freed = sched.release_blocks(adm.slot, n_strip)
                adm.granted -= len(freed)
                self._tier_stripped += len(freed)

        def spill_slot(s: int) -> Optional[int]:
            """Snapshot slot `s`'s pool blocks + meta row (and host-side
            mirrors) into the tier. Async: the gather is dispatched, the
            ids freed immediately by the caller's `preempt`, the host
            copy drains next iteration. Returns the ticket, or None when
            the slot can't restore bit-identically (mid-replay, nothing
            emitted yet) or the host tier is full."""
            if tier is None or s in replay or sched.emitted_total(s) == 0:
                return None
            ids = sched.slot_blocks(s)
            if not ids:
                return None
            payload = dict(
                blocks=self._gather_blocks(
                    cache, jnp.asarray(ids, jnp.int32)),
                meta=self._gather_meta(cache, jnp.int32(s)))
            h = tier.begin_spill(payload, len(ids))
            if h is None:
                return None         # host full -> recompute-on-resume
            aux: dict = dict(n=len(ids))
            if lazy_mirror is not None:
                aux["lazy"] = lazy_mirror.snapshot(s)
            if self._share_state is not None:
                aux["share"] = self._share_state["mirror"].snapshot(s)
            self._tier_aux[h] = aux
            sched.note_swap(s, spills=len(ids),
                            bytes_moved=tier.nbytes_of(h))
            return h

        def try_restore(slot_idx: int, req) -> bool:
            """Land a ticketed continuation's saved blocks back into its
            fresh grant and resume from the last emitted token — no
            replay; restored bytes are checksum-verified bit-identical.
            A refused fetch (injected fault) consumes the ticket and
            returns False: the caller falls back to recompute-on-resume,
            which rebuilds the same stream."""
            nonlocal cache
            h = req.tier_ticket
            req.tier_ticket = None
            req.tier_blocks = 0
            aux = self._tier_aux.pop(h, None)
            got = tier.fetch(h)
            if got is None:                 # refusal: the bytes are gone
                return False
            payload, nbytes, stall = got
            k = aux["n"]
            ids = sched.slot_blocks(slot_idx)
            cache = self._scatter_blocks(
                cache, jnp.asarray(ids[:k], jnp.int32), payload["blocks"])
            cache = self._restore_meta(cache, jnp.int32(slot_idx),
                                       payload["meta"])
            # map the full grant: the k saved blocks plus any headroom
            # blocks the re-admission granted beyond them (future rows)
            row = np.full(self.n_max_blocks, -1, np.int32)
            row[:len(ids)] = ids
            cache = self._grow_tbl(cache, jnp.int32(slot_idx),
                                   jnp.int32(0), jnp.asarray(row))
            clean_slots.discard(slot_idx)
            if lazy_mirror is not None:
                lazy_mirror.restore(slot_idx, aux["lazy"])
            if self._share_state is not None:
                # row mirror only: the restored slot owns fresh exclusive
                # ids, so no CoW watch set ("upto") comes back with it
                self._share_state["mirror"].restore(slot_idx, aux["share"])
            sched.note_swap(slot_idx, fetches=k, bytes_moved=nbytes,
                            stall_s=stall)
            next_tok[slot_idx] = req.emitted_prefix[-1]
            return True

        def admit_ticket_head() -> None:
            """Loop-top admission for a ticketed (spill-preempted)
            continuation: a plain `admit_next` sizes the grant through
            `_request_blocks` (>= its saved blocks), then the fetch lands
            the snapshot into the granted ids. Runs outside the chunked
            machinery — there is no prompt left to stream."""
            nonlocal cache, tok_in, prefill_s
            free = sched.free_slots()
            if not free:
                return
            i = free[0]
            req = sched.admit_next(i)
            if req is None:
                tries = sched.note_retry()
                if self.preemption and tries > self.preempt_patience:
                    v = sched.preempt_victim(exclude=tuple(replay))
                    if v is not None:
                        preempt_slot(v)
                        return
                if (not sched.active_slots()
                        and not sched.prefilling_slots()
                        and tries > self.fail_patience):
                    # the whole pool can't cover the ticket-sized grant:
                    # drop the ticket so the continuation retries as a
                    # plain (smaller-footprint) recompute admission
                    head = sched.head_request()
                    if head is not None and head.tier_ticket is not None:
                        self._drop_ticket(head)
                return
            with self.trace.span("restore", tid=i + 1,
                                 args=dict(uid=req.uid)) as sp:
                ok = try_restore(i, req)
            prefill_s += sp.elapsed
            if ok:
                tok_in = tok_in.at[i].set(int(next_tok[i]))
            else:
                # fetch refused before anything ran in the slot: requeue
                # at the front as an ordinary recompute-on-resume
                # continuation (the chunked machinery re-prefills it)
                sched.preempt(i)

        def promote_for_head() -> None:
            """Pre-admission paging for a warm hit on demoted prefix
            blocks: fetch the head prompt's host-resident nodes back into
            freshly allocated blocks so `match` can hand the admission
            the full read-only hit. A refused fetch drops the node's
            subtree (its bytes are gone); an empty free list leaves the
            admission with the partial (device-resident) hit."""
            nonlocal cache
            share = self._share_state
            if tier is None or share is None or not sched.pending:
                return
            req = sched.head_request()
            if req is None or not self._share_retained(len(req.tokens)):
                return
            index = share["index"]
            for node in index.match_nodes(req.tokens):
                if node.host is None:
                    continue
                got_ids = self.block_allocator.alloc(1)
                if got_ids is None:
                    break
                got = tier.fetch(node.host)
                if got is None:
                    dev_ids, handles = index.drop_node(node)
                    sched.release(-1, dev_ids)
                    for hh in handles:
                        tier.drop(hh)
                    sched.release(-1, got_ids)
                    break
                payload, nbytes, stall = got
                cache = self._scatter_blocks(
                    cache, jnp.asarray(got_ids, jnp.int32), payload)
                index.promote(node, got_ids[0])
                sched.note_swap(-1, fetches=1, bytes_moved=nbytes,
                                stall_s=stall)

        def admit_into(slot_idx: int, ladder: bool = False) -> bool:
            """Fill a free slot from the queue: bucketed batch-1 prefill,
            scatter into the live cache, stream the first token. Loops in
            case a request finishes on its very first token. Returns True
            when a request now occupies the slot (its first token is in
            `next_tok[slot_idx]`). Under paging, `admit_next` may refuse
            while the pool is exhausted — the slot then idles until a
            retire frees blocks (the decode loop retries every free slot
            after each batch of retirements). `ladder=True` (only at
            safe points: the initial fill and the loop-top sweep, never
            mid-record) lets a refused admission claim a victim via the
            preemption ladder."""
            nonlocal cache, prefill_s
            while True:
                req = sched.admit_next(slot_idx)
                if req is None:
                    if self.paged and sched.pending:
                        tries = sched.note_retry()
                        if (ladder and self.preemption
                                and tries > self.preempt_patience):
                            # the ladder: free a victim's blocks and
                            # retry. Replaying slots are never victims —
                            # a victim's progress must have grown since
                            # its last preemption (convergence).
                            v = sched.preempt_victim(exclude=tuple(replay))
                            if v is not None:
                                preempt_slot(v)
                                continue
                        if (not sched.active_slots()
                                and not sched.prefilling_slots()):
                            # nothing running will ever free blocks —
                            # but an *injected* refusal is transient, so
                            # retry a bounded number of times before
                            # concluding the head just doesn't fit this
                            # pool and retiring it "failed" (preserving
                            # every completed request's results).
                            if tries <= self.fail_patience:
                                continue
                            head = sched.head_request()
                            if (tier is not None and head is not None
                                    and head.tier_ticket is not None):
                                # a ticket-sized grant the pool can never
                                # cover: drop the snapshot and retry as a
                                # plain (smaller) recompute continuation
                                self._drop_ticket(head)
                                continue
                            sched.fail_head()
                            continue
                    # nothing admittable: clear the slot so stale KV never
                    # leaks into accounting or a later occupant — under
                    # paging this is load-bearing, not hygiene: a stale
                    # block table would keep routing this garbage row's
                    # appends into freed (soon re-granted) blocks
                    if slot_idx not in clean_slots:
                        cache = self._reset(cache, jnp.int32(slot_idx))
                        clean_slots.add(slot_idx)
                    return False
                if tier is not None and req.tier_ticket is not None:
                    # ticketed continuation: land the snapshot into the
                    # grant instead of re-prefilling; a refused fetch
                    # falls through to recompute-on-resume below
                    with self.trace.span("restore", tid=slot_idx + 1,
                                         args=dict(uid=req.uid)) as sp:
                        ok = try_restore(slot_idx, req)
                    prefill_s += sp.elapsed
                    if ok:
                        return True
                self.key, k1 = jax.random.split(self.key)
                with self.trace.span("prefill", tid=slot_idx + 1,
                                     args=dict(uid=req.uid)) as sp:
                    logits, pc = self._prefill(
                        self.params,
                        {"tokens": jnp.asarray(req.tokens[None])},
                        lb, k1)
                    tok = self.sampler(logits, k1)
                    if self.paged:
                        ids = np.full(self.n_max_blocks, -1, np.int32)
                        got = sched.slot_blocks(slot_idx)
                        ids[:len(got)] = got
                        cache = self._insert(cache, pc, jnp.int32(slot_idx),
                                             jnp.asarray(ids), jnp.int32(0))
                    else:
                        cache = self._insert(cache, pc, jnp.int32(slot_idx))
                    clean_slots.discard(slot_idx)
                    if lazy_mirror is not None:
                        lazy_mirror.admit(slot_idx, len(req.tokens))
                    prev = phases.to("fetch")
                    # kvlint: ok(host-sync: admission prefill's first token — once per admitted request, not per decode step)
                    tok_i = int(jax.device_get(tok)[0])
                    phases.to(prev)
                prefill_s += sp.elapsed
                if req.emitted_prefix:
                    # recompute-on-resume: the prefill covered the
                    # prompt; the committed tokens now *replay* through
                    # the normal decode path (outputs discarded until
                    # the queue drains), so each replay step IS the
                    # original decode step and the stream stays
                    # bit-identical. Nothing is recorded here — the
                    # prefix already holds this prefill's first token.
                    next_tok[slot_idx] = req.emitted_prefix[0]
                    replay[slot_idx] = list(req.emitted_prefix[1:])
                    return True
                next_tok[slot_idx] = tok_i
                reason = sched.record_token(slot_idx, tok_i)
                if reason is None:
                    return True
                sched.retire(slot_idx, reason)   # 1-token request; refill

        # --- chunked admission (long prompts must not stall resident
        # decode): shared machinery on the engine
        # (`_start_chunked_admission` / `_advance_chunked_admission`,
        # also driven by the speculative loop); thin wrappers route the
        # loop's state through it. The scratch (M.PrefillState) is
        # disjoint from the live cache, so resident slots' rows never
        # see a partial prompt — the finalize inserts the same
        # compressed cache a monolithic admission would (bit-identical
        # greedy streams).
        adm: Optional[_ChunkedAdmission] = None

        def advance_admission(run_all: bool):
            nonlocal cache, adm, prefill_s
            cache, adm, first, dt = self._advance_chunked_admission(
                adm, sched, cache, lb, run_all=run_all)
            prefill_s += dt
            if first is not None:
                clean_slots.discard(first[0])
                if lazy_mirror is not None:
                    lazy_mirror.admit(
                        first[0], len(sched.slot_request(first[0]).tokens))
            return first

        if not use_adm:
            for i in range(self.slots):
                admit_into(i)

        # Double-buffered decode: step N+1 is dispatched *before* blocking
        # on step N's token fetch — its inputs are step N's device-side
        # outputs, so the only host sync per step is the (pipelined) fetch
        # of the previous step's tokens. A slot that retires at step N
        # already has a stale step N+1 in flight: that step's output for
        # the slot is dropped from the valid set, the admission's cache
        # insert overwrites the slot wholesale (wiping the stale append),
        # and the next dispatch carries the admitted first token — an
        # admission simply lands one step later than a serial loop would
        # place it. Per-request token streams are unchanged for
        # deterministic sampling/eviction (greedy + full/streaming/h2o/
        # kivi*); stochastic paths (non-greedy samplers, nacl/keyformer
        # gumbel noise) see a different-but-equally-random key order,
        # because dispatching ahead consumes self.key splits in a
        # different sequence around mid-run admissions.
        tok_in = jnp.asarray(next_tok)          # [slots] device-side
        # per-iteration telemetry: one truthiness check per emit site,
        # host-side mirrors only (allocator free list, scheduler slot
        # states — never a device value)
        trace = self.trace
        n_iters = 0
        loop_t0 = time.perf_counter()
        if trace:
            trace.complete("setup", t_setup, loop_t0)
        prefill_at_loop = prefill_s
        fetch_at_loop = phases.totals.get("fetch", 0.0)
        while True:
            it_t0 = time.perf_counter()
            n_iters += 1
            phases.to("admit")
            if tier is not None:
                # pull last iteration's dispatched spill copies to host
                # (decode has run behind them — no hot-path sync)
                tier.drain()
            if use_adm and adm is None:
                if tier is not None and sched.pending:
                    head = sched.head_request()
                    if head is not None and head.tier_ticket is not None:
                        tier.prefetch(head.tier_ticket)
                        admit_ticket_head()
                    else:
                        promote_for_head()
                adm, dt = self._start_admission_timed(sched)
                prefill_s += dt
            if preempt_due:
                # forced preemption injection — the deterministic
                # preempt-at-step-k hook the bit-identity tests drive
                for k_s in [x for x in preempt_due if x[0] == step_idx]:
                    preempt_due.remove(k_s)
                    if k_s[1] in sched.active_slots():
                        preempt_slot(k_s[1])
            if (self.preemption and adm is not None
                    and adm.stalls > self.preempt_patience):
                # a chunk-admission grant has stalled past patience:
                # escalate to the ladder (never victimize the admission's
                # own slot or a mid-resume replay)
                v = sched.preempt_victim(exclude=(adm.slot, *replay))
                if v is not None:
                    preempt_slot(v)
                    adm.stalls = 0
            if self.preemption and not use_adm and sched.pending:
                # admission retry sweep: a head refused earlier may fit
                # now, or may claim a victim through the ladder
                for i in sched.free_slots():
                    if not sched.pending or not admit_into(i, ladder=True):
                        break
                    tok_in = tok_in.at[i].set(int(next_tok[i]))
            if tier_ctrl is not None:
                spill_tick()
            if self.pressure is not None:
                degrade_tick()
            active = sched.active_slots()
            if (self.audit_every and step_idx
                    and step_idx % self.audit_every == 0):
                self._run_audit(sched, cache)
                if trace:
                    trace.instant("audit", args=dict(step=step_idx))
            if lazy_mirror is not None and active:
                # lazy growth: every slot joining this dispatch must have
                # table coverage for the row the dispatch appends. A slot
                # the pool cannot grow retires "oom" (its pending token
                # is recorded first) — the lazy admission rule only
                # reserved prompt coverage. Freed blocks may admit queued
                # work immediately; refilled slots enter the same
                # worklist so their first append is covered too.
                worklist = list(active)
                while worklist:
                    s = worklist.pop(0)
                    rows = lazy_mirror.rows_after_feeds(s, 1)
                    need = paging_lib.request_blocks_prefix(
                        self.spec, self._S_phys, rows, self.block_len)
                    have = len(sched.slot_blocks(s))
                    if need <= have:
                        continue
                    # bounded retry absorbs transient (injected)
                    # refusals — each attempt is a fresh alloc call
                    granted = False
                    for _ in range(self.fail_patience):
                        if sched.grant_blocks(s, need - have):
                            granted = True
                            break
                    if not granted and self.preemption:
                        # the ladder: free victims' blocks until the
                        # grant fits, then requeue *this* slot if other
                        # work still holds blocks that will free —
                        # "oom" stays only for the truly-unservable
                        # (a lone slot the whole pool cannot grow)
                        while not granted:
                            v = sched.preempt_victim(exclude=(s, *replay))
                            if v is None:
                                break
                            preempt_slot(v)
                            if v in active:
                                active.remove(v)
                            if v in worklist:
                                worklist.remove(v)
                            granted = sched.grant_blocks(s, need - have)
                        if not granted and (
                                len(sched.active_slots()) > 1
                                or sched.prefilling_slots()):
                            preempt_slot(s)
                            active.remove(s)
                            continue
                    if granted:
                        ids = sched.slot_blocks(s)[have:]
                        cache = self._grow_tbl(
                            cache, jnp.int32(s), jnp.int32(have),
                            jnp.asarray(ids, jnp.int32))
                        continue
                    # record any committed-but-unfetched token for the
                    # slot before retiring it: a decode token pipelining
                    # in `pending`, or a chunk-admitted first token
                    # still riding `first_pending`
                    reason = None
                    if pending is not None and s in pending[1]:
                        ptok, pvalid = pending
                        decode_tokens += 1
                        reason = sched.record_token(
                            # kvlint: ok(host-sync: lazy-starve retire is a rare pressure event — drain the pending token before the slot dies)
                            s, int(np.asarray(ptok)[s]))
                        pvalid.remove(s)
                    elif first_pending is not None and first_pending[0] == s:
                        reason = sched.record_token(
                            # kvlint: ok(host-sync: lazy-starve retire is a rare pressure event — drain the pending token before the slot dies)
                            s, int(jax.device_get(first_pending[1])[0]))
                        first_pending = None
                    sched.retire(s, reason or "oom")
                    share_retire(s)
                    cache = self._reset(cache, jnp.int32(s))
                    clean_slots.add(s)
                    lazy_mirror.reset(s)
                    active.remove(s)
                    if sched.pending and not use_adm:
                        for i in sched.free_slots():
                            if not sched.pending or not admit_into(i):
                                break
                            tok_in = tok_in.at[i].set(int(next_tok[i]))
                            active.append(i)
                            worklist.append(i)
            share = self._share_state
            if share is not None and active:
                # copy-on-write: a slot whose next append could flush an
                # eviction into its adopted (shared, read-only) prefix
                # blocks un-shares them first — fresh exclusive blocks,
                # device-side row copy, table rewrite. Conservative: all
                # leading shared blocks swap at once (eviction targets
                # are data-dependent; the host only tracks row counts).
                for s in [s for s in list(active) if share["upto"].get(s)]:
                    if not self._cow_due(share["mirror"], s):
                        continue
                    n_watch = share["upto"][s]
                    res = sched.cow_swap(s, n_watch)
                    if res is None:
                        # pool can't cover the full un-share. A copy is
                        # only *required* for blocks another resident
                        # slot maps (refcount >= 3: slot + index +
                        # other); blocks the index alone shares are
                        # disowned instead — the prompt cache pays, the
                        # slot becomes their sole owner in place.
                        # Refcounts fall monotonically with trie depth
                        # (a slot mapping block d maps every ancestor),
                        # so the must-copy set is a prefix.
                        ids_w = sched.slot_blocks(s)[:n_watch]
                        rc = self.block_allocator.refcount
                        n_copy = 0
                        while (n_copy < n_watch
                               and rc(ids_w[n_copy]) >= 3):
                            n_copy += 1
                        dropped = share["index"].disown(ids_w[n_copy:])
                        share["stats"]["evicted_blocks"] += len(dropped)
                        sched.release(-1, dropped)
                        if tier is not None:
                            # the cascade may have unrooted demoted
                            # descendants: their bytes die with the trie
                            for hh in share["index"].take_orphaned_handles():
                                tier.drop(hh)
                        res = (([], []) if n_copy == 0
                               else sched.cow_swap(s, n_copy))
                    if res is not None:
                        old, new = res
                        if new:
                            cache = self._copy_blocks(
                                cache, jnp.asarray(old, jnp.int32),
                                jnp.asarray(new, jnp.int32))
                            cache = self._grow_tbl(
                                cache, jnp.int32(s), jnp.int32(0),
                                jnp.asarray(new, jnp.int32))
                            share["stats"]["cow_copies"] += 1
                            if trace:
                                trace.instant(
                                    "cow", tid=s + 1,
                                    args=dict(blocks=len(new)))
                        share["upto"].pop(s)
                        continue
                    # pool can't cover the un-share: retire "oom" (same
                    # pending-token bookkeeping as the lazy starve path)
                    reason = None
                    if pending is not None and s in pending[1]:
                        ptok, pvalid = pending
                        decode_tokens += 1
                        reason = sched.record_token(
                            # kvlint: ok(host-sync: un-share OOM retire is a rare pressure event — drain the pending token before the slot dies)
                            s, int(np.asarray(ptok)[s]))
                        pvalid.remove(s)
                    elif first_pending is not None and first_pending[0] == s:
                        reason = sched.record_token(
                            # kvlint: ok(host-sync: un-share OOM retire is a rare pressure event — drain the pending token before the slot dies)
                            s, int(jax.device_get(first_pending[1])[0]))
                        first_pending = None
                    sched.retire(s, reason or "oom")
                    share_retire(s)
                    cache = self._reset(cache, jnp.int32(s))
                    clean_slots.add(s)
                    if lazy_mirror is not None:
                        lazy_mirror.reset(s)
                    active.remove(s)
            phases.to("dispatch")
            new_pending = None
            if active:
                self.key, k2 = jax.random.split(self.key)
                tok_dev, cache = self._decode(self.params, cache,
                                              tok_in[:, None], k2,
                                              self._live_rows(active))
                sched.note_decode_step()
                step_idx += 1
                new_pending = (tok_dev, list(active))
                tok_in = tok_dev                # feed N+1 from N, no sync
                if replay:
                    # recompute-on-resume: while a slot replays, each
                    # dispatch's output is the recomputation of an
                    # already-committed token — drop it from the valid
                    # set and feed the next committed token instead.
                    # Once the queue is empty the dispatch just fed the
                    # last committed token, so its output is the first
                    # *new* one: leave it in the valid set.
                    for s in [s for s in list(replay) if s in active]:
                        q = replay[s]
                        if q:
                            new_pending[1].remove(s)
                            tok_in = tok_in.at[s].set(q.pop(0))
                        else:
                            del replay[s]
                if lazy_mirror is not None:
                    for s in active:
                        lazy_mirror.append(s, 1)
                if share is not None:
                    for s in active:
                        share["mirror"].append(s, 1)
            if first_pending is not None:
                # fetch last iteration's first token (its compute has
                # drained behind this iteration's dispatch by now)
                slot0, ftok = first_pending
                phases.to("fetch")
                # kvlint: ok(host-sync: pipelined — last iteration's first token; its compute drained behind this dispatch)
                tok_i = int(jax.device_get(ftok)[0])
                phases.to("record")
                next_tok[slot0] = tok_i
                reason = sched.record_token(slot0, tok_i)
                if reason is not None:
                    sched.retire(slot0, reason)      # 1-token request
                    share_retire(slot0)
                    if new_pending is not None and slot0 in new_pending[1]:
                        new_pending[1].remove(slot0)
                    cache = self._reset(cache, jnp.int32(slot0))
                    clean_slots.add(slot0)
                first_pending = None
            # interleave at most one step of the in-flight admission (a
            # prompt segment, the compress, or the insert) per decode
            # step; with nothing decoding there is nothing to stall, so
            # the remaining steps stream through back-to-back
            phases.to("admit")
            first = (advance_admission(run_all=not active)
                     if use_adm else None)
            if first is not None:
                # the slot joins the next dispatch with its first token —
                # device-to-device; the host fetch + record are deferred
                # to the next iteration like any pending decode token
                slot0, ftok = first
                creq = sched.slot_request(slot0)
                if creq.emitted_prefix:
                    # chunk-admitted continuation: the recomputed first
                    # token is already in the prefix — seed the replay
                    # instead of recording anything
                    tok_in = tok_in.at[slot0].set(
                        int(creq.emitted_prefix[0]))
                    replay[slot0] = list(creq.emitted_prefix[1:])
                else:
                    tok_in = tok_in.at[slot0].set(ftok[0])
                    first_pending = (slot0, ftok)
            done = (pending is None and new_pending is None and adm is None
                    and first_pending is None and not sched.pending)
            if pending is not None:
                ptok, pvalid = pending
                phases.to("fetch")
                # kvlint: ok(host-sync: the one pipelined fetch — step N-1's tokens, dispatched behind step N)
                toks = np.asarray(ptok)         # blocks on step N-1 only
                phases.to("record")
                admitted = []
                retired_any = False
                for i in pvalid:
                    decode_tokens += 1
                    reason = sched.record_token(i, toks[i])
                    if reason is not None:
                        sched.retire(i, reason)
                        share_retire(i)
                        retired_any = True
                        if new_pending is not None and i in new_pending[1]:
                            new_pending[1].remove(i)
                        if use_adm:
                            # admissions restart at the top of the loop;
                            # clear the slot now so its garbage appends
                            # can't route through a stale block table
                            # into freed (soon re-granted) pool blocks
                            cache = self._reset(cache, jnp.int32(i))
                            clean_slots.add(i)
                        elif admit_into(i):
                            admitted.append(i)
                if (self.paged and retired_any and sched.pending
                        and not use_adm):
                    # a retire frees *blocks*, not just its own slot: a
                    # different slot that was refused admission while the
                    # pool was exhausted may fit now. Admission is FIFO,
                    # so the first refusal (head request doesn't fit)
                    # settles every remaining free slot this step.
                    for i in sched.free_slots():
                        if not sched.pending or not admit_into(i):
                            break
                        admitted.append(i)
                if admitted:
                    tok_in = tok_in.at[jnp.asarray(admitted)].set(
                        jnp.asarray(next_tok[admitted]))
            pending = new_pending
            phases.to(None)
            if trace:
                n_active = len(active)
                trace.complete("step", it_t0, args=dict(active=n_active))
                trace.counter("slots", dict(
                    active=len(sched.active_slots()),
                    prefilling=len(sched.prefilling_slots()),
                    free=len(sched.free_slots())))
                if self.paged:
                    trace.counter("pool", dict(
                        free=self.block_allocator.available,
                        active=n_active))
            if done:
                break
        t_end = time.perf_counter()
        decode_s = (t_end - loop_t0) - (prefill_s - prefill_at_loop)
        if self.paged:
            # every run ends with a host-side invariant audit: all slots
            # retired, so anything still allocated must be held by the
            # prefix index — leaks/skew surface here even in tests that
            # only assert on token streams
            if tier is not None:
                tier.drain()
            self._run_audit(sched)
        res = self._continuous_result(
            sched, cache, prefill_s=prefill_s, decode_s=decode_s,
            decode_tokens=decode_tokens, loop_iters=n_iters,
            loop_s=t_end - loop_t0,
            fetch_s=phases.totals.get("fetch", 0.0) - fetch_at_loop,
            # every decode dispatch runs `self._decode` on this store
            inplace_steps=(sched.decode_steps
                           if M.decode_in_place(cache, self.spec) else 0))
        if trace:
            trace.complete("finish", t_end)
        return res
