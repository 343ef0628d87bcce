"""Step-driven multi-tenant serving engine: continuous batching over a
paged KV cache with no drain assumption.

The EdgeAI-Hub's inference runtime.  The unit of work is one
``step()`` — admit, plan, one jitted wave, retire — and the engine
makes progress with whatever frontier it has *right now*: requests
arrive between any two steps (``submit``), leave between any two steps
(``cancel``), and an always-on frontend (``launch.serve``) just loops
``step()`` forever.  ``run_until_drained`` is a thin compatibility
wrapper, not the execution model.

Step-driven lifecycle (admit -> plan -> wave -> retire)
-------------------------------------------------------
* ADMIT — ``core.scheduler.admission_rank`` orders the queue (QoE
  policies: fifo / priority / edf); capacity-aware admission binds
  requests to free slots.  With ``ServeConfig.chunked_prefill`` a
  token-only request skips bucketed prefill entirely: admission is
  pure bookkeeping (``_admit_wave``) and the prompt becomes pending
  catch-up tokens — its chunks are just more spans in the wave plan
  (Sarathi-style), so a long prompt never blocks in-flight decodes
  behind a monolithic prefill.  (Requests carrying extras — VLM image
  embeds, enc-dec audio — still prefill the smallest bucket first,
  since extras only enter the state through prefill.)
* PLAN — each active slot gets a wave span ``(mode, width)``: ``spec``
  (draft-backed verify of up to ``spec_gamma`` tokens), ``catch``
  (teacher-forced prompt catch-up of up to ``catch_chunk`` tokens) or
  ``plain`` (one decode token).  ``ServeConfig.wave_tokens`` is the
  per-wave token budget: ``core.scheduler.plan_wave`` grants every
  slot width >= 1 (liveness) and spends the rest best-rank-first, so
  prefill chunks and decode share one budget under the same QoE
  policy.  The plan is observable at ``engine.last_plan``
  (``scripts/diagnose.py --server``).
* WAVE — ONE jitted call executes the whole plan: mixed spec / catch /
  plain spans ride a single ``model.extend_paged`` (or ``extend``)
  wave; padded rows drop their writes.  Chunk boundaries and
  budget-driven width changes are pure schedule: extend is bitwise
  equal to sequential decode, so tokens never depend on the plan.
* RETIRE — committed tokens land in ``Request.generated``; finished
  slots return their pages to the radix cache (``_finish``), frontier
  pages publish for in-flight sharing, and EOS / length / preemption /
  cancellation free the slot for the next admit.

Cancellation (``cancel(uid)``) mirrors ``_finish``: a live slot's
pages below ``pos`` hold a valid chain and retire into the radix cache
— published frontier pages keep their cache reference, so concurrent
readers of the cancelled chain are untouched; queued or preempted
requests free their detached state.  Zero pages leak in any phase
(``tests/test_cancellation.py``).

Paged KV (block-table decode contract)
--------------------------------------
GLOBAL attention layers no longer own a dense ``max_len`` strip per
slot.  Their K/V lives in a shared pool of ``kv_block_size``-token
pages (``models.layers.init_kv_pages``, allocated by
``kv_pool.KVBlockPool``); each slot holds an ordered list of physical
page ids whose device mirror is the ``(max_slots, max_len //
kv_block_size)`` int32 ``block_tables`` array passed to
``model.decode_step_paged`` every step (-1 = unallocated).  The engine
maintains these invariants:

* before a decode wave, every active slot's table covers its write
  position ``pos`` (``_ensure_blocks`` appends a page on boundary
  crossing; on pool exhaustion the slot is preempted back to the queue
  with its pages detached — "preempt-or-queue");
* admission is capacity-aware: a request is admitted only when enough
  pool blocks exist for its prompt (+1 decode write) counting both
  FREE pages and radix-cache pages evictable right now — not merely
  when a slot is free;
* ``_finish`` RETURNS the slot's full pages to the radix prefix cache
  (sharable configs; the partial tail page and any duplicates of an
  already-indexed prefix are freed) instead of freeing them outright;
  ``preempt`` detaches pages onto ``Request.saved_state`` so resume is
  still re-prefill-free;
* the logical view ``n_blk * kv_block_size == max_len`` makes paged
  decode bit-for-bit identical to the dense path — only HBM residency
  shrinks, from ``max_slots x max_len`` strips to tokens actually in
  flight.

Shared / forked pages (prefix-cache ownership contract)
-------------------------------------------------------
Admission prefill writes prompt K/V DIRECTLY into pages
(``model.prefill_paged`` — no dense strip is materialised and shadow-
copied), which is what lets a radix-cache hit skip prefix prefill
entirely: admission looks the prompt up in ``prefix_cache``
(``serving.prefix_cache.RadixPrefixCache``), increfs the matched chain
and prefills ONLY the unmatched suffix at the chain's end position.
Matching is TOKEN-granular: a hit may end in the middle of a page —
because the query diverges inside a cached page, or because the cached
chain itself ends mid-page (finished chains retire WITH their partial
tail page indexed).  Admission then CoW-forks that one page
(``KVBlockPool.fork`` + device page copy) so the row owns it
privately, and the suffix prefill reads the forked prefix bytes below
``ctx_len`` while scattering its own K/V from ``ctx_len`` onward
through the row's full block table.  The ownership rules:

* a slot's block table may reference pages with refcount > 1 (shared
  prefix, detached twins, in-flight published frontiers); such pages
  are READ-ONLY by construction — every page a suffix/decode/verify
  wave could write is either freshly allocated or was forked private
  at admission.  The per-step ``_cow_guard`` is the backstop: any slot
  whose write span lands in a page with >1 owner trades it for a
  private copy before the wave runs;
* IN-FLIGHT sharing: after every committed wave each live slot
  publishes its pages below the frontier ``floor(pos / block_size) *
  block_size`` into the radix tree (``_publish_frontiers``; the cache
  takes its own reference, duplicate re-publications dedup to
  nothing).  A later request can therefore hit a chain that is still
  decoding: readers pin pages strictly below the frontier, the writer
  only writes at/above ``pos``, and spec-decode rollback
  (``_truncate_slot``) frees only pages above ``pos`` — published
  pages are never written, truncated or evicted from under a reader;
* finished chains are indexed under a key of the full token sequence
  (plus a digest namespace for non-token inputs: VLM image embeds,
  enc-dec audio — their K/V depends on more than token ids); the cache
  holds one reference per indexed page;
* eviction (LRU leaf chains whose pages have refcount 1) runs lazily
  under pool pressure (``_reserve``) — a chain pinned by any reader or
  published by a live slot is never evicted;
* PERSISTENCE: with ``ServeConfig.prefix_persist_path`` set,
  ``close()`` serializes the hot refcount-free chains (token keys +
  page bytes per pool leaf; chains evicted under pressure are spilled
  to the host first) via ``prefix_cache.save_store``, and a new engine
  constructed with the same path rehydrates them — a restarted hub
  serves warm-TTFT hits from step one.  The store header pins page
  geometry, a config digest and a params fingerprint; a corrupt or
  mismatched store is rejected cleanly (``stats()['persist_rejected']``)
  and the engine starts cold;
* sharing is behaviour-invariant: tokens decoded after a prefix hit —
  block-aligned, token-granular, in-flight or rehydrated-from-disk —
  are bit-identical to a cold run (asserted per family in
  ``tests/test_prefix_cache.py`` / ``tests/test_prefix_persist.py``).
  Configs whose decode state is not fully reconstructible from pages
  (local-ring gemma patterns, ssm/hybrid recurrences) never share —
  ``model.prefix_sharable`` gates the cache off and admission stays
  the cold path.

Local ring-window layers stay dense at ``W`` and SSM state is O(1), so
families with no global KV layers (ssm, hybrid) transparently run the
dense path with zero pool demand.

Speculative decoding (draft/verify step contract)
-------------------------------------------------
With ``ServeConfig.spec_decode`` on a ``model.spec_decodable`` config,
every wave is a multi-token EXTEND wave instead of a one-token decode
(``serving.spec_decode`` owns the draft runtime and acceptance rules;
this engine owns the batching and the KV bookkeeping; the dense
``paged=False`` twin runs the same waves via ``model.extend`` and
stays wave-for-wave bit-identical):

* the draft model (own dense cache, one row per slot) proposes up to
  ``spec_gamma - 1`` tokens per slot; ONE jitted
  ``model.extend_paged`` call then scores ``[t0, d_1..d_{v-1}]`` for
  every slot — spec, catch-up and plain slots share the wave, padded
  rows drop their writes;
* before the wave, ``_ensure_blocks``/``_cow_guard`` cover the whole
  write span ``[pos, pos + v)``: a verify over shared prefix-cache
  pages forks them (copy-on-write) first — a speculative write can
  never land in a chain another reader holds;
* acceptance (greedy exact-match, or rejection sampling at
  temperature > 0 — the emitted distribution equals vanilla sampling)
  commits ``n_accepted + 1`` tokens; the verify wave's rejected writes
  sit ABOVE the new frontier where every context read masks them, so
  KV rollback is ``_truncate_slot``: whole tail pages past the
  frontier go back to the pool on block boundaries and
  ``pool.assert_consistent()`` holds after every drain_step, rejected
  runs included;
* greedy speculative output is bit-identical to vanilla decode
  (``extend_paged`` reproduces sequential decode exactly; acceptance
  only keeps argmax matches); draft quality moves ONLY the acceptance
  rate / tokens-per-round counters in ``stats()``, never the tokens.

Admission semantics (exact, see ``model.prefill(true_len=...)``)
----------------------------------------------------------------
* Prompts are right-padded to the smallest prefill bucket that fits and
  prefilled in one batch per bucket.  ``true_len`` makes the padding
  semantically invisible: admission logits are taken at the true last
  prompt token and pad positions never enter the decode state, so a
  5-token prompt in a 16-token bucket decodes bit-identically to an
  unpadded run.  Slot position starts at ``prefix + true_len`` (prefix =
  VLM image tokens), NOT at the bucket size.  (MoE caveat: expert
  capacity is computed from the static padded/batched shape, so token
  DROPPING under capacity pressure can differ from an unpadded run —
  see ``serving/__init__`` and ``moe._moe_tokens``.)
* Prompts longer than the largest bucket are chunked: the first
  ``max(prefill_buckets)`` tokens go through bucketed prefill, the rest
  catch up teacher-forced through the shared wave — ``spec_gamma``
  prompt tokens per multi-token extend wave on extend-capable configs
  (``model.extendable``: all attention families, paged and dense
  engines alike), one per decode step only on the recurrent families
  (ssm/hybrid).
  Sampled outputs are discarded until the prompt is consumed, and
  catch-up requests ride the same batch as running requests, so
  long-prompt admission never stalls other tenants.
* Preemption (``preempt``) extracts the slot's dense cache leaves and
  decode position onto the request and detaches its KV pages;
  re-admission reinserts them directly — no re-prefill, no page copies,
  no lost context.
* ``submit`` validates resumed requests too: a saved state with no room
  left to generate (``pos + pending >= max_len - 1``) or nothing left
  to generate is rejected instead of burning a slot.
* Sampling is per-request: ``Request.temperature`` / ``Request.top_k``
  override the engine-wide defaults inside the jitted decode step.
"""
from __future__ import annotations

import hashlib
import itertools
import os
from contextlib import nullcontext
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.models.layers import NarrowRows
from repro.serving.kv_pool import KVBlockPool, PoolExhausted, \
    blocks_for_tokens
from repro.serving.prefix_cache import (PrefixStoreError, RadixPrefixCache,
                                        dump_chains, load_store, save_store)
from repro.serving.telemetry import SLOT_TID0, MetricsRegistry, Tracer

# NOTE: repro.core.scheduler is imported lazily in _rank —
# core/__init__ pulls in hub.py, which imports this module back.

Params = Any

# Batch-axis discovery probes: the cache is shape-evaluated at TWO
# distinct batch sizes and the batch axis is the (unique) axis whose
# extent changed.  This cannot collide with any other cache dimension —
# the previous single-sentinel scheme (`shape.index(7777)`) silently
# picked the wrong axis whenever max_len/vocab/d_model happened to
# equal the sentinel.
_PROBE_A, _PROBE_B = 3, 5


def _diff_axis(a, b) -> int:
    """Axis where the two probe shapes differ; -1 when none does (a
    batchless shared-pool leaf)."""
    diffs = [i for i, (p, q) in enumerate(zip(a.shape, b.shape)) if p != q]
    if not diffs:
        return -1
    if len(diffs) > 1:
        raise ValueError(
            f"ambiguous batch axis: shapes {a.shape} / {b.shape} differ "
            f"on {diffs}")
    return diffs[0]


def cache_batch_axes(cfg: ModelConfig, max_len: int):
    """Pytree of ints: which axis of each cache leaf is the batch axis.

    Discovered structurally by shape-evaluating the cache at two batch
    sizes — no per-family bookkeeping, no sentinel collisions.
    """
    s1 = jax.eval_shape(partial(M.init_cache, cfg, _PROBE_A, max_len))
    s2 = jax.eval_shape(partial(M.init_cache, cfg, _PROBE_B, max_len))
    return jax.tree.map(_diff_axis, s1, s2)


def paged_cache_axes(cfg: ModelConfig, max_len: int, num_blocks: int,
                     block_size: int, kv_dtype=None):
    """Like ``cache_batch_axes`` for the paged cache: shared page-pool
    leaves have no batch axis and map to -1.  With ``kv_dtype="int8"``
    the probe includes the ``k_scale``/``v_scale`` pool leaves, which
    map to -1 like their int8 K/V twins — all generic pool-leaf
    machinery (CoW copies, chain gathers, persistence) rides on these
    axes and so covers the scales with no special cases."""
    s1 = jax.eval_shape(partial(M.init_paged_cache, cfg, _PROBE_A, max_len,
                                num_blocks, block_size, kv_dtype=kv_dtype))
    s2 = jax.eval_shape(partial(M.init_paged_cache, cfg, _PROBE_B, max_len,
                                num_blocks, block_size, kv_dtype=kv_dtype))
    return jax.tree.map(_diff_axis, s1, s2)


def insert_slot(cache, one, slot: int, axes):
    """Insert a batch=1 cache ``one`` into batched ``cache`` at ``slot``.
    Pool leaves (axis -1) are left untouched — their content lives in
    shared pages addressed by block tables, not per-slot strips."""
    return jax.tree.map(
        lambda full, single, ax: full if ax < 0 else
        jax.lax.dynamic_update_slice_in_dim(
            full, single.astype(full.dtype), slot, axis=ax),
        cache, one, axes)


def extract_slot(cache, slot: int, axes):
    """Slice a batch=1 cache out of batched ``cache`` at ``slot``
    (inverse of ``insert_slot`` — KV-preserving preemption).  Pool
    leaves yield an empty placeholder; their pages are detached via the
    block table instead of copied."""
    return jax.tree.map(
        lambda full, ax: jnp.zeros((0,), full.dtype) if ax < 0 else
        jax.lax.dynamic_slice_in_dim(full, slot, 1, axis=ax),
        cache, axes)


@dataclass
class Request:
    uid: int
    prompt: np.ndarray                  # (prompt_len,) int32
    max_new_tokens: int = 32
    priority: int = 0                   # higher = more urgent (QoE)
    deadline: Optional[float] = None    # for the "edf" admission policy
    temperature: Optional[float] = None  # None -> ServeConfig.temperature
    top_k: Optional[int] = None          # None -> ServeConfig.top_k
    extras: dict = field(default_factory=dict)  # image/audio embeds
    # filled by the engine:
    generated: list = field(default_factory=list)
    done: bool = False
    cancelled: bool = False             # set by engine.cancel(uid)
    arrival: Optional[float] = None     # submission stamp (engine-set)
    saved_state: Optional[dict] = None  # KV snapshot from preemption


@dataclass(frozen=True)
class ServeConfig:
    max_slots: int = 4
    max_len: int = 256
    temperature: float = 0.0            # 0 => greedy
    top_k: int = 0                      # 0 disables top-k filtering
    eos_id: int = -1                    # -1 disables EOS stopping
    prefill_buckets: tuple = (16, 32, 64, 128)
    policy: str = "priority"            # fifo | priority | edf (QoE)
    seed: int = 0
    # paged KV pool (tokens-in-flight memory ceiling instead of
    # max_slots * max_len strips); paged=False restores dense strips
    paged: bool = True
    kv_block_size: int = 16
    kv_pool_blocks: Optional[int] = None  # None -> max_slots*max_len/bs
    # radix prefix cache: finished chains stay indexed for copy-free
    # sharing (only engages on prefix-sharable configs, see
    # model.prefix_sharable; pages are reclaimed LRU under pressure).
    # Matching is TOKEN-granular (a hit may end mid-page; the partial
    # page is CoW-forked at admission) and live slots publish their
    # committed-prefix frontier every wave, so concurrent same-prefix
    # tenants share in flight, not just after the first one finishes.
    prefix_cache: bool = True
    # host-side prefix store: on close() the hot refcount-free chains
    # (token keys + page bytes) are serialized here, and a new engine
    # constructed with the same path rehydrates them — a restarted hub
    # serves warm-TTFT hits immediately.  A corrupt or mismatched-
    # config store is rejected cleanly (fresh cold start, no crash);
    # see serving/prefix_cache.py save_store/load_store.
    prefix_persist_path: Optional[str] = None
    # read paged decode KV through the Pallas paged_attention kernel
    # (scalar-prefetched block tables) instead of the jnp gather —
    # the TPU serving path; default off (gather is the portable twin)
    use_pallas_paged: bool = False
    # speculative decoding (serving/spec_decode.py): a resident draft
    # model proposes spec_gamma tokens per slot and the big model
    # verifies them in ONE extend_paged wave.  draft_arch: registry id
    # of the draft ("self" / None = early-exit self-draft; callers with
    # real draft weights pass `draft=(cfg, params)` to the engine).
    # Engages only on model.spec_decodable configs (quiet vanilla
    # fallback otherwise, mirroring prefix_cache) — on BOTH engines:
    # the dense paged=False twin speculates wave-for-wave identically
    # (slots-masked strips roll back like pages do).  An incompatible
    # draft (vocab mismatch, extras the requests cannot supply) or an
    # out-of-bounds gamma is rejected at engine construction.
    spec_decode: bool = False
    draft_arch: Optional[str] = None
    # also the chunk width of multi-token catch-up prefill (prompts
    # past the largest bucket consume spec_gamma prompt tokens per
    # extend wave instead of 1 per decode step)
    spec_gamma: int = 4
    # ---- step-driven wave plan (Sarathi-style chunked prefill) ----
    # chunked_prefill=True admits prompts as WAVE SPANS: a new
    # request's prompt enters through the same extend wave the decode
    # slots ride (no blocking bucketed prefill call at all for
    # token-only requests; requests carrying extras — VLM images,
    # enc-dec audio — still prefill one minimal bucket first, since
    # embeddings can only enter through prefill).  Prompt chunking is a
    # pure schedule change on extend-capable configs (extend is
    # bit-identical to sequential decode), but prefill and extend only
    # agree to float tolerance — so a chunked engine's tokens match a
    # chunked reference, not a prefill-admitted one.
    chunked_prefill: bool = False
    # max prompt tokens one catch-up slot consumes per extend wave
    # (None -> spec_gamma); raises the static extend width to
    # max(spec_gamma, catch_chunk)
    catch_chunk: Optional[int] = None
    # per-wave token budget across the live admit/decode frontier
    # (core.scheduler.plan_wave ranks slots by the admission policy and
    # shrinks catch-up / speculative widths to fit; every active slot
    # is always granted >= 1 token).  None = unbudgeted.
    wave_tokens: Optional[int] = None
    # prefix-cache admission floor: a radix match shorter than this
    # many tokens is treated as a miss (a 1-token accidental hit would
    # CoW-fork a page for near-zero reuse).  1 = accept any hit.
    min_match_tokens: int = 1
    # ---- quantized serving (capacity lever: edge hubs are pool-bound) --
    # quant_kv="int8" stores the paged pool's K/V as int8 with one f32
    # scale per (page, token-offset, kv-head) head_dim vector riding in
    # parallel k_scale/v_scale pool leaves (~4/head_dim byte overhead;
    # ~3.8x pool capacity at head_dim 64).  Scales are write-once like
    # the pages themselves, so CoW/rollback/in-flight sharing semantics
    # are unchanged, and persistence spills int8 bytes + scales (the
    # store header pins the layout: an f32<->int8 store mismatch is a
    # clean cold start).  Decode/extend logits shift within a small
    # per-family tolerance (gated in tests/test_engine_matrix.py, NOT
    # bit-exact); use_pallas_paged additionally fuses the dequant into
    # the Pallas paged decode AND extend kernels so the f32 pool never
    # materialises.  None = f32 pool, every path stays bit-exact.
    # Families with no pages (ssm, hybrid) quietly ignore it.
    quant_kv: Optional[str] = None
    # quantize the DRAFT model's weights to int8 (per-out-channel
    # scales, models.layers.quantize_matmul_params; TPU matmuls go
    # through the kernels.quant_matmul Pallas kernel).  Greedy spec
    # output stays BIT-exact — the verify model is untouched; only the
    # acceptance rate (perf, not correctness) can shift.  Rejected for
    # the early-exit self-draft, which shares the verify trunk by
    # reference (quantizing would materialise a copy instead of saving
    # memory).
    quant_draft: bool = False
    # ---- telemetry (serving/telemetry.py) ----
    # trace=True records engine-phase spans (admit / plan / dispatch /
    # device sync / retire / publish), per-slot residency tracks and
    # per-request lifecycle events (TTFT decomposition, ITL series,
    # per-round speculative acceptance), exported as Perfetto JSON via
    # engine.dump_chrome_trace(path); each phase span is also a
    # jax.profiler.TraceAnnotation (engine.plan, engine.dispatch, ...),
    # so a profiler trace puts device idle down to host phases.
    # Tracing only OBSERVES: generated tokens are bit-identical to an
    # untraced run.  The metrics registry (engine.metrics) is always on
    # — stats() is a compatibility view over it, traced or not.
    trace: bool = False
    # monotonic clock the tracer stamps against (None =
    # telemetry.default_clock, i.e. time.perf_counter).  Injectable so
    # a replayed trace — fed a deterministic fake clock — is
    # byte-reproducible in tests.
    trace_clock: Optional[Callable[[], float]] = None


class EdgeServingEngine:
    """Continuous-batching decode engine for one model on one device/mesh.

    ``draft``: optional ``(draft_cfg, draft_params)`` for speculative
    decoding — overrides ``ServeConfig.draft_arch`` (which builds a
    randomly-initialised registry smoke draft, or an early-exit
    self-draft for ``"self"``/``None``).
    """

    def __init__(self, cfg: ModelConfig, params: Params, scfg: ServeConfig,
                 draft=None):
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        B, T = scfg.max_slots, scfg.max_len
        bs = scfg.kv_block_size
        if scfg.quant_kv not in (None, "int8"):
            raise ValueError(
                f"quant_kv must be None or 'int8', got {scfg.quant_kv!r}")
        self.paged = bool(scfg.paged)
        # quantization only exists as a POOL layout; the dense twin
        # keeps f32 strips (it is the bit-exact reference the quantized
        # engine is tolerance-gated against)
        self.quant = bool(self.paged and scfg.quant_kv == "int8")
        if self.paged:
            if bs < 1:
                raise ValueError(f"kv_block_size must be >= 1, got {bs}")
            # the logical page view must tile max_len exactly (that is
            # what makes paged == dense bit-for-bit); shrink the block
            # size until it divides rather than reject the config
            while T % bs:
                bs //= 2
            self.n_blk = T // bs
            if scfg.kv_pool_blocks:
                # a user-set pool is a TOKEN budget: if the block size
                # shrank, keep blocks x block_size constant instead of
                # silently shrinking the budget by the same factor
                n_pool = scfg.kv_pool_blocks * scfg.kv_block_size // bs
            else:
                n_pool = B * self.n_blk
            axes = paged_cache_axes(cfg, T, n_pool, bs,
                                    kv_dtype=scfg.quant_kv)
            # families with no global KV layers (ssm, hybrid ring) have
            # zero pool demand — run them on the dense path outright
            self.paged = any(a < 0 for a in jax.tree.leaves(axes))
        self.block_size = bs              # effective page size
        self.quant = bool(self.paged and scfg.quant_kv == "int8")
        if self.paged:
            self.axes = axes
            self.pool = KVBlockPool(n_pool, bs)
            self.cache = M.init_paged_cache(cfg, B, T, n_pool, bs,
                                            kv_dtype=scfg.quant_kv)
            self.block_tables = np.full((B, self.n_blk), -1, np.int32)
            self.slot_blocks: list[list[int]] = [[] for _ in range(B)]
        else:
            self.pool = None
            self.cache = M.init_cache(cfg, B, T)
            self.axes = cache_batch_axes(cfg, T)
        # radix prefix cache: only for configs whose full decode state
        # lives in pages (model.prefix_sharable) — otherwise a hit
        # could not reconstruct ring/recurrent state and sharing would
        # change behaviour
        self.sharable = bool(self.paged and scfg.prefix_cache
                             and M.prefix_sharable(cfg))
        self.prefix_cache = (RadixPrefixCache(
            self.pool, bs, min_match_tokens=scfg.min_match_tokens)
            if self.sharable else None)
        # persistence: chains evicted under pressure are spilled to the
        # host (page bytes captured BEFORE the pool reclaims them) and
        # merged into the close()-time store; a store left by a previous
        # engine with the same path/config rehydrates below
        self._spilled: list = []
        self.persist_loaded_chains = 0
        self.persist_loaded_blocks = 0
        self.persist_rejected = ""
        if self.sharable and scfg.prefix_persist_path:
            self.prefix_cache.on_evict = self._spill_chain
            self._load_prefix_store(scfg.prefix_persist_path)
        # in-flight sharing: tokens (page-aligned) each slot has already
        # published to the radix tree; readers admitted below this
        # frontier share a chain that is STILL decoding
        self.slot_published = [0] * B
        self.published_frontiers = 0
        # multi-token extend path (speculative verify + chunked catch-up
        # consuming spec_gamma tokens per wave): every family that
        # implements extend/extend_paged, on BOTH engines (the dense
        # twin stays wave-for-wave identical to the paged one);
        # gemma-pattern local rings additionally need the chunk to fit
        # the window
        W = min(cfg.local_window, T)
        # static extend-wave width: gamma for speculation, or the
        # catch-up chunk if larger (chunked prefill wants wide catch
        # spans; at most two jit variants compile either way)
        self.K = max(scfg.spec_gamma, scfg.catch_chunk or 0)
        self.extend_ok = bool(M.extendable(cfg)
                              and self.K >= 2
                              and (cfg.pattern_period <= 1
                                   or self.K <= W))
        # packed extend waves: width-1 slots ride a decode-shaped row
        # group and wider spans a bucket of R wide rows (R the smallest
        # of the powers of two up to max_slots, and max_slots, that
        # holds them), on the paged engine of families whose
        # extend_paged takes both groups; the rest keep (B, K) waves
        self.packed = bool(self.extend_ok and self.paged
                           and M.extend_packable(cfg))
        self._wide_buckets = tuple(sorted(
            {1 << i for i in range(B.bit_length())} | {B}))
        self._extend_compiled: dict[tuple, Callable] = {}
        # Sarathi-style admission: prompts become wave spans (pending
        # catch-up from position 0 / the prefix-hit frontier) instead
        # of a blocking bucketed prefill.  Recurrent families with no
        # extend wave still honour the flag — their catch-up rides the
        # plain decode wave one token per step.
        self.chunked = bool(scfg.chunked_prefill)
        # speculative decoding: draft model + acceptance loop.  Engages
        # only where a rejected run can roll back exactly
        # (model.spec_decodable — mirrors the prefix_cache gate);
        # incompatible draft/gamma is a configuration ERROR.
        self.spec = None
        if scfg.spec_decode and M.spec_decodable(cfg):
            from repro.serving.spec_decode import (SpecDecoder,
                                                   make_self_draft,
                                                   validate_spec)
            if draft is not None:
                dcfg, dparams = draft
            elif scfg.draft_arch in (None, "self"):
                if scfg.quant_draft:
                    # the self-draft trunk IS the verify trunk (shared
                    # by reference) — quantizing it would materialise a
                    # private copy, the opposite of saving draft bytes
                    raise ValueError(
                        "quant_draft requires a separate draft model "
                        "(draft_arch or an explicit draft); the "
                        "early-exit self-draft shares the verify trunk "
                        "by reference")
                dcfg, dparams = make_self_draft(
                    cfg, params, key=jax.random.PRNGKey(scfg.seed))
            else:
                from repro.configs import get_smoke_config
                dcfg = get_smoke_config(scfg.draft_arch)
                dparams = M.init_params(dcfg,
                                        jax.random.PRNGKey(scfg.seed))
            problems = validate_spec(cfg, dcfg, scfg.spec_gamma, T)
            if problems:
                raise ValueError("spec_decode misconfigured: "
                                 + "; ".join(problems))
            if scfg.quant_draft:
                from repro.models.layers import quantize_matmul_params
                dparams = quantize_matmul_params(dparams)
            self.spec = SpecDecoder(dcfg, dparams, B, T)
        elif scfg.quant_draft and not scfg.spec_decode:
            raise ValueError("quant_draft without spec_decode: there is "
                             "no draft model to quantize")
        self.tokens = np.zeros((B, 1), np.int32)
        self.pos = np.zeros((B,), np.int32)
        self.temps = np.zeros((B,), np.float32)
        self.topks = np.zeros((B,), np.int32)
        self.active = np.zeros((B,), bool)
        self.slot_req: list[Optional[Request]] = [None] * B
        self.pending: list[Optional[np.ndarray]] = [None] * B
        self.queue: list[Request] = []
        self._key = jax.random.PRNGKey(scfg.seed)
        self._rng = np.random.default_rng(scfg.seed)   # admission sampling
        self._arrival = itertools.count()
        # specialized on the static any_topk flag: the all-greedy /
        # temperature-only path must not pay an O(B·V log V) vocab sort
        # per decoded token (at most two variants ever compile).
        # The cache buffers are DONATED: decode rewrites the KV state
        # in place instead of allocating a second copy every step,
        # halving decode HBM traffic (a no-op where the backend cannot
        # alias, e.g. CPU).
        self._decode = jax.jit(self._decode_fn,
                               static_argnames=("any_topk",),
                               donate_argnums=(1,))
        # per-pool-leaf page copy for copy-on-write forks (cache donated:
        # the fork rewrites one page in place, not a second pool copy)
        self._copy_page = (jax.jit(self._copy_page_fn, donate_argnums=(0,))
                           if self.paged else None)
        # multi-token extend wave (width K static; with and without the
        # full-logits return, and per wide-row bucket when packed)
        self._extend = (jax.jit(self._extend_fn, donate_argnums=(1,),
                                static_argnames=("need_logits",))
                        if self.extend_ok else None)
        self._prefills: dict[tuple, Callable] = {}
        self.steps = 0
        self.completed: list[Request] = []
        self.cancelled: list[Request] = []
        # step-driven observability: the last wave's per-slot plan
        # (mode, width) and how often prompt chunks actually interleave
        # with decode/spec slots in one wave
        self.last_plan: dict[int, tuple] = {}
        self.mixed_waves = 0
        self.wave_admitted = 0      # requests admitted as wave spans
        self.cancels = 0
        # observability: paged-admission effectiveness + pressure events
        self.peak_active = 0
        self.peak_pool_used = 0
        self.exhaust_preempts = 0
        self.reclaims = 0
        self.cow_forks = 0
        # speculative-decoding counters: rounds = (slot, wave) drafting
        # participations; proposed/accepted per round; emitted includes
        # the per-round correction/bonus token
        self.spec_steps = 0
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        # telemetry: the registry is ALWAYS on (stats() below is a
        # compatibility view over it); the tracer only with
        # ServeConfig.trace.  Counters stay plain attributes —
        # benchmarks/tests reset them by assignment (`eng.steps = 0`) —
        # and the registry reads them through callback gauges.
        self.metrics = MetricsRegistry()
        self.tracer = (Tracer(clock=scfg.trace_clock,
                              annotate=jax.profiler.TraceAnnotation)
                       if scfg.trace else None)
        self._legacy_stats = self._register_metrics()

    @property
    def _prefix(self) -> int:
        return self.cfg.num_image_tokens if self.cfg.family == "vlm" else 0

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def reset_rng(self) -> None:
        """Re-seed the sampling state (device PRNG key + admission rng)
        to the ServeConfig seed.  Benchmarks call this after a warmup
        pass so a temperature>0 measured run samples the same tokens a
        cold engine would — replay determinism."""
        self._key = jax.random.PRNGKey(self.scfg.seed)
        self._rng = np.random.default_rng(self.scfg.seed)

    def submit(self, req: Request) -> None:
        limit = self.scfg.max_len - 1 - self._prefix
        if req.saved_state is None:
            if len(req.prompt) > limit:
                raise ValueError(
                    f"prompt length {len(req.prompt)} exceeds max_len "
                    f"budget {limit} (max_len={self.scfg.max_len})")
            worst = self._prefix + len(req.prompt) + req.max_new_tokens
        else:
            st = req.saved_state
            pend = st.get("pending")
            n_pend = 0 if pend is None else int(np.size(pend))
            if len(req.generated) >= req.max_new_tokens:
                raise ValueError(
                    f"resumed request {req.uid} already generated "
                    f"{len(req.generated)}/{req.max_new_tokens} tokens — "
                    "nothing left to decode")
            if int(st["pos"]) + n_pend >= self.scfg.max_len - 1:
                raise ValueError(
                    f"resumed request {req.uid} cannot make progress: "
                    f"pos {int(st['pos'])} + pending {n_pend} >= "
                    f"max_len-1 ({self.scfg.max_len - 1}); it would burn "
                    "a prefill-free slot and finish with zero new tokens")
            worst = (int(st["pos"]) + n_pend + 1
                     + req.max_new_tokens - len(req.generated))
        if self.paged:
            need = blocks_for_tokens(min(worst, self.scfg.max_len),
                                     self.block_size)
            if need > self.pool.num_blocks:
                raise ValueError(
                    f"request {req.uid} may need {need} KV blocks but the "
                    f"pool holds only {self.pool.num_blocks} "
                    f"(kv_pool_blocks); it could never finish")
        if req.arrival is None:
            req.arrival = float(next(self._arrival))
        if self.tracer is not None:
            fresh = req.saved_state is None and not req.generated
            self._revent(req, "submit" if fresh else "resubmit",
                         prompt_tokens=len(req.prompt))
            self._revent(req, "queued", depth=len(self.queue) + 1)
        self.queue.append(req)

    def _rank(self, req: Request):
        from repro.core.scheduler import admission_rank
        return admission_rank(self.scfg.policy, priority=req.priority,
                              arrival=req.arrival, deadline=req.deadline,
                              uid=req.uid)

    def _bucket(self, n: int) -> int:
        for b in self.scfg.prefill_buckets:
            if n <= b:
                return b
        return self.scfg.prefill_buckets[-1]

    def _prefill_fn(self, bucket: int, m: int, extras_sig: tuple,
                    n_ctx: int):
        """Jitted fused admission prefill, cached per (bucket, batch,
        extras, ctx-width) — prompt K/V is written straight into the
        engine cache (pages + slot rows) in the same call, and the
        cache buffers are donated so admission updates them in place.

        ``n_ctx``: static width (in blocks) of the shared-prefix FULL
        tables (context + write span in one view — token-granular hits
        write mid-page through the same table they read); 0 compiles
        the cold no-context variant.
        """
        key = (bucket, m, extras_sig, n_ctx, self.paged)
        if key not in self._prefills:
            cfg, scfg, paged = self.cfg, self.scfg, self.paged

            if n_ctx:
                def fn(params, batch, true_len, cache, slots,
                       full_tables, ctx_len):
                    return M.prefill_paged(
                        cfg, params, batch, scfg.max_len, cache,
                        slots=slots, write_tables=full_tables,
                        ctx_tables=full_tables, ctx_len=ctx_len,
                        true_len=true_len)
            elif paged:
                def fn(params, batch, true_len, cache, slots,
                       write_tables):
                    return M.prefill_paged(
                        cfg, params, batch, scfg.max_len, cache,
                        slots=slots, write_tables=write_tables,
                        true_len=true_len)
            else:
                def fn(params, batch, true_len, cache, slots):
                    return M.prefill_paged(
                        cfg, params, batch, scfg.max_len, cache,
                        slots=slots, true_len=true_len)

            self._prefills[key] = jax.jit(fn, donate_argnums=(3,))
        return self._prefills[key]

    def _sample_first(self, req: Request, logits: np.ndarray) -> int:
        """First generated token, from the admission logits (host-side,
        engine-rng — deterministic for a fixed ServeConfig.seed)."""
        from repro.serving.spec_decode import sample_from_logits
        temp = (self.scfg.temperature if req.temperature is None
                else req.temperature)
        top_k = self.scfg.top_k if req.top_k is None else req.top_k
        return sample_from_logits(logits, temp, top_k, self._rng)

    # -- prefix-cache keys ---------------------------------------------
    def _key_ns(self, req: Request) -> int:
        """Namespace digest for non-token inputs: requests whose K/V
        depends on more than the token ids (VLM images, enc-dec audio)
        only ever share with requests carrying identical extras.
        Memoized on the request — extras are immutable for its
        lifetime, and ``_publish_frontiers`` asks once per page
        crossing (hashing a VLM image tensor per wave would be pure
        rework on the decode loop)."""
        ns = getattr(req, "_ns_digest", None)
        if ns is not None:
            return ns
        if not req.extras:
            ns = 0
        else:
            h = hashlib.sha1()
            for k in sorted(req.extras):
                h.update(k.encode())
                h.update(np.ascontiguousarray(req.extras[k]).tobytes())
            ns = int.from_bytes(h.digest()[:8], "little") & (2 ** 63 - 1)
        req._ns_digest = ns
        return ns

    def _key_tokens(self, req: Request) -> np.ndarray:
        """Logical token sequence whose positions map 1:1 onto the
        slot's pages: VLM image positions become pseudo-tokens (the
        namespace digest already pins the image identity), then the
        prompt, then tokens generated so far (KV-valid prefix of it is
        taken by the caller)."""
        parts = [np.full((self._prefix,), -42, np.int64),
                 np.asarray(req.prompt, np.int64)]
        folded = getattr(req, "_folded_generated", 0)
        if len(req.generated) > folded:
            parts.append(np.asarray(req.generated[folded:], np.int64))
        return np.concatenate(parts)

    def _lookup(self, req: Request) -> None:
        """Radix lookup for a fresh request: acquire (incref) the
        longest usable shared chain — TOKEN-granular, possibly ending
        mid-page and possibly inside a chain another slot is still
        decoding — and stash it on the request for the admission pass.
        Capped at one token short of the prompt (the suffix prefill
        must produce admission logits) and — for VLM — at least the
        image prefix (a shorter match cannot seed a text-only suffix
        prefill)."""
        self._release_ctx(req)          # drop any stale acquisition
        if not self.sharable or req.saved_state is not None:
            return
        key = np.concatenate([np.full((self._prefix,), -42, np.int64),
                              np.asarray(req.prompt, np.int64)])
        blocks, n = self.prefix_cache.match(
            key, namespace=self._key_ns(req), max_tokens=len(key) - 1)
        if n and n < self._prefix:
            self.pool.free(blocks)
            self.prefix_cache.unrecord_hit(
                len(blocks), n, (n // self.block_size) * self.block_size)
            blocks, n = [], 0
        req._ctx_blocks = blocks
        req._ctx_len = n

    def _release_ctx(self, req: Request) -> None:
        """Drop an acquired-but-unused shared chain (request skipped by
        this admission round; the next round re-acquires) — and roll
        the hit accounting back so retries don't inflate the stats."""
        blocks = getattr(req, "_ctx_blocks", None)
        if blocks:
            n = req._ctx_len
            self.pool.free(blocks)
            self.prefix_cache.unrecord_hit(
                len(blocks), n, (n // self.block_size) * self.block_size)
        req._ctx_blocks, req._ctx_len = [], 0

    # -- paged-pool bookkeeping ----------------------------------------
    def _first_span(self, req: Request, suffix_len: int) -> int:
        """Tokens the request's FIRST admission step covers: the full
        bucketed prefill normally; under chunked_prefill just the first
        wave span (the extend chunk width, or one decode token on
        recurrent families) — extras-carrying requests still prefill,
        but only the smallest bucket."""
        if self.chunked:
            if req.extras:
                return min(suffix_len, self.scfg.prefill_buckets[0])
            return min(suffix_len, self.K if self.extend_ok else 1)
        return min(suffix_len, self.scfg.prefill_buckets[-1])

    def _blocks_needed(self, req: Request) -> int:
        """New pool blocks this request needs to be admitted NOW (the
        first admission span's pages + one covering the next write;
        resumed requests already hold pages for [0, pos), prefix-cache
        hits already hold the shared chain's pages).  Chunked-prefill
        admission reserves only the first wave's span — later chunks
        allocate wave by wave (preempt-or-queue backstops a pool that
        fills in between)."""
        if not self.paged:
            return 0
        bs = self.block_size
        if req.saved_state is not None:
            held = len(req.saved_state.get("blocks", ()))
            return max(0, blocks_for_tokens(
                int(req.saved_state["pos"]) + 1, bs) - held)
        L = getattr(req, "_ctx_len", 0)
        if L:
            # token-granular hits: floor(L/bs) pages are shared whole;
            # a partial final page (L % bs != 0) is counted as NEEDED
            # because admission CoW-forks it (the fork's alloc draws
            # one page from the free list)
            suffix = len(req.prompt) - (L - self._prefix)
            n1 = self._first_span(req, suffix)
            return blocks_for_tokens(L + n1 + 1, bs) - L // bs
        n1 = self._first_span(req, len(req.prompt))
        return blocks_for_tokens(self._prefix + n1 + 1, bs)

    def _reserve(self, n: int) -> bool:
        """Make ``n`` pool pages allocatable, evicting LRU prefix-cache
        chains if the free list alone is short."""
        if not self.paged:
            return True
        short = n - self.pool.num_free
        if short > 0 and self.prefix_cache is not None:
            self.prefix_cache.evict(short)
        return self.pool.num_free >= n

    def _avail_blocks(self) -> int:
        """Pages admission may count on: free now + evictable now."""
        if not self.paged:
            return 0
        extra = (self.prefix_cache.evictable_blocks()
                 if self.prefix_cache is not None else 0)
        return self.pool.num_free + extra

    def _set_table(self, slot: int, blocks: list[int]) -> None:
        self.slot_blocks[slot] = blocks
        self.block_tables[slot, :] = -1
        self.block_tables[slot, :len(blocks)] = blocks

    def _place(self, req: Request, slot: int) -> None:
        """Common slot bookkeeping after cache insertion."""
        self.temps[slot] = (self.scfg.temperature if req.temperature is None
                            else req.temperature)
        self.topks[slot] = self.scfg.top_k if req.top_k is None else req.top_k
        self.active[slot] = True
        self.slot_req[slot] = req

    def _admit_resumed(self, req: Request, slot: int) -> None:
        need = self._blocks_needed(req)   # same formula the scan reserved
        self._slot_begin(req, slot)
        self._revent(req, "resume", slot=slot)
        st = req.saved_state
        req.saved_state = None
        if self.paged:
            blocks = list(st.get("blocks", ()))
            if need:  # feasibility pre-checked by the admission scan
                self._reserve(need)
                blocks += self.pool.alloc(need)
            self._set_table(slot, blocks)
        self.cache = insert_slot(self.cache, st["cache"], slot, self.axes)
        if self.spec is not None:
            self.spec.insert(slot, st.get("draft"))
        self.pos[slot] = st["pos"]
        self.tokens[slot, 0] = st["last_tok"]
        self.pending[slot] = st["pending"]
        self._place(req, slot)
        # resume in-flight publication where the preempted slot left it
        # (re-publishing would only dedup, but skip the wasted walks)
        self.slot_published[slot] = int(st.get("published", 0))

    def _admit_wave(self, req: Request, slot: int) -> None:
        """Chunked-prefill admission: NO prefill call — the prompt (or
        the unmatched suffix after a radix hit) becomes the slot's
        pending span and is consumed through the same decode/extend
        waves every other slot rides, ``_first_span`` tokens per wave.
        Shared context pages attach exactly as the prefill path would;
        the first wave's ``_ensure_blocks``/``_cow_guard`` allocate
        fresh pages and CoW-fork a partially-matched tail page on
        demand.  The first generated token is sampled from the wave row
        that consumes the last prompt token (the existing catch-up
        retirement), so admission never blocks in-flight decoders."""
        L = getattr(req, "_ctx_len", 0)
        self._slot_begin(req, slot)
        self._revent(req, "admitted", slot=slot, mode="wave",
                     prefix_hit_tokens=L)
        if self.paged:
            ctx = getattr(req, "_ctx_blocks", None) or []
            self._set_table(slot, list(ctx))
        req._ctx_blocks, req._ctx_len = [], 0
        suffix = np.asarray(req.prompt, np.int32)[max(0, L - self._prefix):]
        if self.spec is not None:
            # the draft still prefills the full prompt (it is cheap and
            # never chunks) so the slot is draft-complete by the time
            # its prompt is consumed — same contract as bucketed
            # catch-up admission
            self.spec.admit_group([req], [slot])
        self.pos[slot] = L
        self.tokens[slot, 0] = int(suffix[0])
        self.pending[slot] = suffix[1:]
        self._place(req, slot)
        # the matched prefix is already indexed (that is what we hit) —
        # publication resumes from its page boundary
        self.slot_published[slot] = (L // self.block_size
                                     * self.block_size)
        self.wave_admitted += 1

    @staticmethod
    def _pow2(n: int) -> int:
        return 1 << (n - 1).bit_length() if n > 1 else n

    def _admit_batch(self) -> None:
        """Admit queued requests into free slots, batching prefill per
        (bucket, extras, shared-context width) group — one compile +
        one device call per group.

        Capacity-aware: a request is taken only if the pool can cover
        its suffix pages + first decode write, counting radix-cache
        pages evictable right now as available.  Fresh requests are
        looked up in the prefix cache first: a hit pins the shared
        chain (incref) and shrinks both the pages needed and the
        prefill to the unmatched suffix.  Requests that don't fit right
        now are skipped, NOT dropped — they wait for pages to free
        (best-effort packing under memory pressure; admission order
        within the feasible set still follows admission_rank)."""
        if not self.queue:
            return
        free = [s for s in range(self.scfg.max_slots) if not self.active[s]]
        if not free:
            return
        self.queue.sort(key=self._rank)
        avail = self._avail_blocks()
        taken, kept = [], []
        for req in self.queue:
            if not free:
                kept.append(req)
                continue
            self._lookup(req)
            need = self._blocks_needed(req)
            # pinning a hit chain removes its pages from the evictable
            # set, so they count against this round's budget too — but
            # ONLY pages this lookup actually pinned (refcount exactly
            # 2 = cache + us; pages another reader already pins were
            # never in the evictable count)
            pinned = sum(1 for b in (getattr(req, "_ctx_blocks", None)
                                     or ())
                         if self.pool.refcount(b) == 2)
            if self.paged and need + pinned > avail:
                self._release_ctx(req)
                kept.append(req)
                continue
            avail -= need + pinned
            taken.append((req, free.pop(0)))
        self.queue = kept

        fresh: dict[tuple, list] = {}   # group key -> [(req, slot)]
        for req, slot in taken:
            if req.saved_state is not None:
                self._admit_resumed(req, slot)
                continue
            if self.chunked and not req.extras:
                self._admit_wave(req, slot)
                continue
            L = getattr(req, "_ctx_len", 0)
            n1 = self._first_span(
                req, len(req.prompt) - max(0, L - self._prefix))
            bucket = self._bucket(n1)
            sig = tuple(sorted(
                (k, np.asarray(v).shape) for k, v in req.extras.items()))
            # hit rows read AND write through one full table covering
            # [0, L + n1) — pow2-bucketed so mixed-depth hits share a
            # compile
            n_ctx = (self._pow2(blocks_for_tokens(L + n1, self.block_size))
                     if L else 0)
            fresh.setdefault((bucket, sig, n_ctx), []).append((req, slot))

        for (bucket, sig, n_ctx), group in fresh.items():
            self._admit_group(bucket, sig, n_ctx, group)

    def _admit_group(self, bucket: int, extras_sig: tuple, n_ctx: int,
                     group) -> None:
        """One fused admission call: batched (suffix-)prefill that
        writes prompt K/V straight into pages + slot rows.  ``n_ctx``
        > 0 means every row is a prefix-cache hit admitted at its
        shared chain's end position — which, with token-granular
        matching, may be MID-page: the partial page is CoW-forked here
        (private copy) so the suffix write never lands in a page the
        cache or another reader still holds."""
        bs = self.block_size
        if self.paged:
            # allocation pass first: a row whose pages cannot be
            # covered even after eviction (a chain pinned mid-scan ate
            # the budget) goes back to the queue instead of raising
            admitted = []
            for req, slot in group:
                need = self._blocks_needed(req)
                L = getattr(req, "_ctx_len", 0)
                try:
                    self._reserve(need)
                    fresh_n = need
                    if L % bs:
                        # fork the partially-matched final page: trade
                        # the reader's ref on the shared page for a
                        # private copy the suffix may write into.
                        # `need` already counts this page, so the fresh
                        # alloc shrinks by one either way: normally the
                        # fork draws that page itself (cache + reader
                        # refs), and if the cache released its ref
                        # mid-scan (a retire upgraded the tail) fork
                        # hands back the now-private page with no
                        # allocation at all.
                        fresh_n = need - 1
                        old = req._ctx_blocks[-1]
                        new = self.pool.fork(old)
                        if new != old:
                            self.cache = self._copy_page(
                                self.cache, jnp.asarray(old),
                                jnp.asarray(new))
                            req._ctx_blocks[-1] = new
                            self.cow_forks += 1
                            self._revent(req, "cow_fork", slot=slot)
                    fresh_alloc = self.pool.alloc(fresh_n)
                except PoolExhausted:
                    self._release_ctx(req)
                    self.queue.append(req)
                    continue
                ctx = getattr(req, "_ctx_blocks", None) or []
                self._set_table(slot, list(ctx) + fresh_alloc)
                admitted.append((req, slot))
            group = admitted
            if not group:
                return
        for req, slot in group:
            self._slot_begin(req, slot)
            self._revent(req, "admitted", slot=slot, mode="prefill",
                         prefix_hit_tokens=getattr(req, "_ctx_len", 0))
        m = len(group)
        prompts = np.zeros((m, bucket), np.int32)
        true_len = np.zeros((m,), np.int32)
        ctx_len = np.zeros((m,), np.int32)
        # hit rows: ONE full table per row (context + write span, from
        # logical block 0) — reads mask below ctx_len, writes scatter
        # from ctx_len; cold rows: a write-span table from block 0
        # including the VLM image prefix
        span = self._prefix + bucket
        n_wblk = n_ctx if n_ctx else blocks_for_tokens(span, bs)
        tables = np.full((m, n_wblk), -1, np.int32)
        suffixes = []
        for i, (req, slot) in enumerate(group):
            L = getattr(req, "_ctx_len", 0)
            suffix = np.asarray(req.prompt, np.int32)[max(0, L - self._prefix):]
            suffixes.append(suffix)
            n1 = min(len(suffix), bucket)
            # pad value is irrelevant (true_len masks it) — repeat last tok
            prompts[i] = suffix[n1 - 1]
            prompts[i, :n1] = suffix[:n1]
            true_len[i] = n1
            ctx_len[i] = L
            if self.paged:
                blk = self.slot_blocks[slot][:n_wblk]
                tables[i, :len(blk)] = blk
        # prefill_dispatch holds the uploads, prefill_sync the copy
        # back, as the decode and extend waves' dispatch and sync do
        with self._span("prefill_dispatch", bucket=bucket, rows=m):
            batch = {"tokens": jnp.asarray(prompts)}
            for k, _ in extras_sig:
                batch[k] = jnp.asarray(
                    np.stack([np.asarray(r.extras[k]) for r, _ in group]))
            slots_arr = jnp.asarray([s for _, s in group], jnp.int32)
            args = [self.params, batch, jnp.asarray(true_len), self.cache,
                    slots_arr]
            if self.paged:
                args.append(jnp.asarray(tables))
            if n_ctx:
                args.append(jnp.asarray(ctx_len))
            logits, self.cache = self._prefill_fn(bucket, m, extras_sig,
                                                  n_ctx)(*args)
        if self.spec is not None:
            # the draft prefills the FULL prompt (it is cheap and never
            # chunks), so catch-up slots are already draft-complete by
            # the time their prompt is consumed
            self.spec.admit_group([r for r, _ in group],
                                  [s for _, s in group])
        with self._span("prefill_sync"):
            logits_host = np.asarray(logits[:, -1], np.float32)  # (m, V)
        for i, (req, slot) in enumerate(group):
            L = int(ctx_len[i])
            n1 = int(true_len[i])
            req._ctx_blocks, req._ctx_len = [], 0
            remainder = suffixes[i][n1:]
            self._revent(req, "prefill_chunk", slot=slot, n=n1)
            tok = None
            if not remainder.size:
                self._revent(req, "prompt_done", slot=slot)
                tok = self._sample_first(req, logits_host[i])
                req.generated.append(tok)
                self._rtokens(req, slot, 1)
                hit_eos = (self.scfg.eos_id >= 0
                           and tok == self.scfg.eos_id)
                if len(req.generated) >= req.max_new_tokens or hit_eos:
                    # the admission token already completed the request
                    # — it never occupies a slot or a decode step, but
                    # its pages DO hold a fully valid chain: index it
                    if self.paged:
                        n_valid = (L if L else self._prefix) + n1
                        self._retire_chain(req, self.slot_blocks[slot],
                                           n_valid)
                        self._set_table(slot, [])
                    req.done = True
                    self.completed.append(req)
                    self._revent(req, "finish", slot=slot,
                                 n_generated=len(req.generated))
                    self._slot_end(slot)
                    continue
            self.pos[slot] = (L if L else self._prefix) + n1
            if remainder.size:
                # chunked prefill: catch up through the decode wave
                self.pending[slot] = remainder[1:]
                self.tokens[slot, 0] = int(remainder[0])
            else:
                self.pending[slot] = None
                self.tokens[slot, 0] = tok
            self._place(req, slot)
            # the matched prefix is already indexed (that is what we
            # hit) — publication resumes from its page boundary
            self.slot_published[slot] = (L // self.block_size
                                         * self.block_size)

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _decode_fn(self, params, cache, tokens, pos, temps, topks, key,
                   block_tables=None, any_topk: bool = False):
        if block_tables is None:
            logits, new_cache = M.decode_step(self.cfg, params, cache,
                                              tokens, pos)
        else:
            logits, new_cache = M.decode_step_paged(
                self.cfg, params, cache, tokens, pos, block_tables,
                self.scfg.use_pallas_paged)
        logits = logits[:, -1, :].astype(jnp.float32)          # (B, V)
        greedy = jnp.argmax(logits, axis=-1)
        masked = logits
        if any_topk:
            V = logits.shape[-1]
            desc = jnp.sort(logits, axis=-1)[:, ::-1]
            kth = jnp.take_along_axis(
                desc, jnp.clip(topks - 1, 0, V - 1)[:, None], axis=1)
            masked = jnp.where((topks > 0)[:, None] & (logits < kth),
                               -jnp.inf, logits)
        scaled = masked / jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.random.categorical(key, scaled, axis=-1)
        nxt = jnp.where(temps > 0, sampled, greedy)
        return nxt.astype(jnp.int32), new_cache

    def _extend_fn(self, params, cache, tokens, pos, valid, block_tables,
                   narrow=None, need_logits: bool = False):
        """Multi-token wave: score up to K tokens per row in one call
        (speculative verify / chunked catch-up).  ``tokens`` (R, K),
        ``pos``, ``valid`` and ``block_tables`` are the wave's rows —
        every slot's (B, K) block, or, with ``narrow``
        (``layers.NarrowRows``), only the R wide rows beside the
        slots' width-1 row group (``model.extend_paged``'s packed
        layout).  Acceptance and sampling are host-side with the engine
        rng — which keeps greedy spec bit-identical to vanilla (argmax
        is rounding-free) and rejection sampling deterministic per
        seed — but an all-greedy wave ships only the per-row argmax
        ids; the float32 logits cross the device boundary only when
        some active slot samples at temperature > 0 (at real vocab
        sizes that transfer dominates the wave).  Outputs are arrays,
        or (narrow, wide) pairs when packed.

        The benchmark's trace reader (``bench/trace.py``) finds this
        wave's device time by the name ``_extend_fn`` in the jitted
        module's name: keep the name."""
        if block_tables is None:
            logits, new_cache = M.extend(self.cfg, params, cache, tokens,
                                         pos, valid)
        else:
            logits, new_cache = M.extend_paged(self.cfg, params, cache,
                                               tokens, pos, block_tables,
                                               valid,
                                               self.scfg.use_pallas_paged,
                                               narrow=narrow)
        logits = jax.tree.map(lambda a: a.astype(jnp.float32), logits)
        greedy = jax.tree.map(
            lambda a: jnp.argmax(a, axis=-1).astype(jnp.int32), logits)
        return greedy, (logits if need_logits else None), new_cache

    def _run_extend(self, args: tuple, need_logits: bool):
        """Dispatch one extend wave.  A packed engine compiles every
        wide-row bucket ahead of time at its first wave (per
        ``need_logits``), so no later wave compiles: a server's warm-up
        then carries every bucket's compile."""
        if not self.packed:
            return self._extend(*args, need_logits=need_logits)
        key = (args[2].shape[0], need_logits)
        if key not in self._extend_compiled:
            for r in self._wide_buckets:
                wide = [jax.ShapeDtypeStruct((r,) + a.shape[1:], a.dtype)
                        for a in args[2:6]]
                self._extend_compiled[(r, need_logits)] = self._extend.lower(
                    *args[:2], *wide, *args[6:],
                    need_logits=need_logits).compile()
        return self._extend_compiled[key](*args)

    def _ensure_blocks(self, spans: Optional[dict] = None) -> None:
        """Guarantee every active slot's table covers its write span
        ``[pos, pos + span)`` (span 1 = plain decode; an extend wave
        passes its per-slot widths).  Crossing block boundaries appends
        pages (evicting LRU prefix-cache chains first under pressure);
        if the pool is truly exhausted the slot is preempted back to
        the queue (pages detached) — preempt-or-queue, never a deadlock
        spin.  Best-ranked slots get first pick of the remaining pages.
        """
        bs = self.block_size
        spans = spans or {}
        needy = []
        for s in range(self.scfg.max_slots):
            if not self.active[s]:
                continue
            target = blocks_for_tokens(
                int(self.pos[s]) + spans.get(s, 1), bs)
            if target > len(self.slot_blocks[s]):
                needy.append((s, target))
        needy.sort(key=lambda t: self._rank(self.slot_req[t[0]]))
        for s, target in needy:
            n = target - len(self.slot_blocks[s])
            try:
                self._reserve(n)
                blk = self.pool.alloc(n)
            except PoolExhausted:
                req = self.preempt(s)
                self.exhaust_preempts += 1
                self.queue.append(req)   # resumes when a page frees
                continue
            j0 = len(self.slot_blocks[s])
            self.slot_blocks[s].extend(blk)
            self.block_tables[s, j0:j0 + n] = blk

    def _copy_page_fn(self, cache, src, dst):
        """Device-side page copy (every pool leaf) for CoW forks."""
        return jax.tree.map(
            lambda leaf, ax: leaf if ax >= 0 else
            leaf.at[:, dst].set(leaf[:, src]),
            cache, self.axes)

    def _cow_guard(self, spans: Optional[dict] = None) -> None:
        """Copy-on-write backstop: no decode/extend wave may write a
        page with more than one owner — a speculative verify over a
        shared prefix-cache chain must fork, never scribble into a
        reader's pages.  Block-granular prefix matching means the write
        span normally lands in private pages (suffixes start at the
        next block boundary), so this almost never fires — but any
        sharer of a TAIL page (token-granular matching, beam forks, a
        spec round whose span begins mid-shared-block) is caught here:
        the slot trades its reference for a fresh page
        (``KVBlockPool.fork``) and copies the page bytes.  On pool
        exhaustion the slot preempts, like ``_ensure_blocks``.
        """
        bs = self.block_size
        spans = spans or {}
        for s in range(self.scfg.max_slots):
            if not self.active[s]:
                continue
            j0 = int(self.pos[s]) // bs
            j1 = min((int(self.pos[s]) + spans.get(s, 1) - 1) // bs,
                     len(self.slot_blocks[s]) - 1)
            for j in range(j0, j1 + 1):
                old = self.slot_blocks[s][j]
                if self.pool.refcount(old) <= 1:
                    continue
                try:
                    self._reserve(1)
                    new = self.pool.fork(old)
                except PoolExhausted:
                    req = self.preempt(s)
                    self.exhaust_preempts += 1
                    self.queue.append(req)
                    break
                self.cache = self._copy_page(self.cache, jnp.asarray(old),
                                             jnp.asarray(new))
                self.slot_blocks[s][j] = new
                self.block_tables[s, j] = new
                self.cow_forks += 1
                self._revent(self.slot_req[s], "cow_fork", slot=s)

    def _has_pending(self) -> bool:
        return any(self.active[s] and self.pending[s] is not None
                   and self.pending[s].size
                   for s in range(self.scfg.max_slots))

    def _apply_budget(self, plan: dict) -> dict:
        """Wave-token budget: shrink catch-up / speculative widths so
        the wave's total fed tokens fit ``ServeConfig.wave_tokens``,
        granting best-QoE-rank first (``core.scheduler.plan_wave``;
        every slot keeps width >= 1 — liveness).  Width is a pure
        schedule lever: shrinking a span never changes the tokens a
        request emits, so QoE shaping here cannot cause token drift."""
        if self.scfg.wave_tokens is None or not plan:
            return plan
        from repro.core.scheduler import plan_wave
        entries = []
        for s, (mode, want) in plan.items():
            r = self.slot_req[s]
            entries.append({"id": s, "want": want, "priority": r.priority,
                            "arrival": r.arrival, "deadline": r.deadline,
                            "uid": r.uid})
        widths = plan_wave(self.scfg.policy, entries,
                           self.scfg.wave_tokens, metrics=self.metrics)
        out = {}
        for s, (mode, want) in plan.items():
            v = min(want, widths[s])
            if mode == "spec" and v < 2:
                # a 1-wide speculative round is just a decode
                mode, v = "plain", 1
            out[s] = (mode, v)
        return out

    def _record_plan(self, plan: dict) -> None:
        """Wave-plan observability: keep the committed plan
        (``last_plan``, read by ``scripts/diagnose.py --server``) and
        count waves where a prompt chunk actually interleaved with a
        decoding/speculating slot — the Sarathi property the open-loop
        benchmark gates on."""
        self.last_plan = dict(plan)
        modes = {m for m, _ in plan.values()}
        if "catch" in modes and len(modes) > 1:
            self.mixed_waves += 1

    def step(self) -> int:
        """ONE step of the always-on serving core — no drain
        assumption; an asyncio frontend (``launch.serve``) calls this
        forever, interleaving arrivals and cancellations between waves:

        * **admit** — rank the queue (``admission_rank``), place what
          fits (capacity-aware); under ``chunked_prefill`` a prompt
          becomes a pending wave span instead of a blocking bucketed
          prefill (``_admit_wave``);
        * **plan** — pick the wave type (multi-token extend while any
          slot speculates or catches up, one-token decode otherwise)
          and per-slot widths, budgeted by ``wave_tokens``
          (``_apply_budget`` -> ``core.scheduler.plan_wave``);
        * **wave** — ONE jitted device call for every active slot;
        * **retire** — sample/accept per slot, finish on EOS / budget /
          room, publish in-flight prefix frontiers.

        Pool-wedge recovery is part of the step contract: when nothing
        stepped but requests are queued and every page is held by
        detached (preempted) requests, the worst-ranked holder is
        force-reclaimed so an always-on loop cannot spin idle.  Returns
        the number of active slots stepped (0 = idle).
        """
        with self._span("step", step=self.steps):
            with self._span("admit", queued=len(self.queue)):
                self._admit_batch()
            if self.extend_ok and (self.spec is not None
                                   or self._has_pending()):
                stepped = self._extend_step()
            else:
                stepped = self._decode_wave()
            if (stepped == 0 and self.paged and self.queue
                    and not self.active.any()):
                # requests requeued by _ensure_blocks mid-step (after
                # this step's admission pass) may need zero new pages —
                # give admission one more look before reclaiming
                with self._span("admit", queued=len(self.queue)):
                    self._admit_batch()
                if not self.active.any():
                    # every queued request is blocked on pool pages held
                    # by detached requests: force-reclaim the worst one
                    self._reclaim()
        return stepped

    def _decode_wave(self) -> int:
        """The plain one-token wave: plan is implicit (every active
        slot has width 1; slots still consuming a prompt on a
        non-extendable family teacher-force one pending token)."""
        with self._span("plan"):
            if self.paged:
                self._ensure_blocks()
                self._cow_guard()
            self._record_plan({
                s: (("catch", 1) if (self.pending[s] is not None
                                     and self.pending[s].size) else
                    ("plain", 1))
                for s in range(self.scfg.max_slots) if self.active[s]})
            n_active = int(self.active.sum())
            if n_active == 0:
                return 0
            self.peak_active = max(self.peak_active, n_active)
            if self.paged:
                self.peak_pool_used = max(self.peak_pool_used,
                                          self.pool.num_used)

        # dispatch holds the uploads, sync the wait and the copy back:
        # the phases leave no host work of the wave outside a span
        with self._span("dispatch", mode="decode", rows=n_active):
            self._key, sub = jax.random.split(self._key)
            any_topk = bool((self.topks[self.active] > 0).any())
            tables = (jnp.asarray(self.block_tables) if self.paged
                      else None)
            nxt, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(self.tokens),
                jnp.asarray(self.pos), jnp.asarray(self.temps),
                jnp.asarray(self.topks), sub, tables, any_topk=any_topk)
        with self._span("sync"):
            nxt_host = np.asarray(nxt)
        with self._span("retire"):
            for slot in range(self.scfg.max_slots):
                if not self.active[slot]:
                    continue
                self.pos[slot] += 1
                req = self.slot_req[slot]
                pend = self.pending[slot]
                out_of_room = int(self.pos[slot]) >= self.scfg.max_len - 1
                if pend is not None and pend.size:
                    # still consuming the prompt: teacher-force the next
                    # prompt token, discard the sampled one
                    self._revent(req, "prefill_chunk", slot=slot, n=1)
                    self.tokens[slot, 0] = int(pend[0])
                    self.pending[slot] = pend[1:]
                    if out_of_room:
                        self._finish(slot, req)
                    continue
                if pend is not None:
                    # the wave that consumed the last prompt token
                    self._revent(req, "prompt_done", slot=slot)
                self.pending[slot] = None
                tok = int(nxt_host[slot])
                self.tokens[slot, 0] = tok
                req.generated.append(tok)
                self._rtokens(req, slot, 1)
                hit_eos = (self.scfg.eos_id >= 0
                           and tok == self.scfg.eos_id)
                if (len(req.generated) >= req.max_new_tokens or hit_eos
                        or out_of_room):
                    self._finish(slot, req)
        with self._span("publish"):
            self._publish_frontiers()
        self.steps += 1
        return n_active

    def _truncate_slot(self, slot: int) -> None:
        """KV rollback: free the slot's pages past its write frontier
        (block-boundary granular).  After a rejected speculation the
        stale K/V above ``pos`` is already invisible (the extend/decode
        context masks strictly below the frontier), so rollback is pure
        bookkeeping — return whole tail pages, keep the partial one the
        next write lands in."""
        if not self.paged:
            return
        keep = blocks_for_tokens(int(self.pos[slot]) + 1, self.block_size)
        blocks = self.slot_blocks[slot]
        if len(blocks) > keep:
            self.pool.free(blocks[keep:])
            self._set_table(slot, blocks[:keep])

    def _extend_step(self) -> int:
        """One multi-token wave: plan per-slot widths, draft proposals
        for speculative slots, verify/teacher-force everything in a
        single ``extend_paged`` call, then accept/rollback.

        Slot modes — ``spec`` (no pending prompt, speculative engine):
        feed ``[t0, d_1..d_{v-1}]``, judge proposals, emit
        ``n_accepted + 1`` tokens; ``catch``: teacher-force the next
        ``v`` pending prompt tokens (sampled rows discarded until the
        prompt is consumed — the multi-token retirement of the old
        1-token-per-step catch-up); ``plain``: a slot out of room for
        proposals rides along at width 1 (vanilla semantics).
        """
        from repro.serving.spec_decode import (accept_greedy,
                                               accept_proposals,
                                               sample_from_logits)
        B, K = self.scfg.max_slots, self.K
        gamma = self.scfg.spec_gamma
        eos = self.scfg.eos_id
        with self._span("plan"):
            plan: dict[int, tuple] = {}
            for s in range(B):
                if not self.active[s]:
                    continue
                pend = self.pending[s]
                npend = 0 if pend is None else int(pend.size)
                room = self.scfg.max_len - 1 - int(self.pos[s])
                if npend:
                    plan[s] = ("catch", max(1, min(1 + npend, K, room)))
                elif self.spec is not None and min(gamma, room) >= 2:
                    plan[s] = ("spec", min(gamma, room))
                else:
                    plan[s] = ("plain", 1)
            plan = self._apply_budget(plan)
            if self.paged:
                spans = {s: v for s, (_, v) in plan.items()}
                self._ensure_blocks(spans)
                self._cow_guard(spans)
                plan = {s: p for s, p in plan.items() if self.active[s]}
            self._record_plan(plan)
            n_active = int(self.active.sum())
            if n_active == 0:
                return 0
            self.peak_active = max(self.peak_active, n_active)
            if self.paged:
                self.peak_pool_used = max(self.peak_pool_used,
                                          self.pool.num_used)
        # the wave's rows: packed, each slot's width-1 row plus R wide
        # rows for the slots fed more than one token; else (B, K)
        wide = ([s for s, (_, v) in plan.items() if v > 1] if self.packed
                else list(range(B)))
        R = next(b for b in self._wide_buckets if b >= len(wide))
        # positions the wave computes, and those that carry a token
        # (model.extend_pad_share reads the padding off these two)
        n_fed = sum(v for _, v in plan.values())
        self.metrics.counter("engine.extend_positions").inc(
            B + R * K if self.packed else B * K)
        self.metrics.counter("engine.extend_fed").inc(n_fed)
        self.metrics.counter("engine.extend_rows").inc(R)

        spec_slots = [s for s, (m, _) in plan.items() if m == "spec"]
        proposals, dists = {}, {}
        if spec_slots:
            # draft only as wide as the widest planned spec span — a
            # budget-shrunk round must not burn draft steps it cannot
            # verify
            k_spec = max(v for s, (m, v) in plan.items() if m == "spec")
            with self._span("draft", slots=len(spec_slots), width=k_spec):
                proposals, dists = self.spec.propose(
                    spec_slots, self.tokens[:, 0], self.temps,
                    self.topks, k_spec, self._rng)

        # all-greedy waves ship only the argmax ids
        need_logits = bool((self.temps[self.active] > 0).any())
        with self._span("dispatch", mode="extend", rows=n_active,
                        fed_tokens=n_fed, rows_wide=R):
            fed = np.zeros((R, K), np.int32)
            valid = (np.zeros((R,), np.int32) if self.packed
                     else np.ones((R,), np.int32))
            for r, s in enumerate(wide):
                if s not in plan:
                    continue
                mode, v = plan[s]
                seq = [int(self.tokens[s, 0])]
                if mode == "catch":
                    seq += [int(t) for t in self.pending[s][:v - 1]]
                elif mode == "spec":
                    seq += proposals[s][:v - 1]
                fed[r, :len(seq)] = seq
                fed[r, len(seq):] = seq[-1]       # pad (write-dropped)
                valid[r] = v
            narrow = None
            if self.packed:
                # a wide slot's narrow row and an idle slot's feed no
                # token: valid 0 drops their writes; pad wide rows
                # carry no table
                tables = np.full((R, self.n_blk), -1, np.int32)
                tables[:len(wide)] = self.block_tables[wide]
                pos = np.zeros((R,), np.int32)
                pos[:len(wide)] = self.pos[wide]
                narrow = NarrowRows(
                    jnp.asarray(self.tokens), jnp.asarray(self.pos),
                    jnp.asarray(self.block_tables),
                    jnp.asarray(np.array(
                        [s in plan and plan[s][1] == 1 for s in range(B)],
                        np.int32)))
                tables = jnp.asarray(tables)
            else:
                pos = self.pos
                tables = (jnp.asarray(self.block_tables) if self.paged
                          else None)
            greedy, logits, self.cache = self._run_extend(
                (self.params, self.cache, jnp.asarray(fed),
                 jnp.asarray(pos), jnp.asarray(valid), tables, narrow),
                need_logits)
        with self._span("sync"):
            greedy = jax.tree.map(np.asarray, greedy)
            if need_logits:
                logits = jax.tree.map(
                    lambda a: np.asarray(a, np.float32), logits)
        # each slot's outputs: its wide row, or its narrow row (width 1)
        row = {s: r for r, s in enumerate(wide)}
        if self.packed:
            def rows_of(out, s):
                return out[1][row[s]] if s in row else out[0][s]
        else:
            def rows_of(out, s):
                return out[s]
        greedy = {s: rows_of(greedy, s) for s in plan}
        if need_logits:
            logits = {s: rows_of(logits, s) for s in plan}

        def sample(s, i, temp, top_k):
            if temp <= 0:
                return int(greedy[s][i])
            return sample_from_logits(logits[s][i], temp, top_k,
                                      self._rng)

        any_spec = False
        with self._span("retire"):
            for s in range(B):
                if s not in plan or not self.active[s]:
                    continue
                mode, v = plan[s]
                req = self.slot_req[s]
                temp, top_k = float(self.temps[s]), int(self.topks[s])
                if mode == "catch":
                    self._revent(req, "prefill_chunk", slot=s, n=v)
                    self.pos[s] += v
                    rest = self.pending[s][v - 1:]
                    out_of_room = (int(self.pos[s])
                                   >= self.scfg.max_len - 1)
                    if rest.size:
                        self.tokens[s, 0] = int(rest[0])
                        self.pending[s] = rest[1:]
                        if out_of_room:
                            self._finish(s, req)
                        continue
                    self._revent(req, "prompt_done", slot=s)
                    self.pending[s] = None
                    tok = sample(s, v - 1, temp, top_k)
                    self.tokens[s, 0] = tok
                    req.generated.append(tok)
                    self._rtokens(req, s, 1)
                    hit_eos = eos >= 0 and tok == eos
                    if (len(req.generated) >= req.max_new_tokens
                            or hit_eos or out_of_room):
                        self._finish(s, req)
                    continue
                if mode == "plain":
                    self.pos[s] += 1
                    tok = sample(s, 0, temp, top_k)
                    self.tokens[s, 0] = tok
                    req.generated.append(tok)
                    self._rtokens(req, s, 1)
                    hit_eos = eos >= 0 and tok == eos
                    if (len(req.generated) >= req.max_new_tokens
                            or hit_eos
                            or int(self.pos[s]) >= self.scfg.max_len - 1):
                        self._finish(s, req)
                    continue
                # speculative round
                any_spec = True
                if temp <= 0:
                    n_acc, emitted = accept_greedy(proposals[s][:v - 1],
                                                   greedy[s][:v])
                else:
                    n_acc, emitted = accept_proposals(
                        proposals[s][:v - 1], dists[s][:v - 1],
                        logits[s][:v], temp, top_k, self._rng)
                self.spec.advance(s, n_acc + 1)
                self.spec_rounds += 1
                self.spec_proposed += v - 1
                self.spec_accepted += n_acc
                # acceptance by draft depth (registry counters) + the
                # per-request round log the trace summaries aggregate
                for j in range(v - 1):
                    self.metrics.counter(f"spec.depth{j}.proposed").inc()
                for j in range(n_acc):
                    self.metrics.counter(f"spec.depth{j}.accepted").inc()
                self._revent(req, "spec_round", slot=s, proposed=v - 1,
                             accepted=n_acc)
                # budget/EOS truncation (both imply the request
                # finishes)
                emit = emitted[:req.max_new_tokens - len(req.generated)]
                if eos >= 0 and eos in emit:
                    emit = emit[:emit.index(eos) + 1]
                req.generated.extend(emit)
                self.spec_emitted += len(emit)
                self._rtokens(req, s, len(emit))
                # frontier: every emitted token except a final
                # correction/bonus was fed (and written) this wave
                self.pos[s] += min(len(emit) + 1, n_acc + 1)
                if (len(req.generated) >= req.max_new_tokens
                        or (eos >= 0 and emit and emit[-1] == eos)
                        or int(self.pos[s]) >= self.scfg.max_len - 1):
                    self._finish(s, req)
                else:
                    self.tokens[s, 0] = emit[-1]
                    self._truncate_slot(s)   # rejected-tail pages back
        if any_spec:
            self.spec_steps += 1
        with self._span("publish"):
            self._publish_frontiers()
        self.steps += 1
        return n_active

    def _publish_frontiers(self) -> None:
        """In-flight sharing: after every committed wave, publish each
        live slot's full pages below its frontier (``pos`` rounded down
        to a page boundary) into the radix tree.  The cache takes its
        own reference (``share`` + ``insert``; duplicates of the slot's
        earlier publications come straight back and are released), so a
        later request can hit a chain that is STILL decoding: readers
        pin pages strictly below the frontier, the writer only ever
        writes at/above ``pos``, and spec-decode rollback
        (``_truncate_slot``) only frees pages above ``pos`` — published
        pages are never written or yanked.  Published pages show
        refcount 2 (slot + cache) while the slot runs, so eviction and
        the admission budget both already treat them as pinned."""
        if self.prefix_cache is None:
            return
        bs = self.block_size
        for s in range(self.scfg.max_slots):
            if not self.active[s] or self.slot_req[s] is None:
                continue
            frontier = (int(self.pos[s]) // bs) * bs
            if frontier <= self.slot_published[s]:
                continue
            req = self.slot_req[s]
            key = self._key_tokens(req)
            n_blk = frontier // bs
            if len(key) < frontier or len(self.slot_blocks[s]) < n_blk:
                continue                      # reclaim-rebuilt slot mid-fold
            blocks = self.slot_blocks[s][:n_blk]
            self.pool.share(blocks)
            dups = self.prefix_cache.insert(key[:frontier], blocks,
                                            namespace=self._key_ns(req))
            self.pool.free(dups)
            self.slot_published[s] = frontier
            self.published_frontiers += 1

    def _retire_chain(self, req: Request, blocks: list[int],
                      n_valid: int) -> None:
        """Return a finished request's pages: index the chain (the
        first ``n_valid`` token positions hold valid K/V — INCLUDING a
        partial tail page, which token-granular matching can now serve)
        in the radix cache — adopting the engine's references — and
        free any duplicates of an already-indexed prefix plus pages
        past the valid span.  Non-sharable configs free everything, as
        before."""
        if not self.sharable or not blocks:
            self.pool.free(blocks)
            return
        key = self._key_tokens(req)[:n_valid]
        nb = blocks_for_tokens(n_valid, self.block_size)
        leftovers = self.prefix_cache.insert(
            key, blocks[:nb], namespace=self._key_ns(req))
        self.pool.free(list(leftovers) + list(blocks[nb:]))

    def _finish(self, slot: int, req: Request) -> None:
        self._revent(req, "finish", slot=slot,
                     n_generated=len(req.generated))
        self._slot_end(slot)
        req.done = True
        self.completed.append(req)
        self.active[slot] = False
        self.slot_req[slot] = None
        self.pending[slot] = None
        self.slot_published[slot] = 0
        if self.paged:
            # KV is valid for [0, pos): everything written by prefill,
            # catch-up and decode waves (the final sampled token was
            # never fed back, so pos stops short of it)
            self._retire_chain(req, self.slot_blocks[slot],
                               int(self.pos[slot]))
            self._set_table(slot, [])

    # ------------------------------------------------------------------
    # prefix-store persistence (warm TTFT across engine restarts)
    # ------------------------------------------------------------------
    def _persist_meta(self) -> dict:
        """Prefix-store header: page geometry, a config digest and a
        params fingerprint.  ``load_store`` refuses a store whose
        header differs — persisted KV bytes are only valid for the
        exact (config, params, page layout) that produced them."""
        from repro.serving.prefix_cache import PERSIST_VERSION
        cfg_digest = hashlib.sha1(repr(self.cfg).encode()).hexdigest()
        fp = hashlib.sha1()
        for lv in jax.tree.leaves(self.params):
            # shape/dtype of every leaf plus a value sample FROM every
            # leaf: a checkpoint that differs anywhere (partial
            # fine-tune, different seed) must trip the fingerprint —
            # persisted KV is a function of the weights
            fp.update(str((tuple(lv.shape), str(lv.dtype))).encode())
            fp.update(np.asarray(jnp.ravel(lv)[:64]).tobytes())
        sig = []
        for lv, ax in zip(jax.tree.leaves(self.cache),
                          jax.tree.leaves(self.axes)):
            if ax < 0:              # pool leaf: (stack, nB, bs, kv...)
                shape = lv.shape[:1] + lv.shape[2:]
                sig.append([list(shape), str(lv.dtype)])
        return {"version": PERSIST_VERSION, "config": cfg_digest,
                "params": fp.hexdigest(), "block_size": self.block_size,
                # pins the pool quant layout explicitly (the leaf sigs
                # already differ — int8 dtypes + extra scale leaves —
                # but the key makes an f32<->int8 mismatch legible in
                # the rejection reason): spilled stores carry int8 page
                # bytes + scales and are only valid for the same layout
                "quant_kv": self.scfg.quant_kv if self.quant else None,
                "leaves": sig}

    def _chain_pages_host(self, blocks) -> list[np.ndarray]:
        """Gather one chain's page bytes to the host: one
        ``(stack, n_chain_blocks, block_size, kv...)`` array per pool
        leaf, in cache-leaf order."""
        ids = np.asarray(blocks, np.int32)
        return [np.asarray(lv[:, ids])
                for lv, ax in zip(jax.tree.leaves(self.cache),
                                  jax.tree.leaves(self.axes)) if ax < 0]

    def _spill_chain(self, ns: int, key, n_leaf: int, blocks) -> None:
        """``RadixPrefixCache.on_evict`` hook (persist mode only):
        capture an evicted chain's pages BEFORE the pool reclaims them
        so pressure-evicted chains still make it into the close()-time
        store.  Spill is capped at one pool's worth of pages — beyond
        that a restart could not rehydrate them anyway."""
        held = sum(blocks_for_tokens(len(k), self.block_size)
                   for _, k, _ in self._spilled)
        if held + len(blocks) > self.pool.num_blocks:
            return
        self._spilled.append((ns, np.asarray(key, np.int64),
                              self._chain_pages_host(blocks)))

    def close(self) -> dict:
        """Flush the radix cache's hot refcount-free chains (plus any
        pressure-spilled ones) to ``ServeConfig.prefix_persist_path``
        so the NEXT engine with this path starts with a warm cache.
        Safe to call on any engine (no-op without a path / on
        non-sharable configs); returns a save summary."""
        path = self.scfg.prefix_persist_path
        if not path or not self.sharable:
            return {"persist_saved_chains": 0, "persist_saved_blocks": 0}
        # resident chains carry their block ids; spilled chains already
        # carry host page bytes.  Dedup on (namespace, key) FIRST —
        # gathering device pages for a chain the dedup would discard is
        # pure wasted transfer at shutdown.
        cand = [(ns, key, ("blocks", blocks))
                for ns, key, blocks in
                dump_chains(self.prefix_cache,
                            max_blocks=self.pool.num_blocks)]
        cand += [(ns, key, ("pages", pages))
                 for ns, key, pages in self._spilled]
        # BIDIRECTIONAL prefix dedup (exact duplicates keep the first,
        # hot-first, occurrence): a chain that is a prefix of any other
        # stored chain is fully covered by it — same tokens produce the
        # same KV bytes — and a store holding both a partial-tail chain
        # AND its extension would drive insert's replacement path at
        # rehydrate (page churn for nothing).
        chains = []
        for i, (ns, key, payload) in enumerate(cand):
            covered = False
            for j, (n2, k2, _) in enumerate(cand):
                if j == i or n2 != ns or len(key) > len(k2):
                    continue
                if len(key) == len(k2) and j > i:
                    continue                   # equal twins: first wins
                if np.array_equal(key, k2[:len(key)]):
                    covered = True
                    break
            if not covered:
                kind, data = payload
                pages = (self._chain_pages_host(data) if kind == "blocks"
                         else data)
                chains.append((ns, key, pages))
        info = save_store(path, self._persist_meta(), chains)
        return {"persist_saved_chains": info["chains"],
                "persist_saved_blocks": info["blocks"]}

    def _load_prefix_store(self, path: str) -> None:
        """Rehydrate a persisted prefix store at construction: allocate
        pool pages, scatter the stored page bytes into the device cache
        and index the chains in the radix tree.  A mismatched or
        corrupt store is REJECTED (reason in ``persist_rejected`` /
        ``stats()``) and the engine simply starts cold."""
        if not os.path.exists(path):
            return
        try:
            chains = load_store(path, self._persist_meta())
        except PrefixStoreError as e:
            self.persist_rejected = str(e)
            return
        leaves, treedef = jax.tree_util.tree_flatten(self.cache)
        axes = jax.tree.leaves(self.axes)
        pool_idx = [i for i, a in enumerate(axes) if a < 0]
        # physical page id -> per-pool-leaf page bytes.  LAST write wins:
        # a page freed mid-load (insert's dup return, or its internal
        # partial-tail replacement) can be re-alloc'd by a later chain,
        # and that later chain owns the page — dict overwrite keeps
        # exactly its payload, never a stale one, and the final
        # refcount filter drops pages that ended up back in the pool.
        pending: dict[int, list[np.ndarray]] = {}
        for ns, key, pages in chains:        # hot-first store order
            nb = blocks_for_tokens(len(key), self.block_size)
            if not self.pool.can_alloc(nb):
                continue
            ids = self.pool.alloc(nb)
            dups = self.prefix_cache.insert(key, ids, namespace=ns)
            nd = len(dups)       # dups are always a PREFIX of ids
            for k in range(nd, nb):
                pending[ids[k]] = [pages[j][:, k]
                                   for j in range(len(pool_idx))]
            self.pool.free(dups)
            self.persist_loaded_chains += 1
            self.persist_loaded_blocks += nb - nd
        pending = {bid: v for bid, v in pending.items()
                   if self.pool.refcount(bid) > 0}
        if pending:
            # ONE scatter per pool leaf — per-chain .at[].set would copy
            # the full (possibly multi-GB) pool tensor once per chain
            order = list(pending)
            arr = np.asarray(order, np.int32)
            for j, li in enumerate(pool_idx):
                chunk = np.stack([pending[b][j] for b in order], axis=1)
                leaves[li] = leaves[li].at[:, arr].set(
                    jnp.asarray(chunk, leaves[li].dtype))
        self.cache = jax.tree_util.tree_unflatten(treedef, leaves)

    # ------------------------------------------------------------------
    # telemetry (serving/telemetry.py)
    # ------------------------------------------------------------------
    def _register_metrics(self) -> dict:
        """Register every serving counter/gauge into the metrics
        registry and return the ``stats()`` compatibility map
        ``{legacy_key: metric_name}`` — built per config axis exactly
        like the historical ad-hoc dict, so the view's key set and
        values are unchanged (snapshot-tested in
        ``tests/test_telemetry.py``).  Counters that tests reset by
        assignment stay plain attributes; the registry samples them via
        callback gauges."""
        m, legacy = self.metrics, {}

        def view(key: str, name: str, fn) -> None:
            m.gauge(name, fn)
            legacy[key] = name

        view("steps", "engine.steps", lambda: self.steps)
        view("peak_active", "engine.peak_active", lambda: self.peak_active)
        view("peak_pool_used", "engine.peak_pool_used",
             lambda: self.peak_pool_used)
        view("exhaust_preempts", "engine.exhaust_preempts",
             lambda: self.exhaust_preempts)
        view("reclaims", "engine.reclaims", lambda: self.reclaims)
        view("cow_forks", "engine.cow_forks", lambda: self.cow_forks)
        view("mixed_waves", "engine.mixed_waves", lambda: self.mixed_waves)
        view("wave_admitted", "engine.wave_admitted",
             lambda: self.wave_admitted)
        view("cancels", "engine.cancels", lambda: self.cancels)
        if self.paged:
            self.pool.attach_metrics(m)
            legacy.update(pool_blocks="kv_pool.blocks",
                          pool_free="kv_pool.free",
                          pool_shared="kv_pool.shared")
        if self.quant or self.scfg.quant_draft:
            from repro.serving.kv_pool import page_bytes
            view("quant_kv", "quant.kv", lambda: self.scfg.quant_kv or "")
            view("quant_draft", "quant.draft",
                 lambda: bool(self.scfg.quant_draft
                              and self.spec is not None))
            # deterministic capacity facts for the baseline gate:
            # bytes of one page under this layout vs f32
            view("quant_page_bytes", "quant.page_bytes",
                 lambda: page_bytes(self.cfg, self.block_size,
                                    self.scfg.quant_kv
                                    if self.quant else None))
            view("quant_f32_page_bytes", "quant.f32_page_bytes",
                 lambda: page_bytes(self.cfg, self.block_size, None))
        if self.scfg.spec_decode:
            view("spec_active", "spec.active",
                 lambda: self.spec is not None)
            view("spec_steps", "spec.steps", lambda: self.spec_steps)
            view("spec_rounds", "spec.rounds", lambda: self.spec_rounds)
            view("spec_proposed", "spec.proposed",
                 lambda: self.spec_proposed)
            view("spec_accepted", "spec.accepted",
                 lambda: self.spec_accepted)
            view("spec_emitted", "spec.emitted", lambda: self.spec_emitted)
            view("spec_acceptance", "spec.acceptance",
                 lambda: self.spec_accepted / max(self.spec_proposed, 1))
            # mean big-model tokens emitted per verify round per slot:
            # 1.0 = vanilla; > 1 = speculation paying off
            view("spec_tokens_per_round", "spec.tokens_per_round",
                 lambda: self.spec_emitted / max(self.spec_rounds, 1))
            # acceptance by DRAFT DEPTH: position j of a proposal
            # within its round (acceptance decays with depth — the
            # signal that picks gamma); bumped in _extend_step
            for j in range(max(self.scfg.spec_gamma - 1, 0)):
                m.counter(f"spec.depth{j}.proposed")
                m.counter(f"spec.depth{j}.accepted")
        if self.prefix_cache is not None:
            for k in self.prefix_cache.attach_metrics(m):
                legacy[f"prefix_{k}"] = f"prefix_cache.{k}"
            view("published_frontiers", "engine.published_frontiers",
                 lambda: self.published_frontiers)
            if self.scfg.prefix_persist_path:
                view("persist_loaded_chains", "persist.loaded_chains",
                     lambda: self.persist_loaded_chains)
                view("persist_loaded_blocks", "persist.loaded_blocks",
                     lambda: self.persist_loaded_blocks)
                view("persist_spilled_chains", "persist.spilled_chains",
                     lambda: len(self._spilled))
                view("persist_rejected", "persist.rejected",
                     lambda: self.persist_rejected)
        return legacy

    def _span(self, name: str, **args):
        """Engine-phase span when tracing; a free no-op context
        otherwise (the untraced step path stays branch-for-branch what
        it was)."""
        if self.tracer is None:
            return nullcontext()
        return self.tracer.span(name, **args)

    def _revent(self, req: Request, name: str, slot: Optional[int] = None,
                **args) -> None:
        """Per-request lifecycle event (no-op untraced): mirrored onto
        the slot's track when resident, the frontend track otherwise."""
        if self.tracer is not None:
            self.tracer.req_event(
                req.uid, name,
                tid=None if slot is None else SLOT_TID0 + slot, **args)

    def _rtokens(self, req: Request, slot: int, n: int) -> None:
        """Token-retirement stamps, called AFTER appending ``n`` tokens
        to ``req.generated``: ``first_token`` closes the TTFT
        decomposition, ``tokens`` feeds the per-request ITL series."""
        if self.tracer is None or n <= 0:
            return
        if len(req.generated) == n:
            self._revent(req, "first_token", slot=slot)
        self._revent(req, "tokens", slot=slot, n=n)

    def _slot_begin(self, req: Request, slot: int) -> None:
        """Open the slot-residency span on the slot's trace track."""
        if self.tracer is not None:
            tid = SLOT_TID0 + slot
            self.tracer.name_track(tid, f"slot{slot}")
            self.tracer.begin(f"u{req.uid}", tid, uid=req.uid)

    def _slot_end(self, slot: int) -> None:
        if self.tracer is not None:
            self.tracer.end(SLOT_TID0 + slot)

    def dump_chrome_trace(self, path: str) -> dict:
        """Write the tracer's Perfetto / chrome://tracing JSON dump to
        ``path`` (engine phases on one track, one track per slot,
        per-request ``request_summary`` instants carrying the TTFT
        decomposition).  Requires ``ServeConfig.trace=True``."""
        if self.tracer is None:
            raise ValueError(
                "tracing is off — construct the engine with "
                "ServeConfig(trace=True) to record a trace")
        return self.tracer.dump_chrome_trace(path)

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        """Pool / prefix-cache observability — a compatibility VIEW
        over the metrics registry (``serving/telemetry.py``): same keys
        and values as the historical ad-hoc dict, now read through the
        registered gauges so every subsystem reports through one path.
        Every call re-checks the pool accounting invariant (free +
        refcounted == total)."""
        if self.paged:
            self.pool.assert_consistent()
        return {key: self.metrics.get(name)
                for key, name in self._legacy_stats.items()}

    # ------------------------------------------------------------------
    def cancel(self, uid: int) -> bool:
        """Abort a request mid-flight — queued, preempted-and-detached,
        mid-catch-up, mid-speculation or plain decoding.  Returns True
        when the request was found (it is marked ``cancelled`` + ``done``
        and moved to ``self.cancelled``, never ``completed``).

        KV semantics mirror ``_finish``: a live slot's pages below
        ``pos`` hold a fully valid chain and retire into the radix
        cache (published frontier pages keep their cache reference, so
        in-flight readers of the cancelled chain are untouched);
        non-sharable configs free everything.  A stale draft row needs
        no cleanup (re-admission rewrites it), and no wave ever sees
        the slot again — cancellation between waves can never roll back
        tokens already delivered.
        """
        for i, req in enumerate(self.queue):
            if req.uid != uid:
                continue
            self.queue.pop(i)
            if self.sharable:
                self._release_ctx(req)       # drop a pinned hit chain
            st = req.saved_state
            if st is not None:
                req.saved_state = None
                if self.paged:
                    self.pool.free(st.get("blocks", ()))
            self._mark_cancelled(req)
            return True
        for s in range(self.scfg.max_slots):
            req = self.slot_req[s]
            if not self.active[s] or req is None or req.uid != uid:
                continue
            self._slot_end(s)
            self.active[s] = False
            self.slot_req[s] = None
            self.pending[s] = None
            self.slot_published[s] = 0
            if self.paged:
                self._retire_chain(req, self.slot_blocks[s],
                                   int(self.pos[s]))
                self._set_table(s, [])
            self._mark_cancelled(req)
            return True
        return False

    def _mark_cancelled(self, req: Request) -> None:
        self._revent(req, "cancel", n_generated=len(req.generated))
        req.done = True
        req.cancelled = True
        self.cancelled.append(req)
        self.cancels += 1

    # ------------------------------------------------------------------
    def preempt(self, slot: int) -> Optional[Request]:
        """Evict a running request (scheduler-driven preemption), taking
        its dense cache leaves and decode position with it; its KV pages
        stay in the pool, DETACHED onto the request — re-submission
        restores the block table and resumes decode exactly where it
        stopped, with NO re-prefill and no page copies."""
        req = self.slot_req[slot]
        if req is None:
            return None
        self._revent(req, "preempt", slot=slot)
        self._slot_end(slot)
        req.saved_state = {
            "cache": extract_slot(self.cache, slot, self.axes),
            "pos": int(self.pos[slot]),
            "last_tok": int(self.tokens[slot, 0]),
            "pending": self.pending[slot],
        }
        if self.spec is not None:
            req.saved_state["draft"] = self.spec.extract(slot)
        if self.paged:
            req.saved_state["blocks"] = self.slot_blocks[slot]
            req.saved_state["published"] = self.slot_published[slot]
            self._set_table(slot, [])
        self.active[slot] = False
        self.slot_req[slot] = None
        self.pending[slot] = None
        self.slot_published[slot] = 0
        return req

    # ------------------------------------------------------------------
    def _drop_saved(self, req: Request) -> None:
        """Forced reclaim under pool exhaustion: release the detached
        pages and rebuild the request as a fresh catch-up prompt
        (original prompt + tokens generated so far).  Re-prefill IS
        required for this one request — the escape hatch that keeps
        ``run_until_drained`` live when detached holders own every page.
        The exact context is replayed, but prefill and decode logits
        only agree to bf16 tolerance, so a greedy tie can flip: the
        contract here is liveness + correct token budget, not the
        bit-exactness the detach/resume path guarantees."""
        st = req.saved_state
        req.saved_state = None
        self.pool.free(st.get("blocks", ()))
        # fold only the not-yet-folded suffix of generated into the
        # replay prompt: a request reclaimed twice must not see its
        # first batch of generated tokens duplicated in the context
        folded = getattr(req, "_folded_generated", 0)
        fresh = req.generated[folded:]
        if fresh:
            req.prompt = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(fresh, np.int32)])
            req._folded_generated = len(req.generated)

    def _reclaim(self) -> None:
        holders = [r for r in self.queue
                   if r.saved_state is not None
                   and r.saved_state.get("blocks")]
        if not holders:
            raise RuntimeError(
                "serving pool wedged: no active slots, queue non-empty, "
                "and no detached pages to reclaim (pool misconfigured?)")
        victim = max(holders, key=self._rank)   # worst-ranked holder
        self._drop_saved(victim)
        self.reclaims += 1

    def drain_step(self) -> int:
        """One ``step()`` with the pool accounting invariant re-checked
        after it — the unit of progress ``run_until_drained`` iterates
        (pool-wedge recovery now lives in ``step()`` itself, so bare
        ``step()`` loops — the asyncio frontend — are equally live)."""
        stepped = self.step()
        if self.paged:
            self.pool.assert_consistent()   # accounting drift backstop
        return stepped

    def run_until_drained(self, max_steps: int = 10_000) -> list[Request]:
        while (self.queue or self.active.any()) and self.steps < max_steps:
            self.drain_step()
        return self.completed
