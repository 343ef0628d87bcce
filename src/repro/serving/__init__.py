"""EdgeAI-Hub serving runtime — continuous batching for one model.

Admission semantics (the contract tests rely on)
------------------------------------------------
* **Paged KV cache.** Global attention layers store K/V in a shared
  pool of ``ServeConfig.kv_block_size``-token pages
  (``serving.kv_pool.KVBlockPool`` + ``models.layers.init_kv_pages``)
  instead of one dense ``max_len`` strip per slot; each slot's ordered
  page list is mirrored to the device as an int32 block table consumed
  by ``model.decode_step_paged``.  Admission is capacity-aware (enough
  FREE PAGES, not merely a free slot), decode appends a page on block
  boundary crossing, and on pool exhaustion a slot is preempted back to
  the queue with its pages detached (preempt-or-queue — no deadlock;
  ``run_until_drained`` force-reclaims a detached holder only when
  nothing else can run).  The logical page view equals ``max_len``, so
  paged decode is bit-for-bit identical to the dense path
  (``ServeConfig.paged=False``) — the engine's memory ceiling drops
  from ``max_slots x max_len`` strips to actual tokens in flight.
  Local ring-window layers stay dense at ``W``; SSM state is O(1);
  families with no global KV (ssm, hybrid) run dense with zero pool
  demand.
* **Shared-prefix radix cache.** Admission prefill writes prompt K/V
  DIRECTLY into pages (``model.prefill_paged`` — no dense strip is
  shadow-copied), and finished chains are returned to a radix index
  (``serving.prefix_cache.RadixPrefixCache``) instead of freed.  A
  later request with the same prefix shares those pages by reference
  and prefills only its unmatched suffix at the chain's end position —
  the common household system/persona prompt is prefilled ONCE per
  hub, not once per request.  Matching is TOKEN-granular: a hit may
  end mid-page (divergence inside a cached page, or a chain's indexed
  partial tail); admission CoW-forks that one page
  (``KVBlockPool.fork`` + device copy, ``_cow_guard`` as the per-wave
  backstop) and the suffix prefill writes from the matched token
  onward.  Sharing is also IN-FLIGHT: every committed wave publishes
  each live slot's pages below its frontier into the tree
  (``_publish_frontiers``), so concurrent same-prefix tenants share a
  chain that is still decoding — readers pin strictly below the
  frontier, writers and spec-decode rollback only touch at/above it.
  With ``ServeConfig.prefix_persist_path``, ``engine.close()``
  PERSISTS the hot refcount-free chains (keys + page bytes) to a
  host-side store and a restarted engine rehydrates them for
  warm-TTFT hits; corrupt/mismatched stores are rejected cleanly.
  Sharing is behaviour-invariant (hit decode — token-granular,
  in-flight or restart-warm — is bit-identical to cold, verified per
  family) and only engages where the full decode state lives in pages
  (``model.prefix_sharable``); LRU chains are evicted under pool
  pressure, never from under a reader.
* **Exact padded prefill.** Prompts are right-padded to the smallest
  ``ServeConfig.prefill_buckets`` entry that fits and prefilled batched
  per bucket.  ``model.prefill(..., true_len=)`` makes the padding
  semantically invisible: admission logits come from the true last
  prompt token, pad positions never enter the KV/ring/SSM state, and
  the slot position starts at ``prefix + true_len`` (prefix = VLM image
  tokens) — NOT at the bucket size.  A non-bucket-aligned prompt decodes
  token-for-token identically to an unpadded single-request run
  (``tests/test_decode_consistency.py::test_padded_admission_matches_reference``).
  One carve-out: MoE expert *capacity* is derived from the static
  (padded, batched) token count, so under capacity pressure the set of
  dropped tokens can differ from an unpadded run — pads never steal
  capacity slots (they route to a sentinel expert), but the capacity
  bound itself is shape-derived.  With ``capacity_factor`` high enough
  that nothing drops, MoE is bit-exact like every other family.
* **Chunked prefill.** Prompts longer than the largest bucket prefill
  their first ``max(prefill_buckets)`` tokens, then catch up through the
  shared batched decode wave (teacher-forced, sampled outputs
  discarded) — long-prompt admission never stalls the other tenants in
  the batch.  With ``ServeConfig.chunked_prefill`` the bucketed call
  disappears entirely for token-only requests: admission is pure
  bookkeeping and the WHOLE prompt catches up as wave spans of up to
  ``catch_chunk`` tokens, planned against decode/spec slots under the
  ``wave_tokens`` per-wave budget (``core.scheduler.plan_wave``) —
  Sarathi-style mixed waves, step-driven with no drain assumption
  (``tests/test_engine_matrix.py`` gates the chunked axis
  token-identical to a chunked dense vanilla engine).
* **QoE admission order.** The queue is ranked by
  ``core.scheduler.admission_rank`` (fifo | priority | edf via
  ``ServeConfig.policy``) — the same policy definition the hub's
  discrete-event scheduler simulates.  Under pool pressure the feasible
  subset is admitted in rank order (infeasible requests wait, they are
  never dropped).
* **Per-request sampling.** ``Request.temperature`` / ``Request.top_k``
  override engine defaults inside the jitted decode step.
* **Speculative decoding.** ``ServeConfig.spec_decode`` turns every
  wave into a draft/verify round (``serving.spec_decode``): a resident
  draft model proposes ``spec_gamma - 1`` tokens per slot and ONE
  ``model.extend_paged`` call verifies them all — greedy output is
  bit-identical to vanilla decode, temperature > 0 uses rejection
  sampling (emitted distribution equals vanilla sampling), and a
  rejected run rolls back by masking + tail-page free
  (``pool.assert_consistent`` holds after every drain_step).  Gated to
  ``model.spec_decodable`` configs, exactly like the prefix cache —
  on both engines (the dense ``paged=False`` twin speculates
  wave-for-wave identically); the same ``extend_paged``/``extend``
  path retires the old 1-token-per-step catch-up prefill on every
  attention family.
* **int8 paged KV (quantized serving).** ``ServeConfig.quant_kv="int8"``
  stores pool pages as int8 with one f32 symmetric scale per
  (page, token offset, kv head) head_dim vector — extra
  ``k_scale``/``v_scale`` pool leaves of shape
  ``(num_blocks, block_size, kv_heads)``, a ~``4/head_dim`` overhead
  that shrinks page bytes ~3.8x at head_dim 64
  (``serving.kv_pool.page_bytes``) and raises the admission ceiling by
  the same factor at fixed HBM.  Quantization happens ON WRITE
  (``models.layers.scatter_kv_pages`` / ``scatter_kv_tokens``) so a
  committed page is never re-scaled — the write-once invariant CoW,
  rollback and in-flight sharing rely on is untouched, and every
  generic page machinery path (CoW copies, chain serialization,
  persistence, preemption) covers the scale leaves automatically
  because they are ordinary pool leaves.  Reads dequantize either by
  gather (jnp path) or FUSED inside the Pallas paged decode/extend
  kernels (``use_pallas_paged`` — ``kernels.flash_attention``
  ``paged_attention`` / ``paged_extend_attention`` with
  ``k_scale``/``v_scale``).  Decode is NOT bit-exact vs f32: the
  engine-matrix gates it tolerance-based (longest-common-prefix +
  first-token agreement vs the dense vanilla reference), while
  quant-vs-quant restart-warm persistence stays bit-identical and a
  store header pins the quant layout (f32<->int8 stores are rejected
  "mismatched", the engine starts cold).  ``quant_draft=True``
  additionally serves a separate draft model with int8 weights via
  ``models.layers.quantize_matmul_params``/``weight_einsum`` (TPU:
  the ``quant_matmul`` Pallas kernel) — greedy spec output remains
  bit-exact because the f32 verify trunk decides every token; a
  quantized draft can only change acceptance rate.  Families without
  paged KV (ssm, hybrid) accept ``quant_kv`` and serve dense
  unquantized (``engine.quant`` reports the armed state).
* **KV-preserving preemption.** ``preempt()`` extracts the slot's dense
  cache leaves and decode position onto ``Request.saved_state`` and
  detaches its KV pages (refcounts held, zero copies); re-submission
  restores the block table — no re-prefill, bit-identical continuation.
  ``submit`` rejects resumed states that could not make progress.

* **Telemetry.** ``serving.telemetry`` is the observability spine:
  every subsystem registers typed counters/gauges/histograms into the
  engine's ``MetricsRegistry`` (``engine.metrics``; ``stats()`` is a
  compatibility view over it) and ``ServeConfig.trace=True`` records
  wave phases + per-request lifecycles against an injectable monotonic
  clock, exported as Perfetto/chrome://tracing JSON via
  ``engine.dump_chrome_trace`` / ``launch.serve --trace`` and
  summarized by ``scripts/diagnose.py --trace``.  Tracing is
  behaviour-neutral (traced tokens bit-identical to untraced — gated
  in ``benchmarks/serving_throughput.py``).

Counter/metric glossary
-----------------------
``stats()`` key (registry name in parens), one line each.

Engine (always present):

* ``steps`` (``engine.steps``) — committed engine waves (prefill
  admissions + decode/extend steps).
* ``peak_active`` (``engine.peak_active``) — max concurrently resident
  requests observed.
* ``peak_pool_used`` (``engine.peak_pool_used``) — max KV pages in
  flight at once.
* ``exhaust_preempts`` (``engine.exhaust_preempts``) — slots preempted
  because the pool ran out of pages mid-decode.
* ``reclaims`` (``engine.reclaims``) — forced reclaims of a detached
  preempted holder to un-wedge admission.
* ``cow_forks`` (``engine.cow_forks``) — copy-on-write page forks
  (mid-page hit tails + in-flight shared frontier writes).
* ``mixed_waves`` (``engine.mixed_waves``) — waves mixing catch-up
  prefill spans with decode/spec slots (chunked prefill).
* ``wave_admitted`` (``engine.wave_admitted``) — requests admitted via
  the zero-prefill chunked path (bookkeeping-only admission).
* ``cancels`` (``engine.cancels``) — requests cancelled mid-flight.
* ``published_frontiers`` (``engine.published_frontiers``; prefix
  configs) — per-wave publications of live chains into the radix index.

KV pool (paged configs; ``kv_pool.*``):

* ``pool_blocks`` (``kv_pool.blocks``) — total physical pages.
* ``pool_free`` (``kv_pool.free``) — pages on the free list now.
* ``pool_shared`` (``kv_pool.shared``) — pages with refcount > 1 now.
* registry-only: ``kv_pool.used`` (allocated pages now),
  ``kv_pool.alloc_blocks`` / ``kv_pool.share_blocks`` /
  ``kv_pool.fork_copies`` / ``kv_pool.reclaimed_blocks`` — cumulative
  page traffic (allocations, reference shares, CoW copies, returns).

Prefix cache (``prefix_cache.*``; prefix configs):

* ``prefix_hits`` / ``prefix_misses`` / ``prefix_hit_rate`` — match
  outcomes at admission (hits actually served).
* ``prefix_hit_blocks`` / ``prefix_hit_tokens`` — pages / tokens served
  by reference instead of re-prefilled.
* ``prefix_hit_tokens_block`` — block-granular counterfactual of
  ``prefix_hit_tokens`` (the token-granularity gain is the delta).
* ``prefix_cached_blocks`` — pages currently indexed in the radix tree.
* ``prefix_evicted_blocks`` / ``prefix_inserted_blocks`` /
  ``prefix_replaced_blocks`` — LRU evictions, chain insertions, partial
  tails superseded by longer chains.
* ``prefix_short_matches`` — matches rejected by the admission floor
  (``min_match_tokens``).
* registry-only: ``prefix_cache.hit_tokens_hist`` — histogram of
  matched tokens per served hit.

Speculative decoding (``spec.*``; spec configs):

* ``spec_active`` (``spec.active``) — a draft model is resident.
* ``spec_steps`` / ``spec_rounds`` — waves that speculated / per-slot
  draft-verify rounds.
* ``spec_proposed`` / ``spec_accepted`` / ``spec_emitted`` — draft
  tokens proposed, accepted, and big-model tokens emitted (accepted +
  the free verify token).
* ``spec_acceptance`` — accepted / proposed.
* ``spec_tokens_per_round`` — emitted / rounds (1.0 = vanilla pace).
* registry-only: ``spec.depth{j}.proposed`` / ``.accepted`` —
  acceptance by draft depth j within a round (decays with depth; the
  signal that picks gamma).

Quantized serving (``quant.*``; quant configs):

* ``quant_kv`` (``quant.kv``) — KV pool dtype ("" = f32).
* ``quant_draft`` (``quant.draft``) — int8-weight draft is serving.
* ``quant_page_bytes`` / ``quant_f32_page_bytes`` — device bytes of one
  page under this layout vs f32 (the capacity lever).

Prefix persistence (``persist.*``; persist configs):

* ``persist_loaded_chains`` / ``persist_loaded_blocks`` — chains/pages
  rehydrated from the store at startup.
* ``persist_spilled_chains`` — chains spilled to the store under pool
  pressure this run.
* ``persist_rejected`` — non-empty reason when a store was rejected
  (corrupt / config mismatch) and the engine started cold.

Scheduler (registry-only; budgeted waves):

* ``sched.budget_utilization`` — histogram of granted/budget per
  planned wave.
* ``sched.demotions`` — slots granted less width than they wanted.

Frontend (registry-only; ``launch.serve.AsyncServingFrontend``):

* ``frontend.steps`` / ``frontend.streams`` / ``frontend.inbox_depth``
  / ``frontend.pending_cancels`` — loop progress and queue depths.

``scripts/check.sh`` runs an import sweep that catches JAX API breaks at
import time.
"""
from repro.serving.engine import (
    EdgeServingEngine,
    Request,
    ServeConfig,
    cache_batch_axes,
    extract_slot,
    insert_slot,
    paged_cache_axes,
)
from repro.serving.kv_pool import KVBlockPool, PoolExhausted, \
    blocks_for_tokens
from repro.serving.prefix_cache import RadixPrefixCache
from repro.serving.spec_decode import (SpecDecoder, accept_proposals,
                                       make_self_draft, validate_spec)
from repro.serving.telemetry import (MetricsRegistry, Tracer,
                                     default_clock, summarize_trace,
                                     validate_chrome_trace)

__all__ = ["EdgeServingEngine", "Request", "ServeConfig",
           "cache_batch_axes", "extract_slot", "insert_slot",
           "paged_cache_axes", "KVBlockPool", "PoolExhausted",
           "blocks_for_tokens", "RadixPrefixCache", "SpecDecoder",
           "accept_proposals", "make_self_draft", "validate_spec",
           "MetricsRegistry", "Tracer", "default_clock",
           "summarize_trace", "validate_chrome_trace"]
