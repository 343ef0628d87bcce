"""Unified model API over all architecture families.

Every family exposes the same five entry points, dispatched on
``cfg.family``:

    init_params(cfg, key)                      -> params pytree
    apply(cfg, params, batch, **opts)          -> (logits, aux_loss)
    loss_fn(cfg, params, batch, **opts)        -> (loss, metrics)
    init_cache(cfg, batch_size, max_len)       -> cache pytree
    prefill(cfg, params, batch, max_len)       -> (logits, cache)
    decode_step(cfg, params, cache, toks, pos) -> (logits, cache)
    init_paged_cache(cfg, b, max_len, nB, bs)  -> cache w/ paged global KV
    decode_step_paged(cfg, params, cache,
                      toks, pos, block_tables) -> (logits, cache)
    prefill_paged(cfg, params, batch, max_len,
                  cache, slots=..., ...)       -> (logits, cache)
    extend_paged(cfg, params, cache, toks[B,S],
                 pos, block_tables)            -> (logits[B,S,V], cache)
                 (or the packed layout, see ``extend_packable``)
    prefix_sharable(cfg)                       -> bool (radix cache ok?)
    extendable(cfg) / spec_decodable(cfg)      -> bool (multi-token
                                                  extend / spec verify?)

``batch`` is a dict: always ``tokens``/``targets``; plus
``image_embeds`` (vlm) or ``audio_embeds`` (encdec) stub-frontend
embeddings.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import encdec, hybrid, moe, ssm, transformer, vlm

Params = Any

_FAMILIES = {
    "dense": transformer,
    "moe": moe,
    "ssm": ssm,
    "hybrid": hybrid,
    "encdec": encdec,
    "vlm": vlm,
}


def family_module(cfg: ModelConfig):
    return _FAMILIES[cfg.family]


def specialize(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """Adapt static config knobs to an input shape (e.g. enc-dec position
    table must cover the assigned decoder length)."""
    if cfg.family == "encdec":
        need = shape.seq_len if shape.kind != "decode" else shape.seq_len
        if cfg.max_target_positions < need:
            cfg = cfg.replace(max_target_positions=need)
    return cfg


# ---------------------------------------------------------------------------
# params / forward
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Params:
    return family_module(cfg).init_params(cfg, key)


def apply(cfg: ModelConfig, params: Params, batch: dict, *,
          use_flash: bool = False, use_kernel: bool = False,
          remat: Optional[str] = None):
    """Full-sequence logits + scalar aux loss (0 where n/a)."""
    tokens = batch["tokens"]
    zero = jnp.zeros((), jnp.float32)
    if cfg.family == "dense":
        return transformer.forward(cfg, params, tokens, use_flash=use_flash,
                                   remat=remat), zero
    if cfg.family == "moe":
        return moe.forward(cfg, params, tokens, use_flash=use_flash,
                           remat=remat)
    if cfg.family == "ssm":
        return ssm.forward(cfg, params, tokens, use_kernel=use_kernel,
                           remat=remat), zero
    if cfg.family == "hybrid":
        return hybrid.forward(cfg, params, tokens, use_flash=use_flash,
                              use_kernel=use_kernel, remat=remat), zero
    if cfg.family == "encdec":
        return encdec.forward(cfg, params, tokens, batch["audio_embeds"],
                              use_flash=use_flash, remat=remat), zero
    if cfg.family == "vlm":
        return vlm.forward(cfg, params, tokens, batch["image_embeds"],
                           use_flash=use_flash, remat=remat), zero
    raise ValueError(cfg.family)


def loss_fn(cfg: ModelConfig, params: Params, batch: dict, **opts):
    logits, aux = apply(cfg, params, batch, **opts)
    targets = batch["targets"]
    S_t = targets.shape[1]
    logits = logits[:, -S_t:]  # vlm prepends image tokens; align to text
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    if mask is not None:
        nll = nll * mask
        denom = jnp.maximum(jnp.sum(mask), 1.0)
    else:
        denom = float(nll.size)
    ce = jnp.sum(nll) / denom
    loss = ce + cfg.router_aux_coef * aux
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch_size: int, max_len: int):
    return family_module(cfg).init_cache(cfg, batch_size, max_len)


def init_paged_cache(cfg: ModelConfig, batch_size: int, max_len: int,
                     num_blocks: int, block_size: int, kv_dtype=None):
    """Decode cache with GLOBAL attention KV in a shared page pool of
    ``num_blocks`` x ``block_size`` tokens (no batch axis on pool
    leaves); local ring windows, SSM state and cross K/V stay dense.
    Serve with ``decode_step_paged``; see ``serving.kv_pool``.

    ``kv_dtype="int8"`` stores pool K/V quantized with per-(page,
    offset, kv-head) f32 scales in parallel ``k_scale``/``v_scale``
    pool leaves (``layers.init_kv_pages(quant=True)``); every paged
    read path dequantizes transparently.  ``None`` keeps the f32 pool.
    """
    return family_module(cfg).init_paged_cache(cfg, batch_size, max_len,
                                               num_blocks, block_size,
                                               kv_dtype=kv_dtype)


def prefill(cfg: ModelConfig, params: Params, batch: dict, max_len: int, *,
            use_flash: bool = False, use_kernel: bool = False,
            true_len=None):
    """Run the prompt and build the decode cache.

    ``true_len``: optional int | (B,) int32 — the true number of TEXT
    tokens per row when ``batch["tokens"]`` is right-padded (bucketed
    serving prefill).  Every family then returns logits at the true last
    prompt token and keeps pad positions out of the decode state, making
    padded prefill bit-exact with an unpadded one.
    """
    tokens = batch["tokens"]
    if cfg.family == "encdec":
        return encdec.prefill(cfg, params, tokens, max_len,
                              audio_embeds=batch["audio_embeds"],
                              use_flash=use_flash, true_len=true_len)
    if cfg.family == "vlm":
        return vlm.prefill(cfg, params, tokens, max_len,
                           image_embeds=batch["image_embeds"],
                           use_flash=use_flash, true_len=true_len)
    if cfg.family == "ssm":
        return ssm.prefill(cfg, params, tokens, max_len,
                           use_kernel=use_kernel, true_len=true_len)
    if cfg.family == "hybrid":
        return hybrid.prefill(cfg, params, tokens, max_len,
                              use_flash=use_flash, use_kernel=use_kernel,
                              true_len=true_len)
    if cfg.family == "moe":
        return moe.prefill(cfg, params, tokens, max_len, use_flash=use_flash,
                           true_len=true_len)
    return transformer.prefill(cfg, params, tokens, max_len,
                               use_flash=use_flash, true_len=true_len)


def decode_step(cfg: ModelConfig, params: Params, cache, tokens, pos):
    return family_module(cfg).decode_step(cfg, params, cache, tokens, pos)


def decode_step_paged(cfg: ModelConfig, params: Params, cache, tokens, pos,
                      block_tables, use_pallas: bool = False):
    """``decode_step`` against ``init_paged_cache``: global-layer KV is
    read/written through ``block_tables`` (B, n_blk) int32 (-1 =
    unallocated).  Token-for-token identical to the dense path when the
    tables cover the same logical positions.  ``use_pallas=True`` reads
    pages through the scalar-prefetched Pallas ``paged_attention``
    kernel instead of the jnp gather (TPU serving path)."""
    return family_module(cfg).decode_step_paged(cfg, params, cache, tokens,
                                                pos, block_tables,
                                                use_pallas)


def extend_paged(cfg: ModelConfig, params: Params, cache, tokens, pos,
                 block_tables, valid_len=None, use_pallas: bool = False,
                 narrow=None):
    """Score S tokens per row against the paged cache in ONE jitted call
    — the multi-token twin of ``decode_step_paged`` used for speculative
    verify and chunked catch-up prefill.

    The **(B, S) layout** (``narrow=None``, every extendable family):
    tokens (B, S) int32 at absolute positions ``pos + i`` (pos: (B,)
    per-slot write frontiers); block_tables: the slot's full (B, n_blk)
    table (context AND write span).  Returns (logits (B, S, V),
    new_cache): row ``i`` is the next-token distribution after
    consuming ``tokens[:, :i+1]``.  The context read is masked strictly
    below ``pos`` (pre-write view), so stale K/V from a rejected
    speculation is invisible and rollback is pure bookkeeping; K/V for
    rows ``i < valid_len`` is written at ``pos + i`` (pad rows drop).

    The **packed layout** (``narrow``, a ``layers.NarrowRows``; only
    where ``extend_packable``): ``tokens`` (R, S), ``pos``,
    ``block_tables`` and ``valid_len`` are R wide rows as above, and
    ``narrow`` holds one width-1 row per slot, read as the decode wave
    reads it.  A wave then computes n + R*S positions instead of n*S.
    Returns ((narrow logits (n, 1, V), wide logits (R, S, V)),
    new_cache).

    ssm/hybrid raise NotImplementedError — gate callers on
    ``extendable`` / ``spec_decodable``.  ``use_pallas=True`` reads a
    QUANTIZED pool through the fused dequant
    ``kernels.flash_attention.paged_extend_attention`` kernel (no-op on
    an f32 pool, which keeps that path bit-exact), and the narrow rows
    through ``paged_attention``.
    """
    kw = {} if narrow is None else {"narrow": narrow}
    return family_module(cfg).extend_paged(cfg, params, cache, tokens,
                                           pos, block_tables, valid_len,
                                           use_pallas=use_pallas, **kw)


def extend(cfg: ModelConfig, params: Params, cache, tokens, pos,
           valid_len=None):
    """Dense twin of ``extend_paged``: the same multi-token scoring
    against the DENSE (strip/ring) decode cache — keeps the
    ``ServeConfig.paged=False`` A/B engine wave-for-wave identical to
    the paged one.  Same return contract and gating."""
    return family_module(cfg).extend(cfg, params, cache, tokens, pos,
                                     valid_len)


def extendable(cfg: ModelConfig) -> bool:
    """Does the family implement multi-token ``extend_paged``?  True for
    every attention family (teacher-forced catch-up never needs
    rollback, so gemma-style local rings qualify too — their pre-write
    chunk read preserves sequential eviction semantics); False for the
    recurrent families (ssm, hybrid), whose state cannot be advanced S
    tokens and later truncated."""
    return cfg.family in ("dense", "moe", "vlm", "encdec")


def extend_packable(cfg: ModelConfig) -> bool:
    """Does ``extend_paged`` take the packed layout (width-1 slots in a
    decode-shaped row group, wider spans in a bucket of wide rows)?
    True for the uniform paged trunks: dense and vlm without local ring
    layers (``pattern_period <= 1``), and moe.  Local-ring families keep
    the (B, S) wave (their rings are per-slot dense state the wide rows
    would have to gather), as does encdec (its cross-attention K/V is
    per slot)."""
    if cfg.family in ("dense", "vlm"):
        return cfg.pattern_period <= 1
    return cfg.family == "moe"


def spec_decodable(cfg: ModelConfig) -> bool:
    """Can this config serve as a speculative-decoding VERIFY model?

    Stronger than ``extendable``: a rejected speculation must roll back
    EXACTLY, which the engine gets for free only where every
    token-position-dependent piece of decode state is masked by
    position (paged KV pages, dense ``slots`` strips) — truncating is
    then pure bookkeeping and stale writes stay invisible until
    overwritten in sequence order.  Local-ring layers fail this (a
    rejected write may have evicted live window context) and ssm/hybrid
    recurrences advance irreversibly, so — mirroring
    ``prefix_sharable`` — those configs never speculate and serve the
    vanilla one-token path instead.
    """
    if cfg.family in ("dense", "vlm"):
        return cfg.pattern_period <= 1
    return cfg.family in ("moe", "encdec")


def prefix_sharable(cfg: ModelConfig) -> bool:
    """Can finished chains be shared through the radix prefix cache?

    True iff every token-position-dependent piece of the decode state
    lives in KV pages (reconstructible for any block-aligned prefix):
    fully-global transformers/VLMs (``pattern_period <= 1``), MoE (all
    attention global) and enc-dec (cross K/V is rebuilt from the audio
    by any suffix prefill; chains are keyed under the audio digest).
    Local-ring (gemma-pattern) and recurrent (ssm/hybrid) state cannot
    be recovered from pages, so those configs never share — the radix
    cache simply stays disabled and admission is the cold path.
    """
    if cfg.family in ("dense", "vlm"):
        return cfg.pattern_period <= 1
    return cfg.family in ("moe", "encdec")


def prefill_paged(cfg: ModelConfig, params: Params, batch: dict, max_len,
                  cache, *, slots, write_tables=None, ctx_tables=None,
                  ctx_len=None, true_len=None, use_flash: bool = False):
    """Admission prefill fused with cache insertion (the paged-serving
    twin of ``prefill``): prompt K/V is written DIRECTLY into the
    engine's cache — global-layer K/V into the shared page pool through
    ``write_tables`` (m, n_wblk), per-slot dense leaves (local rings,
    SSM state, cross K/V) at ``slots`` (m,).  With ``ctx_tables`` /
    ``ctx_len`` the rows are radix-cache-hit SUFFIXES that attend the
    shared prefix's pages and skip its prefill FLOPs entirely (only
    legal when ``prefix_sharable(cfg)``); ``ctx_len`` is TOKEN-granular
    (a hit may start mid-page — the engine pre-forks that page) and
    ``ctx_tables``/``write_tables`` are then both the row's FULL block
    table, read below ``ctx_len`` and scattered into from ``ctx_len``
    (see ``layers.attention_prefill_paged``).  ``write_tables=None`` is
    the dense engine's fused admission.  Returns (last-true-token
    logits, updated cache)."""
    tokens = batch["tokens"]
    kw = dict(slots=slots, write_tables=write_tables,
              ctx_tables=ctx_tables, ctx_len=ctx_len, true_len=true_len)
    if cfg.family == "encdec":
        return encdec.prefill_paged(cfg, params, tokens, max_len, cache,
                                    audio_embeds=batch["audio_embeds"],
                                    use_flash=use_flash, **kw)
    if cfg.family == "vlm":
        return vlm.prefill_paged(cfg, params, tokens, max_len, cache,
                                 image_embeds=batch.get("image_embeds"),
                                 use_flash=use_flash, **kw)
    if cfg.family == "ssm":
        return ssm.prefill_paged(cfg, params, tokens, max_len, cache, **kw)
    if cfg.family == "hybrid":
        return hybrid.prefill_paged(cfg, params, tokens, max_len, cache,
                                    use_flash=use_flash, **kw)
    if cfg.family == "moe":
        return moe.prefill_paged(cfg, params, tokens, max_len, cache,
                                 use_flash=use_flash, **kw)
    return transformer.prefill_paged(cfg, params, tokens, max_len, cache,
                                     use_flash=use_flash, **kw)


# ---------------------------------------------------------------------------
# batch construction
# ---------------------------------------------------------------------------

def make_batch(cfg: ModelConfig, shape: InputShape, key=None) -> dict:
    """Concrete random batch matching ``batch_shapes`` (smoke/e2e use)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    shapes = batch_shapes(cfg, shape)
    out = {}
    for name, sds in shapes.items():
        key, sub = jax.random.split(key)
        if jnp.issubdtype(sds.dtype, jnp.integer):
            out[name] = jax.random.randint(sub, sds.shape, 0, cfg.vocab_size,
                                           dtype=sds.dtype)
        else:
            out[name] = 0.1 * jax.random.normal(sub, sds.shape, sds.dtype)
    return out


def batch_shapes(cfg: ModelConfig, shape: InputShape) -> dict:
    """ShapeDtypeStructs for every model input of a train/prefill batch."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    act = cfg.activation_dtype
    sds = jax.ShapeDtypeStruct
    if cfg.family == "vlm":
        S_text = S - cfg.num_image_tokens
        return {
            "tokens": sds((B, S_text), i32),
            "image_embeds": sds((B, cfg.num_image_tokens,
                                 cfg.image_embed_dim), act),
            "targets": sds((B, S_text), i32),
        }
    if cfg.family == "encdec":
        return {
            "tokens": sds((B, S), i32),
            "audio_embeds": sds((B, cfg.encoder_seq, cfg.d_model), act),
            "targets": sds((B, S), i32),
        }
    return {"tokens": sds((B, S), i32), "targets": sds((B, S), i32)}


def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
