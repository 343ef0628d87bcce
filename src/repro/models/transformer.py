"""Dense decoder-only transformer trunk (gemma/phi/llama families).

Layers are stacked and executed with ``jax.lax.scan``. Architectures
with a local:global attention pattern (gemma2/3) scan over
*super-blocks*: ``pattern_period - 1`` local (sliding-window) layers
followed by one global layer; remainder local layers get their own scan.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L

Params = dict


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_block(cfg: ModelConfig, key, stack=()) -> Params:
    norm_init, _ = L.make_norm(cfg)
    k1, k2 = jax.random.split(key)
    p = {
        "attn": L.init_attention(cfg, k1, stack),
        "mlp": L.init_mlp(cfg, k2, stack=stack),
        "ln1": norm_init(cfg.d_model, stack),
        "ln2": norm_init(cfg.d_model, stack),
    }
    if cfg.sandwich_norms:
        p["ln1_post"] = norm_init(cfg.d_model, stack)
        p["ln2_post"] = norm_init(cfg.d_model, stack)
    return p


def init_trunk(cfg: ModelConfig, key) -> Params:
    nb, rem = cfg.pattern_blocks()
    keys = jax.random.split(key, 3)
    if cfg.pattern_period <= 1:
        return {"layers": init_block(cfg, keys[0], stack=(nb,))}
    p = {
        "super": {
            "local": init_block(cfg, keys[0], stack=(nb, cfg.pattern_period - 1)),
            "global": init_block(cfg, keys[1], stack=(nb,)),
        }
    }
    if rem:
        p["rem_local"] = init_block(cfg, keys[2], stack=(rem,))
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    norm_init, _ = L.make_norm(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "embed": L.init_embedding(cfg, k1),
        "unembed": L.init_unembed(cfg, k2),
        "trunk": init_trunk(cfg, k3),
        "final_norm": norm_init(cfg.d_model),
    }


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def block_fwd(cfg: ModelConfig, p: Params, x, positions, *, is_global,
              use_flash=False):
    _, norm = L.make_norm(cfg)
    h = norm(p["ln1"], x)
    a, _, _ = L.attention_fwd(cfg, p["attn"], h, positions,
                              is_global=is_global, use_flash=use_flash)
    if cfg.sandwich_norms:
        a = norm(p["ln1_post"], a)
    x = x + a
    h = norm(p["ln2"], x)
    m = L.mlp(p["mlp"], h)
    if cfg.sandwich_norms:
        m = norm(p["ln2_post"], m)
    return x + m


def block_prefill(cfg: ModelConfig, p: Params, x, positions, *, is_global,
                  use_flash=False):
    """Like block_fwd but also returns (k, v) for cache construction."""
    _, norm = L.make_norm(cfg)
    h = norm(p["ln1"], x)
    a, k, v = L.attention_fwd(cfg, p["attn"], h, positions,
                              is_global=is_global, use_flash=use_flash)
    if cfg.sandwich_norms:
        a = norm(p["ln1_post"], a)
    x = x + a
    h = norm(p["ln2"], x)
    m = L.mlp(p["mlp"], h)
    if cfg.sandwich_norms:
        m = norm(p["ln2_post"], m)
    return x + m, (k, v)


def block_decode(cfg: ModelConfig, p: Params, x, cache, pos, *, is_global):
    _, norm = L.make_norm(cfg)
    h = norm(p["ln1"], x)
    a, new_cache = L.attention_decode(cfg, p["attn"], h, cache, pos,
                                      is_global=is_global)
    if cfg.sandwich_norms:
        a = norm(p["ln1_post"], a)
    x = x + a
    h = norm(p["ln2"], x)
    m = L.mlp(p["mlp"], h)
    if cfg.sandwich_norms:
        m = norm(p["ln2_post"], m)
    return x + m, new_cache


def block_decode_paged(cfg: ModelConfig, p: Params, x, cache, pos,
                       block_tables, use_pallas: bool = False):
    """``block_decode`` for a GLOBAL layer whose KV lives in the paged
    pool (``layers.attention_decode_paged``)."""
    _, norm = L.make_norm(cfg)
    h = norm(p["ln1"], x)
    a, new_cache = L.attention_decode_paged(cfg, p["attn"], h, cache, pos,
                                            block_tables,
                                            use_pallas=use_pallas)
    if cfg.sandwich_norms:
        a = norm(p["ln1_post"], a)
    x = x + a
    h = norm(p["ln2"], x)
    m = L.mlp(p["mlp"], h)
    if cfg.sandwich_norms:
        m = norm(p["ln2_post"], m)
    return x + m, new_cache


def block_extend_paged(cfg: ModelConfig, p: Params, x, pos, cache,
                       block_tables, valid_len=None, *,
                       use_pallas: bool = False, narrow=None):
    """``block_decode_paged`` for S tokens at once — speculative verify
    / chunked catch-up (``layers.attention_extend_paged``; with
    ``narrow`` the packed two-group wave, x flattened)."""
    _, norm = L.make_norm(cfg)
    h = norm(p["ln1"], x)
    a, new_cache = L.attention_extend_paged(cfg, p["attn"], h, pos, cache,
                                            block_tables, valid_len,
                                            use_pallas=use_pallas,
                                            narrow=narrow)
    if cfg.sandwich_norms:
        a = norm(p["ln1_post"], a)
    x = x + a
    h = norm(p["ln2"], x)
    m = L.mlp(p["mlp"], h)
    if cfg.sandwich_norms:
        m = norm(p["ln2_post"], m)
    return x + m, new_cache


def block_extend(cfg: ModelConfig, p: Params, x, cache, pos, *,
                 is_global, valid_len=None):
    """``block_decode`` for S tokens against a dense (ring/strip) cache
    (``layers.attention_extend``)."""
    _, norm = L.make_norm(cfg)
    h = norm(p["ln1"], x)
    a, new_cache = L.attention_extend(cfg, p["attn"], h, cache, pos,
                                      is_global=is_global,
                                      valid_len=valid_len)
    if cfg.sandwich_norms:
        a = norm(p["ln1_post"], a)
    x = x + a
    h = norm(p["ln2"], x)
    m = L.mlp(p["mlp"], h)
    if cfg.sandwich_norms:
        m = norm(p["ln2_post"], m)
    return x + m, new_cache


def block_prefill_paged(cfg: ModelConfig, p: Params, x, positions, pages,
                        write_tables, ctx_tables=None, ctx_len=None, *,
                        use_flash=False):
    """``block_prefill`` for a GLOBAL layer writing K/V straight into
    its page pool (and, on a prefix-cache hit, attending the shared
    prefix pages) — see ``layers.attention_prefill_paged``."""
    _, norm = L.make_norm(cfg)
    h = norm(p["ln1"], x)
    a, new_pages = L.attention_prefill_paged(
        cfg, p["attn"], h, positions, pages, write_tables, ctx_tables,
        ctx_len, use_flash=use_flash)
    if cfg.sandwich_norms:
        a = norm(p["ln1_post"], a)
    x = x + a
    h = norm(p["ln2"], x)
    m = L.mlp(p["mlp"], h)
    if cfg.sandwich_norms:
        m = norm(p["ln2_post"], m)
    return x + m, new_pages


def _maybe_remat(fn, policy: Optional[str]):
    if not policy or policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn)
    pol = getattr(jax.checkpoint_policies, policy)
    return jax.checkpoint(fn, policy=pol)


def _uniform_layers(cfg: ModelConfig, trunk: Params):
    """The stacked-layer pytree the uniform (``pattern_period <= 1``)
    scan runs over.  ``lax.scan`` takes its trip count from the leaf
    axis-0 extent, so a params tree holding MORE stacked layers than
    ``cfg.num_layers`` is sliced here, in-trace — which is what lets an
    early-exit self-draft (``serving.spec_decode.make_self_draft``)
    share the verify model's full trunk buffer by reference instead of
    materialising a device copy of its first half."""
    layers = trunk["layers"]
    if jax.tree.leaves(layers)[0].shape[0] == cfg.num_layers:
        return layers
    return jax.tree.map(lambda a: a[:cfg.num_layers], layers)


# ---------------------------------------------------------------------------
# trunk forward (train / prefill without cache)
# ---------------------------------------------------------------------------

def trunk_fwd(cfg: ModelConfig, trunk: Params, x, positions, *,
              use_flash=False, remat: Optional[str] = None):
    if cfg.pattern_period <= 1:
        def body(h, lp):
            return block_fwd(cfg, lp, h, positions, is_global=True,
                             use_flash=use_flash), None
        body = _maybe_remat(body, remat)
        x, _ = lax.scan(body, x, _uniform_layers(cfg, trunk))
        return x

    def local_body(h, lp):
        return block_fwd(cfg, lp, h, positions, is_global=False,
                         use_flash=use_flash), None

    def super_body(h, sp):
        h, _ = lax.scan(_maybe_remat(local_body, remat), h, sp["local"])
        h = block_fwd(cfg, sp["global"], h, positions, is_global=True,
                      use_flash=use_flash)
        return h, None

    x, _ = lax.scan(_maybe_remat(super_body, remat), x, trunk["super"])
    if "rem_local" in trunk:
        x, _ = lax.scan(_maybe_remat(local_body, remat), x, trunk["rem_local"])
    return x


def forward(cfg: ModelConfig, params: Params, tokens, *,
            prefix_embeds=None, use_flash=False, remat=None):
    """Full-sequence logits. tokens: (B, S_text).

    prefix_embeds: optional (B, P, d) embeddings prepended (VLM image
    tokens); logits are returned for the full sequence.
    """
    x = L.embed(cfg, params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = trunk_fwd(cfg, params["trunk"], x, positions,
                  use_flash=use_flash, remat=remat)
    _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x)
    return L.unembed(cfg, params["embed"], params["unembed"], x)


# ---------------------------------------------------------------------------
# cache layout + decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    nb, rem = cfg.pattern_blocks()
    if cfg.pattern_period <= 1:
        return {"layers": L.init_kv_cache(cfg, batch, max_len, stack=(nb,))}
    W = min(cfg.local_window, max_len)
    c = {
        "super": {
            "local": L.init_kv_cache(cfg, batch, W,
                                     stack=(nb, cfg.pattern_period - 1)),
            "global": L.init_kv_cache(cfg, batch, max_len, stack=(nb,)),
        }
    }
    if rem:
        c["rem_local"] = L.init_kv_cache(cfg, batch, W, stack=(rem,))
    return c


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int,
                     num_blocks: int, block_size: int,
                     kv_dtype=None) -> Params:
    """Like ``init_cache`` but GLOBAL layers get a shared page pool
    (no batch axis) instead of per-slot ``max_len`` strips; local
    ring-window layers stay dense at W.  ``kv_dtype="int8"`` makes the
    pool quantized (scale leaves ride along; dense ring caches stay
    f32 — they are per-slot, not pool capacity)."""
    quant = kv_dtype == "int8"
    nb, rem = cfg.pattern_blocks()
    if cfg.pattern_period <= 1:
        return {"layers": L.init_kv_pages(cfg, num_blocks, block_size,
                                          stack=(nb,), quant=quant)}
    W = min(cfg.local_window, max_len)
    c = {
        "super": {
            "local": L.init_kv_cache(cfg, batch, W,
                                     stack=(nb, cfg.pattern_period - 1)),
            "global": L.init_kv_pages(cfg, num_blocks, block_size,
                                      stack=(nb,), quant=quant),
        }
    }
    if rem:
        c["rem_local"] = L.init_kv_cache(cfg, batch, W, stack=(rem,))
    return c


def trunk_decode(cfg: ModelConfig, trunk: Params, cache: Params, x, pos):
    """x: (B, 1, d); pos: scalar int32. Returns (x, new_cache)."""
    if cfg.pattern_period <= 1:
        def body(h, inp):
            lp, c = inp
            h, c2 = block_decode(cfg, lp, h, c, pos, is_global=True)
            return h, c2
        x, new_c = lax.scan(body, x, (_uniform_layers(cfg, trunk),
                                      cache["layers"]))
        return x, {"layers": new_c}

    def local_body(h, inp):
        lp, c = inp
        h, c2 = block_decode(cfg, lp, h, c, pos, is_global=False)
        return h, c2

    def super_body(h, inp):
        sp, sc = inp
        h, lc = lax.scan(local_body, h, (sp["local"], sc["local"]))
        h, gc = block_decode(cfg, sp["global"], h, sc["global"], pos,
                             is_global=True)
        return h, {"local": lc, "global": gc}

    x, new_super = lax.scan(super_body, x, (trunk["super"], cache["super"]))
    new_cache = {"super": new_super}
    if "rem_local" in trunk:
        x, rc = lax.scan(local_body, x, (trunk["rem_local"], cache["rem_local"]))
        new_cache["rem_local"] = rc
    return x, new_cache


def decode_step(cfg: ModelConfig, params: Params, cache: Params, tokens, pos):
    """tokens: (B, 1) int32; pos: scalar int32 — position being written."""
    x = L.embed(cfg, params["embed"], tokens)
    x, new_cache = trunk_decode(cfg, params["trunk"], cache, x, pos)
    _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    return logits, new_cache


def trunk_decode_paged(cfg: ModelConfig, trunk: Params, cache: Params, x,
                       pos, block_tables, use_pallas: bool = False):
    """``trunk_decode`` against ``init_paged_cache``: global layers read
    and write KV pages via the (B, n_blk) block table; local ring layers
    are unchanged."""
    if cfg.pattern_period <= 1:
        def body(h, inp):
            lp, c = inp
            h, c2 = block_decode_paged(cfg, lp, h, c, pos, block_tables,
                                       use_pallas)
            return h, c2
        x, new_c = lax.scan(body, x, (_uniform_layers(cfg, trunk),
                                      cache["layers"]))
        return x, {"layers": new_c}

    def local_body(h, inp):
        lp, c = inp
        h, c2 = block_decode(cfg, lp, h, c, pos, is_global=False)
        return h, c2

    def super_body(h, inp):
        sp, sc = inp
        h, lc = lax.scan(local_body, h, (sp["local"], sc["local"]))
        h, gc = block_decode_paged(cfg, sp["global"], h, sc["global"], pos,
                                   block_tables, use_pallas)
        return h, {"local": lc, "global": gc}

    x, new_super = lax.scan(super_body, x, (trunk["super"], cache["super"]))
    new_cache = {"super": new_super}
    if "rem_local" in trunk:
        x, rc = lax.scan(local_body, x, (trunk["rem_local"], cache["rem_local"]))
        new_cache["rem_local"] = rc
    return x, new_cache


def decode_step_paged(cfg: ModelConfig, params: Params, cache: Params,
                      tokens, pos, block_tables, use_pallas: bool = False):
    """Paged twin of ``decode_step``; ``block_tables``: (B, n_blk) int32."""
    x = L.embed(cfg, params["embed"], tokens)
    x, new_cache = trunk_decode_paged(cfg, params["trunk"], cache, x, pos,
                                      block_tables, use_pallas)
    _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    return logits, new_cache


def extend_paged(cfg: ModelConfig, params: Params, cache: Params, tokens,
                 pos, block_tables, valid_len=None,
                 use_pallas: bool = False, narrow=None):
    """Score S tokens per row against the paged cache in ONE jitted call.

    Two layouts:

    * **(R, S)** (``narrow=None``): tokens (R, S) int32 at absolute
      positions ``pos + i`` (pos: (R,) int32 per-row write frontier).
      Global layers extend through their page pool
      (``layers.attention_extend_paged``); local ring layers (gemma
      patterns) extend their dense window with the same pre-write
      causal-suffix semantics (``layers.attention_extend``; requires
      S <= local_window).  Returns (logits (R, S, V), new_cache) — row
      i is the next-token distribution AFTER consuming
      ``tokens[:, :i+1]``, which is what speculative verify and
      multi-token catch-up prefill consume.  Rows ``i >= valid_len`` are
      padding: their logits are garbage and their K/V writes are
      dropped.
    * **packed** (``narrow``, a ``layers.NarrowRows``; uniform trunks
      only, ``pattern_period <= 1``): ``tokens``, ``pos``,
      ``block_tables`` and ``valid_len`` describe R wide rows as above
      (a row with ``valid_len`` 0 and a -1 table is padding), and
      ``narrow`` adds n width-1 rows read as a decode wave reads them.
      Every token-wise layer runs once over the n + R*S tokens.  Returns
      ((narrow logits (n, 1, V), wide logits (R, S, V)), new_cache).
    """
    x = L.embed(cfg, params["embed"], L.wave_tokens(tokens, narrow))
    trunk = params["trunk"]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (tokens.shape[0],))

    if cfg.pattern_period <= 1:
        def body(h, inp):
            lp, c = inp
            h, c2 = block_extend_paged(cfg, lp, h, pos, c, block_tables,
                                       valid_len, use_pallas=use_pallas,
                                       narrow=narrow)
            return h, c2
        x, new_c = lax.scan(body, x, (_uniform_layers(cfg, trunk),
                                      cache["layers"]))
        new_cache = {"layers": new_c}
    else:
        if narrow is not None:
            raise ValueError("a packed extend wave needs a uniform paged "
                             "trunk (pattern_period <= 1)")

        def local_body(h, inp):
            lp, c = inp
            h, c2 = block_extend(cfg, lp, h, c, pos, is_global=False,
                                 valid_len=valid_len)
            return h, c2

        def super_body(h, inp):
            sp, sc = inp
            h, lc = lax.scan(local_body, h, (sp["local"], sc["local"]))
            h, gc = block_extend_paged(cfg, sp["global"], h, pos,
                                       sc["global"], block_tables,
                                       valid_len, use_pallas=use_pallas)
            return h, {"local": lc, "global": gc}

        x, new_super = lax.scan(super_body, x,
                                (trunk["super"], cache["super"]))
        new_cache = {"super": new_super}
        if "rem_local" in trunk:
            x, rc = lax.scan(local_body, x,
                             (trunk["rem_local"], cache["rem_local"]))
            new_cache["rem_local"] = rc

    _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    if narrow is not None:
        logits = L.split_wave(logits, narrow, tokens.shape[0])
    return logits, new_cache


def extend(cfg: ModelConfig, params: Params, cache: Params, tokens, pos,
           valid_len=None):
    """Dense twin of ``extend_paged``: score S tokens against the dense
    strip/ring caches (``ServeConfig.paged=False`` A/B path).  Same row
    semantics; bit-identical to the paged extend on the same logical
    state."""
    x = L.embed(cfg, params["embed"], tokens)
    trunk = params["trunk"]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (x.shape[0],))

    def make_body(is_global):
        def body(h, inp):
            lp, c = inp
            h, c2 = block_extend(cfg, lp, h, c, pos, is_global=is_global,
                                 valid_len=valid_len)
            return h, c2
        return body

    if cfg.pattern_period <= 1:
        x, new_c = lax.scan(make_body(True), x,
                            (_uniform_layers(cfg, trunk),
                             cache["layers"]))
        new_cache = {"layers": new_c}
    else:
        def super_body(h, inp):
            sp, sc = inp
            h, lc = lax.scan(make_body(False), h, (sp["local"],
                                                   sc["local"]))
            h, gc = block_extend(cfg, sp["global"], h, sc["global"], pos,
                                 is_global=True, valid_len=valid_len)
            return h, {"local": lc, "global": gc}

        x, new_super = lax.scan(super_body, x,
                                (trunk["super"], cache["super"]))
        new_cache = {"super": new_super}
        if "rem_local" in trunk:
            x, rc = lax.scan(make_body(False), x,
                             (trunk["rem_local"], cache["rem_local"]))
            new_cache["rem_local"] = rc

    _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    return logits, new_cache


# ---------------------------------------------------------------------------
# prefill: forward + cache construction
# ---------------------------------------------------------------------------

def broadcast_true_len(true_len, batch: int):
    """``true_len`` (int | (B,) int32 | None) -> (B,) int32 | None."""
    if true_len is None:
        return None
    return jnp.broadcast_to(jnp.asarray(true_len, jnp.int32), (batch,))


def gather_last(x, n):
    """x: (B, S, d); n: (B,) true lengths -> (B, 1, d) at index n-1."""
    idx = jnp.maximum(n - 1, 0).astype(jnp.int32)[:, None, None]
    return jnp.take_along_axis(x, idx, axis=1)


def _fill_global(cfg, batch, max_len, k, v, n=None):
    """Dense decode cache from prefill K/V.

    ``n``: optional (B,) true sequence lengths — positions >= n are
    right-padding whose K/V must never be attended: their ``slots``
    entries are set to -1 (invalid), which masks them in
    ``attention_decode`` until the decode loop overwrites them in
    sequence order.
    """
    S = k.shape[1]
    cache = L.init_kv_cache(cfg, batch, max_len, dtype=k.dtype)
    cache["k"] = lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=1)
    cache["v"] = lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=1)
    pos = jnp.arange(max_len, dtype=jnp.int32)
    if n is None:
        slots = jnp.broadcast_to(jnp.where(pos < S, pos, -1),
                                 (batch, max_len))
    else:
        slots = jnp.where(pos[None, :] < n[:, None], pos[None, :], -1)
        slots = jnp.broadcast_to(slots, (batch, max_len))
    cache["slots"] = slots.astype(jnp.int32)
    return cache


def _fill_local(cfg, batch, max_len, k, v, n=None):
    """Sliding-window ring cache from prefill K/V.

    With ``n`` given, the ring holds positions [n-W, n) of each row —
    padded positions must not evict true context (a right-padded row
    whose pads landed in the ring would decode with an empty window).
    """
    S = k.shape[1]
    W = min(cfg.local_window, max_len)
    cache = L.init_kv_cache(cfg, batch, W, dtype=k.dtype)
    if n is not None:
        # ring slot j holds the largest position p <= n-1 with p % W == j
        j = jnp.arange(W, dtype=jnp.int32)
        p = j[None, :] + ((n[:, None] - 1 - j[None, :]) // W) * W  # (B, W)
        valid = (p >= 0) & (p < n[:, None])
        idx = jnp.clip(p, 0, S - 1)
        take = lambda src: jnp.take_along_axis(
            src, idx[..., None, None], axis=1)
        cache["k"] = jnp.where(valid[..., None, None], take(k), 0)
        cache["v"] = jnp.where(valid[..., None, None], take(v), 0)
        cache["slots"] = jnp.where(valid, p, -1)
        return cache
    if S >= W:
        pos = jnp.arange(S - W, S)
        idx = pos % W
        cache["k"] = cache["k"].at[:, idx].set(k[:, S - W:])
        cache["v"] = cache["v"].at[:, idx].set(v[:, S - W:])
        cache["slots"] = cache["slots"].at[:, idx].set(
            pos.astype(jnp.int32)[None])
    else:
        cache["k"] = lax.dynamic_update_slice_in_dim(cache["k"], k, 0, axis=1)
        cache["v"] = lax.dynamic_update_slice_in_dim(cache["v"], v, 0, axis=1)
        cache["slots"] = cache["slots"].at[:, :S].set(
            jnp.arange(S, dtype=jnp.int32)[None])
    return cache


def prefill(cfg: ModelConfig, params: Params, tokens, max_len, *,
            prefix_embeds=None, use_flash=False, true_len=None):
    """Run the prompt, return (last-token logits, cache sized max_len).

    ``true_len``: optional int | (B,) int32 — true TEXT token count per
    row when ``tokens`` is right-padded to a prefill bucket.  Logits are
    then taken at each row's true last token (offset by the prefix
    length for VLM image tokens), and pad positions are marked invalid
    in the caches, so padded prefill is EXACT, not approximate.
    """
    x = L.embed(cfg, params["embed"], tokens)
    P = 0
    if prefix_embeds is not None:
        P = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    n = broadcast_true_len(true_len, B)
    n_full = None if n is None else n + P
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    trunk = params["trunk"]

    if cfg.pattern_period <= 1:
        def body(h, lp):
            h, kv = block_prefill(cfg, lp, h, positions, is_global=True,
                                  use_flash=use_flash)
            return h, kv
        x, (ks, vs) = lax.scan(body, x, _uniform_layers(cfg, trunk))
        cache = {"layers": jax.vmap(
            lambda k, v: _fill_global(cfg, B, max_len, k, v, n_full))(ks, vs)}
    else:
        def local_body(h, lp):
            h, kv = block_prefill(cfg, lp, h, positions, is_global=False,
                                  use_flash=use_flash)
            return h, kv

        def super_body(h, sp):
            h, lkv = lax.scan(local_body, h, sp["local"])
            h, gkv = block_prefill(cfg, sp["global"], h, positions,
                                   is_global=True, use_flash=use_flash)
            return h, (lkv, gkv)

        x, ((lks, lvs), (gks, gvs)) = lax.scan(super_body, x, trunk["super"])
        fill_l = jax.vmap(jax.vmap(
            lambda k, v: _fill_local(cfg, B, max_len, k, v, n_full)))
        fill_g = jax.vmap(
            lambda k, v: _fill_global(cfg, B, max_len, k, v, n_full))
        cache = {"super": {"local": fill_l(lks, lvs),
                           "global": fill_g(gks, gvs)}}
        if "rem_local" in trunk:
            x, (rks, rvs) = lax.scan(local_body, x, trunk["rem_local"])
            cache["rem_local"] = jax.vmap(
                lambda k, v: _fill_local(cfg, B, max_len, k, v, n_full))(
                    rks, rvs)

    _, norm = L.make_norm(cfg)
    x = x[:, -1:] if n_full is None else gather_last(x, n_full)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    return logits, cache


# ---------------------------------------------------------------------------
# paged prefill: admission writes straight into the engine cache
# ---------------------------------------------------------------------------

def scatter_cache_rows(full, rows, slots, axis: int):
    """Scatter an ``m``-row cache subtree into the batched engine cache
    at ``slots`` (every leaf shares the same batch ``axis``)."""
    return jax.tree.map(
        lambda f, r: L.scatter_rows(f, r, slots, axis), full, rows)


def prefill_paged(cfg: ModelConfig, params: Params, tokens, max_len,
                  cache, *, slots, write_tables=None, ctx_tables=None,
                  ctx_len=None, true_len=None, prefix_embeds=None,
                  use_flash=False):
    """Admission prefill fused with cache insertion: runs ``m`` prompt
    rows and writes their decode state DIRECTLY into the engine's
    batched cache — global-layer K/V lands in the shared page pool via
    ``write_tables`` (no dense strip is ever materialised and shadow-
    copied), local ring layers land in their dense rows at ``slots``.

    ``ctx_tables``/``ctx_len`` carry a radix prefix-cache hit: the rows
    are then the UNMATCHED SUFFIX only, positioned at ``ctx_len +
    arange(S)``, and global attention additionally reads the shared
    prefix's pages — the hit skips the prefix's prefill FLOPs entirely.
    Context is only ever passed for fully-paged configs
    (``pattern_period <= 1``); local-ring state cannot be reconstructed
    from pages (see ``model.prefix_sharable``).

    With ``write_tables=None`` this is the dense engine's admission:
    the same prefill math, scattered into per-slot ``max_len`` strips.
    Returns (per-row last-true-token logits, updated engine cache).
    """
    x = L.embed(cfg, params["embed"], tokens)
    P = 0
    if prefix_embeds is not None:
        P = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    n = broadcast_true_len(true_len, B)
    n_full = None if n is None else n + P
    off = (jnp.zeros((B,), jnp.int32) if ctx_len is None
           else jnp.asarray(ctx_len, jnp.int32))
    positions = off[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    paged = write_tables is not None
    if ctx_tables is not None and cfg.pattern_period > 1:
        raise ValueError("prefix-cache context requires a fully-paged "
                         "trunk (pattern_period <= 1)")
    trunk = params["trunk"]
    slots = jnp.asarray(slots, jnp.int32)
    new_cache = dict(cache)

    if cfg.pattern_period <= 1:
        if paged:
            def body(h, inp):
                lp, pg = inp
                h, pg2 = block_prefill_paged(
                    cfg, lp, h, positions, pg, write_tables, ctx_tables,
                    ctx_len, use_flash=use_flash)
                return h, pg2
            x, pages = lax.scan(body, x, (_uniform_layers(cfg, trunk),
                                          cache["layers"]))
            new_cache["layers"] = pages
        else:
            def body(h, lp):
                h, kv = block_prefill(cfg, lp, h, positions,
                                      is_global=True, use_flash=use_flash)
                return h, kv
            x, (ks, vs) = lax.scan(body, x, _uniform_layers(cfg, trunk))
            rows = jax.vmap(lambda k, v: _fill_global(
                cfg, B, max_len, k, v, n_full))(ks, vs)
            new_cache["layers"] = scatter_cache_rows(
                cache["layers"], rows, slots, 1)
    else:
        def local_body(h, lp):
            h, kv = block_prefill(cfg, lp, h, positions, is_global=False,
                                  use_flash=use_flash)
            return h, kv

        if paged:
            def super_body(h, inp):
                sp, pg = inp
                h, lkv = lax.scan(local_body, h, sp["local"])
                h, g = block_prefill_paged(cfg, sp["global"], h, positions,
                                           pg, write_tables,
                                           use_flash=use_flash)
                return h, (lkv, g)
            x, ((lks, lvs), gout) = lax.scan(
                super_body, x, (trunk["super"], cache["super"]["global"]))
        else:
            def super_body(h, sp):
                h, lkv = lax.scan(local_body, h, sp["local"])
                h, g = block_prefill(cfg, sp["global"], h, positions,
                                     is_global=True, use_flash=use_flash)
                return h, (lkv, g)
            x, ((lks, lvs), gout) = lax.scan(super_body, x, trunk["super"])
        fill_l = jax.vmap(jax.vmap(
            lambda k, v: _fill_local(cfg, B, max_len, k, v, n_full)))
        lrows = fill_l(lks, lvs)
        new_super = {
            "local": scatter_cache_rows(cache["super"]["local"], lrows,
                                        slots, 2),
        }
        if paged:
            new_super["global"] = gout
        else:
            gks, gvs = gout
            grows = jax.vmap(lambda k, v: _fill_global(
                cfg, B, max_len, k, v, n_full))(gks, gvs)
            new_super["global"] = scatter_cache_rows(
                cache["super"]["global"], grows, slots, 1)
        new_cache["super"] = new_super
        if "rem_local" in trunk:
            x, (rks, rvs) = lax.scan(local_body, x, trunk["rem_local"])
            rrows = jax.vmap(lambda k, v: _fill_local(
                cfg, B, max_len, k, v, n_full))(rks, rvs)
            new_cache["rem_local"] = scatter_cache_rows(
                cache["rem_local"], rrows, slots, 1)

    _, norm = L.make_norm(cfg)
    x = x[:, -1:] if n_full is None else gather_last(x, n_full)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    return logits, new_cache
