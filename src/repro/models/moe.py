"""Mixture-of-Experts trunk (kimi-k2, granite-moe).

TPU-native expert-parallel design: token->expert dispatch and combine are
*gathers* against an (E, capacity, d) expert buffer, built from a small
integer scatter.  The expert dimension shards over the ``model`` mesh
axis; XLA SPMD inserts the dispatch/combine all-to-alls.  Capacity-based
token dropping (GShard/Switch style) keeps every shape static.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T

Params = dict


# ---------------------------------------------------------------------------
# router + expert FFN
# ---------------------------------------------------------------------------

def init_moe_mlp(cfg: ModelConfig, key, stack=()) -> Params:
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "router": L._dense_init(k1, (d, E), stack),
        "w_gate": L._dense_init(k2, (E, d, f), stack, in_axis_size=d),
        "w_up": L._dense_init(k3, (E, d, f), stack, in_axis_size=d),
        "w_down": L._dense_init(k4, (E, f, d), stack, in_axis_size=f),
    }
    if cfg.num_shared_experts:
        p["shared"] = L.init_mlp(
            cfg, k5, d_ff=cfg.moe_d_ff * cfg.num_shared_experts, stack=stack)
    return p


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = math.ceil(n_tokens * cfg.num_experts_per_tok * cfg.capacity_factor
                  / cfg.num_experts)
    return max(c, 1)


def moe_mlp(cfg: ModelConfig, p: Params, x, token_mask=None,
            expert_capacity: Optional[int] = None):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar f32).
    ``token_mask``: optional (B, S) bool — False marks padding tokens
    excluded from expert capacity (see ``_moe_tokens``).
    ``expert_capacity``: optional slots per expert (per row under
    ``moe_rowwise``) in place of ``capacity`` of the token count.

    cfg.moe_rowwise: dispatch each sequence independently (vmap over
    batch) — the expert buffers then carry the batch dim and shard over
    `data`, instead of one GLOBAL (E, c) buffer that every model-shard
    must process in full (16x redundant expert FLOPs on the 16-way data
    mesh; EXPERIMENTS.md §Perf).  Per-row capacity is the usual
    trade-off (slightly higher dropping variance).
    """
    B, S, d = x.shape
    if token_mask is not None:
        token_mask = token_mask.reshape(B, S)
    if cfg.moe_rowwise:
        out, aux = jax.vmap(
            lambda row, m: _moe_tokens(cfg, p, row, m, expert_capacity))(
                x.reshape(B, S, d),
                (jnp.ones((B, S), bool) if token_mask is None
                 else token_mask))
        return out.reshape(B, S, d), jnp.mean(aux)
    flat_mask = None if token_mask is None else token_mask.reshape(B * S)
    out, aux = _moe_tokens(cfg, p, x.reshape(B * S, d), flat_mask,
                           expert_capacity)
    return out.reshape(B, S, d), aux


def _moe_tokens(cfg: ModelConfig, p: Params, xf, token_mask=None,
                expert_capacity: Optional[int] = None):
    """Capacity-based top-k dispatch over a flat token set xf: (N, d).

    ``token_mask``: optional (N,) bool — False rows (padding) are routed
    to a sentinel expert id E so they never occupy real expert capacity
    (a pad stealing a capacity slot would silently drop a REAL token and
    change its output — padded prefill must be exact).
    ``expert_capacity`` overrides ``capacity(cfg, N)``.
    """
    N, d = xf.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    c = capacity(cfg, N) if expert_capacity is None else expert_capacity

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (N, E)
    gate, eidx = lax.top_k(probs, k)                           # (N, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    if token_mask is not None:
        eidx = jnp.where(token_mask[:, None], eidx, E)
        gate = gate * token_mask[:, None].astype(gate.dtype)

    # load-balancing auxiliary loss (Switch): E * <f_e> . <p_e>
    me = jnp.mean(probs, axis=0)                               # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(eidx, E, dtype=jnp.float32), axis=1), axis=0)
    aux = E * jnp.sum(me * ce)

    # position of each (token, choice) within its expert, via stable sort
    ef = eidx.reshape(-1)                                      # (N*k,)
    order = jnp.argsort(ef, stable=True)                       # (N*k,)
    es = ef[order]
    idx = jnp.arange(N * k, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), es[1:] != es[:-1]])
    seg_start = jax.lax.associative_scan(jnp.maximum,
                                         jnp.where(is_start, idx, 0))
    pos_sorted = idx - seg_start
    pos = jnp.zeros((N * k,), jnp.int32).at[order].set(pos_sorted)
    pos = pos.reshape(N, k)
    valid = pos < c

    # dispatch: (E, c) inverse map expert-slot -> token row (sentinel N)
    tok = jnp.broadcast_to(idx.reshape(N, k)[:, :1] * 0
                           + jnp.arange(N, dtype=jnp.int32)[:, None], (N, k))
    inv = jnp.full((E, c), N, jnp.int32)
    inv = inv.at[eidx, jnp.where(valid, pos, c)].set(tok, mode="drop")
    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xs = x_pad[inv]                                            # (E, c, d)

    # expert FFN (E-sharded einsums); expert weights may be int8 dicts
    # ({"q", "scale"}, layers.quantize_matmul_params) — the E-stacked
    # einsum has no 2D matmul form, so dequantize densely here instead
    # of routing through weight_einsum
    def _w(leaf):
        if isinstance(leaf, dict):
            return (leaf["q"].astype(jnp.float32)
                    * leaf["scale"][..., None, :].astype(jnp.float32)
                    ).astype(xf.dtype)
        return leaf.astype(xf.dtype)

    wg = _w(p["w_gate"])
    wu = _w(p["w_up"])
    wd = _w(p["w_down"])
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, wg)) \
        * jnp.einsum("ecd,edf->ecf", xs, wu)
    ys = jnp.einsum("ecf,efd->ecd", h, wd)                     # (E, c, d)

    # combine: gather each token's k expert outputs (dropped -> zero row)
    ys_pad = jnp.concatenate(
        [ys, jnp.zeros((E, 1, d), ys.dtype)], axis=1)          # (E, c+1, d)
    slot = jnp.where(valid, pos, c)
    y_tok = ys_pad[eidx, slot]                                 # (N, k, d)
    out = jnp.sum(y_tok * gate.astype(y_tok.dtype)[..., None], axis=1)

    if cfg.num_shared_experts:
        out = out + L.mlp(p["shared"], xf[None]).reshape(N, d)
    return out, aux


# ---------------------------------------------------------------------------
# blocks & trunk
# ---------------------------------------------------------------------------

def init_moe_block(cfg: ModelConfig, key, stack=()) -> Params:
    norm_init, _ = L.make_norm(cfg)
    k1, k2 = jax.random.split(key)
    return {
        "attn": L.init_attention(cfg, k1, stack),
        "moe": init_moe_mlp(cfg, k2, stack),
        "ln1": norm_init(cfg.d_model, stack),
        "ln2": norm_init(cfg.d_model, stack),
    }


def init_params(cfg: ModelConfig, key) -> Params:
    norm_init, _ = L.make_norm(cfg)
    ks = jax.random.split(key, 5)
    n_moe = cfg.num_layers - cfg.first_dense_layers
    p = {
        "embed": L.init_embedding(cfg, ks[0]),
        "unembed": L.init_unembed(cfg, ks[1]),
        "moe_layers": init_moe_block(cfg, ks[2], stack=(n_moe,)),
        "final_norm": norm_init(cfg.d_model),
    }
    if cfg.first_dense_layers:
        p["dense_layers"] = T.init_block(
            cfg, ks[3], stack=(cfg.first_dense_layers,))
    return p


def moe_block_fwd(cfg: ModelConfig, p: Params, x, positions, *,
                  use_flash=False, token_mask=None):
    _, norm = L.make_norm(cfg)
    h = norm(p["ln1"], x)
    a, k, v = L.attention_fwd(cfg, p["attn"], h, positions, is_global=True,
                              use_flash=use_flash)
    x = x + a
    h = norm(p["ln2"], x)
    m, aux = moe_mlp(cfg, p["moe"], h, token_mask=token_mask)
    return x + m, aux, (k, v)


def moe_block_decode(cfg: ModelConfig, p: Params, x, cache, pos):
    _, norm = L.make_norm(cfg)
    h = norm(p["ln1"], x)
    a, new_cache = L.attention_decode(cfg, p["attn"], h, cache, pos,
                                      is_global=True)
    x = x + a
    h = norm(p["ln2"], x)
    m, _ = moe_mlp(cfg, p["moe"], h)
    return x + m, new_cache


def moe_block_decode_paged(cfg: ModelConfig, p: Params, x, cache, pos,
                           block_tables, use_pallas: bool = False):
    _, norm = L.make_norm(cfg)
    h = norm(p["ln1"], x)
    a, new_cache = L.attention_decode_paged(cfg, p["attn"], h, cache, pos,
                                            block_tables,
                                            use_pallas=use_pallas)
    x = x + a
    h = norm(p["ln2"], x)
    m, _ = moe_mlp(cfg, p["moe"], h)
    return x + m, new_cache


def _extend_token_mask(cfg: ModelConfig, x, valid_len, narrow=None):
    """The REAL tokens of an extend wave, and the expert capacity they
    get: (mask, capacity), either None where it does not apply.

    Host-side padding must not steal expert capacity from real tokens
    (same contract as padded prefill), so pads are False in the mask:
    in the (B, S) form the rows ``i >= valid_len``, in the packed form
    (``narrow``, x (1, n + R*S, d)) also the narrow rows with ``valid``
    0.  The (B, S) form keeps the capacity its static B*S shape gives —
    the usual carve-out; with capacity ample nothing drops and extend ==
    sequential decode.  The packed wave of n slots and R wide rows of
    width S gets ``min(n + R*S, capacity(cfg, n*S))``: the capacity the
    (n, S) wave of the same slots had, bounded by the wave's token
    count, as an expert takes at most one slot per token.  So where the
    (n, S) wave dropped nothing (capacity at least its token count, as
    at ``capacity_factor`` = experts / top-k), nothing drops here."""
    if narrow is None:
        if valid_len is None:
            return None, None
        S = x.shape[1]
        mask = jnp.arange(S, dtype=jnp.int32)[None, :] < valid_len[:, None]
        return mask, None
    n, R = narrow.pos.shape[0], valid_len.shape[0]
    S = (x.shape[1] - n) // R
    wide = jnp.arange(S, dtype=jnp.int32)[None, :] < valid_len[:, None]
    mask = jnp.concatenate([narrow.valid > 0, wide.reshape(-1)])[None]
    return mask, min(x.shape[1], capacity(cfg, n * S))


def moe_block_extend_paged(cfg: ModelConfig, p: Params, x, pos, cache,
                           block_tables, valid_len=None, *,
                           use_pallas: bool = False, narrow=None):
    """``moe_block_decode_paged`` for S tokens at once (speculative
    verify / chunked catch-up; with ``narrow`` the packed two-group
    wave, see ``transformer.extend_paged``)."""
    _, norm = L.make_norm(cfg)
    h = norm(p["ln1"], x)
    a, new_cache = L.attention_extend_paged(cfg, p["attn"], h, pos, cache,
                                            block_tables, valid_len,
                                            use_pallas=use_pallas,
                                            narrow=narrow)
    x = x + a
    h = norm(p["ln2"], x)
    mask, c = _extend_token_mask(cfg, x, valid_len, narrow)
    m, _ = moe_mlp(cfg, p["moe"], h, token_mask=mask, expert_capacity=c)
    return x + m, new_cache


def moe_block_prefill_paged(cfg: ModelConfig, p: Params, x, positions,
                            pages, write_tables, ctx_tables=None,
                            ctx_len=None, *, use_flash=False,
                            token_mask=None):
    """``moe_block_fwd`` writing attention K/V straight into its page
    pool (and reading a shared-prefix chain on a radix-cache hit)."""
    _, norm = L.make_norm(cfg)
    h = norm(p["ln1"], x)
    a, new_pages = L.attention_prefill_paged(
        cfg, p["attn"], h, positions, pages, write_tables, ctx_tables,
        ctx_len, use_flash=use_flash)
    x = x + a
    h = norm(p["ln2"], x)
    m, _ = moe_mlp(cfg, p["moe"], h, token_mask=token_mask)
    return x + m, new_pages


def forward(cfg: ModelConfig, params: Params, tokens, *, use_flash=False,
            remat: Optional[str] = None):
    """Returns (logits, aux_loss)."""
    x = L.embed(cfg, params["embed"], tokens)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    if cfg.first_dense_layers:
        def dbody(h, lp):
            return T.block_fwd(cfg, lp, h, positions, is_global=True,
                               use_flash=use_flash), None
        x, _ = lax.scan(T._maybe_remat(dbody, remat), x,
                        params["dense_layers"])

    def body(h, lp):
        h, aux, _ = moe_block_fwd(cfg, lp, h, positions, use_flash=use_flash)
        return h, aux
    x, auxes = lax.scan(T._maybe_remat(body, remat), x, params["moe_layers"])

    _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    return logits, jnp.mean(auxes)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    n_moe = cfg.num_layers - cfg.first_dense_layers
    c = {"moe_layers": L.init_kv_cache(cfg, batch, max_len, stack=(n_moe,))}
    if cfg.first_dense_layers:
        c["dense_layers"] = L.init_kv_cache(
            cfg, batch, max_len, stack=(cfg.first_dense_layers,))
    return c


def decode_step(cfg: ModelConfig, params: Params, cache: Params, tokens, pos):
    x = L.embed(cfg, params["embed"], tokens)
    new_cache = {}
    if cfg.first_dense_layers:
        def dbody(h, inp):
            lp, cc = inp
            h, c2 = T.block_decode(cfg, lp, h, cc, pos, is_global=True)
            return h, c2
        x, dc = lax.scan(dbody, x, (params["dense_layers"],
                                    cache["dense_layers"]))
        new_cache["dense_layers"] = dc

    def body(h, inp):
        lp, cc = inp
        h, c2 = moe_block_decode(cfg, lp, h, cc, pos)
        return h, c2
    x, mc = lax.scan(body, x, (params["moe_layers"], cache["moe_layers"]))
    new_cache["moe_layers"] = mc

    _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    return logits, new_cache


def init_paged_cache(cfg: ModelConfig, batch: int, max_len: int,
                     num_blocks: int, block_size: int,
                     kv_dtype=None) -> Params:
    """All MoE attention layers are global: every KV cache is paged."""
    del batch, max_len
    quant = kv_dtype == "int8"
    n_moe = cfg.num_layers - cfg.first_dense_layers
    c = {"moe_layers": L.init_kv_pages(cfg, num_blocks, block_size,
                                       stack=(n_moe,), quant=quant)}
    if cfg.first_dense_layers:
        c["dense_layers"] = L.init_kv_pages(
            cfg, num_blocks, block_size, stack=(cfg.first_dense_layers,),
            quant=quant)
    return c


def decode_step_paged(cfg: ModelConfig, params: Params, cache: Params,
                      tokens, pos, block_tables, use_pallas: bool = False):
    x = L.embed(cfg, params["embed"], tokens)
    new_cache = {}
    if cfg.first_dense_layers:
        def dbody(h, inp):
            lp, cc = inp
            h, c2 = T.block_decode_paged(cfg, lp, h, cc, pos, block_tables,
                                         use_pallas)
            return h, c2
        x, dc = lax.scan(dbody, x, (params["dense_layers"],
                                    cache["dense_layers"]))
        new_cache["dense_layers"] = dc

    def body(h, inp):
        lp, cc = inp
        h, c2 = moe_block_decode_paged(cfg, lp, h, cc, pos, block_tables,
                                       use_pallas)
        return h, c2
    x, mc = lax.scan(body, x, (params["moe_layers"], cache["moe_layers"]))
    new_cache["moe_layers"] = mc

    _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    return logits, new_cache


def extend_paged(cfg: ModelConfig, params: Params, cache: Params, tokens,
                 pos, block_tables, valid_len=None,
                 use_pallas: bool = False, narrow=None):
    """Score S tokens per row against the paged cache in one call (all
    MoE attention is global => fully paged).  See
    ``transformer.extend_paged`` for the (R, S) and packed layouts, the
    row semantics and the ``valid_len`` write-drop contract."""
    x = L.embed(cfg, params["embed"], L.wave_tokens(tokens, narrow))
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (tokens.shape[0],))
    new_cache = {}
    if cfg.first_dense_layers:
        def dbody(h, inp):
            lp, cc = inp
            h, c2 = T.block_extend_paged(cfg, lp, h, pos, cc, block_tables,
                                         valid_len, use_pallas=use_pallas,
                                         narrow=narrow)
            return h, c2
        x, dc = lax.scan(dbody, x, (params["dense_layers"],
                                    cache["dense_layers"]))
        new_cache["dense_layers"] = dc

    def body(h, inp):
        lp, cc = inp
        h, c2 = moe_block_extend_paged(cfg, lp, h, pos, cc, block_tables,
                                       valid_len, use_pallas=use_pallas,
                                       narrow=narrow)
        return h, c2
    x, mc = lax.scan(body, x, (params["moe_layers"], cache["moe_layers"]))
    new_cache["moe_layers"] = mc

    _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    if narrow is not None:
        logits = L.split_wave(logits, narrow, tokens.shape[0])
    return logits, new_cache


def extend(cfg: ModelConfig, params: Params, cache: Params, tokens, pos,
           valid_len=None):
    """Dense twin of ``extend_paged`` (strip caches, same row/write
    semantics)."""
    x = L.embed(cfg, params["embed"], tokens)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (x.shape[0],))
    new_cache = {}
    if cfg.first_dense_layers:
        def dbody(h, inp):
            lp, cc = inp
            h, c2 = T.block_extend(cfg, lp, h, cc, pos, is_global=True,
                                   valid_len=valid_len)
            return h, c2
        x, dc = lax.scan(dbody, x, (params["dense_layers"],
                                    cache["dense_layers"]))
        new_cache["dense_layers"] = dc

    def body(h, inp):
        lp, cc = inp
        _, norm = L.make_norm(cfg)
        hh = norm(lp["ln1"], h)
        a, c2 = L.attention_extend(cfg, lp["attn"], hh, cc, pos,
                                   is_global=True, valid_len=valid_len)
        h = h + a
        hh = norm(lp["ln2"], h)
        m, _ = moe_mlp(cfg, lp["moe"], hh,
                       token_mask=_extend_token_mask(cfg, h, valid_len)[0])
        return h + m, c2
    x, mc = lax.scan(body, x, (params["moe_layers"], cache["moe_layers"]))
    new_cache["moe_layers"] = mc

    _, norm = L.make_norm(cfg)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    return logits, new_cache


def prefill(cfg: ModelConfig, params: Params, tokens, max_len, *,
            use_flash=False, true_len=None):
    x = L.embed(cfg, params["embed"], tokens)
    B, S, _ = x.shape
    n = T.broadcast_true_len(true_len, B)
    token_mask = (None if n is None else
                  jnp.arange(S, dtype=jnp.int32)[None, :] < n[:, None])
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cache = {}
    if cfg.first_dense_layers:
        def dbody(h, lp):
            h, kv = T.block_prefill(cfg, lp, h, positions, is_global=True,
                                    use_flash=use_flash)
            return h, kv
        x, (ks, vs) = lax.scan(dbody, x, params["dense_layers"])
        cache["dense_layers"] = jax.vmap(
            lambda k, v: T._fill_global(cfg, B, max_len, k, v, n))(ks, vs)

    def body(h, lp):
        h, _, kv = moe_block_fwd(cfg, lp, h, positions, use_flash=use_flash,
                                 token_mask=token_mask)
        return h, kv
    x, (ks, vs) = lax.scan(body, x, params["moe_layers"])
    cache["moe_layers"] = jax.vmap(
        lambda k, v: T._fill_global(cfg, B, max_len, k, v, n))(ks, vs)

    _, norm = L.make_norm(cfg)
    x = x[:, -1:] if n is None else T.gather_last(x, n)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    return logits, cache


def prefill_paged(cfg: ModelConfig, params: Params, tokens, max_len,
                  cache, *, slots, write_tables=None, ctx_tables=None,
                  ctx_len=None, true_len=None, use_flash=False):
    """Admission prefill writing K/V straight into the engine cache
    (all MoE attention layers are global => fully paged, so radix
    prefix-cache context is supported).  See ``T.prefill_paged``.

    MoE caveat: expert CAPACITY derives from the static suffix token
    count, so under capacity pressure a hit-admitted suffix can drop a
    different token set than the same tokens inside a cold full-prompt
    prefill — the usual static-shape carve-out (``serving/__init__``);
    with capacity_factor high enough that nothing drops, hits are
    bit-exact like every other family.
    """
    x = L.embed(cfg, params["embed"], tokens)
    B, S, _ = x.shape
    n = T.broadcast_true_len(true_len, B)
    token_mask = (None if n is None else
                  jnp.arange(S, dtype=jnp.int32)[None, :] < n[:, None])
    off = (jnp.zeros((B,), jnp.int32) if ctx_len is None
           else jnp.asarray(ctx_len, jnp.int32))
    positions = off[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    paged = write_tables is not None
    slots = jnp.asarray(slots, jnp.int32)
    new_cache = dict(cache)

    if cfg.first_dense_layers:
        if paged:
            def dbody(h, inp):
                lp, pg = inp
                h, pg2 = T.block_prefill_paged(
                    cfg, lp, h, positions, pg, write_tables, ctx_tables,
                    ctx_len, use_flash=use_flash)
                return h, pg2
            x, dpages = lax.scan(dbody, x, (params["dense_layers"],
                                            cache["dense_layers"]))
            new_cache["dense_layers"] = dpages
        else:
            def dbody(h, lp):
                h, kv = T.block_prefill(cfg, lp, h, positions,
                                        is_global=True, use_flash=use_flash)
                return h, kv
            x, (ks, vs) = lax.scan(dbody, x, params["dense_layers"])
            rows = jax.vmap(lambda k, v: T._fill_global(
                cfg, B, max_len, k, v, n))(ks, vs)
            new_cache["dense_layers"] = T.scatter_cache_rows(
                cache["dense_layers"], rows, slots, 1)

    if paged:
        def body(h, inp):
            lp, pg = inp
            h, pg2 = moe_block_prefill_paged(
                cfg, lp, h, positions, pg, write_tables, ctx_tables,
                ctx_len, use_flash=use_flash, token_mask=token_mask)
            return h, pg2
        x, mpages = lax.scan(body, x, (params["moe_layers"],
                                       cache["moe_layers"]))
        new_cache["moe_layers"] = mpages
    else:
        def body(h, lp):
            h, _, kv = moe_block_fwd(cfg, lp, h, positions,
                                     use_flash=use_flash,
                                     token_mask=token_mask)
            return h, kv
        x, (ks, vs) = lax.scan(body, x, params["moe_layers"])
        rows = jax.vmap(lambda k, v: T._fill_global(
            cfg, B, max_len, k, v, n))(ks, vs)
        new_cache["moe_layers"] = T.scatter_cache_rows(
            cache["moe_layers"], rows, slots, 1)

    _, norm = L.make_norm(cfg)
    x = x[:, -1:] if n is None else T.gather_last(x, n)
    x = norm(params["final_norm"], x)
    logits = L.unembed(cfg, params["embed"], params["unembed"], x)
    return logits, new_cache
