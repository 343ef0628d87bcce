"""Shared neural-net building blocks (pure JAX, functional params-as-pytrees).

Conventions
-----------
* Parameters are nested dicts of jnp arrays. Init functions accept a
  ``stack`` tuple prefix so layers can be stacked for ``jax.lax.scan``.
* Activations run in ``cfg.activation_dtype`` (bf16 by default); softmax
  and norms accumulate in float32.
* Attention is GQA throughout: H query heads grouped over K kv heads.
* Projection weights may be int8-quantized ({"q", "scale"} dict leaves,
  ``quantize_matmul_params``); every matmul site goes through
  ``weight_einsum`` which dispatches on the leaf type.
* The paged KV pool has an int8 layout (``init_kv_pages(quant=True)``):
  K/V bytes are int8 with one f32 scale per (page, token offset,
  kv head) riding in parallel ``k_scale``/``v_scale`` pool leaves; all
  paged attention paths detect it via the ``k_scale`` key.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def _dense_init(key, shape, stack=(), in_axis_size=None, dtype=jnp.float32):
    """Truncated-normal fan-in init, with optional stacking prefix."""
    full = tuple(stack) + tuple(shape)
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return std * jax.random.truncated_normal(key, -2.0, 2.0, full, dtype)


def _zeros(shape, stack=(), dtype=jnp.float32):
    return jnp.zeros(tuple(stack) + tuple(shape), dtype)


def _ones(shape, stack=(), dtype=jnp.float32):
    return jnp.ones(tuple(stack) + tuple(shape), dtype)


# ---------------------------------------------------------------------------
# int8 quantization: KV pages + projection weights
# ---------------------------------------------------------------------------

KV_QMAX = 127.0


def quantize_kv(x, eps: float = 1e-8):
    """Symmetric int8 quantization of a K/V tensor along ``head_dim``.

    x: (..., hd).  Returns (q int8 (..., hd), scale f32 (...)): one
    scale per head_dim vector — ``scale = max|x| / 127``,
    ``q = round(x / scale)``.  The group is deliberately the head_dim
    vector of ONE (token, kv-head) row: committed page rows are
    write-once (rollback, CoW and in-flight prefix sharing all reason
    over bytes that never change after commit), so a scale must never
    depend on tokens written later — a coarser whole-page scale would
    have to re-quantize committed rows on every incremental
    ``scatter_kv_tokens`` write.  Overhead is 4/hd bytes per element
    (~6% at hd=64) on top of the 4x int8-vs-f32 saving.
    """
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / KV_QMAX + eps
    q = jnp.clip(jnp.round(xf / scale[..., None]), -KV_QMAX, KV_QMAX)
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of ``quantize_kv``: q (..., hd) int8, scale (...)."""
    out = q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
    return out.astype(dtype)


def kv_pages_quantized(pages) -> bool:
    """Is this pool dict the int8 layout (scale leaves present)?"""
    return "k_scale" in pages


# weight name -> (contraction dims, output dims), counted from the end
# of the leaf shape (any leading dims are lax.scan stack axes)
QUANT_WEIGHT_DIMS = {
    "wq": (1, 2), "wk": (1, 2), "wv": (1, 2), "wo": (2, 1),
    "w_gate": (1, 1), "w_up": (1, 1), "w_down": (1, 1),
    "w_in": (1, 1), "w_out": (1, 1),
}


def quantize_weight(w, n_in: int, n_out: int):
    """Per-output-channel symmetric int8 quantization of one projection
    weight: the trailing ``n_in`` + ``n_out`` dims are the matmul dims,
    anything before is a stack prefix (kept on BOTH leaves so
    ``lax.scan`` slices quantized layers exactly like f32 ones)."""
    in_axes = tuple(range(w.ndim - n_in - n_out, w.ndim - n_out))
    wf = w.astype(jnp.float32)
    scale = (jnp.max(jnp.abs(wf), axis=in_axes, keepdims=True) / KV_QMAX
             + 1e-12)
    q = jnp.clip(jnp.round(wf / scale), -KV_QMAX, KV_QMAX).astype(jnp.int8)
    return {"q": q, "scale": jnp.squeeze(scale, axis=in_axes)}


def quantize_matmul_params(params):
    """Copy of ``params`` with every attention/MLP projection weight
    replaced by its int8 quantization ({"q", "scale"} dict leaves —
    ``weight_einsum`` dispatches on the dict).  Norms, embeddings and
    biases stay full precision (cheap and precision-critical).  Used to
    quantize a resident draft model's weights (drafts tolerate int8;
    verify logits are untouched, so greedy speculation stays bit-exact
    while draft bytes shrink ~4x)."""
    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for name, sub in node.items():
            dims = QUANT_WEIGHT_DIMS.get(name)
            if (dims is not None and not isinstance(sub, dict)
                    and sub.ndim >= sum(dims)):
                out[name] = quantize_weight(sub, *dims)
            else:
                out[name] = walk(sub)
        return out
    return walk(params)


def weight_einsum(eq, x, w):
    """``jnp.einsum(eq, x, w.astype(x.dtype))`` where ``w`` may instead
    be an int8-quantized weight ({"q", "scale"}; see
    ``quantize_weight``).  Quantized weights contract through the
    ``kernels.quant_matmul`` Pallas kernel on TPU (int8 HBM -> VREG
    dequant -> bf16 MXU) and through the jnp dequant twin elsewhere —
    both implement the ``kernels.ref.quant_matmul_ref`` semantics.
    Assumes (true for every projection in this module) that ``eq``
    contracts x's trailing dims against w's leading matmul dims in
    order and appends w's output dims.
    """
    if not isinstance(w, dict):
        return jnp.einsum(eq, x, w.astype(x.dtype))
    x_spec, w_spec = eq.split("->")[0].split(",")
    n_in = sum(1 for c in w_spec if c in x_spec)
    q, scale = w["q"], w["scale"]
    kd = math.prod(q.shape[:n_in])
    nd = math.prod(q.shape[n_in:])
    x2 = x.reshape(-1, kd)
    if jax.default_backend() == "tpu":
        from repro.kernels import ops as kernel_ops
        out2 = kernel_ops.quant_matmul(x2, q.reshape(kd, nd),
                                       scale.reshape(nd).astype(jnp.float32),
                                       out_dtype=x.dtype)
    else:
        wf = (q.reshape(kd, nd).astype(jnp.float32)
              * scale.reshape(nd)[None, :].astype(jnp.float32))
        out2 = jnp.dot(x2.astype(jnp.float32), wf).astype(x.dtype)
    return out2.reshape(x.shape[:x.ndim - n_in] + q.shape[n_in:])


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, stack=()):
    return {"scale": _zeros((d,), stack)}  # gemma-style (1 + scale)


def rmsnorm(params, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(dtype)


def init_layernorm(d: int, stack=()):
    return {"scale": _ones((d,), stack), "bias": _zeros((d,), stack)}


def layernorm(params, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(dtype)


def make_norm(cfg: ModelConfig):
    if cfg.use_layernorm:
        return (lambda d, stack=(): init_layernorm(d, stack),
                lambda p, x: layernorm(p, x, cfg.norm_eps))
    return (lambda d, stack=(): init_rmsnorm(d, stack),
            lambda p, x: rmsnorm(p, x, cfg.norm_eps))


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim//2,)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, n_heads, head_dim); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd//2)
    angles = angles[..., None, :]  # (..., S, 1, hd//2) to broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int) -> jnp.ndarray:
    """Whisper-style fixed sinusoidal embedding table (n, d)."""
    half = d // 2
    log_timescale = math.log(10_000.0) / max(half - 1, 1)
    inv = jnp.exp(-log_timescale * jnp.arange(half, dtype=jnp.float32))
    scaled = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(scaled), jnp.cos(scaled)], axis=-1)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key, stack=()):
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(k1, (d, H, hd), stack, in_axis_size=d),
        "wk": _dense_init(k2, (d, K, hd), stack, in_axis_size=d),
        "wv": _dense_init(k3, (d, K, hd), stack, in_axis_size=d),
        "wo": _dense_init(k4, (H, hd, d), stack, in_axis_size=H * hd),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = init_rmsnorm(hd, stack)
        p["k_norm"] = init_rmsnorm(hd, stack)
    return p


def _softcap(x, cap: float):
    if cap and cap > 0.0:
        return cap * jnp.tanh(x / cap)
    return x


def attention_weights_and_out(q, k, v, mask, *, scale, softcap=0.0):
    """GQA attention core.

    q: (B, S, K, G, hd)   k, v: (B, T, K, hd)   mask: broadcast (B,1,1,S,T)
    returns (B, S, K, G, hd)
    """
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k, preferred_element_type=jnp.float32)
    scores = _softcap(scores * scale, softcap)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v)


def causal_mask(s: int, t: int, q_offset=0) -> jnp.ndarray:
    """(S, T) causal mask; q position i attends kv positions <= i+q_offset."""
    qpos = jnp.arange(s)[:, None] + q_offset
    kpos = jnp.arange(t)[None, :]
    return kpos <= qpos


def window_mask(s: int, t: int, window: int, q_offset=0) -> jnp.ndarray:
    qpos = jnp.arange(s)[:, None] + q_offset
    kpos = jnp.arange(t)[None, :]
    return (kpos <= qpos) & (kpos > qpos - window)


def _project_seq(cfg: ModelConfig, params, x, positions, *,
                 is_global: bool, kv_x=None):
    """Shared q/k/v projection + qk-norm + RoPE for the full-sequence
    paths (``attention_fwd`` and the paged suffix prefill) — one
    definition so both produce bit-identical projections."""
    q = weight_einsum("bsd,dhq->bshq", x, params["wq"])
    src = x if kv_x is None else kv_x
    k = weight_einsum("btd,dkq->btkq", src, params["wk"])
    v = weight_einsum("btd,dkq->btkq", src, params["wv"])

    if cfg.use_qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(params["k_norm"], k, cfg.norm_eps)

    if not cfg.use_abs_pos and kv_x is None:
        theta = (cfg.rope_theta_global
                 if (is_global and cfg.rope_theta_global) else cfg.rope_theta)
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    return q, k, v


def attention_fwd(cfg: ModelConfig, params, x, positions, *,
                  is_global: bool, kv_x=None, causal: bool = True,
                  use_flash: bool = False):
    """Full-sequence attention (training / prefill).

    x: (B, S, d). kv_x: cross-attention source (B, T, d) or None.
    Local (sliding-window) layers use a chunked implementation when the
    sequence is long enough, giving true O(S*W) cost.
    Returns (out (B,S,d), k, v) — k/v returned for cache construction.
    """
    B, S, d = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5

    q, k, v = _project_seq(cfg, params, x, positions,
                           is_global=is_global, kv_x=kv_x)

    T = k.shape[1]
    qg = q.reshape(B, S, K, G, hd)

    window = 0 if is_global else cfg.local_window
    if kv_x is not None or not causal:
        mask = jnp.ones((S, T), bool)
        out = attention_weights_and_out(qg, k, v, mask[None, None, None],
                                        scale=scale, softcap=cfg.attn_logit_softcap)
    elif use_flash:
        from repro.kernels import ops as kernel_ops
        out = kernel_ops.flash_attention(
            qg.reshape(B, S, H, hd), k, v, scale=scale,
            window=window, softcap=cfg.attn_logit_softcap,
        ).reshape(B, S, K, G, hd)
    elif window and S > 2 * window and S % window == 0:
        out = _chunked_local_attention(qg, k, v, window, scale,
                                       cfg.attn_logit_softcap)
    elif S >= BLOCKWISE_THRESHOLD and S % BLOCKWISE_CHUNK == 0 \
            and T % BLOCKWISE_CHUNK == 0:
        # long full-causal prefill: online-softmax blockwise attention —
        # O(S*chunk) live memory instead of an O(S^2) score tensor (the
        # pure-jnp twin of kernels/flash_attention, used where Pallas
        # can't be lowered for the dry-run)
        out = _blockwise_causal_attention(qg, k, v, scale,
                                          cfg.attn_logit_softcap,
                                          chunk=BLOCKWISE_CHUNK)
    else:
        m = (window_mask(S, T, window) if window else causal_mask(S, T))
        out = attention_weights_and_out(qg, k, v, m[None, None, None],
                                        scale=scale, softcap=cfg.attn_logit_softcap)

    out = out.reshape(B, S, H, hd)
    o = weight_einsum("bshq,hqd->bsd", out, params["wo"])
    return o, k, v


def _chunked_local_attention(qg, k, v, window, scale, softcap):
    """Sliding-window attention in O(S * 2W): chunk + previous chunk.

    qg: (B, S, K, G, hd) with S % window == 0.
    """
    B, S, K, G, hd = qg.shape
    W = window
    C = S // W
    qc = qg.reshape(B, C, W, K, G, hd)
    kc = k.reshape(B, C, W, K, hd)
    vc = v.reshape(B, C, W, K, hd)
    # previous chunk (zeros before chunk 0)
    kprev = jnp.concatenate([jnp.zeros_like(kc[:, :1]), kc[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vc[:, :1]), vc[:, :-1]], axis=1)
    k2 = jnp.concatenate([kprev, kc], axis=2)  # (B, C, 2W, K, hd)
    v2 = jnp.concatenate([vprev, vc], axis=2)

    qpos = jnp.arange(W)[:, None] + W            # within the 2W frame
    kpos = jnp.arange(2 * W)[None, :]
    m = (kpos <= qpos) & (kpos > qpos - W)       # (W, 2W)
    # chunk 0 has no previous chunk
    first = m & (kpos >= W)
    mask = jnp.concatenate(
        [first[None], jnp.broadcast_to(m, (C - 1, W, 2 * W))], axis=0)

    scores = jnp.einsum("bcskgd,bctkd->bckgst", qc, k2,
                        preferred_element_type=jnp.float32)
    scores = _softcap(scores * scale, softcap)
    scores = jnp.where(mask[None, :, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bckgst,bctkd->bcskgd", probs, v2)
    return out.reshape(B, S, K, G, hd)


BLOCKWISE_THRESHOLD = 8192
BLOCKWISE_CHUNK = 1024

# §Perf knob (set by launch builders, process-scoped): sequence-parallel
# blockwise attention.  Tuple (n_shards, NamedSharding for the
# (B, shard, S/shard, K, G, hd) query layout) or None.  Used when query
# heads cannot shard over the model axis: instead of replicating the
# whole attention, each model-shard computes its 1/n slice of the query
# sequence against (once-gathered) full K/V — no redundant FLOPs and
# 1/n of the score HBM traffic per chip.
SEQ_PARALLEL_ATTN = None


def _blockwise_causal_attention(qg, k, v, scale, softcap,
                                chunk: int = BLOCKWISE_CHUNK):
    """Memory-efficient causal attention: lax.scan over (q, kv) chunks
    with a running (max, denom, acc) — the flash algorithm in pure jnp.

    qg: (B, S, K, G, hd); k, v: (B, T, K, hd).  Strictly-above-diagonal
    chunk pairs are masked (not skipped): ~2x upper-triangle FLOPs, but
    O(S * chunk) live memory, which is what prefill_32k needs to fit.

    With SEQ_PARALLEL_ATTN set, the query sequence is sharded over the
    model axis (vmap over shards stays parallel; lax.map inside each
    shard walks its local chunks).
    """
    B, S, K, G, hd = qg.shape
    T = k.shape[1]
    sp = SEQ_PARALLEL_ATTN
    if sp is not None:
        n_sh, shard_sharding = sp
        per = S // n_sh
        if S % n_sh == 0 and per % chunk == 0 and per >= chunk:
            qs = qg.reshape(B, n_sh, per, K, G, hd)
            qs = lax.with_sharding_constraint(qs, shard_sharding)
            offs = jnp.arange(n_sh) * per

            def per_shard(q_shard, off):
                return _blockwise_inner(q_shard, k, v, scale, softcap,
                                        chunk, q_offset=off)
            out = jax.vmap(per_shard, in_axes=(1, 0), out_axes=1)(qs, offs)
            out = lax.with_sharding_constraint(out, shard_sharding)
            return out.reshape(B, S, K, G, hd)
    return _blockwise_inner(qg, k, v, scale, softcap, chunk)


def _blockwise_inner(qg, k, v, scale, softcap, chunk, q_offset=0):
    B, S, K, G, hd = qg.shape
    T = k.shape[1]
    nq, nk = S // chunk, T // chunk
    qc = qg.reshape(B, nq, chunk, K, G, hd).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, chunk, K, hd).transpose(1, 0, 2, 3, 4)
    rel = jnp.arange(chunk)

    def q_block(args):
        qi, q = args  # q: (B, chunk, K, G, hd)

        def kv_step(carry, inp):
            m, l, acc = carry
            kj, kb, vb = inp
            s = jnp.einsum("bskgd,btkd->bkgst", q, kb,
                           preferred_element_type=jnp.float32) * scale
            s = _softcap(s, softcap)
            qpos = q_offset + qi * chunk + rel[:, None]
            kpos = kj * chunk + rel[None, :]
            s = jnp.where(kpos <= qpos, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where((kpos <= qpos)[None, None, None], p, 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgst,btkd->bkgsd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, chunk), jnp.float32)
        a0 = jnp.zeros((B, K, G, chunk, hd), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0),
                                  (jnp.arange(nk), kc, vc))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qg.dtype)
        return out.transpose(0, 3, 1, 2, 4)          # (B, chunk, K, G, hd)

    out = lax.map(q_block, (jnp.arange(nq), qc))     # (nq, B, chunk, ...)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, K, G, hd)


def _decode_project(cfg: ModelConfig, params, x, pos, *, is_global: bool):
    """Shared q/k/v projection + RoPE for the single-token decode paths.

    x: (B, 1, d); pos: (B,) int32.  Returns (q (B,1,H,hd),
    knew (B,1,K,hd), vnew (B,1,K,hd)) — identical math for the dense and
    paged caches, so both decode variants stay bit-for-bit equal.
    """
    q = weight_einsum("bsd,dhq->bshq", x, params["wq"])
    if cfg.use_qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg.norm_eps)

    knew = weight_einsum("bsd,dkq->bskq", x, params["wk"])
    vnew = weight_einsum("bsd,dkq->bskq", x, params["wv"])
    if cfg.use_qk_norm:
        knew = rmsnorm(params["k_norm"], knew, cfg.norm_eps)

    if not cfg.use_abs_pos:
        theta = (cfg.rope_theta_global
                 if (is_global and cfg.rope_theta_global) else cfg.rope_theta)
        posb = pos[:, None]
        q = apply_rope(q, posb, theta)
        knew = apply_rope(knew, posb, theta)
    return q, knew, vnew


def attention_decode(cfg: ModelConfig, params, x, cache, pos, *,
                     is_global: bool, cross_kv=None):
    """Single-token decode. x: (B, 1, d); pos: (B,) int32 per-sequence
    write positions (scalars are broadcast) — continuous batching serves
    requests at different depths in one step.

    cache: dict(k=(B, T, K, hd), v=..., slots=(B, T) ring positions) —
    T == seq_len for global layers, T == window for local ring buffers.
    cross_kv: (k, v) for enc-dec cross attention (no cache update).
    Returns (out, new_cache).
    """
    B, S, d = x.shape
    assert S == 1
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5

    if cross_kv is not None:
        q = weight_einsum("bsd,dhq->bshq", x, params["wq"])
        if cfg.use_qk_norm:
            q = rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k, v = cross_kv
        qg = q.reshape(B, 1, K, G, hd)
        T = k.shape[1]
        mask = jnp.ones((1, 1, 1, 1, T), bool)
        out = attention_weights_and_out(qg, k, v, mask, scale=scale,
                                        softcap=cfg.attn_logit_softcap)
        o = weight_einsum("bshq,hqd->bsd", out.reshape(B, 1, H, hd),
                          params["wo"])
        return o, cache

    q, knew, vnew = _decode_project(cfg, params, x, pos, is_global=is_global)

    T = cache["k"].shape[1]
    slot = pos % T  # global caches have T == max seq, so slot == pos there
    barange = jnp.arange(B)
    kc = cache["k"].at[barange, slot].set(knew[:, 0].astype(cache["k"].dtype))
    vc = cache["v"].at[barange, slot].set(vnew[:, 0].astype(cache["v"].dtype))
    slots = cache["slots"].at[barange, slot].set(pos)

    window = 0 if is_global else cfg.local_window
    valid = (slots >= 0) & (slots <= pos[:, None])
    if window:
        valid &= slots > (pos[:, None] - window)
    mask = valid[:, None, None, None, :]  # (B,1,1,1,T)

    qg = q.reshape(B, 1, K, G, hd)
    out = attention_weights_and_out(qg, kc.astype(x.dtype), vc.astype(x.dtype),
                                    mask, scale=scale,
                                    softcap=cfg.attn_logit_softcap)
    o = weight_einsum("bshq,hqd->bsd", out.reshape(B, 1, H, hd),
                      params["wo"])
    return o, {"k": kc, "v": vc, "slots": slots}


def init_kv_cache(cfg: ModelConfig, batch: int, length: int, stack=(),
                  dtype=None):
    """Empty cache dict with stacking prefix (e.g. per layer)."""
    dtype = dtype or cfg.activation_dtype
    K, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": _zeros((batch, length, K, hd), stack, dtype),
        "v": _zeros((batch, length, K, hd), stack, dtype),
        "slots": jnp.full(tuple(stack) + (batch, length), -1, jnp.int32),
    }


def init_kv_pages(cfg: ModelConfig, num_blocks: int, block_size: int,
                  stack=(), dtype=None, quant: bool = False):
    """Paged KV pool for GLOBAL attention layers.

    Physical pages of ``block_size`` tokens shared by every slot; there
    is NO batch axis — ownership lives entirely in the engine's block
    tables (``serving.kv_pool``).  No ``slots`` array either: validity
    is derived from (block_table, pos) at decode time.

    ``quant=True`` stores K/V as int8 with one f32 scale per (page,
    token offset, kv head) head_dim vector riding in parallel
    ``k_scale``/``v_scale`` leaves of shape (nB, bs, K).  The scale
    leaves have the exact pool layout (page-leading, no batch axis), so
    the engine's generic pool-leaf machinery — CoW page copies, chain
    gathers, persistence scatters — applies to them unchanged.
    """
    dtype = dtype or cfg.activation_dtype
    K, hd = cfg.num_kv_heads, cfg.head_dim
    if quant:
        return {
            "k": _zeros((num_blocks, block_size, K, hd), stack, jnp.int8),
            "v": _zeros((num_blocks, block_size, K, hd), stack, jnp.int8),
            "k_scale": _zeros((num_blocks, block_size, K), stack,
                              jnp.float32),
            "v_scale": _zeros((num_blocks, block_size, K), stack,
                              jnp.float32),
        }
    return {
        "k": _zeros((num_blocks, block_size, K, hd), stack, dtype),
        "v": _zeros((num_blocks, block_size, K, hd), stack, dtype),
    }


def scatter_kv_pages(pages, k, v, write_tables):
    """Write a per-row K/V strip straight into the shared page pool.

    pages: dict(k=(nB, bs, K, hd), v=...); k, v: (B, T, K, hd);
    write_tables: (B, n_wblk) int32 physical page per covered logical
    block (-1 = unallocated -> write dropped).  T is right-padded up to
    ``n_wblk * bs`` — pad K/V lands beyond each row's true length and is
    positionally masked at read time, exactly like the dense path's
    ``slots=-1`` padding.
    """
    nB, bs = pages["k"].shape[0], pages["k"].shape[1]
    B, T = k.shape[0], k.shape[1]
    n_wblk = write_tables.shape[1]
    pad = n_wblk * bs - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, n_wblk, bs, *k.shape[2:])
    vb = v.reshape(B, n_wblk, bs, *v.shape[2:])
    tgt = jnp.where(write_tables >= 0, write_tables, nB)  # nB is OOB
    if kv_pages_quantized(pages):
        kq, ks = quantize_kv(kb)
        vq, vs = quantize_kv(vb)
        return {
            "k": pages["k"].at[tgt].set(kq, mode="drop"),
            "v": pages["v"].at[tgt].set(vq, mode="drop"),
            "k_scale": pages["k_scale"].at[tgt].set(ks, mode="drop"),
            "v_scale": pages["v_scale"].at[tgt].set(vs, mode="drop"),
        }
    return {
        "k": pages["k"].at[tgt].set(kb.astype(pages["k"].dtype),
                                    mode="drop"),
        "v": pages["v"].at[tgt].set(vb.astype(pages["v"].dtype),
                                    mode="drop"),
    }


def gather_kv_pages(pages, ctx_tables):
    """Materialise the logical K/V view of a shared-prefix chain.

    ctx_tables: (B, n_cblk) int32 physical pages (-1 pad rows gather
    garbage the caller masks via ``ctx_len``).  Returns (k, v) each
    (B, n_cblk * bs, K, hd).
    """
    nB, bs = pages["k"].shape[0], pages["k"].shape[1]
    B = ctx_tables.shape[0]
    bt = jnp.clip(ctx_tables, 0, nB - 1)
    kg = pages["k"][bt].reshape(B, -1, *pages["k"].shape[2:])
    vg = pages["v"][bt].reshape(B, -1, *pages["v"].shape[2:])
    if kv_pages_quantized(pages):
        ks = pages["k_scale"][bt].reshape(B, -1, *pages["k_scale"].shape[2:])
        vs = pages["v_scale"][bt].reshape(B, -1, *pages["v_scale"].shape[2:])
        return dequantize_kv(kg, ks), dequantize_kv(vg, vs)
    return kg, vg


def scatter_rows(full, rows, slots, axis: int):
    """Insert ``m`` single-request rows into a batched cache leaf in one
    shot: ``full`` has the slot/batch dimension at ``axis``; ``rows``
    carries the same leaf with ``m`` entries there; ``slots``: (m,)
    int32 slot indices (distinct)."""
    idx = (slice(None),) * axis + (slots,)
    return full.at[idx].set(rows.astype(full.dtype))


def attention_prefill_paged(cfg: ModelConfig, params, x, positions, pages,
                            write_tables, ctx_tables=None, ctx_len=None, *,
                            use_flash: bool = False):
    """Prefill attention for a GLOBAL layer that writes K/V straight
    into the paged pool — and, on a prefix-cache hit, attends the shared
    prefix's pages instead of recomputing them.

    x: (B, S, d) suffix activations; positions: (B, S) ABSOLUTE
    positions (``ctx_len + arange(S)``); pages: this layer's pool dict.

    Cold rows (``ctx_tables=None``): ``write_tables`` is (B, n_wblk) —
    physical pages covering the suffix span from logical block 0 — and
    the compute delegates to ``attention_fwd`` so the cold paged
    admission is the exact same math as the dense-strip path.

    Hit rows: the prefix match is TOKEN-granular, so the suffix write
    starts at ``ctx_len`` which may land mid-page (the engine has
    already CoW-forked that partial page private).  ``ctx_tables`` and
    ``write_tables`` are then BOTH the row's full block table (logical
    block ``i`` -> physical page): the context is the gathered view of
    that table masked to positions ``< ctx_len`` (per row), and the
    suffix K/V is scattered token-by-token at absolute positions
    ``ctx_len + i`` through the same table (``scatter_kv_tokens``) —
    overwriting, in order, exactly the stale tail the mask was hiding.
    Returns (out (B, S, d), new_pages).
    """
    B, S, d = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5

    if ctx_tables is None:
        o, k, v = attention_fwd(cfg, params, x, positions, is_global=True,
                                use_flash=use_flash)
        return o, scatter_kv_pages(pages, k, v, write_tables)

    q, k, v = _project_seq(cfg, params, x, positions, is_global=True)
    quant = kv_pages_quantized(pages)
    if quant:
        # the suffix attends its own int8 round-trip so the hit-path
        # logits match what later decode reads of these pages see
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k = dequantize_kv(kq, ks, k.dtype)
        v = dequantize_kv(vq, vs, v.dtype)
    ck, cv = gather_kv_pages(pages, ctx_tables)
    Tc = ck.shape[1]
    # context part: logical positions [0, Tc) valid where < ctx_len
    # (token-granular — a partial final page contributes exactly its
    # matched tokens; pad rows of a mixed-depth admission group mask
    # out here); suffix part: plain causal within the suffix
    ctx_ok = jnp.arange(Tc, dtype=jnp.int32)[None, :] < ctx_len[:, None]
    mask = jnp.concatenate(
        [jnp.broadcast_to(ctx_ok[:, None, :], (B, S, Tc)),
         jnp.broadcast_to(causal_mask(S, S), (B, S, S))], axis=-1)
    k_all = jnp.concatenate([ck.astype(x.dtype), k], axis=1)
    v_all = jnp.concatenate([cv.astype(x.dtype), v], axis=1)
    qg = q.reshape(B, S, K, G, hd)
    out = attention_weights_and_out(qg, k_all, v_all,
                                    mask[:, None, None], scale=scale,
                                    softcap=cfg.attn_logit_softcap)
    o = weight_einsum("bshq,hqd->bsd", out.reshape(B, S, H, hd),
                      params["wo"])
    if quant:
        return o, _scatter_tokens_quant(pages, kq, ks, vq, vs,
                                        write_tables,
                                        jnp.asarray(ctx_len, jnp.int32))
    return o, scatter_kv_tokens(pages, k, v, write_tables,
                                jnp.asarray(ctx_len, jnp.int32))


def _decode_read(cfg: ModelConfig, q, pages, block_tables, pos, dtype,
                 use_pallas: bool):
    """One query per row against the pool AS WRITTEN: the positions
    ``<= pos`` of the row's table.  q: (B, 1, H, hd); returns
    (B, 1, H, hd) in ``dtype``.  ``use_pallas`` reads through the
    Pallas ``paged_attention`` kernel, otherwise through the gathered
    logical view (the same values and mask)."""
    B = q.shape[0]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5
    if use_pallas:
        from repro.kernels import ops as kernel_ops
        out = kernel_ops.paged_attention(
            q[:, 0], pages["k"], pages["v"], block_tables, pos + 1,
            scale=scale, softcap=cfg.attn_logit_softcap,
            k_scale=pages.get("k_scale"), v_scale=pages.get("v_scale"))
        return out[:, None].astype(dtype)
    kg, vg = gather_kv_pages(pages, block_tables)
    bs = pages["k"].shape[1]
    t = jnp.arange(block_tables.shape[1] * bs, dtype=jnp.int32)
    allocated = jnp.repeat(block_tables >= 0, bs, axis=1)
    valid = allocated & (t[None, :] <= pos[:, None])
    out = attention_weights_and_out(
        q.reshape(B, 1, K, H // K, hd), kg.astype(dtype), vg.astype(dtype),
        valid[:, None, None, None, :], scale=scale,
        softcap=cfg.attn_logit_softcap)
    return out.reshape(B, 1, H, hd)


def _extend_read(cfg: ModelConfig, q, k, v, pages, block_tables, pos, dtype,
                 use_pallas: bool):
    """``S`` queries per row against the PRE-WRITE pool — the positions
    ``< pos`` of the row's table — and, causally, against the row's own
    ``S`` new tokens (k, v: (B, S, K, hd)).  q: (B, S, H, hd); returns
    (B, S, H, hd) in ``dtype``.  On an int8 pool ``use_pallas`` reads
    through the fused dequant ``paged_extend_attention`` kernel; on any
    other pool the gathered view is read (bit-exact)."""
    B, S = q.shape[:2]
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5
    if kv_pages_quantized(pages) and use_pallas:
        from repro.kernels import ops as kernel_ops
        out = kernel_ops.paged_extend_attention(
            q, pages["k"], pages["v"], k, v, block_tables, pos,
            scale=scale, softcap=cfg.attn_logit_softcap,
            k_scale=pages["k_scale"], v_scale=pages["v_scale"])
        return out.astype(dtype)
    ck, cv = gather_kv_pages(pages, block_tables)
    bs = pages["k"].shape[1]
    L = block_tables.shape[1] * bs
    t = jnp.arange(L, dtype=jnp.int32)
    allocated = jnp.repeat(block_tables >= 0, bs, axis=1)
    ctx_ok = allocated & (t[None, :] < pos[:, None])               # (B, L)
    mask = jnp.concatenate(
        [jnp.broadcast_to(ctx_ok[:, None, :], (B, S, L)),
         jnp.broadcast_to(causal_mask(S, S), (B, S, S))], axis=-1)
    k_all = jnp.concatenate([ck.astype(dtype), k], axis=1)
    v_all = jnp.concatenate([cv.astype(dtype), v], axis=1)
    out = attention_weights_and_out(
        q.reshape(B, S, K, H // K, hd), k_all, v_all, mask[:, None, None],
        scale=scale, softcap=cfg.attn_logit_softcap)
    return out.reshape(B, S, H, hd)


def attention_decode_paged(cfg: ModelConfig, params, x, cache, pos,
                           block_tables, *, use_pallas: bool = False):
    """Single-token decode against a paged KV pool (GLOBAL layers only —
    local ring-window layers stay dense at W, SSM state is O(1)).

    ``use_pallas=True`` swaps the jnp gather read for the Pallas
    ``kernels.flash_attention.paged_attention`` kernel (scalar-prefetched
    block tables stream pages into VMEM — the logical K/V view is never
    materialised in HBM); the write path and masking semantics are
    identical, so the two reads agree to kernel accumulation tolerance.

    x: (B, 1, d); pos: (B,) int32 write positions.
    cache: dict(k=(num_blocks, bs, K, hd), v=...) — the shared page pool
    (per-layer once the surrounding scan strips the stack axis).
    block_tables: (B, n_blk) int32 physical page ids per logical block,
    -1 = unallocated.  Logical capacity n_blk * bs equals the engine's
    ``max_len``, so the gathered K/V tensor has the same shape, values
    and mask as the dense path — decode is bit-for-bit identical; only
    HBM residency shrinks from ``max_slots x max_len`` strips to pages
    actually in flight.

    The new token's K/V is scattered into page ``block_tables[b,
    pos//bs]`` at offset ``pos % bs``; rows whose table entry is -1
    (inactive or stalled slots) drop the write so a freed-and-reused
    page can never be corrupted by a stale slot.
    """
    B, S, d = x.shape
    assert S == 1
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))

    q, knew, vnew = _decode_project(cfg, params, x, pos, is_global=True)

    nB, bs = cache["k"].shape[0], cache["k"].shape[1]
    blk, off = pos // bs, pos % bs
    phys = block_tables[jnp.arange(B), blk]
    wphys = jnp.where(phys >= 0, phys, nB)       # nB is OOB => dropped
    if kv_pages_quantized(cache):
        kq1, ks1 = quantize_kv(knew[:, 0])
        vq1, vs1 = quantize_kv(vnew[:, 0])
        new_cache = _scatter_at(cache, wphys, off, kq1, vq1, ks1, vs1)
    else:
        new_cache = _scatter_at(cache, wphys, off, knew[:, 0], vnew[:, 0])
    out = _decode_read(cfg, q, new_cache, block_tables, pos, x.dtype,
                       use_pallas)
    o = weight_einsum("bshq,hqd->bsd", out, params["wo"])
    return o, new_cache


def _scatter_at(pages, tgt, off, k, v, k_scale=None, v_scale=None):
    """Write token K/V at (page ``tgt``, offset ``off``) pairs of any
    matching index shape (``tgt`` = the pool's page count drops the
    write).  With ``k_scale``/``v_scale`` the K/V are already int8 and
    their scales land in the pool's scale leaves."""
    new = {
        "k": pages["k"].at[tgt, off].set(k.astype(pages["k"].dtype),
                                         mode="drop"),
        "v": pages["v"].at[tgt, off].set(v.astype(pages["v"].dtype),
                                         mode="drop"),
    }
    if k_scale is not None:
        new["k_scale"] = pages["k_scale"].at[tgt, off].set(k_scale,
                                                           mode="drop")
        new["v_scale"] = pages["v_scale"].at[tgt, off].set(v_scale,
                                                           mode="drop")
    return new


def scatter_kv_tokens(pages, k, v, block_tables, pos, valid_len=None):
    """Write ``S`` consecutive tokens' K/V into the page pool at absolute
    positions ``pos + i`` through each row's block table (the multi-token
    twin of the single-write in ``attention_decode_paged``).

    pages: dict(k=(nB, bs, K, hd), v=...); k, v: (B, S, K, hd);
    block_tables: (B, n_blk) int32 (-1 = unallocated -> write dropped);
    pos: (B,) int32 first write position; valid_len: optional (B,) int32
    — rows ``i >= valid_len`` are host-side padding whose writes are
    dropped (a padded token must never touch a page: on families with
    additional dense ring state the same drop keeps rings clean, and in
    pages it keeps rollback reasoning local to REAL protocol writes).
    Writes past the table's logical span (``n_blk * bs``) are dropped.
    """
    if kv_pages_quantized(pages):
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return _scatter_tokens_quant(pages, kq, ks, vq, vs, block_tables,
                                     pos, valid_len)
    tgt, off = _token_write_targets(pages, k.shape[0], k.shape[1],
                                    block_tables, pos, valid_len)
    return _scatter_at(pages, tgt, off, k, v)


def _token_write_targets(pages, B, S, block_tables, pos, valid_len):
    """(tgt, off) page/offset pairs for an S-token scatter; dropped
    writes (unallocated / out-of-span / pad rows) map tgt to the OOB
    page index ``nB``."""
    nB, bs = pages["k"].shape[0], pages["k"].shape[1]
    n_blk = block_tables.shape[1]
    p = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]     # (B, S)
    blk = jnp.clip(p // bs, 0, n_blk - 1)
    off = p % bs
    phys = jnp.take_along_axis(block_tables, blk, axis=1)          # (B, S)
    ok = (phys >= 0) & (p < n_blk * bs)
    if valid_len is not None:
        ok &= jnp.arange(S, dtype=jnp.int32)[None, :] < valid_len[:, None]
    return jnp.where(ok, phys, nB), off            # nB is OOB => dropped


def _scatter_tokens_quant(pages, kq, ks, vq, vs, block_tables, pos,
                          valid_len=None):
    """Token scatter of PRE-quantized K/V (+ scales).  Callers that
    already round-tripped the suffix for attention pass the same ints
    here — re-quantizing the dequantized values would drift (the eps in
    the scale would be applied twice)."""
    tgt, off = _token_write_targets(pages, kq.shape[0], kq.shape[1],
                                    block_tables, pos, valid_len)
    return _scatter_at(pages, tgt, off, kq, vq, ks, vs)


class NarrowRows(NamedTuple):
    """The decode-shaped row group of a packed paged extend wave: one
    token per slot at the slot's write position, read through the
    slot's block table.  ``valid`` is 1 where the slot feeds this token
    and 0 where it feeds none here (an idle slot, or one whose span
    rides a wide row): a 0 row drops its K/V write and the caller
    discards its outputs."""
    tokens: jax.Array            # (n, 1) int32
    pos: jax.Array               # (n,) int32
    block_tables: jax.Array      # (n, n_blk) int32
    valid: jax.Array             # (n,) int32, 0 or 1


def wave_tokens(tokens, narrow: Optional[NarrowRows] = None):
    """The token ids an extend wave embeds: ``tokens`` (R, S) as given,
    or, with a narrow group, both groups flattened narrow-first into one
    (1, n + R*S) row — the layout every token-wise layer then runs on."""
    if narrow is None:
        return tokens
    return jnp.concatenate([narrow.tokens.reshape(-1),
                            tokens.reshape(-1)])[None]


def split_wave(t, narrow: NarrowRows, rows: int):
    """Inverse of ``wave_tokens`` for a per-token array (1, n + R*S, ...):
    (narrow part (n, 1, ...), wide part (R, S, ...))."""
    n = narrow.pos.shape[0]
    t = t[0]
    return t[:n, None], t[n:].reshape(rows, -1, *t.shape[1:])


def attention_extend_paged(cfg: ModelConfig, params, x, pos, pages,
                           block_tables, valid_len=None, *,
                           use_pallas: bool = False,
                           narrow: Optional[NarrowRows] = None):
    """Multi-token decode against the paged pool: score ``S`` proposed /
    teacher-forced tokens per row in ONE call (speculative verify,
    chunked catch-up prefill) — the causal-suffix machinery of
    ``attention_prefill_paged`` applied at an arbitrary mid-block
    position.

    x: (R, S, d) token activations at absolute positions ``pos + i``;
    pages: this layer's pool dict; block_tables: (R, n_blk) the row's
    FULL table (context and write span in one view).  The context is the
    PRE-WRITE gathered view masked strictly below ``pos`` — stale
    entries from a previously rejected speculation (positions >= pos)
    are invisible, which is exactly what makes KV rollback a no-op on
    pages — and the S new tokens attend each other causally as a
    suffix.  K/V for rows ``i < valid_len`` is then scattered into the
    pages at ``pos + i`` (see ``scatter_kv_tokens``; rejected proposals
    stay written but stay masked until overwritten in sequence order).
    Returns (out (R, S, d), new_pages).

    With ``narrow`` the wave is PACKED: x is (1, n + R*S, d), the n
    width-1 rows of ``narrow`` first (``wave_tokens``), then the R wide
    rows above.  The projections run once over every token; the wide
    rows read the pre-write pool as above, both groups' K/V land in one
    scatter, and the narrow rows then read the written pool exactly as
    ``attention_decode_paged`` does (the Pallas ``paged_attention``
    kernel under ``use_pallas``).  Returns (out (1, n + R*S, d),
    new_pages).

    On a quantized pool the suffix attends the int8 ROUND-TRIP of its
    own K/V — the same values every later read of those pages sees —
    and ``use_pallas=True`` swaps the wide rows' gather read for the
    fused dequant ``kernels.flash_attention.paged_extend_attention``
    kernel (pages never materialise in f32; the kernel receives the
    already round-tripped suffix so both reads agree to accumulation
    tolerance).  On an f32 pool the wide rows ignore ``use_pallas`` and
    stay bit-exact.
    """
    H, hd = cfg.num_heads, cfg.head_dim
    n = 0 if narrow is None else narrow.pos.shape[0]
    R = block_tables.shape[0]
    S = (x.shape[1] - n) // R if narrow is not None else x.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (R,))
    positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    if narrow is not None:
        positions = jnp.concatenate([narrow.pos,
                                     positions.reshape(-1)])[None]

    q, k, v = _project_seq(cfg, params, x, positions, is_global=True)
    scales = ()
    if kv_pages_quantized(pages):
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k = dequantize_kv(kq, ks, k.dtype)
        v = dequantize_kv(vq, vs, v.dtype)
        written, scales = (kq, vq), (ks, vs)
    else:
        written = (k, v)

    def wide(t):
        return t if narrow is None else split_wave(t, narrow, R)[1]

    # the wide rows read the pool before this wave's writes land
    out = _extend_read(cfg, wide(q), wide(k), wide(v), pages, block_tables,
                       pos, x.dtype, use_pallas)
    tgt, off = _token_write_targets(pages, R, S, block_tables, pos,
                                    valid_len)
    if narrow is None:
        new_pages = _scatter_at(pages, tgt, off, *written, *scales)
    else:
        ntgt, noff = _token_write_targets(pages, n, 1, narrow.block_tables,
                                          narrow.pos, narrow.valid)
        flat = [t[0] for t in written + scales]
        new_pages = _scatter_at(
            pages, jnp.concatenate([ntgt.reshape(-1), tgt.reshape(-1)]),
            jnp.concatenate([noff.reshape(-1), off.reshape(-1)]), *flat)
        # the narrow rows read the pool after it, as a decode wave does
        out_n = _decode_read(cfg, split_wave(q, narrow, R)[0], new_pages,
                             narrow.block_tables, narrow.pos, x.dtype,
                             use_pallas)
        out = jnp.concatenate([out_n.reshape(n, H, hd),
                               out.reshape(R * S, H, hd)])[None]
    o = weight_einsum("bshq,hqd->bsd", out, params["wo"])
    return o, new_pages


def attention_extend(cfg: ModelConfig, params, x, cache, pos, *,
                     is_global: bool, valid_len=None):
    """Multi-token decode against a DENSE cache (global strip or local
    ring) — the non-paged leg of ``extend_paged`` for families whose
    trunk mixes paged global layers with dense ring layers.

    The old entries are read PRE-write and masked strictly below
    ``pos``: sequential decode would evict ring entry ``(pos+j) % W``
    only at step ``j``, after steps ``i < j`` attended it, so a
    write-then-read over the whole chunk would lose context — reading
    the pre-write ring plus the new tokens as a causal suffix preserves
    exactly the sequential semantics (requires S <= window).  Rows
    ``i >= valid_len`` (host padding) drop their writes so pad tokens
    can never evict live ring context.
    """
    B, S, d = x.shape
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    scale = cfg.attn_scale if cfg.attn_scale is not None else hd ** -0.5
    positions = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]

    q, knew, vnew = _project_seq(cfg, params, x, positions,
                                 is_global=is_global)

    T = cache["k"].shape[1]
    window = 0 if is_global else cfg.local_window
    slots = cache["slots"]                                         # (B, T)
    old_ok = (slots[:, None, :] >= 0) & \
        (slots[:, None, :] < pos[:, None, None])
    rel = jnp.arange(S, dtype=jnp.int32)
    new_ok = rel[None, :] <= rel[:, None]                          # (S, S)
    if window:
        old_ok &= slots[:, None, :] > (positions[:, :, None] - window)
        new_ok &= (rel[:, None] - rel[None, :]) < window
    mask = jnp.concatenate(
        [jnp.broadcast_to(old_ok, (B, S, T)),
         jnp.broadcast_to(new_ok, (B, S, S))], axis=-1)
    k_all = jnp.concatenate([cache["k"].astype(x.dtype), knew], axis=1)
    v_all = jnp.concatenate([cache["v"].astype(x.dtype), vnew], axis=1)
    qg = q.reshape(B, S, K, G, hd)
    out = attention_weights_and_out(qg, k_all, v_all, mask[:, None, None],
                                    scale=scale,
                                    softcap=cfg.attn_logit_softcap)
    o = weight_einsum("bshq,hqd->bsd", out.reshape(B, S, H, hd),
                      params["wo"])

    ring = positions % T
    ok = (jnp.arange(S, dtype=jnp.int32)[None, :] < valid_len[:, None]
          if valid_len is not None else jnp.ones((B, S), bool))
    ring_w = jnp.where(ok, ring, T)                # T is OOB => dropped
    barange = jnp.arange(B)[:, None]
    kc = cache["k"].at[barange, ring_w].set(
        knew.astype(cache["k"].dtype), mode="drop")
    vc = cache["v"].at[barange, ring_w].set(
        vnew.astype(cache["v"].dtype), mode="drop")
    new_slots = cache["slots"].at[barange, ring_w].set(positions,
                                                      mode="drop")
    return o, {"k": kc, "v": vc, "slots": new_slots}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, d_ff=None, stack=()):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(k1, (d, f), stack),
        "w_up": _dense_init(k2, (d, f), stack),
        "w_down": _dense_init(k3, (f, d), stack, in_axis_size=f),
    }


def mlp(params, x, activation="silu"):
    act = jax.nn.gelu if activation == "gelu" else jax.nn.silu
    h = act(weight_einsum("bsd,df->bsf", x, params["w_gate"])) \
        * weight_einsum("bsd,df->bsf", x, params["w_up"])
    return weight_einsum("bsf,fd->bsd", h, params["w_down"])


def init_gelu_mlp(cfg: ModelConfig, key, stack=()):
    """Whisper-style 2-matrix GELU MLP."""
    d, f = cfg.d_model, cfg.d_ff
    k1, k2 = jax.random.split(key)
    return {
        "w_in": _dense_init(k1, (d, f), stack),
        "b_in": _zeros((f,), stack),
        "w_out": _dense_init(k2, (f, d), stack, in_axis_size=f),
        "b_out": _zeros((d,), stack),
    }


def gelu_mlp(params, x):
    h = jax.nn.gelu(weight_einsum("bsd,df->bsf", x, params["w_in"])
                    + params["b_in"].astype(x.dtype))
    return weight_einsum("bsf,fd->bsd", h, params["w_out"]) \
        + params["b_out"].astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def init_embedding(cfg: ModelConfig, key):
    std = cfg.d_model ** -0.5  # keeps tied-unembed logits O(1)
    p = {"table": std * jax.random.normal(key, (cfg.vocab_size, cfg.d_model))}
    return p


def embed(cfg: ModelConfig, params, tokens):
    x = params["table"].astype(cfg.activation_dtype)[tokens]
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(cfg: ModelConfig, emb_params, head_params, x):
    if cfg.tie_embeddings:
        w = emb_params["table"].astype(x.dtype)
        logits = jnp.einsum("bsd,vd->bsv", x, w)
    else:
        w = head_params["w"].astype(x.dtype)
        logits = jnp.einsum("bsd,dv->bsv", x, w)
    logits = _softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    return logits


def init_unembed(cfg: ModelConfig, key):
    if cfg.tie_embeddings:
        return {}
    return {"w": _dense_init(key, (cfg.d_model, cfg.vocab_size))}
