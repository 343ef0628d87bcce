"""Decoder layers of grouped-query attention with rotary embeddings and a
SwiGLU feed-forward block, dense (Phi-3, Llama style) or a sparse
mixture of experts (Granite-MoE, Mixtral style), every layer alike.

The published layout stacks each per-layer weight over the layers
(``canonical_shapes``), and the reference slices that one tree, drawn
once per seed.  The feed-forward block is the mixture of experts where
the configuration has ``num_local_experts``: a softmax router, the
``num_experts_per_tok`` best experts per token with their probabilities
renormalised to sum to one, and a SwiGLU expert each.  No token is ever
dropped: the reference computes every expert for every token and the
unchosen ones weigh zero, which is plain rather than fast.
"""
from __future__ import annotations

import copy
import json
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.reference import mm

Q_BLOCK = 512           # query rows per attention block
KV_BYTES = ACT_BYTES = 2  # the paged pool and the activations are bf16
NORMS = ("ln1", "ln2", "final_norm")
# published keys -> ``ModelConfig`` fields
PROGRAM_KEYS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "num_local_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "attention_multiplier": "attn_scale",
}
# CPU test size: every width shrunk, the same code paths
SMALL = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
             vocab_size=512, num_hidden_layers=2, intermediate_size=64)

__all__ = ["program_fields", "canonical", "to_program", "weights",
           "layer_weights", "embed", "layer", "head", "token_flops",
           "decode_attention_call", "smoke"]


def is_moe(conf: dict) -> bool:
    return bool(conf.get("num_local_experts"))


def head_dim(conf: dict) -> int:
    return conf.get("head_dim") or (conf["hidden_size"]
                                    // conf["num_attention_heads"])


def program_fields(conf: dict) -> dict:
    kw = {field: conf[key] for key, field in PROGRAM_KEYS.items()
          if key in conf}
    kw["head_dim"] = head_dim(conf)
    kw["moe_d_ff" if is_moe(conf) else "d_ff"] = conf["intermediate_size"]
    return kw


def smoke(conf: dict) -> dict:
    c = copy.deepcopy(conf)
    c.update(SMALL)
    if is_moe(c):
        c.update(num_local_experts=4, num_experts_per_tok=2)
        c["program"] = dict(c.get("program", {}), capacity_factor=2.0)
    return c


# ------------------------------------------------------------------ layout
def global_shapes(conf: dict) -> dict:
    d, V = conf["hidden_size"], conf["vocab_size"]
    shapes = {"embed": (V, d), "final_norm": (d,)}
    if not conf["tie_word_embeddings"]:
        shapes["lm_head"] = (d, V)
    return shapes


def layer_shapes(conf: dict, l: int) -> dict:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    K, hd, f = conf["num_key_value_heads"], head_dim(conf), \
        conf["intermediate_size"]
    shapes = {"ln1": (d,), "ln2": (d,), "wq": (d, H, hd), "wk": (d, K, hd),
              "wv": (d, K, hd), "wo": (H, hd, d)}
    if is_moe(conf):
        E = conf["num_local_experts"]
        shapes.update(router=(d, E), w_gate=(E, d, f), w_up=(E, d, f),
                      w_down=(E, f, d))
    else:
        shapes.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
    return shapes


def canonical_shapes(conf: dict) -> dict:
    """Every weight, each per-layer one stacked over the layers."""
    L = conf["num_hidden_layers"]
    shapes = {k: (L,) + s for k, s in layer_shapes(conf, 0).items()}
    return {**global_shapes(conf), **shapes}


def fan_in(name: str, shape: tuple):
    if name in NORMS:
        return None
    if name == "embed":
        return shape[1]
    if name == "wo":
        return shape[-3] * shape[-2]
    return shape[-2] if name in ("w_gate", "w_up", "w_down", "router",
                                 "lm_head") else shape[-3]


def canonical(conf: dict, key) -> dict:
    return W.draw(key, canonical_shapes(conf), fan_in)


def to_program(conf: dict, w: dict) -> dict:
    norm = lambda g: {"scale": g - 1.0}        # program scales by 1 + s
    layers = {
        "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
        "ln1": norm(w["ln1"]), "ln2": norm(w["ln2"]),
    }
    ffn = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    tree = {
        "embed": {"table": w["embed"]},
        "unembed": ({} if conf["tie_word_embeddings"]
                    else {"w": w["lm_head"]}),
        "final_norm": norm(w["final_norm"]),
    }
    if is_moe(conf):
        layers["moe"] = dict(ffn, router=w["router"])
        tree["moe_layers"] = layers
    else:
        layers["mlp"] = ffn
        tree["trunk"] = {"layers": layers}
    return tree


# ------------------------------------------------------------------ reference
def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """Rotary embedding, rotate-half layout: x (T, n, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(conf, w, h, mode):
    """Causal grouped-query attention over the whole sequence, in blocks
    of ``Q_BLOCK`` query rows."""
    T = h.shape[0]
    H, K = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, G = head_dim(conf), H // K
    scale = conf.get("attention_multiplier", hd ** -0.5)
    pos = jnp.arange(T)
    theta = conf["rope_theta"]
    q = rope(mm("td,dhk->thk", h, w["wq"], mode), pos, theta)
    k = rope(mm("td,dhk->thk", h, w["wk"], mode), pos, theta)
    v = mm("td,dhk->thk", h, w["wv"], mode)
    q = q.reshape(T, K, G, hd)
    outs = []
    for q0 in range(0, T, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        s = mm("qkgd,tkd->kgqt", qb, k, mode) * scale
        causal = (jnp.arange(T)[None, :]
                  <= (q0 + jnp.arange(qb.shape[0]))[:, None])
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        outs.append(mm("kgqt,tkd->qkgd", p, v, mode))
    o = jnp.concatenate(outs, 0).reshape(T, H, hd)
    return mm("thk,hkd->td", o, w["wo"], mode)


def dense_ffn(conf, w, h, mode):
    """SwiGLU: ``down(silu(gate(h)) * up(h))``."""
    a = jax.nn.silu(mm("td,df->tf", h, w["w_gate"], mode))
    return mm("tf,fd->td", a * mm("td,df->tf", h, w["w_up"], mode),
              w["w_down"], mode)


def moe_ffn(conf, w, h, mode):
    """Softmax router, top-k experts renormalised, a SwiGLU expert each."""
    k = conf["num_experts_per_tok"]
    probs = jax.nn.softmax(mm("td,de->te", h, w["router"], mode), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top / top.sum(-1,
                                                                keepdims=True))
    a = jax.nn.silu(mm("td,edf->tef", h, w["w_gate"], mode))
    y = mm("tef,efd->ted", a * mm("td,edf->tef", h, w["w_up"], mode),
           w["w_down"], mode)
    return jnp.einsum("te,ted->td", gate, y, precision=jax.lax.Precision.HIGHEST)


def _layer(conf, mode, x, w):
    ffn = moe_ffn if is_moe(conf) else dense_ffn
    r = conf.get("residual_multiplier", 1.0)
    eps = conf["rms_norm_eps"]
    x = x + r * attention(conf, w, rmsnorm(x, w["ln1"], eps), mode)
    return x + r * ffn(conf, w, rmsnorm(x, w["ln2"], eps), mode)


def _head(conf, mode, x, gain, table):
    x = rmsnorm(x, gain, conf["rms_norm_eps"])
    eq = "td,vd->tv" if conf["tie_word_embeddings"] else "td,dv->tv"
    return mm(eq, x, table, mode) / conf.get("logits_scaling", 1.0)


@lru_cache(maxsize=None)
def _jitted(fn, conf_json: str, mode: str):
    return jax.jit(partial(fn, json.loads(conf_json), mode))


def weights(conf: dict, seed: int) -> dict:
    """The whole stacked tree, drawn once; layers are slices of it."""
    return W.canonical(conf, seed)


def layer_weights(conf: dict, w: dict, l: int) -> dict:
    return {k: w[k][l] for k in layer_shapes(conf, l)}


def embed(conf: dict, w: dict, tokens):
    return w["embed"][jnp.asarray(tokens)] * conf.get("embedding_multiplier",
                                                      1.0)


def layer(conf: dict, mode: str, l: int):
    return _jitted(_layer, W.conf_key(conf), mode)


def head(conf: dict, mode: str, w: dict, x):
    table = w["embed"] if conf["tie_word_embeddings"] else w["lm_head"]
    return _jitted(_head, W.conf_key(conf), mode)(x, w["final_norm"], table)


# ------------------------------------------------------------------ counts
def layer_matmul_flops(conf: dict) -> int:
    """Projections and feed-forward of one layer, for one token."""
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    K, hd, f = conf["num_key_value_heads"], head_dim(conf), \
        conf["intermediate_size"]
    proj = 2 * (d * H * hd + 2 * d * K * hd + H * hd * d)
    if is_moe(conf):
        E, k = conf["num_local_experts"], conf["num_experts_per_tok"]
        return proj + 2 * d * E + k * 2 * 3 * d * f
    return proj + 2 * 3 * d * f


def attention_flops(conf: dict, ctx: int) -> int:
    """Scores and weighted values of one layer for one query token that
    attends ``ctx`` positions (itself included)."""
    return 2 * 2 * ctx * conf["num_attention_heads"] * head_dim(conf)


def token_flops(conf: dict, p0: int, n: int, emits: int) -> int:
    L = conf["num_hidden_layers"]
    per_pos = attention_flops(conf, 1)
    return (L * n * layer_matmul_flops(conf)
            + L * per_pos * (n * p0 + n * (n + 1) // 2)
            + 2 * conf["hidden_size"] * conf["vocab_size"] * emits)


def decode_attention_call(conf: dict, ctxs) -> tuple[int, int]:
    """Every row reads its own keys and values once and its query, and
    writes its output."""
    H, K, hd = conf["num_attention_heads"], conf["num_key_value_heads"], \
        head_dim(conf)
    flops = sum(attention_flops(conf, c) for c in ctxs)
    moved = sum(2 * c * K * hd * KV_BYTES + 2 * H * hd * ACT_BYTES
                for c in ctxs)
    return flops, moved
