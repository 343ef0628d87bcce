"""Plain reference of the served models, in float32 ``jax.numpy``.

It follows the published description of each architecture and imports
nothing of the program.  Weights come from ``bench.weights.canonical``.
One layer runs at a time, with attention in blocks of query rows, so a
whole prompt of the longest context fits beside nothing else on the
chip.  ``mode="fp8"`` is the control: every matrix product takes its
operands rounded to float8 (e4m3, one scale per tensor), the precision
step below the bfloat16 the configurations compute in.

Each family's feed-forward block is in ``bench/reference/<family>.py``.
"""
from __future__ import annotations

import importlib
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
Q_BLOCK = 512           # query rows per attention block
LEN_BUCKET = 512        # sequences are padded to a multiple of this
ROW_BUCKET = 128        # and the rows read back to a multiple of this


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(eq: str, a, b, mode: str):
    """Matrix product at full float32 precision; in the control both
    operands are first rounded to float8."""
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


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
    T = h.shape[0]
    H, K = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, G = spec.head_dim(conf), H // K
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


def _layer(conf, ffn, mode, x, w):
    r = conf.get("residual_multiplier", 1.0)
    eps = conf["rms_norm_eps"]
    x = x + r * attention(conf, w, rmsnorm(x, w["ln1"], eps), mode)
    return x + r * ffn(conf, w, rmsnorm(x, w["ln2"], eps), mode)


def _head(conf, mode, x, gain, table):
    x = rmsnorm(x, gain, conf["rms_norm_eps"])
    eq = "td,vd->tv" if conf["tie_word_embeddings"] else "td,dv->tv"
    return mm(eq, x, table, mode) / conf.get("logits_scaling", 1.0)


class Model:
    """The reference of one configuration at one precision: the layer
    and head programs, jitted once and used for every sequence."""

    def __init__(self, conf: dict, mode: str = "f32"):
        fam = importlib.import_module(
            f"bench.reference.{'moe' if spec.is_moe(conf) else 'dense'}")
        self.conf = conf
        self.layer = jax.jit(partial(_layer, conf, fam.ffn, mode))
        self.head = jax.jit(partial(_head, conf, mode))

    def logits(self, w: dict, seq: np.ndarray, rows: np.ndarray):
        """Next-token logits of ``seq`` (1-D token ids) at positions
        ``rows``, as a (len(rows), vocab) float32 array."""
        conf = self.conf
        T = len(seq)
        Tp = LEN_BUCKET * math.ceil(T / LEN_BUCKET)  # causal: pads at end
        toks = np.zeros(Tp, np.int32)
        toks[:T] = seq
        x = w["embed"][jnp.asarray(toks)] * conf.get("embedding_multiplier",
                                                     1.0)
        for l in range(conf["num_hidden_layers"]):
            x = self.layer(x, {k: w[k][l] for k in PER_LAYER if k in w})
        table = w["embed"] if conf["tie_word_embeddings"] else w["lm_head"]
        n = len(rows)
        padded = np.zeros(ROW_BUCKET * math.ceil(n / ROW_BUCKET), np.int32)
        padded[:n] = rows
        return self.head(x[jnp.asarray(padded)], w["final_norm"], table)[:n]


PER_LAYER = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
             "w_down", "router")


def logits(conf: dict, w: dict, seq: np.ndarray, rows: np.ndarray,
           mode: str = "f32"):
    """``Model(conf, mode).logits(w, seq, rows)`` for a single call."""
    return Model(conf, mode).logits(w, seq, rows)
