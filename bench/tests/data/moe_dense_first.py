"""Test-only architecture (``model_type`` moe_dense_first): a dense first
layer, then layers of a sparse mixture of experts with a shared expert
beside the routed ones, the layout of DeepSeek-V3 and Kimi-K2, here
with grouped-query attention and a softmax router as the program's
``moe`` family runs it (``first_dense_layers``, ``num_shared_experts``).

It stands for the next architecture added to the benchmark: it lives
with its configuration (``moe-dense-first.json``) under
``bench/tests/data``, and a test makes it importable as
``bench.arch.moe_dense_first``.  Its layers differ by kind, so its
weights are drawn a layer at a time: layer ``l`` from ``fold_in(key, l +
1)``, the embedding, final norm and head from ``fold_in(key, 0)``, and
the reference holds one layer's weights at a time.
"""
from __future__ import annotations

import copy
import json
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.arch import _gqa


def dense(conf: dict, l: int) -> bool:
    return l < conf["first_k_dense_replace"]


def program_fields(conf: dict) -> dict:
    return {
        "d_model": conf["hidden_size"], "num_layers": conf["num_hidden_layers"],
        "num_heads": conf["num_attention_heads"],
        "num_kv_heads": conf["num_key_value_heads"],
        "head_dim": conf["head_dim"], "vocab_size": conf["vocab_size"],
        "rope_theta": conf["rope_theta"], "norm_eps": conf["rms_norm_eps"],
        "tie_embeddings": conf["tie_word_embeddings"],
        "d_ff": conf["intermediate_size"],
        "moe_d_ff": conf["moe_intermediate_size"],
        "num_experts": conf["n_routed_experts"],
        "num_experts_per_tok": conf["num_experts_per_tok"],
        "num_shared_experts": conf["n_shared_experts"],
        "first_dense_layers": conf["first_k_dense_replace"],
    }


def smoke(conf: dict) -> dict:
    return copy.deepcopy(conf)          # already at a CPU size


# ------------------------------------------------------------------ layout
_global_shapes = _gqa.global_shapes


def _layer_shapes(conf: dict, l: int) -> dict:
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    K, hd = conf["num_key_value_heads"], conf["head_dim"]
    shapes = {"ln1": (d,), "ln2": (d,), "wq": (d, H, hd), "wk": (d, K, hd),
              "wv": (d, K, hd), "wo": (H, hd, d)}
    if dense(conf, l):
        f = conf["intermediate_size"]
        shapes.update(w_gate=(d, f), w_up=(d, f), w_down=(f, d))
        return shapes
    E, f = conf["n_routed_experts"], conf["moe_intermediate_size"]
    fs = f * conf["n_shared_experts"]
    shapes.update(router=(d, E), w_gate=(E, d, f), w_up=(E, d, f),
                  w_down=(E, f, d), shared_gate=(d, fs), shared_up=(d, fs),
                  shared_down=(fs, d))
    return shapes


def fan_in(name: str, shape: tuple):
    if name in ("ln1", "ln2", "final_norm"):
        return None
    if name == "embed":
        return shape[1]
    if name == "wo":
        return shape[0] * shape[1]
    if name in ("wq", "wk", "wv"):
        return shape[0]
    return shape[-2]


def _draw_global(conf, key):
    return W.draw(jax.random.fold_in(key, 0), _global_shapes(conf), fan_in)


def _draw_layer(conf, l, key):
    return W.draw(jax.random.fold_in(key, l + 1), _layer_shapes(conf, l),
                  fan_in)


def canonical(conf: dict, key) -> dict:
    return {"global": _draw_global(conf, key),
            "layers": [_draw_layer(conf, l, key)
                       for l in range(conf["num_hidden_layers"])]}


def to_program(conf: dict, w: dict) -> dict:
    g, nd = w["global"], conf["first_k_dense_replace"]
    norm = lambda x: {"scale": x - 1.0}        # program scales by 1 + s

    def stack(layers, **names):         # program's name = published name
        return {k: jnp.stack([x[n] for x in layers]) for k, n in names.items()}

    def block(layers, **ffn):
        return dict(attn=stack(layers, wq="wq", wk="wk", wv="wv", wo="wo"),
                    ln1=norm(stack(layers, s="ln1")["s"]),
                    ln2=norm(stack(layers, s="ln2")["s"]), **ffn)

    swiglu = dict(w_gate="w_gate", w_up="w_up", w_down="w_down")
    dense_l, moe_l = w["layers"][:nd], w["layers"][nd:]
    moe = stack(moe_l, router="router", **swiglu)
    moe["shared"] = stack(moe_l, w_gate="shared_gate", w_up="shared_up",
                          w_down="shared_down")
    tree = {"embed": {"table": g["embed"]},
            "unembed": ({} if conf["tie_word_embeddings"]
                        else {"w": g["lm_head"]}),
            "final_norm": norm(g["final_norm"]),
            "moe_layers": block(moe_l, moe=moe)}
    if nd:
        tree["dense_layers"] = block(dense_l, mlp=stack(dense_l, **swiglu))
    return tree


# ------------------------------------------------------------------ reference
def _layer(conf, mode, is_dense, x, w):
    eps = conf["rms_norm_eps"]
    x = x + _gqa.attention(conf, w, _gqa.rmsnorm(x, w["ln1"], eps), mode)
    h = _gqa.rmsnorm(x, w["ln2"], eps)
    if is_dense:
        return x + _gqa.dense_ffn(conf, w, h, mode)
    shared = {"w_gate": w["shared_gate"], "w_up": w["shared_up"],
              "w_down": w["shared_down"]}
    return x + (_gqa.moe_ffn(conf, w, h, mode)
                + _gqa.dense_ffn(conf, shared, h, mode))


@lru_cache(maxsize=None)
def _jitted(conf_json: str, what: str, is_dense: bool = False,
            mode: str = "f32"):
    """One program per configuration and kind of layer."""
    conf = json.loads(conf_json)
    if what == "global":
        return jax.jit(partial(_draw_global, conf))
    if what == "layer weights":         # the layer's index is an argument
        shapes = _layer_shapes(conf, 0 if is_dense
                              else conf["first_k_dense_replace"])
        return jax.jit(lambda key, l: W.draw(jax.random.fold_in(key, l + 1),
                                             shapes, fan_in))
    return jax.jit(partial(_layer, conf, mode, is_dense))


def weights(conf: dict, seed: int) -> dict:
    """The seed's key and the weights outside the layers; each layer's
    are drawn when the reference reaches it."""
    key = W.seed_key(seed)
    return {"key": key,
            "global": _jitted(W.conf_key(conf), "global")(key)}


def layer_weights(conf: dict, src: dict, l: int) -> dict:
    return _jitted(W.conf_key(conf), "layer weights", dense(conf, l))(
        src["key"], l)


def embed(conf: dict, src: dict, tokens):
    return _gqa.embed(conf, src["global"], tokens)


def layer(conf: dict, mode: str, l: int):
    return _jitted(W.conf_key(conf), "layer", dense(conf, l), mode)


def head(conf: dict, mode: str, src: dict, x):
    return _gqa.head(conf, mode, src["global"], x)


# ------------------------------------------------------------------ counts
def _layer_matmul_flops(conf: dict, l: int) -> int:
    """Projections and feed-forward of layer ``l``, for one token: the
    routed experts' top-k and every shared expert."""
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    K, hd = conf["num_key_value_heads"], conf["head_dim"]
    proj = 2 * (d * H * hd + 2 * d * K * hd + H * hd * d)
    if dense(conf, l):
        return proj + 2 * 3 * d * conf["intermediate_size"]
    f, E = conf["moe_intermediate_size"], conf["n_routed_experts"]
    k, s = conf["num_experts_per_tok"], conf["n_shared_experts"]
    return proj + 2 * d * E + (k + s) * 2 * 3 * d * f


def token_flops(conf: dict, p0: int, n: int, emits: int) -> int:
    L = conf["num_hidden_layers"]
    per_pos = 4 * conf["num_attention_heads"] * conf["head_dim"]
    return (n * sum(_layer_matmul_flops(conf, l) for l in range(L))
            + L * per_pos * (n * p0 + n * (n + 1) // 2)
            + 2 * conf["hidden_size"] * conf["vocab_size"] * emits)


decode_attention_call = _gqa.decode_attention_call
