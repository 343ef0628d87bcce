"""Random weights from the seed, made by the benchmark itself.

``canonical`` lays the weights out by the published architecture
(embedding, per-layer projections, norms, router and experts, head);
the plain reference reads that layout.  ``program_params`` puts the same
numbers into the program's parameter tree.  Both are one jitted call on
the device, in float32, the type the program serves its weights in.
The program gets its weights from here, never from its own
``init_params``, so the reference takes nothing the program has made.
"""
from __future__ import annotations

import json
from functools import lru_cache, partial

import jax
import numpy as np

from bench import spec

NORM_SPREAD = 0.1       # norm gains are 1 + 0.1 N(0, 1): not all ones


def seed_key(seed: int):
    """A JAX key from any whole number, also past 32 bits."""
    s = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(s[0] >> 1)),
                              int(s[1] >> 1))


def canonical_shapes(conf: dict) -> dict:
    d, V = conf["hidden_size"], conf["vocab_size"]
    L, H = conf["num_hidden_layers"], conf["num_attention_heads"]
    K, hd, f = conf["num_key_value_heads"], spec.head_dim(conf), \
        spec.ffn_width(conf)
    shapes = {
        "embed": (V, d),
        "ln1": (L, d), "ln2": (L, d), "final_norm": (d,),
        "wq": (L, d, H, hd), "wk": (L, d, K, hd), "wv": (L, d, K, hd),
        "wo": (L, H, hd, d),
    }
    if spec.is_moe(conf):
        E = conf["num_local_experts"]
        shapes.update(router=(L, d, E), w_gate=(L, E, d, f),
                      w_up=(L, E, d, f), w_down=(L, E, f, d))
    else:
        shapes.update(w_gate=(L, d, f), w_up=(L, d, f), w_down=(L, f, d))
    if not conf["tie_word_embeddings"]:
        shapes["lm_head"] = (d, V)
    return shapes


def _fan_in(name: str, shape: tuple) -> int:
    if name == "embed":
        return shape[1]
    if name == "wo":
        return shape[-3] * shape[-2]
    return shape[-2] if name in ("w_gate", "w_up", "w_down", "router",
                                 "lm_head") else shape[-3]


def _canonical(conf: dict, key) -> dict:
    out = {}
    for i, (name, shape) in enumerate(sorted(canonical_shapes(conf).items())):
        k = jax.random.fold_in(key, i)
        if name in ("ln1", "ln2", "final_norm"):
            out[name] = 1.0 + NORM_SPREAD * jax.random.normal(k, shape)
        else:
            std = _fan_in(name, shape) ** -0.5
            out[name] = std * jax.random.truncated_normal(k, -2.0, 2.0,
                                                          shape)
    return out


@lru_cache(maxsize=None)
def _jitted(conf_json: str, program: bool):
    """One jitted maker per configuration, so a process that makes many
    seeds' weights traces and compiles it once."""
    conf = json.loads(conf_json)
    if program:
        return jax.jit(lambda k: _to_program(conf, _canonical(conf, k)))
    return jax.jit(partial(_canonical, conf))


def _key(conf: dict) -> str:
    return json.dumps(conf, sort_keys=True)


def canonical(conf: dict, seed: int) -> dict:
    """The weights in the published layout (float32, on the device)."""
    return _jitted(_key(conf), False)(seed_key(seed))


def _to_program(conf: dict, w: dict) -> dict:
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
    if spec.is_moe(conf):
        layers["moe"] = dict(ffn, router=w["router"])
        tree["moe_layers"] = layers
    else:
        layers["mlp"] = ffn
        tree["trunk"] = {"layers": layers}
    return tree


def program_params(conf: dict, mcfg, seed: int):
    """The program's parameter tree holding the canonical numbers,
    checked leaf by leaf against the shapes of ``init_params``."""
    from repro.models import model as M

    want = jax.eval_shape(partial(M.init_params, mcfg),
                          jax.random.PRNGKey(0))
    fn = _jitted(_key(conf), True)
    got = jax.eval_shape(fn, seed_key(0))
    if (jax.tree.structure(got) != jax.tree.structure(want)
            or any(a.shape != b.shape for a, b in
                   zip(jax.tree.leaves(got), jax.tree.leaves(want)))):
        raise ValueError("the program's parameter tree no longer matches "
                         f"the benchmark's layout: {want} vs {got}")
    return fn(seed_key(seed))
