"""Sharding rules: params / batches / caches -> PartitionSpecs.

Rule engine over leaf *names* with dims-addressed-from-the-right (so the
same rule covers scan-stacked and unstacked params).  Every candidate
axis assignment is divisibility-checked against the mesh — non-divisible
dims fall back down the candidate list, ending at replication.  This is
what lets ONE rule set cover all 10 architectures on the (16,16) and
(2,16,16) production meshes.

``fsdp=True`` additionally shards a second dim of every large tensor
over the data axis (ZeRO-3-style), the lever that fits the 1T-param MoE
(EXPERIMENTS.md §Dry-run).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np

from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P
from repro.configs.base import InputShape, ModelConfig


def data_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _axsize(mesh: Mesh, ax) -> int:
    if isinstance(ax, tuple):
        return int(np.prod([mesh.shape[a] for a in ax]))
    return mesh.shape[ax]


def _assign(shape, mesh, candidates) -> P:
    """candidates: list of (dim_from_right, axis). First divisible wins
    per axis; one dim gets at most one axis."""
    spec = [None] * len(shape)
    used_dims = set()
    used_axes = set()
    for dim_r, ax in candidates:
        dim = len(shape) + dim_r if dim_r < 0 else dim_r
        if dim < 0 or dim >= len(shape) or dim in used_dims:
            continue
        key = ax if isinstance(ax, tuple) else (ax,)
        if any(a in used_axes for a in key):
            continue
        if shape[dim] % _axsize(mesh, ax) == 0 and shape[dim] > 0:
            spec[dim] = ax
            used_dims.add(dim)
            used_axes.update(key)
    return P(*spec)


# rule table: leaf name -> (primary candidates, fsdp extra candidates)
# dims are from-the-right so scan-stacking prefixes don't matter.
# CAREFUL: expert tensors (E, d, f) share leaf names with dense MLPs
# (d, f) — disambiguated by rank in the rule fn (a -3 rule applied to a
# scan-stacked dense (L, d, f) would shard the LAYER dim, which makes
# XLA all-gather the whole stack per step).
_PARAM_RULES = {
    # attention
    "wq": ([(-2, "model"), (-3, "model")], [(-3, "data")]),
    "wk": ([(-2, "model"), (-1, "model"), (-3, "model")], [(-3, "data")]),
    "wv": ([(-2, "model"), (-1, "model"), (-3, "model")], [(-3, "data")]),
    "wo": ([(-3, "model"), (-1, "model")], [(-1, "data")]),
    # dense MLPs (2D unstacked)
    "w_gate": ([(-1, "model")], [(-2, "data")]),
    "w_up": ([(-1, "model")], [(-2, "data")]),
    "w_down": ([(-2, "model")], [(-1, "data")]),
    "router": ([(-1, "model")], []),
    # whisper mlp
    "w_in": ([(-1, "model")], [(-2, "data")]),
    "w_out": ([(-2, "model")], [(-1, "data")]),
    # ssm
    "in_proj": ([(-2, "model")], [(-1, "data")]),
    "out_proj": ([(-2, "model")], [(-1, "data")]),
    # embeddings
    "table": ([(-2, "model"), (-1, "model")], [(-1, "data"), (-2, "data")]),
    "w": ([(-1, "model"), (-2, "model")], [(-2, "data")]),  # unembed
    "w1": ([(-1, "model")], []),                            # vlm projector
    "w2": ([(-1, "model")], []),
    "pos_table": ([], []),
}

# routed-expert tensors: (layers?, E, d, f) — expert-parallel over model,
# fsdp shards the ffn dim over data (the 1T-MoE memory lever)
_EXPERT_RULES = {
    "w_gate": ([(-3, "model"), (-1, "model")], [(-1, "data")]),
    "w_up": ([(-3, "model"), (-1, "model")], [(-1, "data")]),
    "w_down": ([(-3, "model"), (-2, "model")], [(-2, "data")]),
}


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if hasattr(entry, "key"):
            return str(entry.key)
    return ""


def param_pspecs(cfg: ModelConfig, mesh: Mesh, params_shape: Any,
                 *, fsdp: bool = False, smart: bool = False) -> Any:
    """PartitionSpec pytree matching ``params_shape`` (shapes pytree).

    ``smart=True`` enables the §Perf beyond-baseline rules: attention
    projections are kept OFF the model axis when the head counts don't
    divide it (indivisible-head sharding leaves q/k sharded on head_dim,
    which makes XLA all-reduce an S x S score tile per attention block —
    the phi3 prefill pathology).  FSDP then carries the memory.
    """
    msize = mesh.shape["model"]
    heads_div = cfg.num_heads > 0 and cfg.num_heads % msize == 0
    kv_div = cfg.num_kv_heads > 0 and cfg.num_kv_heads % msize == 0
    da = data_axes(mesh)
    dax = da[0] if len(da) == 1 else tuple(da)  # multi-pod: ('pod','data')

    def rule(path, leaf):
        name = _leaf_name(path)
        shape = leaf.shape
        if len(shape) <= 1:
            return P()
        path_keys = [str(getattr(e, "key", "")) for e in path]
        if name in _EXPERT_RULES and len(shape) >= 4 and "moe" in path_keys:
            prim, extra = _EXPERT_RULES[name]
        else:
            prim, extra = _PARAM_RULES.get(name, ([], []))
        if smart:
            if name in ("wq", "wo") and not heads_div:
                prim = []
            if name in ("wk", "wv") and not kv_div:
                prim = []
        extra = [(d, dax if ax == "data" else ax) for d, ax in extra]
        cands = list(prim) + (list(extra) if fsdp else [])
        return _assign(shape, mesh, cands)

    return jax.tree_util.tree_map_with_path(rule, params_shape)


def opt_pspecs(param_specs: Any, opt_shape: Any, mesh: Mesh) -> Any:
    """Optimizer state specs: moments MIRROR their parameter's spec.

    int8 moments are shape-preserving (optimizer._q8_encode): q carries
    the parameter spec (last axis kept only while the padded size stays
    divisible); scale drops the last axis (it is per-BLOCK, tiny).
    Mirroring matters: any mismatch forces XLA to reshard the decoded
    f32 moments every step.
    """
    def for_moment(pspec, leaf):
        if isinstance(leaf, dict) and "q" in leaf:
            q_shape = leaf["q"].shape
            entries = list(pspec) + [None] * (len(q_shape) - len(pspec))
            q_spec = []
            for dim, ax in enumerate(entries):
                ok = (ax is not None
                      and q_shape[dim] % _axsize(mesh, ax) == 0)
                q_spec.append(ax if ok else None)
            s_spec = q_spec[:-1] + [None, None]
            return {"q": P(*q_spec), "scale": P(*s_spec)}
        return pspec

    is_q8 = lambda x: isinstance(x, dict) and "q" in x
    return {
        "step": P(),
        "m": jax.tree.map(for_moment, param_specs, opt_shape["m"],
                          is_leaf=lambda x: is_q8(x)),
        "v": jax.tree.map(for_moment, param_specs, opt_shape["v"],
                          is_leaf=lambda x: is_q8(x)),
    }


def batch_pspecs(cfg: ModelConfig, mesh: Mesh, batch_shape: Any) -> Any:
    da = data_axes(mesh)
    ax = da if len(da) == 1 else tuple(da)

    def rule(path, leaf):
        b = leaf.shape[0]
        if b % _axsize(mesh, ax if isinstance(ax, tuple) else ax[0]) == 0:
            first = ax if isinstance(ax, str) else tuple(ax)
            return P(first, *([None] * (len(leaf.shape) - 1)))
        return P(*([None] * len(leaf.shape)))

    return jax.tree_util.tree_map_with_path(rule, batch_shape)


def cache_pspecs(cfg: ModelConfig, mesh: Mesh, cache_shape: Any,
                 batch: int, *, smart: bool = False) -> Any:
    """KV/SSM cache specs.  Batch over data when divisible; batch=1
    long-context decode shards the TIME axis over data instead and
    heads/channels over model.

    ``smart=True``: when kv heads don't divide the model axis, shard the
    cache on TIME over model instead of head_dim — head_dim-sharded
    caches force a full per-layer cache all-gather at every decode step
    (the internvl2 decode pathology); time-sharded caches only move an
    (B, H, 1, T) score strip.
    """
    da = data_axes(mesh)
    dax = da[0] if len(da) == 1 else tuple(da)
    d_n = _axsize(mesh, dax)
    m_n = _axsize(mesh, "model")
    batch_ok = batch % d_n == 0
    kv_div = cfg.num_kv_heads > 0 and cfg.num_kv_heads % m_n == 0

    def rule(path, leaf):
        name = _leaf_name(path)
        shape = leaf.shape
        cands = []
        if name in ("k", "v", "cross_k", "cross_v"):
            # (..., B, T, K, hd)
            kv_c = ([(-2, "model")] if kv_div or not smart
                    else [(-2, "model"), (-3, "model")])
            tail = [(-1, "model")] if not smart else []
            if batch_ok:
                cands = [(-4, dax)] + kv_c + tail
            else:
                cands = [(-3, dax)] + kv_c + tail
        elif name == "slots":
            # (..., B, T)
            cands = [(-2, dax)] if batch_ok else [(-1, dax)]
        elif name == "conv":
            # (..., B, w-1, ch)
            cands = ([(-3, dax), (-1, "model")] if batch_ok
                     else [(-1, "model")])
        elif name == "ssm":
            # (..., B, h, p, n)
            cands = ([(-4, dax), (-3, "model")] if batch_ok
                     else [(-3, "model")])
        return _assign(shape, mesh, cands)

    return jax.tree_util.tree_map_with_path(rule, cache_shape)


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
