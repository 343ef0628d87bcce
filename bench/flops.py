"""Operations and bytes that the published math requires, from shapes.

Counts are per model token and per paged-attention call, for a
configuration file's published sizes (``bench/configs``).  A
multiply-add counts as two operations.  Nothing here counts what an
implementation adds on top: padding, experts computed beyond the top-k,
logits of prompt positions that produce no token, or pages read past a
row's length.
"""
from __future__ import annotations

from bench import spec


def _dims(conf: dict):
    return (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], spec.head_dim(conf),
            spec.ffn_width(conf), conf["num_hidden_layers"])


def layer_matmul_flops(conf: dict) -> int:
    """Projections and feed-forward of one layer, for one token."""
    d, H, K, hd, f, _ = _dims(conf)
    proj = 2 * (d * H * hd + 2 * d * K * hd + H * hd * d)
    if spec.is_moe(conf):
        E, k = conf["num_local_experts"], conf["num_experts_per_tok"]
        return proj + 2 * d * E + k * 2 * 3 * d * f
    return proj + 2 * 3 * d * f


def head_flops(conf: dict) -> int:
    return 2 * conf["hidden_size"] * conf["vocab_size"]


def attention_flops(conf: dict, ctx: int) -> int:
    """Scores and weighted values of one layer for one query token that
    attends ``ctx`` positions (itself included)."""
    _, H, _, hd, _, _ = _dims(conf)
    return 2 * 2 * ctx * H * hd


def token_flops(conf: dict, pos: int, emits: bool) -> int:
    """One token fed at position ``pos`` (it attends ``pos + 1``
    positions); ``emits``: its logits produce a served token."""
    L = conf["num_hidden_layers"]
    out = L * (layer_matmul_flops(conf) + attention_flops(conf, pos + 1))
    return out + (head_flops(conf) if emits else 0)


def paged_attention_call(conf: dict, ctxs, kv_bytes: int = 2,
                         act_bytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one decode-attention call of one layer
    over rows whose contexts are ``ctxs``: every row reads its own keys
    and values once and its query, and writes its output."""
    _, H, K, hd, _, _ = _dims(conf)
    flops = sum(attention_flops(conf, c) for c in ctxs)
    moved = sum(2 * c * K * hd * kv_bytes + 2 * H * hd * act_bytes
                for c in ctxs)
    return flops, moved
