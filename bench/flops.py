"""Operations and bytes that the published math requires, from shapes.

Counts are per model token and per decode-attention call, for a
configuration file's published sizes (``bench/configs``); each
architecture's module in ``bench/arch`` does the counting.  A
multiply-add counts as two operations.  Nothing here counts what an
implementation adds on top: padding, experts computed beyond the top-k,
logits of prompt positions that produce no token, or pages read past a
row's length.
"""
from __future__ import annotations

from bench import arch


def token_flops(conf: dict, p0: int, n: int, emits: int) -> int:
    """``n`` tokens fed from position ``p0`` (the i-th of them attends
    ``p0 + i + 1`` positions, itself included), ``emits`` of whose
    logits produce a served token."""
    return arch.module(conf).token_flops(conf, p0, n, emits)


def paged_attention_call(conf: dict, ctxs) -> tuple[int, int]:
    """(operations, bytes) of one decode-attention call of one layer
    over rows whose contexts are ``ctxs``."""
    return arch.module(conf).decode_attention_call(conf, ctxs)
