"""Plain reference of the served models, in float32 ``jax.numpy``.

It follows the published description of each architecture and imports
nothing of the program.  Each architecture's layers are in its module
under ``bench/arch``; this module runs them one layer at a time,
holding that layer's weights alone, over every sequence, with
attention in blocks of query rows, so a whole prompt of the longest
context fits beside nothing else on the chip.  ``mode="fp8"`` is the
control: every matrix product takes its operands rounded to float8
(e4m3, one scale per tensor), the precision step below the bfloat16 the
configurations compute in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
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


def _bucket(xs, size: int) -> np.ndarray:
    out = np.zeros(size * math.ceil(len(xs) / size), np.int32)
    out[:len(xs)] = xs
    return out


def logits(conf: dict, seed: int, seqs, rows, modes=("f32",)):
    """Next-token logits of each sequence of ``seqs`` (1-D token ids) at
    its positions ``rows[i]``, in each precision of ``modes``, with the
    seed's weights.  Yields, sequence by sequence, ``{mode:
    (len(rows[i]), vocab) float32}``.  A layer's weights are fetched
    once and run over every sequence in every mode before the next
    layer's; sequences are padded at the end (attention is causal)."""
    mod = arch.module(conf)
    src = mod.weights(conf, seed)
    x0 = [mod.embed(conf, src, _bucket(s, LEN_BUCKET)) for s in seqs]
    xs = {m: list(x0) for m in modes}
    del x0
    for l in range(conf["num_hidden_layers"]):
        w = mod.layer_weights(conf, src, l)
        for m in modes:
            fn = mod.layer(conf, m, l)
            xs[m] = [fn(x, w) for x in xs[m]]
        del w
    for i, r in enumerate(rows):
        picked = jnp.asarray(_bucket(r, ROW_BUCKET))
        out = {}
        for m in modes:
            out[m] = mod.head(conf, m, src, xs[m][i][picked])[:len(r)]
            xs[m][i] = None
        yield out
