"""Feed-forward block of a sparse mixture of experts (Granite-MoE,
Mixtral style): a softmax router, the ``num_experts_per_tok`` best
experts per token with their probabilities renormalised to sum to one,
and a SwiGLU expert each.  No token is ever dropped.  Every expert is
computed for every token and the unchosen ones weigh zero, which is
plain rather than fast."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import mm


def ffn(conf, w, h, mode):
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
