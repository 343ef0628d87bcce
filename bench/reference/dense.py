"""Feed-forward block of a dense decoder (Phi-3, Llama style): SwiGLU,
``down(silu(gate(h)) * up(h))``."""
from __future__ import annotations

import jax

from bench.reference import mm


def ffn(conf, w, h, mode):
    a = jax.nn.silu(mm("td,df->tf", h, w["w_gate"], mode))
    return mm("tf,fd->td", a * mm("td,df->tf", h, w["w_up"], mode),
              w["w_down"], mode)
