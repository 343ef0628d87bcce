"""Granite-MoE (``model_type`` granitemoe): grouped-query attention with
rotary embeddings and ``attention_multiplier`` as the score scale, then
a sparse mixture of SwiGLU experts under a softmax top-k router, every
layer alike (``bench/arch/_gqa.py``).  Granite's embedding, residual
and logits scalings are read only where the configuration states them
at its top level; ``granite-moe-1b-a400m.json`` keeps them under
``published``, as the program has no such scalings."""
from bench.arch._gqa import *  # noqa: F401,F403
