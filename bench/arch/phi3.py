"""Phi-3 (``model_type`` phi3): grouped-query attention with rotary
embeddings and a dense SwiGLU feed-forward block, every layer alike, and
an untied head (``bench/arch/_gqa.py``).  Its ``sliding_window`` is not
applied (see ``phi3-medium-14b-4l.json``)."""
from bench.arch._gqa import *  # noqa: F401,F403
