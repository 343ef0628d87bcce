"""Architecture modules: everything the benchmark knows of a model's
layers, one module per published ``model_type``.

A configuration file's own ``model_type`` picks
``bench/arch/<model_type>.py``; a new architecture is a new module here
and touches no shared file.  Each module provides:

- ``program_fields(conf)``: the program's ``ModelConfig`` fields from
  the published keys;
- ``canonical(conf, key)``: every weight in the published layout
  (layers may differ, such as a dense first layer before expert
  layers), drawn from a key with ``bench.weights.draw`` (traced inside
  ``bench.weights``'s jitted makers);
- ``to_program(conf, w)``: the program's parameter tree holding those
  weights;
- the plain reference, driven a layer at a time by ``bench.reference``:
  ``weights(conf, seed)`` opens one seed's weights,
  ``layer_weights(conf, src, l)`` gives layer ``l``'s alone,
  ``embed(conf, src, tokens)``, ``layer(conf, mode, l)`` (a jitted
  ``(x, w) -> x``, one per kind of layer) and
  ``head(conf, mode, src, x)``;
- ``token_flops(conf, p0, n, emits)``: the published operations of
  ``n`` tokens fed from position ``p0``, ``emits`` of whose logits
  produce a served token; ``decode_attention_call(conf, ctxs)``: the
  operations and bytes of one decode-attention call of one layer;
- ``smoke(conf)``: a copy shrunk for the CPU tests.
"""
from __future__ import annotations

import importlib
from pathlib import Path

ARCH_DIR = Path(__file__).resolve().parent


def known() -> list[str]:
    return sorted(p.stem for p in ARCH_DIR.glob("*.py")
                  if not p.stem.startswith("_"))


def module(conf: dict):
    """The architecture module named by the file's ``model_type``."""
    name = f"{__name__}.{conf['model_type']}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise KeyError(f"no architecture module for model_type "
                       f"{conf['model_type']!r}; known: {known()}") from None
