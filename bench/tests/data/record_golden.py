"""Record ``golden.json``, the values ``test_bench_golden.py`` checks for
exact equality, with the benchmark as it stood before the architecture
modules: at commit ea46852, whose ``bench`` API this script calls
(``reference.logits(conf, w, tokens, rows, mode)``,
``weights._canonical``, ``weights._to_program``,
``flops.token_flops(conf, p, emits)``).  From the root of a checkout of
that commit:

    git archive ea46852 | tar -x -C <dir>
    cd <dir> && PYTHONPATH=.:src JAX_PLATFORMS=cpu \
        python3 <this file> golden.json

gives this directory's ``golden.json`` byte for byte.
"""
import hashlib
import json
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, harness, reference, spec, weights
from bench.tests import smoke

CONFIGS = ["granite-moe-1b-a400m", "phi3-medium-14b-4l"]
SEEDS = [7, 4294967311]
POSITIONS = [(0, 0), (0, 1), (99, 1), (511, 0), (2047, 1), (4095, 1)]
CTXS = [[1], [1000, 3000], [17, 256, 2048, 4096]]
# engine lifecycle events of four steps: admissions with prefix hits,
# prompt chunks, and decoding slots that emit
EVENTS = [
    [{"name": "admitted 1", "args": {"uid": 1, "prefix_hit_tokens": 0}},
     {"name": "admitted 2", "args": {"uid": 2, "prefix_hit_tokens": 32}},
     {"name": "prefill_chunk 1", "args": {"uid": 1, "n": 64}},
     {"name": "prefill_chunk 2", "args": {"uid": 2, "n": 17}},
     {"name": "tokens 2", "args": {"uid": 2, "n": 1}}],
    [{"name": "prefill_chunk 1", "args": {"uid": 1, "n": 40}},
     {"name": "tokens 1", "args": {"uid": 1, "n": 1}},
     {"name": "tokens 2", "args": {"uid": 2, "n": 1}}],
    [{"name": "tokens 1", "args": {"uid": 1}},
     {"name": "tokens 2", "args": {"uid": 2}},
     {"name": "retired 2", "args": {}}],
    [{"name": "admitted 3", "args": {"uid": 3, "prefix_hit_tokens": 1000}},
     {"name": "prefill_chunk 3", "args": {"uid": 3, "n": 128}},
     {"name": "tokens 1", "args": {"uid": 1, "n": 1}}],
]


def digest(a):
    a = np.asarray(a)
    return f"{a.dtype}{list(a.shape)}:" + hashlib.sha256(
        np.ascontiguousarray(a).tobytes()).hexdigest()


def leaves(tree):
    return {jax.tree_util.keystr(p): digest(v)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def shapes(tree):
    return {jax.tree_util.keystr(p): [list(v.shape), str(v.dtype)]
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def counts(conf):
    log = SimpleNamespace(conf=conf, pos={})
    steps = [harness.StepLog._account(log, ev) for ev in EVENTS]
    return {"token_flops": [[p, 1, e, flops.token_flops(conf, p, bool(e))]
                            for p, e in POSITIONS],
            "paged_attention_call": [[c, *flops.paged_attention_call(conf, c)]
                                     for c in CTXS],
            "steps": steps}


def main():
    out = {"configs": {}}
    for name in CONFIGS:
        full = spec.load_config(name)
        conf = smoke.conf(name)
        mcfg = spec.model_config(conf)
        rec = {"smoke_conf": conf, "seeds": {},
               "counts": {"smoke": counts(conf), "full": counts(full)}}
        toks = np.random.default_rng(0).integers(0, conf["vocab_size"], 40,
                                                 dtype=np.int32)
        rows = np.arange(40)
        for seed in SEEDS:
            w = weights.canonical(conf, seed)
            p = weights.program_params(conf, mcfg, seed)
            rec["seeds"][str(seed)] = {
                "canonical": leaves(w), "program": leaves(p),
                "logits": {m: digest(reference.logits(conf, w, toks, rows, m))
                           for m in ("f32", "fp8")}}
        fm = spec.model_config(full)
        from repro.models import model as M
        rec["full_shapes"] = {
            "canonical": shapes(jax.eval_shape(
                lambda k: weights._canonical(full, k), jax.random.PRNGKey(0))),
            "program": shapes(jax.eval_shape(
                lambda k: weights._to_program(full, weights._canonical(full, k)),
                jax.random.PRNGKey(0))),
            "init_params": shapes(jax.eval_shape(
                lambda k: M.init_params(fm, k), jax.random.PRNGKey(0)))}
        out["configs"][name] = rec
    json.dump(out, open(sys.argv[1], "w"), indent=1, sort_keys=True)


if __name__ == "__main__":
    main()
