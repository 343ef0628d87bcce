"""The architecture modules (``bench/arch``) give exactly what the
benchmark gave before them, for every configuration in ``data/golden.json``.

The file was recorded with the harness of commit ea46852, which built
its two architectures into the shared files: at smoke size on the CPU,
for seeds 7 and 4294967311, a SHA-256 of every canonical weight, of
every leaf of the program's parameter tree, and of the reference's and
the float8 control's logits over a fixed 40-token sequence; at smoke
size and at the published widths, the operations of single tokens
(``flops.token_flops``), of the engine steps of a fixed list of
lifecycle events (what ``step_mfu`` sums), and the operations and bytes
of decode-attention calls over fixed contexts (what
``paged_attention_roofline`` reads); and at the published widths the
shapes and types of the canonical tree, the program's tree and
``init_params``.  Every comparison is for equality.
"""
import hashlib
import json
from functools import partial
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import arch, flops, harness, reference, spec, weights
from bench.tests import smoke

GOLDEN = json.loads((Path(__file__).parent / "data" / "golden.json")
                    .read_text())["configs"]
CASES = [(n, int(s)) for n in sorted(GOLDEN) for s in GOLDEN[n]["seeds"]]
TOKENS = 40
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


def digest(a) -> str:
    a = np.asarray(a)
    return f"{a.dtype}{list(a.shape)}:" + hashlib.sha256(
        np.ascontiguousarray(a).tobytes()).hexdigest()


def leaves(tree) -> dict:
    return {jax.tree_util.keystr(p): digest(v)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def shapes(tree) -> dict:
    return {jax.tree_util.keystr(p): [list(v.shape), str(v.dtype)]
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_smoke_configuration(name):
    assert smoke.conf(name) == GOLDEN[name]["smoke_conf"]


@pytest.mark.parametrize("name,seed", CASES)
def test_weights_and_program_tree(name, seed):
    conf = smoke.conf(name)
    want = GOLDEN[name]["seeds"][str(seed)]
    assert leaves(weights.canonical(conf, seed)) == want["canonical"]
    params = weights.program_params(conf, spec.model_config(conf), seed)
    assert leaves(params) == want["program"]


@pytest.mark.parametrize("name,seed", CASES)
def test_reference_and_control_logits(name, seed):
    conf = smoke.conf(name)
    toks = np.random.default_rng(0).integers(0, conf["vocab_size"], TOKENS,
                                             dtype=np.int32)
    out = next(reference.logits(conf, seed, [toks], [np.arange(TOKENS)],
                                ("f32", "fp8")))
    assert {m: digest(v) for m, v in out.items()} == \
        GOLDEN[name]["seeds"][str(seed)]["logits"]


@pytest.mark.parametrize("size", ["smoke", "full"])
@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_operation_and_byte_counts(name, size):
    conf = smoke.conf(name) if size == "smoke" else spec.load_config(name)
    want = GOLDEN[name]["counts"][size]
    assert [[p, n, e, flops.token_flops(conf, p, n, e)]
            for p, n, e, _ in want["token_flops"]] == want["token_flops"]
    assert [[c, *flops.paged_attention_call(conf, c)]
            for c, _, _ in want["paged_attention_call"]] == \
        want["paged_attention_call"]
    log = SimpleNamespace(conf=conf, pos={})
    assert [harness.StepLog._account(log, ev) for ev in EVENTS] == \
        want["steps"]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_full_width_tree_shapes(name):
    from repro.models import model as M
    conf = spec.load_config(name)
    mcfg = spec.model_config(conf)
    key = jax.random.PRNGKey(0)
    want = GOLDEN[name]["full_shapes"]
    assert shapes(jax.eval_shape(partial(arch.module(conf).canonical, conf),
                                 key)) == want["canonical"]
    assert shapes(jax.eval_shape(weights.program_maker(conf, mcfg),
                                 key)) == want["program"]
    assert shapes(jax.eval_shape(lambda k: M.init_params(mcfg, k),
                                 key)) == want["init_params"]
