"""The plain reference agrees with the program at smoke size, with the
program computing in float32; the float8 control does not.  Every
configuration of ``BENCHMARK.json``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec, weights
from bench.tests import smoke

CONFIGS = sorted(c["name"] for c in spec.load_benchmark()["configs"])
SEED = 4294967311


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_program_forward(name):
    from repro.models import model as M
    conf = smoke.conf(name)
    mcfg = spec.model_config(conf).replace(dtype="float32")
    params = weights.program_params(conf, mcfg, SEED)
    toks = np.random.default_rng(0).integers(0, conf["vocab_size"], 40,
                                             dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = M.apply(mcfg, params, {"tokens": jnp.asarray(toks)[None]})
    out = next(reference.logits(conf, SEED, [toks], [np.arange(40)],
                                ("f32", "fp8")))
    want = out["f32"]
    err = float(jnp.max(jnp.abs(got[0] - want)))
    assert err < 2e-3, err
    assert float(jnp.max(jnp.abs(out["fp8"] - want))) > 20 * max(err, 1e-4)


def test_weights_are_the_seeds():
    conf = smoke.conf("phi3-medium-14b-4l")
    a = weights.canonical(conf, 7)
    b = weights.canonical(conf, 7)
    c = weights.canonical(conf, 8)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wq"], c["wq"])
    assert abs(float(jnp.mean(a["ln1"])) - 1.0) < 0.05


@pytest.mark.parametrize("stated", [None, "bfloat16"])
def test_program_weights_never_narrower_than_stated(monkeypatch, stated):
    """A program whose ``init_params`` holds bfloat16 weights is refused
    for a configuration that states none (float32) and served in
    bfloat16 for one that states it."""
    from repro.models import model as M
    conf = smoke.conf("phi3-medium-14b-4l")
    if stated:
        conf["served_dtype"] = stated
    mcfg = spec.model_config(conf)
    init = M.init_params
    monkeypatch.setattr(M, "init_params", lambda cfg, key: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), init(cfg, key)))
    if stated is None:
        with pytest.raises(ValueError, match="narrower"):
            weights.program_maker(conf, mcfg)
        return
    params = weights.program_params(conf, mcfg, SEED)
    assert {str(a.dtype) for a in jax.tree.leaves(params)} == {"bfloat16"}
