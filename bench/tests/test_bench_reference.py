"""The plain reference agrees with the program at smoke size, with the
program computing in float32; the float8 control does not."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, spec, weights
from bench.tests import smoke

CONFIGS = ["granite-moe-1b-a400m", "phi3-medium-14b-4l"]


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_program_forward(name):
    from repro.models import model as M
    conf = smoke.conf(name)
    mcfg = spec.model_config(conf).replace(dtype="float32")
    params = weights.program_params(conf, mcfg, 4294967311)
    toks = np.random.default_rng(0).integers(0, conf["vocab_size"], 40,
                                             dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = M.apply(mcfg, params, {"tokens": jnp.asarray(toks)[None]})
    w = weights.canonical(conf, 4294967311)
    rows = np.arange(40)
    want = reference.logits(conf, w, toks, rows)
    err = float(jnp.max(jnp.abs(got[0] - want)))
    assert err < 2e-3, err
    ctl = reference.logits(conf, w, toks, rows, "fp8")
    assert float(jnp.max(jnp.abs(ctl - want))) > 20 * max(err, 1e-4)


def test_weights_are_the_seeds():
    conf = smoke.conf("phi3-medium-14b-4l")
    a = weights.canonical(conf, 7)
    b = weights.canonical(conf, 7)
    c = weights.canonical(conf, 8)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wq"], c["wq"])
    assert abs(float(jnp.mean(a["ln1"])) - 1.0) < 0.05
