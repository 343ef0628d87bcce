"""Split computing + early exit (the paper's offloading & sustainability
mechanisms) — execution correctness and decision sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, get_smoke_config
from repro.core import earlyexit as EE
from repro.core import split as SP
from repro.core.network import CHANNEL_CATALOGUE, MultiChannelLink
from repro.core.perf_model import DEVICE_CATALOGUE
from repro.models import model as M
from repro.models import transformer as T

CFG = get_smoke_config("phi3-medium-14b")


@pytest.fixture(scope="module")
def setup():
    params = M.init_params(CFG, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                              CFG.vocab_size)
    return params, toks


@pytest.mark.parametrize("split", [0, 1, 2])
def test_split_forward_equivalence(setup, split):
    params, toks = setup
    full = T.forward(CFG, params, toks)
    out, payload = SP.split_forward(CFG, params, toks, split, bits=8)
    scale = float(jnp.abs(full).max()) + 1.0
    assert float(jnp.abs(out - full).max()) / scale < 0.05
    if 0 < split < CFG.num_layers:
        assert payload > 0
    else:
        assert payload == 0


def test_higher_bits_less_error(setup):
    params, toks = setup
    full = T.forward(CFG, params, toks)
    errs = []
    for bits in (4, 8):
        out, _ = SP.split_forward(CFG, params, toks, 1, bits=bits)
        errs.append(float(jnp.abs(out - full).max()))
    assert errs[1] < errs[0]


@settings(max_examples=20, deadline=None)
@given(st.floats(-50, 50), st.integers(2, 8))
def test_activation_quant_roundtrip(scale, bits):
    x = scale * jax.random.normal(jax.random.PRNGKey(0), (4, 8, 16))
    q, s = SP.quantize_activations(x, bits)
    back = SP.dequantize_activations(q, s, jnp.float32)
    step = float(jnp.max(jnp.abs(x), axis=-1).max()) / (2 ** (bits - 1) - 1)
    assert float(jnp.abs(back - x).max()) <= step * 0.51 + 1e-6


def test_choose_split_slow_link_avoids_activation_transfer():
    """On a near-dead channel the optimum is an ENDPOINT: shipping an
    int8 activation tensor mid-network (~655 KB here, ~26 s on zigbee)
    can never beat raw tokens up (k=0) or predictions back (k=L).
    LM token payloads are tiny, so full offload may still win — the
    split sweet spot needs payload-heavy inputs or better channels."""
    cfg = get_config("phi3-medium-14b")
    phone = DEVICE_CATALOGUE["flagship-phone"]
    hub = DEVICE_CATALOGUE["edgeai-hub"]
    slow = MultiChannelLink([CHANNEL_CATALOGUE["zigbee"]])
    fast = MultiChannelLink([CHANNEL_CATALOGUE["ethernet"]])
    d_slow = SP.choose_split(cfg, phone, hub, slow, 1, 128)
    d_fast = SP.choose_split(cfg, phone, hub, fast, 1, 128)
    assert d_slow.split in (0, cfg.num_layers)  # endpoint only
    assert d_fast.total_s < d_slow.total_s      # better channel helps
    # and a weak device + fast link prefers offloading the tail
    weak = DEVICE_CATALOGUE["iot-sensor"]
    d_weak = SP.choose_split(cfg, weak, hub, fast, 1, 128)
    assert d_weak.split < cfg.num_layers


def test_choose_split_covers_all_cuts():
    cfg = get_config("gemma2-9b").replace(pattern_period=1)
    phone = DEVICE_CATALOGUE["mid-phone"]
    hub = DEVICE_CATALOGUE["edgeai-hub"]
    link = MultiChannelLink([CHANNEL_CATALOGUE["wifi-legacy"]])
    d = SP.choose_split(cfg, phone, hub, link, 1, 512)
    assert 0 <= d.split <= cfg.num_layers
    assert d.total_s > 0


# ---------------------------------------------------------------------------
# early exit
# ---------------------------------------------------------------------------

def test_exit_heads_training_loss(setup):
    params, toks = setup
    heads = EE.init_exit_heads(CFG, jax.random.PRNGKey(2), [0])
    loss = EE.exit_loss(CFG, params, heads, {"tokens": toks,
                                             "targets": toks})
    assert float(loss) > 0 and not bool(jnp.isnan(loss))
    # grad over the float head params only (exit_layers are static ints)
    grads = jax.grad(lambda ex: EE.exit_loss(
        CFG, params, {"exits": ex, "exit_layers": heads["exit_layers"]},
        {"tokens": toks, "targets": toks}))(heads["exits"])
    assert all(not bool(jnp.isnan(g).any())
               for g in jax.tree.leaves(grads))


def test_low_threshold_exits_early(setup):
    params, toks = setup
    heads = EE.init_exit_heads(CFG, jax.random.PRNGKey(2), [0])
    eager = EE.serve_early_exit(CFG, params, heads, toks, threshold=0.0)
    never = EE.serve_early_exit(CFG, params, heads, toks, threshold=1.1)
    assert eager.expected_layers <= never.expected_layers
    assert eager.flops_saved_frac > 0
    assert never.flops_saved_frac == 0
    assert eager.predictions.shape == toks.shape
