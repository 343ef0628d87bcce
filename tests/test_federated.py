"""Federated substrate: FedAvg, DP clipping, SecAgg mask cancellation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import InputShape, get_smoke_config
from repro.core import trustzones as tz
from repro.core.hub import EdgeAIHub
from repro.data import DataConfig, data_iterator
from repro.models import model as M
from repro.training import federated as fed
from repro.training import optimizer as opt

CFG = get_smoke_config("gemma3-1b")
SHAPE = InputShape("t", 32, 4, "train")


def _client_batches(n_clients, n_batches=2):
    out = {}
    for c in range(n_clients):
        it = data_iterator(CFG, SHAPE, DataConfig(seed=c, branching=2))
        out[c] = [next(it) for _ in range(n_batches)]
    return out


def test_fed_round_improves_loss():
    params = M.init_params(CFG, jax.random.PRNGKey(0))
    data = _client_batches(3)
    eval_b = data[0][0]
    before = float(M.loss_fn(CFG, params, eval_b)[0])
    fcfg = fed.FedConfig(local_steps=2, local_lr=0.5)
    for r in range(3):
        params, info = fed.fed_round(CFG, fcfg, params, data, r)
    after = float(M.loss_fn(CFG, params, eval_b)[0])
    assert after < before


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 6), st.integers(0, 100))
def test_secagg_masks_cancel_exactly(n_clients, round_seed):
    """Property: Σ masked(delta_i) == Σ delta_i (server never needs the
    individual updates)."""
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": jnp.ones((4,), jnp.float32)}
    deltas = {c: jax.tree.map(lambda x, c=c: x * (c + 1), tree)
              for c in range(n_clients)}
    clients = list(deltas)
    masked = {c: fed.secagg_mask(deltas[c], c, clients, round_seed)
              for c in clients}
    plain_sum = jax.tree.map(lambda *xs: sum(xs), *deltas.values())
    masked_sum = jax.tree.map(lambda *xs: sum(xs), *masked.values())
    for a, b in zip(jax.tree.leaves(plain_sum), jax.tree.leaves(masked_sum)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-3)


def test_secagg_individual_updates_are_hidden():
    tree = {"w": jnp.ones((8,), jnp.float32)}
    masked = fed.secagg_mask(tree, 0, [0, 1, 2], round_seed=1)
    assert float(jnp.abs(masked["w"] - tree["w"]).max()) > 0.1


@settings(max_examples=20, deadline=None)
@given(st.floats(0.1, 5.0))
def test_dp_clip_bounds_norm(clip):
    delta = {"w": jnp.full((32,), 7.0)}
    clipped = fed.clip_update(delta, clip)
    assert float(opt.global_norm(clipped)) <= clip * 1.001


def test_dp_noise_changes_update():
    tree = {"w": jnp.zeros((16,))}
    noisy = fed.add_gaussian_noise(tree, 0.1, jax.random.PRNGKey(0))
    assert float(jnp.abs(noisy["w"]).max()) > 0


def test_hub_federated_round_respects_zones():
    hub = EdgeAIHub.create()
    params = M.init_params(CFG, jax.random.PRNGKey(0))
    client_data = {n: [next(data_iterator(CFG, SHAPE, DataConfig(seed=i)))]
                   for i, n in enumerate(["alice-phone", "bob-phone",
                                          "living-room-tv"])}
    # alice's PERSONAL data: bob's phone must be excluded (owner gate)
    item = tz.DataItem("alice-voice", "personal", "alice")
    new_params, info = hub.federated_round(
        CFG, fed.FedConfig(local_steps=1, local_lr=0.1), params,
        client_data, item)
    assert len(info["clients"]) == 2  # alice-phone + tv; bob-phone gated

    # work data: no work-zone device exists in the home => hard refusal
    with pytest.raises(tz.AccessError):
        hub.federated_round(
            CFG, fed.FedConfig(local_steps=1, local_lr=0.1), params,
            client_data, tz.DataItem("corp-docs", "work", "alice"))


def test_full_private_pipeline():
    """FedAvg + clipping + secagg + DP noise in one round still learns."""
    params = M.init_params(CFG, jax.random.PRNGKey(0))
    data = _client_batches(4)
    fcfg = fed.FedConfig(local_steps=2, local_lr=0.5, dp_clip=5.0,
                         dp_noise_multiplier=0.01, secure_aggregation=True)
    eval_b = data[0][0]
    before = float(M.loss_fn(CFG, params, eval_b)[0])
    for r in range(3):
        params, _ = fed.fed_round(CFG, fcfg, params, data, r)
    after = float(M.loss_fn(CFG, params, eval_b)[0])
    assert after < before
