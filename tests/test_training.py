"""Training substrate: optimizer, 8-bit moments, checkpoint, data, loop."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import InputShape, get_smoke_config
from repro.data import DataConfig, data_iterator, synthetic_tokens
from repro.models import model as M
from repro.training import checkpoint as ckpt
from repro.training import optimizer as opt
from repro.training.trainer import TrainConfig, train_loop


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_minimizes_quadratic():
    cfg = opt.OptimizerConfig(learning_rate=0.1, warmup_steps=1,
                              total_steps=200, weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0, 2.0])}
    state = opt.init_opt_state(cfg, params)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}
        params, state, _ = opt.adamw_update(cfg, grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.2


def test_lr_schedule_shape():
    cfg = opt.OptimizerConfig(learning_rate=1.0, warmup_steps=10,
                              total_steps=100, min_lr_ratio=0.1)
    lrs = [float(opt.lr_schedule(cfg, jnp.int32(s))) for s in range(101)]
    assert lrs[0] < lrs[9] <= 1.0            # warmup
    assert lrs[10] == pytest.approx(1.0, abs=0.01)
    assert lrs[100] == pytest.approx(0.1, abs=0.02)  # decays to floor


@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(-100, 100), min_size=1, max_size=600))
def test_q8_roundtrip_error_bound(vals):
    x = jnp.asarray(vals, jnp.float32)
    enc = opt._q8_encode(x)
    dec = opt._q8_decode(enc, x.shape, x.size)
    # block-wise error <= half a quantization step of the block max
    step = float(jnp.max(jnp.abs(x))) / 127.0 + 1e-9
    assert float(jnp.abs(dec - x).max()) <= step * 1.01
    assert dec.shape == x.shape


def test_int8_moments_train_real_model():
    """Regression: sqrt-domain int8 v — linear-quantized v diverges on a
    real LM (EXPERIMENTS.md §Perf Hillclimb 3 coda)."""
    cfg = get_smoke_config("gemma3-1b")
    shape = InputShape("t", 64, 8, "train")
    tcfg = TrainConfig(optimizer=opt.OptimizerConfig(
        learning_rate=1e-3, warmup_steps=5, total_steps=40,
        moments_dtype="int8"), remat=None)
    it = data_iterator(cfg, shape, DataConfig(branching=2))
    _, hist = train_loop(cfg, tcfg, it, 30, log_every=5)
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.8
    assert hist[-1]["loss"] < 10.0  # linear-v int8 blows past 100 here


def test_int8_moments_track_float32():
    """8-bit Adam converges on the same toy problem."""
    params = {"w": jnp.full((512,), 4.0)}
    out = {}
    for dt in ("float32", "int8"):
        cfg = opt.OptimizerConfig(learning_rate=0.1, warmup_steps=1,
                                  total_steps=300, weight_decay=0.0,
                                  moments_dtype=dt)
        p, s = dict(params), opt.init_opt_state(cfg, params)
        for _ in range(100):
            p, s, _ = opt.adamw_update(cfg, {"w": 2 * p["w"]}, s, p)
        out[dt] = float(jnp.abs(p["w"]).max())
    assert out["int8"] < 0.5
    assert abs(out["int8"] - out["float32"]) < 0.3


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_sharded():
    a = synthetic_tokens(DataConfig(seed=3), 128, 4, 16, step=7)
    b = synthetic_tokens(DataConfig(seed=3), 128, 4, 16, step=7)
    np.testing.assert_array_equal(a, b)
    s0 = synthetic_tokens(DataConfig(seed=3, shard_index=0, num_shards=2),
                          128, 2, 16, step=7)
    s1 = synthetic_tokens(DataConfig(seed=3, shard_index=1, num_shards=2),
                          128, 2, 16, step=7)
    assert not np.array_equal(s0, s1)


def test_bigram_chain_is_learnable_structure():
    toks = synthetic_tokens(DataConfig(seed=0, branching=2), 64, 8, 200, 0)
    # successor sets are limited to `branching` per token
    succ = {}
    for row in toks:
        for a, b in zip(row[:-1], row[1:]):
            succ.setdefault(int(a), set()).add(int(b))
    assert max(len(v) for v in succ.values()) <= 2


def test_train_loop_reduces_loss():
    cfg = get_smoke_config("gemma3-1b")
    shape = InputShape("t", 64, 8, "train")
    tcfg = TrainConfig(optimizer=opt.OptimizerConfig(
        learning_rate=3e-3, warmup_steps=5, total_steps=60), remat=None)
    it = data_iterator(cfg, shape, DataConfig(branching=2))
    _, hist = train_loop(cfg, tcfg, it, 25, log_every=5)
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.7


def test_grad_accumulation_matches_single_batch():
    cfg = get_smoke_config("phi3-medium-14b")
    shape = InputShape("t", 16, 8, "train")
    key = jax.random.PRNGKey(0)
    batch = M.make_batch(cfg, shape, key)
    from repro.training import trainer as tr
    base = tr.TrainConfig(remat=None, microbatches=1)
    acc = tr.TrainConfig(remat=None, microbatches=4)
    s1 = tr.init_train_state(cfg, base, key)
    s2 = jax.tree.map(lambda x: x, s1)
    s1, m1 = tr.make_train_step(cfg, base)(s1, batch)
    s2, m2 = tr.make_train_step(cfg, acc)(s2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-3)
    a = jax.tree.leaves(s1["params"])[3]
    b = jax.tree.leaves(s2["params"])[3]
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=2e-3)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    cfg = get_smoke_config("granite-moe-1b-a400m")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    path = os.path.join(tmp_path, "ck.npz")
    ckpt.save(path, params, {"note": "test"})
    zeros = jax.tree.map(jnp.zeros_like, params)
    restored = ckpt.restore(path, zeros)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


def test_checkpoint_shape_mismatch_raises(tmp_path):
    path = os.path.join(tmp_path, "ck.npz")
    ckpt.save(path, {"w": jnp.ones((4,))})
    with pytest.raises(ValueError):
        ckpt.restore(path, {"w": jnp.ones((5,))})
