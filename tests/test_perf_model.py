"""Performance controller: roofline estimators + historical EWMA."""
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core.perf_model import (
    DEVICE_CATALOGUE,
    HistoricalEstimator,
    TaskCost,
    estimate,
    inference_cost,
    model_flops_per_token,
    training_cost,
)


def test_hub_dominates_phone():
    cost = inference_cost(get_config("phi3-medium-14b"), 1, 128)
    hub = estimate(cost, DEVICE_CATALOGUE["edgeai-hub"])
    phone = estimate(cost, DEVICE_CATALOGUE["mid-phone"])
    assert hub.latency_s < phone.latency_s
    assert not phone.fits_memory        # 28 GB f16 weights vs 6 GB phone
    assert hub.fits_memory or cost.mem_bytes > 16e9


def test_decode_is_memory_bound_on_edge():
    """The paper's TinyBERT point: single-token decode streams weights."""
    for name in ("flagship-phone", "mid-phone", "edgeai-hub"):
        cost = inference_cost(get_config("gemma2-9b"), 1, 1)
        est = estimate(cost, DEVICE_CATALOGUE[name])
        assert est.bottleneck == "memory"


def test_training_far_heavier_than_inference():
    cfg = get_config("gemma3-1b")
    t = training_cost(cfg, 8, 128)
    i = inference_cost(cfg, 8, 128)
    assert t.flops == pytest.approx(3 * i.flops)
    assert t.mem_bytes > i.mem_bytes


def test_moe_flops_use_active_params():
    kimi = get_config("kimi-k2-1t-a32b")
    assert kimi.param_count() > 15 * kimi.active_param_count()
    f = model_flops_per_token(kimi)
    assert f == 2.0 * kimi.active_param_count()


@settings(max_examples=30, deadline=None)
@given(st.floats(1e9, 1e15), st.floats(1e6, 1e12))
def test_estimate_roofline_property(flops, mem):
    """latency == max(compute, memory) and DVFS slows compute."""
    dev = DEVICE_CATALOGUE["flagship-phone"]
    cost = TaskCost(flops=flops, weight_bytes=mem, activation_bytes=0.0)
    est = estimate(cost, dev)
    assert est.latency_s == pytest.approx(
        max(est.compute_s, est.memory_s))
    slow = estimate(cost, dev, dvfs=0.5)
    assert slow.compute_s >= est.compute_s


def test_historical_estimator_converges():
    h = HistoricalEstimator(alpha=0.5)
    assert h.predict("t", "d") is None
    for _ in range(10):
        h.observe("t", "d", 2.0)
    assert h.predict("t", "d") == pytest.approx(2.0, rel=0.01)
    h.observe("t", "d", 4.0)
    assert 2.0 < h.predict("t", "d") < 4.0
