"""The trace reduction on a small trace written out by hand
(``data/small_trace.textproto``): three engine steps in a 50 ms window,
two decode waves that call the paged kernel and one extend wave.

Device (ms after the window opens): decode 1-9 (kernel 2-4), extend
13-29, decode 32-48 (kernel 32-36); a while loop holds the first decode
wave's ops.  Host: steps 0-10, 12-30, 31-50; a submit 10-11.  One
request is in flight through the whole window.
"""
from pathlib import Path

import pytest

from bench import flops, spec, trace
from bench.spec import metric_readers

DATA = Path(__file__).parent / "data" / "small_trace.textproto"
OFFSET = 5.0                    # trace clock = perf_counter + 5 s
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    parsed = trace.parse(ProfileData.from_text_proto(DATA.read_text()))
    steps = [
        {"i": 0, "t0": 10.000, "t1": 10.010, "n": 2, "kind": "decode",
         "flops": 10**9, "ctxs": [100, 200]},
        {"i": 1, "t0": 10.012, "t1": 10.030, "n": 2, "kind": "extend",
         "flops": 4 * 10**9, "ctxs": []},
        {"i": 2, "t0": 10.031, "t1": 10.050, "n": 2, "kind": "decode",
         "flops": 10**9, "ctxs": [101, 201]},
    ]
    return trace.reduce(parsed, (10.0, 10.05), steps, [(10.0, 10.05)])


def test_busy_idle_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(0.050)
    assert reduced["busy_s"] == pytest.approx(0.040)
    assert reduced["inflight_s"] == pytest.approx(0.050)
    assert reduced["idle_inflight_s"] == pytest.approx(0.010)
    gaps = dict(reduced["idle_gaps"])
    assert gaps["in engine.step"] == pytest.approx(0.007)
    assert gaps["in submit"] == pytest.approx(0.001)
    assert gaps["between steps"] == pytest.approx(0.002)


def test_programs_kernel_and_ops(reduced):
    assert sorted(reduced["programs"]["decode"]) == pytest.approx(
        [0.008, 0.016])
    assert reduced["programs"]["extend"] == pytest.approx([0.016])
    kern = [s["kernel_s"] for s in reduced["steps"]]
    assert kern == pytest.approx([0.002, 0.0, 0.004])
    ops = dict(reduced["device_ops"])
    assert "%while.1" not in ops
    assert ops["%fusion.1"] == pytest.approx(0.017)
    assert ops["%paged_attention.3"] == pytest.approx(0.006)


def test_readers(reduced):
    bench = spec.load_benchmark()
    conf = spec.load_config("granite-moe-1b-a400m")
    rec = {"profile": reduced, "conf": conf, "peaks": PEAK}
    r = metric_readers([m["name"] for m in bench["per_layer"]])
    assert r["device.idle_share"](rec) == pytest.approx(20.0)
    assert r["model.decode_wave_ms"](rec) == pytest.approx(12.0)
    assert r["model.prefill_share"](rec) == pytest.approx(40.0)
    assert r["step_mfu"](rec) == pytest.approx(
        100 * 6e9 / (0.047 * 197e12))
    L = conf["num_hidden_layers"]
    least = 0.0
    for ctxs in ([100, 200], [101, 201]):
        fl, by = flops.paged_attention_call(conf, ctxs)
        least += L * max(fl / 197e12, by / 819e9)
    assert r["paged_attention_roofline"](rec) == pytest.approx(
        100 * least / 0.006)
