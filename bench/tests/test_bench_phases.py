"""The idle split by the program's phases (``bench/phases.py``) on
``data/small_trace_phases.textproto``: the three steps, device ops,
submit and window of ``data/small_trace.textproto`` (see
``test_bench_trace.py``), with the engine's phase annotations on an
executor thread and the frontend's ``frontend.drain`` (around the
submit) and ``frontend.deliver`` spans.

Idle (ms after the window opens): 0-1, 9-13, 29-32, 48-50.  Phases:
step 0: admit 0-0.5, plan 0.5-1, dispatch 1-2, sync 2-9, retire 9-9.5,
publish 9.5-10; step 1: admit 12-12.25, plan 12.25-12.5, (no phase
12.5-13), dispatch 13-14, sync 14-29, retire 29-29.75, publish
29.75-30; step 2: admit 31-31.8 holding prefill_dispatch 31.2-31.6,
plan 31.8-32, dispatch 32-33, sync 33-48, retire 48-49.5, publish
49.5-50.  Frontend: drain 10-11.5, deliver 30-30.6.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import phases, spec, trace

DATA = Path(__file__).parent / "data" / "small_trace_phases.textproto"
OLD_DATA = Path(__file__).parent / "data" / "small_trace.textproto"
STEPS = [
    {"i": 0, "t0": 10.000, "t1": 10.010},
    {"i": 1, "t0": 10.012, "t1": 10.030},
    {"i": 2, "t0": 10.031, "t1": 10.050},
]
ARGS = ((10.0, 10.05), STEPS, [(10.0, 10.05)])


def _split(path):
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(path.read_text())
    parsed = trace.parse(pd)
    return (phases.split(parsed, phases.parse(pd), *ARGS),
            trace.reduce(parsed, *ARGS))


def test_idle_split_by_phase():
    got, old = _split(DATA)
    gaps = dict(got["idle_gaps"])
    want = {"in engine.admit": 1.15, "in engine.plan": 0.95,
            "in engine.retire": 2.75, "in engine.publish": 1.25,
            "in engine.prefill_dispatch": 0.4,
            "in engine.dispatch": 0.0, "in engine.sync": 0.0,
            "in engine.step (no phase)": 0.5,
            "in submit": 1.0, "in frontend.drain": 0.5,
            "in frontend.deliver": 0.6, "between steps": 0.9}
    assert set(gaps) == set(want)
    for k, v in want.items():
        assert gaps[k] == pytest.approx(v * 1e-3, abs=1e-12), k
    # the split entries add up to the unsplit ones of trace.reduce
    before = dict(old["idle_gaps"])
    assert before["in engine.step"] == pytest.approx(0.007)
    assert before["between steps"] == pytest.approx(0.002)
    in_step = sum(v for k, v in gaps.items() if k.startswith("in engine."))
    between = sum(gaps[k] for k in ("in frontend.drain",
                                    "in frontend.deliver", "between steps"))
    assert in_step == pytest.approx(before["in engine.step"])
    assert between == pytest.approx(before["between steps"])
    assert gaps["in submit"] == before["in submit"]
    assert sum(gaps.values()) == pytest.approx(old["idle_inflight_s"])


def test_host_gap_per_step():
    got, _ = _split(DATA)
    # every phase but sync: step 0 admit+plan+retire+publish, step 1 the
    # same (its no-phase half millisecond left out), step 2 with the
    # nested prefill dispatch
    assert [r["host_gap_s"] for r in got["steps"]] == pytest.approx(
        [0.002, 0.0015, 0.003])
    assert got["host_gap_ms"] == pytest.approx(6.5 / 3)


def test_trace_without_phases_reduces_as_before():
    got, old = _split(OLD_DATA)
    assert got["idle_gaps"] == old["idle_gaps"]
    assert got["host_gap_ms"] is None


def test_subtract_and_innermost():
    assert phases.subtract([(0, 10), (20, 30)], [(2, 3), (9, 21)]) == [
        (0, 2), (3, 9), (21, 30)]
    assert phases.subtract([(0, 1)], []) == [(0, 1)]
    by, left = phases.innermost(
        [(0, 10)], [("a", 1, 8), ("b", 2, 4), ("c", 8, 9)])
    assert by == pytest.approx({"a": 5, "b": 2, "c": 1})
    assert left == [(0, 1), (9, 10)]


def _pad_share(before, after):
    read = spec.metric_readers(["model.extend_pad_share"])[
        "model.extend_pad_share"]
    return read({"registry": (before, after)})


def test_extend_pad_share_reader():
    before = {"engine.extend_positions": 1024, "engine.extend_fed": 100}
    after = {"engine.extend_positions": 1024 * 5, "engine.extend_fed": 600}
    assert _pad_share(before, after) == pytest.approx(
        100 * (1 - 500 / 4096))
    # a program without the counters (or with no extend wave in the
    # window) reports nothing, and does not raise
    assert _pad_share({}, {}) is None
    assert _pad_share(before, before) is None


def test_exits_nonzero_without_a_tpu():
    root = Path(__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, str(root / "bench" / "phases.py"), "--workload",
         "granite-chat", "--seeds", "1", "--seconds", "1", "--modes", "off"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_gc_pauses_are_recorded(monkeypatch):
    monkeypatch.setattr(phases, "GC", [])
    phases._gc_pause("start", {"generation": 2})
    phases._gc_pause("stop", {"generation": 2})
    [(a, b, gen)] = phases.GC
    assert a <= b and gen == 2
