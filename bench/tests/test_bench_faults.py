"""The check fails what it must: the float8 control at smoke size, and a
run whose decode wave is broken underneath, either altering the token
where it is produced or returning the cache it was given (a step that
leaves its state unchanged)."""
import time

import pytest

from bench import harness, spec
from bench import run as runner
from bench.tests import smoke
from bench.tests.test_bench_harness import PEAK


@pytest.mark.parametrize("cell", ["granite-chat", "phi3-docqa"])
def test_control_fails_the_limit(cell):
    out = harness.run(cell, 777, 3.0, False, t_start=time.perf_counter(),
                      conf=smoke.cell_conf(cell), mix=smoke.mix(cell),
                      control=True)
    limit = smoke.cell_conf(cell)["check"]["mean_logit_gap"]
    chk = out["check"]
    assert chk["mean_logit_gap"] <= limit < chk["control_mean_logit_gap"], chk


def _altered_token(decode):
    def broken(self, *a, **kw):
        nxt, cache = decode(self, *a, **kw)
        return (nxt + 1) % self.cfg.vocab_size, cache
    return broken


def _state_unchanged(decode):
    def broken(self, params, cache, *a, **kw):
        nxt, _ = decode(self, params, cache, *a, **kw)
        return nxt, cache                  # the wave's K/V never lands
    return broken


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged])
def test_fault_in_the_decode_wave_is_caught(monkeypatch, fault):
    from repro.serving.engine import EdgeServingEngine
    monkeypatch.setattr(EdgeServingEngine, "_decode_fn",
                        fault(EdgeServingEngine._decode_fn))
    cell = "granite-chat"
    bench = spec.load_benchmark()
    out = harness.run(cell, 99, 3.0, False, t_start=time.perf_counter(),
                      conf=smoke.cell_conf(cell), mix=smoke.mix(cell))
    res = runner.result(bench, spec.find_cell(bench, cell), out, PEAK, False)
    assert not res["correct"], res["checks"]
