"""A whole run at smoke size on the CPU, the chip check skipped: the
served tokens pass the reference check in every cell, and the result
line carries what the contract asks for."""
import json
import time

import pytest

from bench import harness, spec
from bench import run as runner
from bench.tests import smoke

CELLS = [c["name"] for c in spec.load_benchmark()["workloads"]]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run_cell(cell, seed, trace=False, **kw):
    out = harness.run(cell, seed, 3.0, trace, t_start=time.perf_counter(),
                      conf=smoke.cell_conf(cell), mix=smoke.mix(cell), **kw)
    return out, runner.result(spec.load_benchmark(),
                              spec.find_cell(spec.load_benchmark(), cell),
                              out, PEAK, trace)


@pytest.mark.parametrize("cell", CELLS)
def test_smoke_run_is_correct(cell):
    out, res = run_cell(cell, 4294967311)
    assert out["compiles_in_window"] == 0
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {
        m["name"] for m in spec.cell_metrics(spec.load_benchmark(), cell,
                                             "end_to_end")}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    json.dumps(res)


def test_traced_smoke_run_reports_per_layer_metrics():
    cell = "granite-chat"
    out, res = run_cell(cell, 12345, trace=True)
    assert res["correct"]
    got = set(res["metrics"])
    # the CPU has no device plane: only the host-side readers find data
    assert {"frontend.inbox_wait_ms", "scheduler.queue_wait_ms",
            "scheduler.batch_occupancy", "prefix_cache.hit_share"} <= got
    assert 0 < res["metrics"]["scheduler.batch_occupancy"]["value"] <= 100
    assert "breakdown" in res and "busy_s" in res["device"]
    steps = out["record"]["steps"]
    assert {s["kind"] for s in steps} == {"decode", "extend"}


def test_closed_loop_cuts_at_the_window_end():
    """The closed loop of ``nightly-summaries`` (its cell is held back,
    see PERF.md): clients keep the queue full, what is in flight at the
    window's end is cut, and every finished request passes the check."""
    m = smoke.mix("granite-chat")
    closed = spec.load_traffic("nightly-summaries")
    m.update(loop="closed", clients=4, requests=closed["requests"],
             pool_seed=closed["pool_seed"])
    out = harness.run("granite-chat", 31, 3.0, False,
                      t_start=time.perf_counter(),
                      conf=smoke.cell_conf("granite-chat"), mix=m)
    recs = out["record"]["requests"]
    assert out["check"]["mean_logit_gap"] <= 0.002
    assert any(r.cut for r in recs) and not any(r.failed for r in recs)
    assert out["e2e"]["_failed"] == 0 and out["e2e"]["output_tok_s"] > 0
