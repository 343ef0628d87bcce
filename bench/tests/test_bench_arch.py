"""A new architecture needs new files only.  ``data/moe_dense_first.py``
and ``data/moe-dense-first.json`` add a layout that the program's
``moe`` family runs and the shared files know nothing of (a dense first
layer, then expert layers with a shared expert, weights drawn a layer at
a time); put where ``bench/arch`` finds it, it runs through
``harness.run`` at smoke size on the CPU and comes out correct, and its
float8 control does not."""
import importlib.util
import json
import sys
import time
from pathlib import Path

import pytest

from bench import arch, harness, spec
from bench import run as runner
from bench.tests import smoke
from bench.tests.test_bench_harness import PEAK

DATA = Path(__file__).parent / "data"
TYPE = "moe_dense_first"


@pytest.fixture
def conf(monkeypatch):
    name = f"bench.arch.{TYPE}"
    found = importlib.util.spec_from_file_location(name, DATA / f"{TYPE}.py")
    mod = importlib.util.module_from_spec(found)
    found.loader.exec_module(mod)
    monkeypatch.setitem(sys.modules, name, mod)
    return smoke.shrink(json.loads((DATA / "moe-dense-first.json")
                                   .read_text()))


def test_new_architecture_runs_correct_and_control_fails(conf):
    cell = "granite-chat"          # its mix; the configuration is the new one
    bench = spec.load_benchmark()
    out = harness.run(cell, 4294967377, 3.0, False,
                      t_start=time.perf_counter(), bench=bench, conf=conf,
                      mix=smoke.mix(cell), control=True)
    res = runner.result(bench, spec.find_cell(bench, cell), out, PEAK, False)
    chk, limit = out["check"], conf["check"]["mean_logit_gap"]
    assert out["compiles_in_window"] == 0
    assert res["correct"] and res["failed"] == 0, res["checks"]
    assert chk["control_mean_logit_gap"] > limit, chk
    assert out["record"]["steps"] and chk["tokens"] > 0


def test_program_tree_has_the_new_layers(conf):
    from bench import weights
    params = weights.program_params(conf, spec.model_config(conf), 5)
    assert params["dense_layers"]["mlp"]["w_gate"].shape == (1, 128, 256)
    assert params["moe_layers"]["moe"]["shared"]["w_down"].shape == (2, 64,
                                                                     128)


def test_unknown_model_type_names_the_known_modules():
    with pytest.raises(KeyError, match="granitemoe.*phi3"):
        arch.module({"model_type": "no_such_model"})
