"""What the harness finds by name, and the shape ``BENCHMARK.json``
must keep: every cell's configuration and mix file, every per-layer
metric's reader, and the names, units and lists they share."""
import json
import re

import pytest

from bench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_lists_what_the_harness_found(capsys):
    found = []
    for cell in BENCH["workloads"]:
        conf = spec.load_config(cell["config"])
        mix = spec.load_traffic(cell["traffic"])
        found.append(f"{cell['name']}: config {cell['config']} "
                     f"({conf['registry_id']}), traffic {cell['traffic']} "
                     f"({mix['loop']} loop)")
    readers = spec.metric_readers([m["name"] for m in BENCH["per_layer"]])
    found += [f"metric {n}: bench/metrics/{n}.py" for n in readers]
    print("\n".join(found))
    assert len(readers) == len(BENCH["per_layer"])
    assert all(callable(r) for r in readers.values())


def test_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"])
               for m in BENCH["end_to_end"] + BENCH["per_layer"])
    assert len(json.dumps(BENCH)) < 64 * 1024
    texts = [x["why"] for x in BENCH["configs"] + BENCH["workloads"]]
    texts += [m["layer"] for m in BENCH["per_layer"]]
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_reports_what_its_metrics_move(cell):
    e2e = {m["name"] for m in spec.cell_metrics(BENCH, cell, "end_to_end")}
    layer = spec.cell_metrics(BENCH, cell, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    assert all(m["moves"] in e2e for m in layer)


def test_configuration_files_state_their_cuts():
    for c in BENCH["configs"]:
        conf = json.loads(open(spec.ROOT / c["file"]).read())
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert set(conf["published"]) >= set(conf["reduced"])
        for key in conf["reduced"]:
            assert conf[key] != conf["published"][key]
        assert not any(k.endswith(("_dim", "_rank", "_size")) or
                       k in ("num_experts_per_tok", "intermediate_size")
                       for k in conf["reduced"])
