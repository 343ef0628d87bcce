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


def test_end_to_end_metrics_read_what_the_harness_measures():
    from bench import harness
    got = harness.end_to_end([], 0.0, 1.0, 1.0)
    assert spec.quantity("itl_p95_ms.cold") == "itl_p95_ms"
    assert all(spec.quantity(m["name"]) in got for m in BENCH["end_to_end"])


# the chip's-share cut: depth, the experts held and the vocabulary's
# slice may be cut, with floors; a width never is
EXPERT_COUNTS = ("num_local_experts", "n_routed_experts", "num_experts")
LEAST_EXPERTS = 8
LEAST_VOCAB_SHARE = 8   # at least 1/8 of the published vocabulary


def is_width(key: str) -> bool:
    return (key in ("hidden_size", "num_experts_per_tok")
            or key.endswith(("_dim", "_rank", "intermediate_size")))


def cut_faults(conf: dict) -> list[str]:
    """What breaks the cut rules in a configuration file: a reduced key
    that is a width, that is not stated as published or not changed, a
    vocabulary below an eighth of the published one, an expert count
    below 8, or cuts with no ``deployment`` stated."""
    faults = []
    for key in conf["reduced"]:
        if key not in conf.get("published", {}):
            faults.append(f"{key}: no published value")
            continue
        was, now = conf["published"][key], conf[key]
        if now == was:
            faults.append(f"{key}: listed as reduced, not changed")
        if is_width(key):
            faults.append(f"{key}: a width is never reduced")
        elif key == "vocab_size" and now * LEAST_VOCAB_SHARE < was:
            faults.append(f"vocab_size {now}: under an eighth of {was}")
        elif key in EXPERT_COUNTS and now < LEAST_EXPERTS:
            faults.append(f"{key} {now}: fewer than {LEAST_EXPERTS}")
    if conf["reduced"] and not conf.get("deployment"):
        faults.append("reduced keys but no deployment stated")
    return faults


def test_configuration_files_state_their_cuts():
    for c in BENCH["configs"]:
        conf = json.loads(open(spec.ROOT / c["file"]).read())
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert cut_faults(conf) == [], c["name"]


# (key, published, as cut, passes): Kimi-K2-Instruct's published sizes
CUTS = [
    ("num_hidden_layers", 61, 5, True),
    ("vocab_size", 163840, 20480, True),
    ("vocab_size", 163840, 20479, False),
    ("n_routed_experts", 384, 8, True),
    ("n_routed_experts", 384, 4, False),
    ("num_local_experts", 32, 8, True),
    ("hidden_size", 7168, 3584, False),
    ("intermediate_size", 18432, 9216, False),
    ("moe_intermediate_size", 2048, 1024, False),
    ("kv_lora_rank", 512, 256, False),
    ("qk_rope_head_dim", 64, 32, False),
    ("num_experts_per_tok", 8, 4, False),
]


@pytest.mark.parametrize("key,was,now,passes", CUTS)
def test_cut_rule(key, was, now, passes):
    conf = {key: now, "reduced": [key], "published": {key: was},
            "deployment": "each layer divided over 48 chips"}
    assert (cut_faults(conf) == []) == passes, cut_faults(conf)


def test_cut_rule_needs_a_deployment():
    conf = {"vocab_size": 20480, "reduced": ["vocab_size"],
            "published": {"vocab_size": 163840}}
    assert cut_faults(conf) == ["reduced keys but no deployment stated"]
    assert cut_faults(dict(conf, reduced=[])) == []
