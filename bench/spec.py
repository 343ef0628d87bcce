"""Find a cell's files by name and turn them into the program's configs.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
configuration lives in ``bench/configs/<config>.json``, its layers in
``bench/arch/<model_type>.py``, the mix in
``bench/traffic/<traffic>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  Nothing here knows a cell or an
architecture by name, so a later change adds a cell, a configuration, an
architecture, a mix or a metric by adding files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

from bench import arch

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    known = [c["name"] for c in bench["workloads"]]
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: {known}")


def load_config(name: str) -> dict:
    """The configuration file ``bench/configs/<name>.json``."""
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


def load_traffic(name: str) -> dict:
    """The traffic mix file ``bench/traffic/<name>.json``."""
    return json.loads((BENCH_DIR / "traffic" / f"{name}.json").read_text())


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registry entry named by ``registry_id``, with every published size
    of the file written over it (by the architecture's
    ``program_fields``) and then the file's ``program`` fields
    (settings of the program with no published key)."""
    from repro.configs import get_config
    from repro.configs.base import ModelConfig

    kw = arch.module(conf).program_fields(conf)
    kw.update(conf.get("program", {}))
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(kw) - known)
    if unknown:
        raise KeyError(f"ModelConfig has no field(s) {unknown}")
    return get_config(conf["registry_id"]).replace(**kw)


def serve_config(conf: dict, **extra):
    """``ServeConfig`` from the file's ``serve`` fields.  A field the
    program no longer has is reported and left to the program's own
    default (returned as the second value), so a change that removes a
    setting does not break the benchmark."""
    from repro.serving import ServeConfig

    fields = {f.name for f in dataclasses.fields(ServeConfig)}
    kw = dict(conf.get("serve", {}))
    kw.update(extra)
    dropped = sorted(k for k in kw if k not in fields)
    kw = {k: (tuple(v) if isinstance(v, list) else v)
          for k, v in kw.items() if k in fields}
    return ServeConfig(**kw), dropped


def metric_readers(names) -> dict:
    """``{name: read}`` for each per-layer metric, loaded from
    ``bench/metrics/<name>.py``."""
    out = {}
    for name in names:
        path = BENCH_DIR / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{len(out)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[name] = mod.read
    return out


def quantity(name: str) -> str:
    """What an end-to-end metric reads: ``itl_p95_ms.cold`` is the
    harness's ``itl_p95_ms`` in the cells it lists, under a bound of its
    own, so cells that spread differently can hold one quantity to
    different bounds."""
    return name.split(".", 1)[0]


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell``
    reports: those that list it, or list no cells at all."""
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]
