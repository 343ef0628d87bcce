"""Smoke-size copies of the benchmark's configurations and mixes, for
running the harness on the CPU in tests: every width shrunk (by each
architecture's ``smoke``, ``bench/arch``), the same code paths (paged
pool, prefix cache, chunked prefill, Pallas decode kernel in interpret
mode)."""
from __future__ import annotations

import copy

from bench import arch, spec


def conf(name: str) -> dict:
    return shrink(spec.load_config(name))


def shrink(conf: dict) -> dict:
    """The architecture's own CPU shrink (``smoke`` of its module), with
    a pool of 4 slots of 256 positions and 16-token chunks."""
    c = arch.module(conf).smoke(conf)
    c["serve"].update(max_slots=4, max_len=256, catch_chunk=16)
    return c


def mix(cell: str, rate: float = 3.0) -> dict:
    bench = spec.load_benchmark()
    m = copy.deepcopy(spec.load_traffic(spec.find_cell(bench, cell)["traffic"]))
    for k in ("question", "answer", "document"):
        if k in m:
            m[k]["max"] = min(m[k]["max"], 40)
            m[k]["min"] = min(m[k]["min"], 8)
    if "shared_prefixes" in m:
        m["shared_prefixes"]["tokens"] = 24
    m["max_total_tokens"] = 255
    if m["loop"] == "open":
        m["rate_per_s"] = rate
    else:
        m["clients"] = 4
    return m


def cell_conf(cell: str) -> dict:
    return conf(spec.find_cell(spec.load_benchmark(), cell)["config"])
