#!/usr/bin/env python3
"""Readings that set a cell's limit: the served tokens' widest logit gap
below the reference's best on many seeds, and the float8 control's on
some of them, at the cell's own size and load.

    python3 bench/readings.py --workload granite-chat --seeds 1,2,3 \
        --control 1,2,3 --seconds 15

Run on the chip.  One process: the programs are built and warmed once,
and each seed brings its own weights and traffic.  One JSON line per
seed.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import harness, spec  # noqa: E402
from bench.run import enable_compile_cache  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    enable_compile_cache()
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    conf = spec.load_config(cell["config"])
    mix = spec.load_traffic(cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control.split(",") if s}
    server = harness.Server(conf, seeds[0])
    for seed in seeds:
        t = time.perf_counter()
        server.reseed(seed)
        rec = server.window(mix, seed, args.seconds)
        server.drop_weights()
        picked = harness.sample(rec["requests"], seed,
                                conf["check"]["tokens"])
        chk = harness.logit_gaps(conf, seed, picked, control=seed in control)
        e2e = harness.end_to_end(rec["requests"], rec["window"][0],
                                 args.seconds, 0.0)
        print(json.dumps({"workload": cell["name"], "seed": seed, **chk,
                          "ttft_p95_ms": e2e["ttft_p95_ms"],
                          "output_tok_s": e2e["output_tok_s"],
                          "failed": e2e["_failed"],
                          "wall_s": time.perf_counter() - t}), flush=True)


if __name__ == "__main__":
    main()
