#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest rate at which 90% of the
requests due in the window meet both latency limits of the mix
(``slo``: time to first token of ``ttft_ms`` plus ``ttft_ms_per_prompt_
token`` per prompt token, and a mean gap between tokens of at most
``itl_mean_ms``) with no growing backlog.

    python3 bench/sweep.py --workload granite-chat --rates 0.5,1,1.5 \
        --seconds 30 --seed 1

Run on the chip, once, when a cell is defined; its rate then goes into
the mix file as a number.  One process serves every rate (and every
listed workload of the same configuration) from one set-up.  One JSON
line per (workload, rate).
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

ATTAINMENT = 0.9
STOP = 0.5              # a rate this far past the knee ends the sweep
DRAIN_S = 15.0          # a request unfinished this long after the window
                        # has missed its limits anyway


def judge(rec: dict, slo: dict, T: float) -> dict:
    t0 = rec["window"][0]
    due = [r for r in rec["requests"] if t0 <= r.due < t0 + T]
    met, ttfts = 0, []
    for r in due:
        if not r.done or r.failed or not r.stamps:
            ttfts.append(float("inf"))
            continue
        ttft = (r.stamps[0] - r.due) * 1e3
        ttfts.append(ttft)
        gaps = [b - a for a, b in zip(r.stamps, r.stamps[1:])]
        itl = 1e3 * sum(gaps) / len(gaps) if gaps else 0.0
        limit = slo["ttft_ms"] + slo["ttft_ms_per_prompt_token"] * len(r.prompt)
        met += ttft <= limit and itl <= slo["itl_mean_ms"]
    third = max(1, len(ttfts) // 3)
    first, last = sorted(ttfts[:third]), sorted(ttfts[-third:])
    growing = last[len(last) // 2] > 2 * max(first[len(first) // 2], 1.0)
    e2e = harness.end_to_end(rec["requests"], t0, T, 0.0)
    return {"requests": len(due), "attainment": met / max(len(due), 1),
            "growing_backlog": bool(growing),
            "ttft_p50_ms": harness.pct([x for x in ttfts
                                        if x != float("inf")], 50),
            "ttft_p95_ms": e2e["ttft_p95_ms"], "itl_p95_ms": e2e["itl_p95_ms"],
            "output_tok_s": e2e["output_tok_s"], "drain_s": rec["drain_s"]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True,
                    help="cells of one configuration, comma-separated")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()
    bench = spec.load_benchmark()
    cells = [spec.find_cell(bench, n) for n in args.workload.split(",")]
    conf = spec.load_config(cells[0]["config"])
    server = harness.Server(conf, args.seed)
    for cell in cells:
        mix = spec.load_traffic(cell["traffic"])
        for rate in [float(r) for r in args.rates.split(",")]:
            t = time.perf_counter()
            rec = server.window(dict(mix, rate_per_s=rate), args.seed,
                                args.seconds, drain_s=DRAIN_S)
            row = {"workload": cell["name"], "rate_per_s": rate,
                   **judge(rec, mix["slo"], args.seconds),
                   "wall_s": time.perf_counter() - t}
            print(json.dumps(row), flush=True)
            if row["attainment"] < STOP:
                break                   # far past the knee: stop this mix


if __name__ == "__main__":
    main()
