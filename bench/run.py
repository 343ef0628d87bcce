#!/usr/bin/env python3
"""Run one cell of the serving benchmark once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with a TPU.  Exits non-zero
and prints no result when JAX finds no TPU, fewer chips than the cell
asks for, or no program beside the benchmark.  ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from
a traced run.  Earlier lines (standard error) give the split of the
set-up, compiles inside the window and the generator's lateness; the
last line of standard output is the result object, and the numbers
compared against the reference come last on both streams.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def fail(msg: str, code: int = 3):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache in ``.jax_cache`` at the root
    of the checkout, a fixed path (it is part of the cache's key), with
    every program cached however quickly it compiled."""
    import jax
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro" / "serving").is_dir():
        fail("no program beside the benchmark (src/repro is missing)")
    from bench import spec
    bench = spec.load_benchmark(ROOT)
    cell = spec.find_cell(bench, args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found (JAX platform is {devs[0].platform!r}); the "
             "benchmark runs only on the chip")
    if len(devs) < cell["chips"]:
        fail(f"cell {cell['name']} needs {cell['chips']} chips, JAX sees "
             f"{len(devs)}")
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if devs[0].device_kind not in peaks:
        fail(f"device kind {devs[0].device_kind!r} is not in "
             "bench/peaks.json")
    cache = enable_compile_cache()
    print(f"bench: {cell['name']} on {devs[0].device_kind} x{len(devs)}; "
          f"compile cache {cache}", file=sys.stderr, flush=True)

    from bench import harness
    out = harness.run(cell["name"], args.seed, args.seconds,
                      bool(args.trace), t_start=T_START, bench=bench)
    print(json.dumps(result(bench, cell, out, peaks[devs[0].device_kind],
                            bool(args.trace))), flush=True)


def result(bench: dict, cell: dict, out: dict, peak: dict,
           trace: bool) -> dict:
    """The result object: ``correct``, ``attempted``, ``failed``, the
    cell's end-to-end (untraced) or per-layer (traced) metrics, the
    device, the breakdown of a traced run, and last the numbers compared
    with their limits."""
    from bench import spec
    conf, e2e = out["record"]["conf"], out["e2e"]
    if trace:
        wanted = spec.cell_metrics(bench, cell["name"], "per_layer")
        readers = spec.metric_readers([m["name"] for m in wanted])
        rec = dict(out["record"], peaks=peak)
        values = {m["name"]: readers[m["name"]](rec) for m in wanted}
    else:
        wanted = spec.cell_metrics(bench, cell["name"], "end_to_end")
        values = {m["name"]: e2e.get(spec.quantity(m["name"]))
                  for m in wanted}
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if values[m["name"]] is not None}
    chk = out["check"]
    limit = conf["check"]["mean_logit_gap"]
    checks = {"mean_logit_gap": {"value": chk["mean_logit_gap"],
                                 "limit": limit},
              "compared_tokens": {"value": chk["tokens"], "limit": 1}}
    res = {"correct": bool(chk["tokens"] >= 1
                           and chk["mean_logit_gap"] <= limit),
           "attempted": e2e["_attempted"], "failed": e2e["_failed"],
           "metrics": metrics, "device": dict(out["device"])}
    if trace:
        prof = out["record"].get("profile") or {}
        res["device"].update(busy_s=prof.get("busy_s", 0.0),
                             window_s=prof.get("window_s", 0.0))
        res["breakdown"] = {"device_ops": prof.get("device_ops", []),
                            "idle_gaps": prof.get("idle_gaps", [])}
    res["checks"] = checks
    print(f"reference check took {out['check_s']:.3f} s over "
          f"{chk['requests']} requests; widest gap {chk['max_logit_gap']}, "
          f"share not the reference's first choice "
          f"{chk['mismatch_share']}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return res


if __name__ == "__main__":
    main()
