#!/usr/bin/env python3
"""Device idle time put down to the program's own host phases.

With tracing on, the engine enters each phase span as a profiler
annotation (``engine.admit``, ``engine.plan``, ``engine.dispatch``,
``engine.sync``, ``engine.retire``, ``engine.publish``, ... inside
``engine.step``), and the frontend its ``frontend.drain`` and
``frontend.deliver``.  ``split`` puts each idle gap of the profiler
window (idle while a request is in flight, as ``bench/trace.py``
reduces it) down to the innermost phase it falls in:

* ``idle_gaps``: ``trace.reduce``'s ``in engine.step`` entry split into
  one entry per engine phase plus ``in engine.step (no phase)``, and
  its ``between steps`` into ``in frontend.deliver``, ``in
  frontend.drain`` and the rest.  The entries sum to the same idle
  total; a trace with no such events gives ``trace.reduce``'s entries;
* ``host_gap_s`` of each whole step: the idle inside its own phases
  other than ``engine.sync``; ``host_gap_ms`` is their mean.

The benchmark's result line does not carry these yet: that takes an
edit of ``bench/trace.py``, a change of the benchmark of its own.  Until
then this script runs cells on the chip and prints them:

    python3 bench/phases.py --workload granite-chat --seeds 1,2,3 \\
        --seconds 30 --modes off,trace,profile

One process per configuration (cells of one configuration may be
listed, comma-separated).  ``off`` serves with tracing off, ``trace``
with ``ServeConfig.trace=True`` and no profiler (what tracing costs),
``profile`` adds the benchmark's profiler window and prints the split.
Each line also gives the longest garbage collection inside the window
(``gc_max_ms``), which shows a host stall as device idle.  One JSON line
per (mode, cell, seed), naming the device; no reference
check is run.  Exits non-zero, as ``bench/run.py`` does, when JAX finds
no TPU or a device kind that ``bench/peaks.json`` lacks.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import os
import statistics
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import trace  # noqa: E402
from bench.trace import clip, intersect, length, union  # noqa: E402

PREFIXES = ("engine.", "frontend.")
STEP, SYNC = "engine.step", "engine.sync"


def parse(pd) -> list:
    """The host's ``engine.*`` and ``frontend.*`` events of a
    ``jax.profiler.ProfileData``: ``(name, start_s, end_s)``."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    t = ev.start_ns * 1e-9
                    out.append((ev.name, t, t + ev.duration_ns * 1e-9))
    return out


def subtract(x, y) -> list:
    """The parts of ``x`` that ``y`` leaves uncovered (both sorted and
    disjoint, as ``union`` returns them)."""
    out, j = [], 0
    for a, b in x:
        while j < len(y) and y[j][1] <= a:
            j += 1
        t, k = a, j
        while k < len(y) and y[k][0] < b:
            if y[k][0] > t:
                out.append((t, y[k][0]))
            t = max(t, y[k][1])
            k += 1
        if t < b:
            out.append((t, b))
    return out


def innermost(left, spans) -> tuple:
    """Put each part of ``left`` down to the innermost of the nested
    ``spans`` that covers it: ``({name: seconds}, what no span
    covers)``."""
    levels: dict = {}
    open_ends: list = []
    for name, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while open_ends and open_ends[-1] <= a:
            open_ends.pop()
        levels.setdefault(len(open_ends), {}).setdefault(
            name, []).append((a, b))
        open_ends.append(b)
    out: dict = {}
    for depth in sorted(levels, reverse=True):
        for name, iv in levels[depth].items():
            got = intersect(left, union(iv))
            out[name] = out.get(name, 0.0) + length(got)
            left = subtract(left, union(got))
    return out, left


def split(parsed: dict, phases: list, window: tuple, steps: list,
          inflight: list) -> dict:
    """``parsed`` is ``trace.parse``'s, ``phases`` this module's
    ``parse``; ``window``, ``steps`` and ``inflight`` as
    ``trace.reduce`` takes them.  The clock offset, busy and idle time
    are found as ``trace.reduce`` finds them."""
    step_spans = {int(s[3]["i"]): s for s in parsed["spans"]
                  if s[0] == "bench.step" and "i" in s[3]}
    offs = [step_spans[r["i"]][1] - r["t0"] for r in steps
            if r["i"] in step_spans]
    if not offs:
        return {}
    off = statistics.median(offs)
    w0, w1 = window[0] + off, window[1] + off
    busy = union(clip([(a, b) for _, a, b in parsed["ops"]], w0, w1))
    flight = union(clip([(a + off, b + off) for a, b in inflight], w0, w1))
    idle = union(trace.gaps(busy, flight))

    def spans(name):
        return union([(s[1], s[2]) for s in parsed["spans"]
                      if s[0] == name])

    in_step = intersect(idle, spans("bench.step"))
    in_submit = intersect(idle, spans("bench.submit"))
    between = subtract(subtract(idle, spans("bench.step")),
                       spans("bench.submit"))
    engine = [p for p in phases
              if p[0].startswith("engine.") and p[0] != STEP]
    frontend = [p for p in phases if p[0].startswith("frontend.")]
    by_phase, no_phase = innermost(in_step, engine)
    by_front, rest = innermost(between, frontend)
    gaps = {f"in {n}": v for n, v in {**by_phase, **by_front}.items()}
    gaps["in engine.step (no phase)" if engine else "in engine.step"] = (
        length(no_phase))
    gaps["in submit"] = length(in_submit)
    gaps["between steps"] = length(idle) - sum(gaps.values())

    host = intersect(idle, union([(a, b) for n, a, b in engine
                                  if n != SYNC]))
    rows = []
    for r in steps:
        a, b = r["t0"] + off, r["t1"] + off
        if a >= w0 and b <= w1:
            rows.append({"i": r["i"],
                         "host_gap_s": length(clip(host, a, b))})
    return {
        "idle_gaps": sorted(gaps.items(), key=lambda kv: -kv[1]),
        # a window that lost device events shows as few ops and one
        # long idle gap while the device computes a wave
        "device_ops": sum(1 for _, a, b in parsed["ops"]
                          if a < w1 and b > w0),
        "longest_idle_s": max((b - a for a, b in idle), default=0.0),
        "steps": rows,
        "host_gap_ms": (1e3 * statistics.mean(r["host_gap_s"]
                                              for r in rows)
                        if rows and engine else None),
    }


def load(prof: dict, steps: list, recs: list) -> dict:
    """``trace.load``'s reduction of the profile under ``prof["dir"]``,
    with this module's ``split`` of it under ``"phases"``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(prof["dir"], "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return {}
    pd = ProfileData.from_file(paths[0])
    parsed = trace.parse(pd)
    inflight = [(r.submit, r.stamps[-1]) for r in recs if r.stamps]
    window = (prof["t_started"], prof["t_stop"])
    out = trace.reduce(parsed, window, steps, inflight)
    out["phases"] = split(parsed, parse(pd), window, steps, inflight)
    return out


GC: list = []            # (start, stop, generation) of each collection


def _gc_pause(phase: str, info: dict, start=[0.0]) -> None:
    if phase == "start":
        start[0] = time.perf_counter()
    else:
        GC.append((start[0], time.perf_counter(), info["generation"]))


def row(rec: dict, seconds: float) -> dict:
    """What one window printed: the end-to-end numbers and, from a
    profiled window, the idle split and the extend waves' padding with
    its base."""
    from bench import harness
    e2e = harness.end_to_end(rec["requests"], rec["window"][0], seconds,
                             0.0)
    out = {k: e2e[k] for k in ("ttft_p95_ms", "itl_p95_ms",
                               "output_tok_s")}
    out["failed"] = e2e["_failed"]
    # a host stall seen as idle: the longest garbage collection inside
    # the window, which holds every thread of the process
    w0, w1 = rec["window"]
    pauses = [(b - a, g) for a, b, g in GC if a < w1 and b > w0]
    out["gc_max_ms"], out["gc_max_generation"] = (
        (1e3 * max(pauses)[0], max(pauses)[1]) if pauses else (0.0, None))
    before, after = rec["registry"]
    for key in ("engine.extend_positions", "engine.extend_fed"):
        out[key] = after.get(key, 0) - before.get(key, 0)
    p = rec["profile"]
    if p and p.get("phases"):
        ph = p["phases"]
        gaps = dict(ph["idle_gaps"])
        in_step = sum(v for k, v in gaps.items()
                      if k.startswith("in engine."))
        no_phase = gaps.get("in engine.step (no phase)", 0.0)
        out.update(busy_s=p["busy_s"], window_s=p["window_s"],
                   device_ops=ph["device_ops"],
                   longest_idle_ms=1e3 * ph["longest_idle_s"],
                   idle_inflight_s=p["idle_inflight_s"],
                   whole_steps=len(ph["steps"]),
                   host_gap_ms=ph["host_gap_ms"],
                   no_phase_share=(100.0 * no_phase / in_step
                                   if in_step and ph["host_gap_ms"]
                                   is not None else None),
                   idle_gaps=ph["idle_gaps"])
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True,
                    help="cells of one configuration, comma-separated")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--modes", default="off,trace,profile")
    args = ap.parse_args()
    import jax
    from bench import harness, spec
    from bench.run import BENCH_DIR, enable_compile_cache, fail
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found (JAX platform is {dev.platform!r}); the "
             "phase split is a device measurement")
    peaks = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if dev.device_kind not in peaks:
        fail(f"device kind {dev.device_kind!r} is not in bench/peaks.json")
    enable_compile_cache()
    # patches the harness: it reduces a profiled window through
    # ``trace.load``, and this script's ``load`` also splits it by
    # phase.  Goes when ``trace.reduce`` does the split itself.
    trace.load = load
    gc.callbacks.append(_gc_pause)
    bench = spec.load_benchmark()
    cells = [spec.find_cell(bench, n) for n in args.workload.split(",")]
    conf = spec.load_config(cells[0]["config"])
    seeds = [int(s) for s in args.seeds.split(",")]
    for mode in args.modes.split(","):
        server = harness.Server(conf, seeds[0], trace=mode != "off")
        for cell in cells:
            mix = spec.load_traffic(cell["traffic"])
            for seed in seeds:
                t = time.perf_counter()
                server.reseed(seed)
                rec = server.window(mix, seed, args.seconds,
                                    trace=mode == "profile")
                print(json.dumps({"mode": mode, "workload": cell["name"],
                                  "seed": seed,
                                  "device_kind": dev.device_kind,
                                  **row(rec, args.seconds),
                                  "wall_s": time.perf_counter() - t}),
                      flush=True)
        server.drop_weights()
        server.eng.cache = None
        del server
        gc.collect()


if __name__ == "__main__":
    main()
