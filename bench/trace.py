"""From the profiler's trace to the numbers the per-layer metrics read.

``parse`` takes the device planes' ops and programs (XLA modules) and
the host's ``bench.*`` spans out of a ``jax.profiler.ProfileData``;
``reduce`` puts the client's and the step log's ``perf_counter`` stamps
on the trace's clock (through the ``bench.step`` spans, which carry the
step index) and works out:

* busy time: the union of the device's op intervals in the window;
* in flight: the union of the intervals from a request's submit to its
  last token; the idle share is taken over that part of the window only,
  so it does not rise when the server gets faster and waits for work;
* each whole step inside the window with its device time, the time of
  the paged decode kernel's events inside it, and the program (decode
  or extend wave) that ran;
* the breakdown: the device ops that took most time (loops and calls
  that hold other ops left out), and the idle gaps by what the host was
  doing (inside a step, in a submit, or between steps).

Op names are the HLO instruction's name (``%paged_attention.9``, the
custom call of the Pallas decode kernel, or ``%fusion.12``).
"""
from __future__ import annotations

import glob
import os
import statistics

KERNEL = "paged"        # the paged decode kernel's event name holds this
DECODE_PROGRAM = "_decode_fn"
EXTEND_PROGRAM = "_extend_fn"
CONTAINERS = ("%while", "%conditional", "%call")   # ops that hold others


def parse(pd) -> dict:
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dst is None:
                    continue
                for ev in line.events:
                    t = ev.start_ns * 1e-9
                    dst.append((ev.name.split(" = ")[0], t,
                                t + ev.duration_ns * 1e-9))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        t = ev.start_ns * 1e-9
                        spans.append((ev.name, t, t + ev.duration_ns * 1e-9,
                                      dict(ev.stats)))
    return {"ops": ops, "modules": modules, "spans": spans}


def union(iv) -> list:
    out = []
    for a, b in sorted(iv):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(iv, lo, hi) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def length(iv) -> float:
    return sum(b - a for a, b in iv)


def intersect(x, y) -> list:
    """Intersection of two unions of intervals."""
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if a < b:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy, inside) -> list:
    """The parts of ``inside`` that ``busy`` leaves uncovered."""
    out = []
    for lo, hi in inside:
        t = lo
        for a, b in clip(busy, lo, hi):
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if t < hi:
            out.append((t, hi))
    return out


def reduce(parsed: dict, window: tuple, steps: list, inflight: list,
           top: int = 10) -> dict:
    """``window``, ``steps`` (each with ``i``, ``t0``, ``t1``) and
    ``inflight`` intervals are on the ``perf_counter`` clock."""
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
    idle = gaps(busy, flight)

    kernel = [(a, b) for n, a, b in parsed["ops"] if KERNEL in n]
    whole = []
    for r in steps:
        a, b = r["t0"] + off, r["t1"] + off
        if a < w0 or b > w1:
            continue
        row = dict(r, dur=b - a,
                   kernel_s=sum(min(y, b) - max(x, a) for x, y in kernel
                                if y > a and x < b))
        whole.append(row)

    by_op: dict = {}
    for n, a, b in parsed["ops"]:
        if a >= w0 and b <= w1 and not n.startswith(CONTAINERS):
            by_op[n] = by_op.get(n, 0.0) + (b - a)
    host = {"in engine.step": [(s[1], s[2]) for s in parsed["spans"]
                               if s[0] == "bench.step"],
            "in submit": [(s[1], s[2]) for s in parsed["spans"]
                          if s[0] == "bench.submit"]}
    by_host = {k: length(intersect(union(idle), union(v)))
               for k, v in host.items()}
    by_host["between steps"] = length(idle) - sum(by_host.values())
    programs = {"decode": [], "extend": [], "other": []}
    for n, a, b in parsed["modules"]:
        if a >= w0 and b <= w1:
            key = ("decode" if DECODE_PROGRAM in n else
                   "extend" if EXTEND_PROGRAM in n else "other")
            programs[key].append(b - a)
    return {
        "window_s": w1 - w0, "busy_s": length(busy),
        "inflight_s": length(flight), "idle_inflight_s": length(idle),
        "steps": whole, "programs": programs,
        "device_ops": sorted(by_op.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(by_host.items(), key=lambda kv: -kv[1])[:top],
    }


def load(prof: dict, steps: list, recs: list) -> dict:
    """Read the profile written under ``prof["dir"]`` and reduce it."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(prof["dir"], "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return {}
    parsed = parse(ProfileData.from_file(paths[0]))
    inflight = [(r.submit, r.stamps[-1]) for r in recs if r.stamps]
    return reduce(parsed, (prof["t_started"], prof["t_stop"]), steps,
                  inflight)
