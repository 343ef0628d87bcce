"""Whole step's share of the chip's bf16 peak (%): operations the
published model requires for the tokens that the engine steps wholly
inside the profiler window fed (``bench/flops.py``; cached-prefix
tokens, padding and experts beyond the top-k do not count) over the
steps' annotated durations times the peak.  Moves ``itl_p95_ms``."""


def read(rec):
    steps = (rec["profile"] or {}).get("steps", [])
    dur = sum(s["dur"] for s in steps)
    if not dur or "flops" not in steps[0]:
        return None
    return 100.0 * sum(s["flops"] for s in steps) / (
        dur * rec["peaks"]["bf16_flops_per_s"])
