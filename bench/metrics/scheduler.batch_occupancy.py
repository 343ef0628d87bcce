"""Batch occupancy (%): the slots each engine step stepped (its return
value) over ``max_slots``, averaged over the steps that started in the
window.  Moves ``output_tok_s``."""


def read(rec):
    t0, t1 = rec["window"]
    ns = [s["n"] for s in rec["steps"] if t0 <= s["t0"] < t1]
    return 100.0 * sum(ns) / (len(ns) * rec["max_slots"]) if ns else None
