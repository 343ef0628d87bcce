"""Prefill share (%): device time of the extend-wave program
(``jit(_extend_fn)``, which carries the prompt chunks) over the
device's busy time in the profiler window.  Moves ``itl_p95_ms``: in
every open-loop cell the ITL tail is an extend wave's length."""


def read(rec):
    p = rec["profile"] or {}
    if not p.get("busy_s"):
        return None
    return 100.0 * sum(p["programs"]["extend"]) / p["busy_s"]
