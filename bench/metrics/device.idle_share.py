"""Device idle share (%): the part of the profiler window in which a
request was in flight (submitted, last token not yet streamed) and no
op ran on the device, over that part.  Moves ``itl_p95_ms``."""


def read(rec):
    p = rec["profile"] or {}
    if not p.get("inflight_s"):
        return None
    return 100.0 * p["idle_inflight_s"] / p["inflight_s"]
