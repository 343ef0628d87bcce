"""Decode wave (ms): mean device time of the decode-wave program
(``jit(_decode_fn)``) in the profiler window.  Moves ``itl_p95_ms``."""


def read(rec):
    xs = (rec["profile"] or {}).get("programs", {}).get("decode", [])
    return 1e3 * sum(xs) / len(xs) if xs else None
