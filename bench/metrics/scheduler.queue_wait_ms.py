"""Engine queue wait, p95 (ms): ``queue_wait_us`` of the engine tracer's
request summaries (submit to admitted into a slot), over the requests
due in the window.  Moves ``ttft_p95_ms``."""
import numpy as np


def read(rec):
    t0, t1 = rec["window"]
    s = rec["summaries"]
    xs = [s[r.uid]["queue_wait_us"] / 1e3 for r in rec["requests"]
          if t0 <= r.due < t1 and r.uid in s
          and s[r.uid]["queue_wait_us"] is not None]
    return float(np.percentile(xs, 95)) if xs else None
