"""Frontend inbox wait, p95 (ms): the frontend's own stamp when it hands
a request to the engine (``StreamHandle.t_submit``, reset when the
inbox is drained between steps) minus the client's submit stamp, over
the requests due in the window.  Moves ``ttft_p95_ms``."""
import numpy as np


def read(rec):
    t0, t1 = rec["window"]
    xs = [(r.fe_submit - r.submit) * 1e3 for r in rec["requests"]
          if t0 <= r.due < t1 and r.fe_submit is not None]
    return float(np.percentile(xs, 95)) if xs else None
