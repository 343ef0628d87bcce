"""``device.idle_share`` in phi3-docqa-cold, where it moves
``itl_p95_ms.cold``: that cell's ITL p95 swings between two modes and is
held to a bound of its own.  The reading is ``device.idle_share``'s,
whose reader this one runs."""
from bench.spec import metric_readers

read = metric_readers(["device.idle_share"])["device.idle_share"]
