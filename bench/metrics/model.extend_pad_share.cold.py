"""``model.extend_pad_share`` in phi3-docqa-cold, where it moves
``itl_p95_ms.cold``: that cell's ITL p95 swings between two modes and is
held to a bound of its own.  The reading is
``model.extend_pad_share``'s, whose reader this one runs."""
from bench.spec import metric_readers

read = metric_readers(["model.extend_pad_share"])["model.extend_pad_share"]
