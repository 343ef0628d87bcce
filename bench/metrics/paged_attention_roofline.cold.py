"""``paged_attention_roofline`` in phi3-docqa-cold, where it moves
``itl_p95_ms.cold``: that cell's ITL p95 swings between two modes and is
held to a bound of its own.  The reading is
``paged_attention_roofline``'s, whose reader this one runs."""
from bench.spec import metric_readers

read = metric_readers(["paged_attention_roofline"])["paged_attention_roofline"]
