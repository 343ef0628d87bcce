"""Prefix-cache hit share (%) in the document cells, which report no
``ttft_p95_ms``: there it moves ``itl_p95_ms``, since a follow-up that
hits its document feeds one question's extend waves instead of a
document's, and the ITL tail is an extend wave.  The reading is
``prefix_cache.hit_share``'s, whose reader this one runs."""
from bench.spec import metric_readers

read = metric_readers(["prefix_cache.hit_share"])["prefix_cache.hit_share"]
