"""Prefix-cache hit share (%): prompt tokens served from the radix cache
(the registry's ``prefix_cache.hit_tokens``, read before the window and
after the drain) over the prompt tokens of every request submitted.
Moves ``ttft_p95_ms``."""


def read(rec):
    before, after = rec["registry"]
    key = "prefix_cache.hit_tokens"
    total = sum(len(r.prompt) for r in rec["requests"] if r.submit)
    if key not in after or not total:
        return None
    return 100.0 * (after[key] - before.get(key, 0)) / total
