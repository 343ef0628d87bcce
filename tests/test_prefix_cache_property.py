"""Property-fuzz the token-granular radix prefix cache against a plain
dict-of-token-tuples oracle.

The oracle models the cache's DOCUMENTED adoption semantics (see
``RadixPrefixCache.insert``): per namespace it holds the set of chains
whose tokens are matchable, and

* ``match(q)`` must return EXACTLY ``min(longest common prefix of q
  with any chain, max_tokens)`` tokens and ``ceil(n / bs)`` pages;
* ``insert(key)`` adopts the unmatched tail iff the divergence point
  ``m`` lands on a page boundary or exactly extends a resident chain
  that ends mid-page (upgrade) — a mid-page divergence keeps the
  resident chain; adopted inserts return exactly ``m // bs`` duplicate
  pages, refused/duplicate inserts return every page;
* ``evict`` removes leaf chains only (observed through the
  ``on_evict`` callback, which the oracle uses to truncate its
  chains) and never touches pages a reader still holds.

Interleavings also exercise the in-flight publication protocol (incref
then insert a growing page-aligned prefix of a still-owned chain, free
the returned duplicates) — the exact sequence the engine's
``_publish_frontiers`` drives.  After every operation
``pool.assert_consistent()`` must hold, and when every reader and
owner releases at the end, a full evict must return the pool to
all-free (zero leaked pages).
"""
import numpy as np

from repro.serving.kv_pool import KVBlockPool, blocks_for_tokens
from repro.serving.prefix_cache import RadixPrefixCache

from hypothesis import given, settings
from hypothesis import strategies as st

BS = 4
POOL_BLOCKS = 96
ALPHABET = 3          # tiny vocab => dense prefix collisions
NAMESPACES = (0, 7)


def _common(a, b):
    lim = min(len(a), len(b))
    for i in range(lim):
        if a[i] != b[i]:
            return i
    return lim


class Oracle:
    """Reference model: per-namespace set of matchable chains."""

    def __init__(self):
        self.chains = {ns: set() for ns in NAMESPACES}

    def expect_match(self, ns, q, cap):
        m = max((_common(q, c) for c in self.chains[ns]), default=0)
        return min(m, cap)

    def apply_insert(self, ns, key):
        """Returns expected duplicate-page count for ``insert(key)``."""
        key = tuple(key)
        total = blocks_for_tokens(len(key), BS)
        m = max((_common(key, c) for c in self.chains[ns]), default=0)
        if m == len(key):
            return total                       # fully covered: all dups
        upgrade = any(_common(key, c) == m and len(c) == m
                      for c in self.chains[ns])
        if m % BS == 0 or upgrade:
            if upgrade and m % BS != 0:
                # the tree REPLACES an upgraded partial-tail leaf: the
                # subsumed chain's mid-page endpoint no longer exists,
                # so a later insert reaching depth m mid-page is a
                # refused divergence, not another upgrade
                self.chains[ns] = {c for c in self.chains[ns]
                                   if not (len(c) == m
                                           and _common(key, c) == m)}
            self.chains[ns].add(key)
            return m // BS
        return total                           # mid-page divergence refused

    def apply_evict(self, ns, full_key, n_leaf):
        """Truncate chains that ended inside the evicted leaf edge."""
        cut = len(full_key) - n_leaf
        prefix = tuple(full_key[:cut])
        kept = set()
        for c in self.chains[ns]:
            if _common(c, tuple(full_key)) == len(c) and len(c) > cut:
                if cut:
                    kept.add(prefix)           # ancestors stay indexed
            else:
                kept.add(c)
        self.chains[ns] = kept


def _rand_key(rng, max_len=24):
    n = int(rng.integers(1, max_len + 1))
    return tuple(int(t) for t in rng.integers(0, ALPHABET, n))


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_radix_cache_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    pool = KVBlockPool(POOL_BLOCKS, BS)
    evictions = []
    cache = RadixPrefixCache(
        pool, on_evict=lambda ns, k, nl, blks: evictions.append((ns, k, nl)))
    oracle = Oracle()
    readers = []          # block lists held by simulated readers
    owned = []            # in-flight chains: [ns, key, blocks, published]
    hit_stats = []        # (blocks, tokens) of recorded hits, for unrecord

    for _ in range(120):
        ns = NAMESPACES[int(rng.integers(len(NAMESPACES)))]
        op = rng.random()
        if op < 0.30:                                       # match
            q = _rand_key(rng)
            cap = int(rng.integers(1, len(q) + 1))
            blocks, n = cache.match(np.asarray(q, np.int64),
                                    namespace=ns, max_tokens=cap)
            expect = oracle.expect_match(ns, q, cap)
            assert n == expect, (seed, q, cap, n, expect)
            assert len(blocks) == blocks_for_tokens(n, BS)
            if n:
                if rng.random() < 0.5:
                    readers.append(blocks)                  # keep pinned
                else:
                    pool.free(blocks)
                    cache.unrecord_hit(len(blocks), n, (n // BS) * BS)
        elif op < 0.55:                                     # insert finished
            key = _rand_key(rng)
            nb = blocks_for_tokens(len(key), BS)
            if not pool.can_alloc(nb):
                continue
            blocks = pool.alloc(nb)
            expect_dups = oracle.apply_insert(ns, key)
            dups = cache.insert(np.asarray(key, np.int64), blocks,
                                namespace=ns)
            assert len(dups) == expect_dups, (seed, key, dups, expect_dups)
            pool.free(dups)
        elif op < 0.70:                                     # start in-flight
            key = _rand_key(rng)
            nb = blocks_for_tokens(len(key), BS)
            if not pool.can_alloc(nb):
                continue
            owned.append([ns, key, pool.alloc(nb), 0])
        elif op < 0.85 and owned:                           # publish frontier
            ch = owned[int(rng.integers(len(owned)))]
            cns, key, blocks, published = ch
            frontier = min(published + BS, (len(key) // BS) * BS)
            if frontier <= published:
                continue
            pub_blocks = blocks[:frontier // BS]
            pool.share(pub_blocks)
            oracle.apply_insert(cns, key[:frontier])
            dups = cache.insert(np.asarray(key[:frontier], np.int64),
                                pub_blocks, namespace=cns)
            pool.free(dups)
            ch[3] = frontier
        elif op < 0.92 and owned:                           # finish in-flight
            cns, key, blocks, _ = owned.pop(int(rng.integers(len(owned))))
            oracle.apply_insert(cns, key)
            dups = cache.insert(np.asarray(key, np.int64), blocks,
                                namespace=cns)
            pool.free(dups)
        else:                                               # evict
            want = int(rng.integers(1, 9))
            n_before = len(evictions)
            cache.evict(want)
            for ens, ekey, enl in evictions[n_before:]:
                oracle.apply_evict(ens, tuple(int(t) for t in ekey), enl)
        pool.assert_consistent()
        assert cache.hits >= 0 and cache.hit_tokens >= 0
        assert cache.hit_tokens >= cache.hit_tokens_block >= 0

    # drain: every reader and owner releases -> zero leaked pages
    for blocks in readers:
        pool.free(blocks)
    for _, _, blocks, _ in owned:
        pool.free(blocks)
    n_before = len(evictions)
    cache.evict(POOL_BLOCKS)
    for ens, ekey, enl in evictions[n_before:]:
        oracle.apply_evict(ens, tuple(int(t) for t in ekey), enl)
    pool.assert_consistent()
    assert cache.num_blocks == 0
    assert pool.num_free == POOL_BLOCKS, "leaked pages"
    for ns in NAMESPACES:
        q = _rand_key(rng)
        assert cache.match(np.asarray(q, np.int64), namespace=ns,
                           max_tokens=len(q)) == ([], 0)
