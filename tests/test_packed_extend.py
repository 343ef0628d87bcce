"""Packed extend waves: width-1 slots ride a decode-shaped row group,
wider spans a bucket of R wide rows (``model.extend_paged``'s packed
layout, ``EdgeServingEngine._extend_step``).

The guarantees:
* the packed wave emits the same tokens as the (B, K) wave of every
  slot, greedy and sampled, with and without speculation, on the dense
  and MoE families; the pool stays consistent and leaks nothing;
* neither a wide pad row nor the narrow row of a wide slot writes a
  pool byte, whatever tokens they carry;
* the counters and the MoE capacity follow the wave's rows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.models import moe
from repro.models.layers import NarrowRows
from repro.serving import EdgeServingEngine, Request, ServeConfig

ARCHS = ["phi3-medium-14b", "granite-moe-1b-a400m"]
SLOTS, K = 6, 8
# 30, 20 and 17 tokens catch up 8 at a time; 5, 3 and 6 take one wave.
# Wide rows per extend wave: all six (R = max_slots), then three
# (bucket 4), then two, then one.
PROMPTS = (30, 20, 17, 5, 3, 6)
ROWS = [6, 4, 2, 1]


def _cfg(arch):
    cfg = get_smoke_config(arch).replace(dtype="float32")
    if cfg.family == "moe":
        cfg = cfg.replace(capacity_factor=cfg.num_experts
                          / cfg.num_experts_per_tok)
    return cfg


@pytest.fixture(scope="module", params=ARCHS)
def model(request):
    cfg = _cfg(request.param)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _engine(model, packed=True, **kw):
    cfg, params = model
    eng = EdgeServingEngine(cfg, params, ServeConfig(
        max_slots=SLOTS, max_len=96, prefill_buckets=(8, 16, 32), seed=3,
        chunked_prefill=True, catch_chunk=K, **kw),
        draft=(cfg, params) if kw.get("spec_decode") else None)
    assert eng.packed
    eng.packed = packed          # False: every slot's (B, K) block
    return eng


def _serve(eng, temperature=0.0):
    """Serve PROMPTS; returns (tokens per request, wide rows per extend
    wave)."""
    rng = np.random.default_rng(7)
    vocab = eng.cfg.vocab_size
    for uid, n in enumerate(PROMPTS):
        eng.submit(Request(uid=uid, prompt=rng.integers(0, vocab, n,
                                                        dtype=np.int32),
                           max_new_tokens=5, temperature=temperature))
    rows = []
    while eng.queue or eng.active.any():
        before = eng.metrics.collect().get("engine.extend_rows", 0)
        eng.step()
        eng.pool.assert_consistent()
        rows.append(eng.metrics.collect().get("engine.extend_rows", 0)
                    - before)
    cached = eng.prefix_cache.num_blocks if eng.prefix_cache else 0
    assert eng.pool.num_free + cached == eng.pool.num_blocks
    assert all(not b for b in eng.slot_blocks)
    return ({r.uid: tuple(r.generated) for r in eng.completed},
            [r for r in rows if r])


@pytest.mark.parametrize("case", ["greedy", "sampled", "spec"])
def test_packed_wave_matches_full_wave(model, case):
    """One wide slot (R = 1), three (bucket 4) and every slot (R =
    max_slots) all occur; the tokens equal the (B, K) wave's.  Sampled:
    temperature > 0 on a fixed seed, so the waves ship their logits.
    Spec: greedy speculation on the packed wave equals vanilla."""
    kw = {"spec_decode": True, "spec_gamma": 4} if case == "spec" else {}
    temp = 0.8 if case == "sampled" else 0.0
    got, rows = _serve(_engine(model, **kw), temp)
    assert len(got) == len(PROMPTS)
    if case == "spec":
        want, _ = _serve(_engine(model), temp)
    else:
        want, full_rows = _serve(_engine(model, packed=False), temp)
        assert rows == ROWS and set(full_rows) == {SLOTS}
    assert got == want


def test_packed_counters_and_moe_capacity(model, monkeypatch):
    """``engine.extend_rows`` sums R; ``engine.extend_positions`` counts
    B + R*K a wave; a MoE wave's expert capacity is
    min(B + R*K, capacity(cfg, B*K))."""
    cfg, _ = model
    seen = []
    real = moe.moe_mlp

    def spy(cfg_, p, x, token_mask=None, expert_capacity=None):
        if token_mask is not None:
            seen.append((x.shape[1], expert_capacity))
        return real(cfg_, p, x, token_mask, expert_capacity)

    monkeypatch.setattr(moe, "moe_mlp", spy)
    eng = _engine(model)
    _, rows = _serve(eng)
    snap = eng.metrics.collect()
    assert snap["engine.extend_rows"] == sum(ROWS)
    assert snap["engine.extend_positions"] == sum(SLOTS + r * K
                                                  for r in ROWS)
    if cfg.family == "moe":
        want = {(SLOTS + r * K,
                 min(SLOTS + r * K, moe.capacity(cfg, SLOTS * K)))
                for r in ROWS}
        assert set(seen) == want
    else:
        assert not seen


@pytest.mark.parametrize("factor", [0.25, 4.0])
def test_packed_capacity_bound(factor):
    """The bound picks either side: below the wave's token count when
    the (B, K) wave's capacity is, the token count otherwise."""
    cfg = get_smoke_config("granite-moe-1b-a400m").replace(
        capacity_factor=factor)
    B, R = 4, 2
    narrow = NarrowRows(jnp.zeros((B, 1), jnp.int32),
                        jnp.zeros((B,), jnp.int32),
                        jnp.zeros((B, 4), jnp.int32),
                        jnp.array([1, 0, 0, 1], jnp.int32))
    x = jnp.zeros((1, B + R * K, cfg.d_model))
    mask, c = moe._extend_token_mask(cfg, x, jnp.array([K, 3], jnp.int32),
                                     narrow)
    assert c == min(B + R * K, moe.capacity(cfg, B * K))
    assert c == (moe.capacity(cfg, B * K) if factor < 1 else B + R * K)
    assert np.asarray(mask).sum() == 2 + K + 3


@pytest.mark.parametrize("arch", ARCHS)
def test_packed_pads_write_nothing(arch):
    """Model level: after a packed wave every pool byte outside the fed
    positions is unchanged — a wide pad row (valid 0, here with a real
    table) and the narrow rows of wide or idle slots (valid 0) write
    nothing, whatever token they carry; the fed rows' logits do not
    depend on those tokens either."""
    cfg = _cfg(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    max_len, bs, B = 64, 8, 4
    n_blk = max_len // bs
    tables = np.stack([np.arange(n_blk) + i * n_blk
                       for i in range(B)]).astype(np.int32)
    cache = M.init_paged_cache(cfg, B, max_len, B * n_blk, bs)
    prompt = rng.integers(0, cfg.vocab_size, (B, 6)).astype(np.int32)
    _, cache = M.prefill_paged(
        cfg, params, {"tokens": jnp.asarray(prompt)}, max_len, cache,
        slots=jnp.arange(B), write_tables=jnp.asarray(tables[:, :2]))
    pos = np.array([6, 6, 6, 6], np.int32)
    # slot 0 width 1 (narrow), slot 1 wide over 5, slot 2 wide over 3,
    # slot 3 idle; wide row 2 is padding with slot 3's table
    narrow_valid = np.array([1, 0, 0, 0], np.int32)
    wide_slots = [1, 2, 3]
    wide_valid = np.array([5, 3, 0], np.int32)

    def run(junk):
        toks = rng.integers(0, cfg.vocab_size, (B, 1)).astype(np.int32)
        wide = np.random.default_rng(1).integers(
            0, cfg.vocab_size, (3, K)).astype(np.int32)
        # what no valid row feeds: the narrow tokens of slots 1-3, the
        # wide pad row, the positions past each wide row's width
        toks[1:] = junk
        wide[0, 5:] = wide[1, 3:] = wide[2] = junk
        toks[0] = 11
        narrow = NarrowRows(jnp.asarray(toks), jnp.asarray(pos),
                            jnp.asarray(tables), jnp.asarray(narrow_valid))
        (ln, lw), new = M.extend_paged(
            cfg, params, cache, jnp.asarray(wide), jnp.asarray(pos[1:]),
            jnp.asarray(tables[wide_slots]), jnp.asarray(wide_valid),
            narrow=narrow)
        return np.asarray(ln[0]), np.asarray(lw[0, :5]), \
            np.asarray(lw[1, :3]), new

    a, b = run(1), run(2)
    for x, y in zip(a[:3], b[:3]):
        assert np.array_equal(x, y), arch
    fed = np.zeros((B * n_blk, bs), bool)
    for slot, width in ((0, 1), (1, 5), (2, 3)):
        for p in range(6, 6 + width):
            fed[tables[slot, p // bs], p % bs] = True
    for old, new_a, new_b in zip(jax.tree.leaves(cache),
                                 jax.tree.leaves(a[3]),
                                 jax.tree.leaves(b[3])):
        old, new_a, new_b = map(np.asarray, (old, new_a, new_b))
        # stacked per layer: (layers, pages, bs, ...)
        keep = np.broadcast_to(~fed, old.shape[:3])
        assert np.array_equal(old[keep], new_a[keep]), arch
        assert np.array_equal(new_a, new_b), arch
        assert not np.array_equal(old, new_a), arch
