"""Always-on async frontend: streaming, mid-flight arrival/cancel,
graceful shutdown.

Contract under test (``launch.serve.AsyncServingFrontend``): requests
arrive into a live step loop with no drain assumption; every generated
token streams to the request's handle (and optional callback) in
order; ``cancel`` tears a request down mid-flight with zero leaked
pages; ``shutdown`` flushes the prefix-persist store so a restarted
engine rehydrates warm chains.
"""
import asyncio

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.serve import AsyncServingFrontend
from repro.models import model as M
from repro.serving import EdgeServingEngine, Request, ServeConfig

ARCH = "phi3-medium-14b"        # sharable + spec-decodable smoke arch


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config(ARCH)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _scfg(**kw):
    base = dict(max_slots=2, max_len=96, prefill_buckets=(8, 16), seed=11)
    base.update(kw)
    return ServeConfig(**base)


def _req(uid, n=6, **kw):
    rng = np.random.default_rng(100 + uid)
    kw.setdefault("max_new_tokens", 5)
    return Request(uid=uid, prompt=rng.integers(0, 64, n, dtype=np.int32),
                   **kw)


def _assert_no_leak(eng):
    cached = eng.prefix_cache.num_blocks if eng.prefix_cache else 0
    assert eng.pool.num_free + cached == eng.pool.num_blocks
    eng.pool.assert_consistent()


def test_streamed_tokens_match_generated(setup):
    cfg, params = setup
    eng = EdgeServingEngine(cfg, params, _scfg())

    async def run():
        fe = AsyncServingFrontend(eng)
        await fe.start()
        handles = [fe.submit(_req(uid)) for uid in range(4)]
        streams = {}

        async def collect(h):
            streams[h.uid] = [tok async for tok in h]
        await asyncio.gather(*(collect(h) for h in handles))
        done = [await h.done for h in handles]
        await fe.shutdown()
        return done, streams

    done, streams = asyncio.run(run())
    assert len(done) == 4
    for r in done:
        assert not r.cancelled and len(r.generated) == 5
        assert streams[r.uid] == [int(t) for t in r.generated]
    _assert_no_leak(eng)


def test_mid_flight_arrival_and_callback(setup):
    """A request submitted while another is decoding joins the live
    batch; the per-token callback fires once per token, in order."""
    cfg, params = setup
    eng = EdgeServingEngine(cfg, params, _scfg(chunked_prefill=True))
    seen = []

    async def run():
        fe = AsyncServingFrontend(eng)
        await fe.start()
        h0 = fe.submit(_req(0, max_new_tokens=10))
        # wait for first token, then land a second request mid-decode
        first = await h0.tokens.get()
        assert first is not None
        h1 = fe.submit(_req(1, max_new_tokens=3),
                       on_token=lambda req, tok: seen.append((req.uid, tok)))
        r1 = await h1.done
        r0 = await h0.done
        await fe.shutdown()
        return r0, r1

    r0, r1 = asyncio.run(run())
    assert len(r0.generated) == 10 and len(r1.generated) == 3
    assert seen == [(1, int(t)) for t in r1.generated]
    assert eng.stats()["wave_admitted"] >= 1
    _assert_no_leak(eng)


def test_cancel_mid_flight_stops_stream(setup):
    cfg, params = setup
    eng = EdgeServingEngine(cfg, params, _scfg())

    async def run():
        fe = AsyncServingFrontend(eng)
        await fe.start()
        h = fe.submit(_req(0, max_new_tokens=64))
        await h.tokens.get()                    # it is decoding
        ok = await fe.cancel(h.uid)
        r = await h.done
        # stream must terminate (None sentinel) without hanging
        toks = [t async for t in h]
        unknown = await fe.cancel(999)
        await fe.shutdown()
        return ok, r, toks, unknown

    ok, r, toks, unknown = asyncio.run(run())
    assert ok and r.cancelled and r.done
    assert unknown is False
    assert len(r.generated) < 64
    _assert_no_leak(eng)


def test_shutdown_nodrain_cancels_outstanding(setup):
    cfg, params = setup
    eng = EdgeServingEngine(cfg, params, _scfg())

    async def run():
        fe = AsyncServingFrontend(eng)
        await fe.start()
        hs = [fe.submit(_req(uid, max_new_tokens=64)) for uid in range(3)]
        await hs[0].tokens.get()
        await fe.shutdown(drain=False)
        return [await h.done for h in hs]

    done = asyncio.run(run())
    assert all(r.done for r in done)
    assert any(r.cancelled for r in done)
    assert eng.stats()["cancels"] >= 1
    _assert_no_leak(eng)


def test_shutdown_flushes_persist_store(setup, tmp_path):
    """Graceful shutdown writes hot chains; a restarted engine
    rehydrates them warm."""
    cfg, params = setup
    path = str(tmp_path / "hub_store.npz")
    sys_prompt = np.arange(1, 17, dtype=np.int32)   # page-aligned prefix

    def reqs():
        out = []
        for uid in range(3):
            rng = np.random.default_rng(uid)
            tail = rng.integers(0, 64, 4, dtype=np.int32)
            out.append(Request(uid=uid,
                               prompt=np.concatenate([sys_prompt, tail]),
                               max_new_tokens=4))
        return out

    async def serve(eng):
        fe = AsyncServingFrontend(eng)
        await fe.start()
        hs = [fe.submit(r) for r in reqs()]
        for h in hs:
            await h.done
        return await fe.shutdown()

    eng1 = EdgeServingEngine(cfg, params, _scfg(prefix_persist_path=path))
    stats = asyncio.run(serve(eng1))
    assert stats["persist_saved_chains"] >= 1

    eng2 = EdgeServingEngine(cfg, params, _scfg(prefix_persist_path=path))
    st = eng2.stats()
    assert st["persist_loaded_chains"] >= 1
    asyncio.run(serve(eng2))
    assert eng2.stats()["prefix_hits"] >= 1     # restart-warm hit


def test_failed_step_fails_handles_and_shutdown(setup):
    """A step that raises (say, a kernel the device refuses) must fail
    every outstanding handle and re-raise from shutdown() — never leave
    the caller awaiting a loop that has died."""
    cfg, params = setup
    eng = EdgeServingEngine(cfg, params, _scfg())
    real_step = eng.step

    def step():
        if eng.steps >= 2:
            raise RuntimeError("device step failed")
        return real_step()
    eng.step = step

    async def run():
        fe = AsyncServingFrontend(eng)
        await fe.start()
        handles = [fe.submit(_req(uid, max_new_tokens=20))
                   for uid in range(3)]
        for h in handles:
            with pytest.raises(RuntimeError, match="device step failed"):
                await asyncio.wait_for(h.done, timeout=60)
        with pytest.raises(RuntimeError, match="frontend stopped"):
            fe.submit(_req(9))
        with pytest.raises(RuntimeError, match="device step failed"):
            await asyncio.wait_for(fe.shutdown(), timeout=60)
        return handles

    handles = asyncio.run(run())
    assert all(h.done.done() for h in handles)
