"""Always-on serving frontend over the step-driven engine.

``AsyncServingFrontend`` wraps ``EdgeServingEngine`` in an asyncio
event loop with no drain assumption: requests arrive and are cancelled
mid-flight, tokens stream to per-request callbacks / async iterators
as each engine step retires them, and a graceful shutdown flushes the
prefix-persist store via ``engine.close()``.

Threading model: the engine is single-threaded.  All engine calls
(``submit`` / ``cancel`` / ``step`` / ``close``) happen from the one
background ``_run`` task; the public API only posts intents to an
inbox and wakes the loop, so callers never race a step that is
executing in the default executor.  ``step()`` itself runs via
``run_in_executor`` so the event loop stays responsive to arrivals
during a jitted wave.  A step that raises stops the loop: every
outstanding ``StreamHandle.done`` fails with the exception and
``shutdown()`` re-raises it, so a broken device run ends in an error,
never in a hang.

CLI:

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b \
      --requests 8 --max-new 16 --policy edf --mode async

``--mode async`` (default) staggers arrivals over the run and streams
tokens as they retire; ``--mode drain`` keeps the legacy
submit-all-then-drain loop.  Reports tokens/sec and TTFT and
inter-token latency percentiles, timed from the CLI's own submit stamps.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
from pathlib import Path
from typing import Callable, Optional

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_smoke_config, get_config
from repro.models import model as M
from repro.serving import EdgeServingEngine, Request, ServeConfig
from repro.serving.telemetry import FRONTEND_TID, default_clock


class StreamHandle:
    """Per-request streaming view handed back by ``submit``.

    Tokens arrive on an asyncio queue as the engine retires them
    (``None`` sentinel terminates the stream); ``done`` resolves with
    the finished ``Request`` (``req.cancelled`` distinguishes a
    mid-flight cancel from natural completion).

    ``t_submit`` is stamped again when the frontend hands the request
    to the engine (its inbox drain between steps), so it is that
    hand-off, not the caller's ``submit``: a caller that wants latency
    from its own submit keeps its own stamp.  ``t_tokens`` holds the
    time each token was delivered.
    """

    def __init__(self, req: Request, clock: Callable[[], float] = None):
        self.req = req
        self.uid = req.uid
        self.delivered = 0
        self.tokens: asyncio.Queue = asyncio.Queue()
        self.done: asyncio.Future = (
            asyncio.get_event_loop().create_future())
        self._clock = clock if clock is not None else default_clock
        self.t_submit = self._clock()
        self.t_tokens: list[float] = []     # arrival time of each token

    def __aiter__(self):
        return self._gen()

    async def _gen(self):
        while True:
            tok = await self.tokens.get()
            if tok is None:
                return
            yield tok


class AsyncServingFrontend:
    """Always-on asyncio frontend: admit, stream, cancel, shut down.

    The background task loops ``engine.step()`` while work exists and
    parks on an event when idle, so an idle frontend burns no cycles
    but wakes instantly on the next arrival.  Per-token delivery works
    by diffing ``req.generated`` after each step — the engine stays
    oblivious to the frontend.
    """

    def __init__(self, engine: EdgeServingEngine,
                 trace_path: Optional[str] = None):
        self.engine = engine
        self.trace_path = trace_path
        # share the engine's trace clock so frontend stamps and engine
        # spans land on one timeline (monotonic, never the wall clock)
        self._clock = (engine.tracer.clock if engine.tracer is not None
                       else default_clock)
        self._inbox: list[Request] = []
        self._cancels: list[tuple[int, asyncio.Future, float]] = []
        self._handles: dict[int, StreamHandle] = {}
        self._callbacks: dict[int, Callable] = {}
        self._wake: Optional[asyncio.Event] = None
        self._task: Optional[asyncio.Task] = None
        self._closing = False
        self._error: Optional[BaseException] = None
        self.steps = 0
        m = engine.metrics
        m.gauge("frontend.steps", lambda: self.steps)
        m.gauge("frontend.streams", lambda: len(self._handles))
        m.gauge("frontend.inbox_depth", lambda: len(self._inbox))
        m.gauge("frontend.pending_cancels", lambda: len(self._cancels))

    def metrics(self) -> dict:
        """Deterministic snapshot of the WHOLE registry — engine,
        subsystems, and the frontend gauges above."""
        return self.engine.metrics.collect()

    # -- public API (call from coroutines on the running loop) --------
    async def start(self) -> None:
        self._wake = asyncio.Event()
        self._task = asyncio.create_task(self._run())

    def submit(self, req: Request,
               on_token: Optional[Callable[[Request, int], None]] = None,
               ) -> StreamHandle:
        """Enqueue a request; returns a handle streaming its tokens."""
        if self._error is not None:
            raise RuntimeError("frontend stopped on an engine failure") \
                from self._error
        if self._closing:
            raise RuntimeError("frontend is shutting down")
        h = StreamHandle(req, clock=self._clock)
        self._handles[req.uid] = h
        if on_token is not None:
            self._callbacks[req.uid] = on_token
        self._inbox.append(req)
        self._wake.set()
        return h

    def cancel(self, uid: int) -> asyncio.Future:
        """Request mid-flight cancellation; the future resolves
        True/False once the engine processed it (between steps)."""
        fut = asyncio.get_event_loop().create_future()
        self._cancels.append((uid, fut, self._clock()))
        self._wake.set()
        return fut

    async def shutdown(self, drain: bool = True) -> dict:
        """Stop the loop and flush the prefix-persist store.

        ``drain=True`` finishes in-flight and queued work first;
        ``drain=False`` cancels everything outstanding.  Re-raises the
        exception that stopped the step loop, if one did.
        """
        if not drain:
            for uid in list(self._handles):
                self.cancel(uid)
        self._closing = True
        self._wake.set()
        if self._task is not None:
            await self._task            # re-raises a failed step
        out = self.engine.close()           # persists hot chains
        if self.trace_path and self.engine.tracer is not None:
            out["trace"] = dict(
                self.engine.dump_chrome_trace(self.trace_path),
                path=self.trace_path)
        return out

    # -- internals ----------------------------------------------------
    def _drain_control(self) -> None:
        """Apply queued submits/cancels on the loop thread, between
        steps — the only place besides ``step`` that touches the
        engine."""
        eng = self.engine
        tr = eng.tracer
        inbox, self._inbox = self._inbox, []
        for req in inbox:
            h = self._handles[req.uid]
            h.t_submit = self._clock()
            eng.submit(req)
        cancels, self._cancels = self._cancels, []
        for uid, fut, t0 in cancels:
            ok = eng.cancel(uid)
            if ok and tr is not None:
                tr.instant("cancel_applied", tid=FRONTEND_TID, uid=uid,
                           latency_ms=(self._clock() - t0) * 1e3)
            if not fut.done():
                fut.set_result(ok)
            if ok:
                self._resolve(uid)
        if tr is not None and (inbox or cancels):
            tr.counter("frontend_queues", tid=FRONTEND_TID,
                       streams=len(self._handles),
                       engine_queue=len(eng.queue))

    def _deliver(self) -> None:
        """Diff ``req.generated`` against what each handle has seen and
        stream the delta; resolve handles whose request finished."""
        now = self._clock()
        for uid in list(self._handles):
            h = self._handles[uid]
            req = h.req
            n = len(req.generated)
            while h.delivered < n:
                tok = int(req.generated[h.delivered])
                h.delivered += 1
                h.t_tokens.append(now)
                h.tokens.put_nowait(tok)
                cb = self._callbacks.get(uid)
                if cb is not None:
                    cb(req, tok)
            if req.done:
                self._resolve(uid)

    def _resolve(self, uid: int) -> None:
        h = self._handles.pop(uid, None)
        self._callbacks.pop(uid, None)
        if h is None:
            return
        h.tokens.put_nowait(None)
        if not h.done.done():
            h.done.set_result(h.req)

    def _fail(self, exc: BaseException) -> None:
        """The step loop died: fail every outstanding handle and
        pending cancel with ``exc`` so no awaiter waits forever."""
        self._error = exc
        for uid in list(self._handles):
            h = self._handles.pop(uid)
            self._callbacks.pop(uid, None)
            h.tokens.put_nowait(None)
            if not h.done.done():
                h.done.set_exception(exc)
        for _, fut, _ in self._cancels:
            if not fut.done():
                fut.set_exception(exc)
        self._cancels = []

    async def _run(self) -> None:
        try:
            await self._loop()
        except Exception as e:
            self._fail(e)
            raise

    async def _loop(self) -> None:
        loop = asyncio.get_running_loop()
        eng = self.engine
        while True:
            with eng._span("drain", tid=FRONTEND_TID):
                self._drain_control()
            busy = bool(eng.queue) or bool(eng.active.any())
            if busy:
                await loop.run_in_executor(None, eng.step)
                self.steps += 1
                with eng._span("deliver", tid=FRONTEND_TID):
                    self._deliver()
                continue
            if self._inbox or self._cancels:
                continue                    # new intents — apply now
            if self._closing:
                return
            self._wake.clear()
            if self._inbox or self._cancels or self._closing:
                continue                    # landed before the clear
            await self._wake.wait()


def latency_stats(submitted) -> dict:
    """TTFT and inter-token latency p50/p99 (ms, linearly interpolated)
    over ``submitted``, pairs of the caller's own submit stamp and the
    request's ``StreamHandle``; None where there is no sample."""
    ttft = [(h.t_tokens[0] - t) * 1e3 for t, h in submitted if h.t_tokens]
    itl = [(b - a) * 1e3 for _, h in submitted
           for a, b in zip(h.t_tokens, h.t_tokens[1:])]
    return {f"{name}_p{q}_ms": (round(float(np.percentile(xs, q)), 2)
                                if xs else None)
            for name, xs in (("ttft", ttft), ("itl", itl))
            for q in (50, 99)}


# ---------------------------------------------------------------- CLI
def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the
    cache there and nothing else is set here.  Otherwise the cache goes
    to ``.jax_cache/`` at the root of the checkout: a fixed path, since
    a directory that moves between runs never hits.  Entry points call
    this; importing the module does not.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _build_engine(args):
    cfg = (get_smoke_config(args.arch) if args.scale == "smoke"
           else get_config(args.arch))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    if args.params:
        from repro.training import checkpoint as ckpt
        params = ckpt.restore(args.params, params)
    scfg = ServeConfig(max_slots=args.slots, max_len=args.max_len,
                       temperature=args.temperature, top_k=args.top_k,
                       policy=args.policy, spec_decode=args.spec,
                       draft_arch=args.draft if args.spec else None,
                       spec_gamma=args.gamma,
                       chunked_prefill=args.chunked,
                       prefix_persist_path=args.persist,
                       trace=bool(args.trace))
    return cfg, EdgeServingEngine(cfg, params, scfg)


def _make_requests(cfg, args) -> list:
    rng = np.random.default_rng(0)
    reqs = []
    for uid in range(args.requests):
        n = int(rng.integers(args.min_prompt, args.max_prompt + 1))
        extras = {}
        if cfg.family == "vlm":
            extras["image_embeds"] = rng.normal(
                0, 0.1, (cfg.num_image_tokens, cfg.image_embed_dim)
            ).astype(np.float32)
        if cfg.family == "encdec":
            extras["audio_embeds"] = rng.normal(
                0, 0.1, (cfg.encoder_seq, cfg.d_model)).astype(np.float32)
        reqs.append(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size, n, dtype=np.int32),
            max_new_tokens=args.max_new,
            priority=uid % 3,
            deadline=float(uid) if args.policy == "edf" else None,
            extras=extras))
    return reqs


async def _serve_async(eng, reqs, args) -> dict:
    """Open-loop style demo: staggered arrivals into a live frontend."""
    fe = AsyncServingFrontend(eng, trace_path=args.trace)
    await fe.start()
    submitted = []
    gap = args.arrival_gap_ms / 1e3
    for req in reqs:
        submitted.append((default_clock(), fe.submit(req)))
        if gap:
            await asyncio.sleep(gap)
    done = [await h.done for _, h in submitted]
    out = latency_stats(submitted)
    out.update(await fe.shutdown())
    out["requests"] = len(done)
    out["tokens"] = sum(len(r.generated) for r in done)
    out["decode_steps"] = fe.steps
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma3-1b")
    ap.add_argument("--scale", choices=("smoke", "full"), default="smoke")
    ap.add_argument("--mode", choices=("async", "drain"), default="async",
                    help="async: always-on frontend with staggered "
                         "arrivals and streaming; drain: legacy "
                         "submit-all-then-drain loop")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="0 disables top-k filtering")
    ap.add_argument("--policy", choices=("fifo", "priority", "edf"),
                    default="priority",
                    help="QoE admission ordering (core.scheduler)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding (serving.spec_decode)")
    ap.add_argument("--draft", default="self",
                    help="draft arch for --spec: a registry id, or "
                         "'self' for the early-exit self-draft")
    ap.add_argument("--gamma", type=int, default=4,
                    help="speculation width (proposals per round + 1); "
                         "also the multi-token catch-up chunk")
    ap.add_argument("--chunked", action="store_true",
                    help="chunked prefill: admit prompts as wave spans "
                         "interleaved with decode (no blocking prefill)")
    ap.add_argument("--arrival-gap-ms", type=float, default=5.0,
                    help="async mode: gap between request arrivals")
    ap.add_argument("--persist", metavar="PATH", default=None,
                    help="prefix-store path: rehydrate the radix prefix "
                         "cache from PATH at startup (warm TTFT after a "
                         "hub restart) and save the hot chains back on "
                         "exit; a corrupt or mismatched-config store is "
                         "rejected cleanly (cold start).  Only engages "
                         "on prefix-sharable archs (see "
                         "scripts/diagnose.py --cache)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="enable wave/request tracing and dump a "
                         "Perfetto/chrome://tracing JSON to PATH at "
                         "shutdown (summarize with scripts/diagnose.py "
                         "--trace PATH)")
    ap.add_argument("--min-prompt", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--params", default=None,
                    help="checkpoint from launch.train (else random init)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg, eng = _build_engine(args)
    reqs = _make_requests(cfg, args)
    t0 = default_clock()

    if args.mode == "async":
        out = asyncio.run(_serve_async(eng, reqs, args))
        dt = default_clock() - t0
        out["elapsed_s"] = round(dt, 2)
        out["tok_per_s"] = round(out["tokens"] / dt, 1)
        out["policy"] = args.policy
        done = eng.completed
    else:
        t_submit, t_first = {}, {}
        for req in reqs:
            eng.submit(req)
            t_submit[req.uid] = default_clock()
        while eng.queue or eng.active.any():
            eng.step()
            now = default_clock()
            for r in reqs:
                if r.uid not in t_first and r.generated:
                    t_first[r.uid] = now
        done = eng.completed
        dt = default_clock() - t0
        toks = sum(len(r.generated) for r in done)
        ttft = sorted((t_first[u] - t_submit[u]) * 1e3 for u in t_first)
        out = {
            "requests": len(done), "decode_steps": eng.steps,
            "tokens": toks, "elapsed_s": round(dt, 2),
            "tok_per_s": round(toks / dt, 1),
            "ttft_p50_ms": round(ttft[len(ttft) // 2], 1),
            "ttft_p99_ms": round(ttft[min(len(ttft) - 1,
                                          int(0.99 * len(ttft)))], 1),
            "policy": args.policy,
        }
        if args.persist:
            out.update(eng.close())     # save the warm chains back
        if args.trace:
            out["trace"] = dict(eng.dump_chrome_trace(args.trace),
                                path=args.trace)

    st = eng.stats()
    if args.spec:
        out.update({
            "spec_active": st["spec_active"],
            "spec_accept_rate": round(st["spec_acceptance"], 3),
            "spec_tokens_per_step": round(st["spec_tokens_per_round"], 3),
        })
    if args.chunked:
        out.update({"mixed_waves": st["mixed_waves"],
                    "wave_admitted": st["wave_admitted"]})
    if args.persist:
        out.update({
            "persist_loaded_chains": st.get("persist_loaded_chains", 0),
            "persist_loaded_blocks": st.get("persist_loaded_blocks", 0),
            "persist_rejected": st.get("persist_rejected", ""),
            "prefix_hits": st.get("prefix_hits", 0),
            "prefix_hit_tokens": st.get("prefix_hit_tokens", 0),
        })
    print(json.dumps(out))
    for r in done[:3]:
        print(f"  req {r.uid}: {list(map(int, r.generated[:10]))}...")


if __name__ == "__main__":
    main()
