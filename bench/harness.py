"""One run of one cell: build, warm up, serve the mix from the client
side for the window, drain, then check the served tokens against the
plain reference.

The client is this process's asyncio loop driving the program's own
``AsyncServingFrontend``: ``submit`` -> ``EdgeServingEngine.step`` ->
the jitted decode and extend waves -> the Pallas paged decode kernel.
Every end-to-end time is taken from the client's ``perf_counter``
stamps with tracing off; the traced run (``trace=True``) adds the
engine's own tracer, ``TraceAnnotation`` spans around each step and
submit, and a profiler window, and reports the per-layer metrics.
"""
from __future__ import annotations

import asyncio
import gc
import itertools
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np

from bench import flops, spec, traffic, weights

DRAIN_S = 60.0          # a request due in the window may finish this late
CHECK_REQUESTS = (4, 64)  # requests the check compares, at least and at most
WARM_TOKENS = 3         # new tokens of each warm-up request


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


@dataclass
class Rec:
    """One request as the client saw it (``perf_counter`` seconds)."""
    uid: int
    prompt: np.ndarray
    max_new: int
    due: float
    submit: float = 0.0
    fe_submit: float | None = None       # frontend's own stamp at drain
    stamps: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    done: bool = False
    failed: bool = False
    cut: bool = False           # cancelled at a closed loop's window end


class CompileWatch:
    """Backend compiles (a persistent-cache hit counts its read) and
    cache hits and misses, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class StepLog:
    """Wraps the engine's ``step`` and ``submit`` (instance attributes
    shadow the methods, so the program is untouched).  Every step's
    host interval and active slots are kept; with the engine's tracer
    on, each step's lifecycle events give the tokens it fed, their
    positions and the tokens it emitted, and both calls are wrapped in
    ``jax.profiler.TraceAnnotation`` spans named ``bench.step`` and
    ``bench.submit``."""

    def __init__(self, eng, conf: dict, annotate: bool):
        import jax
        self.conf = conf
        self.steps: list[dict] = []
        self.pos: dict[int, int] = {}
        step, submit = eng.step, eng.submit
        ann = jax.profiler.TraceAnnotation if annotate else None

        def wrapped_step():
            i = len(self.steps)
            tr = eng.tracer
            e0 = len(tr.events) if tr is not None else 0
            t0 = time.perf_counter()
            with ann("bench.step", i=i) if ann else nullcontext():
                n = step()
            row = {"i": i, "t0": t0, "t1": time.perf_counter(), "n": n}
            if tr is not None:
                row.update(self._account(tr.events[e0:]))
            self.steps.append(row)
            return n

        def wrapped_submit(req):
            with ann("bench.submit", uid=req.uid) if ann else nullcontext():
                return submit(req)

        eng.step, eng.submit = wrapped_step, wrapped_submit

    def _account(self, events) -> dict:
        fed, emitted = {}, {}
        for ev in events:
            a = ev.get("args") or {}
            if "uid" not in a:
                continue
            kind, uid = ev["name"].split(" ")[0], a["uid"]
            if kind == "admitted":
                self.pos[uid] = int(a.get("prefix_hit_tokens", 0))
            elif kind == "prefill_chunk":
                fed[uid] = fed.get(uid, 0) + int(a.get("n", 1))
            elif kind == "tokens":
                emitted[uid] = emitted.get(uid, 0) + int(a.get("n", 1))
        rows, total = [], 0
        for uid in sorted(set(fed) | set(emitted)):
            n = fed.get(uid, 1)          # a decoding slot feeds one token
            p0 = self.pos.get(uid, 0)
            self.pos[uid] = p0 + n
            rows.append((p0, n))
            total += flops.token_flops(self.conf, p0, n,
                                       min(emitted.get(uid, 0), 1))
        return {"kind": "extend" if fed else "decode", "flops": total,
                "ctxs": [p0 + 1 for p0, n in rows] if not fed else []}


# ------------------------------------------------------------------ client
async def _sleep_until(t: float) -> None:
    await asyncio.sleep(max(0.0, t - time.perf_counter()))


async def _request(fe, rec: Rec) -> Rec:
    from repro.serving import Request
    req = Request(uid=rec.uid, prompt=rec.prompt,
                  max_new_tokens=rec.max_new)
    rec.submit = time.perf_counter()
    h = fe.submit(req)
    async for tok in h:
        rec.stamps.append(time.perf_counter())
        rec.tokens.append(int(tok))
    finished = await h.done
    rec.fe_submit = h.t_submit
    rec.done = not finished.cancelled
    return rec


async def _open_loop(fe, plan, vocab, t0, T, recs, uids):
    async def session(idx, s):
        rng = np.random.default_rng([plan.seed, idx])
        history = np.concatenate(
            [plan.prefixes[s.prefix] if s.prefix >= 0
             else np.zeros(0, np.int32),
             traffic.tokens(rng, vocab, s.document)]).astype(np.int32)
        prev_end = None
        for i, turn in enumerate(s.turns):
            due = (t0 + s.arrival_s if i == 0
                   else prev_end + turn.think_s)
            if due >= t0 + T:
                return
            prompt = np.concatenate(
                [history, traffic.tokens(rng, vocab, turn.question)])
            if len(prompt) + turn.answer > plan.max_total:
                return
            await _sleep_until(due)
            rec = Rec(next(uids), prompt, turn.answer, due)
            recs.append(rec)
            await _request(fe, rec)
            history = np.concatenate(
                [prompt, np.asarray(rec.tokens, np.int32)])
            prev_end = rec.stamps[-1]

    return [asyncio.create_task(session(i, s))
            for i, s in enumerate(plan.sessions)]


async def _closed_loop(fe, plan, vocab, t0, T, recs, uids):
    work = iter(enumerate(plan.sessions))

    async def client():
        for idx, s in work:
            now = time.perf_counter()
            if now >= t0 + T:
                return
            rng = np.random.default_rng([plan.seed, idx])
            turn = s.turns[0]
            rec = Rec(next(uids), traffic.tokens(rng, vocab, turn.question),
                      turn.answer, now)
            recs.append(rec)
            await _request(fe, rec)

    return [asyncio.create_task(client()) for _ in range(plan.clients)]


async def _serve(eng, plan, vocab, T, profile=None,
                 drain_s: float = DRAIN_S) -> tuple:
    """Run the window and the drain; returns (t0, records)."""
    from repro.launch.serve import AsyncServingFrontend
    fe = AsyncServingFrontend(eng)
    await fe.start()
    recs: list[Rec] = []
    uids = itertools.count(1_000_000)
    t0 = time.perf_counter()
    start = _open_loop if plan.loop == "open" else _closed_loop
    tasks = await start(fe, plan, vocab, t0, T, recs, uids)
    if profile is not None:
        tasks.append(asyncio.create_task(profile(t0)))
    if plan.loop == "closed":
        # a closed loop measures the window's work: what is still in
        # flight at its end is cut, not drained
        await _sleep_until(t0 + T)
        for rec in recs:
            if not rec.done:
                rec.cut = True
                await fe.cancel(rec.uid)
    done, pending = await asyncio.wait(tasks, timeout=T + drain_s)
    for t in done:
        t.result()                      # an engine failure ends the run
    for rec in recs:
        if not rec.done and not rec.cut:
            rec.failed = True
            await fe.cancel(rec.uid)
    for t in pending:
        t.cancel()
    await fe.shutdown()
    return t0, recs


async def _warm(eng, vocab: int) -> None:
    """Run every program the window uses once: the extend wave (a
    prompt consumed in chunks), the decode wave, and the page copy of a
    prefix hit that ends inside a page (copy-on-write fork)."""
    from repro.launch.serve import AsyncServingFrontend
    from repro.serving import Request
    bs = eng.block_size
    rng = np.random.default_rng(0)
    first = traffic.tokens(rng, vocab, 3 * bs + 3)
    second = np.concatenate([first[:bs + bs // 2],
                             traffic.tokens(rng, vocab, bs)])
    fe = AsyncServingFrontend(eng)
    await fe.start()
    for uid, prompt in ((-1, first), (-2, second)):
        h = fe.submit(Request(uid=uid, prompt=prompt,
                              max_new_tokens=WARM_TOKENS))
        await h.done
    await fe.shutdown()


def _clear(eng) -> None:
    """Back to an empty pool after warm-up: evict what warm-up left in
    the prefix cache, re-seed sampling."""
    if eng.prefix_cache is not None:
        eng.prefix_cache.evict(eng.pool.num_blocks)
    eng.reset_rng()


# ------------------------------------------------------------------ check
def sample(recs, seed: int, tokens: int) -> list:
    """Finished requests to compare: the one with the most served
    tokens, then others in an order drawn from the seed, until
    ``tokens`` served tokens in at least ``CHECK_REQUESTS[0]`` requests,
    or ``CHECK_REQUESTS[1]`` requests."""
    ok = [r for r in recs if r.done and not r.failed and r.tokens]
    if not ok:
        return []
    ok.sort(key=lambda r: r.uid)
    first = max(ok, key=lambda r: len(r.tokens))
    rest = [r for r in ok if r is not first]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [first], len(first.tokens)
    for i in order:
        if (n >= tokens and len(out) >= CHECK_REQUESTS[0]
                or len(out) >= CHECK_REQUESTS[1]):
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def logit_gaps(conf: dict, seed: int, reqs, control: bool = False) -> dict:
    """How far each served token's reference logit lies below the
    reference's best at its position, over every served token of
    ``reqs``: the mean (the number compared), the widest gap and the
    share of tokens that are not the reference's first choice.  With
    ``control``, the same for the token that the float8 reference puts
    first at each of those positions."""
    import jax.numpy as jnp
    from bench import reference
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
            for r in reqs]
    rows = [np.arange(len(r.prompt) - 1, len(r.prompt) + len(r.tokens) - 1)
            for r in reqs]
    modes = ("f32", "fp8") if control else ("f32",)
    gaps = {"": [], "control_": []} if control else {"": []}
    for r, out in zip(reqs, reference.logits(conf, seed, seqs, rows, modes)):
        ref = out["f32"]
        best = ref.max(-1)
        for k in gaps:
            pick = (jnp.asarray(r.tokens, jnp.int32) if not k
                    else out["fp8"].argmax(-1))
            got = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
            gaps[k].append(np.asarray(best - got, np.float64))
    out = {"tokens": sum(len(r.tokens) for r in reqs),
           "requests": len(reqs)}
    for k, g in gaps.items():
        g = np.concatenate(g) if g else np.zeros(0)
        out[f"{k}mean_logit_gap"] = float(g.mean()) if g.size else 0.0
        out[f"{k}max_logit_gap"] = float(g.max()) if g.size else 0.0
        out[f"{k}mismatch_share"] = float((g > 0).mean()) if g.size else 0.0
    return out


# ------------------------------------------------------------------ run
def pct(xs, q: float):
    """Percentile ``q`` (0-100) by linear interpolation (numpy's
    default); None for no samples."""
    return float(np.percentile(np.asarray(xs, float), q)) if len(xs) else None


def end_to_end(recs, t0: float, T: float, setup_s: float) -> dict:
    due = [r for r in recs if t0 <= r.due < t0 + T]
    ttft = [(r.stamps[0] - r.due) * 1e3 for r in due if r.stamps]
    itl = [(b - a) * 1e3 for r in due for a, b in zip(r.stamps, r.stamps[1:])]
    out_tok = sum(1 for r in recs for s in r.stamps if t0 <= s < t0 + T)
    return {"ttft_p95_ms": pct(ttft, 95), "itl_p95_ms": pct(itl, 95),
            "output_tok_s": out_tok / T, "setup_s": setup_s,
            "_attempted": len(due),
            "_failed": sum(1 for r in due
                           if r.failed or not (r.done or r.cut))}


class Server:
    """The program under test, built from the configuration file and
    warmed once per process.  ``window`` serves one mix for one window
    and returns the run record; ``reseed`` swaps in another seed's
    weights (same shapes, so no program compiles again)."""

    def __init__(self, conf: dict, seed: int, trace: bool = False):
        import jax
        from repro.serving import EdgeServingEngine
        self.conf = conf
        self.watch = CompileWatch()
        t = time.perf_counter()
        self.mcfg = spec.model_config(conf)
        self.scfg, dropped = spec.serve_config(conf, trace=trace)
        for name in dropped:
            log(f"serve field {name!r} is gone from ServeConfig; the "
                "program now decides it")
        self.eng = EdgeServingEngine(
            self.mcfg, weights.program_params(conf, self.mcfg, seed),
            self.scfg)
        jax.block_until_ready((self.eng.params, self.eng.cache))
        self.init_s = time.perf_counter() - t
        t = time.perf_counter()
        asyncio.run(_warm(self.eng, self.mcfg.vocab_size))
        _clear(self.eng)
        self.warm_s = time.perf_counter() - t
        self.steps = StepLog(self.eng, conf, annotate=trace)

    def reseed(self, seed: int) -> None:
        import jax
        self.eng.params = None
        gc.collect()
        self.eng.params = weights.program_params(self.conf, self.mcfg, seed)
        jax.block_until_ready(self.eng.params)

    def drop_weights(self) -> None:
        """Free the program's weights so the reference has the chip."""
        self.eng.params = None
        gc.collect()

    def window(self, mix: dict, seed: int, seconds: float,
               trace: bool = False, drain_s: float = DRAIN_S) -> dict:
        import jax
        eng = self.eng
        plan = traffic.make_plan(mix, seed, seconds, self.mcfg.vocab_size)
        self.steps.steps.clear()
        self.steps.pos.clear()
        compiles0 = self.watch.compiles
        reg0 = eng.metrics.collect()
        prof, profile = {}, None
        if trace:
            prof["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
            lead, length = min(2.0, seconds / 4), min(4.0, seconds / 2)

            async def profile(t0):
                await _sleep_until(t0 + lead)
                loop = asyncio.get_running_loop()
                await loop.run_in_executor(None, jax.profiler.start_trace,
                                           prof["dir"])
                prof["t_started"] = time.perf_counter()
                await _sleep_until(prof["t_started"] + length)
                prof["t_stop"] = time.perf_counter()
                await loop.run_in_executor(None, jax.profiler.stop_trace)

        t0, recs = asyncio.run(_serve(eng, plan, self.mcfg.vocab_size,
                                      seconds, profile, drain_s))
        t_end = time.perf_counter()
        late = [r.submit - r.due for r in recs if r.submit]
        record = {
            "conf": self.conf, "window": (t0, t0 + seconds),
            "max_slots": self.scfg.max_slots, "requests": recs,
            "steps": list(self.steps.steps),
            "registry": (reg0, eng.metrics.collect()),
            "summaries": ({s["uid"]: s
                           for s in eng.tracer.request_summaries()}
                          if eng.tracer is not None else {}),
            "compiles_in_window": self.watch.compiles - compiles0,
            "lateness_p95_s": pct(late, 95) or 0.0,
            "lateness_max_s": max(late or [0.0]),
            "drain_s": t_end - t0 - seconds,
            "profile": None,
        }
        if trace:
            from bench import trace as tr
            record["profile"] = tr.load(prof, record["steps"], recs)
            shutil.rmtree(prof["dir"], ignore_errors=True)
        _clear(eng)
        return record


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, bench: dict | None = None, conf: dict | None = None,
        mix: dict | None = None, control: bool = False) -> dict:
    """One run; returns the raw result (metrics, checks, device)."""
    import jax

    bench = bench or spec.load_benchmark()
    cell = spec.find_cell(bench, cell_name)
    conf = conf or spec.load_config(cell["config"])
    mix = mix or spec.load_traffic(cell["traffic"])
    server = Server(conf, seed, trace)
    record = server.window(mix, seed, seconds, trace)
    t0 = record["window"][0]
    setup_s = t0 - t_start
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    e2e = end_to_end(record["requests"], t0, seconds, setup_s)
    w = server.watch
    log(f"setup {setup_s:.3f} s: process and imports "
        f"{setup_s - server.init_s - server.warm_s:.3f} s, weights and "
        f"engine {server.init_s:.3f} s, warm-up {server.warm_s:.3f} s "
        f"(compile or cache read {w.seconds:.3f} s; cache hits "
        f"{w.cache_hits}, misses {w.cache_misses})")
    log(f"compiles inside the window: {record['compiles_in_window']}; "
        f"generator lateness p95 {1e3 * record['lateness_p95_s']:.3f} ms, "
        f"max {1e3 * record['lateness_max_s']:.3f} ms; drain ended "
        f"{record['drain_s']:.3f} s after the window")
    result = {"e2e": e2e, "record": record,
              "compiles_in_window": record["compiles_in_window"],
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": stats.get("peak_bytes_in_use")}}
    # free the program's state before the reference takes the chip
    server.drop_weights()
    server.eng.cache = None
    del server
    gc.collect()
    t_check = time.perf_counter()
    picked = sample(record["requests"], seed, conf["check"]["tokens"])
    result["check"] = logit_gaps(conf, seed, picked, control=control)
    result["check_s"] = time.perf_counter() - t_check
    return result
