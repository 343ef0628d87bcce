"""Telemetry subsystem tests: the metrics registry and tracer as pure
units (fake clock — no wall time anywhere), the Chrome-trace export
contract, and the engine integration gates from ISSUE 9:

* ``stats()`` schema snapshots per config axis (paged / prefix / spec /
  quant) — a PR silently dropping or renaming a counter fails loudly;
* registry-backed ``stats()`` equals the pre-refactor ad-hoc dict,
  recomputed from the same engine attributes, on a mixed traffic trace;
* tracing is behaviour-neutral: trace-on tokens bit-identical to
  trace-off, and the dump is valid Chrome-trace JSON whose per-request
  TTFT decomposition sums exactly.
"""
import json

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serving import EdgeServingEngine, Request, ServeConfig
from repro.serving.telemetry import (Counter, Gauge, Histogram,
                                     MetricsRegistry, Tracer,
                                     validate_chrome_trace,
                                     summarize_trace)

ARCH = "phi3-medium-14b"   # fully paged: sharable AND spec-decodable


# ---------------------------------------------------------------------------
# metrics registry (pure units)
# ---------------------------------------------------------------------------

def test_counter_monotonic():
    c = Counter("x")
    c.inc()
    c.inc(3)
    assert c.read() == 4
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_vs_callback():
    g = Gauge("x")
    g.set(7)
    assert g.read() == 7
    cb = Gauge("y", fn=lambda: 42)
    assert cb.read() == 42
    with pytest.raises(ValueError):
        cb.set(1)          # callback-sampled gauges reject direct set


def test_gauge_latest_callback_wins():
    m = MetricsRegistry()
    m.gauge("fe.streams", lambda: 1)
    # a second frontend re-attaching to the same engine must not leave
    # the gauge bound to the dead frontend's closure
    m.gauge("fe.streams", lambda: 2)
    assert m.get("fe.streams") == 2


def test_histogram_buckets_fixed_and_validated():
    with pytest.raises(ValueError):
        Histogram("bad", ())
    with pytest.raises(ValueError):
        Histogram("bad", (3, 2, 1))
    h = Histogram("h", (1, 2, 4))
    for v in (0.5, 1.0, 3.0, 99.0):
        h.observe(v)
    r = h.read()
    assert r["buckets"] == [1.0, 2.0, 4.0]
    assert r["counts"] == [2, 0, 1, 1]      # <=1, <=2, <=4, overflow
    assert r["count"] == 4 and r["sum"] == pytest.approx(103.5)


def test_registry_get_or_create_and_type_clash():
    m = MetricsRegistry()
    assert m.counter("a") is m.counter("a")     # idempotent
    with pytest.raises(ValueError):
        m.gauge("a")                            # type clash
    assert "a" in m and "b" not in m
    m.gauge("g", lambda: 5)
    m.histogram("h", (1,)).observe(0)
    snap = m.collect()
    assert list(snap) == sorted(snap)           # deterministic order
    assert snap["a"] == 0 and snap["g"] == 5
    assert snap["h"]["count"] == 1


# ---------------------------------------------------------------------------
# tracer (fake clock)
# ---------------------------------------------------------------------------

def make_clock(step_s: float = 0.001):
    t = [0.0]

    def clock():
        t[0] += step_s
        return t[0]
    return clock


def test_spans_nest_and_validate():
    tr = Tracer(clock=make_clock())
    with tr.span("outer", step=1):
        with tr.span("inner"):
            tr.instant("mark")
    tr.begin("u7", tid=10)
    tr.end(10)
    tr.end(10)                        # idempotent: no unmatched E
    evs = tr.chrome_events()
    assert validate_chrome_trace(evs) == []
    xs = [e for e in evs if e["ph"] == "X"]
    inner = next(e for e in xs if e["name"] == "inner")
    outer = next(e for e in xs if e["name"] == "outer")
    # proper nesting: inner fully inside outer
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert outer["args"] == {"step": 1}


def test_open_residencies_auto_close():
    tr = Tracer(clock=make_clock())
    tr.begin("u1", tid=10)
    tr.begin("u2", tid=11)
    assert validate_chrome_trace(tr.chrome_events()) == []


def test_request_summary_decomposition_exact():
    tr = Tracer(clock=make_clock())
    tr.req_event(5, "submit")
    tr.req_event(5, "queued", depth=1)
    tr.req_event(5, "admitted", slot=0)
    tr.req_event(5, "prefill_chunk", n=8)
    tr.req_event(5, "prompt_done")
    tr.req_event(5, "first_token")
    tr.req_event(5, "tokens", n=1)
    tr.req_event(5, "spec_round", proposed=3, accepted=2)
    tr.req_event(5, "tokens", n=3)
    tr.req_event(5, "finish", n_generated=4)
    (row,) = tr.request_summaries()
    assert row["uid"] == 5
    # segments share boundary stamps -> sum is exact, not approximate
    assert (row["queue_wait_us"] + row["prefill_us"]
            + row["first_wave_us"]) == row["ttft_us"]
    assert row["e2e_us"] >= row["ttft_us"]
    assert row["n_tokens"] == 4
    # tokens retired by one wave share a stamp: the wave gap is > 0,
    # intra-wave gaps are exactly 0
    assert len(row["itl_us"]) == 3
    assert row["itl_us"][0] > 0 and row["itl_us"][1:] == [0.0, 0.0]
    assert row["spec_rounds"] == [(3, 2)]


def test_dump_and_summarize_roundtrip(tmp_path):
    tr = Tracer(clock=make_clock())
    with tr.span("step"):
        tr.req_event(0, "submit")
        tr.req_event(0, "finish")
    path = tmp_path / "t.json"
    meta = tr.dump_chrome_trace(str(path))
    trace = json.loads(path.read_text())
    assert len(trace["traceEvents"]) == meta["events"]
    s = summarize_trace(trace)
    assert s["problems"] == []
    assert s["phases"][0]["name"] == "step"
    assert len(s["requests"]) == 1


def test_validator_catches_structural_breaks():
    assert validate_chrome_trace([{"ph": "B", "ts": 0}])  # missing keys
    bad = [{"ph": "E", "ts": 0, "pid": 0, "tid": 3}]
    assert any("without matching B" in p
               for p in validate_chrome_trace(bad))
    open_b = [{"ph": "B", "name": "u", "ts": 0, "pid": 0, "tid": 3}]
    assert any("unclosed" in p for p in validate_chrome_trace(open_b))


# ---------------------------------------------------------------------------
# scheduler budget metrics
# ---------------------------------------------------------------------------

def test_plan_wave_records_budget_metrics():
    from repro.core.scheduler import plan_wave
    m = MetricsRegistry()
    entries = [{"id": 0, "want": 4, "uid": 0},
               {"id": 1, "want": 4, "uid": 1}]
    widths = plan_wave("fifo", entries, budget=5, metrics=m)
    assert sum(widths.values()) == 5
    h = m.get("sched.budget_utilization")
    assert h["count"] == 1 and h["sum"] == pytest.approx(1.0)
    assert m.get("sched.demotions") >= 1      # someone got < want
    # unbudgeted plans record nothing
    plan_wave("fifo", entries, budget=None, metrics=m)
    assert m.get("sched.budget_utilization")["count"] == 1


# ---------------------------------------------------------------------------
# stats() schema snapshots per config axis
# ---------------------------------------------------------------------------

ENGINE_KEYS = ("steps", "peak_active", "peak_pool_used",
               "exhaust_preempts", "reclaims", "cow_forks", "mixed_waves",
               "wave_admitted", "cancels")
POOL_KEYS = ("pool_blocks", "pool_free", "pool_shared")
QUANT_KEYS = ("quant_kv", "quant_draft", "quant_page_bytes",
              "quant_f32_page_bytes")
SPEC_KEYS = ("spec_active", "spec_steps", "spec_rounds", "spec_proposed",
             "spec_accepted", "spec_emitted", "spec_acceptance",
             "spec_tokens_per_round")
PREFIX_KEYS = ("prefix_hits", "prefix_misses", "prefix_hit_rate",
               "prefix_hit_blocks", "prefix_hit_tokens",
               "prefix_hit_tokens_block", "prefix_cached_blocks",
               "prefix_evicted_blocks", "prefix_inserted_blocks",
               "prefix_replaced_blocks", "prefix_short_matches",
               "published_frontiers")

SCHEMA_AXES = [
    # (tag, scfg kwargs, expected stats() key tuple)
    ("dense", dict(paged=False, prefix_cache=False), ENGINE_KEYS),
    ("paged", dict(prefix_cache=False), ENGINE_KEYS + POOL_KEYS),
    ("prefix", dict(prefix_cache=True),
     ENGINE_KEYS + POOL_KEYS + PREFIX_KEYS),
    ("spec", dict(prefix_cache=False, spec_decode=True,
                  draft_arch="self"),
     ENGINE_KEYS + POOL_KEYS + SPEC_KEYS),
    ("quant", dict(prefix_cache=False, quant_kv="int8"),
     ENGINE_KEYS + POOL_KEYS + QUANT_KEYS),
]


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config(ARCH).replace(dtype="float32")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("tag,kw,expected",
                         SCHEMA_AXES, ids=[a[0] for a in SCHEMA_AXES])
def test_stats_schema_snapshot(model, tag, kw, expected):
    cfg, params = model
    eng = EdgeServingEngine(cfg, params, ServeConfig(
        max_slots=2, max_len=96, prefill_buckets=(8, 16, 32), **kw))
    assert tuple(eng.stats().keys()) == expected, tag


# ---------------------------------------------------------------------------
# engine integration: trace neutrality + registry-backed stats()
# ---------------------------------------------------------------------------

def _traffic(vocab):
    rng = np.random.default_rng(42)
    shared = rng.integers(0, vocab, 21, dtype=np.int32)
    prompts = [
        np.concatenate([shared,
                        rng.integers(0, vocab, 4, dtype=np.int32)]),
        np.concatenate([shared,
                        rng.integers(0, vocab, 7, dtype=np.int32)]),
        rng.integers(0, vocab, 5, dtype=np.int32),
        rng.integers(0, vocab, 41, dtype=np.int32),   # chunked catch-up
    ]
    return [Request(uid=u, prompt=p, max_new_tokens=5, priority=u % 3,
                    deadline=float(u)) for u, p in enumerate(prompts)]


def _run(cfg, params, trace, clock=None):
    eng = EdgeServingEngine(cfg, params, ServeConfig(
        max_slots=2, max_len=96, prefill_buckets=(8, 16, 32), seed=3,
        paged=True, prefix_cache=True, spec_decode=True,
        draft_arch="self", policy="priority",
        trace=trace, trace_clock=clock))
    for r in _traffic(cfg.vocab_size):
        eng.submit(r)
    eng.run_until_drained()
    return eng


@pytest.fixture(scope="module")
def traced_pair(model):
    cfg, params = model
    untraced = _run(cfg, params, trace=False)
    traced = _run(cfg, params, trace=True, clock=make_clock(1e-4))
    return untraced, traced


def test_tracing_is_behaviour_neutral(traced_pair):
    untraced, traced = traced_pair
    t0 = {r.uid: tuple(r.generated) for r in untraced.completed}
    t1 = {r.uid: tuple(r.generated) for r in traced.completed}
    assert t0 == t1
    assert untraced.stats() == traced.stats()


def test_stats_equals_pre_refactor_dict(traced_pair):
    """The compatibility view must reproduce the historical ad-hoc
    dict — recomputed here from the same engine attributes the old
    ``stats()`` read directly."""
    eng, _ = traced_pair
    expected = {
        "steps": eng.steps,
        "peak_active": eng.peak_active,
        "peak_pool_used": eng.peak_pool_used,
        "exhaust_preempts": eng.exhaust_preempts,
        "reclaims": eng.reclaims,
        "cow_forks": eng.cow_forks,
        "mixed_waves": eng.mixed_waves,
        "wave_admitted": eng.wave_admitted,
        "cancels": eng.cancels,
        "pool_blocks": eng.pool.num_blocks,
        "pool_free": eng.pool.num_free,
        "pool_shared": eng.pool.num_shared,
        "spec_active": eng.spec is not None,
        "spec_steps": eng.spec_steps,
        "spec_rounds": eng.spec_rounds,
        "spec_proposed": eng.spec_proposed,
        "spec_accepted": eng.spec_accepted,
        "spec_emitted": eng.spec_emitted,
        "spec_acceptance": eng.spec_accepted / max(eng.spec_proposed, 1),
        "spec_tokens_per_round": (eng.spec_emitted
                                  / max(eng.spec_rounds, 1)),
        **{f"prefix_{k}": v for k, v in eng.prefix_cache.stats().items()},
        "published_frontiers": eng.published_frontiers,
    }
    assert eng.stats() == expected


def test_chrome_trace_valid_and_ttft_exact(traced_pair, tmp_path):
    _, eng = traced_pair
    path = tmp_path / "trace.json"
    meta = eng.dump_chrome_trace(str(path))
    trace = json.loads(path.read_text())
    assert meta["requests"] == 4
    assert validate_chrome_trace(trace["traceEvents"]) == []
    rows = {r["uid"]: r for r in eng.tracer.request_summaries()}
    assert sorted(rows) == [0, 1, 2, 3]
    for row in rows.values():
        parts = (row["queue_wait_us"], row["prefill_us"],
                 row["first_wave_us"], row["ttft_us"])
        assert None not in parts, row
        assert sum(parts[:3]) == pytest.approx(parts[3], abs=1e-6)
        assert row["e2e_us"] is not None and row["e2e_us"] >= parts[3]
        assert row["n_tokens"] == 5
        assert len(row["itl_us"]) == 4
    # speculative rounds are attributed per request, with depth
    # counters aggregated in the registry
    assert any(r["spec_rounds"] for r in rows.values())
    snap = eng.metrics.collect()
    assert snap["spec.depth0.proposed"] >= 1
    # prefix-cache hit-length histogram observed the shared prefix
    assert snap["prefix_cache.hit_tokens_hist"]["count"] >= 1
    # kv_pool traffic counters moved
    assert snap["kv_pool.alloc_blocks"] > 0


def test_dump_requires_trace_enabled(model):
    cfg, params = model
    eng = EdgeServingEngine(cfg, params, ServeConfig(
        max_slots=2, max_len=96, prefill_buckets=(8, 16, 32)))
    with pytest.raises(ValueError):
        eng.dump_chrome_trace("/tmp/never.json")


# ---------------------------------------------------------------------------
# phase spans as profiler annotations; extend-wave padding counters
# ---------------------------------------------------------------------------

def _chunked_engine(model, **kw):
    cfg, params = model
    return cfg, EdgeServingEngine(cfg, params, ServeConfig(
        max_slots=4, max_len=96, prefill_buckets=(8, 16, 32), seed=3,
        chunked_prefill=True, catch_chunk=8, **kw))


def _chunked_requests():
    rng = np.random.default_rng(7)
    return [Request(uid=u, prompt=rng.integers(0, 64, n, dtype=np.int32),
                    max_new_tokens=4) for u, n in ((0, 20), (1, 5))]


def test_extend_wave_counters_exact(model):
    """4 slots, K=8, packed waves: each extend wave computes one
    width-1 row per slot (4 positions) plus R wide rows of 8, R the
    bucket holding the slots fed more than one token.  The 20-token
    prompt catches up 8, 8, 4 tokens; the 5-token prompt feeds 5 (a
    wide row beside the first: R = 2), then rides along at width 1 in
    the narrow group while the other still catches up (R = 1).  Three
    extend waves, then decode waves (which count nothing)."""
    _, eng = _chunked_engine(model)
    assert eng.packed
    for r in _chunked_requests():
        eng.submit(r)
    eng.run_until_drained()
    snap = eng.metrics.collect()
    assert snap["engine.extend_positions"] == (4 + 2 * 8) + (4 + 8) + (4 + 8)
    assert snap["engine.extend_fed"] == (8 + 5) + (8 + 1) + (4 + 1)
    assert snap["engine.extend_rows"] == 2 + 1 + 1
    assert not any(k.startswith("extend") for k in eng.stats())


class _Slow:
    """Stands in for a module the engine imports (``np``, ``jnp``,
    ``jax.random``): every ``asarray`` / ``split`` call advances the
    fake clock by ``cost`` first, so host work the phases leave
    uncovered shows as a hole in the step."""

    def __init__(self, mod, clock_state, cost, names):
        self._mod, self._t, self._cost, self._names = (mod, clock_state,
                                                       cost, names)

    def __getattr__(self, name):
        fn = getattr(self._mod, name)
        if name not in self._names:
            return fn

        def slow(*a, **k):
            self._t[0] += self._cost
            return fn(*a, **k)
        return slow


def test_phases_cover_each_step(model, monkeypatch):
    """Every engine step is covered by its phases: the only gaps left
    are the single clock tick between one phase's end stamp and the
    next one's start stamp (and at the step's two edges).  Host work
    between phases (the uploads, the PRNG split, the copy back)
    advances the fake clock and would open a wider hole."""
    from repro.serving import engine as engine_mod
    tick, cost = 1e-6, 1e-3
    t = [0.0]

    def clock():
        t[0] += tick
        return t[0]

    monkeypatch.setattr(engine_mod, "np",
                        _Slow(np, t, cost, {"asarray"}))
    monkeypatch.setattr(engine_mod, "jnp",
                        _Slow(engine_mod.jnp, t, cost, {"asarray"}))
    fake_jax = _Slow(jax, t, cost, ())
    fake_jax.random = _Slow(jax.random, t, cost, {"split"})
    monkeypatch.setattr(engine_mod, "jax", fake_jax)
    _, eng = _chunked_engine(model, trace=True, trace_clock=clock)
    for r in _chunked_requests():
        eng.submit(r)
    eng.run_until_drained()
    phases = [e for e in eng.tracer.events
              if e["ph"] == "X" and e["tid"] == 0]
    steps = [e for e in phases if e["name"] == "step"]
    assert len(steps) == eng.steps > 3
    names = set()
    for st in steps:
        a, b = st["ts"], st["ts"] + st["dur"]
        inner = sorted((e["ts"], e["ts"] + e["dur"]) for e in phases
                       if e is not st and a <= e["ts"] <= b)
        names |= {e["name"] for e in phases
                  if e is not st and a <= e["ts"] <= b}
        edge = a
        for x, y in inner:
            assert x - edge <= tick * 1e6 * 1.5, (st, x - edge)
            edge = max(edge, y)
        assert b - edge <= tick * 1e6 * 1.5
    assert {"admit", "plan", "dispatch", "sync", "retire",
            "publish"} <= names


def test_phases_reach_the_profiler_trace(model, tmp_path):
    """With tracing on, each phase is a host annotation in the JAX
    profiler's trace: every ``engine.*`` phase lies inside an
    ``engine.step`` on the same thread, and the step carries its
    index."""
    import glob
    import os
    from jax.profiler import ProfileData
    _, eng = _chunked_engine(model, trace=True)
    for r in _chunked_requests():
        eng.submit(r)
    with jax.profiler.trace(str(tmp_path)):
        eng.run_until_drained()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    steps, phases = {}, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("engine."):
                    continue
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == "engine.step":
                    steps.setdefault((plane.name, line.name), []).append(iv)
                    assert "step" in dict(ev.stats)
                else:
                    phases.append(((plane.name, line.name), ev.name, iv))
    assert sum(map(len, steps.values())) == eng.steps
    assert {n for _, n, _ in phases} >= {
        "engine.admit", "engine.plan", "engine.dispatch", "engine.sync",
        "engine.retire", "engine.publish"}
    for where, name, (a, b) in phases:
        assert any(x <= a and b <= y for x, y in steps[where]), name


def test_untraced_run_never_annotates(model, monkeypatch):
    """With tracing off neither the engine nor the frontend creates a
    profiler annotation."""
    import asyncio

    from repro.launch.serve import AsyncServingFrontend

    def boom(*a, **k):
        raise AssertionError("annotation created with tracing off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", boom)
    _, eng = _chunked_engine(model)

    async def serve():
        fe = AsyncServingFrontend(eng)
        await fe.start()
        handles = [fe.submit(r) for r in _chunked_requests()]
        for h in handles:
            await h.done
        await fe.shutdown()

    asyncio.run(serve())
    assert eng.tracer is None
    assert sorted(len(r.generated) for r in eng.completed) == [4, 4]
