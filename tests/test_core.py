"""EdgeAI-Hub core: scheduler, orchestrator, placement, network, zones."""
import math

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import trustzones as tz
from repro.core.network import CHANNEL_CATALOGUE, MultiChannelLink
from repro.core.orchestrator import Orchestrator, TaskSpec
from repro.core.hub import EdgeAIHub, default_home
from repro.core.placement import PlacementOption, greedy_partition, \
    solve_knapsack
from repro.core.perf_model import DEVICE_CATALOGUE, estimate, inference_cost
from repro.core.scheduler import AITask, EdgeScheduler
from repro.configs import get_config, get_smoke_config


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def _tasks(durations, device="d0", **kw):
    return [AITask(uid=i, kind="inference", duration_s=d, device=device, **kw)
            for i, d in enumerate(durations)]


def test_fifo_order():
    s = EdgeScheduler("fifo")
    for t in _tasks([1.0, 1.0, 1.0]):
        s.submit(t)
    done = s.run()
    assert [t.uid for t in done] == [0, 1, 2]


def test_qoe_p99_is_ceil_quantile():
    """Regression: the old p99 index ``int(0.99*n) - 1`` was biased LOW
    for small samples (n=2 reported the MINIMUM latency as "p99").  The
    tail quantile must match np.percentile(..., method='higher')."""
    import numpy as np
    from repro.core.scheduler import quantile_higher

    rng = np.random.default_rng(0)
    for n in range(1, 12):
        vals = rng.uniform(0.1, 9.0, n).tolist()
        expect = float(np.percentile(vals, 99, method="higher"))
        assert quantile_higher(vals, 0.99) == pytest.approx(expect), n

    # end-to-end: two sequential tasks — p99 latency is the LONGER one
    s = EdgeScheduler("fifo")
    for t in _tasks([1.0, 3.0]):
        s.submit(t)
    s.run()
    rep = s.qoe_report()
    assert rep["p99_latency_s"] == pytest.approx(4.0)  # 1.0 wait + 3.0
    with pytest.raises(ValueError):
        quantile_higher([], 0.99)


def test_priority_preemption():
    s = EdgeScheduler("priority")
    low = AITask(uid=0, kind="inference", duration_s=10.0, device="d",
                 priority=0, arrival=0.0)
    high = AITask(uid=1, kind="stream", duration_s=1.0, device="d",
                  priority=5, arrival=2.0)
    s.submit(low)
    s.submit(high)
    done = s.run()
    assert done[0].uid == 1 and done[0].finish_time == pytest.approx(3.0)
    assert done[1].preemptions == 1
    assert done[1].finish_time == pytest.approx(11.0)  # banked progress


def test_edf_meets_feasible_deadlines():
    s = EdgeScheduler("edf")
    s.submit(AITask(uid=0, kind="i", duration_s=2.0, device="d",
                    deadline=10.0, arrival=0.0))
    s.submit(AITask(uid=1, kind="i", duration_s=1.0, device="d",
                    deadline=2.0, arrival=0.5))
    done = s.run()
    assert all(not t.missed_deadline for t in done)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(
    st.floats(0.01, 5.0),      # duration
    st.floats(0.0, 10.0),      # arrival
    st.integers(0, 3)),        # priority
    min_size=1, max_size=12))
def test_scheduler_invariants(spec):
    """Property: every task completes exactly once, start >= arrival,
    finish = start + total duration accounting preemption gaps, and the
    device never runs two tasks at once."""
    s = EdgeScheduler("priority")
    for i, (dur, arr, pri) in enumerate(spec):
        s.submit(AITask(uid=i, kind="i", duration_s=dur, device="d",
                        arrival=arr, priority=pri))
    done = s.run()
    assert sorted(t.uid for t in done) == list(range(len(spec)))
    for t in done:
        assert t.start_time >= t.arrival - 1e-9
        assert t.finish_time >= t.start_time + t.duration_s - 1e-6
    # non-overlap of execution on the single device
    spans = []
    running = {}
    for time_, ev, uid, dev in s.trace:
        if ev == "start":
            running[uid] = time_
        elif ev in ("preempt", "finish"):
            spans.append((running.pop(uid), time_))
    spans.sort()
    for (s0, e0), (s1, e1) in zip(spans, spans[1:]):
        assert s1 >= e0 - 1e-9


def test_scheduler_determinism():
    def run():
        s = EdgeScheduler("edf")
        for i in range(8):
            s.submit(AITask(uid=i, kind="i", duration_s=0.5 + i * 0.1,
                            device="d", arrival=i * 0.2,
                            deadline=i * 0.2 + 3, priority=i % 2))
        s.run()
        return [(t.uid, t.finish_time) for t in s.completed]
    assert run() == run()


# ---------------------------------------------------------------------------
# orchestrator: placement, trust zones, fault tolerance
# ---------------------------------------------------------------------------

def test_orchestrator_places_training_on_hub():
    hub = EdgeAIHub.create()
    spec = TaskSpec(kind="training", model=get_smoke_config("gemma3-1b"),
                    batch=8, seq=128)
    placement = hub.orchestrator.place(spec)
    assert placement.device == "hub"  # only train-capable device


def test_orchestrator_respects_zones():
    hub = EdgeAIHub.create()
    data = tz.DataItem("alice-health", "personal", "alice")
    spec = TaskSpec(kind="inference", model=get_smoke_config("gemma3-1b"),
                    batch=1, seq=64, data=data)
    placement = hub.orchestrator.place(spec)
    owner = hub.registry.get(placement.device).owner
    zone = hub.registry.get(placement.device).zone
    assert tz.allowed(data, placement.device, zone, owner)
    assert placement.device not in ("bob-phone", "bob-old-phone")


def test_fault_tolerance_reassigns():
    hub = EdgeAIHub.create()
    cfg = get_smoke_config("gemma3-1b")
    uid = hub.submit(TaskSpec(kind="inference", model=cfg, batch=64,
                              seq=2048, arrival=0.0))
    placement = hub.orchestrator._task_meta[uid][1]
    moved = hub.orchestrator.fail_device(placement.device)
    assert moved  # task was re-placed
    new_dev = hub.orchestrator._task_meta[moved[0]][1].device
    assert new_dev != placement.device
    rep = hub.run()
    assert rep["completed"] >= 1


def test_historical_estimator_updates():
    hub = EdgeAIHub.create()
    cfg = get_smoke_config("gemma3-1b")
    for _ in range(3):
        hub.submit(TaskSpec(kind="inference", model=cfg, batch=1, seq=64))
    hub.run()
    key = hub.orchestrator._task_kind(
        TaskSpec(kind="inference", model=cfg, batch=1, seq=64))
    devs = [n for n in hub.registry.names()
            if hub.orchestrator.history.predict(key, n) is not None]
    assert devs


# ---------------------------------------------------------------------------
# placement knapsack
# ---------------------------------------------------------------------------

def test_knapsack_beats_greedy_or_ties():
    opts = [
        PlacementOption("hub", "npu-train", cost=8, utility=10.0),
        PlacementOption("hub", "npu-infer", cost=4, utility=6.0),
        PlacementOption("phone", "npu-infer", cost=5, utility=5.5),
        PlacementOption("tv", "npu-infer", cost=3, utility=3.0),
        PlacementOption("sensor", "none", cost=0, utility=0.5),
    ]
    exact, u_exact = solve_knapsack(opts, budget=12)
    greedy, u_greedy = greedy_partition(opts, budget=12)
    assert u_exact >= u_greedy - 1e-9
    assert sum(o.cost for o in exact) <= 12
    devices = [o.device for o in exact]
    assert len(devices) == len(set(devices))  # one option per device


@settings(max_examples=30, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 4), st.integers(1, 9),
                          st.floats(0.1, 10)), min_size=1, max_size=8),
       st.integers(1, 20))
def test_knapsack_feasible_and_optimal_vs_greedy(items, budget):
    opts = [PlacementOption(f"d{d}", "acc", cost=c, utility=u)
            for d, c, u in items]
    exact, u_exact = solve_knapsack(opts, budget)
    assert sum(o.cost for o in exact) <= budget
    _, u_greedy = greedy_partition(opts, budget)
    assert u_exact >= u_greedy - 1e-9


# ---------------------------------------------------------------------------
# network
# ---------------------------------------------------------------------------

def test_multichannel_striping_beats_single():
    link = MultiChannelLink([CHANNEL_CATALOGUE["wifi6"],
                             CHANNEL_CATALOGUE["5g-local"]])
    payload = 100e6  # 100 MB
    striped = link.send(payload).latency_s
    _, single = link.best_single_channel(payload)
    assert striped < single


def test_bandwidth_slicing():
    link = MultiChannelLink([CHANNEL_CATALOGUE["wifi6"]])
    assert link.reserve("stream", 0.6)
    assert not link.reserve("other", 0.6)   # over-subscribed
    assert link.reserve("other", 0.4)
    link.release("stream")
    assert link.reserve("third", 0.5)


def test_small_payload_prefers_low_latency_channel():
    link = MultiChannelLink([CHANNEL_CATALOGUE["wifi-legacy"],
                             CHANNEL_CATALOGUE["uwb"]])
    ch, _ = link.best_single_channel(100.0)       # 100 B ping
    assert ch.name == "uwb"
    ch, _ = link.best_single_channel(500e6)       # bulk transfer
    assert ch.name == "wifi-legacy"


# ---------------------------------------------------------------------------
# trust zones
# ---------------------------------------------------------------------------

def test_zone_lattice():
    pol = tz.ZonePolicy()
    assert pol.zone_allows("public", "household")
    assert pol.zone_allows("personal", "personal")
    assert not pol.zone_allows("work", "household")
    assert not pol.zone_allows("household", "work")


def test_acl_overrides():
    d = tz.DataItem("doc", "work", "alice",
                    acl_allow=frozenset({"hub"}),
                    acl_deny=frozenset({"bob-phone"}))
    assert tz.allowed(d, "hub", "household", "alice")       # explicit allow
    assert not tz.allowed(d, "bob-phone", "work", "bob")    # explicit deny
    assert not tz.allowed(d, "tv", "household", "alice")    # zone blocks


@settings(max_examples=40, deadline=None)
@given(st.sampled_from(["personal", "household", "work", "public"]),
       st.sampled_from(["personal", "household", "work", "public"]),
       st.booleans())
def test_personal_data_never_leaves_owner(data_zone, dev_zone, same_owner):
    d = tz.DataItem("x", data_zone, "alice")
    owner = "alice" if same_owner else "eve"
    if data_zone == "personal" and not same_owner:
        assert not tz.allowed(d, "dev", dev_zone, owner)


def test_check_raises():
    d = tz.DataItem("x", "work", "alice")
    with pytest.raises(tz.AccessError):
        tz.check(d, "tv", "household", "alice")
