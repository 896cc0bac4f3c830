"""Chaos harness + lane supervision + serve retry (PR 8).

Three layers under test, bottom-up:

1. the harness itself (``repro.runtime.chaos``): spec validation/env
   parsing, seeded determinism of fault placement, the kill switch;
2. the substrate's reaction: Relic bounded waits raise ``RelicDeadError``
   with exact loss accounting, RelicPool quarantines/respawns dead lanes
   with the lost count *deterministically* equal to the dead ring's
   in-flight count (the PR's acceptance criterion, at lanes 2 and 4);
3. the serve layer's recovery: idempotent requests retried across task
   errors and lane death, everything else failing fast.

Every fault here is injected deterministically (seeded plans, counted kill
switches) — no sleeps-as-synchronization, no flaky timing assumptions
beyond "a live lane eventually drains its ring".
"""

import threading
import time

import pytest

from repro.core.relic import Relic, RelicDeadError
from repro.core.relic_pool import LaneFailedError, RelicPool
from repro.core.schedulers import make_scheduler
from repro.runtime.chaos import (
    ChaosInjectedError,
    ChaosScheduler,
    ChaosSpec,
    FaultPlan,
    KillSwitch,
    plan_bursts,
)
from repro.serve import RetryPolicy, ServeScheduler
from repro.serve.request import STATUS_ERROR


# ------------------------------------------------------------------- spec


def test_chaos_spec_validation():
    with pytest.raises(ValueError, match="raise_rate"):
        ChaosSpec(raise_rate=1.5)
    with pytest.raises(ValueError, match="stall_rate"):
        ChaosSpec(stall_rate=-0.1)
    with pytest.raises(ValueError, match="exceed 1"):
        ChaosSpec(raise_rate=0.6, stall_rate=0.6)
    with pytest.raises(ValueError, match="stall_s"):
        ChaosSpec(stall_s=-1.0)
    with pytest.raises(ValueError, match="kill_after"):
        ChaosSpec(kill_after=-1)
    with pytest.raises(ValueError, match="burst"):
        ChaosSpec(burst=-2)


def test_chaos_spec_default_is_semantics_preserving():
    # The registered "chaos" substrate runs the full conformance suite
    # under the default spec: it must not replace any task's effect.
    spec = ChaosSpec()
    assert spec.raise_rate == 0.0
    assert spec.stall_rate > 0.0


def test_chaos_spec_from_env(monkeypatch):
    monkeypatch.delenv("RELIC_CHAOS", raising=False)
    assert ChaosSpec.from_env() == ChaosSpec()
    monkeypatch.setenv(
        "RELIC_CHAOS",
        "seed=7, raise_rate=0.25, stall_rate=0.1, stall_s=0.001,"
        " kill_after=3, burst=4, inner=spin")
    spec = ChaosSpec.from_env()
    assert spec == ChaosSpec(seed=7, raise_rate=0.25, stall_rate=0.1,
                             stall_s=0.001, kill_after=3, burst=4,
                             inner="spin")
    monkeypatch.setenv("RELIC_CHAOS", "kill_after=none")
    assert ChaosSpec.from_env().kill_after is None


def test_chaos_spec_from_env_rejects_garbage(monkeypatch):
    monkeypatch.setenv("RELIC_CHAOS", "warp_speed=9")
    with pytest.raises(ValueError, match="unknown key"):
        ChaosSpec.from_env()
    monkeypatch.setenv("RELIC_CHAOS", "seed=banana")
    with pytest.raises(ValueError, match="bad value"):
        ChaosSpec.from_env()
    monkeypatch.setenv("RELIC_CHAOS", "just-noise")
    with pytest.raises(ValueError, match="key=value"):
        ChaosSpec.from_env()


# ------------------------------------------------------------------- plan


def test_fault_plan_is_deterministic():
    spec = ChaosSpec(seed=42, raise_rate=0.3, stall_rate=0.3)
    fn = lambda: None  # noqa: E731

    def classify(plan):
        out = []
        for _ in range(200):
            d = plan.decorate(fn)
            out.append("none" if d is fn else d.__name__)
        return out

    a = classify(FaultPlan(spec))
    b = classify(FaultPlan(spec))
    assert a == b
    assert "chaos_raise" in a and "chaos_stall" in a and "none" in a
    other = classify(FaultPlan(ChaosSpec(seed=43, raise_rate=0.3,
                                         stall_rate=0.3)))
    assert a != other


def test_fault_plan_wrappers_behave():
    plan = FaultPlan(ChaosSpec(raise_rate=1.0, stall_rate=0.0))
    with pytest.raises(ChaosInjectedError):
        plan.decorate(lambda: 1)()
    assert plan.injected_raises == 1

    plan = FaultPlan(ChaosSpec(raise_rate=0.0, stall_rate=1.0, stall_s=0.0))
    assert plan.decorate(lambda x: x + 1)(2) == 3   # result preserved

    def boom():
        raise KeyError("real")

    with pytest.raises(KeyError):                   # real errors preserved
        plan.decorate(boom)()
    assert plan.injected_stalls == 2


def test_plan_bursts_deterministic_and_exact():
    spec = ChaosSpec(seed=5, burst=4)
    a = plan_bursts(spec, 37)
    assert a == plan_bursts(spec, 37)
    assert sum(a) == 37
    assert all(1 <= n <= 4 for n in a)
    assert plan_bursts(ChaosSpec(burst=0), 3) == [1, 1, 1]
    assert plan_bursts(spec, 0) == []
    with pytest.raises(ValueError, match="total"):
        plan_bursts(spec, -1)


# ---------------------------------------------------------------- the pair


def test_kill_switch_validation():
    with pytest.raises(ValueError, match="after_bursts"):
        KillSwitch(after_bursts=-1)


def test_relic_bounded_wait_raises_on_dead_assistant():
    r = Relic(capacity=8).start()
    KillSwitch(after_bursts=0).arm(r)
    sink = []
    for i in range(8):
        r.submit(sink.append, i)
    with pytest.raises(RelicDeadError) as ei:
        r.wait()
    err = ei.value
    # Exact loss accounting: whatever the assistant popped-but-never-ran
    # plus whatever is still on the ring, and nothing was double-counted.
    assert err.submitted == 8
    assert err.lost == err.submitted - err.completed
    assert err.lost > 0
    assert "dead" in str(err)
    # A dead pair is not restartable, but shutdown must not hang.
    r.shutdown()


def test_relic_submit_slow_path_raises_on_dead_assistant():
    # Fill the ring past capacity with the assistant dead: the producer's
    # full-ring spin must raise, not hang (the pre-PR8 behaviour).
    r = Relic(capacity=4).start()
    KillSwitch(after_bursts=0).arm(r)
    with pytest.raises(RelicDeadError):
        for i in range(64):
            r.submit(time.sleep, 0)


# ---------------------------------------------------------------- the pool


@pytest.mark.parametrize("lanes", [2, 4])
def test_pool_quarantine_loss_is_exact(lanes):
    # Acceptance criterion: kill one lane under load; the lost count the
    # pool reports equals the dead ring's in-flight count exactly, and the
    # global ledger submitted == completed + lost stays balanced.
    pool = RelicPool(lanes=lanes, capacity=64).start()
    ks = KillSwitch(after_bursts=0).arm(pool._lanes[1])
    total = 50 * lanes
    for i in range(total):
        pool.submit(time.sleep, 0)
    with pytest.raises(LaneFailedError) as ei:
        pool.wait()
    err = ei.value
    assert ks.fired
    assert len(err.failures) == 1
    f = err.failures[0]
    assert f.lane_index == 1
    assert not f.respawned
    assert f.lost == f.submitted - f.completed
    assert f.lost > 0
    assert err.lost == f.lost == pool.lost_tasks
    assert pool.stats.completed + pool.lost_tasks == pool.stats.submitted
    assert pool.live_lanes == tuple(i for i in range(lanes) if i != 1)
    pool.shutdown()


@pytest.mark.parametrize("lanes", [2, 4])
def test_pool_respawn_recovers_capacity(lanes):
    pool = RelicPool(lanes=lanes, capacity=64, respawn=True).start()
    ks = KillSwitch(after_bursts=0).arm(pool._lanes[1])
    total = 50 * lanes
    for i in range(total):
        pool.submit(time.sleep, 0)
    with pytest.raises(LaneFailedError) as ei:
        pool.wait()
    f = ei.value.failures[0]
    assert ks.fired and f.respawned and f.lost > 0
    # The replacement lane is live and serving again at full width.
    assert pool.live_lanes == tuple(range(lanes))
    before = pool.stats.completed
    for i in range(total):
        pool.submit(time.sleep, 0)
    pool.wait()                        # clean: the failure was consumed
    assert pool.stats.completed == before + total
    assert pool.lost_tasks == f.lost   # no further loss
    assert pool.in_flight_estimate() == 0
    pool.shutdown()


def test_pool_fully_dead_keeps_raising():
    pool = RelicPool(lanes=2, capacity=16).start()
    KillSwitch(after_bursts=0).arm(pool._lanes[0])
    KillSwitch(after_bursts=0).arm(pool._lanes[1])
    for i in range(16):
        pool.submit(time.sleep, 0)
    with pytest.raises(LaneFailedError):
        pool.wait()
    # Permanently dead: every later wait()/submit keeps saying so rather
    # than silently succeeding against nothing.
    with pytest.raises(LaneFailedError):
        pool.wait()
    with pytest.raises(LaneFailedError):
        for i in range(1000):
            pool.submit(time.sleep, 0)
    pool.shutdown()


def test_pool_burst_raises_once_every_lane_died_mid_sweep():
    """A burst whose remainders are stuck on rings whose assistants all
    died (respawn off) has no live lane to re-stripe to: the sweep's
    liveness probe quarantines them and raises instead of spinning. The
    producer runs on a thread of its own so a hang fails the test."""
    outcome = []

    def producer():
        pool = RelicPool(lanes=2, capacity=2, start_awake=True).start()
        switches = [KillSwitch(after_bursts=0).arm(lane)
                    for lane in pool._lanes]
        pool.submit(time.sleep, 0)
        pool.submit(time.sleep, 0)
        deadline = time.time() + 5
        while (any(lane._assistant.is_alive() for lane in pool._lanes)
               and time.time() < deadline):
            time.sleep(0.001)
        assert all(ks.fired for ks in switches)
        try:
            pool.submit_batch([(time.sleep, (0,), {})] * 20)
        except LaneFailedError as e:
            outcome.append((e, pool.live_lanes))
        pool.shutdown()

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    t.join(10)
    assert not t.is_alive(), "the sweep spun on a fully dead pool"
    assert len(outcome) == 1
    assert outcome[0][1] == ()


def test_pool_scheduler_adapter_surfaces_lane_failures():
    sched = make_scheduler("relic-pool", lanes=2, capacity=32,
                           respawn=True).start()
    pool = sched._pool
    ks = KillSwitch(after_bursts=0).arm(pool._lanes[0])
    for i in range(80):
        sched.submit(time.sleep, 0)
    deadline = time.monotonic() + 5.0
    failures = []
    while not failures and time.monotonic() < deadline:
        failures = sched.poll_lane_failures()
        time.sleep(0)
    assert ks.fired
    assert [f.lane_index for f in failures] == [0]
    assert failures[0].respawned
    # Consumed via polling: wait() no longer raises for it.
    while sched.in_flight_estimate() > 0 and time.monotonic() < deadline:
        time.sleep(0)
    assert sched.in_flight_estimate() == 0
    sched.close()


# ------------------------------------------------------------- chaos sched


def test_chaos_scheduler_injects_raises():
    spec = ChaosSpec(raise_rate=1.0, stall_rate=0.0)
    with ChaosScheduler(spec=spec) as sched:
        sched.submit(lambda: 1)
        with pytest.raises(ChaosInjectedError):
            sched.wait()


def test_chaos_scheduler_registered():
    sched = make_scheduler("chaos")
    assert isinstance(sched, ChaosScheduler)
    with sched:
        out = []
        sched.submit(out.append, 1)
        sched.wait()
    assert out == [1]


# ------------------------------------------------------------- retry policy


def test_retry_policy_validation():
    with pytest.raises(ValueError, match="max_attempts"):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError, match="multiplier"):
        RetryPolicy(multiplier=0.5)
    with pytest.raises(ValueError, match="max_backoff_s"):
        RetryPolicy(base_backoff_s=1.0, max_backoff_s=0.5)
    with pytest.raises(ValueError, match="jitter"):
        RetryPolicy(jitter=1.0)


def test_retry_policy_from_env(monkeypatch):
    from repro.runtime.config import resolve_serve_config
    monkeypatch.setenv("RELIC_SERVE_RETRIES", "5")
    policy = RetryPolicy.from_config(resolve_serve_config())
    assert policy.max_attempts == 6 and policy.retries == 5
    assert policy.allows(5) and not policy.allows(6)


def test_retry_policy_delay_is_deterministic_and_bounded():
    p = RetryPolicy(max_attempts=5, base_backoff_s=0.001, multiplier=2.0,
                    max_backoff_s=0.004, jitter=0.5, seed=3)
    for attempt in range(1, 5):
        d1 = p.delay(rid=17, attempt=attempt)
        d2 = p.delay(rid=17, attempt=attempt)
        assert d1 == d2
        cap = min(0.001 * 2 ** (attempt - 1), 0.004)
        assert 0.5 * cap <= d1 <= 1.5 * cap
    assert p.delay(17, 1) != p.delay(18, 1)   # jitter varies per request
    with pytest.raises(ValueError, match="attempt"):
        p.delay(0, 0)


# ------------------------------------------------------------- serve retry


def _flaky(counter, fail_times, key="k"):
    counter[key] = counter.get(key, 0) + 1
    if counter[key] <= fail_times:
        raise RuntimeError(f"boom {counter[key]}")
    return counter[key]


def test_serve_retries_idempotent_task_error():
    calls = {}
    with ServeScheduler(lanes=2) as server:
        client = server.open_client()
        resp = client.submit(_flaky, calls, 2, deadline_s=30.0,
                             idempotent=True)
        assert resp.result(timeout=30) == 3
        assert resp.attempts == 3
    assert server.stats()["retries"] == 2


def test_serve_fails_fast_without_idempotent():
    calls = {}
    with ServeScheduler(lanes=2) as server:
        client = server.open_client()
        resp = client.submit(_flaky, calls, 2, deadline_s=30.0)
        with pytest.raises(RuntimeError, match="boom 1"):
            resp.result(timeout=30)
        assert resp.attempts == 1


def test_serve_retry_budget_exhausts_to_error():
    calls = {}
    policy = RetryPolicy(max_attempts=2, jitter=0.0, base_backoff_s=0.0)
    with ServeScheduler(lanes=2, retry_policy=policy) as server:
        client = server.open_client()
        resp = client.submit(_flaky, calls, 5, deadline_s=30.0,
                             idempotent=True)
        with pytest.raises(RuntimeError, match="boom 2"):
            resp.result(timeout=30)
        assert resp.attempts == 2
        assert resp.status == STATUS_ERROR


def test_serve_inline_mode_retries_too():
    calls = {}
    with ServeScheduler(lanes=0) as server:
        client = server.open_client()
        resp = client.submit(_flaky, calls, 1, deadline_s=30.0,
                             idempotent=True)
        assert resp.result(timeout=30) == 2
        assert resp.attempts == 2


def test_serve_lane_death_retries_idempotent_requests():
    with ServeScheduler(lanes=4) as server:
        client = server.open_client()
        deadline = time.monotonic() + 5.0
        while server._sched is None and time.monotonic() < deadline:
            time.sleep(0)
        pool = server._sched._pool
        ks = KillSwitch(after_bursts=0).arm(pool._lanes[1])
        resps = [client.submit(time.sleep, 0, deadline_s=30.0,
                               idempotent=True) for _ in range(300)]
        for r in resps:
            assert r.result(timeout=30) is None
        snap = server.stats()
        assert ks.fired
        assert snap["lane_failures"] >= 1
        retried = sum(1 for r in resps if r.attempts > 1)
        assert snap["lost_requests"] == retried
        assert pool.live_lanes == (0, 1, 2, 3)   # respawned under serve


def test_serve_lane_death_errors_non_idempotent_requests():
    with ServeScheduler(lanes=2) as server:
        client = server.open_client()
        deadline = time.monotonic() + 5.0
        while server._sched is None and time.monotonic() < deadline:
            time.sleep(0)
        pool = server._sched._pool
        ks = KillSwitch(after_bursts=0).arm(pool._lanes[0])
        resps = [client.submit(time.sleep, 0, deadline_s=30.0)
                 for _ in range(200)]
        outcomes = set()
        lost = 0
        for r in resps:
            assert r.wait(timeout=30)
            outcomes.add(r.status)
            if r.status == STATUS_ERROR:
                lost += 1
                assert isinstance(r.error, LaneFailedError)
        assert ks.fired
        assert lost == server.stats()["lost_requests"]
        assert lost > 0


def test_serve_stats_surface_robustness_fields():
    with ServeScheduler(lanes=2) as server:
        snap = server.stats()
    for key in ("retries", "lane_failures", "lost_requests",
                "stalled_lanes", "straggler_lanes"):
        assert key in snap
