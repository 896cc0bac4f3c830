"""RelicPool semantics: lane striping, broadcast hints, cross-lane errors.

The pool-specific half of the PR 5 coverage (the generic Scheduler contract
for ``relic-pool``/``relic2``/``relic4`` lives in the conformance suite,
which parametrizes over every registered substrate automatically).
"""

import threading
import time

import pytest

from repro.core.relic import Relic, RelicUsageError
from repro.core.relic_pool import RelicPool
from repro.core.schedulers import make_scheduler
from repro.core.spsc import SpscRing
from repro.runtime.config import resolve_spin_pause_every

LANE_COUNTS = [1, 2, 4]


# ------------------------------------------------------------ lane striping

@pytest.mark.parametrize("lanes", LANE_COUNTS)
def test_submit_stripes_round_robin_over_every_lane(lanes):
    """Single submissions land on all lanes, evenly (pure round-robin when
    no ring ever fills)."""
    done = []
    with RelicPool(lanes=lanes, start_awake=True) as pool:
        for i in range(8 * lanes):
            pool.submit(done.append, i)
        pool.wait()
    assert sorted(done) == list(range(8 * lanes))
    assert [s.submitted for s in pool.stats.lanes] == [8] * lanes


@pytest.mark.parametrize("lanes", LANE_COUNTS)
def test_submit_batch_shards_across_every_lane_in_one_pass(lanes):
    """A burst is dealt out as contiguous near-equal shards."""
    done = []
    with RelicPool(lanes=lanes, start_awake=True) as pool:
        pool.submit_batch([(done.append, (i,), {}) for i in range(8 * lanes)])
        pool.wait()
    assert sorted(done) == list(range(8 * lanes))
    assert [s.submitted for s in pool.stats.lanes] == [8] * lanes


def test_small_burst_rotates_lanes_across_bursts():
    """A burst smaller than the lane count advances the round-robin cursor
    by its remainder, so successive small bursts cover all lanes."""
    with RelicPool(lanes=4, start_awake=True) as pool:
        for _ in range(4):
            pool.submit_batch([(lambda: None, (), {})] * 3)
        pool.wait()
    assert [s.submitted for s in pool.stats.lanes] == [3, 3, 3, 3]


def test_each_lane_preserves_fifo_locally():
    """The SPSC invariant survives pooling: per-lane completion order is
    per-lane submission order (global order is explicitly NOT promised)."""
    lanes = 3
    per_lane = [[] for _ in range(lanes)]
    with RelicPool(lanes=lanes, start_awake=True) as pool:
        for i in range(60):
            # round-robin: submission i goes to lane i % lanes
            per = per_lane[i % lanes]
            pool.submit(per.append, i)
        pool.wait()
    for lane_idx, got in enumerate(per_lane):
        assert got == sorted(got), f"lane {lane_idx} reordered"
        assert [g % lanes for g in got] == [lane_idx] * len(got)


def test_single_lane_pool_is_globally_fifo():
    out = []
    with RelicPool(lanes=1, start_awake=True) as pool:
        for i in range(200):
            pool.submit(out.append, i)
        pool.wait()
    assert out == list(range(200))


def test_full_lane_falls_back_to_least_loaded():
    """When the round-robin target's ring is full, submit() places the task
    on another (least-loaded) lane instead of spinning on the full one —
    even while the full lane's assistant is wedged behind a long task.
    Where an overflowed task lands — lane 1's primary, or a handoff ring
    when every primary is momentarily full — depends on how fast lane 1
    drains, so the counts are per lane across both rings: what is fixed
    is that the wedged lane's primary takes nothing past its capacity."""
    gate = threading.Event()
    with RelicPool(lanes=2, capacity=2, start_awake=True) as pool:
        pool.submit(gate.wait)          # lane 0's assistant blocks here
        # Deterministic: wait until lane 0's assistant has actually popped
        # the blocker (ring drained) before filling the ring — a fixed
        # sleep makes the submitted-count assertions flaky on a loaded
        # runner.
        deadline = time.time() + 5
        while len(pool._lanes[0]._ring) and time.time() < deadline:
            time.sleep(0.001)
        assert not len(pool._lanes[0]._ring), "assistant never popped"
        # Fill lane 0's ring while it is blocked. Round-robin alternates,
        # so submit 2*capacity+1 tasks: lane 0 receives capacity and is
        # full, after which its round-robin turns must overflow to lane 1.
        for i in range(8):
            pool.submit(lambda: None)
        lane0, lane1 = pool.stats.lanes
        primary = [len(r) for r in pool._runs]
        handoff = [len(r) for r in pool._oruns]
        assert primary[0] == 3          # the blocker + its full ring (cap 2)
        assert lane0.submitted == primary[0] + handoff[0]
        assert lane1.submitted == primary[1] + handoff[1]
        # Every task past lane 0's capacity went elsewhere: lane 1 (either
        # ring) or lane 0's handoff ring, at lane-idle priority.
        assert lane1.submitted + handoff[0] == 6
        gate.set()
        pool.wait()
        assert pool.stats.completed == 9


# ----------------------------------------------------------- hint broadcast

def test_hints_broadcast_to_every_lane():
    lanes = 3
    pool = RelicPool(lanes=lanes).start()       # start_awake=False: parked
    try:
        time.sleep(0.05)
        assert sum(s.parks for s in pool.stats.lanes) == lanes
        pool.wake_up_hint()
        time.sleep(0.05)
        for lane in pool._lanes:
            assert lane._awake.is_set()
        pool.sleep_hint()
        for lane in pool._lanes:
            assert not lane._awake.is_set()
        # Advisory rule survives broadcast: a barrier over parked lanes
        # un-parks them rather than deadlocking.
        done = []
        for i in range(6):
            pool.submit(done.append, i)
        pool.wait()
        assert sorted(done) == list(range(6))
    finally:
        pool.shutdown()


# ----------------------------------------------- first-error-wins across lanes

def test_first_error_by_submission_order_wins_across_lanes():
    """Submission order, not lane order, decides which error wait()
    re-raises: a later-submitted failure on lane 0 must lose to an
    earlier-submitted failure on lane 1."""

    def boom(exc):
        raise exc

    with RelicPool(lanes=2, start_awake=True) as pool:
        pool.submit(lambda: None)               # seq 0 -> lane 0
        pool.submit(boom, IndexError("seq 1"))  # seq 1 -> lane 1 (earliest)
        pool.submit(boom, ValueError("seq 2"))  # seq 2 -> lane 0
        pool.submit(boom, KeyError("seq 3"))    # seq 3 -> lane 1
        with pytest.raises(IndexError, match="seq 1"):
            pool.wait()
        assert pool.stats.task_errors == 3
        # The channel is cleared: the next window's own first error wins.
        pool.submit(boom, ZeroDivisionError())  # lane 0
        with pytest.raises(ZeroDivisionError):
            pool.wait()
        assert pool.stats.task_errors == 4
        done = []
        pool.submit(done.append, "after")       # still usable
        pool.wait()
        assert done == ["after"]


def test_first_error_ordering_covers_submit_batch_shards():
    """Shard striping keeps the submission-order error rule: the earliest
    failing task of a burst wins even when a lower-numbered lane also
    fails (with a later task of the same burst)."""

    def boom(exc):
        raise exc

    tasks = [(lambda: None, (), {}) for _ in range(8)]
    # lanes=2, burst of 8 -> lane 0 gets seqs 0-3, lane 1 gets seqs 4-7.
    tasks[4] = (boom, (IndexError("seq 4"),), {})   # lane 1, earliest failure
    tasks[6] = (boom, (KeyError("seq 6"),), {})     # lane 1
    tasks[5] = (boom, (ValueError("seq 5"),), {})   # lane 1
    tasks[7] = (boom, (OSError("seq 7"),), {})      # lane 1
    with RelicPool(lanes=2, start_awake=True) as pool:
        pool.submit_batch(tasks)
        with pytest.raises(IndexError, match="seq 4"):
            pool.wait()
        assert pool.stats.task_errors == 4


def test_rotated_burst_error_ordering_beats_lane_order():
    """Discriminates seq-order from lane-order: after the cursor rotates,
    the HIGHER-numbered lane holds the earlier seqs of the next burst —
    its failure must win over a lower-numbered lane's later failure (an
    implementation ordering errors by lane index would raise the wrong
    one)."""

    def boom(exc):
        raise exc

    with RelicPool(lanes=2, start_awake=True) as pool:
        # burst of 3: rem=1 advances the cursor to lane 1 (seqs 0-2 ok)
        pool.submit_batch([(lambda: None, (), {})] * 3)
        # burst of 8 from cursor=1: lane 1 gets seqs 3-6, lane 0 seqs 7-10
        tasks = [(lambda: None, (), {}) for _ in range(8)]
        tasks[2] = (boom, (IndexError("early, lane 1"),), {})  # seq 5
        tasks[5] = (boom, (ValueError("late, lane 0"),), {})   # seq 8
        pool.submit_batch(tasks)
        lane0, lane1 = pool.stats.lanes
        assert lane0.submitted == 6 and lane1.submitted == 5  # rotation held
        with pytest.raises(IndexError, match="early, lane 1"):
            pool.wait()
        assert pool.stats.task_errors == 2


def test_burst_shards_flow_past_a_wedged_lane():
    """Two-phase burst delivery: a lane wedged behind a long task (its
    ring full) must not stop the other lanes' shards of the same burst
    from being delivered and run — including the cross-shard-dependency
    shape where the wedged task itself waits on later-shard work."""
    release = threading.Event()
    other_done = threading.Event()
    with RelicPool(lanes=2, capacity=2, start_awake=True) as pool:
        pool.submit(release.wait)       # wedge lane 0 (popped, blocking)
        deadline = time.time() + 5
        while len(pool._lanes[0]._ring) and time.time() < deadline:
            time.sleep(0.001)
        pool.submit(lambda: None)       # lane 1's rr turn
        pool.submit(lambda: None)       # lane 0 ring: 1
        pool.submit(lambda: None)       # lane 1
        pool.submit(lambda: None)       # lane 0 ring: 2 == capacity, full
        # Burst of 8 (cursor is at lane 1): lane 1's shard is tasks[0..3],
        # lane 0's is tasks[4..7] and cannot be handed off until the wedge
        # clears. Two-phase delivery means lane 1's shard runs WHILE the
        # producer is still blocked sweeping lane 0's remainder — the
        # releaser thread records whether that actually happened before it
        # clears the wedge (the cross-shard dependency the sweep exists
        # for). Head-of-line delivery would record False: nothing of lane
        # 1's shard would run until the 5 s timeout force-released it.
        done = []
        tasks = [(done.append, (i,), {}) for i in range(8)]
        tasks[1] = (other_done.set, (), {})     # lands in lane 1's shard

        ran_before_release = []

        def releaser():
            ran_before_release.append(other_done.wait(5))
            release.set()

        t = threading.Thread(target=releaser)
        t.start()
        pool.submit_batch(tasks)        # main thread: the only producer
        t.join(5)
        pool.wait()
        assert ran_before_release == [True], \
            "lane 1's shard never ran past the wedged lane 0"
    assert sorted(done) == [0, 2, 3, 4, 5, 6, 7]


def test_seq_log_stays_bounded_without_wait():
    """A fire-and-observe consumer that never calls wait() (pipeline-style
    use on a long-lived scope) must not grow the per-lane seq log one
    entry per task forever: completed tasks' entries are trimmed on the
    submit path, keeping the log O(capacity)."""
    with RelicPool(lanes=2, capacity=8, start_awake=True) as pool:
        for i in range(5_000):
            pool.submit(lambda: None)
        high_water = max(len(r) for r in pool._runs)
        # in-flight bound is 2*capacity; the log trims at 4*capacity, so
        # it must never get far past that (slack for the racy _completed)
        assert high_water <= 2 * pool._trim_at, high_water
        pool.wait()
        assert pool.stats.completed == 5_000
        assert all(len(r) == 0 for r in pool._runs)


def test_first_error_ordering_survives_seq_log_trimming():
    """Submission-order error ordering must hold even after the log has
    been trimmed many times: a pending error's entry is kept mappable."""

    def boom(exc):
        raise exc

    with RelicPool(lanes=2, capacity=4, start_awake=True) as pool:
        for i in range(200):          # many trims at capacity 4
            pool.submit(lambda: None)
        # earliest-submitted failure (whatever lane striping/fallback
        # placed it on) must win over the later one
        pool.submit(boom, IndexError("earlier"))
        for i in range(150):          # more trims after the pending error
            pool.submit(lambda: None)
        pool.submit(boom, ValueError("later"))
        with pytest.raises(IndexError, match="earlier"):
            pool.wait()
        assert pool.stats.task_errors == 2


# ------------------------------------------------------------------- misuse

def test_assistant_threads_cannot_submit():
    errs = []
    with RelicPool(lanes=2, start_awake=True) as pool:
        def recursive():
            try:
                pool.submit(lambda: None)
            except RelicUsageError as e:
                errs.append(e)

        for _ in range(2):
            pool.submit(recursive)
        pool.wait()
    assert len(errs) == 2


def test_submit_after_shutdown_raises_and_lanes_match():
    pool = RelicPool(lanes=2).start()
    pool.shutdown()
    with pytest.raises(RelicUsageError, match="shutdown"):
        pool.submit(lambda: None)
    with pytest.raises(RelicUsageError, match="shutdown"):
        pool.submit_batch([(lambda: None, (), {})])
    with pytest.raises(RelicUsageError, match="already started"):
        pool.start()


def test_pool_rejects_nonpositive_lanes():
    with pytest.raises(ValueError, match="lanes"):
        RelicPool(lanes=0)


def test_convenience_names_reject_conflicting_lane_counts():
    """relic2/relic4 ARE their lane counts: an explicit conflicting
    lanes= must raise, never silently mislabel a differently-sized pool
    (BENCH rows are keyed by name). The matching count and the generic
    name stay configurable."""
    with pytest.raises(ValueError, match="fixed at lanes=4"):
        make_scheduler("relic4", lanes=2)
    assert make_scheduler("relic4", lanes=4).workers == 4   # no-op explicit
    assert make_scheduler("relic-pool", lanes=3).workers == 3


# ----------------------------------------------------------- aggregate stats

def test_stats_aggregate_and_expose_lanes():
    with RelicPool(lanes=2, start_awake=True) as pool:
        for i in range(10):
            pool.submit(lambda: None)
        pool.wait()
        assert pool.stats.submitted == 10
        assert pool.stats.completed == 10
        assert pool.stats.task_errors == 0
        assert len(pool.stats.lanes) == 2
        assert sum(s.submitted for s in pool.stats.lanes) == 10
        assert "lanes=2" in repr(pool.stats)


def test_scheduler_adapter_close_keeps_error_observable():
    sched = make_scheduler("relic2").start()
    sched.submit(lambda: 1 / 0)
    sched.close()
    assert sched.stats.task_errors == 1
    assert isinstance(sched.stats.last_error, ZeroDivisionError)


# ---------------------------------------- satellite: SpscRing.__len__ clamp

def test_ring_len_clamps_negative_observer_estimate():
    """A third (observer) thread can see a fresh _head against a stale
    _tail, making tail-head negative; len() must clamp to 0 (the pool's
    least-loaded picker and stats readers never see -1). Simulated by
    writing the counters the way the stale read would present them."""
    ring = SpscRing(8)
    for i in range(4):
        ring.push(i)
    assert len(ring) == 4
    ring._head = 5                      # observer: fresh head, stale tail
    ring._tail = 3
    assert len(ring) == 0


def test_ring_push_many_stop_bounds_the_window():
    """push_many's stop parameter pushes exactly items[start:stop] — the
    shard hand-off RelicPool uses on one shared flattened burst."""
    ring = SpscRing(16)
    items = list(range(10))
    assert ring.push_many(items, 2, 7) == 5
    assert ring.pop_many() == [2, 3, 4, 5, 6]
    assert ring.push_many(items, 7, 7) == 0     # empty window: no-op
    assert ring.push_many(items, 8) == 2        # stop=None: to the end
    assert ring.pop_many() == [8, 9]


# ------------------------------- satellite: RELIC_SPIN_PAUSE_EVERY override

def test_spin_pause_every_env_override(monkeypatch):
    monkeypatch.setenv("RELIC_SPIN_PAUSE_EVERY", "7")
    assert resolve_spin_pause_every() == 7
    rt = Relic()
    assert rt._spin_pause_every == 7
    pool = RelicPool(lanes=2)
    assert all(lane._spin_pause_every == 7 for lane in pool._lanes)
    spin = make_scheduler("spin")
    assert spin._spin_pause_every == 7
    # Re-read per instance, not frozen at import: a later change to the
    # environment is visible to the next runtime.
    monkeypatch.setenv("RELIC_SPIN_PAUSE_EVERY", "3")
    assert Relic()._spin_pause_every == 3


def test_spin_pause_every_env_unset_uses_cpu_heuristic(monkeypatch):
    monkeypatch.delenv("RELIC_SPIN_PAUSE_EVERY", raising=False)
    import os

    expected = 1 if (os.cpu_count() or 1) < 2 else 64
    assert resolve_spin_pause_every() == expected
    monkeypatch.setenv("RELIC_SPIN_PAUSE_EVERY", "")
    assert resolve_spin_pause_every() == expected


@pytest.mark.parametrize("cpus,expected", [
    # Yield-every-iteration only when producer+assistant genuinely
    # outnumber the host's contexts (1 context). A 2-context host is the
    # paper's own §VI shape (one SMT core) and must spin mostly-hot — the
    # pre-PR 6 threshold (< 2 + 1) misclassified it as oversubscribed.
    (None, 1),
    (1, 1),
    (2, 64),
    (4, 64),
])
def test_spin_cadence_pinned_per_host_context_count(monkeypatch, cpus, expected):
    import os

    monkeypatch.delenv("RELIC_SPIN_PAUSE_EVERY", raising=False)
    monkeypatch.setattr(os, "cpu_count", lambda: cpus)
    assert resolve_spin_pause_every() == expected
    assert Relic()._spin_pause_every == expected


@pytest.mark.parametrize("bad", ["0", "-3", "many", "1.5"])
def test_spin_pause_every_env_invalid_raises(monkeypatch, bad):
    monkeypatch.setenv("RELIC_SPIN_PAUSE_EVERY", bad)
    with pytest.raises(ValueError, match="RELIC_SPIN_PAUSE_EVERY"):
        resolve_spin_pause_every()


def test_spin_pause_override_still_completes_work(monkeypatch):
    """The cadence is a perf knob, never a correctness knob: an aggressive
    override must not change observable semantics."""
    monkeypatch.setenv("RELIC_SPIN_PAUSE_EVERY", "1")
    done = []
    with RelicPool(lanes=2, capacity=2, start_awake=True) as pool:
        pool.submit_batch([(done.append, (i,), {}) for i in range(50)])
        pool.wait()
    assert sorted(done) == list(range(50))


# ------------------------------- tentpole: skew resistance (dynamic balancing)

def _wedge_lane(pool, lane_idx, gate):
    """Submit a blocking task destined for ``lane_idx`` (rr cursor must be
    there) and wait until that lane's assistant has actually popped it."""
    popped = threading.Event()

    def wedge():
        popped.set()
        gate.wait()

    pool.submit(wedge)
    assert popped.wait(5), "wedge task never ran"
    deadline = time.time() + 5
    while len(pool._lanes[lane_idx]._ring) and time.time() < deadline:
        time.sleep(0.001)
    assert not len(pool._lanes[lane_idx]._ring), "wedge never drained"


def test_restripe_redeals_stuck_remainder_past_a_wedged_lane():
    """The headline re-striping behavior: a burst whose shard is stuck
    behind a wedged lane is re-dealt to the lanes with room, so
    submit_batch RETURNS while the wedge still holds (with static
    striping the sweep would spin until the wedge cleared — which in
    this test is never, before the producer's own gate.set())."""
    gate = threading.Event()
    watchdog = threading.Timer(30, gate.set)   # a regression must fail on
    watchdog.start()                           # counts, not hang the suite
    try:
        with RelicPool(lanes=2, capacity=2, start_awake=True) as pool:
            _wedge_lane(pool, 0, gate)         # rr: first submit -> lane 0
            done = []
            pool.submit_batch([(done.append, (i,), {}) for i in range(20)])
            # Re-striping delivered the whole burst despite the wedge:
            # lane 0 holds only the wedge + its ring capacity; everything
            # else was re-dealt to lane 1 (primary and handoff ring).
            lane0, lane1 = pool.stats.lanes
            assert lane0.submitted == 3, lane0.submitted
            assert lane1.submitted == 18, lane1.submitted
            gate.set()
            pool.wait()
        assert sorted(done) == list(range(20))
    finally:
        watchdog.cancel()


def test_wedged_lane_keeps_its_own_fifo_under_restriping():
    """Re-striping moves only *not-yet-pushed* remainders: tasks already
    in the wedged lane's ring stay there and run in push order, and the
    helper lane's pre-burst tasks keep their relative order too."""
    gate = threading.Event()
    events = []

    def rec(label):
        events.append((threading.current_thread().name, label))

    with RelicPool(lanes=2, capacity=2, start_awake=True) as pool:
        _wedge_lane(pool, 0, gate)
        pool.submit(rec, "l1-a")       # lane 1 (rr)
        pool.submit(rec, "l0-a")       # lane 0 ring slot 1
        pool.submit(rec, "l1-b")       # lane 1
        pool.submit(rec, "l0-b")       # lane 0 ring slot 2 (now full)
        pool.submit_batch([(rec, (f"burst-{i}",), {}) for i in range(12)])
        gate.set()
        pool.wait()
    lane0 = [lab for name, lab in events if name == "relic-pool-lane0"]
    lane1 = [lab for name, lab in events if name == "relic-pool-lane1"]
    # The wedged lane ran exactly its ring content, in FIFO order; every
    # burst task was re-dealt to lane 1 (lane 0 had no room throughout).
    assert lane0 == ["l0-a", "l0-b"]
    assert lane1.index("l1-a") < lane1.index("l1-b")
    assert len(events) == 16


def test_handoff_ring_accepts_singles_when_every_primary_is_full():
    """Single-submit fallback, rebalancing edition: when every lane's
    primary ring is full (all assistants wedged), submit() hands the task
    to a handoff ring and returns instead of busy-waiting."""
    gate = threading.Event()
    ready = [threading.Event(), threading.Event()]

    def wedge(i):
        ready[i].set()
        gate.wait()

    done = []
    with RelicPool(lanes=2, capacity=1, start_awake=True) as pool:
        pool.submit(wedge, 0)          # lane 0 assistant blocks
        pool.submit(wedge, 1)          # lane 1 assistant blocks
        assert ready[0].wait(5) and ready[1].wait(5)
        deadline = time.time() + 5
        while (any(len(lane._ring) for lane in pool._lanes)
               and time.time() < deadline):
            time.sleep(0.001)
        pool.submit(lambda: None)      # fills lane 0's 1-task ring
        pool.submit(lambda: None)      # fills lane 1's 1-task ring
        pool.submit(done.append, 99)   # every primary full -> handoff ring
        assert sum(len(lane._oring) for lane in pool._lanes) == 2  # 1 task
        gate.set()
        pool.wait()
        assert pool.stats.completed == 5
    assert done == [99]


def test_error_in_handoff_task_wins_by_submission_order():
    """A failure that rode a handoff ring is ordered by its pool-global
    submission seq like any other: earlier-submitted handoff error beats
    a later-submitted primary-ring error."""

    def boom(exc):
        raise exc

    gate = threading.Event()
    ready = [threading.Event(), threading.Event()]

    def wedge(i):
        ready[i].set()
        gate.wait()

    with RelicPool(lanes=2, capacity=1, start_awake=True) as pool:
        pool.submit(wedge, 0)
        pool.submit(wedge, 1)
        assert ready[0].wait(5) and ready[1].wait(5)
        deadline = time.time() + 5
        while (any(len(lane._ring) for lane in pool._lanes)
               and time.time() < deadline):
            time.sleep(0.001)
        pool.submit(lambda: None)                    # seq 2: fills lane 0
        pool.submit(lambda: None)                    # seq 3: fills lane 1
        pool.submit(boom, IndexError("handoff, seq 4"))   # -> handoff ring
        assert sum(len(lane._oring) for lane in pool._lanes) == 2
        gate.set()
        # Drain everything, then fail later on a primary ring: the wait()
        # must re-raise the earlier (handoff) error.
        deadline = time.time() + 10
        while pool.stats.completed < 5 and time.time() < deadline:
            time.sleep(0.001)
        pool.submit(boom, ValueError("primary, seq 5"))
        with pytest.raises(IndexError, match="handoff, seq 4"):
            pool.wait()
        assert pool.stats.task_errors == 2
        # Consumed as one unit: no stale index on any lane (PR 6 bugfix).
        for s in pool.stats.lanes:
            assert s.last_error is None
            assert s.first_error_index is None
            assert s.first_error_handoff_index is None


def test_earlier_primary_error_beats_later_handoff_error():
    """The mirror direction: an earlier-submitted primary-ring failure
    wins over a later failure that rode a handoff ring."""

    def boom(exc):
        raise exc

    gate = threading.Event()
    ready = [threading.Event(), threading.Event()]

    def wedge(i):
        ready[i].set()
        gate.wait()

    with RelicPool(lanes=2, capacity=1, start_awake=True) as pool:
        pool.submit(wedge, 0)
        pool.submit(wedge, 1)
        assert ready[0].wait(5) and ready[1].wait(5)
        deadline = time.time() + 5
        while (any(len(lane._ring) for lane in pool._lanes)
               and time.time() < deadline):
            time.sleep(0.001)
        pool.submit(boom, IndexError("primary, seq 2"))  # fills lane 0
        pool.submit(lambda: None)                        # seq 3: fills lane 1
        pool.submit(boom, ValueError("handoff, seq 4"))  # -> handoff ring
        gate.set()
        with pytest.raises(IndexError, match="primary, seq 2"):
            pool.wait()
        assert pool.stats.task_errors == 2


def test_handoff_tasks_cannot_submit():
    """§VI-A survives rebalancing: a task delivered through a handoff
    ring still runs on an assistant thread, which cannot submit."""
    gate = threading.Event()
    ready = [threading.Event(), threading.Event()]

    def wedge(i):
        ready[i].set()
        gate.wait()

    errs = []
    with RelicPool(lanes=2, capacity=1, start_awake=True) as pool:
        def recursive():
            try:
                pool.submit(lambda: None)
            except RelicUsageError as e:
                errs.append(e)

        pool.submit(wedge, 0)
        pool.submit(wedge, 1)
        assert ready[0].wait(5) and ready[1].wait(5)
        deadline = time.time() + 5
        while (any(len(lane._ring) for lane in pool._lanes)
               and time.time() < deadline):
            time.sleep(0.001)
        pool.submit(lambda: None)
        pool.submit(lambda: None)
        pool.submit(recursive)         # every primary full -> handoff ring
        assert sum(len(lane._oring) for lane in pool._lanes) == 2
        gate.set()
        pool.wait()
    assert len(errs) == 1


def test_single_lane_skips_handoff_machinery():
    """A single-lane pool has nowhere to re-deal to: it never rebalances
    and its lane has no handoff ring, with or without respawn. Every lane
    of a multi-lane pool has one."""
    multi = RelicPool(lanes=2)
    assert multi._rebalance
    assert all(lane._oring is not None for lane in multi._lanes)
    for respawn in (False, True):
        single = RelicPool(lanes=1, capacity=2, start_awake=True,
                           respawn=respawn)
        assert not single._rebalance
        assert single._lanes[0]._oring is None
        done = []
        with single as pool:
            pool.submit_batch([(done.append, (i,), {}) for i in range(50)])
            pool.wait()
        assert done == list(range(50))      # one lane: global FIFO


def test_handoff_seq_log_cleared_and_bounded():
    """The handoff-ring seq log obeys the same discipline as the primary
    log: trimmed between barriers, cleared by wait()."""
    with RelicPool(lanes=2, capacity=2, start_awake=True) as pool:
        # Small rings + a 1-cpu-friendly flood: primaries fill routinely,
        # so singles flow through the handoff rings too.
        for i in range(2_000):
            pool.submit(lambda: None)
        assert max(len(r) for r in pool._oruns) <= 2 * pool._trim_at
        pool.wait()
        assert pool.stats.completed == 2_000
        assert all(len(r) == 0 for r in pool._runs)
        assert all(len(r) == 0 for r in pool._oruns)


def test_free_slots_is_a_safe_push_window():
    """``SpscRing.free_slots`` is the producer-side lower bound the
    re-striper sizes its windows with: a push of that many items must
    succeed in full, and the bound only grows as the consumer drains."""
    ring = SpscRing(8)
    assert ring.free_slots() == 8
    assert ring.push_many([0, 1, 2, 3, 4, 5], 0, 6) == 6
    assert ring.free_slots() == 2
    assert ring.push_many([6, 7], 0, 2) == 2
    assert ring.free_slots() == 0
    assert len(ring.pop_many(3)) == 3
    # Still a valid lower bound even before the producer re-reads head...
    assert ring.free_slots() <= 3
    # ...and a push sized by it always lands entirely.
    room = ring.free_slots()
    assert ring.push_many(list(range(room)), 0, room) == room


def test_wait_after_error_clears_every_error_field_on_the_pool():
    """PR 6 bugfix regression: wait() raising must consume the error
    *atomically* — ``last_error`` AND both first-error indexes clear
    together, so a later wait() cannot mis-order a fresh error against a
    stale index from the previous window."""
    with RelicPool(lanes=2, capacity=4, start_awake=True) as pool:
        def boom():
            raise ValueError("window 1")
        pool.submit(lambda: None)
        pool.submit(boom)
        with pytest.raises(ValueError, match="window 1"):
            pool.wait()
        for s in pool.stats.lanes:
            assert s.last_error is None
            assert s.first_error_index is None
            assert s.first_error_handoff_index is None
        # The next window is clean: errors order among themselves only.
        pool.submit(lambda: None)
        pool.wait()
        assert pool.stats.task_errors == 1


# ------------------- satellite: interrupt-safe burst accounting (reconcile)

class _InterruptingTime:
    """Stand-in for ``relic_pool.time``: the first ``sleep`` raises (the
    KeyboardInterrupt-mid-sweep scenario); everything else passes through."""

    def __init__(self):
        self.fired = False

    def sleep(self, seconds):
        if not self.fired:
            self.fired = True
            raise KeyboardInterrupt

    def __getattr__(self, name):
        return getattr(time, name)


def test_interrupt_escaping_sweep_cannot_wedge_wait(monkeypatch):
    """A BaseException escaping the remainder sweep must leave
    ``submitted`` == tasks actually handed to rings (accounting is
    committed per push, not up front): the next wait() then terminates.
    Pre-PR 6, the whole shard was accounted before delivery, so the
    interrupt stranded submitted > pushed and wait() busy-spun forever."""
    monkeypatch.setenv("RELIC_SPIN_PAUSE_EVERY", "1")   # sweep yields ASAP
    gate = threading.Event()
    fake_time = _InterruptingTime()
    with RelicPool(lanes=2, capacity=2, start_awake=True) as pool:
        _wedge_lane(pool, 0, gate)
        _wedge_lane(pool, 1, gate)
        import repro.core.relic_pool as relic_pool_mod
        monkeypatch.setattr(relic_pool_mod, "time", fake_time)
        done = []
        with pytest.raises(KeyboardInterrupt):
            # Burst of 20: with both lanes wedged neither shard can be
            # delivered past its ring, and re-striping has no lane with
            # room to re-deal to, so the sweep spins and the injected
            # interrupt unwinds out of submit_batch mid-burst.
            pool.submit_batch([(done.append, (i,), {}) for i in range(20)])
        assert fake_time.fired
        monkeypatch.setattr(relic_pool_mod, "time", time)
        gate.set()
        # The discriminating assertion: every accounted task is really in
        # a ring (or already done), so *live* completion (stats.completed
        # is a barrier-time snapshot) converges to submitted — the
        # condition wait() spins on. Pre-fix this times out: the shard was
        # accounted up front, so submitted > tasks actually pushed.
        live = lambda: sum(lane._completed for lane in pool._lanes)
        deadline = time.time() + 10
        while live() < pool.stats.submitted and time.time() < deadline:
            time.sleep(0.005)
        assert live() == pool.stats.submitted
        # The pool stays usable: wait() returns, later windows are clean.
        pool.wait()
        pool.submit(done.append, "after")
        pool.wait()
        assert "after" in done
