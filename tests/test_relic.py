"""Relic host runtime + SPSC ring semantics (paper §VI)."""

import threading
import time

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.relic import Relic, RelicUsageError
from repro.core.spsc import SpscRing


# ---------------------------------------------------------------- SPSC ring

@given(st.lists(st.integers(), max_size=300),
       st.integers(min_value=1, max_value=64))
@settings(deadline=None, max_examples=50)
def test_spsc_fifo_property(items, capacity):
    """Single-threaded FIFO + capacity invariants for any push/pop schedule."""
    ring = SpscRing(capacity)
    out = []
    pending = list(items)
    while pending or len(ring):
        pushed = False
        if pending and ring.push(pending[0]):
            pending.pop(0)
            pushed = True
        if not pushed or len(ring) > capacity // 2:
            got = ring.pop()
            if got is not None:
                out.append(got)
        assert len(ring) <= capacity
    assert out == items


@pytest.mark.parametrize("capacity", [1, 2, 3, 128])
def test_spsc_wraparound_past_capacity_multiples(capacity):
    """Head/tail are monotonically increasing counters: behaviour must be
    identical long after the indices pass several capacity multiples."""
    ring = SpscRing(capacity)
    n = capacity * 7 + 3  # lands mid-window, several wraps in
    sent = 0
    got = []
    while len(got) < n:
        while sent < n and ring.push(sent):
            sent += 1
        assert len(ring) <= capacity
        item = ring.pop()
        if item is not None:
            got.append(item)
    assert got == list(range(n))
    assert ring.empty() and not ring.full()
    # counters sit far past capacity; a fresh cycle still behaves
    assert ring.push("x") and ring.pop() == "x" and ring.pop() is None


def test_spsc_capacity_one_edge_case():
    """capacity=1 alternates strictly full/empty — the tightest schedule."""
    ring = SpscRing(1)
    assert ring.pop() is None
    for i in range(10):
        assert ring.push(i)
        assert ring.full() and not ring.push(99)  # one slot only
        assert len(ring) == 1
        assert ring.pop() == i
        assert ring.empty() and ring.pop() is None


@pytest.mark.parametrize("capacity", [1, 2, 7])
def test_spsc_concurrent_1p1c_fifo_no_loss(capacity):
    """One producer + one consumer interleaving arbitrarily: FIFO order is
    preserved and no item is lost or duplicated, even at capacity 1."""
    ring = SpscRing(capacity)
    n = 20_000
    out = []
    stop = threading.Event()

    def consumer():
        while len(out) < n and not stop.is_set():
            item = ring.pop()
            if item is not None:
                out.append(item)
            else:
                time.sleep(0)

    t = threading.Thread(target=consumer)
    t.start()
    try:
        i = 0
        while i < n:
            if ring.push(i):
                i += 1
            else:
                time.sleep(0)
        t.join(30)
    finally:
        stop.set()
        t.join(5)
    assert out == list(range(n))


@given(st.data(), st.integers(min_value=1, max_value=8))
@settings(deadline=None, max_examples=30)
def test_spsc_property_any_interleaving_is_fifo(data, capacity):
    """Model-based check: under ANY single-threaded push/pop interleaving
    (chosen by hypothesis), the ring agrees with an ideal FIFO of the same
    capacity, including across many wraparounds."""
    ring = SpscRing(capacity)
    model: list = []
    next_item = 0
    for _ in range(data.draw(st.integers(10, 200))):
        if data.draw(st.booleans()):
            pushed = ring.push(next_item)
            assert pushed == (len(model) < capacity)
            if pushed:
                model.append(next_item)
                next_item += 1
        else:
            got = ring.pop()
            assert got == (model.pop(0) if model else None)
        assert len(ring) == len(model)
        assert ring.empty() == (not model)
        assert ring.full() == (len(model) == capacity)


@given(st.data(), st.integers(min_value=1, max_value=8))
@settings(deadline=None, max_examples=30)
def test_spsc_property_batched_ops_match_fifo(data, capacity):
    """Model-based check for the batch paths: under ANY single-threaded
    interleaving of push/pop/push_many/pop_many (chosen by hypothesis), the
    ring agrees with an ideal FIFO — push_many accepts exactly the free
    slots, pop_many returns exactly the available items (up to its cap),
    and the cached head/tail snapshots never change observable behaviour."""
    ring = SpscRing(capacity)
    model: list = []
    next_item = 0
    for _ in range(data.draw(st.integers(10, 150))):
        op = data.draw(st.sampled_from(
            ["push", "pop", "push_many", "pop_many"]))
        if op == "push":
            pushed = ring.push(next_item)
            assert pushed == (len(model) < capacity)
            if pushed:
                model.append(next_item)
                next_item += 1
        elif op == "pop":
            got = ring.pop()
            assert got == (model.pop(0) if model else None)
        elif op == "push_many":
            k = data.draw(st.integers(0, capacity + 2))
            items = list(range(next_item, next_item + k))
            pushed = ring.push_many(items)
            assert pushed == min(k, capacity - len(model))
            model.extend(items[:pushed])
            next_item += pushed
        else:
            cap = data.draw(st.one_of(st.none(),
                                      st.integers(0, capacity + 2)))
            got = ring.pop_many(cap)
            want_n = len(model) if cap is None else min(cap, len(model))
            assert got == model[:want_n]
            del model[:want_n]
        assert len(ring) == len(model)
        assert ring.empty() == (not model)
        assert ring.full() == (len(model) == capacity)


@given(st.lists(st.integers(1, 7), min_size=1, max_size=60),
       st.integers(min_value=1, max_value=8))
@settings(deadline=None, max_examples=30)
def test_spsc_property_push_many_pop_many_roundtrip(chunks, capacity):
    """Feeding arbitrary chunk sizes through push_many while pop_many drains
    opportunistically preserves FIFO with no loss or duplication, across
    many wraparounds (the cached snapshots go stale and refresh)."""
    ring = SpscRing(capacity)
    sent = 0
    out = []
    for k in chunks:
        items = list(range(sent, sent + k))
        pos = 0
        while pos < k:
            pos += ring.push_many(items, pos)   # offset retry: no tail copy
            if pos < k:          # full: drain a burst, then keep pushing
                out.extend(ring.pop_many())
        sent += k
    out.extend(ring.pop_many())
    assert out == list(range(sent))
    assert ring.empty()


@pytest.mark.parametrize("capacity", [1, 2, 7])
def test_spsc_concurrent_batched_1p1c_fifo_no_loss(capacity):
    """push_many producer + pop_many consumer interleaving across threads:
    FIFO order preserved, nothing lost or duplicated, even at capacity 1
    (where every batch degenerates to single-slot hand-offs)."""
    ring = SpscRing(capacity)
    n = 20_000
    out = []
    stop = threading.Event()

    def consumer():
        while len(out) < n and not stop.is_set():
            got = ring.pop_many()
            if got:
                out.extend(got)
            else:
                time.sleep(0)

    t = threading.Thread(target=consumer)
    t.start()
    try:
        i = 0
        while i < n:
            batch = list(range(i, min(i + 13, n)))
            pos = 0
            while pos < len(batch):
                pushed = ring.push_many(batch[pos:])
                if pushed:
                    pos += pushed
                else:
                    time.sleep(0)
            i += len(batch)
        t.join(30)
    finally:
        stop.set()
        t.join(5)
    assert out == list(range(n))


def test_spsc_push_many_accepts_tuple_and_empty():
    ring = SpscRing(4)
    assert ring.push_many(()) == 0
    assert ring.push_many((10, 11)) == 2
    assert ring.pop_many(1) == [10]
    assert ring.pop_many() == [11]
    assert ring.pop_many() == []


def test_spsc_push_many_start_offset():
    """The `start` offset pushes items[start:] without the caller slicing
    (the backpressure retry path for bursts larger than the ring)."""
    ring = SpscRing(3)
    items = [0, 1, 2, 3, 4]
    assert ring.push_many(items) == 3
    assert ring.push_many(items, 3) == 0          # full: nothing, no copy
    assert ring.pop_many(2) == [0, 1]
    assert ring.push_many(items, 3) == 2
    assert ring.pop_many() == [2, 3, 4]
    assert ring.push_many(items, 5) == 0          # exhausted offset: no-op
    assert ring.push_many(items, 7) == 0          # overshot offset: no rewind
    assert len(ring) == 0 and ring.empty()


def test_spsc_pop_many_nonpositive_budget_is_a_noop():
    """A zero/negative max_items must not rewind _head (regression: a
    negative budget used to move the head backwards, resurrecting cleared
    slots and re-delivering items)."""
    ring = SpscRing(4)
    assert ring.push_many((1, 2)) == 2
    assert ring.pop_many(0) == []
    assert ring.pop_many(-1) == []
    assert len(ring) == 2
    assert ring.pop_many() == [1, 2]
    assert ring.pop_many(-5) == [] and ring.empty()


def test_spsc_full_empty():
    ring = SpscRing(2)
    assert ring.pop() is None
    assert ring.push(1) and ring.push(2)
    assert not ring.push(3)           # full
    assert ring.pop() == 1
    assert ring.push(3)
    assert [ring.pop(), ring.pop()] == [2, 3]
    assert ring.empty()


def test_spsc_threaded_fifo():
    ring = SpscRing(8)
    n = 5000
    out = []

    def consumer():
        while len(out) < n:
            item = ring.pop()
            if item is not None:
                out.append(item)
            else:
                time.sleep(0)

    t = threading.Thread(target=consumer)
    t.start()
    i = 0
    while i < n:
        if ring.push(i):
            i += 1
        else:
            time.sleep(0)
    t.join(10)
    assert out == list(range(n))


# ------------------------------------------------------------ Relic runtime

def test_relic_runs_tasks_in_order():
    out = []
    with Relic() as rt:
        rt.wake_up_hint()
        for i in range(500):
            rt.submit(out.append, i)
        rt.wait()
    assert out == list(range(500))  # single consumer => submit order


def test_relic_rejects_assistant_submit():
    """Paper §VI-A: the assistant thread cannot submit (no recursion)."""
    errs = []
    with Relic(start_awake=True) as rt:
        def recursive():
            try:
                rt.submit(lambda: None)
            except RelicUsageError as e:
                errs.append(e)

        rt.submit(recursive)
        rt.wait()
    assert len(errs) == 1


def test_relic_rejects_foreign_thread():
    with Relic(start_awake=True) as rt:
        rt.submit(lambda: None)
        rt.wait()
        err = []

        def other():
            try:
                rt.submit(lambda: None)
            except RelicUsageError as e:
                err.append(e)

        t = threading.Thread(target=other)
        t.start()
        t.join()
        assert err


def test_relic_task_error_surfaces_at_wait():
    with Relic(start_awake=True) as rt:
        rt.submit(lambda: 1 / 0)
        with pytest.raises(ZeroDivisionError):
            rt.wait()


def test_relic_first_error_wins_not_last():
    """Regression: ``stats.last_error`` was overwritten per failure, so
    ``wait()`` raised the LAST error while the SPI (docs/schedulers.md and
    every other substrate) documents first-error-wins."""
    with Relic(start_awake=True) as rt:
        rt.submit(lambda: (_ for _ in ()).throw(KeyError("first")))
        rt.submit(lambda: 1 / 0)
        rt.submit(lambda: (_ for _ in ()).throw(IndexError("last")))
        with pytest.raises(KeyError, match="first"):
            rt.wait()
        assert rt.stats.task_errors == 3
        rt.wait()  # cleared: nothing re-raises


def test_relic_shutdown_timeout_on_wedged_task_is_non_restartable():
    """Regression: ``shutdown(timeout)`` used to null the assistant even
    when ``join(timeout)`` expired, leaking the live thread — a subsequent
    ``start()`` would put a second consumer on the SPSC ring."""
    release = threading.Event()
    rt = Relic(start_awake=True).start()
    rt.submit(release.wait)           # wedge the assistant
    with pytest.raises(RelicUsageError, match="non-restartable"):
        rt.shutdown(timeout=0.1)
    with pytest.raises(RelicUsageError):
        rt.start()                    # no second consumer, ever
    with pytest.raises(RelicUsageError):
        rt.submit(lambda: None)       # still shut down
    release.set()                     # un-wedge; assistant observes shutdown
    rt.shutdown()                     # now exits cleanly (and is idempotent)
    rt.shutdown()


def test_relic_sleep_hint_parks_assistant():
    rt = Relic(start_awake=False).start()   # asleep until hinted
    time.sleep(0.05)
    parked = rt.stats.parks
    assert parked >= 1
    spins_asleep = rt.stats.assistant_empty_spins
    time.sleep(0.05)
    # parked assistant must not burn spin iterations
    assert rt.stats.assistant_empty_spins == spins_asleep
    rt.wake_up_hint()
    out = []
    rt.submit(out.append, 1)
    rt.wait()
    assert out == [1]
    rt.shutdown()


def test_relic_submit_batch_runs_in_order_and_mixes_with_submit():
    out = []
    with Relic(start_awake=True) as rt:
        rt.submit(out.append, 0)
        rt.submit_batch([(out.append, (i,), {}) for i in range(1, 400)])
        rt.submit(out.append, 400)
        rt.wait()
    assert out == list(range(401))
    assert rt.stats.submitted == rt.stats.completed == 401


def test_relic_submit_batch_backpressures_past_capacity():
    """A burst several times the ring capacity must block-and-drain, not
    drop: the producer busy-waits on free slots (paper §VI-A bounded ring)."""
    out = []
    with Relic(capacity=4, start_awake=True) as rt:
        rt.submit_batch(
            [(lambda i=i: (time.sleep(0.0002), out.append(i)), (), {})
             for i in range(100)])
        rt.wait()
    assert out == list(range(100))
    assert rt.stats.producer_full_spins > 0


def test_relic_submit_batch_rejected_from_assistant():
    """Paper §VI-A: no recursive spawn — the batch entry point included."""
    errs = []
    with Relic(start_awake=True) as rt:
        def recursive():
            try:
                rt.submit_batch([(lambda: None, (), {})])
            except RelicUsageError as e:
                errs.append(e)

        rt.submit(recursive)
        rt.wait()
    assert len(errs) == 1


def test_relic_submit_batch_unparks_a_sleeping_assistant():
    """Advisory hints must not deadlock a full-ring burst (§VI-B rule)."""
    out = []
    with Relic(capacity=2) as rt:     # starts parked (start_awake=False)
        time.sleep(0.02)
        rt.submit_batch([(out.append, (i,), {}) for i in range(20)])
        rt.wait()
    assert out == list(range(20))


def test_relic_backpressure_capacity():
    """Producer busy-waits when the bounded ring is full, never drops."""
    out = []
    with Relic(capacity=4, start_awake=True) as rt:
        for i in range(100):
            rt.submit(lambda i=i: (time.sleep(0.0005), out.append(i)))
        rt.wait()
    assert out == list(range(100))
    assert rt.stats.submitted == rt.stats.completed == 100


def test_relic_wait_clears_error_index_with_the_error():
    """PR 6 bugfix regression: ``wait()`` used to clear ``last_error`` but
    leave ``stats.first_error_index`` stale, so the next window's error
    could be ordered against a dead index (the pool maps these indexes to
    pool-global submission seqs — a stale one mis-orders cross-lane
    first-error-wins). Both fields are one unit: they clear together."""
    with Relic(start_awake=True) as rt:
        rt.submit(lambda: None)
        rt.submit(lambda: (_ for _ in ()).throw(KeyError("w1")))
        with pytest.raises(KeyError, match="w1"):
            rt.wait()
        assert rt.stats.last_error is None
        assert rt.stats.first_error_index is None      # pre-fix: stale 1
        assert rt.stats.first_error_handoff_index is None
        # A fresh window's first failure gets a fresh index.
        rt.submit(lambda: None)
        rt.submit(lambda: None)
        rt.submit(lambda: 1 / 0)
        with pytest.raises(ZeroDivisionError):
            rt.wait()
        assert rt.stats.first_error_index is None
        assert rt.stats.task_errors == 2


def test_relic_submit_accounts_only_after_the_push_lands(monkeypatch):
    """Interrupt safety on the pair: ``submitted`` commits after the ring
    accepts the task, so a BaseException unwinding out of a full-ring
    spin leaves ``submitted`` == tasks actually delivered and the next
    ``wait()`` terminates instead of spinning for a phantom task."""
    import repro.core.relic as relic_mod

    class _RaisingTime:
        def __init__(self):
            self.fired = False
        def sleep(self, seconds):
            if not self.fired:
                self.fired = True
                raise KeyboardInterrupt
        def __getattr__(self, name):
            return getattr(time, name)

    monkeypatch.setenv("RELIC_SPIN_PAUSE_EVERY", "1")
    gate = threading.Event()
    fake = _RaisingTime()
    with Relic(capacity=1, start_awake=True) as rt:
        popped = threading.Event()
        rt.submit(lambda: (popped.set(), gate.wait()))
        assert popped.wait(5)
        rt.submit(lambda: None)            # fills the 1-slot ring
        monkeypatch.setattr(relic_mod, "time", fake)
        with pytest.raises(KeyboardInterrupt):
            rt.submit(lambda: None)        # full ring -> spin -> interrupt
        assert fake.fired
        monkeypatch.setattr(relic_mod, "time", time)
        assert rt.stats.submitted == 2     # the un-pushed task is NOT counted
        gate.set()
        rt.wait()                          # terminates: no phantom task
        assert rt.stats.completed == 2


# ------------------------------------------------- the one assistant loop

@pytest.mark.parametrize("handoff_ring, fail_on", [
    (False, "primary"),
    (True, "primary"),
    (True, "handoff"),
], ids=["pair", "lane-primary-error", "lane-handoff-error"])
def test_assistant_loop_drains_primary_first_and_records_errors_per_ring(
        handoff_ring, fail_on):
    """The one assistant loop, on the plain pair and on a pool lane with a
    handoff ring: primary work drains before handed-off work, shutdown
    drains both rings, and a failure's index counts completions on the
    ring that carried it (so the pool can map it to submission order)."""
    rt = Relic(capacity=16, start_awake=True)
    if handoff_ring:
        rt._oring = SpscRing(2 * 16)        # what a multi-lane pool attaches
    ran = []

    def put(ring, tag):
        target = rt._ring if ring == "primary" else rt._oring
        assert target.push2(ran.append, (tag,))
        rt.stats.submitted += 1

    # Both rings loaded before the assistant exists, handoff ring first:
    # the primary burst still runs first.
    if handoff_ring:
        for i in range(3):
            put("handoff", f"h{i}")
    for i in range(2):
        put("primary", f"p{i}")
    rt.start()
    rt.wait()
    assert ran == ["p0", "p1"] + (["h0", "h1", "h2"] if handoff_ring else [])

    # A failure on one ring is indexed among that ring's completions only:
    # 2 primary and 3 handoff tasks completed before it.
    def boom(tag):
        raise ValueError(tag)

    target = rt._ring if fail_on == "primary" else rt._oring
    put(fail_on, "ok")
    assert target.push2(boom, ("first",))
    rt.stats.submitted += 1
    assert target.push2(boom, ("second",))
    rt.stats.submitted += 1
    rt._barrier()
    assert rt.stats.task_errors == 2
    if fail_on == "primary":
        assert rt.stats.first_error_index == 3
        assert rt.stats.first_error_handoff_index is None
    else:
        assert rt.stats.first_error_handoff_index == 4
        assert rt.stats.first_error_index is None
    with pytest.raises(ValueError, match="first"):
        rt.wait()
    assert rt.stats.first_error_index is None
    assert rt.stats.first_error_handoff_index is None

    # Shutdown drains every ring, primary first, even from a parked
    # assistant that was never woken for the work.
    rt.sleep_hint()
    deadline = time.time() + 5
    parks = rt.stats.parks
    while rt.stats.parks == parks and time.time() < deadline:
        time.sleep(0.001)
    del ran[:]
    if handoff_ring:
        put("handoff", "h-last")
    put("primary", "p-last")
    rt.shutdown()
    assert ran == ["p-last"] + (["h-last"] if handoff_ring else [])
    assert rt._completed == rt.stats.submitted
