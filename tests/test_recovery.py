"""Stream-network recovery: farm quarantine/respawn, exact loss accounting,
and advisory stage supervision (PR 10).

The PR 8 supervise/quarantine/respawn discipline lifted up a stratum. The
hard invariants under test:

* a dead farm worker's lost in-flight set is computed EXACTLY as
  dealt-minus-released (the per-worker dealt ledger), and surfaced as
  :class:`StageFailedError.lost_tags` — the regression half: before
  PR 10 the collector raised a bare ``RelicDeadError`` whose count was
  the *stash* size, so callers could not re-submit the lost work;
* ``Farm(respawn=True)`` replaces the dead worker with a fresh stage +
  fresh rings (1P1C preserved) and re-emits exactly the lost tags,
  exactly once: output complete and in order, ``reemitted_tags`` ==
  measured ``lost_tags``, dedup ledger untouched (``dup_dropped == 0``);
* ``Pipeline(supervisor=)`` stays advisory: stalled/straggler *flags*,
  never an exception, and the bounded waits still decide "dead".

Kills are injected deterministically via
:class:`repro.runtime.chaos.StageKillSwitch` (the stream-loop analogue
of the Relic ``KillSwitch``): the loop dies by ``SystemExit`` with the
popped item unprocessed, exactly the "assistant died" escape class.
"""

import threading

import pytest

from repro.core.relic import RelicDeadError
from repro.runtime.chaos import StageKillSwitch
from repro.runtime.fault import LaneSupervisor
from repro.stream import (Farm, Pipeline, Stage, StageFailedError,
                          StreamUsageError, WorkerFailure)

N = 120


def _cause_chain(err):
    seen = []
    while err is not None:
        seen.append(err)
        err = err.__cause__
    return seen


def _find(err, cls):
    for e in _cause_chain(err):
        if isinstance(e, cls):
            return e
    return None


# ---------------------------------------------------------------- fail-stop


def test_dead_worker_error_carries_lost_tags():
    """Satellite regression: the death report must say *which* in-flight
    items died with the worker, not just how many. (Pre-PR-10 this
    surfaced as a bare RelicDeadError counting the collector's stash —
    callers could not re-submit the lost work.)"""
    gate = threading.Event()

    def work(x):
        gate.wait(5)
        return x + 1

    f = Farm(work, workers=2, capacity=8)
    ks = StageKillSwitch(after_items=2).arm(f._workers[0])
    with pytest.raises(RelicDeadError) as ei:
        with Pipeline([f]) as pipe:
            threading.Timer(0.2, gate.set).start()
            pipe.run(range(N))
    sfe = _find(ei.value, StageFailedError)
    assert sfe is not None, f"no StageFailedError in {_cause_chain(ei.value)}"
    assert ks.fired
    assert sfe.stage == f._workers[0].name
    assert len(sfe.lost_tags) >= 1
    assert sfe.lost == len(sfe.lost_tags)           # count == tag set
    assert list(sfe.lost_tags) == sorted(set(sfe.lost_tags))
    assert all(0 <= t < N for t in sfe.lost_tags)
    # The dead worker's ledger is the error's tag set, exactly.
    assert tuple(sfe.lost_tags) == f.failures[0].lost_tags
    assert f.failures[0].respawned is False


def test_dead_worker_lost_tags_bounded_by_window():
    """The lost set is bounded by the worker's in-flight window (its input
    ring capacity + the one popped item) — dealt-minus-released can never
    blame more than was actually outstanding."""
    gate = threading.Event()
    cap = 4

    def work(x):
        gate.wait(5)
        return x

    f = Farm(work, workers=2, capacity=cap)
    StageKillSwitch(after_items=0).arm(f._workers[1])
    with pytest.raises(RelicDeadError) as ei:
        with Pipeline([f]) as pipe:
            threading.Timer(0.2, gate.set).start()
            pipe.run(range(N))
    sfe = _find(ei.value, StageFailedError)
    assert sfe is not None
    assert 1 <= len(sfe.lost_tags) <= cap + 1


# ------------------------------------------------------------------ respawn


@pytest.mark.parametrize("workers,kill_at,after", [(2, 1, 3), (4, 2, 0)])
def test_respawn_completes_exactly_once(workers, kill_at, after):
    """The acceptance invariant: kill a worker mid-stream with a backlog
    in flight; the farm must finish with every item exactly once, the
    re-emitted tags equal to the measured lost tags, and the ledger
    balanced."""
    gate = threading.Event()

    def work(x):
        gate.wait(5)
        return x * x

    f = Farm(work, workers=workers, respawn=True, capacity=8)
    ks = StageKillSwitch(after_items=after).arm(f._workers[kill_at])
    with Pipeline([f]) as pipe:
        threading.Timer(0.2, gate.set).start()
        out = pipe.run(range(N))
    assert out == [x * x for x in range(N)]
    assert ks.fired
    assert len(f.failures) == 1
    failure = f.failures[0]
    assert isinstance(failure, WorkerFailure)
    assert failure.worker_index == kill_at
    assert failure.respawned and failure.reemitted
    assert failure.recovered_s >= failure.detected_s
    # exactly-once: replayed tags == lost tags, nothing dropped as dup
    assert sorted(f.reemitted_tags) == list(failure.lost_tags)
    assert f.dup_dropped == 0
    assert f.lost_tags == failure.lost_tags
    # ledger balanced: every item entered and left the farm once
    assert f.items_in == N and f.items_out == N
    # the fresh worker actually took over the slot
    assert f._workers[kill_at].name.endswith("r1")
    assert f._workers[kill_at].error() is None


def test_respawn_unordered_completes():
    f = Farm(lambda x: -x, workers=3, respawn=True, ordered=False)
    StageKillSwitch(after_items=1).arm(f._workers[2])
    with Pipeline([f]) as pipe:
        out = pipe.run(range(N))
    assert sorted(out) == sorted(-x for x in range(N))
    assert len(f.failures) == 1
    assert f.dup_dropped == 0


def test_respawn_two_workers_die():
    """Two independent kills in one run: both slots recover, stream
    completes, the two failures' lost sets are disjoint."""
    gate = threading.Event()

    def work(x):
        gate.wait(5)
        return x + 7

    f = Farm(work, workers=3, respawn=True, capacity=4)
    StageKillSwitch(after_items=1).arm(f._workers[0])
    StageKillSwitch(after_items=2).arm(f._workers[2])
    with Pipeline([f]) as pipe:
        threading.Timer(0.2, gate.set).start()
        out = pipe.run(range(N))
    assert out == [x + 7 for x in range(N)]
    assert len(f.failures) == 2
    tags = [set(fl.lost_tags) for fl in f.failures]
    assert tags[0].isdisjoint(tags[1])
    assert sorted(f.reemitted_tags) == sorted(tags[0] | tags[1])
    assert f.dup_dropped == 0


def test_respawned_worker_can_die_again():
    """A respawned slot is a first-class worker: kill the replacement too
    and the farm still completes (generation counter keeps ring/stage
    names unique)."""
    gate = threading.Event()

    def work(x):
        gate.wait(5)
        return x * 2

    f = Farm(work, workers=2, respawn=True, capacity=4)
    StageKillSwitch(after_items=1).arm(f._workers[1])

    killed_second = []

    def arm_replacement():
        # once the first respawn happened, arm the fresh worker too
        for _ in range(2000):
            if f._gen[1] == 1 and f._workers[1].name.endswith("r1"):
                StageKillSwitch(after_items=1).arm(f._workers[1])
                killed_second.append(True)
                return
            threading.Event().wait(0.001)

    t = threading.Thread(target=arm_replacement)
    with Pipeline([f]) as pipe:
        t.start()
        threading.Timer(0.2, gate.set).start()
        out = pipe.run(range(N))
    t.join()
    assert out == [x * 2 for x in range(N)]
    assert f.dup_dropped == 0
    if killed_second and len(f.failures) == 2:
        assert f._gen[1] == 2
        assert sorted(f.reemitted_tags) == sorted(
            t for fl in f.failures for t in fl.lost_tags)


def test_take_worker_failures_drains():
    f = Farm(lambda x: x, workers=2, respawn=True)
    StageKillSwitch(after_items=0).arm(f._workers[0])
    with Pipeline([f]) as pipe:
        out = pipe.run(range(30))
    assert out == list(range(30))
    took = f.take_worker_failures()
    assert len(took) == 1
    assert f.failures == ()
    assert f.take_worker_failures() == ()


def test_respawn_false_by_default():
    f = Farm(lambda x: x, workers=2)
    assert f._respawn is False
    assert "respawn=False" in repr(f)
    assert f.stats()["respawn"] is False


# -------------------------------------------------------- stage kill switch


def test_stage_kill_switch_validates():
    with pytest.raises(ValueError):
        StageKillSwitch(after_items=-1)


def test_stage_kill_switch_on_plain_pipeline_stage():
    """A killed pipeline stage (not in a farm) is the fail-stop case: the
    driver's bounded wait surfaces RelicDeadError with the stage's
    SystemExit as the chained cause."""
    st = Stage(lambda x: x, name="victim")
    ks = StageKillSwitch(after_items=3).arm(st)
    with pytest.raises(RelicDeadError) as ei:
        with Pipeline([st]) as pipe:
            pipe.run(range(20))
    assert ks.fired and ks.killed_after == 3
    assert _find(ei.value, SystemExit) is not None


# ------------------------------------------------------- stage supervision


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_pipeline_supervisor_flags_stalled_stage():
    clock = FakeClock()
    sup = LaneSupervisor(n_lanes=2, heartbeat_s=0.1, clock=clock)
    gate = threading.Event()

    def wedge(x):
        gate.wait(5)
        return x

    pipe = Pipeline([lambda x: x + 1, wedge], supervisor=sup)
    try:
        with pipe:
            assert sup.names == ["<lambda>", "wedge"]
            pipe.put(0)
            flagged = False
            for _ in range(400):
                clock.t += 0.15
                pipe.check_stages()
                if pipe.stalled_stages():
                    flagged = True
                    break
                threading.Event().wait(0.005)
            assert flagged
            assert pipe.stalled_stages() == ["wedge"]
            assert sup.stalled_names() == ["wedge"]
            gate.set()
            assert pipe.get() == 1
            # progress clears the flag on the next sweeps
            for _ in range(400):
                clock.t += 0.15
                pipe.check_stages()
                if not pipe.stalled_stages():
                    break
                threading.Event().wait(0.005)
            assert pipe.stalled_stages() == []
    finally:
        gate.set()


def test_pipeline_supervisor_advisory_only():
    """A stalled flag never raises, and an unsupervised pipeline reports
    empty flags from the same accessors."""
    pipe = Pipeline([lambda x: x])
    with pipe:
        assert pipe.check_stages() is False
        assert pipe.stalled_stages() == []
        assert pipe.straggler_stages() == []
        assert pipe.run([1, 2, 3]) == [1, 2, 3]


def test_pipeline_supervisor_size_mismatch_raises():
    with pytest.raises(StreamUsageError):
        Pipeline([lambda x: x], supervisor=LaneSupervisor(n_lanes=3))


def test_lane_supervisor_names():
    sup = LaneSupervisor(n_lanes=2, names=["a", "b"])
    assert sup.names == ["a", "b"]
    assert sup.stalled_names() == []
    with pytest.raises(ValueError):
        LaneSupervisor(n_lanes=2, names=["only-one"])
    unnamed = LaneSupervisor(n_lanes=1)
    assert unnamed._name(0) == "lane0"


def test_pipeline_supervisor_does_not_rename_existing():
    sup = LaneSupervisor(n_lanes=1, names=["custom"])
    with Pipeline([lambda x: x], supervisor=sup) as pipe:
        assert sup.names == ["custom"]
        assert pipe.run([1]) == [1]


# ------------------------------------------------------------- invariants


def test_no_lock_no_queue_in_recovery_path():
    """The recovery machinery must not smuggle a lock or MPMC queue onto
    the item path — same structural pin as tests/test_stream.py."""
    import inspect

    import repro.stream.farm as farm_mod
    src = inspect.getsource(farm_mod)
    assert "Lock(" not in src
    assert "queue.Queue" not in src
