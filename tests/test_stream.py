"""The streaming dataflow layer (repro.stream) and its three consumers.

Covers: Pipeline/Stage/Farm basics (exactly-once, FIFO, in-order farm
release, inline degradation), in-stream failure markers, supervision
(dead-stage detection), the structural
"no locks, SPSC-only" pins the PR acceptance demands, TaskGraph
``streaming=True`` parity with the barriered wavefront baseline (plus the
overlap a wavefront cannot express), the rebuilt PrefetchPipeline /
CheckpointManager (killed-assistant regression, overlapped saves), and
the oracle-checked ``Workload.streamed()`` variants on every substrate
including the chaos harness.
"""

import inspect
import threading
import time

import numpy as np
import pytest

import repro.stream.farm as farm_mod
import repro.stream.pipeline as pipeline_mod
import repro.stream.stage as stage_mod
from repro.core.relic import RelicDeadError
from repro.core.schedulers import available_schedulers, make_scheduler
from repro.data import DataConfig, PrefetchPipeline, SyntheticLM
from repro.stream import (Farm, Pipeline, Stage, StreamError, StreamFailure,
                          StreamUsageError, worker_alive)
from repro.tasks.api import TaskCancelledError, TaskGraph
from repro.workloads import make_workload

ALL = available_schedulers()


# ------------------------------------------------------------ pipeline basics

def test_pipeline_exactly_once_and_fifo():
    """Every item traverses every stage exactly once, in order."""
    seen = []
    with Pipeline([lambda x: x + 1,
                   lambda x: x * 10,
                   lambda x: (seen.append(x), x)[1]]) as pipe:
        out = pipe.run(list(range(50)))
    assert out == [(i + 1) * 10 for i in range(50)]
    assert seen == out  # stage 3 saw them in FIFO order


def test_pipeline_put_get_interleaved():
    with Pipeline([lambda x: x * 2]) as pipe:
        for i in range(10):
            pipe.put(i)
        got = [pipe.get() for _ in range(10)]
    assert got == [i * 2 for i in range(10)]


def test_pipeline_serial_substrate_runs_inline():
    """A workers==0 node degrades the whole network to inline execution on
    the driver — same results, no threads."""
    before = threading.active_count()
    with Pipeline([lambda x: x + 1, lambda x: x * 3],
                  substrate="serial") as pipe:
        assert pipe.inline
        assert threading.active_count() == before
        out = pipe.run(list(range(20)))
    assert out == [(i + 1) * 3 for i in range(20)]


def test_pipeline_scheduler_instance_fuses_stages():
    """A Scheduler *instance* substrate hosts one loop: callable stages are
    composed into a single node on it."""
    with Pipeline([lambda x: x + 1, lambda x: x * 2],
                  substrate=make_scheduler("relic")) as pipe:
        assert len(pipe.nodes) == 1
        out = pipe.run([1, 2, 3])
    assert out == [4, 6, 8]


def test_pipeline_stats_and_in_flight():
    with Pipeline([lambda x: x]) as pipe:
        pipe.run(list(range(7)))
        stats = pipe.stats()
        assert stats[0]["items_in"] == 7 and stats[0]["items_out"] == 7
        assert pipe.in_flight() == 0


def test_pipeline_get_without_feed_is_usage_error():
    with Pipeline([lambda x: x]) as pipe:
        with pytest.raises(StreamUsageError):
            pipe.get_raw()


# ------------------------------------------------------------------- failures

def test_failure_marker_flows_not_kills():
    """A stage exception becomes an in-stream marker; later items still
    flow, and get() surfaces the original as __cause__."""
    def boom(x):
        if x == 3:
            raise ValueError("item three")
        return x * 10

    with Pipeline([boom, lambda x: x + 1]) as pipe:
        outs, errs = [], []
        for i in range(6):
            pipe.put(i)
        for _ in range(6):
            try:
                outs.append(pipe.get())
            except StreamError as e:
                errs.append(e)
    assert outs == [1, 11, 21, 41, 51]
    (err,) = errs
    assert isinstance(err.__cause__, ValueError)
    assert "boom" in str(err)


def test_run_preserves_slot_accounting_with_failures():
    """One-in/one-out even when some items fail: run() returns a marker in
    the failed slot, everything else unscathed."""
    def maybe(x):
        if x % 4 == 0:
            raise RuntimeError(f"no {x}")
        return x

    with Pipeline([maybe]) as pipe:
        out = pipe.run(list(range(12)), raw=True)
    assert len(out) == 12
    for i, o in enumerate(out):
        if i % 4 == 0:
            assert type(o) is StreamFailure
            assert str(o.error) == f"no {i}"
        else:
            assert o == i


# ----------------------------------------------------------------------- farm

@pytest.mark.parametrize("ordered", [True, False])
def test_farm_all_items_once(ordered):
    f = Farm(lambda x: x * x, workers=3, ordered=ordered)
    with Pipeline([f]) as pipe:
        out = pipe.run(list(range(40)))
    if ordered:
        assert out == [i * i for i in range(40)]
    else:
        assert sorted(out) == [i * i for i in range(40)]


def test_farm_in_order_release_under_skew():
    """Ordered collector stashes early finishers until their index is due."""
    def slow_evens(x):
        if x % 2 == 0:
            time.sleep(0.002)
        return x

    with Pipeline([Farm(slow_evens, workers=4, ordered=True)]) as pipe:
        out = pipe.run(list(range(30)))
    assert out == list(range(30))


def test_farm_worker_exception_is_marker():
    f = Farm(lambda x: 1 // x, workers=2, ordered=True)
    with Pipeline([f]) as pipe:
        out = pipe.run([2, 1, 0, 4], raw=True)
    assert out[:2] == [0, 1]
    assert type(out[2]) is StreamFailure
    assert isinstance(out[2].error, ZeroDivisionError)
    assert out[3] == 0


def test_farm_composes_with_stages():
    """Mixed [fn, Farm, fn] network: rings stay 1P1C end to end."""
    with Pipeline([lambda x: x + 1,
                   Farm(lambda x: x * 2, workers=3, ordered=True),
                   lambda x: x - 1]) as pipe:
        out = pipe.run(list(range(25)))
    assert out == [(i + 1) * 2 - 1 for i in range(25)]


def test_farm_rejects_instance_substrate():
    with pytest.raises(StreamUsageError):
        Farm(lambda x: x, substrate=make_scheduler("relic"))


# ---------------------------------------------------------------- supervision

def test_dead_stage_raises_relic_dead_error():
    """SystemExit kills a stage loop (not a marker); the consumer's bounded
    wait notices and raises RelicDeadError chaining the original."""
    def die(x):
        if x == 2:
            raise SystemExit("stage killed")
        return x

    pipe = Pipeline([die]).start()
    try:
        for i in range(5):
            pipe.put(i)
        with pytest.raises(RelicDeadError) as ei:
            for _ in range(5):
                pipe.get_raw()
        assert isinstance(ei.value.__cause__, SystemExit)
    finally:
        pipe.close()    # cleanup is tolerant: never raises for dead stages


def test_worker_alive_duck_typing():
    assert worker_alive(make_scheduler("serial")) is True
    sched = make_scheduler("relic")
    assert worker_alive(sched) is True  # not started yet -> not dead
    sched.start()
    try:
        assert worker_alive(sched) is True
    finally:
        sched.close()


# ----------------------------------------------------- structural lock pins

def test_stream_layer_has_no_locks():
    """Acceptance pin: no locks or MPMC queues anywhere on the streaming
    hot path — composition of 1P1C rings replaces them."""
    for mod in (stage_mod, pipeline_mod, farm_mod):
        src = inspect.getsource(mod)
        assert "Lock(" not in src, mod.__name__
        assert "queue.Queue" not in src, mod.__name__


def test_prefetch_pipeline_push_lock_is_gone():
    p = PrefetchPipeline(SyntheticLM(DataConfig(8, 4, 50)),
                         DataConfig(8, 4, 50))
    assert not hasattr(p, "_push_lock")
    assert "Lock" not in inspect.getsource(PrefetchPipeline)


def test_checkpoint_write_lock_is_gone(tmp_path):
    from repro.checkpoint import CheckpointManager
    mgr = CheckpointManager(tmp_path, async_=False)
    assert not hasattr(mgr, "_write_lock")
    assert "Lock" not in inspect.getsource(CheckpointManager)


# ------------------------------------------------------- TaskGraph streaming

def _diamond():
    g = TaskGraph()
    g.task("a", lambda: 2)
    g.task("b", lambda a: a + 1, deps=("a",))
    g.task("c", lambda a: a * 10, deps=("a",))
    g.task("d", lambda b, c: b + c, deps=("b", "c"))
    return g


@pytest.mark.parametrize("name", ALL)
def test_taskgraph_streaming_matches_wavefront(name):
    want = _diamond().run(name, streaming=False)
    got = _diamond().run(name, streaming=True)
    assert got == want == {"a": 2, "b": 3, "c": 20, "d": 23}


def test_taskgraph_streaming_overlaps_wavefronts():
    """A deep independent chain completes while a slow sibling of its root
    still runs — the schedule a barriered wavefront cannot produce."""
    order = []
    g = TaskGraph()
    g.task("slow", lambda: (time.sleep(0.15), order.append("slow"))[0])
    prev = None
    for i in range(4):
        name = f"f{i}"
        # dep result is ignored; the chain just has to be sequential
        if prev is None:
            g.task(name, lambda i=i: order.append(f"f{i}"))
        else:
            g.task(name, lambda _x, i=i: order.append(f"f{i}"), deps=(prev,))
        prev = name
    g.run("relic", streaming=True)
    assert order.index("slow") == len(order) - 1


@pytest.mark.parametrize("streaming", [False, True])
def test_taskgraph_failure_and_cancellation_parity(streaming):
    g = TaskGraph()
    g.task("ok", lambda: 1)
    g.task("bad", lambda: 1 // 0)
    g.task("down", lambda bad: bad, deps=("bad",))
    with pytest.raises(ZeroDivisionError):
        g.run("relic", streaming=streaming)


def test_taskgraph_as_stream_alias():
    assert _diamond().as_stream("serial") == _diamond().run(
        "serial", streaming=True)


def test_taskgraph_streaming_cancelled_downstream():
    g = TaskGraph()
    g.task("bad", lambda: 1 // 0)
    g.task("down", lambda bad: bad, deps=("bad",))
    try:
        g.run("relic", streaming=True)
    except ZeroDivisionError:
        pass
    with pytest.raises(TaskCancelledError):
        g.handle("down").result()


# ------------------------------------------- rebuilt PrefetchPipeline (PR 8)

def test_prefetch_killed_assistant_raises_dead_error():
    """The PR 8 supervision gap, closed: a producer killed mid-stream (by a
    non-Exception BaseException) surfaces as RelicDeadError with loss
    diagnostics instead of an unbounded consumer spin."""
    class KilledSource:
        def __init__(self):
            self.dc = DataConfig(8, 4, 50, prefetch=2)

        def batch(self, index):
            if index >= 1:
                raise SystemExit("assistant killed")
            return {"tokens": np.zeros((4, 8), np.int32)}

    src = KilledSource()
    p = PrefetchPipeline(src, src.dc).start()
    try:
        with pytest.raises(RelicDeadError) as ei:
            for _ in range(4):
                p.next_batch()
        assert "dead" in str(ei.value)
        assert isinstance(ei.value.__cause__, SystemExit)
    finally:
        try:
            p.stop()
        except RelicDeadError:
            pass


def test_prefetch_transform_overlaps_as_second_stage():
    dc = DataConfig(8, 4, 50, prefetch=4)
    calls = []

    def tag(batch):
        calls.append(1)
        batch = dict(batch)
        batch["tagged"] = True
        return batch

    p = PrefetchPipeline(SyntheticLM(dc), dc, transform=tag).start()
    try:
        assert len(p._pipe.nodes) == 2  # produce + transform stages
        for _ in range(6):
            assert p.next_batch()["tagged"] is True
        assert len(calls) >= 6
    finally:
        p.stop()


# ------------------------------------------ rebuilt CheckpointManager (PR 9)

def test_checkpoint_overlapped_saves_land_in_order(tmp_path):
    """Back-to-back async saves overlap (serialize N+1 while N publishes)
    yet publish FIFO: retention keeps exactly the newest `keep`."""
    from repro.checkpoint import CheckpointManager
    state = {"w": np.arange(16, dtype=np.float32)}
    mgr = CheckpointManager(tmp_path, keep=2, async_=True)
    for s in range(6):
        mgr.save(state, s)
    mgr.close()
    steps = sorted(p.name for p in tmp_path.glob("step_*"))
    assert steps == ["step_00000004", "step_00000005"]
    assert not list(tmp_path.glob("*.tmp*"))  # no leaked tmp dirs


# ------------------------------------------------- streamed workload oracles

@pytest.mark.parametrize("name", ["stencil", "json"])
@pytest.mark.parametrize("substrate", ["serial", "relic", "chaos"])
def test_streamed_workloads_pass_oracles(name, substrate):
    """streamed() is oracle-checked like every other variant — including
    under the chaos substrate's default stall plan."""
    w = make_workload(name)
    w.check(w.streamed(substrate))


def test_streamed_matches_serial_variant():
    w = make_workload("stencil")
    streamed = w.streamed("relic")
    serial = w.serial()
    for a, b in zip(streamed, serial):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
