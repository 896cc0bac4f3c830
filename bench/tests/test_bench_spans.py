"""The program's spans in a traced run (``bench.spans``) and the per-layer
metrics that read them, on a synthetic ``.xplane.pb`` with program spans
and stats on two host lines."""

import os
import shutil

import pytest

from bench import run
from bench import spans as sp
from bench import trace as tr
from bench.tests.test_bench_trace import DEVICE0, DEVICE1, _line, _meta
from bench.tests.test_bench_trace import xplane  # noqa: F401 (fixture)

# The device lines of test_bench_trace, with the program's spans on two
# host lines (times in microseconds). Main thread: the benchmark's spans
# (bench.window [0, 10), bench.step [0, 5), bench.sync [5, 10)), graph.run
# over [1, 9.5), task.submit of tasks 1 and 2 over [1.5, 2) and [2, 2.5),
# graph.join over [5.5, 9). Worker thread: task 1 runs over [2.25, 4.75),
# holding graph.dispatch [2.25, 2.75) and graph.sync [3, 4.5); task 2 over
# [6, 8.5), holding graph.sync [6.75, 8.5).
PROGRAM_NAMES = ["bench.window", "bench.step", "bench.sync", "graph.run",
                 "task.submit", "graph.join", "task.run", "graph.dispatch",
                 "graph.sync"]
STATS = {"task": 1, "name": 2, "wave": 3}
MAIN = [(1, 0, 10, {}), (2, 0, 5, {}), (3, 5, 5, {}), (4, 1, 8.5, {}),
        (5, 1.5, 0.5, {"task": 1}), (5, 2, 0.5, {"task": 2}),
        (6, 5.5, 3.5, {"wave": 1})]
WORKER = [(7, 2.25, 2.5, {"task": 1, "name": "bfs"}),
          (8, 2.25, 0.5, {"name": "bfs"}), (9, 3, 1.5, {"name": "bfs"}),
          (7, 6, 2.5, {"task": 2, "name": "cc"}),
          (9, 6.75, 1.75, {"name": "cc"})]

NEW_READERS = ["relic.handoff_p95_us", "hosttask.join_ms_per_graph",
               "hosttask.dispatch_ms_per_graph", "hosttask.sync_ms_per_graph",
               "serve.boundary_idle_ms_per_request",
               "data.prefetch_wait_ms_per_step"]


def _stat(key, value):
    kind = "int64_value" if isinstance(value, int) else "str_value"
    value = value if isinstance(value, int) else f'"{value}"'
    return f"stats {{ metadata_id: {STATS[key]} {kind}: {value} }}"


def _host_line(line_id, events):
    evs = " ".join(
        f"events {{ metadata_id: {m} offset_ps: {round(a * 10**6)} "
        f"duration_ps: {round(d * 10**6)} "
        + " ".join(_stat(k, v) for k, v in stats.items()) + " }"
        for m, a, d, stats in events)
    return (f'lines {{ id: {line_id} name: "python" timestamp_ns: 1000000 '
            f'{evs} }}')


@pytest.fixture(scope="module")
def program_xplane(tmp_path_factory):
    from jax.profiler import ProfileData

    ops = _meta(["fusion.1", "copy.2"])
    stats = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for n, i in STATS.items())
    text = "\n".join([
        f'planes {{ id: 1 name: "/device:TPU:0" {_line(DEVICE0, "XLA Ops")} '
        f'{ops} }}',
        f'planes {{ id: 2 name: "/device:TPU:1" {_line(DEVICE1, "XLA Ops")} '
        f'{ops} }}',
        f'planes {{ id: 3 name: "/host:CPU" {_host_line(1, MAIN)} '
        f'{_host_line(2, WORKER)} {_meta(PROGRAM_NAMES)} {stats} }}',
    ])
    path = tmp_path_factory.mktemp("trace") / "p.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.fixture
def as_latest(tmp_path, monkeypatch):
    """Put a trace where ``bench.spans.of_run`` looks for the run's own."""
    monkeypatch.setattr(sp, "ROOT", tmp_path)
    sp._cache.clear()

    def put(path, cell="cell"):
        where = tmp_path / ".bench_out" / "trace" / cell
        where.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, where / "t.xplane.pb")
        return str(where / "t.xplane.pb")

    yield put
    sp._cache.clear()


def test_program_spans_keep_their_line_and_stats(program_xplane):
    _, spans = sp.read_events(program_xplane)
    runs = [s for s in spans if s[0] == "task.run"]
    assert [s.stats for s in runs] == [{"task": 1, "name": "bfs"},
                                       {"task": 2, "name": "cc"}]
    main = {s.line for s in spans if s[0].startswith("bench.")}
    assert len(main) == 1 and {s.line for s in runs}.isdisjoint(main)


def test_idle_is_charged_to_the_innermost_program_span(program_xplane):
    got = sp.reduce_file(program_xplane)
    # gaps [3,4) in bfs's graph.sync, [5,8) in task 2's task.run (graph.join
    # on the main thread is longer), [9,10) in bench.sync alone
    assert got["idle_by_span"] == pytest.approx(
        {"task.run": 3e-6, "graph.sync": 1e-6, "bench.sync": 1e-6})
    assert got["window_s"] == pytest.approx(10e-6)
    # the benchmark's own reduction reads the file as it reads one with
    # no program spans
    plain = tr.reduce_file(program_xplane)
    assert [row[0].split(" (")[0] for row in plain["breakdown"]["idle_gaps"]
            ] == ["bench.sync", "bench.step"]
    assert plain["busy_s"] == pytest.approx(5.5e-6)


def test_program_span_counts_seconds_and_self_time(program_xplane):
    names = sp.reduce_file(program_xplane)["names"]
    expect = {  # count, seconds, self seconds, idle seconds (us)
        "graph.run": (1, 8.5, 4.0, 0), "task.submit": (2, 1.0, 1.0, 0),
        "graph.join": (1, 3.5, 3.5, 0), "task.run": (2, 5.0, 1.25, 3),
        "graph.dispatch": (1, 0.5, 0.5, 0), "graph.sync": (2, 3.25, 3.25, 1)}
    assert set(names) == set(expect)
    for name, (n, secs, own, idle) in expect.items():
        rec = names[name]
        assert rec["count"] == n, name
        assert rec["seconds"] == pytest.approx(secs * 1e-6), name
        assert rec["self_s"] == pytest.approx(own * 1e-6), name
        assert rec["idle_s"] == pytest.approx(idle * 1e-6), name


def test_handoff_pairs_submit_and_run_by_task_id(program_xplane):
    got = sp.reduce_file(program_xplane)["handoff_s"]
    # task 1: submit ends at 2, runs at 2.25; task 2: ends 2.5, runs at 6
    assert got == pytest.approx([0.25e-6, 3.5e-6])
    early = [sp.Span("task.submit", 0.0, 2.0, 0, {"task": 7}),
             sp.Span("task.run", 1.0, 3.0, 1, {"task": 7}),
             sp.Span("task.run", 4.0, 5.0, 1, {"task": 8})]
    assert sp.handoffs(early) == [-1.0]   # started before submit returned


def test_hosttask_readers_on_the_program_trace(program_xplane, as_latest):
    trace = tr.reduce_file(as_latest(program_xplane))
    read = {m: run.metric_reader(m)({}, trace) for m in NEW_READERS[:4]}
    assert read == pytest.approx({
        "relic.handoff_p95_us": 3.5, "hosttask.join_ms_per_graph": 3.5e-3,
        "hosttask.dispatch_ms_per_graph": 0.5e-3,
        "hosttask.sync_ms_per_graph": 3.25e-3})


def test_serve_and_data_readers(monkeypatch):
    # device busy [0,1), [2,3), [6,10); the window is [0,10)
    ops = {"/device:TPU:0": [("a", 0.0, 1.0), ("b", 2.0, 3.0),
                             ("c", 6.0, 10.0)]}
    spans = [sp.Span("bench.window", 0.0, 10.0),
             sp.Span("serve.request", 0.0, 5.0, 1, {"rid": 0}),
             sp.Span("serve.cache_init", 0.0, 0.2, 1, {"rid": 0}),
             sp.Span("serve.prefill", 0.2, 1.0, 1, {"rid": 0}),
             sp.Span("serve.first_token", 1.0, 2.0, 1, {"rid": 0}),
             sp.Span("serve.step", 2.0, 3.0, 1, {"rid": 0, "pos": 4}),
             sp.Span("serve.step", 4.0, 4.8, 1, {"rid": 0, "pos": 5}),
             sp.Span("serve.request", 5.5, 12.0, 1, {"rid": 1}),
             sp.Span("data.wait", 0.0, 0.5, 2), sp.Span("data.wait", 5.0,
                                                        6.0, 2)]
    got = sp.reduce(ops, spans)
    monkeypatch.setattr(sp, "of_run", lambda trace: got)
    # gap [1,2) in serve.first_token counts; gap [3,6) in serve.step not;
    # one request ended in the window
    assert run.metric_reader("serve.boundary_idle_ms_per_request")(
        {}, {}) == pytest.approx(1000.0)
    assert run.metric_reader("data.prefetch_wait_ms_per_step")(
        {}, {}) == pytest.approx(750.0)


@pytest.mark.parametrize("metric", NEW_READERS)
def test_span_readers_read_nothing_without_their_spans(metric, xplane,
                                                       as_latest):
    read = run.metric_reader(metric)
    assert read({}, tr.reduce_file(xplane)) is None     # no trace file
    trace = tr.reduce_file(as_latest(xplane))
    assert sp.of_run(trace)["names"] == {}
    assert read({}, trace) is None


def test_of_run_takes_only_the_runs_own_trace(program_xplane, xplane,
                                              as_latest):
    assert sp.of_run(None) is None and sp.latest_trace() is None
    mine = tr.reduce_file(program_xplane)
    older = as_latest(program_xplane, "a")
    os.utime(older, (1, 1))
    newer = as_latest(xplane, "b")         # another cell's, newer
    other = tr.reduce_file(xplane)
    assert sp.of_run(other)["names"] == {}
    # the newest trace has another window: not this run's
    assert sp.of_run(dict(mine, window_s=mine["window_s"] * 2)) is None
    later = os.path.getmtime(newer) + 10
    os.utime(older, (later, later))        # now the newest
    assert set(sp.of_run(mine)["names"]) >= {"graph.run", "task.run"}


def test_attribute_all_agrees_with_attribute():
    import random

    rng = random.Random(5)
    spans = []
    for i in range(300):
        a = rng.uniform(0, 100)
        spans.append((f"s{i % 7}", a, a + rng.expovariate(0.2)))
    busy = tr.union((t, t + rng.uniform(0, 0.5))
                    for t in (rng.uniform(0, 100) for _ in range(400)))
    idle = tr.gaps(busy, 0, 100)
    assert sp.attribute_all(idle, spans) == [tr.attribute(g, spans)
                                             for g in idle]


def test_program_spans_record_in_the_benchmarks_trace(tmp_path):
    from repro.runtime import metrics

    assert not metrics.spans_enabled()
    t = tr.Tracer(True, str(tmp_path / "t"))
    t.start()
    try:
        assert metrics.spans_enabled()
    finally:
        path = t.stop()
    assert not metrics.spans_enabled() and path.endswith(".xplane.pb")
    tr.Tracer(False, str(tmp_path / "u")).start()
    assert not metrics.spans_enabled()
