"""Program spans (``repro.runtime.metrics.span``): with no profiler trace
recording, the task graph, serving and prefetch paths build no
``TraceAnnotation``; inside a ``jax.profiler`` trace, each layer's spans
appear with the ids that pair them across threads."""

import collections
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.runtime import metrics


class _Counting(jax.profiler.TraceAnnotation):
    made = collections.Counter()

    def __init__(self, name, /, **ids):
        _Counting.made[name] += 1
        super().__init__(name, **ids)


@pytest.fixture
def counting(monkeypatch):
    """Count every ``TraceAnnotation`` the program constructs."""
    _Counting.made.clear()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Counting)
    monkeypatch.setattr(metrics, "_annotation", _Counting)
    yield _Counting.made


@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs import get_config
    from repro.launch.serve import load

    return load(get_config("relic_tiny", smoke=True))


def _graph_run():
    from repro.tasks.api import TaskGraph, TaskScope

    g = TaskGraph()
    g.task("a", lambda: 1)
    g.task("b", lambda: 2)
    g.task("c", lambda: 3)
    g.task("d", lambda a, b, c: a + b + c, deps=("a", "b", "c"))
    with TaskScope("relic") as scope:
        assert g.run(scope)["d"] == 6


def _served(model, params):
    from repro.launch.serve import serve

    prompts = jnp.zeros((1, 4), jnp.int32)
    (resp,) = serve(model, params, [prompts], gen=3, cache_len=8)
    assert resp.status == "ok" and len(resp.result()) == 3
    return resp


def _prefetched():
    from repro.data import DataConfig, PrefetchPipeline, SyntheticLM

    dc = DataConfig(seq_len=8, global_batch=2, vocab_size=50, prefetch=2)
    pipe = PrefetchPipeline(SyntheticLM(dc), dc).start()
    try:
        assert pipe.next_batch()["tokens"].shape == (2, 8)
    finally:
        pipe.stop()


def test_span_is_the_shared_null_context_while_off():
    assert not metrics.spans_enabled()
    assert metrics.span("task.run", task=1) is metrics.span("data.wait")


def test_spans_off_build_no_annotation(counting, tiny_model):
    _graph_run()
    _served(*tiny_model)
    _prefetched()
    assert sum(counting.values()) == 0


def test_spans_on_build_annotations_inside_a_trace(counting, tmp_path):
    # the control for the test above: the count sees what spans build
    jax.profiler.start_trace(str(tmp_path))
    try:
        _graph_run()
    finally:
        jax.profiler.stop_trace()
    assert counting["task.submit"] == 2 and counting["task.run"] == 4
    assert counting["graph.run"] == 1 and counting["graph.join"] == 2


def _events(path):
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_no, line in enumerate(plane.lines):
            out.extend((e.name, line_no, e.start_ns, e.start_ns
                        + e.duration_ns, dict(e.stats))
                       for e in line.events
                       if e.name.startswith(("task.", "graph.", "serve.",
                                             "data.")))
    return out


def test_spans_on_land_in_the_profiler_trace_with_their_ids(tmp_path,
                                                            tiny_model):
    import glob

    from repro.serve import Request

    model, params = tiny_model
    _served(model, params)          # compiled before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _graph_run()
        resp = _served(model, params)
        _prefetched()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = _events(path)
    names = collections.Counter(name for name, *_ in events)
    assert names["graph.run"] == 1 and names["graph.join"] == 2
    graph_runs = [ev for ev in events if ev[0] == "task.run"
                  and ev[4]["name"] in "abcd"]
    assert sorted(ev[4]["name"] for ev in graph_runs) == list("abcd")
    assert names["data.wait"] == 1
    assert names["serve.request"] == 1 and names["serve.step"] == 2
    for name in ("serve.cache_init", "serve.prefill", "serve.first_token",
                 "serve.finish"):
        assert names[name] == 1, name

    # every submitted task's run carries its id (and its label) on another
    # thread; of the graph's tasks, the producer runs c and d inline
    submits = {ev[4]["task"]: ev for ev in events if ev[0] == "task.submit"}
    runs = {ev[4]["task"]: ev for ev in events if ev[0] == "task.run"}
    assert set(submits) <= set(runs)
    handed = sorted(runs[task][4]["name"] for task in submits)
    assert [n for n in handed if n in "abcd"] == ["a", "b"]
    for task, sub in submits.items():
        run = runs[task]
        assert run[1] != sub[1] and run[2] >= sub[2]

    # the request's spans share its rid, which the server's span carries
    rid = resp.request.rid
    serving = [ev for ev in events if ev[0].startswith("serve.")]
    assert {ev[4]["rid"] for ev in serving} == {rid}
    (request,) = [ev for ev in serving if ev[0] == "serve.request"]
    for ev in serving:
        assert ev[1] == request[1]                   # the lane's thread
        assert request[2] <= ev[2] and ev[3] <= request[3]
    steps = sorted(ev[4]["pos"] for ev in serving if ev[0] == "serve.step")
    assert steps == [4, 5]
    assert Request.next_rid() > rid


def test_serve_request_ids_come_from_the_shared_counter(tiny_model):
    from repro.serve import Request

    before = Request.next_rid()
    resp = _served(*tiny_model)
    assert resp.request.rid > before


def test_spans_follow_the_profiler_on_every_thread(tmp_path):
    seen = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        t = threading.Thread(target=lambda: seen.append(
            metrics.spans_enabled()))
        t.start()
        t.join()
    finally:
        jax.profiler.stop_trace()
    assert seen == [True] and not metrics.spans_enabled()


def test_spans_need_no_jax_where_none_is_imported():
    code = ("import sys; from repro.runtime import metrics; "
            "assert not metrics.spans_enabled(); "
            "assert metrics.span('data.wait') is metrics._NULL_SPAN; "
            "assert 'jax' not in sys.modules")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    subprocess.run([sys.executable, "-c", code], check=True, env=env)
