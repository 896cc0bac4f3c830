"""A whole run of every cell, at a small size on the CPU (the look for a
chip skipped): sound, it comes out correct; with the timed path broken
underneath, it comes out not correct."""

import functools

import pytest

from bench import run
from bench.tests.tiny import make_root

SEED = 2**33 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


def _run(root, workload, seconds=1.0, trace=False):
    return run.run_cell(workload, SEED, seconds, trace, require_chip=False,
                        root=root, t_start=0.0)


@pytest.mark.parametrize("workload", run.load_spec()["workloads"],
                         ids=lambda w: w["name"])
def test_sound_run_is_correct(root, workload):
    line = _run(root, workload["name"], seconds=2.0)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) >= {"setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "checks"
    phases = line["setup"]["phases_s"]
    assert list(phases)[:2] == ["import", "devices"] and len(phases) > 2
    gap = line["metrics"]["setup_s"]["value"] - sum(phases.values())
    assert 0 <= gap < 0.5      # the phases account for the set-up


def test_traced_run_reports_layers_and_breakdown(root):
    line = _run(root, "gap-kron5-relic", trace=True)
    assert line["correct"]
    assert "hosttask.device_ms_per_graph" in line["metrics"] or \
        line["device"]["busy_s"] == 0.0     # the CPU has no device plane
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _altered_serve_step(make):
    def make_step(model):
        step = make(model)

        def altered(params, cache, tokens, pos):
            nxt, logits, cache = step(params, cache, tokens, pos)
            return (nxt + 1) % model.cfg.vocab_size, logits, cache
        return altered
    return make_step


def test_serve_token_altered_where_produced(root, monkeypatch):
    from repro.launch import serve

    monkeypatch.setattr(serve, "make_serve_step",
                        _altered_serve_step(serve.make_serve_step))
    line = _run(root, "phi3-serve-decode")
    assert not line["correct"], line["checks"]


def _train_fault(kind, make):
    def make_step(model, oc):
        step = make(model, oc)

        def faulty(state, batch):
            if kind == "half_batch":
                rows = batch["tokens"].shape[0] // 2
                return step(state, {k: v[:rows] for k, v in batch.items()})
            _, metrics = step(state, batch)
            return state, metrics
        return faulty
    return make_step


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch"])
def test_train_step_fault(root, monkeypatch, kind):
    from repro.launch import steps

    monkeypatch.setattr(steps, "make_train_step",
                        _train_fault(kind, steps.make_train_step))
    line = _run(root, "phi3-train-stage4")
    assert not line["correct"], line["checks"]


def test_gap_answer_altered_where_produced(root, monkeypatch):
    from repro.tasks import graph

    tc = graph.triangle_count
    monkeypatch.setattr(graph, "triangle_count",
                        functools.wraps(tc)(lambda adj: tc(adj) + 1.0))
    line = _run(root, "gap-kron5-relic")
    assert not line["correct"], line["checks"]
    assert line["checks"]["exact_mismatches"]["value"] > 0


def test_gap_answer_altered_in_one_graph_of_the_window(root, monkeypatch):
    """Every graph of the window is checked, not a sample: one wrong
    answer among them makes the run not correct."""
    from repro.tasks import graph

    tc, calls = graph.triangle_count, []

    def once_wrong(adj):
        calls.append(1)
        return tc(adj) + (1.0 if len(calls) == 7 else 0.0)

    monkeypatch.setattr(graph, "triangle_count",
                        functools.wraps(tc)(once_wrong))
    line = _run(root, "gap-kron5-relic")
    assert line["attempted"] > 20
    assert not line["correct"], line["checks"]
    assert line["checks"]["exact_mismatches"]["value"] == 2   # tc, summary
