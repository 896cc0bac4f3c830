"""The controls, at a size a CPU test can hold: the reference put in the
program's place one precision below what the configuration states, and
the training faults, each judged by ``Outcome.correct`` against the cell's
limits as a run would judge it.

The limits were set from the same readings at the cells' own sizes on the
chip (``bench/control.py``; ``PERF.md`` gives the readings). Training and
the GAP graph are checked against those limits: the GAP input is the
cell's own size, and a bfloat16 master copy loses Adam's first updates at
any size. The served model's limit belongs to its full depth and width
(there the int8 control is judged not correct on every seed); at this
size the fp8 control is judged not correct, and the int8 one reads at
least three times what the program reads."""

import functools

from bench import control, run
from bench.drivers.task_graph import compare, graph_input
from bench.reference import gap as ref_gap
from bench.tests.tiny import make_root

SEEDS = [2**33 + 21, 5, 77]


def _cell(tmp_path, workload, **sizes):
    root = make_root(tmp_path, **sizes)
    return run.load_cell(run.load_spec(root), workload, root)


def _readings(cell, seeds):
    kind = cell["traffic_file"]["kind"]
    return list(control.READINGS[kind](cell, seeds, len(seeds)))


def test_judged_is_the_runs_correct():
    limits = {"a": 1.0, "b": 0.0}
    assert control.judged({"a": 1.0, "b": 0.0}, limits)["correct"]
    assert not control.judged({"a": 1.5, "b": 0.0}, limits)["correct"]
    assert not control.judged({"a": float("nan")}, limits)["correct"]
    assert not control.judged({"other": 0.0}, limits)["correct"]


def test_gap_one_pass_control_is_not_correct(tmp_path):
    cell = _cell(tmp_path, "gap-kron5-relic")
    for line in _readings(cell, SEEDS):
        assert line["program"]["correct"], line
        assert not line["control"]["default"]["correct"], line


def test_gap_three_pass_control_is_not_correct_on_most_graphs(tmp_path):
    """Precision.HIGH computes bc exactly on about a third of the graphs,
    and its pagerank error there lies within float32's own: no limit on
    those answers can catch it on such a graph. On the others bc reads ten
    times the program's worst, so the limit fails it."""
    c = _cell(tmp_path, "gap-kron5-relic")["config_file"]
    high = functools.partial(ref_gap.lowp_matvec, passes=3)
    verdicts = []
    for seed in range(121, 145):
        adj, w = graph_input(c, seed)
        ref = ref_gap.gap_suite(adj, w, c)
        verdicts.append(control.judged(compare(
            ref_gap.gap_suite(adj, w, c, high), ref), c["checks"])["correct"])
    assert verdicts[:3] == [False] * 3        # the seeds read on the chip
    assert verdicts.count(False) >= 16, verdicts


def test_train_controls_and_faults_are_not_correct(tmp_path):
    cell = _cell(tmp_path, "phi3-train-stage4")
    for line in _readings(cell, SEEDS[:2]):
        assert line["program"]["correct"], line
        assert not line["control"]["bf16_master"]["correct"], line
        for fault in line["faults"].values():
            assert not fault["correct"], line


def test_serve_low_precision_controls(tmp_path):
    cell = _cell(
        tmp_path, "phi3-serve-decode",
        lm=dict(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
                head_dim=64, vocab_size=2048),
        traffic={"serve_queue": dict(batch=4, gen=48, cache_len=64)})
    for line in _readings(cell, SEEDS):
        program = line["program"]["served_logit_gap"]
        assert line["program"]["correct"], line
        assert line["control"]["int8"]["served_logit_gap"] >= 3 * program, line
        assert not line["control"]["fp8"]["correct"], line
