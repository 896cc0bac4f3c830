"""Oracle conformance for ``repro.workloads``: every workload, every
execution variant, against its independent oracle — plus the regression
tests for the paper_kernels block-until-ready bug and its deprecation shim.

Workload instances are cached per (name, n_instances) at module scope:
inputs and jit warmup are paid once, and the variant tests reuse the same
compiled kernels (the jit cache is process-wide anyway).
"""

import json as json_mod
import warnings

import numpy as np
import pytest

from repro.core.schedulers import available_schedulers, make_scheduler
from repro.tasks import jsonparse
from repro.tasks.api import TaskScope
from repro.workloads import (PAPER_WORKLOADS, VARIANTS, WorkloadOracleError,
                             available_workloads, make_workload,
                             results_agree)

ALL = available_workloads()
_CACHE = {}


def workload(name, n_instances=None):
    key = (name, n_instances)
    if key not in _CACHE:
        _CACHE[key] = make_workload(name, n_instances=n_instances)
    return _CACHE[key]


def test_registry_covers_paper_and_growth():
    """The paper's seven kernels plus the two scenario-growth workloads."""
    assert set(PAPER_WORKLOADS) <= set(ALL)
    assert {"stencil", "histogram"} <= set(ALL)
    assert len(ALL) >= 9
    assert VARIANTS == ("serial", "paired", "chunked")
    with pytest.raises(ValueError, match="unknown workload"):
        make_workload("no-such-workload")


# ---------------------------------------------------- variant oracle coverage

@pytest.mark.parametrize("name", ALL)
def test_serial_passes_oracle(name):
    w = workload(name)
    w.check(w.serial())


@pytest.mark.parametrize("name", ALL)
def test_paired_matches_serial_and_oracle(name):
    w = workload(name)
    serial = w.serial()
    with TaskScope("relic") as scope:
        paired = w.paired(scope)
    w.check(paired)
    assert all(results_agree(s, p) for s, p in zip(serial, paired))


@pytest.mark.parametrize("name", ALL)
def test_chunked_matches_serial_and_oracle(name):
    w = workload(name)
    serial = w.serial()
    with TaskScope("condvar") as scope:
        chunked = w.chunked(scope, grain=1)
    w.check(chunked)
    assert all(results_agree(s, c) for s, c in zip(serial, chunked))


@pytest.mark.parametrize("sub", available_schedulers())
def test_all_variants_agree_on_every_substrate(sub):
    """One cheap workload (histogram), both offload variants, all five
    substrates: the variant results must be indistinguishable from serial
    no matter the scheduling structure underneath."""
    w = workload("histogram", n_instances=4)
    serial = w.serial()
    with TaskScope(sub) as scope:
        paired = w.paired(scope)
        chunked_fine = w.chunked(scope, grain=1)
        chunked_coarse = w.chunked(scope, grain=2)
    for got in (paired, chunked_fine, chunked_coarse):
        w.check(got)
        assert all(results_agree(s, g) for s, g in zip(serial, got))


def test_more_than_two_instances_chunked():
    """Worksharing past the paper's pair: 4 instances, every grain."""
    w = workload("stencil", n_instances=4)
    with TaskScope("relic") as scope:
        for grain in (1, 2, 4):
            w.check(w.chunked(scope, grain=grain))
        w.check(w.paired(scope))


def test_min_instances_enforced():
    with pytest.raises(ValueError, match=">= 2 instances"):
        make_workload("histogram", n_instances=1)


def test_oracle_actually_rejects():
    """check() must fail loudly on corrupted results — the oracle is the
    benchmark's correctness gate, so prove it has teeth."""
    w = workload("histogram")
    good = w.serial()
    with pytest.raises(WorkloadOracleError):
        w.check(good[:1])                      # wrong instance count
    bad = [np.asarray(good[0]).copy() for _ in good]
    bad[1][0] += 1                             # instances disagree
    with pytest.raises(WorkloadOracleError):
        w.check(bad)
    corrupt = [np.asarray(r).copy() + 1 for r in good]   # oracle mismatch
    with pytest.raises(WorkloadOracleError):
        w.check(corrupt)


def test_json_oracle_counts_crosscheck():
    """The json workload's oracle is jsonparse.oracle_counts: verify the
    cross-check end-to-end against the kernel output."""
    w = workload("json")
    structural, depth, ok = w.serial()[0]
    expected = jsonparse.oracle_counts(jsonparse.WIDGET_JSON)
    assert bool(ok)
    assert int(np.asarray(structural).sum()) == expected["structural"]
    assert int(np.asarray(depth).max()) == expected["max_depth"]
    # and the check_one path itself accepts the kernel's own output
    w.check_one((structural, depth, ok))


# ------------------------------------------- block-until-ready regression

def test_task_closures_return_ready_results():
    """Regression (paper_kernels._pair): task closures must block until the
    result is ready, so paired-task timings measure compute, not async
    dispatch. bc is the heaviest kernel — an unblocked dispatch would
    still be in flight here."""
    w = workload("bc")
    for task in w.tasks:
        out = task()
        assert out.is_ready()


def test_paper_kernels_shim_is_deprecated_and_blocking():
    from benchmarks.paper_kernels import build_tasks

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        tasks = build_tasks()
    assert any(issubclass(c.category, DeprecationWarning) for c in caught)
    assert set(tasks) == set(PAPER_WORKLOADS)
    task_a, task_b, fused = tasks["bc"]
    assert task_a().is_ready() and task_b().is_ready()
    assert fused().is_ready()
    # historical contract: the json entry returns ONE scalar array
    # (structural.sum() + depth[-1] + ok), not the workload's raw tuple
    ja, jb, jf = tasks["json"]
    out = ja()
    assert out.shape == () and out.block_until_ready() is out
    assert float(ja()) == float(jb())
    assert jf().shape == (2,)  # fused: one value per instance


# ------------------------------------------------- the trajectory gate logic

def test_compare_against_flags_only_real_regressions(tmp_path, capsys):
    from benchmarks.run import Emitter, compare_against, load_baseline

    payload = {
        "meta": {"cpu_count": 2, "spin_pause_every": 1, "python": "3.10"},
        "sections": {"paper": [
            {"name": "paper/bc/serial", "us_per_call": 100.0, "derived": ""},
            {"name": "paper/bc/paired/relic", "us_per_call": 50.0,
             "derived": ""},
            {"name": "paper/gone/serial", "us_per_call": 1.0, "derived": ""},
        ]},
    }
    path = tmp_path / "BENCH_base.json"
    path.write_text(json_mod.dumps(payload))
    baseline = load_baseline(str(path))

    em = Emitter()
    em.sections = {"paper": [
        {"name": "paper/bc/serial", "us_per_call": 101.0, "derived": ""},
        {"name": "paper/bc/paired/relic", "us_per_call": 80.0, "derived": ""},
        {"name": "paper/new/serial", "us_per_call": 5.0, "derived": ""},
    ]}
    compared, regs = compare_against(em, baseline, tol=0.25)
    assert compared == 2
    assert [r["name"] for r in regs] == ["paper/bc/paired/relic"]
    assert regs[0]["ratio"] == pytest.approx(1.6)
    out = capsys.readouterr().out
    assert "REGRESSION paper/bc/paired/relic" in out

    em2 = Emitter()
    em2.sections = {"paper": [
        {"name": "paper/bc/serial", "us_per_call": 101.0, "derived": ""}]}
    assert compare_against(em2, baseline, tol=0.25) == (1, [])


def test_compare_against_speedup_metric_cancels_host_drift(tmp_path, capsys):
    """metric='speedup' gates on the recorded speedup-over-serial: a run
    that is uniformly 2x slower in absolute µs (host drift) passes, a row
    that actually *lost* speedup is flagged, and rows without a speedup on
    both sides are skipped rather than miscompared."""
    from benchmarks.run import Emitter, compare_against, load_baseline

    payload = {
        "meta": {"cpu_count": 2, "spin_pause_every": 1, "python": "3.10"},
        "sections": {"paper": [
            {"name": "paper/bc/serial", "us_per_call": 100.0,
             "derived": "n=2;speedup=1.000;oracle=ok"},
            {"name": "paper/bc/paired/relic", "us_per_call": 125.0,
             "derived": "speedup=0.800;oracle=ok"},
            {"name": "paper/bc/chunked/relic", "us_per_call": 125.0,
             "derived": "speedup=0.800;oracle=ok"},
            {"name": "paper/bc/paired/spin", "us_per_call": 125.0,
             "derived": "no-speedup-here"},
        ]},
    }
    path = tmp_path / "BENCH_base.json"
    path.write_text(json_mod.dumps(payload))
    baseline = load_baseline(str(path))

    em = Emitter()
    em.sections = {"paper": [
        # host 2x slower across the board...
        {"name": "paper/bc/serial", "us_per_call": 200.0,
         "derived": "n=2;speedup=1.000;oracle=ok"},
        # ...same relative speedup: NOT a regression under the metric
        {"name": "paper/bc/paired/relic", "us_per_call": 250.0,
         "derived": "speedup=0.800;oracle=ok"},
        # ...but this row genuinely lost speedup (0.8 -> 0.5)
        {"name": "paper/bc/chunked/relic", "us_per_call": 400.0,
         "derived": "speedup=0.500;oracle=ok"},
        # no speedup on the baseline side: skipped, never compared
        {"name": "paper/bc/paired/spin", "us_per_call": 999.0,
         "derived": "speedup=0.100"},
    ]}
    compared, regs = compare_against(em, baseline, tol=0.25,
                                     metric="speedup")
    assert compared == 3          # serial + the two relic rows
    assert [r["name"] for r in regs] == ["paper/bc/chunked/relic"]
    assert regs[0]["ratio"] == pytest.approx(1.6)
    out = capsys.readouterr().out
    assert "REGRESSION paper/bc/chunked/relic: speedup 0.800 -> 0.500" in out
    assert "metric speedup" in out

    # the same run under metric='us' flags every drifted row instead
    compared_us, regs_us = compare_against(em, baseline, tol=0.25)
    assert {r["name"] for r in regs_us} >= {
        "paper/bc/serial", "paper/bc/paired/relic"}

    # total collapse must fail the gate loudly, not drop out of it: a cell
    # whose recorded speedup rounds to 0.000 is a (huge) regression
    em3 = Emitter()
    em3.sections = {"paper": [
        {"name": "paper/bc/paired/relic", "us_per_call": 1e6,
         "derived": "speedup=0.000;oracle=ok"}]}
    compared3, regs3 = compare_against(em3, baseline, tol=0.25,
                                       metric="speedup")
    assert compared3 == 1
    assert [r["name"] for r in regs3] == ["paper/bc/paired/relic"]
    assert regs3[0]["ratio"] > 1000


def test_compare_gate_fails_closed(tmp_path, capsys):
    """A gate that gates nothing must fail: zero shared rows is an error,
    and a missing/invalid baseline dies before any timing would run."""
    from benchmarks.run import Emitter, compare_against, load_baseline

    path = tmp_path / "BENCH_other.json"
    path.write_text(json_mod.dumps({"sections": {"spsc": [
        {"name": "spsc/overhead/relic/single", "us_per_call": 1.0,
         "derived": ""}]}}))
    em = Emitter()
    em.sections = {"paper": [
        {"name": "paper/bc/serial", "us_per_call": 100.0, "derived": ""}]}
    compared, regs = compare_against(em, load_baseline(str(path)), tol=0.25)
    assert compared == 0 and regs == []
    assert "FAILED" in capsys.readouterr().out

    with pytest.raises(FileNotFoundError):
        load_baseline(str(tmp_path / "missing.json"))
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    with pytest.raises(SystemExit):
        load_baseline(str(bad))


# ------------------------------------------------- skewed task costs (PR 6)

_SKEW_CACHE = {}


def skewed(name, n_instances=4, skew=1.0, skew_seed=0):
    key = (name, n_instances, skew, skew_seed)
    if key not in _SKEW_CACHE:
        _SKEW_CACHE[key] = make_workload(
            name, n_instances=n_instances, skew=skew, skew_seed=skew_seed)
    return _SKEW_CACHE[key]


def test_skew_repeats_follow_a_seeded_power_law():
    """The cost profile is Zipf-by-rank (heaviest repeats n, rank r costs
    ~r**-alpha of it, floor 1), its placement is a seeded shuffle, and the
    whole thing is deterministic per (skew, seed)."""
    w = skewed("histogram", n_instances=8, skew=1.0, skew_seed=0)
    assert sorted(w.repeats, reverse=True) == [8, 4, 3, 2, 2, 1, 1, 1]
    again = make_workload("histogram", n_instances=8, skew=1.0, skew_seed=0)
    assert again.repeats == w.repeats                  # deterministic
    other = make_workload("histogram", n_instances=8, skew=1.0, skew_seed=7)
    assert sorted(other.repeats) == sorted(w.repeats)  # same multiset...
    assert other.repeats != w.repeats                  # ...different layout
    heavier = make_workload("histogram", n_instances=8, skew=2.0)
    assert max(heavier.repeats) == 8
    assert sum(heavier.repeats) < sum(w.repeats)       # steeper tail decay
    flat = make_workload("histogram", n_instances=8)
    assert flat.repeats == [1] * 8                     # skew=None: uniform
    assert "skew" in repr(w) and "skew" not in repr(flat)
    with pytest.raises(ValueError, match="positive exponent"):
        make_workload("histogram", n_instances=8, skew=0.0)


@pytest.mark.parametrize("name", ALL)
def test_skewed_serial_passes_oracle(name):
    """Skew changes the cost profile, never the results: every workload's
    skewed run must pass the same oracle as the uniform one."""
    w = skewed(name)
    assert max(w.repeats) == 4 and min(w.repeats) == 1
    w.check(w.serial())


@pytest.mark.parametrize("lanes", [1, 2, 4])
@pytest.mark.parametrize("name", ALL)
def test_skewed_chunked_passes_oracle_at_every_lane_count(name, lanes):
    """The benchmark's skew section shape: chunked execution of a skewed
    workload over a RelicPool, oracle-checked at lanes 1/2/4 (lanes >= 2
    exercises the rebalancing machinery end-to-end under real kernels)."""
    w = skewed(name)
    serial = w.serial()
    with TaskScope(make_scheduler("relic-pool", lanes=lanes)) as scope:
        chunked = w.chunked(scope, grain=1)
    w.check(chunked)
    assert all(results_agree(s, c) for s, c in zip(serial, chunked))
