"""Every entry of ``BENCHMARK.json`` loads by name from its own files, and
the file keeps to the benchmark's format."""

import json
import re

import pytest

from bench import run

SPEC = run.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_loads_by_name(workload):
    cell = run.load_cell(SPEC, workload)
    driver = run.driver_for(cell)
    assert callable(driver.run)
    assert cell["chips"] in (1, 4)
    # every limit the driver compares is stated in the configuration
    assert cell["config_file"]["checks"]
    assert cell["config_file"]["name"] == cell["config"]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_layer(workload):
    e2e = [m["name"] for m in run.metrics_of(SPEC, workload, "end_to_end")]
    layer = run.metrics_of(SPEC, workload, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2
    assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_layer_metric_reader_loads_and_reads_nothing_from_nothing(metric):
    assert run.metric_reader(metric)({}, None) is None


def test_names_units_and_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in SPEC["configs"]] + CELLS
             + [m["name"] for m in METRICS])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_configs_are_used_and_name_their_cuts():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/configs/")
        body = json.loads((run.ROOT / c["file"]).read_text())
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in
                       c["reduced"])


def test_peaks_table_names_its_source():
    peaks = json.loads((run.BENCH / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all(v["source"] for v in peaks.values())


def test_window_length_fits_a_full_check_of_24_cells():
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
