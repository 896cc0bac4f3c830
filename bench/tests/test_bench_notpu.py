"""Where JAX finds no TPU, a run exits non-zero and prints no result; so
does a directory that holds only the benchmark's own files."""

import os
import shutil
import subprocess
import sys

import pytest

from bench import run


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=240)


ARGS = ("--workload", "gap-kron5-relic", "--seed", str(2**33 + 3),
        "--seconds", "1", "--trace", "0")


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(run.ROOT, *ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, *ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_in_process_check_refuses_the_cpu():
    with pytest.raises(run.NoChip, match="needs a TPU"):
        run.check_devices(1)


def test_runtime_env_is_set_unless_given(monkeypatch):
    for name in run.RUNTIME_ENV:
        monkeypatch.delenv(name, raising=False)
    run.set_runtime_env()
    assert all(os.environ[k] == v for k, v in run.RUNTIME_ENV.items())
    name = next(iter(run.RUNTIME_ENV))
    monkeypatch.setenv(name, "123")
    run.set_runtime_env()
    assert os.environ[name] == "123"
