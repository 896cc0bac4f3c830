"""chip_smoke.py's phases at SMOKE configs on the CPU, so the script does not
rot between chip runs. The TPU check lives in ``main()`` only; the phases
themselves run on whatever backend JAX has."""

import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

import chip_smoke
from repro.configs import get_config

ROOT = Path(chip_smoke.__file__).resolve().parent

# A JAX deprecation on the main path fails these tests.
pytestmark = pytest.mark.filterwarnings("error::DeprecationWarning")


def _serve_smoke():
    chip_smoke.serve_phase(get_config("phi3_mini_3p8b", smoke=True),
                           batch=2, prompt_len=8, gen=4, cache_len=16,
                           n_requests=3)


def test_serve_phase_smoke(capsys):
    _serve_smoke()
    out = capsys.readouterr().out
    assert '"phase": "serve", "ok": true' in out
    assert '"requests": 3' in out


def test_serve_phase_check_can_fail(monkeypatch):
    # A bound no result can meet: the phase must raise, not just print.
    monkeypatch.setattr(chip_smoke, "LOGIT_ATOL", -1.0)
    with pytest.raises(AssertionError, match="phase serve failed"):
        _serve_smoke()


def test_train_phase_smoke(capsys):
    chip_smoke.train_phase("relic_tiny", smoke=True, batch=4, seq=32,
                           steps=4, resume_to=6, ckpt_every=2)
    captured = capsys.readouterr()
    assert '"phase": "train", "ok": true' in captured.out
    assert "resumed from step 4" in captured.err


def test_hosttask_phase(capsys):
    chip_smoke.hosttask_phase()
    assert '"phase": "hosttask", "ok": true' in capsys.readouterr().out


def test_four_chip_phases_on_virtual_devices():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import chip_smoke
        from repro.configs import get_config
        chip_smoke.sharded_train_phase(get_config("relic_tiny", smoke=True),
                                       batch=8, seq=32)
        chip_smoke.ring_matmul_phase(s=256, d=128, f=512)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-W", "error::DeprecationWarning",
                        "-c", code], env=env, cwd=ROOT, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert '"phase": "sharded", "ok": true' in r.stdout
    assert '"phase": "ring", "ok": true' in r.stdout
    assert '"devices": 4' in r.stdout


def _run_script(script: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=300)


def test_main_refuses_cpu():
    r = _run_script(ROOT / "chip_smoke.py", ROOT)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = _run_script(tmp_path / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
