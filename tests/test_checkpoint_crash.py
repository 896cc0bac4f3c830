"""Crash-consistent checkpointing (PR 10): torn-write tolerance, checksum
verification, fallback restore, and the train.py --resume path end to end.

The failure model: a save can die at any point between "serialize starts"
and "os.replace publishes" (process kill, OOM, disk full). The invariants
under test:

* ``latest_step()``/``restore()`` never trust a manifest that does not
  parse and validate — a torn ``manifest.json`` is skipped with a warning
  (the pre-PR-10 regression: ``latest_step`` accepted any dir where the
  manifest merely *existed*, so a truncated one made ``restore`` raise
  ``JSONDecodeError`` instead of falling back);
* every published entry carries a CRC32 over its stored bytes; restore
  verifies and falls back to the next-latest valid step, quarantining the
  corrupt dir as ``.corrupt`` (kept, never deleted);
* ``_gc`` never collects the last manifest-valid checkpoint, even when
  ``keep`` says it should;
* a chaos kill mid-save (``FsFaultInjector``, every crash point, mid-file
  tears included) always leaves the directory restorable to a complete,
  checksum-valid earlier step — swept by hypothesis;
* ``train.py --resume`` recovers from a mid-save kill: resumes from the
  last *published* step with bit-identical state (the flag had zero test
  coverage before this PR).
"""

import json
import tempfile
import warnings
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointCorruptError, CheckpointManager
from repro.checkpoint.manager import FORMAT_VERSION, MANIFEST
from repro.runtime.chaos import FsCrash, FsFaultInjector
from repro.runtime.config import resolve_checkpoint_config


def _state(scale=1):
    return {
        "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) * scale,
        "b": jnp.full((5,), 0.5 * scale, dtype=jnp.bfloat16),
        "n": jnp.asarray(scale, dtype=jnp.int32),
    }


def _assert_state_equal(a, b):
    fa, _ = jax.tree_util.tree_flatten(a)
    fb, _ = jax.tree_util.tree_flatten(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        xa, ya = np.asarray(x), np.asarray(y)
        assert xa.dtype == ya.dtype
        assert xa.tobytes() == ya.tobytes()      # bit-identical


def _quiet_mgr(d, **kw):
    kw.setdefault("async_", False)
    return CheckpointManager(d, **kw)


# -------------------------------------------------- satellite: torn manifest


def test_latest_step_skips_torn_manifest(tmp_path):
    """Regression: a truncated manifest.json must make latest_step() skip
    that dir (with a warning), not nominate it for restore() to crash on.
    (Pre-fix this asserted the buggy behaviour: latest_step() == 2 and
    restore() raising JSONDecodeError.)"""
    mgr = _quiet_mgr(tmp_path, keep=0)
    mgr.save(_state(1), 1)
    mgr.save(_state(2), 2)
    man = tmp_path / "step_00000002" / MANIFEST
    man.write_text(man.read_text()[:25])        # torn mid-write
    fresh = _quiet_mgr(tmp_path)
    with pytest.warns(RuntimeWarning, match="skipping"):
        assert fresh.latest_step() == 1
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        restored, step = fresh.restore(_state(0))
    assert step == 1
    _assert_state_equal(restored, _state(1))


def test_latest_step_skips_unknown_future_format(tmp_path):
    mgr = _quiet_mgr(tmp_path)
    mgr.save(_state(1), 1)
    mgr.save(_state(2), 2)
    man = tmp_path / "step_00000002" / MANIFEST
    doc = json.loads(man.read_text())
    doc["format_version"] = FORMAT_VERSION + 97
    man.write_text(json.dumps(doc))
    with pytest.warns(RuntimeWarning, match="format_version"):
        assert _quiet_mgr(tmp_path).latest_step() == 1


def test_manifest_carries_format_version_and_crc(tmp_path):
    _quiet_mgr(tmp_path).save(_state(3), 7)
    doc = json.loads((tmp_path / "step_00000007" / MANIFEST).read_text())
    assert doc["format_version"] == FORMAT_VERSION
    assert doc["checksum"] is True
    for ent in doc["entries"].values():
        assert isinstance(ent["crc32"], int)
        assert ent["nbytes"] > 0


# ------------------------------------------------------- checksum + fallback


def test_bitflip_fails_explicit_restore_then_falls_back(tmp_path):
    mgr = _quiet_mgr(tmp_path, keep=0)
    mgr.save(_state(1), 1)
    mgr.save(_state(2), 2)
    ef = tmp_path / "step_00000002" / "w.npy"
    raw = bytearray(ef.read_bytes())
    raw[-3] ^= 0xFF                              # flip payload bits
    ef.write_bytes(bytes(raw))
    fresh = _quiet_mgr(tmp_path)
    # explicit step: the caller asked for exactly this state — raise
    with pytest.raises(CheckpointCorruptError, match="checksum mismatch"):
        fresh.restore(_state(0), step=2)
    # latest-wins: quarantine + fall back
    with pytest.warns(RuntimeWarning, match="quarantined"):
        restored, step = fresh.restore(_state(0))
    assert step == 1
    _assert_state_equal(restored, _state(1))
    corrupt = list(tmp_path.glob("step_00000002.corrupt*"))
    assert len(corrupt) == 1                     # kept for post-mortem
    assert not (tmp_path / "step_00000002").exists()


def test_truncated_entry_file_falls_back(tmp_path):
    mgr = _quiet_mgr(tmp_path, keep=0)
    mgr.save(_state(1), 1)
    mgr.save(_state(2), 2)
    ef = tmp_path / "step_00000002" / "b.npy"
    ef.write_bytes(ef.read_bytes()[:10])         # mid-file kill
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        restored, step = _quiet_mgr(tmp_path).restore(_state(0))
    assert step == 1
    _assert_state_equal(restored, _state(1))


def test_all_corrupt_raises_filenotfound(tmp_path):
    mgr = _quiet_mgr(tmp_path)
    mgr.save(_state(1), 1)
    ef = tmp_path / "step_00000001" / "w.npy"
    raw = bytearray(ef.read_bytes())
    raw[-1] ^= 0x01
    ef.write_bytes(bytes(raw))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(FileNotFoundError, match="quarantined"):
            _quiet_mgr(tmp_path).restore(_state(0))


def test_checksum_off_writes_v1_compatible_entries(tmp_path):
    mgr = _quiet_mgr(tmp_path, checksum=False)
    mgr.save(_state(4), 3)
    doc = json.loads((tmp_path / "step_00000003" / MANIFEST).read_text())
    assert all("crc32" not in e for e in doc["entries"].values())
    # a checksum-on manager still restores it (entries just unverified)
    restored, step = _quiet_mgr(tmp_path).restore(_state(0))
    assert step == 3
    _assert_state_equal(restored, _state(4))


def test_checkpoint_config_env(monkeypatch):
    monkeypatch.delenv("RELIC_CKPT_CHECKSUM", raising=False)
    assert resolve_checkpoint_config().checksum is True
    monkeypatch.setenv("RELIC_CKPT_CHECKSUM", "0")
    assert resolve_checkpoint_config().checksum is False
    assert resolve_checkpoint_config(checksum=True).checksum is True
    monkeypatch.setenv("RELIC_CKPT_CHECKSUM", "maybe")
    with pytest.raises(ValueError):
        resolve_checkpoint_config()


def test_restore_is_bit_identical(tmp_path):
    st8 = _state(13)
    _quiet_mgr(tmp_path).save(st8, 11)
    restored, step = _quiet_mgr(tmp_path).restore(_state(0))
    assert step == 11
    _assert_state_equal(restored, st8)


# ------------------------------------------------------------ gc protection


def test_gc_never_collects_last_valid_checkpoint(tmp_path):
    """keep=1 with the newest checkpoint torn: retention must spare the
    newest *valid* dir below the keep window instead of deleting it."""
    mgr = _quiet_mgr(tmp_path, keep=1)
    mgr.save(_state(1), 1)
    torn = tmp_path / "step_00000002"
    torn.mkdir()
    (torn / MANIFEST).write_text('{"step": 2, "ent')    # torn
    mgr._gc()
    assert (tmp_path / "step_00000001").exists()        # spared
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert _quiet_mgr(tmp_path).latest_step() == 1


def test_gc_ignores_quarantined_dirs(tmp_path):
    mgr = _quiet_mgr(tmp_path, keep=2)
    for s in (1, 2, 3):
        mgr.save(_state(s), s)
    (tmp_path / "step_00000009.corrupt").mkdir()
    mgr._gc()
    assert (tmp_path / "step_00000009.corrupt").exists()
    assert not (tmp_path / "step_00000001").exists()    # normal retention
    assert (tmp_path / "step_00000003").exists()


# --------------------------------------------------- chaos crash-point sweep


def test_fs_fault_injector_validates():
    with pytest.raises(ValueError):
        FsFaultInjector(crash_point="nonsense")
    with pytest.raises(ValueError):
        FsFaultInjector(at_save=-1)
    with pytest.raises(ValueError):
        FsFaultInjector(torn_bytes=-2)


@given(
    point=st.sampled_from(FsFaultInjector.POINTS),
    at_save=st.integers(0, 2),
    at_index=st.integers(0, 2),
    torn=st.sampled_from([None, 0, 7, 40]),
)
@settings(deadline=None, max_examples=20)
def test_crash_point_sweep_always_restores_valid_step(point, at_save,
                                                      at_index, torn):
    """Hypothesis sweep of the satellite: kill a save at every
    serialize/publish boundary (and mid-file) across a sequence of saves;
    restore must always return a complete, checksum-valid earlier step —
    never a torn one."""
    with tempfile.TemporaryDirectory() as td:
        mgr = _quiet_mgr(td, keep=0)
        FsFaultInjector(crash_point=point, at_save=at_save,
                        at_index=at_index, torn_bytes=torn).arm(mgr)
        published = 0
        try:
            for step in (1, 2, 3, 4):
                mgr.save(_state(step), step)
                published = step
        except FsCrash:
            pass
        assert published == at_save      # saves before the crash landed
        fresh = _quiet_mgr(td)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            if published == 0:
                assert fresh.latest_step() is None
                with pytest.raises(FileNotFoundError):
                    fresh.restore(_state(0))
            else:
                assert fresh.latest_step() == published
                restored, got = fresh.restore(_state(0))
                assert got == published
                _assert_state_equal(restored, _state(published))


# ----------------------------------------------- satellite: train.py resume


@pytest.mark.filterwarnings("ignore::RuntimeWarning")
def test_train_resume_after_mid_save_kill(tmp_path, capsys):
    """End to end on relic_tiny: train with periodic checkpoints, chaos-kill
    the run mid-save, then --resume — the rerun must pick up from the last
    *published* step with state bit-identical to that checkpoint."""
    from repro.launch.train import main as train_main

    ckpt = str(tmp_path / "ckpt")
    common = ["--arch", "relic_tiny", "--smoke", "--batch", "4",
              "--seq", "32", "--log-every", "50",
              "--ckpt", ckpt, "--ckpt-every", "5"]
    # Run 1: crash the second save (step 10) mid-manifest.
    with pytest.raises(FsCrash):
        train_main(common + ["--steps", "20", "--ckpt-chaos", "manifest:1"])
    dirs = sorted(p.name for p in Path(ckpt).glob("step_*"))
    assert "step_00000005" in dirs               # published before the kill
    assert "step_00000010" not in dirs           # the torn save never lands
    # The surviving checkpoint is the resume source, bit-for-bit: what a
    # fresh manager restores equals the published files exactly.
    mgr = CheckpointManager(ckpt, async_=False)
    assert mgr.latest_step() == 5
    doc = json.loads((Path(ckpt) / "step_00000005" / MANIFEST).read_text())
    for key, ent in doc["entries"].items():
        arr = np.load(Path(ckpt) / "step_00000005" / ent["file"])
        import zlib
        assert zlib.crc32(np.ascontiguousarray(arr).tobytes()) == ent["crc32"]
    # Run 2: resume. Step counter restarts from the published step and the
    # run completes to a finite loss.
    loss = train_main(common + ["--steps", "20", "--resume"])
    out = capsys.readouterr().out
    assert "resumed from step 5" in out
    assert np.isfinite(loss)


def test_train_resume_with_no_steps_left(tmp_path, capsys):
    """--resume when the last save is already at --steps takes no step and
    reports no loss (NaN), instead of failing on an unbound metric."""
    from repro.launch.train import main as train_main

    common = ["--arch", "relic_tiny", "--smoke", "--batch", "2",
              "--seq", "16", "--steps", "2", "--ckpt",
              str(tmp_path / "ckpt"), "--ckpt-every", "2"]
    assert np.isfinite(train_main(common))
    assert np.isnan(train_main(common + ["--resume"]))
    assert "resumed from step 2" in capsys.readouterr().out
