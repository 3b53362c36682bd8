"""Crash-consistent checkpointing: the manifest commit record, verify-on-
restore fallback, torn side files — and the full-contract subprocess
regression (kill -9 at a randomized instant mid-run, then --resume).

Fast tests drive :class:`CheckpointManager` directly with a tiny
TrainState; the slow test SIGKILLs a live ``train.py`` and proves the
resume handshake end to end (rc 0, monotone step counter, fallback to the
newest intact step).
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest
from conftest import clean_cpu_env

from d4pg_tpu.agent import create_train_state
from d4pg_tpu.agent.state import D4PGConfig
from d4pg_tpu.chaos import truncate_checkpoint_step
from d4pg_tpu.runtime.checkpoint import (
    CheckpointManager,
    load_trainer_meta,
    save_trainer_meta,
    trainer_meta_path,
)

CFG = D4PGConfig(obs_dim=3, action_dim=1, hidden_sizes=(8, 8))


def _state(step=0):
    st = create_train_state(CFG, jax.random.PRNGKey(0))
    return st.replace(step=st.step + step) if step else st


def _mgr(tmp_path, **kw):
    return CheckpointManager(str(tmp_path / "checkpoints"), **kw)


def _save_attested(mgr, step, state):
    mgr.save(step, state)
    mgr.wait()
    mgr.write_manifest(step)


class TestManifest:
    def test_write_and_verify_roundtrip(self, tmp_path):
        mgr = _mgr(tmp_path)
        _save_attested(mgr, 1, _state())
        ok, why, warnings = mgr.verify_step(1)
        assert ok, why
        assert warnings == []
        m = mgr.load_manifest(1)
        assert m["step"] == 1 and m["files"]  # digests every orbax file
        mgr.close()

    def test_truncation_detected_and_fallback(self, tmp_path):
        mgr = _mgr(tmp_path)
        _save_attested(mgr, 1, _state(1))
        _save_attested(mgr, 2, _state(2))
        truncate_checkpoint_step(mgr.step_dir(2))
        ok, why, _ = mgr.verify_step(2)
        assert not ok and ("truncated" in why or "digest" in why)
        restored, step, fallbacks = mgr.restore_verified(_state())
        assert step == 1 and len(fallbacks) == 1
        assert int(jax.device_get(restored.step)) == int(
            jax.device_get(_state(1).step)
        )
        # the corrupt newer step was PRUNED: a resumed run re-saving at
        # step 2 must not collide with the dead branch
        assert mgr.all_steps() == [1]
        assert not os.path.exists(mgr.manifest_path(2))
        _save_attested(mgr, 2, _state(2))
        _, step2, fb2 = mgr.restore_verified(_state())
        assert step2 == 2 and fb2 == []
        mgr.close()

    def test_uncommitted_step_skipped(self, tmp_path):
        """kill -9 between the Orbax save and the manifest write leaves the
        newest step unattested: restore must use the previous intact one."""
        mgr = _mgr(tmp_path)
        _save_attested(mgr, 1, _state(1))
        mgr.save(2, _state(2))
        mgr.wait()  # step 2 fully on disk, but NO manifest = never committed
        _, step, fallbacks = mgr.restore_verified(_state())
        assert step == 1
        assert fallbacks and "no manifest" in fallbacks[0]
        assert mgr.all_steps() == [1]  # the uncommitted branch was pruned
        mgr.close()

    def test_legacy_run_without_manifests_still_restores(self, tmp_path):
        mgr = _mgr(tmp_path)
        mgr.save(1, _state(1))
        mgr.save(2, _state(2))
        mgr.wait()
        _, step, fallbacks = mgr.restore_verified(_state())
        assert step == 2 and fallbacks == []  # pre-manifest runs: best effort
        mgr.close()

    def test_delete_removes_manifest_with_bytes(self, tmp_path):
        mgr = _mgr(tmp_path)
        _save_attested(mgr, 1, _state(1))
        assert os.path.exists(mgr.manifest_path(1))
        mgr.delete(1)
        assert not os.path.exists(mgr.manifest_path(1))
        mgr.close()

    def test_manifest_pruned_with_max_to_keep(self, tmp_path):
        mgr = _mgr(tmp_path, max_to_keep=2)
        for s in (1, 2, 3):
            _save_attested(mgr, s, _state(s))
        live = set(mgr.all_steps())
        assert 1 not in live
        assert not os.path.exists(mgr.manifest_path(1))
        assert os.path.exists(mgr.manifest_path(3))
        mgr.close()

    def test_stale_log_dir_save_is_loud_not_silent(self, tmp_path):
        """Orbax silently skips saves at steps older than the newest on
        disk — the exact signature of reusing another run's log dir
        without --resume. That used to train forever while never
        checkpointing; it must raise with the remedy instead. A re-save
        at the CURRENT latest step (preemption right after a periodic
        save) stays legitimately quiet."""
        mgr = _mgr(tmp_path)
        _save_attested(mgr, 2000, _state(2000))
        with pytest.raises(RuntimeError, match="--resume, or use a fresh"):
            mgr.save(4, _state(4))
        mgr.save(2000, _state(2000))  # same-step re-save: no error
        mgr.wait()
        mgr.close()

    def test_side_file_drift_warns_but_restores(self, tmp_path):
        """Crash between a NEWER save's meta write and its manifest: the
        chosen older step sees a drifted side file — warn, don't fail."""
        mgr = _mgr(tmp_path)
        log_dir = str(tmp_path)
        save_trainer_meta(log_dir, 100, 1.0)
        mgr.save(1, _state(1))
        mgr.wait()
        mgr.write_manifest(1, side_files=[trainer_meta_path(log_dir)])
        save_trainer_meta(log_dir, 999, 2.0)  # the "newer crashed save"
        ok, _, warnings = mgr.verify_step(1)
        assert ok and warnings and "differs" in warnings[0]
        _, step, fallbacks = mgr.restore_verified(_state())
        assert step == 1 and fallbacks == []
        mgr.close()


class TestTornMeta:
    def test_missing_meta_is_empty(self, tmp_path):
        assert load_trainer_meta(str(tmp_path)) == {}

    def test_torn_meta_degrades_to_empty_with_warning(self, tmp_path, capsys):
        """Satellite bugfix: a torn/corrupt trainer_meta.json used to raise
        JSONDecodeError and kill the resume — it must degrade to {}."""
        path = trainer_meta_path(str(tmp_path))
        os.makedirs(os.path.dirname(path))
        path_obj = open(path, "w")
        path_obj.write('{"env_steps": 123, "ewma_re')  # torn mid-write
        path_obj.close()
        assert load_trainer_meta(str(tmp_path)) == {}
        assert "unreadable/corrupt" in capsys.readouterr().out

    def test_intact_meta_roundtrips(self, tmp_path):
        os.makedirs(tmp_path / "checkpoints")
        save_trainer_meta(str(tmp_path), 7, 1.5, extra={"x": 1})
        assert load_trainer_meta(str(tmp_path)) == {
            "env_steps": 7, "ewma_return": 1.5, "x": 1,
        }


def test_corrupt_replay_snapshot_raises_caught_types(tmp_path):
    """The trainer's resume wraps buffer.restore in (OSError, ValueError,
    KeyError, BadZipFile) — a truncated npz must raise within that set so
    resume degrades instead of dying."""
    import zipfile

    from d4pg_tpu.replay import ReplayBuffer

    snap = tmp_path / "replay.npz"
    buf = ReplayBuffer(64, 3, 1)
    buf.add(np.zeros(3), np.zeros(1), 0.0, np.zeros(3), 1.0)
    buf.snapshot(str(snap))
    raw = snap.read_bytes()
    snap.write_bytes(raw[: len(raw) // 2])  # torn mid-write
    with pytest.raises(
        (OSError, ValueError, KeyError, zipfile.BadZipFile)
    ):
        ReplayBuffer(64, 3, 1).restore(str(snap))


# ---------------------------------------------------------- the full contract
@pytest.mark.slow
def test_kill9_mid_checkpointing_run_then_resume_restores_intact_step(tmp_path):
    """ISSUE-5 acceptance: kill -9 a checkpointing train.py at a randomized
    instant, then --resume — it must come back with rc 0, restore the
    newest INTACT step (falling back past any partial save), and keep the
    step counter monotone."""
    env = clean_cpu_env()
    run = str(tmp_path / "run")
    ckpt_dir = os.path.join(run, "checkpoints")
    args = [
        sys.executable, "train.py",
        "--env", "Pendulum-v1", "--hidden-sizes", "16,16",
        "--total-steps", "100000", "--warmup", "16",
        "--bsize", "8", "--rmsize", "512",
        "--eval-interval", "100000", "--checkpoint-interval", "8",
        "--num-envs", "1", "--snapshot-replay", "--log-dir", run,
    ]
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        args, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=cwd,
    )
    lines = []
    th = threading.Thread(
        target=lambda: lines.extend(proc.stdout), daemon=True
    )
    th.start()

    def manifests():
        try:
            return sorted(
                int(f[len("manifest_"):-len(".json")])
                for f in os.listdir(ckpt_dir)
                if f.startswith("manifest_") and f.endswith(".json")
            )
        except (OSError, ValueError):
            return []

    # Wait until at least one checkpoint COMMITTED, then kill at a seeded-
    # random instant within the next checkpoint interval — the kill lands
    # mid-save, mid-snapshot, or between, and resume must survive all of
    # them.
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline and not manifests():
        if proc.poll() is not None:
            pytest.fail("train.py died early:\n" + "".join(lines)[-3000:])
        time.sleep(0.2)
    committed = manifests()
    assert committed, "no checkpoint committed within 300 s"
    rng = np.random.default_rng(0xD4)
    time.sleep(float(rng.uniform(0.0, 2.0)))
    proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=60)
    th.join(timeout=10)
    attested_after_kill = manifests()
    assert attested_after_kill, "kill erased every manifest?"

    resume_to = max(attested_after_kill) + 8
    out = subprocess.run(
        args[:6] + [
            "--total-steps", str(resume_to), "--warmup", "16",
            "--bsize", "8", "--rmsize", "512",
            "--eval-interval", "100000", "--checkpoint-interval", "8",
            "--num-envs", "1", "--snapshot-replay", "--log-dir", run,
            "--resume",
        ],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=600,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    assert "[checkpoint] resumed from step" in out.stdout
    restored = int(
        out.stdout.split("[checkpoint] resumed from step", 1)[1].split()[0]
    )
    # the restored step is one the manifest set attests (newest intact —
    # a crash-torn newer step is skipped, logged as a fallback)
    assert restored in attested_after_kill
    assert restored == max(
        s for s in attested_after_kill if s <= restored
    )
    # monotone: the resumed leg ran past the restored step and
    # re-checkpointed at a strictly later one
    final = manifests()
    assert final and max(final) >= restored
