"""Graceful preemption: the SIGTERM/SIGINT → checkpoint → exit-75 path.

The fast tests drive :meth:`Trainer.request_preemption` directly (the
signal handler's only action) so tier-1 covers the checkpoint-and-stop
contract without process games; the slow test delivers a real SIGTERM to a
live ``train.py`` subprocess and asserts the full contract — exit 75,
trainer meta, replay snapshot — i.e. what a TPU-VM preemption notice sees.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import pytest
from conftest import clean_cpu_env

from d4pg_tpu.runtime.trainer import Trainer
from train import build_parser, config_from_args, install_preemption_handlers


def _tiny_args(tmp, extra=()):
    return build_parser().parse_args(
        [
            "--env", "pendulum",
            "--total-steps", "6",
            "--warmup", "130",
            "--eval-interval", "6",
            "--checkpoint-interval", "6",
            "--num-envs", "2",
            "--bsize", "16",
            "--log-dir", str(tmp),
            *extra,
        ]
    )


def test_preempt_before_train_checkpoints_and_stops(tmp_path):
    t = Trainer(config_from_args(_tiny_args(tmp_path / "a")))
    t.request_preemption()
    out = t.train()
    t.close()
    assert t.preempted
    assert out == {}  # no grad steps ran, no eval row
    # the preemption checkpoint landed: meta + an Orbax step
    assert os.path.exists(tmp_path / "a" / "checkpoints" / "trainer_meta.json")
    assert t.ckpt.latest_step() is not None


def test_preempt_mid_train_saves_and_resumes(tmp_path):
    cfg = config_from_args(
        _tiny_args(tmp_path / "b", ("--total-steps", "100000"))
    )
    t = Trainer(cfg)
    # arm the preemption shortly after the loop starts making progress
    def arm():
        while t.grad_steps < 2:
            time.sleep(0.01)
        t.request_preemption()

    th = threading.Thread(target=arm, daemon=True)
    th.start()
    t.train()
    th.join(timeout=30)
    saved_step = t.ckpt.latest_step()
    t.close()
    assert t.preempted
    assert saved_step is not None and saved_step >= 2
    # a --resume leg picks up from the preemption checkpoint
    t2 = Trainer(
        config_from_args(
            _tiny_args(
                tmp_path / "b",
                ("--total-steps", str(saved_step + 2), "--resume"),
            )
        )
    )
    assert t2.grad_steps == saved_step
    t2.close()


def test_install_preemption_handlers_wiring():
    """The installed handler calls the stop callback on the FIRST signal
    and restores the default disposition so a second one hard-kills."""
    fired = []
    old_term = signal.getsignal(signal.SIGTERM)
    old_int = signal.getsignal(signal.SIGINT)
    try:
        install_preemption_handlers(lambda: fired.append(True))
        signal.raise_signal(signal.SIGTERM)
        assert fired == [True]
        assert signal.getsignal(signal.SIGTERM) is signal.SIG_DFL
        # SIGINT handler is independent and still armed
        assert signal.getsignal(signal.SIGINT) is not signal.SIG_DFL
    finally:
        signal.signal(signal.SIGTERM, old_term)
        signal.signal(signal.SIGINT, old_int)


@pytest.mark.slow
def test_sigterm_on_live_training_run_exits_75(tmp_path):
    """Full contract over a real process: SIGTERM mid-run → checkpoint +
    replay snapshot + exit code 75 (EX_TEMPFAIL, the --resume handshake)."""
    env = clean_cpu_env()
    run = str(tmp_path / "run")
    proc = subprocess.Popen(
        [
            sys.executable, "train.py",
            "--env", "Pendulum-v1", "--hidden-sizes", "16,16",
            "--total-steps", "100000", "--warmup", "16",
            "--bsize", "8", "--rmsize", "512",
            "--eval-interval", "100000", "--checkpoint-interval", "100000",
            "--num-envs", "1", "--snapshot-replay", "--log-dir", run,
        ],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    lines = []

    def pump():
        for line in proc.stdout:
            lines.append(line)

    th = threading.Thread(target=pump, daemon=True)
    th.start()
    deadline = time.time() + 300
    while time.time() < deadline and not any("config:" in l for l in lines):
        time.sleep(0.5)
    time.sleep(20)  # past warmup, into grad steps
    proc.send_signal(signal.SIGTERM)
    rc = proc.wait(timeout=240)
    th.join(timeout=10)
    out = "".join(lines)
    assert rc == 75, out[-3000:]
    assert "[preempt]" in out
    assert os.path.exists(os.path.join(run, "checkpoints", "trainer_meta.json"))
    assert os.path.exists(os.path.join(run, "checkpoints", "replay.npz"))
