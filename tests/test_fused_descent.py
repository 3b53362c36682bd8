"""ISSUE 16: the fused descent-in-scan kernel tier, the double-buffered
ring ingest, and the large-batch (``--batch-scale``) recipe.

The fused tier's contract is BYTE parity, not tolerance: the one-program
scan body (ops/pallas_fused_step.py) computes its loss tile and descent
tile with the literal ``loss_tile``/``descend_tile`` functions the
separate-programs oracle runs, on identical inputs, with the identical
backward program — so fused-vs-oracle equality is structural and these
tests pin it end to end (kernel outputs, gradients, whole TrainState +
priority tree across multi-dispatch megastep runs, bf16 and ensemble
included). The ingest double buffer's contract is that staging is
INVISIBLE: stage()+flush() must be byte-identical to a plain flush(),
including under ring-wrap overwrites between stage and flush.

Fast tests keep the small-capacity shapes of tests/test_megastep.py;
the large-batch 400-step guard acceptance and the scaled-recipe solve
ride the slow tier.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.agent import D4PGConfig, create_train_state
from d4pg_tpu.config import TrainConfig, apply_batch_scale, apply_env_preset
from d4pg_tpu.models.critic import DistConfig
from d4pg_tpu.ops.categorical import make_support
from d4pg_tpu.ops.pallas_fused_step import fused_categorical_loss_descent
from d4pg_tpu.ops.pallas_projection import fused_categorical_loss
from d4pg_tpu.ops.pallas_tree import find_prefix_pallas
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.device_ring import DeviceRingSync, device_ring_init
from d4pg_tpu.replay.source import RequestedCaps, composition_matrix, negotiate
from d4pg_tpu.replay.uniform import ReplayBuffer, Transition
from d4pg_tpu.runtime.megastep import (
    make_megastep_device_per,
    make_megastep_device_per_fused,
)


def _leaves_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(jax.device_get(a))
    lb = jax.tree_util.tree_leaves(jax.device_get(b))
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


# ------------------------------------------------------ kernel-level parity
def _kernel_inputs(B=40, A=11, L=300, seed=0):
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(size=(B, A)).astype(np.float32))
    p = jax.nn.softmax(jnp.asarray(r.normal(size=(B, A)).astype(np.float32)))
    rew = jnp.asarray(r.uniform(-1, 0, B).astype(np.float32))
    disc = jnp.asarray(r.uniform(0, 0.99, B).astype(np.float32))
    lane = dper.tree_from_priorities(
        r.uniform(0.1, 2.0, L).astype(np.float32), L
    ).sums[0]
    pre = jnp.asarray(
        r.uniform(0, float(lane[1]) * 0.999, B).astype(np.float32)
    )
    return q, p, rew, disc, pre, lane


class TestFusedStepKernel:
    SUP = make_support(-5.0, 5.0, 11)

    def test_byte_identical_to_separate_programs(self):
        """ce/overlap match fused_categorical_loss and the descent matches
        find_prefix_pallas — all to the BYTE (the fused kernel runs the
        same tile functions on the same operands)."""
        q, p, rew, disc, pre, lane = _kernel_inputs()
        ce_f, ov_f, idx_f = fused_categorical_loss_descent(
            self.SUP, q, p, rew, disc, pre, lane, interpret=True
        )
        ce_s, ov_s = fused_categorical_loss(
            self.SUP, q, p, rew, disc, interpret=True
        )
        idx_s = find_prefix_pallas(lane, pre, interpret=True)
        assert np.asarray(ce_f).tobytes() == np.asarray(ce_s).tobytes()
        assert np.asarray(ov_f).tobytes() == np.asarray(ov_s).tobytes()
        np.testing.assert_array_equal(np.asarray(idx_f), np.asarray(idx_s))
        np.testing.assert_array_equal(
            np.asarray(idx_f), np.asarray(dper.descend_prefix(lane, pre))
        )
        assert np.asarray(idx_f).dtype == np.int32

    def test_gradients_byte_identical(self):
        """Both tiers share _fused_loss_grad_kernel, so an IS-weighted
        loss gradient through either is the same bytes."""
        q, p, rew, disc, pre, lane = _kernel_inputs(seed=1)
        w = jnp.asarray(
            np.random.default_rng(2).uniform(0.2, 1.0, q.shape[0])
            .astype(np.float32)
        )

        def loss_fused(qq):
            ce, ov, _idx = fused_categorical_loss_descent(
                self.SUP, qq, p, rew, disc, pre, lane, interpret=True
            )
            return jnp.sum(ce * w) + 0.5 * jnp.sum(ov * w)

        def loss_sep(qq):
            ce, ov = fused_categorical_loss(
                self.SUP, qq, p, rew, disc, interpret=True
            )
            return jnp.sum(ce * w) + 0.5 * jnp.sum(ov * w)

        gf = np.asarray(jax.grad(loss_fused)(q))
        gs = np.asarray(jax.grad(loss_sep)(q))
        assert gf.tobytes() == gs.tobytes()

    def test_train_step_descent_requires_pallas_fused(self):
        """The descent kwarg is the fused tier's seam: any other
        projection backend must refuse loudly, not silently diverge."""
        from d4pg_tpu.agent.d4pg import train_step

        cfg = D4PGConfig(projection_backend="xla")
        with pytest.raises(ValueError, match="pallas_fused"):
            train_step(cfg, None, None, descent=(None, None))


# --------------------------------------------------- megastep-level parity
_C, _K, _B = 64, 3, 8


def _agent_cfg(**kw) -> D4PGConfig:
    return D4PGConfig(
        obs_dim=3, action_dim=1, hidden_sizes=(16, 16),
        dist=DistConfig(num_atoms=11, v_min=-5.0, v_max=5.0),
        projection_backend="pallas_fused", **kw,
    )


def _fill_buf(n=48, seed=5, cap=_C) -> ReplayBuffer:
    buf = ReplayBuffer(cap, 3, 1)
    if n == 0:
        return buf
    r = np.random.default_rng(seed)
    buf.add_batch(Transition(
        r.normal(size=(n, 3)).astype(np.float32),
        r.uniform(-1, 1, (n, 1)).astype(np.float32),
        r.uniform(-1, 0, n).astype(np.float32),
        r.normal(size=(n, 3)).astype(np.float32),
        np.full(n, 0.99, np.float32),
    ))
    return buf


def _per_setup(cfg):
    ring = device_ring_init(_C, 3, 1)
    sync = DeviceRingSync(_fill_buf(), chunk_cap=16)
    dps = dper.DevicePerSync(_C, cfg.per_alpha)
    sync.tree_hook = dps.on_chunk
    return sync.flush(ring), dps


def _run_pair(cfg, dispatches):
    """Run the separate-programs oracle and the fused tier lockstep from
    identical seeds; return their final (state, tree, key, metrics)."""
    ring_o, dps_o = _per_setup(cfg)
    ring_f, dps_f = _per_setup(cfg)
    oracle = make_megastep_device_per(cfg, _K, _B, tree_backend="pallas")
    fused = make_megastep_device_per_fused(cfg, _K, _B)
    s_o = create_train_state(cfg, jax.random.PRNGKey(1))
    s_f = create_train_state(cfg, jax.random.PRNGKey(1))
    k_o, k_f = jax.random.PRNGKey(7), jax.random.PRNGKey(7)
    t_o, t_f = dps_o.tree, dps_f.tree
    for _ in range(dispatches):
        s_o, t_o, k_o, m_o = oracle(s_o, ring_o, t_o, k_o)
        s_f, t_f, k_f, m_f = fused(s_f, ring_f, t_f, k_f)
    return (s_o, t_o, k_o, m_o), (s_f, t_f, k_f, m_f)


def _assert_pair_byte_equal(o, f):
    s_o, t_o, k_o, m_o = o
    s_f, t_f, k_f, m_f = f
    assert _leaves_equal(s_o, s_f), "TrainState diverged"
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(t_o.sums)),
        np.asarray(jax.device_get(t_f.sums)),
    )
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(t_o.max_priority)),
        np.asarray(jax.device_get(t_f.max_priority)),
    )
    np.testing.assert_array_equal(np.asarray(k_o), np.asarray(k_f))
    for k in m_o:
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(m_o[k])),
            np.asarray(jax.device_get(m_f[k])), err_msg=k,
        )


class TestFusedMegastepParity:
    def test_byte_identical_vs_separate_programs(self):
        """Whole-TrainState + tree + key + metrics byte parity over 3
        donated dispatches: the fused tier IS the oracle, relocated."""
        o, f = _run_pair(_agent_cfg(), dispatches=3)
        _assert_pair_byte_equal(o, f)

    def test_byte_identical_bf16_ensemble(self):
        """The flagship recipe's compute path — bf16 trunks + stacked
        REDQ ensemble — stays byte-identical fused-vs-oracle too (the
        descent pipelining is orthogonal to what the loss computes)."""
        cfg = _agent_cfg(
            compute_dtype="bfloat16", critic_ensemble=2,
            ensemble_min_targets=2,
        )
        o, f = _run_pair(cfg, dispatches=2)
        _assert_pair_byte_equal(o, f)

    def test_bf16_recipe_within_pinned_tolerance_of_f32(self):
        """The recipe's end-to-end bf16 claim vs the f32 reference at
        pinned tolerances: one dispatch (same PRNG → same draws, the tree
        only updates post-scan), losses within 5% + 0.02, f32-master
        params within 1e-3 after K grad steps."""
        ring_a, dps_a = _per_setup(_agent_cfg())
        ring_b, dps_b = _per_setup(_agent_cfg())
        f32 = make_megastep_device_per_fused(_agent_cfg(), _K, _B)
        bf16 = make_megastep_device_per_fused(
            _agent_cfg(compute_dtype="bfloat16"), _K, _B
        )
        s_a = create_train_state(_agent_cfg(), jax.random.PRNGKey(1))
        s_b = create_train_state(
            _agent_cfg(compute_dtype="bfloat16"), jax.random.PRNGKey(1)
        )
        s_a, _, _, m_a = f32(s_a, ring_a, dps_a.tree, jax.random.PRNGKey(7))
        s_b, _, _, m_b = bf16(s_b, ring_b, dps_b.tree, jax.random.PRNGKey(7))
        np.testing.assert_allclose(
            np.asarray(jax.device_get(m_a["critic_loss"])),
            np.asarray(jax.device_get(m_b["critic_loss"])),
            rtol=0.05, atol=0.02,
        )
        for x, y in zip(
            jax.tree_util.tree_leaves(jax.device_get(s_a.critic_params)),
            jax.tree_util.tree_leaves(jax.device_get(s_b.critic_params)),
        ):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), atol=1e-3
            )


# ------------------------------------------------ double-buffered ingest
class TestIngestStaging:
    def _pair(self, cap=_C, chunk_cap=16):
        """Two identical (buffer, ring, sync) triples with slot-recording
        tree hooks."""
        out = []
        for _ in range(2):
            buf = _fill_buf(0, cap=cap)
            sync = DeviceRingSync(buf, chunk_cap=chunk_cap)
            seen = []
            sync.tree_hook = lambda s, seen=seen: seen.append(
                np.asarray(jax.device_get(s)).copy()
            )
            out.append((buf, device_ring_init(cap, 3, 1), sync, seen))
        return out

    def _add(self, buf, n, seed):
        r = np.random.default_rng(seed)
        buf.add_batch(Transition(
            r.normal(size=(n, 3)).astype(np.float32),
            r.uniform(-1, 1, (n, 1)).astype(np.float32),
            r.uniform(-1, 0, n).astype(np.float32),
            r.normal(size=(n, 3)).astype(np.float32),
            np.full(n, 0.99, np.float32),
        ))

    def test_stage_then_flush_byte_equal_plain_flush(self):
        """stage()+flush() is invisible: same ring bytes, same tree-hook
        slot sequence, same byte/chunk counters as a plain flush."""
        (buf_a, ring_a, sync_a, seen_a), (buf_b, ring_b, sync_b, seen_b) = (
            self._pair()
        )
        self._add(buf_a, 48, seed=5)
        self._add(buf_b, 48, seed=5)
        ring_a = sync_a.flush(ring_a)
        assert sync_b.stage()
        ring_b = sync_b.flush(ring_b)
        assert _leaves_equal(ring_a, ring_b)
        assert sync_a._synced == sync_b._synced == 48
        assert sync_a.bytes_ingested == sync_b.bytes_ingested
        assert sync_a.chunks_ingested == sync_b.chunks_ingested == 3
        assert len(seen_a) == len(seen_b)
        for x, y in zip(seen_a, seen_b):
            np.testing.assert_array_equal(x, y)

    def test_stage_survives_ring_wrap_overwrites(self):
        """Rows overwritten between stage() and flush() are re-shipped by
        the remainder loop AFTER the staged scatter — the mirrored ring
        equals a from-scratch full mirror (last-write-wins)."""
        (buf_a, ring_a, sync_a, _), (buf_b, ring_b, sync_b, _) = self._pair()
        for buf in (buf_a, buf_b):
            self._add(buf, 48, seed=5)
        ring_a = sync_a.flush(ring_a)
        # 10 fresh rows get staged; then 70 more writes wrap the 64-row
        # ring and overwrite every staged slot before the flush.
        for buf, seed in ((buf_a, 11), (buf_b, 11)):
            self._add(buf, 10, seed=seed)
        assert sync_b.stage()
        for buf, seed in ((buf_a, 12), (buf_b, 12)):
            self._add(buf, 70, seed=seed)
        ring_a = sync_a.flush(ring_a)
        ring_b = sync_b.flush(ring_b)
        assert _leaves_equal(ring_a, ring_b)
        # And both equal a from-scratch mirror of the final buffer state.
        buf_c = _fill_buf(0)
        self._add(buf_c, 48, seed=5)
        self._add(buf_c, 10, seed=11)
        self._add(buf_c, 70, seed=12)
        sync_c = DeviceRingSync(buf_c, chunk_cap=16)
        ring_c = sync_c.flush(device_ring_init(_C, 3, 1))
        assert _leaves_equal(ring_a, ring_c)

    def test_stage_noop_and_single_consume(self):
        (buf, ring, sync, _), _ = self._pair()
        assert not sync.stage()          # nothing pending
        self._add(buf, 10, seed=3)
        assert sync.stage()
        assert sync.stage()              # idempotent while staged
        ring = sync.flush(ring)
        assert sync.chunks_ingested == 1  # staged chunk covered it all
        assert sync._staged is None
        assert int(np.asarray(jax.device_get(ring.size))) == 10
        assert sync.flush(ring) is ring  # nothing left pending


# ------------------------------------------------------ recipe + gating
class TestBatchScaleRecipe:
    def test_scaling_rules_pinned(self):
        cfg = apply_env_preset(TrainConfig(env="pendulum", batch_scale=8))
        s = apply_batch_scale(cfg)
        assert s.batch_size == 2048
        assert s.agent.lr_actor == pytest.approx(8e-4)
        assert s.agent.lr_critic == pytest.approx(8e-4)
        assert s.agent.per_beta_steps == 100_000 // 8
        assert s.warmup_steps == 8_000
        assert s.steps_per_dispatch == 1
        # K floors at 1 but divides when it can
        s2 = apply_batch_scale(dataclasses.replace(
            cfg, batch_scale=4, steps_per_dispatch=8
        ))
        assert s2.steps_per_dispatch == 2

    def test_scale_one_is_identity(self):
        cfg = apply_env_preset(TrainConfig(env="pendulum"))
        assert apply_batch_scale(cfg) == cfg

    def test_cli_wires_the_recipe(self):
        from train import build_parser, config_from_args

        args = build_parser().parse_args([
            "--env", "pendulum", "--batch-scale", "8",
            "--replay-placement", "device", "--projection", "pallas_fused",
            "--fused-descent", "--ingest-prefetch",
        ])
        cfg = config_from_args(args)
        assert cfg.batch_size == 2048 and cfg.batch_scale == 8
        assert cfg.agent.lr_actor == pytest.approx(8e-4)
        assert cfg.fused_descent and cfg.ingest_prefetch


class TestFusedNegotiation:
    def test_fused_descent_verdicts(self):
        ok = RequestedCaps(placement="device", fused_descent=True,
                           projection="pallas_fused")
        assert negotiate(ok).verdict == "pass"
        codes = {
            g.code for g in negotiate(RequestedCaps(
                placement="host", fused_descent=True
            )).gaps
        }
        assert {"fused_descent_device_only",
                "fused_descent_requires_pallas_fused"} <= codes
        assert "fused_descent_single_device" in {
            g.code for g in negotiate(dataclasses.replace(ok, dp=2)).gaps
        }
        assert "fused_descent_requires_per" in {
            g.code for g in negotiate(
                dataclasses.replace(ok, prioritized=False)
            ).gaps
        }
        assert "fused_descent_categorical_only" in {
            g.code for g in negotiate(
                dataclasses.replace(ok, dist_kind="quantile")
            ).gaps
        }

    def test_ingest_prefetch_declared(self):
        n = negotiate(RequestedCaps(placement="host", ingest_prefetch=True))
        assert n.verdict == "negotiated"
        assert "ingest_prefetch_ignored" in n.actions
        assert negotiate(
            RequestedCaps(placement="device", ingest_prefetch=True)
        ).verdict == "pass"

    def test_matrix_declares_large_batch_scenario(self):
        cells = {
            (c["scenario"], c["placement"]): c for c in composition_matrix()
        }
        assert cells[("large_batch_fused", "device")]["verdict"] == "pass"
        assert cells[("large_batch_fused", "host")]["verdict"] == "gap"


# ------------------------------------------------------- trainer-level
def _recipe_trainer_cfg(log_dir: str, **kw) -> TrainConfig:
    agent = D4PGConfig(
        hidden_sizes=(16, 16), dist=DistConfig(num_atoms=11),
        projection_backend="pallas_fused",
    )
    base = dict(
        env="pendulum", num_envs=2, total_steps=8, warmup_steps=48,
        batch_size=8, steps_per_dispatch=2, eval_interval=1000,
        eval_episodes=1, checkpoint_interval=100_000, replay_capacity=512,
        prioritized=True, tree_backend="numpy", agent=agent,
        log_dir=log_dir, concurrent_eval=False, seed=3,
        replay_placement="device", device_tree_backend="pallas",
        fused_descent=True, ingest_prefetch=True, debug_guards=True,
    )
    base.update(kw)
    return apply_env_preset(TrainConfig(**base))


def _run_trainer(cfg):
    from d4pg_tpu.runtime.trainer import Trainer

    t = Trainer(cfg)
    try:
        t.train()
        return t, jax.device_get(t.state)
    finally:
        t.close()


class TestFusedTrainerGuards:
    def test_fused_recipe_guards_clean(self, tmp_path):
        """THE fast end-to-end smoke of the whole ISSUE-16 stack: fused
        descent + double-buffered ingest under --debug-guards. Sentinel
        budgets hold (megastep/ring_ingest/tree_ingest all compile ONCE),
        the zero-transfer steady state is clean, no ledger hold leaks,
        and the ingest_stage timer actually ran."""
        t, _ = _run_trainer(_recipe_trainer_cfg(str(tmp_path / "fused")))
        assert t._megastep_warm
        counts = t.sentinel.counts()
        assert counts["megastep"] == 1
        assert counts["ring_ingest"] == 1
        assert counts["tree_ingest"] == 1
        assert t._ledger.stats()["active_holds"] == 0
        assert t._ledger.stats()["trips"] == 0
        row = t._timers.scalars()
        assert row["stage_ingest_stage_calls"] > 0

    def test_fused_trainer_byte_equal_oracle_trainer(self, tmp_path):
        """Flipping --fused-descent (and --ingest-prefetch with it) moves
        NOTHING in a seeded device-PER run: byte-identical params and
        optimizer moments after a full train() leg."""
        _, s_fused = _run_trainer(
            _recipe_trainer_cfg(str(tmp_path / "fused"))
        )
        _, s_oracle = _run_trainer(_recipe_trainer_cfg(
            str(tmp_path / "oracle"), fused_descent=False,
            ingest_prefetch=False,
        ))
        assert _leaves_equal(s_fused.actor_params, s_oracle.actor_params)
        assert _leaves_equal(s_fused.critic_params, s_oracle.critic_params)
        assert _leaves_equal(
            s_fused.critic_opt_state, s_oracle.critic_opt_state
        )

    @pytest.mark.slow
    def test_large_batch_400_step_guards_clean(self, tmp_path):
        """The ISSUE-16 acceptance run: 400 grad steps at the large-batch
        shape (B=2048, bf16, ensemble off to bound wall time) under
        --debug-guards — zero guard trips, zero leaked holds, budgets
        megastep=1 / ring_ingest=1 / tree_ingest=1."""
        agent = D4PGConfig(
            hidden_sizes=(16, 16), dist=DistConfig(num_atoms=11),
            projection_backend="pallas_fused", compute_dtype="bfloat16",
        )
        t, _ = _run_trainer(_recipe_trainer_cfg(
            str(tmp_path / "big"), agent=agent, num_envs=4,
            total_steps=400, warmup_steps=2500, batch_size=2048,
            steps_per_dispatch=4, replay_capacity=4096,
        ))
        assert t._megastep_warm
        counts = t.sentinel.counts()
        assert counts["megastep"] == 1
        assert counts["ring_ingest"] == 1
        assert counts["tree_ingest"] == 1
        assert t._ledger.stats()["active_holds"] == 0
        assert t._ledger.stats()["trips"] == 0

    @pytest.mark.slow
    def test_scaled_recipe_solve_quality_parity(self, tmp_path):
        """Solve-quality parity on pendulum: the --batch-scale 4 recipe
        (B=512, lr x4, beta-anneal /4, warmup x4) at the SAME data budget
        as the integration baseline must clear the same learning bar
        (trained beats random init by > 250 return)."""
        from train import build_parser, config_from_args
        from d4pg_tpu.envs import Pendulum
        from d4pg_tpu.runtime import evaluate

        args = build_parser().parse_args([
            "--env", "pendulum",
            "--total-steps", "1500",      # 6000 baseline steps / S=4
            "--warmup", "2000",           # recipe scales this x4
            "--eval-interval", "100000",
            "--checkpoint-interval", "1000000",
            "--num-envs", "8",
            "--bsize", "128",             # recipe scales this to 512
            "--batch-scale", "4",
            "--n-step", "3",
            "--tau", "0.005",
            "--lr-actor", "5e-4",         # recipe scales to 2e-3
            "--lr-critic", "5e-4",
            "--seed", "0",
            "--replay-placement", "device",
            "--device-tree-backend", "pallas",
            "--projection", "pallas_fused",
            "--fused-descent",
            "--ingest-prefetch",
            "--rmsize", "16384",
            "--log-dir", str(tmp_path / "recipe"),
        ])
        cfg = config_from_args(args)
        cfg = dataclasses.replace(
            cfg,
            agent=dataclasses.replace(cfg.agent, hidden_sizes=(64, 64)),
            # same env-interaction budget as the baseline: 2.0 x S
            env_steps_per_train_step=8.0,
        )
        base_state = create_train_state(cfg.agent, jax.random.PRNGKey(123))
        base = evaluate(
            cfg.agent, Pendulum(), base_state.actor_params,
            jax.random.PRNGKey(7), 10,
        )
        trainer, state = _run_trainer(cfg)
        trained = evaluate(
            cfg.agent, Pendulum(), state.actor_params,
            jax.random.PRNGKey(7), 10,
        )
        improvement = trained["eval_return_mean"] - base["eval_return_mean"]
        assert improvement > 250.0, (
            f"scaled recipe lost solve quality: random "
            f"{base['eval_return_mean']:.0f} -> trained "
            f"{trained['eval_return_mean']:.0f}"
        )
