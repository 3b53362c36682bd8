"""Device-resident replay + fused megastep (ROADMAP item 1).

The contracts under test, in dependency order:

1. the HBM ring is a byte-exact mirror of the host buffer's slots,
   through chunked ingest, ring wrap, and snapshot restore, with exactly
   ONE ingest compile;
2. seeded small-scale f32 parity: the device ring's uniform path (in-
   kernel ``jax.random`` draw) produces a BYTE-IDENTICAL TrainState vs
   the host oracle (host-gathered batches through the same fused scan)
   given the same key — the acceptance contract of the megastep;
3. frozen-literal hybrid determinism: ``sample_block_indices`` draws the
   exact pinned index stream, equal to ``sample_block``'s on every tree
   backend — so flipping ``replay_placement`` host↔hybrid moves no
   seeded run (and a full two-Trainer run proves it end to end,
   byte-identical params included);
4. the trainer's device placement runs clean under ``--debug-guards``
   with the TIGHTENED zero-transfer budget (no H2D — explicit or
   implicit — and no D2H at the steady-state dispatch site), zero
   recompiles after warmup, zero leaked ledger holds;
5. placement validation: the flag surface fails loudly on unsupported
   combinations instead of silently ignoring them.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from d4pg_tpu.agent import D4PGConfig, create_train_state  # noqa: E402
from d4pg_tpu.agent.d4pg import fused_train_scan  # noqa: E402
from d4pg_tpu.config import TrainConfig, apply_env_preset  # noqa: E402
from d4pg_tpu.models.critic import DistConfig  # noqa: E402
from d4pg_tpu.replay.device_ring import (  # noqa: E402
    DeviceRingSync,
    device_ring_init,
)
from d4pg_tpu.replay.per import PrioritizedReplayBuffer  # noqa: E402
from d4pg_tpu.replay.uniform import ReplayBuffer, Transition  # noqa: E402
from d4pg_tpu.runtime.megastep import (  # noqa: E402
    draw_uniform_indices,
    make_megastep_uniform,
)


def _small_cfg() -> D4PGConfig:
    return D4PGConfig(
        obs_dim=3,
        action_dim=1,
        hidden_sizes=(16, 16),
        dist=DistConfig(num_atoms=11, v_min=-5.0, v_max=5.0),
    )


def _fill(buf, n, seed=0):
    r = np.random.default_rng(seed)
    obs_dim = buf.obs.shape[1]
    act_dim = buf.action.shape[1]
    buf.add_batch(
        Transition(
            r.normal(size=(n, obs_dim)).astype(np.float32),
            r.uniform(-1, 1, (n, act_dim)).astype(np.float32),
            r.uniform(-1, 0, n).astype(np.float32),
            r.normal(size=(n, obs_dim)).astype(np.float32),
            np.full(n, 0.99, np.float32),
        )
    )


def _leaves_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(jax.device_get(a))
    lb = jax.tree_util.tree_leaves(jax.device_get(b))
    return len(la) == len(lb) and all(
        np.array_equal(x, y) for x, y in zip(la, lb)
    )


# ------------------------------------------------------------- ring mirror
class TestDeviceRingMirror:
    def test_mirror_matches_host_slots(self):
        buf = ReplayBuffer(32, 3, 1)
        _fill(buf, 20)
        ring = device_ring_init(32, 3, 1)
        sync = DeviceRingSync(buf, chunk_cap=8)  # forces multi-chunk flush
        ring = sync.flush(ring)
        assert int(ring.size) == 20
        for field in ("obs", "action", "reward", "next_obs", "discount"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ring, field))[:20], getattr(buf, field)[:20]
            )

    def test_mirror_through_ring_wrap(self):
        buf = ReplayBuffer(16, 3, 1)
        ring = device_ring_init(16, 3, 1)
        sync = DeviceRingSync(buf, chunk_cap=8)
        _fill(buf, 10, seed=1)
        ring = sync.flush(ring)
        _fill(buf, 10, seed=2)  # wraps: slots 10..15, then 0..3
        ring = sync.flush(ring)
        assert int(ring.size) == 16
        for field in ("obs", "reward"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ring, field)), getattr(buf, field)
            )

    def test_pending_beyond_capacity_collapses_to_full_resync(self):
        buf = ReplayBuffer(8, 3, 1)
        ring = device_ring_init(8, 3, 1)
        sync = DeviceRingSync(buf, chunk_cap=8)
        _fill(buf, 30, seed=3)  # 30 writes into an 8-slot ring
        assert sync.pending() == 8  # only the surviving slots ship
        ring = sync.flush(ring)
        np.testing.assert_array_equal(np.asarray(ring.obs), buf.obs)
        assert sync.pending() == 0

    def test_flush_noop_when_nothing_pending(self):
        buf = ReplayBuffer(16, 3, 1)
        _fill(buf, 4)
        ring = device_ring_init(16, 3, 1)
        sync = DeviceRingSync(buf)
        ring = sync.flush(ring)
        chunks = sync.chunks_ingested
        ring = sync.flush(ring)  # nothing new
        assert sync.chunks_ingested == chunks

    def test_single_ingest_compile_across_flushes(self):
        buf = ReplayBuffer(64, 3, 1)
        ring = device_ring_init(64, 3, 1)
        sync = DeviceRingSync(buf, chunk_cap=16)
        for seed in range(4):
            _fill(buf, 10, seed=seed)
            ring = sync.flush(ring)
        # one fixed chunk shape -> exactly one compiled specialization
        # (the recompile sentinel budgets this at 1 in --debug-guards runs)
        assert sync.ingest_fn._cache_size() == 1

    def test_restore_resyncs_whole_buffer(self, tmp_path):
        src = ReplayBuffer(16, 3, 1)
        _fill(src, 12, seed=9)
        snap = str(tmp_path / "replay.npz")
        src.snapshot(snap)
        dst = ReplayBuffer(16, 3, 1)
        dst.restore(snap)
        ring = device_ring_init(16, 3, 1)
        sync = DeviceRingSync(dst, chunk_cap=8)
        ring = sync.flush(ring)
        assert int(ring.size) == 12
        np.testing.assert_array_equal(np.asarray(ring.obs)[:12], dst.obs[:12])


# ------------------------------------------------- uniform megastep parity
class TestUniformMegastepParity:
    def test_byte_identical_vs_host_oracle(self):
        """The acceptance contract: same transitions + same seeded key ⇒
        the uniform megastep (in-kernel draw + in-jit ring gather) and the
        host oracle (host-gathered staged batches through the same fused
        scan) produce byte-identical TrainStates after N dispatches, f32,
        small scale. Depends on the uniform path carrying NO weights key
        on either side (see megastep_uniform_body's determinism note)."""
        from functools import partial

        cfg = _small_cfg()
        K, B, rows = 3, 8, 64
        buf = ReplayBuffer(128, 3, 1)
        _fill(buf, rows)
        ring = DeviceRingSync(buf, chunk_cap=32).flush(
            device_ring_init(128, 3, 1)
        )
        mega = make_megastep_uniform(cfg, K, B)
        fused = jax.jit(partial(fused_train_scan, cfg), donate_argnums=(0,))
        state_dev = create_train_state(cfg, jax.random.PRNGKey(1))
        state_host = create_train_state(cfg, jax.random.PRNGKey(1))
        key = jax.random.PRNGKey(7)
        k = key
        for _ in range(3):
            # oracle: replicate the in-kernel draw on host (threefry is
            # backend-deterministic), gather host-side, stage, scan
            _, k_idx = jax.random.split(k)
            idx = np.asarray(draw_uniform_indices(k_idx, K, B, jnp.int32(rows)))
            batches = {
                name: jnp.asarray(
                    np.stack([getattr(buf, name)[idx[i]] for i in range(K)])
                )
                for name in ("obs", "action", "reward", "next_obs", "discount")
            }
            state_host, _, _ = fused(state_host, batches)
            state_dev, k, _metrics = mega(state_dev, ring, k)
        # the WHOLE TrainState: params, targets, both Adam moment sets
        assert _leaves_equal(state_dev, state_host)

    def test_different_keys_diverge(self):
        """Sanity for the parity test: the comparison is not vacuous."""
        cfg = _small_cfg()
        buf = ReplayBuffer(128, 3, 1)
        _fill(buf, 64)
        ring = DeviceRingSync(buf).flush(device_ring_init(128, 3, 1))
        mega = make_megastep_uniform(cfg, 2, 8)
        s1, _, _ = mega(
            create_train_state(cfg, jax.random.PRNGKey(1)), ring,
            jax.random.PRNGKey(7),
        )
        s2, _, _ = mega(
            create_train_state(cfg, jax.random.PRNGKey(1)), ring,
            jax.random.PRNGKey(8),
        )
        assert not _leaves_equal(s1.actor_params, s2.actor_params)


# --------------------------------------------- wide rows, stored lane-dense
class TestWideRowStorage:
    """ISSUE 25: an obs width of 136 is stored 16 rows to a storage row
    (``DeviceRing``: ``[C/16, 2176]``). The same megastep over the same
    rows stored logically (the rule switched off: ``P`` = 1 is the old
    ``[C, W]`` ring and the old ``field[idx]``) must return the same bits
    everywhere: state, tree, priorities, metrics."""

    W, A, C, ROWS, K, B = 136, 2, 128, 96, 3, 8

    def _cfg(self):
        return dataclasses.replace(_small_cfg(), obs_dim=self.W, action_dim=self.A)

    def _run(self, kind):
        from d4pg_tpu.replay import device_per as dper
        from d4pg_tpu.runtime.megastep import (
            make_megastep_device_per,
            make_megastep_hybrid,
        )

        cfg = self._cfg()
        buf = ReplayBuffer(self.C, self.W, self.A)
        _fill(buf, self.ROWS)
        sync = DeviceRingSync(buf, chunk_cap=40)    # 40 % 16: chunks straddle
        dps = dper.DevicePerSync(self.C, cfg.per_alpha)
        sync.tree_hook = dps.on_chunk
        ring = sync.flush(device_ring_init(self.C, self.W, self.A))
        state = create_train_state(cfg, jax.random.PRNGKey(1))
        key, out = jax.random.PRNGKey(7), []
        if kind == "uniform":
            mega = make_megastep_uniform(cfg, self.K, self.B)
        elif kind == "per":
            mega, tree = make_megastep_device_per(cfg, self.K, self.B), dps.tree
        else:
            mega = make_megastep_hybrid(cfg)
            r = np.random.default_rng(5)
        for _ in range(3):
            if kind == "uniform":
                state, key, metrics = mega(state, ring, key)
            elif kind == "per":
                state, tree, key, metrics = mega(state, ring, tree, key)
                out.append(jax.device_get(tree))   # donated next round
            else:
                idx = r.integers(0, self.ROWS, (self.K, self.B)).astype(np.int32)
                idx[0, :2] = self.ROWS - 1
                w = r.uniform(0.5, 1.0, (self.K, self.B)).astype(np.float32)
                state, metrics, priorities = mega(
                    state, ring, jnp.asarray(idx), jnp.asarray(w))
                out.append(priorities)
            out.append(metrics)
        return ring, jax.device_get((state, key, out))

    @pytest.mark.parametrize("kind", ["uniform", "per", "hybrid"])
    def test_packed_ring_is_bit_identical_to_logical_rows(self, kind, monkeypatch):
        from d4pg_tpu.replay import device_ring

        packed_ring, packed = self._run(kind)
        assert packed_ring.obs.shape == (self.C // 16, 16 * self.W)
        assert packed_ring.describe_storage()["obs"]["rows_per_storage_row"] == 16
        monkeypatch.setattr(device_ring, "rows_per_storage_row", lambda width: 1)
        logical_ring, logical = self._run(kind)
        assert logical_ring.obs.shape == (self.C, self.W)
        assert _leaves_equal(packed, logical)
        np.testing.assert_array_equal(
            np.asarray(packed_ring.logical("obs")), np.asarray(logical_ring.obs))


    def test_trainer_logs_the_ring_storage_once(self, tmp_path, capsys):
        """The counter that says the storage engaged is static, so the
        trainer prints it once at start-up (``describe_storage``)."""
        import json

        from d4pg_tpu.runtime.trainer import Trainer

        t = Trainer(_trainer_cfg("device", str(tmp_path / "d")))
        try:
            described = t._ring.describe_storage()
        finally:
            t.close()
        lines = [line for line in capsys.readouterr().out.splitlines()
                 if line.startswith("[replay] device ring storage: ")]
        assert len(lines) == 1
        logged = json.loads(lines[0].split(": ", 1)[1])
        assert logged["obs"]["stored_shape"] == list(described["obs"]["stored_shape"])
        assert logged["obs"]["rows_per_storage_row"] == 1     # pendulum: 3 wide
        assert set(logged) == {"obs", "action", "reward", "next_obs", "discount"}


# ------------------------------------------------ hybrid index determinism
def _per_buf(backend: str) -> PrioritizedReplayBuffer:
    buf = PrioritizedReplayBuffer(64, 3, 2, tree_backend=backend)
    r = np.random.default_rng(5)
    buf.add_batch(
        Transition(
            r.normal(size=(48, 3)).astype(np.float32),
            r.uniform(-1, 1, (48, 2)).astype(np.float32),
            r.uniform(-1, 0, 48).astype(np.float32),
            r.normal(size=(48, 3)).astype(np.float32),
            np.full(48, 0.99, np.float32),
        )
    )
    buf.update_priorities(
        np.arange(48), r.uniform(0.1, 3.0, 48).astype(np.float64)
    )
    return buf


# The determinism contract, frozen: this exact seeded buffer + rng(123) +
# B=4, K=3, step=7 must draw THESE indices forever — the stream
# sample_block consumes (one uniform of size K·B over stratified bounds,
# round-robin dealt). If this literal moves, seeded PER runs diverge when
# flipping replay_placement between host and hybrid.
FROZEN_HYBRID_IDX = [[3, 12, 26, 39], [4, 16, 27, 42], [9, 21, 34, 45]]


class TestHybridIndexDeterminism:
    @pytest.mark.parametrize("backend", ["numpy", "auto"])
    def test_frozen_literal_and_sample_block_equality(self, backend):
        buf = _per_buf(backend)
        idx, w, gen = buf.sample_block_indices(
            4, 3, np.random.default_rng(123), step=7
        )
        assert idx.tolist() == FROZEN_HYBRID_IDX
        blk = _per_buf(backend).sample_block(
            4, 3, np.random.default_rng(123), step=7
        )
        np.testing.assert_array_equal(blk["indices"].idx, idx)
        np.testing.assert_array_equal(blk["indices"].gen, gen)
        np.testing.assert_array_equal(blk["weights"], w)


# ------------------------------------------------- trainer-level contracts
def _trainer_cfg(placement: str, log_dir: str, **kw) -> TrainConfig:
    agent = D4PGConfig(hidden_sizes=(16, 16), dist=DistConfig(num_atoms=11))
    base = dict(
        env="pendulum",
        num_envs=2,
        total_steps=8,
        warmup_steps=48,
        batch_size=8,
        steps_per_dispatch=2,
        eval_interval=1000,
        eval_episodes=1,
        checkpoint_interval=100_000,
        replay_capacity=512,
        prioritized=True,
        tree_backend="numpy",
        agent=agent,
        log_dir=log_dir,
        concurrent_eval=False,
        seed=3,
        replay_placement=placement,
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


class TestTrainerPlacement:
    @pytest.mark.slow
    def test_hybrid_byte_identical_to_host(self, tmp_path):
        """Flipping replay_placement host↔hybrid moves NOTHING in a seeded
        run: same PER index stream (sample_block_indices == sample_block),
        same rows (ring mirrors the host buffer byte-exactly), same IS
        weights ⇒ byte-identical params, targets, and optimizer moments
        after a full train() leg on a real env."""
        _, s_host = _run_trainer(
            _trainer_cfg("host", str(tmp_path / "host"))
        )
        _, s_hyb = _run_trainer(
            _trainer_cfg("hybrid", str(tmp_path / "hyb"))
        )
        assert _leaves_equal(s_host.actor_params, s_hyb.actor_params)
        assert _leaves_equal(s_host.critic_params, s_hyb.critic_params)
        assert _leaves_equal(s_host.actor_opt_state, s_hyb.actor_opt_state)

    def test_device_placement_guards_clean(self, tmp_path):
        """Device placement under --debug-guards: the steady-state
        dispatch runs under the TIGHTENED zero-transfer budget
        (no_transfers: explicit H2D and any D2H raise), the recompile
        budget holds after warmup, and no ledger hold leaks."""
        t, _ = _run_trainer(
            _trainer_cfg(
                "device", str(tmp_path / "dev"), prioritized=False,
                debug_guards=True,
            )
        )
        assert t._megastep_warm  # steady-state dispatches ran tight-guarded
        counts = t.sentinel.counts()
        assert counts["megastep"] == 1
        assert counts["ring_ingest"] == 1
        assert t._ledger.stats()["active_holds"] == 0
        assert t._ledger.stats()["trips"] == 0

    def test_device_metrics_row_has_zero_count_h2d(self, tmp_path):
        """The ride-along bugfix: device-placement metrics rows carry the
        per-dispatch host stages as EXPLICIT zeros (0 s / 0 calls), and
        the megastep stages as live counters."""
        t, _ = _run_trainer(
            _trainer_cfg("device", str(tmp_path / "dev"), prioritized=False)
        )
        row = t._timers.scalars()
        assert row["stage_h2d_stage_calls"] == 0.0
        assert row["stage_h2d_stage_s"] == 0.0
        assert row["stage_sample_calls"] == 0.0
        assert row["stage_megastep_dispatch_calls"] > 0
        assert row["stage_ingest_chunk_calls"] > 0

    def test_placement_validation(self, tmp_path):
        from d4pg_tpu.runtime.trainer import Trainer

        with pytest.raises(ValueError, match="hybrid is the PER mode"):
            Trainer(
                _trainer_cfg(
                    "hybrid", str(tmp_path / "a"), prioritized=False
                )
            )
        with pytest.raises(ValueError, match="transfer-dtype|transfer_dtype"):
            Trainer(
                _trainer_cfg(
                    "device", str(tmp_path / "b"), prioritized=False,
                    transfer_dtype="bfloat16",
                )
            )
        with pytest.raises(ValueError, match="host|device|hybrid"):
            Trainer(_trainer_cfg("gpu", str(tmp_path / "c")))

    def test_no_transfers_guard_catches_injected_violations(self):
        """The tightened budget is a real guard, not a comment: an
        explicit device_put (sanctioned under the old budget) and a D2H
        fetch both raise inside no_transfers; the megastep dispatch
        itself passes (its operands are device-resident)."""
        from d4pg_tpu.analysis import no_transfers

        cfg = _small_cfg()
        buf = ReplayBuffer(64, 3, 1)
        _fill(buf, 32)
        ring = DeviceRingSync(buf).flush(device_ring_init(64, 3, 1))
        mega = make_megastep_uniform(cfg, 2, 4)
        state = create_train_state(cfg, jax.random.PRNGKey(0))
        key = jax.device_put(jax.random.PRNGKey(1))
        state, key, _ = mega(state, ring, key)  # warmup compile (exempt)
        with no_transfers():
            state, key, metrics = mega(state, ring, key)  # clean
        with pytest.raises(Exception, match="[Dd]isallowed.*transfer"):
            with no_transfers():
                jax.device_put(np.zeros(4, np.float32))  # explicit H2D
        if jax.default_backend() != "cpu":
            # On the CPU backend a fetch is zero-copy (no transfer event
            # fires), so the D2H half is only assertable on a real device.
            with pytest.raises(Exception, match="[Dd]isallowed.*transfer"):
                with no_transfers():
                    np.asarray(metrics["critic_loss"])

    def test_device_keeps_prioritized_on_device(self, tmp_path, capsys):
        """ISSUE 14: `--replay-placement device` with the default PER
        flag KEEPS prioritized replay — the priority structure is the
        device-resident segment tree (tests/test_device_per.py has the
        full contract), the host buffer a plain ring, no downgrade."""
        from d4pg_tpu.runtime.trainer import Trainer

        t = Trainer(_trainer_cfg("device", str(tmp_path / "d")))
        try:
            assert t.config.prioritized is True
            assert isinstance(t.buffer, ReplayBuffer)
            assert not isinstance(t.buffer, PrioritizedReplayBuffer)
            assert t._dev_per is not None
        finally:
            t.close()
        assert "disabling PER" not in capsys.readouterr().out
