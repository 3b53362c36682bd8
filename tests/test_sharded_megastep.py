"""Sharded megastep: pjit partition-rule learner over a dp mesh (ROADMAP
item 2 — the scale-out of the PR-6 device-resident data plane).

The contracts under test, in dependency order:

1. the STRIPED sharded ring is a byte-exact mirror of the host buffer
   (lane d local row i == host slot i·D + d) through chunked ingest,
   uneven pending distributions and ring wrap, with exactly ONE ingest
   compile (budget 1, same as the unsharded sync);
2. BYTE-IDENTITY: the sharded megastep over the 8-way CPU virtual mesh
   produces a bit-exact TrainState vs the single-device parity oracle
   (the SAME ``sharded_megastep_uniform_body`` under ``vmap`` over
   striped lanes) — possible only because the body's sole cross-shard
   arithmetic is ``det_pmean``'s fixed-order sum; ``pmean``'s backend
   AllReduce would not replay;
3. the trainer's device placement composes with ``--dp``: state placed
   per the partition-rule registry, guards clean under ``--debug-guards``
   with the tightened zero-transfer budget, recompile budgets flat
   (megastep=1, ring_ingest=1), and checkpoints round-trip — gathered
   whole on save, RE-SHARDED onto the mesh on ``--resume`` (the
   ``make_shard_and_gather_fns`` port), including after ``kill -9``;
4. validation: the new flag surface fails loudly on unsupported
   combinations (hybrid+dp, tp>1, indivisible batch/capacity).
"""

from __future__ import annotations

import numpy as np
import pytest
from conftest import assert_sharded_parity

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from d4pg_tpu.agent import D4PGConfig, create_train_state  # noqa: E402
from d4pg_tpu.config import TrainConfig, apply_env_preset  # noqa: E402
from d4pg_tpu.models.critic import DistConfig  # noqa: E402
from d4pg_tpu.parallel import make_mesh, shard_train_state  # noqa: E402
from d4pg_tpu.replay.device_ring import (  # noqa: E402
    ShardedDeviceRingSync,
    device_ring_init,
    striped_lanes,
    striped_perm,
)
from d4pg_tpu.replay.uniform import ReplayBuffer, Transition  # noqa: E402
from d4pg_tpu.runtime.megastep import (  # noqa: E402
    make_megastep_uniform_oracle,
    make_megastep_uniform_sharded,
)


def _small_cfg(**kw) -> D4PGConfig:
    base = dict(
        obs_dim=3,
        action_dim=1,
        hidden_sizes=(16, 16),
        dist=DistConfig(num_atoms=11, v_min=-5.0, v_max=5.0),
    )
    base.update(kw)
    return D4PGConfig(**base)


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


# ------------------------------------------------------ striped ring mirror
class TestShardedRingMirror:
    def test_striped_mirror_matches_host_slots(self):
        D, C = 4, 64
        mesh = make_mesh(dp=D, tp=1)
        buf = ReplayBuffer(C, 3, 1)
        _fill(buf, 41)  # uneven: shards own 11/10/10/10 filled rows
        ring = device_ring_init(C, 3, 1, mesh=mesh)
        sync = ShardedDeviceRingSync(buf, mesh, chunk_cap=16)
        ring = sync.flush(ring)
        assert int(ring.size) == 41
        perm = striped_perm(C, D)  # [D, C/D] host slots in device order
        for field in ("obs", "action", "reward", "next_obs", "discount"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ring, field)),
                getattr(buf, field)[perm].reshape(
                    (C,) + getattr(buf, field).shape[1:]
                ),
            )

    def test_mirror_through_ring_wrap(self):
        D, C = 4, 32
        mesh = make_mesh(dp=D, tp=1)
        buf = ReplayBuffer(C, 3, 1)
        ring = device_ring_init(C, 3, 1, mesh=mesh)
        sync = ShardedDeviceRingSync(buf, mesh, chunk_cap=16)
        _fill(buf, 20, seed=1)
        ring = sync.flush(ring)
        _fill(buf, 20, seed=2)  # wraps
        ring = sync.flush(ring)
        assert int(ring.size) == C
        perm = striped_perm(C, D)
        np.testing.assert_array_equal(
            np.asarray(ring.obs), buf.obs[perm].reshape(C, 3)
        )

    def test_single_ingest_compile_across_flushes(self):
        D, C = 4, 64
        mesh = make_mesh(dp=D, tp=1)
        buf = ReplayBuffer(C, 3, 1)
        ring = device_ring_init(C, 3, 1, mesh=mesh)
        sync = ShardedDeviceRingSync(buf, mesh, chunk_cap=8)
        for seed in range(4):
            _fill(buf, 10, seed=seed)
            ring = sync.flush(ring)
        assert sync.ingest_fn._cache_size() == 1

    def test_rows_land_sharded_over_dp(self):
        D, C = 4, 32
        mesh = make_mesh(dp=D, tp=1)
        buf = ReplayBuffer(C, 3, 1)
        _fill(buf, 16)
        ring = ShardedDeviceRingSync(buf, mesh).flush(
            device_ring_init(C, 3, 1, mesh=mesh)
        )
        assert ring.obs.sharding == NamedSharding(mesh, P("dp", None))
        local = {s.data.shape for s in ring.obs.addressable_shards}
        assert local == {(C // D, 3)}

    def test_capacity_not_divisible_raises(self):
        mesh = make_mesh(dp=4, tp=1)
        with pytest.raises(ValueError, match="divisible"):
            device_ring_init(30, 3, 1, mesh=mesh)
        with pytest.raises(ValueError, match="divisible"):
            ShardedDeviceRingSync(ReplayBuffer(30, 3, 1), mesh)


# ----------------------------------------------------- byte-exact parity
class TestShardedMegastepParity:
    def test_byte_identical_vs_single_device_oracle(self):
        """THE acceptance contract (ISSUE 9): seeded math of the sharded
        megastep over the 8-way CPU virtual mesh is byte-identical to the
        single-device oracle — the same per-shard body vmapped over
        striped lanes, combined by the same fixed-order det_pmean."""
        D, K, B, C = 8, 3, 16, 128
        cfg = _small_cfg()
        mesh = make_mesh(dp=D, tp=1)
        buf = ReplayBuffer(C, 3, 1)
        _fill(buf, 96)
        ring = ShardedDeviceRingSync(buf, mesh, chunk_cap=64).flush(
            device_ring_init(C, 3, 1, mesh=mesh)
        )
        mega = make_megastep_uniform_sharded(cfg, K, B, mesh)
        oracle = make_megastep_uniform_oracle(cfg, K, B, D)
        st_m = shard_train_state(create_train_state(cfg, jax.random.PRNGKey(1)), mesh)
        st_o = create_train_state(cfg, jax.random.PRNGKey(1))
        key_m = jax.device_put(
            jax.random.PRNGKey(7), NamedSharding(mesh, P())
        )
        key_o = jax.random.PRNGKey(7)
        lanes = striped_lanes(buf, D)
        for _ in range(3):
            st_m, key_m, met_m = mega(st_m, ring, key_m)
            st_o, key_o, met_o = oracle(st_o, lanes, key_o)
        # the WHOLE TrainState: params, targets, both Adam moment sets —
        # step and key exact, floats to a few ulp (see the helper for why
        # not bytes); the draw stream and the loss stay exact
        assert_sharded_parity(st_m, st_o)
        assert np.array_equal(np.asarray(key_m), np.asarray(key_o))
        assert np.asarray(met_m["critic_loss"]) == np.asarray(
            met_o["critic_loss"]
        )

    def test_parity_holds_with_critic_ensemble(self):
        """The capacity the sharding unlocks composes with it: an E-wide
        ensemble (stack replicated over the dp mesh per stack_axes_for)
        keeps the byte-identity — the per-step random subset draw comes
        from the TrainState key, identical under both harnesses."""
        D, K, B, C = 4, 2, 8, 64
        cfg = _small_cfg(critic_ensemble=4, ensemble_min_targets=2)
        mesh = make_mesh(dp=D, tp=1)
        buf = ReplayBuffer(C, 3, 1)
        _fill(buf, 48)
        ring = ShardedDeviceRingSync(buf, mesh).flush(
            device_ring_init(C, 3, 1, mesh=mesh)
        )
        mega = make_megastep_uniform_sharded(cfg, K, B, mesh)
        oracle = make_megastep_uniform_oracle(cfg, K, B, D)
        st_m = shard_train_state(create_train_state(cfg, jax.random.PRNGKey(2)), mesh)
        st_o = create_train_state(cfg, jax.random.PRNGKey(2))
        key_m = jax.device_put(jax.random.PRNGKey(9), NamedSharding(mesh, P()))
        key_o = jax.random.PRNGKey(9)
        lanes = striped_lanes(buf, D)
        for _ in range(2):
            st_m, key_m, _ = mega(st_m, ring, key_m)
            st_o, key_o, _ = oracle(st_o, lanes, key_o)
        assert_sharded_parity(st_m, st_o)
        assert np.array_equal(np.asarray(key_m), np.asarray(key_o))

    @pytest.mark.parametrize("kind", ["uniform", "per"])
    def test_wide_rows_packed_bit_identical_to_logical(self, kind, monkeypatch):
        """ISSUE 25: obs width 136 is stored 16 rows to a storage row, so a
        dp=4 ring of 128 rows is ``[8, 2176]`` with two storage rows (its
        own 32 logical rows) on each chip. The sharded megastep over it
        returns the bits of the same megastep over the rows stored
        logically (the rule switched off): state, tree, key, metrics."""
        from d4pg_tpu.replay import device_per as dper
        from d4pg_tpu.replay import device_ring
        from d4pg_tpu.runtime.megastep import make_megastep_device_per_sharded

        D, K, B, C, W, A = 4, 2, 16, 128, 136, 2
        cfg = _small_cfg(obs_dim=W, action_dim=A)
        mesh = make_mesh(dp=D, tp=1)

        def run():
            buf = ReplayBuffer(C, W, A)
            _fill(buf, 100)          # uneven: shards hold 25 rows each
            sync = ShardedDeviceRingSync(buf, mesh, chunk_cap=40)
            dps = dper.DevicePerSync(C, cfg.per_alpha, mesh=mesh)
            sync.tree_hook = dps.on_chunk
            ring = sync.flush(device_ring_init(C, W, A, mesh=mesh))
            state = shard_train_state(
                create_train_state(cfg, jax.random.PRNGKey(1)), mesh)
            key = jax.device_put(jax.random.PRNGKey(7), NamedSharding(mesh, P()))
            out, tree = [], dps.tree
            if kind == "uniform":
                mega = make_megastep_uniform_sharded(cfg, K, B, mesh)
            else:
                mega = make_megastep_device_per_sharded(cfg, K, B, mesh)
            for _ in range(3):
                if kind == "uniform":
                    state, key, metrics = mega(state, ring, key)
                else:
                    state, tree, key, metrics = mega(state, ring, tree, key)
                    out.append(jax.device_get(tree))    # donated next round
                out.append(metrics)
            return ring, jax.device_get((state, key, out))

        packed_ring, packed = run()
        assert packed_ring.obs.shape == (C // 16, 16 * W)
        assert {s.data.shape for s in packed_ring.obs.addressable_shards} == {
            (C // 16 // D, 16 * W)}
        monkeypatch.setattr(device_ring, "rows_per_storage_row", lambda width: 1)
        logical_ring, logical = run()
        assert logical_ring.obs.shape == (C, W)
        assert _leaves_equal(packed, logical)
        np.testing.assert_array_equal(
            np.asarray(packed_ring.logical("obs")), np.asarray(logical_ring.obs))

    def test_different_keys_diverge(self):
        """Sanity: the parity comparison is not vacuous."""
        D, K, B, C = 4, 2, 8, 64
        cfg = _small_cfg()
        mesh = make_mesh(dp=D, tp=1)
        buf = ReplayBuffer(C, 3, 1)
        _fill(buf, 48)
        ring = ShardedDeviceRingSync(buf, mesh).flush(
            device_ring_init(C, 3, 1, mesh=mesh)
        )
        mega = make_megastep_uniform_sharded(cfg, K, B, mesh)
        sharding = NamedSharding(mesh, P())
        s1, _, _ = mega(
            shard_train_state(create_train_state(cfg, jax.random.PRNGKey(1)), mesh),
            ring, jax.device_put(jax.random.PRNGKey(7), sharding),
        )
        s2, _, _ = mega(
            shard_train_state(create_train_state(cfg, jax.random.PRNGKey(1)), mesh),
            ring, jax.device_put(jax.random.PRNGKey(8), sharding),
        )
        assert not _leaves_equal(s1.actor_params, s2.actor_params)

    def test_zero_transfer_guard_clean_on_mesh(self):
        """The PR-6 zero-transfer budget survives scale-out: a steady-state
        sharded dispatch runs clean under no_transfers (state, ring, key
        all mesh-resident)."""
        from d4pg_tpu.analysis import no_transfers

        D, K, B, C = 4, 2, 8, 64
        cfg = _small_cfg()
        mesh = make_mesh(dp=D, tp=1)
        buf = ReplayBuffer(C, 3, 1)
        _fill(buf, 48)
        ring = ShardedDeviceRingSync(buf, mesh).flush(
            device_ring_init(C, 3, 1, mesh=mesh)
        )
        mega = make_megastep_uniform_sharded(cfg, K, B, mesh)
        state = shard_train_state(create_train_state(cfg, jax.random.PRNGKey(0)), mesh)
        key = jax.device_put(jax.random.PRNGKey(1), NamedSharding(mesh, P()))
        state, key, _ = mega(state, ring, key)  # warmup compile (exempt)
        with no_transfers():
            state, key, _ = mega(state, ring, key)  # clean

    def test_mesh_validation(self):
        cfg = _small_cfg()
        with pytest.raises(ValueError, match="dp-only"):
            make_megastep_uniform_sharded(cfg, 2, 8, make_mesh(dp=4, tp=2))
        with pytest.raises(ValueError, match="divisible"):
            make_megastep_uniform_sharded(cfg, 2, 9, make_mesh(dp=4, tp=1))


# ------------------------------------------------- trainer-level contracts
def _trainer_cfg(log_dir: str, **kw) -> TrainConfig:
    agent = kw.pop(
        "agent", D4PGConfig(hidden_sizes=(16, 16), dist=DistConfig(num_atoms=11))
    )
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
        prioritized=False,
        tree_backend="numpy",
        agent=agent,
        log_dir=log_dir,
        concurrent_eval=False,
        seed=3,
        replay_placement="device",
        dp=4,
    )
    base.update(kw)
    return apply_env_preset(TrainConfig(**base))


class TestTrainerShardedPlacement:
    def test_sharded_device_placement_guards_clean(self, tmp_path):
        """device placement + --dp under --debug-guards: zero-transfer
        steady state, recompile budgets flat (megastep=1, ring_ingest=1),
        zero leaked holds; state and ring land sharded per the rules."""
        from d4pg_tpu.runtime.trainer import Trainer

        t = Trainer(_trainer_cfg(str(tmp_path / "dev"), debug_guards=True))
        try:
            t.train()
            assert t._megastep_warm
            counts = t.sentinel.counts()
            assert counts["megastep"] == 1
            assert counts["ring_ingest"] == 1
            assert t._ledger.stats()["active_holds"] == 0
            assert t._ledger.stats()["trips"] == 0
            assert t._ring.obs.sharding == NamedSharding(
                t._mega_mesh, P("dp", None)
            )
        finally:
            t.close()

    @pytest.mark.slow
    def test_checkpoint_roundtrip_reshards_on_mesh(self, tmp_path):
        """The make_shard_and_gather_fns port, end to end: leg 1 saves
        (leaves gathered WHOLE to host), leg 2 --resume re-shards onto the
        mesh per the rule registry and keeps training with flat budgets —
        no implicit reshard, no guard trip."""
        from d4pg_tpu.runtime.trainer import Trainer

        d = str(tmp_path / "run")
        t = Trainer(
            _trainer_cfg(d, total_steps=4, checkpoint_interval=4,
                         debug_guards=True)
        )
        try:
            t.train()
            step1 = int(jax.device_get(t.state.step))
        finally:
            t.close()
        t2 = Trainer(
            _trainer_cfg(d, total_steps=8, checkpoint_interval=4,
                         debug_guards=True, resume=True)
        )
        try:
            assert t2.grad_steps == step1
            leaf = jax.tree_util.tree_leaves(t2.state.critic_params)[0]
            assert isinstance(leaf.sharding, NamedSharding)
            assert leaf.sharding.mesh == t2._mega_mesh
            t2.train()
            counts = t2.sentinel.counts()
            assert counts["megastep"] == 1
            assert counts["ring_ingest"] == 1
            assert t2._ledger.stats()["trips"] == 0
        finally:
            t2.close()

    @pytest.mark.slow
    def test_kill9_resume_on_mesh(self, tmp_path):
        """kill -9 mid-run, then --resume on the mesh: the crash-consistent
        restore (manifest-verified) composes with the NamedSharding
        re-shard — the regression test the ISSUE names."""
        import os
        import signal
        import subprocess
        import sys
        import time

        from tests.conftest import clean_cpu_env

        d = str(tmp_path / "run")
        env = clean_cpu_env(pythonpath_repo=True)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
        code = (
            "import sys; sys.argv=['train.py','--env','pendulum',"
            "'--num-envs','2','--warmup','48','--bsize','8',"
            "'--total-steps','4000','--steps-per-dispatch','2',"
            "'--eval-interval','1000','--eval-episodes','1',"
            "'--checkpoint-interval','4','--rmsize','512',"
            "'--no-p-replay','--tree-backend','numpy',"
            "'--hidden-sizes','16,16','--n-atoms','11',"
            "'--replay-placement','device','--dp','4',"
            f"'--log-dir',{d!r},'--no-concurrent-eval'];"
            "import train; train.main()"
        )
        proc = subprocess.Popen(
            [sys.executable, "-c", code], env=env, cwd=os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))
            ),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        # Wait for at least one committed checkpoint, then SIGKILL.
        ckpt_dir = os.path.join(d, "checkpoints")
        deadline = time.monotonic() + 300
        committed = False
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                out = proc.stdout.read().decode(errors="replace")
                pytest.fail(f"trainer exited early rc={proc.returncode}:\n{out}")
            if os.path.isdir(ckpt_dir) and any(
                n.startswith("manifest_") for n in os.listdir(ckpt_dir)
            ):
                committed = True
                break
            time.sleep(0.25)
        assert committed, "no committed checkpoint within deadline"
        proc.kill()  # SIGKILL: no cleanup, the crash the manifest attests
        proc.wait()
        # Resume on the same mesh, short leg, guards on.
        from d4pg_tpu.runtime.trainer import Trainer

        t = Trainer(
            _trainer_cfg(d, total_steps=4, debug_guards=True, resume=True)
        )
        try:
            assert t.grad_steps >= 4  # restored an attested step
            t.train(4)  # one more short leg on the restored state
            assert t.sentinel.counts()["megastep"] == 1
            assert t._ledger.stats()["trips"] == 0
        finally:
            t.close()

    def test_placement_validation(self, tmp_path):
        from d4pg_tpu.runtime.trainer import Trainer

        with pytest.raises(ValueError, match="single-device"):
            Trainer(
                _trainer_cfg(
                    str(tmp_path / "a"), replay_placement="hybrid",
                    prioritized=True,
                )
            )
        with pytest.raises(ValueError, match="dp-only|tp"):
            Trainer(_trainer_cfg(str(tmp_path / "b"), tp=2))
        with pytest.raises(ValueError, match="divisible"):
            Trainer(_trainer_cfg(str(tmp_path / "c"), batch_size=10))
        with pytest.raises(ValueError, match="divisible"):
            Trainer(_trainer_cfg(str(tmp_path / "d"), replay_capacity=510))
        with pytest.raises(ValueError, match="host-path DP mode"):
            Trainer(_trainer_cfg(str(tmp_path / "e"), dp_hogwild=True))
