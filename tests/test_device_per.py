"""Device-resident PER (ISSUE 14 / ROADMAP item 2 of the current arc).

The contracts under test, in dependency order:

1. the device segment tree is structurally the host tree: same layout,
   same totals, same descent (identical index draws for identical
   prefixes on the f64 host trees, both backends), same duplicate
   (last-wins) write-back semantics, pad slots dropped;
2. FROZEN-LITERAL STREAM PARITY (the PR-6 discipline): the device draw's
   prefixes are reproducible on host from the same key, so over multiple
   dispatches the host sum-tree oracle descended with those exact
   prefixes yields IDENTICAL seeded index draws, f32-resolution-equal IS
   weights, and f32-close post-writeback priorities — pinned as frozen
   literals so the device stream can never silently shift, on BOTH host
   tree backends (numpy and native);
3. the Pallas blocked-prefix-scan descent (``ops/pallas_tree.py``,
   interpret mode on CPU) equals the XLA reference descent —
   the backend-ladder oracle contract;
4. SHARDED BIT-IDENTITY (the PR-9 discipline): the dp=8 mesh device-PER
   megastep produces a bit-exact TrainState AND priority tree vs the
   single-device vmap oracle over striped lanes — possible only because
   the body's cross-shard arithmetic is det_pmean plus exact
   order-independent min/max reduces;
5. the trainer's ``--replay-placement device`` now KEEPS prioritized
   replay (plain host ring + device tree, no downgrade), runs clean
   under ``--debug-guards`` with the tightened zero-transfer budget and
   flat compile budgets (megastep=1, ring_ingest=1, tree_ingest=1), and
   snapshots/restores the tree priorities across --resume.
"""

from __future__ import annotations

import numpy as np
import pytest
from conftest import assert_sharded_parity

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from d4pg_tpu.agent import D4PGConfig, create_train_state  # noqa: E402
from d4pg_tpu.config import TrainConfig, apply_env_preset  # noqa: E402
from d4pg_tpu.models.critic import DistConfig  # noqa: E402
from d4pg_tpu.replay import device_per as dper  # noqa: E402
from d4pg_tpu.replay.per import PrioritizedReplayBuffer  # noqa: E402
from d4pg_tpu.replay.segment_tree import SumTree  # noqa: E402
from d4pg_tpu.replay.uniform import ReplayBuffer, Transition  # noqa: E402

CAP, K, B, SIZE = 64, 3, 4, 48


def _per_buf(backend: str) -> PrioritizedReplayBuffer:
    """The seeded host buffer the frozen literals are pinned against
    (same recipe as test_megastep's hybrid determinism fixture)."""
    buf = PrioritizedReplayBuffer(CAP, 3, 2, tree_backend=backend)
    r = np.random.default_rng(5)
    buf.add_batch(
        Transition(
            r.normal(size=(SIZE, 3)).astype(np.float32),
            r.uniform(-1, 1, (SIZE, 2)).astype(np.float32),
            r.uniform(-1, 0, SIZE).astype(np.float32),
            r.normal(size=(SIZE, 3)).astype(np.float32),
            np.full(SIZE, 0.99, np.float32),
        )
    )
    buf.update_priorities(
        np.arange(SIZE), r.uniform(0.1, 3.0, SIZE).astype(np.float64)
    )
    return buf


def _tree_from_buf(buf) -> dper.DevicePerTree:
    """Seed a device tree with the host buffer's exact α'd leaves."""
    pa = np.zeros(CAP, np.float32)
    pa[:SIZE] = np.asarray(buf._sum.get(np.arange(SIZE)), np.float32)
    return dper.tree_from_priorities(
        pa, CAP, max_priority=float(buf._max_priority)
    )


# ------------------------------------------------------------ tree structure
class TestDeviceTreeStructure:
    def test_set_leaves_matches_host_tree(self):
        r = np.random.default_rng(0)
        pri = r.uniform(0.1, 3.0, CAP)
        ht = SumTree(CAP)
        ht.set(np.arange(CAP), pri)
        lane = dper.set_leaves(
            jnp.zeros(dper.tree_width(CAP), jnp.float32),
            jnp.arange(CAP, dtype=jnp.int32),
            jnp.asarray(pri, jnp.float32),
            CAP,
        )
        half = dper.tree_width(CAP) // 2
        np.testing.assert_allclose(
            np.asarray(lane[half: half + CAP]), pri.astype(np.float32),
            rtol=0,
        )
        assert abs(float(lane[1]) - ht.sum()) < 1e-4

    def test_descend_matches_host_tree_exactly(self):
        r = np.random.default_rng(1)
        pri = r.uniform(0.1, 3.0, CAP)
        ht = SumTree(CAP)
        ht.set(np.arange(CAP), pri)
        lane = dper.set_leaves(
            jnp.zeros(dper.tree_width(CAP), jnp.float32),
            jnp.arange(CAP, dtype=jnp.int32),
            jnp.asarray(pri, jnp.float32),
            CAP,
        )
        pre = r.uniform(0.0, float(lane[1]) * (1 - 1e-6), 256)
        idx_d = dper.descend_prefix(lane, jnp.asarray(pre, jnp.float32))
        idx_h = ht.find_prefixsum_idx(pre)
        np.testing.assert_array_equal(np.asarray(idx_d), idx_h)

    def test_descend_skips_zero_mass_leaves(self):
        """The >= boundary semantics: a prefix landing exactly on a
        cumsum boundary selects the NEXT nonzero leaf (host contract)."""
        pri = np.array([2.0, 0.0, 3.0, 0.0], np.float64)
        lane = dper.set_leaves(
            jnp.zeros(8, jnp.float32), jnp.arange(4, dtype=jnp.int32),
            jnp.asarray(pri, jnp.float32), 4,
        )
        idx = dper.descend_prefix(
            lane, jnp.asarray([0.0, 1.9, 2.0, 4.9], jnp.float32)
        )
        assert np.asarray(idx).tolist() == [0, 0, 2, 2]

    def test_update_duplicates_last_wins(self):
        """The host trees' numpy-assignment duplicate semantics, made
        deterministic on device via the scatter-max winner pick."""
        lane = dper.set_leaves(
            jnp.zeros(dper.tree_width(CAP), jnp.float32),
            jnp.arange(CAP, dtype=jnp.int32),
            jnp.ones(CAP, jnp.float32),
            CAP,
        )
        lane = dper.update_leaves_last_wins(
            lane,
            jnp.asarray([3, 5, 3, 7, 3], jnp.int32),
            jnp.asarray([9.0, 2.0, 4.0, 6.0, 1.5], jnp.float32),
            CAP,
        )
        half = dper.tree_width(CAP) // 2
        assert float(lane[half + 3]) == 1.5   # last write
        assert float(lane[half + 5]) == 2.0
        assert float(lane[half + 7]) == 6.0
        ht = SumTree(CAP)
        ht.set(np.arange(CAP), np.ones(CAP))
        ht.set(np.array([3, 5, 3, 7, 3]), np.array([9.0, 2.0, 4.0, 6.0, 1.5]))
        assert abs(float(lane[1]) - ht.sum()) < 1e-4

    def test_pad_slots_are_dropped(self):
        """Ring-ingest pad slots (value == capacity) must not seed
        phantom mass — not even into the pow2 padding leaves."""
        lane = jnp.zeros(dper.tree_width(48), jnp.float32)  # 48 < L=64
        lane2 = dper.tree_ingest_lane_body(
            0.6, 48, lane, jnp.float32(1.0),
            jnp.full(16, 48, jnp.int32),  # all pads
        )
        assert float(jnp.abs(lane2).sum()) == 0.0

    def test_snapshot_restore_roundtrip_striped(self):
        r = np.random.default_rng(3)
        pa = r.uniform(0.1, 2.0, CAP).astype(np.float32)
        for shards in (1, 4):
            sync = dper.DevicePerSync.__new__(dper.DevicePerSync)
            sync.capacity, sync.alpha = CAP, 0.6
            sync._mesh, sync.n_shards = None, shards
            sync.local_capacity = CAP // shards
            sync.restore_host(pa, 2.5)
            got, mp = sync.snapshot_host()
            np.testing.assert_array_equal(got, pa)
            assert mp == 2.5


# ------------------------------------- frozen-literal host-tree stream parity
# The determinism contract, frozen: PRNGKey(7) split once, fold_in(0),
# over the seeded _per_buf tree at step=7 must draw THESE indices (and
# batch 0's IS weights round to THESE values). If either literal moves,
# seeded device-PER runs change their sampling stream — which is a fact
# about this repo's draw code AND about the installed JAX's threefry
# stream: the literals pin the jax range in pyproject.toml (0.9.x, where
# ``jax_threefry_partitionable`` defaults to True). They were re-derived
# for it in PR 21; with the flag forced back to False the same code still
# draws the pre-0.5 literals ([[3, 12, 25, 37], [7, 18, 30, 40],
# [9, 21, 33, 46]]), i.e. the draw code did not move, the generator did.
# The device-vs-host agreement asserted next to them is exact either way.
FROZEN_DEVICE_PER_IDX = [[0, 14, 26, 38], [6, 16, 29, 42], [10, 21, 32, 45]]
FROZEN_DEVICE_PER_W0 = [0.5499, 0.53441, 0.48069, 0.47235]


class TestHostTreeStreamParity:
    @pytest.mark.parametrize("backend", ["numpy", "auto"])
    def test_frozen_stream_and_multi_dispatch_parity(self, backend):
        """Device tree vs host sum-tree over 3 draw→writeback rounds:
        identical index draws (exact), IS weights and post-writeback
        priorities at f32 resolution, max-priority tracking — the
        device-side draw pinned by frozen literals, on both host tree
        backends."""
        host = _per_buf(backend)
        tree = _tree_from_buf(host)
        key = jax.random.PRNGKey(7)

        draw = jax.jit(
            lambda lane, k: dper.lane_draw(lane, k, K, B, jnp.int32(SIZE))
        )
        wb = jax.jit(
            lambda lane, i, p: dper.write_back_lane(
                lane, i, p, host.alpha, host.eps, CAP
            )
        )
        half = dper.tree_width(CAP) // 2
        for step in (7, 8, 9):
            key, k_draw = jax.random.split(key)
            k_lane = jax.random.fold_in(k_draw, jnp.int32(0))
            lane = tree.sums[0]
            idx, p_leaf, total = draw(lane, k_lane)
            # -- the host oracle: same prefixes (threefry is backend-
            # deterministic), descended on the HOST f64 tree
            pre = dper.host_prefixes(k_lane, K, B, float(lane[1]))
            idx_h = host._sum.find_prefixsum_idx(
                np.asarray(pre, np.float64).reshape(-1)
            ).reshape(K, B)
            idx_h = np.minimum(idx_h, SIZE - 1)
            np.testing.assert_array_equal(np.asarray(idx), idx_h)
            if step == 7:
                assert np.asarray(idx).tolist() == FROZEN_DEVICE_PER_IDX
            # -- IS weights: host formula (f64 trees) vs device f32
            beta = host.beta(step)
            p_h = host._sum.get(idx_h.reshape(-1)) / host._sum.sum()
            w_h = (p_h * SIZE) ** (-beta)
            w_h /= (host._min.min() / host._sum.sum() * SIZE) ** (-beta)
            w_d = dper.importance_weights(
                p_leaf, total,
                dper.lane_min_leaf(lane) / total,
                jnp.int32(SIZE), 1,
                dper.beta_at(jnp.int32(step), host.beta0, host.beta_steps),
            )
            np.testing.assert_allclose(
                np.asarray(w_d).reshape(-1), w_h, rtol=2e-5
            )
            if step == 7:
                assert [
                    round(float(x), 5) for x in np.asarray(w_d)[0]
                ] == FROZEN_DEVICE_PER_W0
            # -- write-back: same synthetic TD block through both sides
            td = np.random.default_rng(100 + step).uniform(
                0.05, 2.0, (K, B)
            ).astype(np.float32)
            lane2, mp_local = wb(lane, idx, jnp.asarray(td))
            host.update_priorities(
                idx_h.reshape(-1), td.reshape(-1).astype(np.float64)
            )
            np.testing.assert_allclose(
                np.asarray(lane2[half: half + SIZE]),
                np.asarray(host._sum.get(np.arange(SIZE)), np.float64),
                rtol=2e-6,
            )
            tree = dper.DevicePerTree(
                lane2[None], jnp.maximum(tree.max_priority, mp_local)
            )
            assert (
                abs(float(tree.max_priority) - host._max_priority) < 1e-5
            )

    def test_beta_matches_host_schedule(self):
        host = _per_buf("numpy")
        for step in (0, 1, 50_000, 100_000, 200_000):
            assert abs(
                float(dper.beta_at(jnp.int32(step), host.beta0,
                                   host.beta_steps))
                - host.beta(step)
            ) < 1e-6


# ---------------------------------------------------------- pallas backend
class TestPallasDescent:
    def test_matches_xla_descent(self):
        """The kernel runs the tree walk itself, so it returns the XLA
        descent's leaf for every draw on arbitrary f32 priorities (incl. a
        non-pow2 capacity → zero-mass pad leaves, and draw counts off the
        128 tile). cap=48 keeps every level inside the first vreg;
        cap=5000 (L = 8192) reaches the levels swept by the row loop."""
        from d4pg_tpu.ops.pallas_tree import find_prefix_pallas

        r = np.random.default_rng(2)
        for cap, shape in ((48, (3, 7)), (5000, (2, 300))):
            pri = (r.exponential(1.0, cap) + 1e-6) ** 0.6
            pri[r.random(cap) < 0.05] = 0.0  # zero-mass holes are skipped
            lane = dper.set_leaves(
                jnp.zeros(dper.tree_width(cap), jnp.float32),
                jnp.arange(cap, dtype=jnp.int32),
                jnp.asarray(pri, jnp.float32),
                cap,
            )
            pre = jnp.asarray(
                r.uniform(0.0, float(lane[1]) * (1 - 1e-6), shape),
                jnp.float32,
            )
            # boundary prefixes: node sums themselves (>= goes right)
            pre = pre.at[0, :4].set(lane[jnp.asarray([2, 4, 5, 9])])
            idx_x = dper.descend_prefix(lane, pre)
            idx_p = find_prefix_pallas(lane, pre, interpret=True)
            np.testing.assert_array_equal(
                np.asarray(idx_p), np.asarray(idx_x)
            )

    def test_lane_draw_backend_equivalence(self):
        """The full draw path (prefixes + descent + clamp) is backend-
        invariant on the frozen stream."""
        host = _per_buf("numpy")
        tree = _tree_from_buf(host)
        key = jax.random.fold_in(
            jax.random.split(jax.random.PRNGKey(7))[1], jnp.int32(0)
        )
        idx_x, _, _ = dper.lane_draw(
            tree.sums[0], key, K, B, jnp.int32(SIZE), tree_backend="xla"
        )
        idx_p, _, _ = dper.lane_draw(
            tree.sums[0], key, K, B, jnp.int32(SIZE),
            tree_backend="pallas", interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(idx_p), np.asarray(idx_x))
        assert np.asarray(idx_x).tolist() == FROZEN_DEVICE_PER_IDX


# ------------------------------------------------------- sharded bit-parity
def _small_cfg(**kw) -> D4PGConfig:
    base = dict(
        obs_dim=3,
        action_dim=1,
        hidden_sizes=(16, 16),
        dist=DistConfig(num_atoms=11, v_min=-5.0, v_max=5.0),
    )
    base.update(kw)
    return D4PGConfig(**base)


def _fill_uniform(buf, n, seed=0):
    r = np.random.default_rng(seed)
    buf.add_batch(
        Transition(
            r.normal(size=(n, 3)).astype(np.float32),
            r.uniform(-1, 1, (n, 1)).astype(np.float32),
            r.uniform(-1, 0, n).astype(np.float32),
            r.normal(size=(n, 3)).astype(np.float32),
            np.full(n, 0.99, np.float32),
        )
    )


class TestShardedDevicePerParity:
    def test_byte_identical_vs_single_device_oracle(self):
        """The PR-9 acceptance contract, extended to PER: the 8-way mesh
        device-PER megastep (shard-local subtrees + fixed-order root
        combine) is BIT-EXACT — TrainState, subtree lanes, AND the
        max-priority scalar — vs the same body under vmap over striped
        lanes, across multiple draw→train→write-back dispatches."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from d4pg_tpu.parallel import make_mesh, shard_train_state
        from d4pg_tpu.replay.device_ring import (
            ShardedDeviceRingSync,
            device_ring_init,
            striped_lanes,
        )
        from d4pg_tpu.runtime.megastep import (
            make_megastep_device_per_oracle,
            make_megastep_device_per_sharded,
        )

        cfg = _small_cfg()
        D, C, k, b = 8, 64, 2, 16
        buf = ReplayBuffer(C, 3, 1)
        _fill_uniform(buf, C)
        mesh = make_mesh(dp=D, tp=1)
        ring = device_ring_init(C, 3, 1, mesh=mesh)
        sync = ShardedDeviceRingSync(buf, mesh)
        dps = dper.DevicePerSync(C, cfg.per_alpha, mesh=mesh)
        sync.tree_hook = dps.on_chunk
        ring = sync.flush(ring)  # mirrors rows AND seeds every leaf
        # oracle side: striped lane view + an identically seeded lane tree
        lanes = striped_lanes(buf, D)
        tree_o = dper.tree_from_priorities(
            np.ones(C, np.float32), C, n_shards=D
        )
        mega = make_megastep_device_per_sharded(cfg, k, b, mesh)
        oracle = make_megastep_device_per_oracle(cfg, k, b, D)
        s_mesh = shard_train_state(
            create_train_state(cfg, jax.random.PRNGKey(1)), mesh
        )
        s_or = create_train_state(cfg, jax.random.PRNGKey(1))
        key_m = jax.device_put(
            jax.random.PRNGKey(7), NamedSharding(mesh, P())
        )
        key_o = jax.random.PRNGKey(7)
        tree_m = dps.tree
        for _ in range(3):
            s_mesh, tree_m, key_m, _m = mega(s_mesh, ring, tree_m, key_m)
            s_or, tree_o, key_o, _o = oracle(s_or, lanes, tree_o, key_o)
        # TrainState: step/key exact, floats to a few ulp (the helper says
        # why not bytes). Everything that decides WHAT is drawn — the draw
        # key, every subtree lane, the max-priority scalar — stays exact.
        assert_sharded_parity(s_mesh, s_or)
        assert np.array_equal(np.asarray(key_m), np.asarray(key_o))
        assert np.array_equal(
            np.asarray(jax.device_get(tree_m.sums)),
            np.asarray(jax.device_get(tree_o.sums)),
        )
        assert np.array_equal(
            np.asarray(jax.device_get(tree_m.max_priority)),
            np.asarray(jax.device_get(tree_o.max_priority)),
        )

    def test_sharded_tree_lanes_land_on_dp(self):
        """The PER_TREE_RULES placement: subtree lanes split over "dp"
        (one per device), the max-priority scalar replicated."""
        from d4pg_tpu.parallel import make_mesh

        mesh = make_mesh(dp=4, tp=1)
        dps = dper.DevicePerSync(64, 0.6, mesh=mesh)
        assert not dps.tree.sums.sharding.is_fully_replicated
        assert len(dps.tree.sums.sharding.device_set) == 4
        assert dps.tree.max_priority.sharding.is_fully_replicated
        # each device holds exactly one [1, 2L] lane
        shard_shapes = {
            s.data.shape for s in dps.tree.sums.addressable_shards
        }
        assert shard_shapes == {(1, dper.tree_width(16))}

    def test_capacity_not_divisible_raises(self):
        from d4pg_tpu.parallel import make_mesh

        with pytest.raises(ValueError, match="divisible"):
            dper.device_per_init(62, n_shards=4, mesh=make_mesh(dp=4, tp=1))


# ------------------------------------------------------- trainer contracts
def _trainer_cfg(log_dir: str, **kw) -> TrainConfig:
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
        replay_placement="device",
    )
    base.update(kw)
    return apply_env_preset(TrainConfig(**base))


class TestTrainerDevicePer:
    def test_device_keeps_per_with_device_tree(self, tmp_path, capsys):
        """The ISSUE-14 flip: `--replay-placement device` with PER no
        longer downgrades — the host buffer is a plain ring and the
        priority structure is the device tree."""
        from d4pg_tpu.runtime.trainer import Trainer

        t = Trainer(_trainer_cfg(str(tmp_path / "d")))
        try:
            assert t.config.prioritized is True
            assert isinstance(t.buffer, ReplayBuffer)
            assert not isinstance(t.buffer, PrioritizedReplayBuffer)
            assert t._dev_per is not None
            # bound methods compare equal (identity is per-access)
            assert t._ring_sync.tree_hook == t._dev_per.on_chunk
        finally:
            t.close()
        assert "disabling PER" not in capsys.readouterr().out

    def test_guards_clean_with_per(self, tmp_path):
        """Device-PER under --debug-guards: the steady-state dispatch
        runs under the ZERO-transfer budget with prioritized replay ON,
        compile budgets flat (megastep=1, ring_ingest=1, tree_ingest=1 —
        one fixed program each), zero ledger holds, and the device tree
        actually carries the write-backs (max_priority moved off its
        1.0 seed)."""
        from d4pg_tpu.runtime.trainer import Trainer

        t = Trainer(_trainer_cfg(str(tmp_path / "g"), debug_guards=True))
        try:
            t.train()
            assert t._megastep_warm
            counts = t.sentinel.counts()
            assert counts["megastep"] == 1
            assert counts["ring_ingest"] == 1
            assert counts["tree_ingest"] == 1
            assert t._ledger.stats()["active_holds"] == 0
            assert t._ledger.stats()["trips"] == 0
            assert float(t._dev_per.tree.max_priority) != 1.0
            # the tree's mass covers exactly the mirrored rows
            pa, _ = t._dev_per.snapshot_host()
            assert (pa > 0).sum() == len(t.buffer)
        finally:
            t.close()

    def test_hybrid_still_works_as_legacy(self, tmp_path, capsys):
        """Hybrid negotiates (legacy host-tree oracle), says so, and
        keeps its PrioritizedReplayBuffer."""
        from d4pg_tpu.runtime.trainer import Trainer

        t = Trainer(
            _trainer_cfg(str(tmp_path / "h"), replay_placement="hybrid")
        )
        try:
            assert isinstance(t.buffer, PrioritizedReplayBuffer)
        finally:
            t.close()
        assert "legacy host sum-tree" in capsys.readouterr().out

    @pytest.mark.slow
    def test_snapshot_restores_tree_priorities(self, tmp_path):
        """--snapshot-replay + --resume round-trips the device tree: the
        sidecar (device_per.npz) restores the exact α'd leaf priorities
        and max-priority instead of re-seeding at max."""
        from d4pg_tpu.runtime.trainer import Trainer

        d = str(tmp_path / "snap")
        t = Trainer(
            _trainer_cfg(
                d, snapshot_replay=True, total_steps=4,
                checkpoint_interval=4,
            )
        )
        try:
            t.train()
            t._save_checkpoint()
            pa0, mp0 = t._dev_per.snapshot_host()
        finally:
            t.close()
        assert (pa0 > 0).any()
        t2 = Trainer(
            _trainer_cfg(
                d, snapshot_replay=True, total_steps=8, resume=True,
            )
        )
        try:
            pa1, mp1 = t2._dev_per.snapshot_host()
            np.testing.assert_array_equal(pa0, pa1)
            assert mp0 == mp1
        finally:
            t2.close()

    @pytest.mark.slow
    def test_sharded_trainer_guards_clean_with_per(self, tmp_path):
        """device+PER composes with --dp over the 8-way virtual mesh
        under --debug-guards (the acceptance-run shape, miniaturized)."""
        from d4pg_tpu.runtime.trainer import Trainer

        t = Trainer(
            _trainer_cfg(
                str(tmp_path / "dp"), dp=8, batch_size=16,
                debug_guards=True,
            )
        )
        try:
            t.train()
            counts = t.sentinel.counts()
            assert counts["megastep"] == 1
            assert counts["ring_ingest"] == 1
            assert counts["tree_ingest"] == 1
            assert t._dev_per.tree.sums.shape[0] == 8
        finally:
            t.close()

    @pytest.mark.slow
    def test_pallas_backend_trains(self, tmp_path):
        """The Pallas descent is reachable end-to-end from the config
        (interpret mode on CPU) and passes the same guard contract."""
        from d4pg_tpu.runtime.trainer import Trainer

        t = Trainer(
            _trainer_cfg(
                str(tmp_path / "p"), device_tree_backend="pallas",
                total_steps=4, debug_guards=True,
            )
        )
        try:
            t.train()
            assert t.sentinel.counts()["megastep"] == 1
        finally:
            t.close()
