"""Round-7 host data-plane: native batched sample/gather/write-back,
zero-alloc staging, batched n-step ingest, per-stage telemetry.

Covers the oracle contracts the tentpole rests on:

- ``sample_block`` (native) ≡ the NumPy tree path: identical indices,
  gathered rows, IS weights, and generation stamps under a fixed seed;
- batched ``update_priorities`` ≡ NumPy semantics, including the
  generation-stamp drop of recycled slots and the max-priority reduce;
- ``tree_backend="auto"`` degrades to NumPy with no behavior change when
  the native build is unavailable (monkeypatched ``load_library`` failure);
- the ``sample_many``/``sample_block`` seeded RNG stream is a frozen
  determinism contract (PR 1 changed it once; this pins it);
- ``BatchedNStepWriter`` emits exactly what N sequential ``NStepWriter``s
  emit;
- a foreign ``libsumtree*.so`` is never loaded: the binary is keyed on a
  hash of the committed source, not on file mtimes;
- ``StageTimers`` telemetry lands in a training run's metrics.jsonl.
"""

import os
import shutil

import numpy as np
import pytest

from d4pg_tpu.replay import (
    BatchedNStepWriter,
    MinTree,
    NStepWriter,
    PrioritizedReplayBuffer,
    ReplayBuffer,
    SumTree,
)
from d4pg_tpu.replay.uniform import Transition

native = pytest.importorskip("d4pg_tpu.replay.native")

try:
    native.load_library()
    HAVE_NATIVE = True
except Exception:
    HAVE_NATIVE = False

needs_native = pytest.mark.skipif(not HAVE_NATIVE, reason="g++ build unavailable")


def _filled_pair(rows=200, capacity=256, obs_dim=3, act_dim=2, seed=0, **kw):
    """Two identically-filled PERs, NumPy oracle + native."""
    bufs = [
        PrioritizedReplayBuffer(capacity, obs_dim, act_dim, tree_backend=tb, **kw)
        for tb in ("numpy", "native")
    ]
    rng = np.random.default_rng(seed)
    t = Transition(
        rng.normal(size=(rows, obs_dim)).astype(np.float32),
        rng.uniform(-1, 1, (rows, act_dim)).astype(np.float32),
        rng.normal(size=rows).astype(np.float32),
        rng.normal(size=(rows, obs_dim)).astype(np.float32),
        np.full(rows, 0.99, np.float32),
    )
    pri = np.random.default_rng(seed + 1).uniform(0.05, 4.0, rows)
    for b in bufs:
        b.add_batch(t)
        b.update_priorities(np.arange(rows), pri)
    return bufs


@needs_native
def test_sample_block_native_matches_numpy_oracle():
    """The tentpole contract: one fused C call ≡ the NumPy path — same
    indices, same gathered rows, same IS weights, same generation stamps."""
    a, b = _filled_pair()
    for k, B, step in ((1, 32, 0), (4, 16, 7), (8, 8, 123)):
        ba = a.sample_block(B, k, np.random.default_rng(42), step=step)
        bb = b.sample_block(B, k, np.random.default_rng(42), step=step)
        np.testing.assert_array_equal(ba["indices"].idx, bb["indices"].idx)
        np.testing.assert_array_equal(ba["indices"].gen, bb["indices"].gen)
        np.testing.assert_array_equal(ba["weights"], bb["weights"])
        for key in ("obs", "action", "reward", "next_obs", "discount"):
            np.testing.assert_array_equal(ba[key], bb[key])


@needs_native
def test_update_priorities_native_matches_numpy_oracle():
    """Post-write-back tree mass (sum + min leaves) and max_priority agree,
    with duplicate indices and [K, B]-shaped inputs."""
    a, b = _filled_pair(seed=3)
    rng = np.random.default_rng(9)
    idx = rng.integers(0, 200, size=(4, 16))  # duplicates likely
    pri = rng.uniform(0.01, 7.0, size=(4, 16))
    a.update_priorities(idx, pri)
    b.update_priorities(idx, pri)
    leaves = np.arange(200)
    np.testing.assert_allclose(
        a._sum.get(leaves), b._sum.get(leaves), rtol=1e-12
    )
    assert a._min.min() == pytest.approx(b._min.min(), rel=1e-12)
    assert a._max_priority == pytest.approx(b._max_priority, rel=1e-12)


@needs_native
def test_update_priorities_native_generation_filter():
    """Write-backs for recycled slots are dropped natively, exactly as the
    NumPy SampledIndices path drops them."""
    a, b = _filled_pair(rows=8, capacity=8, obs_dim=1, act_dim=1, eps=0.0, alpha=1.0)
    sa = a.sample_block(4, 2, np.random.default_rng(0), step=0)
    sb = b.sample_block(4, 2, np.random.default_rng(0), step=0)
    # recycle the whole ring while the "dispatch" is in flight
    rng = np.random.default_rng(5)
    t = Transition(
        rng.normal(size=(8, 1)).astype(np.float32),
        rng.normal(size=(8, 1)).astype(np.float32),
        rng.normal(size=8).astype(np.float32),
        rng.normal(size=(8, 1)).astype(np.float32),
        np.full(8, 0.99, np.float32),
    )
    a.add_batch(t)
    b.add_batch(t)
    a.update_priorities(sa["indices"], np.full((2, 4), 1e-6))
    b.update_priorities(sb["indices"], np.full((2, 4), 1e-6))
    np.testing.assert_allclose(
        a._sum.get(np.arange(8)), b._sum.get(np.arange(8)), rtol=1e-12
    )
    # every update dropped → leaves still carry the fresh-insert seed
    np.testing.assert_allclose(
        b._sum.get(np.arange(8)), b._max_priority**b.alpha, rtol=1e-12
    )
    assert a._max_priority == b._max_priority


def test_sample_block_batches_equal_sample_many():
    """Dealt [K, B] block batch i ≡ sample_many's batch i (the round-robin
    stratification contract), on the NumPy path."""
    buf = _filled_pair()[0]
    K, B = 4, 16
    blk = buf.sample_block(B, K, np.random.default_rng(11), step=5)
    sm = buf.sample_many(B, K, np.random.default_rng(11), step=5)
    for i in range(K):
        np.testing.assert_array_equal(
            np.asarray(sm[i]["indices"].idx), blk["indices"].idx[i]
        )
        np.testing.assert_array_equal(sm[i]["weights"], blk["weights"][i])
        for key in ("obs", "action", "reward", "next_obs", "discount"):
            np.testing.assert_array_equal(sm[i][key], blk[key][i])


def test_sample_and_sample_block_k1_share_the_stream():
    """sample() and sample_block(B, 1) consume identical RNG state and
    return the same batch — the trainer's K=1 switch to the block path
    cannot move seeded runs."""
    buf = _filled_pair()[0]
    r1, r2 = np.random.default_rng(5), np.random.default_rng(5)
    s = buf.sample(16, r1, step=3)
    blk = buf.sample_block(16, 1, r2, step=3)
    assert r1.bit_generator.state == r2.bit_generator.state
    np.testing.assert_array_equal(np.asarray(s["indices"].idx), blk["indices"].idx[0])
    np.testing.assert_array_equal(s["weights"], blk["weights"][0])
    np.testing.assert_array_equal(s["obs"], blk["obs"][0])


@pytest.mark.parametrize("tree_backend", ["numpy"] + (["native"] if HAVE_NATIVE else []))
def test_seeded_draw_stream_contract(tree_backend):
    """The sample_many/sample_block RNG stream is a DETERMINISM CONTRACT
    (PR 1's K·B-wide descent changed seeded draws once; this freezes it):
    one Generator.uniform of size K·B over the equal-mass stratified
    bounds, low edge inclusive — nothing else may touch the stream.

    The frozen fixture: capacity 64, 40 uniform-priority inserts,
    sample_block(B=4, K=2, rng=default_rng(123), step=0).
    """
    buf = PrioritizedReplayBuffer(
        64, 1, 1, alpha=1.0, tree_backend=tree_backend
    )
    buf.add_batch(
        Transition(
            np.arange(40, dtype=np.float32)[:, None],
            np.zeros((40, 1), np.float32),
            np.zeros(40, np.float32),
            np.zeros((40, 1), np.float32),
            np.ones(40, np.float32),
        )
    )
    blk = buf.sample_block(4, 2, np.random.default_rng(123), step=0)
    # the documented recipe, reimplemented independently
    tree = SumTree(64)
    tree.set(np.arange(40), np.ones(40))
    total = tree.sum()
    bounds = np.linspace(0.0, total, 8 + 1)
    prefixes = np.random.default_rng(123).uniform(bounds[:-1], bounds[1:])
    prefixes = np.minimum(prefixes, np.nextafter(total, 0.0))
    expect = np.minimum(tree.find_prefixsum_idx(prefixes), 39)
    dealt = expect.reshape(4, 2).T  # draw j → block[j % K, j // K]
    np.testing.assert_array_equal(blk["indices"].idx, dealt)
    # the frozen literal — if this moves, seeded replays break: bump it
    # ONLY with a changelog entry declaring the stream change
    np.testing.assert_array_equal(
        blk["indices"].idx, [[3, 11, 20, 34], [5, 15, 29, 36]]
    )


def test_auto_backend_falls_back_to_numpy_without_gcc(monkeypatch):
    """tree_backend='auto' with a failing native build (no g++ / bad
    toolchain) must silently produce the NumPy path with identical
    sampling behavior — no crash anywhere in the block pipeline."""
    monkeypatch.setattr(
        native, "load_library",
        lambda: (_ for _ in ()).throw(RuntimeError("g++ not found")),
    )
    buf = PrioritizedReplayBuffer(64, 3, 2, tree_backend="auto")
    assert isinstance(buf._sum, SumTree) and isinstance(buf._min, MinTree)
    assert not buf._use_native
    rng = np.random.default_rng(0)
    for i in range(40):
        buf.add(rng.normal(size=3), rng.normal(size=2), float(i), rng.normal(size=3), 0.99)
    blk = buf.sample_block(8, 2, np.random.default_rng(1), step=0)
    assert blk["obs"].shape == (2, 8, 3)
    buf.update_priorities(blk["indices"], np.abs(rng.normal(size=(2, 8))) + 0.1)
    # oracle equivalence of the fallback: same numbers as an explicit numpy buffer
    ref = PrioritizedReplayBuffer(64, 3, 2, tree_backend="numpy")
    rng = np.random.default_rng(0)
    for i in range(40):
        ref.add(rng.normal(size=3), rng.normal(size=2), float(i), rng.normal(size=3), 0.99)
    b2 = ref.sample_block(8, 2, np.random.default_rng(1), step=0)
    np.testing.assert_array_equal(blk["indices"].idx, b2["indices"].idx)
    np.testing.assert_array_equal(blk["obs"], b2["obs"])


@needs_native
def test_foreign_binary_is_never_loaded(tmp_path, monkeypatch):
    """The build dir is gitignored and travels with directory copies, so a
    binary found there proves nothing about the committed source — not even
    with a NEWER mtime (the old staleness test). The library is keyed on a
    hash of ``sumtree.cpp``: a foreign file is ignored and the source is
    compiled; a changed source resolves to a different binary."""
    src = tmp_path / "sumtree.cpp"
    shutil.copy(native._source_path(), src)
    bdir = tmp_path / "build"
    bdir.mkdir()
    foreign = bdir / "libsumtree.so"
    foreign.write_bytes(b"definitely not an ELF shared object")
    t = os.stat(src).st_mtime + 3600
    os.utime(foreign, (t, t))  # newer than the source: mtime would trust it
    monkeypatch.setattr(native, "_source_path", lambda: str(src))
    monkeypatch.setattr(native, "_build_dir", lambda: str(bdir))
    monkeypatch.setattr(native, "_LIB", None)  # restored after the test
    lib = native.load_library()
    assert lib.st_root is not None
    built = native.library_path()
    assert os.path.getsize(built) > 1000
    assert foreign.read_bytes().startswith(b"definitely")  # left untouched
    with open(src, "a") as f:
        f.write("\n// edited\n")
    assert native.library_path() != built


class TestBatchedNStepWriter:
    def _run_pair(self, N, n, gamma, T, seed=0, term_steps=(), trunc_steps=()):
        rng = np.random.default_rng(seed)
        term = np.zeros((T, N), bool)
        trunc = np.zeros((T, N), bool)
        for t, i in term_steps:
            term[t, i] = True
        for t, i in trunc_steps:
            trunc[t, i] = True
        # distinct obs per (actor, step) so rows are identifiable
        obs = (
            np.arange(N)[None, :, None] * 1000.0
            + np.arange(T + 1)[:, None, None]
            + np.zeros((1, 1, 2))
        ).astype(np.float32)
        act = rng.uniform(-1, 1, (T, N, 1)).astype(np.float32)
        rew = rng.normal(size=(T, N))
        seq = ReplayBuffer(4096, 2, 1)
        writers = [NStepWriter(seq, n, gamma) for _ in range(N)]
        bat = ReplayBuffer(4096, 2, 1)
        bw = BatchedNStepWriter(bat, N, n, gamma)
        for t in range(T):
            for i in range(N):
                writers[i].add(
                    obs[t, i], act[t, i], float(rew[t, i]), obs[t + 1, i],
                    terminated=bool(term[t, i]), truncated=bool(trunc[t, i]),
                )
            bw.add_batch(obs[t], act[t], rew[t], obs[t + 1], term[t], trunc[t])
        return seq, bat

    @staticmethod
    def _rows(buf):
        g = buf.gather(np.arange(len(buf)))
        m = np.concatenate(
            [g["obs"], g["action"], g["reward"][:, None], g["next_obs"],
             g["discount"][:, None]], axis=1,
        )
        return m[np.lexsort(m.T)]

    def test_matches_sequential_writers_with_episode_ends(self):
        """Content parity (as row sets — only cross-actor insertion order
        may differ) through terminations, truncations, and partial-window
        flushes."""
        seq, bat = self._run_pair(
            N=3, n=4, gamma=0.9, T=50,
            term_steps=((7, 0), (20, 2), (41, 1)),
            trunc_steps=((13, 1), (33, 0), (44, 2)),
        )
        assert len(seq) == len(bat) > 0
        np.testing.assert_array_equal(self._rows(seq), self._rows(bat))

    def test_steady_state_identical_and_ordered(self):
        """No episode ends: byte-identical buffers INCLUDING ring order
        (the fast path emits in actor order, like the sequential loop)."""
        seq, bat = self._run_pair(N=4, n=3, gamma=0.8, T=20)
        assert len(seq) == len(bat) == 4 * (20 - 3 + 1)
        ga = seq.gather(np.arange(len(seq)))
        gb = bat.gather(np.arange(len(bat)))
        for key in ga:
            np.testing.assert_array_equal(ga[key], gb[key])

    def test_n1_every_step_emits(self):
        seq, bat = self._run_pair(N=2, n=1, gamma=0.99, T=10, term_steps=((4, 0),))
        assert len(bat) == 20
        np.testing.assert_array_equal(self._rows(seq), self._rows(bat))

    def test_reset_drops_windows(self):
        buf = ReplayBuffer(64, 1, 1)
        bw = BatchedNStepWriter(buf, 2, 3, 0.9)
        o = np.zeros((2, 1), np.float32)
        a = np.zeros((2, 1), np.float32)
        bw.add_batch(o, a, np.ones(2), o, np.zeros(2, bool), np.zeros(2, bool))
        bw.reset()
        bw.add_batch(o, a, np.ones(2), o, np.zeros(2, bool), np.ones(2, bool))
        # post-reset: only the single fresh step flushes (m=1 windows)
        assert len(buf) == 2
        np.testing.assert_allclose(buf.discount[:2], 0.9)

    def test_drop_actor_plus_mask_matches_scalar_restart(self):
        """Supervised-pool recovery semantics: on a worker failure the
        actor's in-flight window is dropped WHOLE (drop_actor) and its
        rows are masked (active=) until it rejoins — content-identical to
        a scalar NStepWriter that resets at the failure and is fed only
        the post-restart subsequence. No torn transition reaches replay."""
        N, n, gamma, T = 2, 3, 0.9, 12
        down = range(4, 7)  # actor 1 dark on these steps
        rng = np.random.default_rng(5)
        obs = (
            np.arange(N)[None, :, None] * 1000.0
            + np.arange(T + 1)[:, None, None]
            + np.zeros((1, 1, 2))
        ).astype(np.float32)
        act = rng.uniform(-1, 1, (T, N, 1)).astype(np.float32)
        rew = rng.normal(size=(T, N))
        seq = ReplayBuffer(4096, 2, 1)
        writers = [NStepWriter(seq, n, gamma) for _ in range(N)]
        bat = ReplayBuffer(4096, 2, 1)
        bw = BatchedNStepWriter(bat, N, n, gamma)
        zeros = np.zeros(N, bool)
        mask = np.array([True, False])
        for t in range(T):
            if t == min(down):  # the failure instant
                bw.drop_actor(1)
                writers[1].reset()
            live = mask if t in down else None
            bw.add_batch(obs[t], act[t], rew[t], obs[t + 1], zeros, zeros,
                         active=live)
            for i in range(N):
                if live is not None and not live[i]:
                    continue
                writers[i].add(
                    obs[t, i], act[t, i], float(rew[t, i]), obs[t + 1, i],
                    terminated=False, truncated=False,
                )
        assert len(seq) == len(bat) > 0
        np.testing.assert_array_equal(self._rows(seq), self._rows(bat))

    def test_masked_add_with_episode_ends_matches(self):
        """Mask + termination on the SAME step (the surviving actor's
        episode ends while another is down) takes the degraded path —
        emission must still match the scalar writers."""
        N, n, gamma = 3, 3, 0.8
        rng = np.random.default_rng(9)
        seq = ReplayBuffer(4096, 2, 1)
        writers = [NStepWriter(seq, n, gamma) for _ in range(N)]
        bat = ReplayBuffer(4096, 2, 1)
        bw = BatchedNStepWriter(bat, N, n, gamma)
        mask = np.array([True, False, True])
        for t in range(8):
            obs = rng.normal(size=(N, 2)).astype(np.float32)
            nxt = rng.normal(size=(N, 2)).astype(np.float32)
            a = rng.uniform(-1, 1, (N, 1)).astype(np.float32)
            r = rng.normal(size=N)
            term = np.array([t == 5, False, False])
            live = mask if t in (4, 5) else None
            bw.add_batch(obs, a, r, nxt, term, np.zeros(N, bool), active=live)
            for i in range(N):
                if live is not None and not live[i]:
                    continue
                writers[i].add(obs[i], a[i], float(r[i]), nxt[i],
                               terminated=bool(term[i]), truncated=False)
        assert len(seq) == len(bat) > 0
        np.testing.assert_array_equal(self._rows(seq), self._rows(bat))


def test_stage_timers_accumulate_and_report():
    from d4pg_tpu.utils.profiling import StageTimers

    t = StageTimers(annotate_prefix=None)
    with t.stage("sample"):
        pass
    with t.stage("sample"):
        pass
    with t.stage("h2d_stage"):
        pass
    s = t.scalars()
    assert s["stage_sample_calls"] == 2.0 and s["stage_sample_s"] >= 0.0
    assert s["stage_h2d_stage_calls"] == 1.0
    ms = t.summary_ms(per=2)
    assert set(ms) == {"sample", "h2d_stage"}


@pytest.mark.parametrize("steps_per_dispatch", [1, 2])
def test_trainer_writes_stage_telemetry(tmp_path, steps_per_dispatch):
    """A training run's metrics.jsonl rows carry the per-stage counters —
    the telemetry half of the tentpole, end to end through the trainer."""
    import json

    from d4pg_tpu.agent.state import D4PGConfig
    from d4pg_tpu.config import TrainConfig, apply_env_preset
    from d4pg_tpu.runtime.trainer import Trainer

    cfg = TrainConfig(
        env="pendulum",
        total_steps=2 * steps_per_dispatch,
        warmup_steps=32,
        batch_size=16,
        num_envs=2,
        eval_interval=steps_per_dispatch,
        checkpoint_interval=10**6,
        steps_per_dispatch=steps_per_dispatch,
        log_dir=str(tmp_path / "run"),
        agent=D4PGConfig(hidden_sizes=(16, 16)),
    )
    t = Trainer(apply_env_preset(cfg))
    try:
        t.train()
    finally:
        t.close()
    rows = [
        json.loads(line)
        for line in open(tmp_path / "run" / "metrics.jsonl")
    ]
    last = rows[-1]
    for stage in (
        "env_step", "replay_insert", "sample", "h2d_stage", "train_dispatch",
        "priority_writeback",
    ):
        assert last[f"stage_{stage}_s"] >= 0.0, stage
        assert last[f"stage_{stage}_calls"] >= 1.0, stage
    # dispatch accounting: one train_dispatch per K-step dispatch
    assert last["stage_train_dispatch_calls"] == 2.0
