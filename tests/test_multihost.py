"""Multi-host learner (ISSUE 17): one mesh across processes, per-host ingest.

Contracts under test, in dependency order:

1. DEGENERATE EXACTNESS (fast, tier-1): ``MultihostRingSync`` on a
   single-process 8-device mesh (P=1, L=D) is byte-identical to
   ``ShardedDeviceRingSync`` fed the same host stream — same striped
   layout, same compiled ingest program, the cursor all-gather collapses
   to a local read. Its snapshot pair round-trips: ``gather_snapshot``
   reproduces the exact ``ReplayBuffer.snapshot`` npz layout and
   ``deal_snapshot`` is its inverse.
2. LAYOUT ALGEBRA (fast, tier-1, pure host): the gapless-total formula
   equals a brute-force simulation of the interleaved global write
   stream, and the per-process snapshot deal partitions the global rows
   exactly (disjoint cover, correct local slots) for P>1 — the math that
   makes per-host ingest exact, tested without spawning processes.
3. TOPOLOGY BIT-EXACTNESS (slow, THE tentpole contract): a 2-process ×
   4-device mesh — real ``jax.distributed`` over gloo — produces
   bit-identical TrainState (params, targets, BOTH Adam moment sets),
   device ring, device-PER tree, ``det_pmean`` reductions and
   ``fold_in(global shard index)`` in-kernel draws vs the 8-device
   single-process run of the SAME code, after multiple megastep
   dispatches interleaved with per-host ingest, with a zero-transfer
   steady-state dispatch on both topologies.
4. ELASTIC RESUME (slow): a run checkpointed on 2×4 resumes on 1×8 and
   back on 2×4 through the real CLI — replay snapshot and device-PER
   priority sidecar byte-compare across the topology change.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from d4pg_tpu.parallel import make_mesh  # noqa: E402
from d4pg_tpu.replay.device_ring import (  # noqa: E402
    MultihostRingSync,
    ShardedDeviceRingSync,
    device_ring_init,
)
from d4pg_tpu.replay.uniform import ReplayBuffer, Transition  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- topology child
# One script, two topologies: ``nprocs`` 1 (the 8-device single-process
# oracle) or 2 (2 × 4-device jax.distributed over gloo). Every process
# deals itself the global write stream rows its shards own — the global
# writes k with (k % D) // L == rank, in increasing k order — so the
# interleaved stream is identical across topologies by construction.
CHILD_EXACT = textwrap.dedent(
    """
    import sys
    nprocs, rank, out = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={8 // nprocs}"
    )
    sys.path.insert(0, __REPO__)
    import numpy as np
    import jax
    if nprocs > 1:
        from d4pg_tpu.parallel import initialize_distributed
        initialize_distributed(
            coordinator_address=__COORD__,
            num_processes=nprocs, process_id=rank,
        )
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P
    from d4pg_tpu.agent import D4PGConfig, create_train_state
    from d4pg_tpu.models.critic import DistConfig
    from d4pg_tpu.parallel import make_mesh, shard_train_state
    from d4pg_tpu.parallel.distributed import gather_global, stage_global
    from d4pg_tpu.parallel.dp import det_pmean
    from d4pg_tpu.replay.device_per import DevicePerSync
    from d4pg_tpu.replay.device_ring import MultihostRingSync, device_ring_init
    from d4pg_tpu.replay.uniform import ReplayBuffer, Transition
    from d4pg_tpu.runtime.megastep import make_megastep_device_per_sharded
    from d4pg_tpu.analysis import no_transfers

    D, K, B, C = 8, 2, 16, 128
    L = D // nprocs
    cfg = D4PGConfig(obs_dim=3, action_dim=1, hidden_sizes=(16, 16),
                     dist=DistConfig(num_atoms=11, v_min=-5.0, v_max=5.0))
    mesh = make_mesh(dp=D, tp=1)

    # One deterministic GLOBAL write stream, identical on every process
    # (same seed); each process adds only its deal — the global writes k
    # with (k % D) // L == rank, in increasing k order (host p's m-th
    # local write IS global write (m//L)*D + p*L + (m%L)).
    N1, N2 = 96, 64
    r = np.random.default_rng(0)
    g = dict(
        obs=r.normal(size=(N1 + N2, 3)).astype(np.float32),
        action=r.uniform(-1, 1, (N1 + N2, 1)).astype(np.float32),
        reward=r.uniform(-1, 0, N1 + N2).astype(np.float32),
        next_obs=r.normal(size=(N1 + N2, 3)).astype(np.float32),
        discount=np.full(N1 + N2, 0.99, np.float32),
    )
    def add_deal(buf, lo, hi):
        mine = [k for k in range(lo, hi) if (k % D) // L == rank]
        buf.add_batch(Transition(*(g[f][mine] for f in
            ("obs", "action", "reward", "next_obs", "discount"))))

    buf = ReplayBuffer(C // nprocs, 3, 1)
    ring = device_ring_init(C, 3, 1, mesh=mesh)
    sync = MultihostRingSync(buf, mesh, chunk_cap=64)
    per = DevicePerSync(C, alpha=0.6, mesh=mesh)
    sync.tree_hook = per.on_chunk
    mega = make_megastep_device_per_sharded(cfg, K, B, mesh)
    state = shard_train_state(create_train_state(cfg, jax.random.PRNGKey(1)), mesh)
    key = stage_global(mesh, P(), np.asarray(jax.random.PRNGKey(7)))

    met = None
    for lo, hi in ((0, N1), (N1, N1 + N2)):
        add_deal(buf, lo, hi)
        ring = sync.flush(ring)
        for _ in range(2):
            state, per.tree, key, met = mega(state, ring, per.tree, key)
    # steady state is zero-transfer on THIS topology too: even an
    # explicit device_put (or any D2H fetch) inside this dispatch raises
    with no_transfers():
        state, per.tree, key, met = mega(state, ring, per.tree, key)
    print(f"proc {rank} ZERO_TRANSFER_DISPATCH_OK")

    # det_pmean over the process-spanning mesh: fixed-order reduction
    vals = stage_global(
        mesh, P("dp", None),
        (np.arange(D * 4, dtype=np.float32) / 7.0).reshape(D, 4) ** 2,
    )
    red = jax.jit(
        shard_map(lambda x: det_pmean(x, "dp", D), mesh=mesh,
                  in_specs=P("dp", None), out_specs=P(), check_vma=False),
        out_shardings=NamedSharding(mesh, P()),
    )(vals)
    # shard-local in-kernel draws: fold_in(GLOBAL shard index)
    draws = jax.jit(
        shard_map(
            lambda k: jax.random.uniform(
                jax.random.fold_in(k[0], jax.lax.axis_index("dp")), (1, 4)
            ),
            mesh=mesh, in_specs=P(None), out_specs=P("dp", None),
            check_vma=False,
        ),
        out_shardings=NamedSharding(mesh, P("dp", None)),
    )(stage_global(mesh, P(None), np.asarray(jax.random.PRNGKey(11))[None]))

    snap = sync.gather_snapshot(ring)          # collective
    pa, mp = per.snapshot_host()               # collective
    leaves = [gather_global(x) for x in jax.tree_util.tree_leaves(state)]
    payload = {f"state_{i}": a for i, a in enumerate(leaves)}
    payload.update(snap)
    payload["per_pa"] = pa
    payload["per_mp"] = np.float32(mp)
    payload["det_pmean"] = gather_global(red)
    payload["draws"] = gather_global(draws)
    payload["critic_loss"] = gather_global(met["critic_loss"])
    if rank == 0:
        np.savez(out, **payload)
    print(f"proc {rank} EXACT_OK")
    """
)

def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def child_env() -> dict:
    return {
        k: v
        for k, v in os.environ.items()
        # the child script pins its own platform, device count and import
        # path; it must not inherit this process's
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS", "PYTHONPATH")
    }


def run_exact_topology(workdir: str, nprocs: int, timeout: int = 420) -> str:
    """Run the topology child at ``nprocs`` (1 or 2); returns the npz path
    process 0 wrote. Raises on any nonzero child or missing OK marker."""
    out = os.path.join(workdir, f"exact_p{nprocs}.npz")
    script = os.path.join(workdir, f"child_p{nprocs}.py")
    coord = f"127.0.0.1:{free_port()}"
    with open(script, "w") as f:
        f.write(
            CHILD_EXACT.replace("__REPO__", repr(REPO)).replace(
                "__COORD__", repr(coord)
            )
        )
    procs = [
        subprocess.Popen(
            [sys.executable, script, str(nprocs), str(rank), out],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env=child_env(), text=True,
        )
        for rank in range(nprocs)
    ]
    outs = [p.communicate(timeout=timeout)[0] for p in procs]
    for rank, (p, text) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise RuntimeError(
                f"topology child nprocs={nprocs} rank {rank} rc="
                f"{p.returncode}:\n{text}"
            )
        for marker in (f"proc {rank} EXACT_OK",
                       f"proc {rank} ZERO_TRANSFER_DISPATCH_OK"):
            if marker not in text:
                raise RuntimeError(
                    f"topology child nprocs={nprocs} rank {rank} missing "
                    f"{marker!r}:\n{text}"
                )
    return out


def compare_npz(a_path: str, b_path: str) -> dict:
    """Byte-compare two topology payloads: same keys, same dtypes, same
    bits. Returns counts + any mismatching key names."""
    mismatches = []
    with np.load(a_path) as a, np.load(b_path) as b:
        if sorted(a.files) != sorted(b.files):
            mismatches.append(
                f"key sets differ: {sorted(a.files)} vs {sorted(b.files)}"
            )
            keys = sorted(set(a.files) & set(b.files))
        else:
            keys = sorted(a.files)
        state_leaves = sum(1 for k in keys if k.startswith("state_"))
        for k in keys:
            if a[k].dtype != b[k].dtype:
                mismatches.append(f"{k}: dtype {a[k].dtype} vs {b[k].dtype}")
            elif not np.array_equal(a[k], b[k]):
                mismatches.append(f"{k}: bits differ")
    return {
        "keys_compared": len(keys),
        "state_leaves": state_leaves,
        "mismatches": mismatches,
    }


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


# --------------------------------------------- 1. degenerate exactness (P=1)
class TestMultihostSyncDegenerate:
    """P=1 is a real point of the multihost algebra (L=D, base=0), so the
    whole class runs in-process on the 8-device virtual mesh and tier-1
    pins it without spawning processes."""

    FIELDS = ("obs", "action", "reward", "next_obs", "discount")

    def test_flush_matches_sharded_sync_bitwise(self):
        D, C = 8, 64
        mesh = make_mesh(dp=D, tp=1)
        buf_m, buf_s = ReplayBuffer(C, 3, 1), ReplayBuffer(C, 3, 1)
        ring_m = device_ring_init(C, 3, 1, mesh=mesh)
        ring_s = device_ring_init(C, 3, 1, mesh=mesh)
        sync_m = MultihostRingSync(buf_m, mesh, chunk_cap=32)
        sync_s = ShardedDeviceRingSync(buf_s, mesh, chunk_cap=32)
        # uneven fills + a wrap: the layouts must stay identical throughout
        for n, seed in ((41, 1), (17, 2), (30, 3)):
            _fill(buf_m, n, seed=seed)
            _fill(buf_s, n, seed=seed)
            ring_m = sync_m.flush(ring_m)
            ring_s = sync_s.flush(ring_s)
            for f in self.FIELDS + ("size",):
                np.testing.assert_array_equal(
                    np.asarray(getattr(ring_m, f)),
                    np.asarray(getattr(ring_s, f)),
                )

    def test_single_ingest_compile_across_flushes(self):
        """Same recompile budget as the single-process sync: the sentinel's
        ring_ingest == 1 contract holds per process."""
        mesh = make_mesh(dp=8, tp=1)
        buf = ReplayBuffer(64, 3, 1)
        ring = device_ring_init(64, 3, 1, mesh=mesh)
        sync = MultihostRingSync(buf, mesh, chunk_cap=16)
        for seed in range(4):
            _fill(buf, 11, seed=seed)
            ring = sync.flush(ring)
        assert sync.ingest_fn._cache_size() == 1

    def test_gather_snapshot_matches_buffer_snapshot(self, tmp_path):
        """gather_snapshot reproduces the exact ReplayBuffer.snapshot npz
        layout — rows in global slot order plus pos/size — so multi-host
        checkpoints restore onto ANY topology."""
        D, C = 8, 64
        mesh = make_mesh(dp=D, tp=1)
        buf = ReplayBuffer(C, 3, 1)
        ring = device_ring_init(C, 3, 1, mesh=mesh)
        sync = MultihostRingSync(buf, mesh, chunk_cap=32)
        _fill(buf, 50, seed=4)
        _fill(buf, 30, seed=5)  # wraps: pos=16, size=C
        ring = sync.flush(ring)
        snap = sync.gather_snapshot(ring)
        path = str(tmp_path / "replay.npz")
        buf.snapshot(path)
        with np.load(path) as z:
            for k in self.FIELDS + ("pos", "size"):
                np.testing.assert_array_equal(snap[k], z[k])
                assert snap[k].dtype == z[k].dtype, k

    def test_deal_snapshot_roundtrip(self, tmp_path):
        """deal → flush → gather is the identity on snapshot bytes: the
        elastic-resume guarantee, in-process."""
        D, C = 8, 64
        mesh = make_mesh(dp=D, tp=1)
        src = ReplayBuffer(C, 3, 1)
        _fill(src, 80, seed=6)  # wrapped source
        path = str(tmp_path / "replay.npz")
        src.snapshot(path)

        buf = ReplayBuffer(C, 3, 1)
        sync = MultihostRingSync(buf, mesh, chunk_cap=32)
        with np.load(path) as z:
            n = sync.deal_snapshot(z)
        assert n == C
        assert buf.total_added == src.total_added
        ring = sync.flush(device_ring_init(C, 3, 1, mesh=mesh))
        snap = sync.gather_snapshot(ring)
        with np.load(path) as z:
            for k in self.FIELDS + ("pos", "size"):
                np.testing.assert_array_equal(snap[k], z[k])


# ------------------------------------------------- 2. layout algebra (P>1)
def _bare_sync(P_, L_, p, buf=None):
    """A MultihostRingSync shell for process ``p`` of a P_×L_ topology —
    the host-side layout algebra (_gapless_total, deal_snapshot) needs no
    mesh, so P>1 is testable in one process."""
    s = MultihostRingSync.__new__(MultihostRingSync)
    s.n_processes = P_
    s.local_shards = L_
    s.n_shards = P_ * L_
    s.shard_lo = p * L_
    s._buffer = buf
    s.host_capacity = buf.capacity if buf is not None else 0
    s.capacity = s.host_capacity * P_
    s.local_capacity = s.capacity // s.n_shards if buf is not None else 0
    s._synced = 0
    return s


class TestMultihostLayoutAlgebra:
    @pytest.mark.parametrize("P_,L_", [(2, 4), (4, 2), (2, 2), (3, 2)])
    def test_gapless_total_matches_brute_force(self, P_, L_):
        """Host p's k-th local write is global write (k//L)*D + p*L + (k%L);
        the agreed fill count must be the longest fully-landed prefix of
        that interleaved stream — no more (a gap would publish a row some
        host never wrote), no less."""
        D = P_ * L_
        sync = _bare_sync(P_, L_, 0)
        rng = np.random.default_rng(0)
        for _ in range(50):
            totals = rng.integers(0, 40, size=P_)
            landed = set()
            for p in range(P_):
                for k in range(int(totals[p])):
                    landed.add((k // L_) * D + p * L_ + (k % L_))
            T = 0
            while T in landed:
                T += 1
            assert sync._gapless_total(totals) == T, totals

    @pytest.mark.parametrize("P_,L_,T", [(2, 4, 0), (2, 4, 5), (2, 4, 13),
                                         (2, 4, 32), (4, 2, 29), (2, 2, 39)])
    def test_deal_partitions_global_rows_exactly(self, P_, L_, T):
        """deal_snapshot on each process of a P_×L_ topology: local slots
        hold exactly the global slots the striping assigns, the per-host
        shares are a disjoint cover of the snapshot rows, and the
        reconstructed lifetime cursors re-derive the same global T."""
        D = P_ * L_
        C = 32
        host_cap = C // P_
        size = min(T, C)
        pos = T % C
        data = {
            "size": np.asarray(size), "pos": np.asarray(pos),
            "obs": np.arange(size, dtype=np.float32).reshape(size, 1),
            "action": np.zeros((size, 1), np.float32),
            "reward": np.zeros(size, np.float32),
            "next_obs": np.zeros((size, 1), np.float32),
            "discount": np.zeros(size, np.float32),
        }
        # Wrapped snapshots reconstruct T as pos+capacity (same rule as
        # ReplayBuffer.restore) — recompute the T the deal actually sees.
        T_seen = pos + C if size == C else size
        covered = []
        totals = []
        for p in range(P_):
            buf = ReplayBuffer(host_cap, 1, 1)
            sync = _bare_sync(P_, L_, p, buf)
            n = sync.deal_snapshot(data)
            totals.append(buf.total_added)
            base = p * L_
            m = np.arange(n)
            j = (m // L_) * D + base + (m % L_)
            # every dealt global slot must be a snapshot row
            assert (j < size).all()
            np.testing.assert_array_equal(buf.obs[:n, 0], j.astype(np.float32))
            covered.append(j)
            t_p = (T_seen // D) * L_ + int(np.clip(T_seen % D - base, 0, L_))
            assert buf.total_added == t_p
        allj = np.concatenate(covered) if covered else np.array([], np.int64)
        assert len(allj) == len(set(allj.tolist()))  # disjoint
        assert len(allj) == size                     # ...and a full cover
        # the reconstructed cursors agree on the same global fill count
        sync0 = _bare_sync(P_, L_, 0, ReplayBuffer(host_cap, 1, 1))
        assert min(sync0._gapless_total(np.asarray(totals)), C) == size


# ------------------------------------- 3. topology bit-exactness (tentpole)
@pytest.mark.slow
def test_two_process_mesh_bit_exact_vs_single_process_oracle(tmp_path):
    """THE tentpole contract: the 2-process × 4-device global mesh — real
    jax.distributed init, per-host ingest into local shards only, multiple
    dispatches — is BIT-exact vs the 8-device single-process run: every
    TrainState leaf (params, targets, both Adam moment sets), the
    assembled ring, the device-PER tree sidecar, det_pmean reductions,
    fold_in(global shard index) draws, and the loss metrics. Each
    topology also proves the zero-transfer steady state (the child
    dispatches once under no_transfers)."""
    single = run_exact_topology(str(tmp_path), 1)
    multi = run_exact_topology(str(tmp_path), 2)
    res = compare_npz(single, multi)
    assert res["mismatches"] == []
    assert res["state_leaves"] > 0
    assert res["keys_compared"] > res["state_leaves"]  # ring/tree/draws too


# --------------------------------------------------- 4. elastic resume (CLI)
def _cli_args(d: str, steps: int, resume: bool) -> list:
    args = [
        sys.executable, "train.py", "--env", "pendulum",
        "--hidden-sizes", "16,16", "--n-atoms", "11",
        "--total-steps", str(steps), "--warmup", "24", "--bsize", "8",
        "--rmsize", "256", "--dp", "8", "--replay-placement", "device",
        "--num-envs", "2", "--eval-interval", "100000",
        "--eval-episodes", "1", "--checkpoint-interval", "12",
        "--snapshot-replay", "--no-concurrent-eval",
        "--log-dir", d, "--seed", "3",
    ]
    if resume:
        args.append("--resume")
    return args


def _run_leg(d: str, steps: int, nprocs: int, resume: bool) -> list:
    env = child_env()
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={8 // nprocs}"
    )
    env["PYTHONPATH"] = REPO
    args = _cli_args(d, steps, resume)
    if nprocs > 1:
        coord = f"localhost:{free_port()}"
        procs = [
            subprocess.Popen(
                args + ["--coordinator", coord, "--num-processes",
                        str(nprocs), "--process-id", str(rank)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                env=env, text=True, cwd=REPO,
            )
            for rank in range(nprocs)
        ]
    else:
        procs = [
            subprocess.Popen(
                args, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                env=env, text=True, cwd=REPO,
            )
        ]
    outs = [p.communicate(timeout=420)[0] for p in procs]
    for rank, (p, text) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"leg nprocs={nprocs} rank {rank}:\n{text}"
        assert "done:" in text
    return outs


@pytest.mark.slow
def test_elastic_resume_across_topology_changes(tmp_path):
    """2×4 → 1×8 → 2×4 through the real CLI: each leg resumes the previous
    topology's checkpoint (Orbax state re-sharded onto the new mesh,
    replay snapshot dealt/restored, device-PER sidecar reloaded), and the
    sidecar written by the 2-process collective gather byte-round-trips
    through a 1×8 restore."""
    from d4pg_tpu.replay.device_per import DevicePerSync

    d = str(tmp_path / "run")
    _run_leg(d, 24, nprocs=2, resume=False)
    per_path = os.path.join(d, "checkpoints", "device_per.npz")
    replay_path = os.path.join(d, "checkpoints", "replay.npz")
    assert os.path.exists(per_path) and os.path.exists(replay_path)

    # Cross-topology sidecar byte-compare: bytes written by the 2×4
    # collective snapshot, restored onto THIS process's 1×8 mesh, must
    # snapshot back identically (restore_host/snapshot_host inverse pair).
    with np.load(per_path) as z:
        pa24, mp24 = z["priorities_alpha"], float(z["max_priority"])
    per = DevicePerSync(256, alpha=0.6, mesh=make_mesh(dp=8, tp=1))
    per.restore_host(pa24, mp24)
    pa18, mp18 = per.snapshot_host()
    assert pa18.tobytes() == pa24.tobytes()
    assert mp18 == mp24
    # ...and the replay snapshot restores/re-snapshots byte-identically
    # through the single-process buffer (the 1×8 leg's restore path).
    buf = ReplayBuffer(256, 3, 1)
    n = buf.restore(replay_path)
    assert n > 0
    resnap = str(tmp_path / "resnap.npz")
    buf.snapshot(resnap)
    with np.load(replay_path) as a, np.load(resnap) as b:
        for k in a.files:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)

    # --total-steps counts THIS invocation's grad steps: leg 2 runs
    # 24 -> 36 on 1x8, leg 3 runs 36 -> 48 back on 2x4.
    out_18 = _run_leg(d, 12, nprocs=1, resume=True)
    assert "resumed from step 24" in out_18[0]
    assert "restored replay snapshot" in out_18[0]
    assert "restored device-PER priorities" in out_18[0]

    out_24 = _run_leg(d, 12, nprocs=2, resume=True)
    for text in out_24:
        assert "resumed from step 36" in text
        assert "restored replay snapshot" in text
        assert "restored device-PER priorities" in text
    # bit-identical completion on both processes of the final leg: the
    # mesh is one SPMD program, so every MODEL metric must agree exactly
    # (the *_per_sec rates are per-process wall-clock and legitimately
    # differ)
    import ast

    done = [
        ast.literal_eval(
            next(
                ln for ln in reversed(t.splitlines())
                if ln.startswith("done:")
            )[len("done:"):].strip()
        )
        for t in out_24
    ]
    model = [
        {k: v for k, v in d.items() if not k.endswith("_per_sec")}
        for d in done
    ]
    assert model[0] == model[1]
