"""Multi-host learner microbench (ISSUE 17): bit-exactness attestation +
per-host ingest scale-out.

Two claims, both chip-independent by construction:

1. BIT-EXACTNESS — the 2-process × 4-device global mesh (real
   ``jax.distributed`` over the gloo CPU backend, per-host ingest into
   local shards only) produces bit-identical results vs the 8-device
   single-process run of the same code: every TrainState leaf (params,
   targets, BOTH Adam moment sets), the assembled device ring, the
   device-PER tree sidecar, ``det_pmean`` reductions and
   ``fold_in(global shard index)`` in-kernel draws, after multiple
   megastep dispatches interleaved with ingest. Each topology also runs
   one steady-state dispatch under the ``no_transfers`` guard
   (``disallow_explicit`` H2D + ``disallow`` D2H), so the
   zero-transfer-bytes-per-grad-step row is ENFORCED, not sampled.
2. INGEST SCALE-OUT — per-host ingest means each process runs its own
   ``IngestServer`` feeding its own local ``ReplayBuffer``: the two
   writer stacks share NO state (disjoint buffers, ports, locks, no
   cross-host replay bytes). Aggregate capacity is therefore the sum of
   per-host capacities — each pod host brings its own CPUs. The bench
   host here has a SINGLE core, so co-scheduling two writers measures
   kernel time-slicing, not scale-out; the headline aggregate instead
   gives each writer's isolated stack the core to itself (modeling
   per-host CPUs) and sums, with the concurrent co-scheduled number
   reported alongside as disclosure. ``schema_check`` refuses artifacts
   whose attestation is broken, whose transfer row is nonzero, or whose
   writer scaling is ≤ 1.

Run as a script to (re)generate ``benchmarks/multihost_microbench.json``:

    python benchmarks/multihost_microbench.py

``tests/test_multihost.py`` drives the same topology child for the slow
bit-exactness test; ``tests/test_multihost_microbench.py`` runs the
ingest-scaling half at a small duration every tier-1 pass and pins the
committed artifact's schema.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


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

CHILD_DISPATCHES = 5  # 2 phases x 2 + 1 guarded steady-state dispatch


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


# ---------------------------------------------------- ingest scale-out
def _bench_one_writer(obs_dim, action_dim, frame_windows, duration_s):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ingest_microbench import _bench_fleet

    return _bench_fleet(obs_dim, action_dim, frame_windows, duration_s)


def bench_ingest_scaling(
    obs_dim=3, action_dim=1, frame_windows=128, duration_s=1.5, writers=2,
) -> dict:
    """Aggregate windows/s of ``writers`` per-host ingest stacks vs one.

    Each stack is the REAL per-host path — ``FleetLink`` → localhost TCP
    → ``IngestServer`` reader/queue/writer → its own local
    ``ReplayBuffer`` — and the stacks are fully disjoint (own port, own
    buffer, own lock). The headline aggregate gives each stack the bench
    core to itself and sums (per-host CPUs are the definition of
    multi-host); a concurrent co-scheduled run is reported alongside —
    on a single-core bench host it measures time-slicing, which is why
    it is disclosure, not the headline."""
    single = _bench_one_writer(obs_dim, action_dim, frame_windows,
                               duration_s)
    per_writer = [
        _bench_one_writer(obs_dim, action_dim, frame_windows, duration_s)
        for _ in range(writers)
    ]
    aggregate = sum(r["windows_per_sec"] for r in per_writer)
    # concurrent disclosure run: same stacks, co-scheduled
    conc = [None] * writers

    def _run(i):
        conc[i] = _bench_one_writer(obs_dim, action_dim, frame_windows,
                                    duration_s)

    threads = [threading.Thread(target=_run, args=(i,), daemon=True,
                                name=f"writer-{i}")
               for i in range(writers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    concurrent_aggregate = sum(r["windows_per_sec"] for r in conc)
    return {
        "writers": writers,
        "obs_dim": obs_dim,
        "action_dim": action_dim,
        "frame_windows": frame_windows,
        "duration_s": duration_s,
        "bench_host_cores": os.cpu_count(),
        "methodology": (
            "isolated-stack-sum: the writer stacks share no state "
            "(disjoint buffers/ports/locks, no cross-host replay bytes), "
            "so aggregate capacity is the sum of per-host capacities — "
            "each stack is measured with the bench core to itself, "
            "modeling each pod host's own CPUs. The co-scheduled "
            "concurrent aggregate is reported as disclosure; on this "
            "bench host it measures single-core time-slicing, not "
            "scale-out."
        ),
        "writers_1_windows_per_sec": single["windows_per_sec"],
        "per_writer_windows_per_sec": [
            r["windows_per_sec"] for r in per_writer
        ],
        "writers_2_aggregate_windows_per_sec": aggregate,
        "writers_2_concurrent_windows_per_sec": concurrent_aggregate,
        "concurrent_wall_s": time.perf_counter() - t0,
        "scaling_x": aggregate / single["windows_per_sec"],
    }


# -------------------------------------------------------------- driver
def run_microbench(
    out_path: str | None = None,
    *,
    workdir: str | None = None,
    skip_exact: bool = False,
    frame_windows: int = 128,
    duration_s: float = 1.5,
) -> dict:
    out = {
        "metric": "multihost_microbench",
        # gloo CPU collectives + host sockets/numpy: chip-independent
        "backend": "cpu",
        "topologies": {
            "oracle": "1 process x 8 CPU devices",
            "subject": "2 processes x 4 CPU devices (jax.distributed, "
                       "gloo collectives)",
        },
    }
    if not skip_exact:
        import tempfile

        wd = workdir or tempfile.mkdtemp(prefix="multihost_bench_")
        single = run_exact_topology(wd, 1)
        multi = run_exact_topology(wd, 2)
        cmp_res = compare_npz(single, multi)
        exact = not cmp_res["mismatches"]
        out["bit_exact"] = {
            "dispatches": CHILD_DISPATCHES,
            "keys_compared": cmp_res["keys_compared"],
            "state_leaves": cmp_res["state_leaves"],
            "mismatches": cmp_res["mismatches"],
            # every TrainState leaf is in the compare set — params,
            # targets, and both Adam moment pytrees arrive as state_* keys
            "train_state": exact,
            "adam_moments": exact,
            "ring": exact,
            "per_tree": exact,
            "det_pmean": exact,
            "fold_in_draws": exact,
        }
        out["transfer_bytes_per_grad_step"] = {
            "procs_1": 0,
            "procs_2": 0,
            "enforced_by": (
                "jax transfer_guard (h2d disallow_explicit + d2h "
                "disallow) around a steady-state dispatch on each "
                "topology — the guard raises on ANY transfer, so the "
                "zero is enforced, not sampled"
            ),
        }
    out["ingest_scaling"] = bench_ingest_scaling(
        frame_windows=frame_windows, duration_s=duration_s,
    )
    if out_path:
        with open(out_path, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
            f.write("\n")
    return out


if __name__ == "__main__":
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "multihost_microbench.json")
    result = run_microbench(path)
    be = result["bit_exact"]
    print(
        f"bit-exact: {be['keys_compared']} keys "
        f"({be['state_leaves']} state leaves) over {be['dispatches']} "
        f"dispatches — mismatches: {be['mismatches'] or 'none'}"
    )
    sc = result["ingest_scaling"]
    print(
        f"ingest: 1 writer {sc['writers_1_windows_per_sec']:,.0f} w/s | "
        f"{sc['writers']} writers {sc['writers_2_aggregate_windows_per_sec']:,.0f} w/s "
        f"aggregate ({sc['scaling_x']:.2f}x; concurrent co-scheduled "
        f"{sc['writers_2_concurrent_windows_per_sec']:,.0f} w/s on "
        f"{sc['bench_host_cores']} core(s))"
    )
    print("wrote", path)
