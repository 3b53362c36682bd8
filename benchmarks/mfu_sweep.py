"""MFU sweep: where does the framework's compute utilization land when the
shapes allow it? (VERDICT round-3 next #4)

The flagship bench's single-digit MFU is a property of the WORKLOAD (3x256
MLPs, batch 256: arithmetic intensity ~60 FLOP/B, far under the ~240 FLOP/B
ridge of a v5e) — this script provides the contrast points that make that
claim checkable rather than asserted:

1. batch sweep 256 -> 4096 on the flagship MLP config — MFU and HBM
   utilization per point (bigger batch raises intensity: the params/
   optimizer traffic amortizes over more rows);
2. the conv (pixel) critic config at 48x48x2 — convolutions carry far more
   FLOPs per byte than the tiny MLPs;
3. a "wide" MLP variant (1024-wide hiddens, batch 4096) — MXU-saturating
   matmul shapes with the same train-step machinery;
4. the MEGASTEP configuration (``--replay-placement device``): the fused
   device-resident-replay training loop (``runtime/megastep.py``) at the
   mlp256 / B >= 512 shapes where points 1-3 measured the 9% -> 53% MFU
   headroom — the data plane that exists to close exactly that gap, with
   ``transfer_bytes_per_grad_step`` 0 by construction and ``mfu`` from the
   same single-step XLA cost model as every other row;
5. the SHARDED megastep (``--replay-placement device --dp N``): the same
   loop spanning the dp mesh (striped sharded ring, shard-local draws,
   deterministic grad mean — ROADMAP item 2) at the wide shapes where tp/
   stack sharding is load-bearing, transfer bytes still 0;
6. the DEVICE-PER megastep (``--replay-placement device`` with PER on —
   ISSUE 14): the priority segment tree in HBM, so the wide-shape rows
   are finally reachable by runs using the sampling scheme the paper's
   D4PG actually uses (prioritized replay, Horgan et al. 2018) — with
   ``transfer_bytes_per_grad_step`` still 0 by construction;
7. the LARGE-BATCH RECIPE shape (ISSUE 16): device-PER with the fused
   descent-in-scan Pallas tier, bf16, at the exact B/K that
   ``train.py --p-replay --batch-scale 8 --fused-descent`` dispatches —
   the REAL prioritized training shape living at the MXU-filling point
   the sweep proved out (see docs/data_plane.md "Large-batch recipe").

Points 1-3 run through ``bench.bench_tpu`` (device-resident pool, fused
K-step scan); points 4-7 through ``bench.bench_megastep`` (device ring +
in-kernel draw; ``dp=`` for the sharded rows) — the SAME pinned timing
protocol (pipelined dispatches, donated state, value-transfer sync),
parameterized rather than copied, so the rows can never drift apart.

Run on the real chip:        python benchmarks/mfu_sweep.py
CPU-interpret megastep rows: JAX_PLATFORMS=cpu \
                             python benchmarks/mfu_sweep.py --megastep-only
CPU sharded rows:            JAX_PLATFORMS=cpu \
                             python benchmarks/mfu_sweep.py --sharded-only
CPU device-PER rows:         JAX_PLATFORMS=cpu \
                             python benchmarks/mfu_sweep.py --device-per-only
CPU large-batch row:         JAX_PLATFORMS=cpu \
                             python benchmarks/mfu_sweep.py --large-batch-only
(--megastep-only / --sharded-only / --device-per-only /
--large-batch-only keep the committed on-chip rows and replace only their
own row family, each
tagged with the backend that produced it; rerun WITHOUT the flags on the
TPU VM to refresh everything on-chip. ``--sharded`` / ``--device-per`` /
``--large-batch`` add their rows to a full refresh.)

Prints one JSON line per point and writes benchmarks/mfu_sweep_results.json.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import bench_megastep, bench_tpu  # noqa: E402

RESULTS = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "mfu_sweep_results.json"
)


def bench_point(label: str, **kwargs) -> dict:
    out = bench_tpu("bfloat16", **kwargs)
    row = {
        "bench": "mfu_sweep",
        "config": label,
        "batch": kwargs.get("batch", 256),
        "compute_dtype": "bfloat16",
        "steps_per_sec": round(out["steps_per_sec"], 1),
    }
    for k in ("flops_per_grad_step", "bytes_per_grad_step"):
        if k in out:
            row[k] = round(out[k])
    if "flops_per_grad_step" in out and out.get("bytes_per_grad_step"):
        row["intensity_flop_per_byte"] = round(
            out["flops_per_grad_step"] / out["bytes_per_grad_step"], 1
        )
    for k, nd in (
        ("achieved_tflops", 3),
        ("mfu", 5),
        ("achieved_gbps", 1),
        ("xla_bytes_util", 4),
    ):
        if k in out:
            row[k] = round(out[k], nd)
    return row


def megastep_point(batch: int, *, k_steps: int = 32, steps: int = 6) -> dict:
    """One megastep row at the flagship mlp256 model: device placement,
    in-kernel uniform draw, zero per-grad-step transfers. Tagged with the
    backend so CPU-interpret placeholders are never mistaken for chip
    numbers."""
    import jax

    out = bench_megastep(
        placement="device", batch=batch, k=k_steps, steps=steps,
    )
    row = {
        "bench": "mfu_sweep",
        "config": "megastep_mlp256",
        "batch": batch,
        "compute_dtype": "float32",
        "backend": jax.default_backend(),
        "steps_per_sec": round(out["steps_per_sec"], 1),
        "transfer_bytes_per_grad_step": out["transfer_bytes_per_grad_step"],
    }
    for k, nd in (
        ("flops_per_grad_step", 0),
        ("achieved_tflops", 3),
        ("mfu", 5),
    ):
        if k in out:
            row[k] = round(out[k], nd) if nd else round(out[k])
    if jax.default_backend() == "cpu":
        row["note"] = (
            "CPU-interpret placeholder (not a chip row); rerun "
            "benchmarks/mfu_sweep.py on-chip for the real MFU"
        )
    return row


def megastep_rows() -> list[dict]:
    rows = []
    # B >= 512 is where points 1-3 measured the MFU headroom opening up
    # (0.092 -> 0.232 from batch alone); 256 anchors the flagship shape.
    for batch in (256, 512, 1024):
        rows.append(megastep_point(batch))
        print(json.dumps(rows[-1]), flush=True)
    return rows


def sharded_point(batch: int, dp: int, *, hidden: int = 256,
                  k_steps: int = 32, steps: int = 4) -> dict:
    """One SHARDED megastep row (runtime/megastep.py:
    make_megastep_uniform_sharded): dp-sharded ring + shard-local draws,
    transfer bytes 0 by construction. Wide-shape points because that is
    where sharding is load-bearing (53% MFU only at MXU-friendly widths,
    mfu_sweep_results.json) — on CPU the steps/s is a placeholder like
    every other cpu-tagged row; the zero-transfer column is the
    chip-independent half."""
    import jax

    if jax.device_count() < dp:
        raise RuntimeError(
            f"sharded_point(dp={dp}) needs {dp} devices, have "
            f"{jax.device_count()} — on CPU run via the __main__ entry "
            "(it configures the virtual mesh) or set "
            "--xla_force_host_platform_device_count"
        )
    out = bench_megastep(
        placement="device", batch=batch, k=k_steps, steps=steps,
        hidden=hidden, dp=dp,
    )
    row = {
        "bench": "mfu_sweep",
        "config": f"sharded_megastep_mlp{hidden}",
        "batch": batch,
        "dp": dp,
        "compute_dtype": "float32",
        "backend": jax.default_backend(),
        "steps_per_sec": round(out["steps_per_sec"], 1),
        "transfer_bytes_per_grad_step": out["transfer_bytes_per_grad_step"],
    }
    if jax.default_backend() == "cpu":
        row["note"] = (
            "CPU virtual-mesh placeholder (not a chip row); rerun "
            "benchmarks/mfu_sweep.py --sharded on a multi-chip VM for "
            "real scaling"
        )
    return row


def sharded_rows() -> list[dict]:
    rows = []
    # The wide shapes the sharding exists for: flagship width at large
    # batch, then the MXU width (hidden 1024 shards 128-wide per tp rank
    # at dp=8... dp-only mesh: batch splits 8-way, ring splits 8-way).
    for batch, hidden in ((512, 256), (1024, 512)):
        rows.append(sharded_point(batch, dp=8, hidden=hidden))
        print(json.dumps(rows[-1]), flush=True)
    return rows


def device_per_point(batch: int, dp: int | None = None, *, hidden: int = 256,
                     k_steps: int = 32, steps: int = 6) -> dict:
    """One DEVICE-RESIDENT PER megastep row (ISSUE 14): in-kernel
    stratified descent + IS weights + write-back, zero per-grad-step
    transfers WITH prioritized replay on. Wide-shape points because this
    is what makes the mfu headroom rows reachable by real PER runs; dp
    spans the virtual mesh with shard-local subtrees."""
    import jax

    if dp and jax.device_count() < dp:
        raise RuntimeError(
            f"device_per_point(dp={dp}) needs {dp} devices, have "
            f"{jax.device_count()} — on CPU run via the __main__ entry "
            "(it configures the virtual mesh)"
        )
    out = bench_megastep(
        placement="device", per=True, batch=batch, k=k_steps, steps=steps,
        hidden=hidden, dp=dp,
    )
    row = {
        "bench": "mfu_sweep",
        "config": f"device_per_megastep_mlp{hidden}",
        "batch": batch,
        "dp": int(dp or 1),
        "compute_dtype": "float32",
        "backend": jax.default_backend(),
        "steps_per_sec": round(out["steps_per_sec"], 1),
        "transfer_bytes_per_grad_step": out["transfer_bytes_per_grad_step"],
    }
    for k, nd in (
        ("flops_per_grad_step", 0),
        ("achieved_tflops", 3),
        ("mfu", 5),
    ):
        if k in out:
            row[k] = round(out[k], nd) if nd else round(out[k])
    if jax.default_backend() == "cpu":
        row["note"] = (
            "CPU-interpret placeholder (not a chip row); rerun "
            "benchmarks/mfu_sweep.py --device-per on-chip for the real MFU"
        )
    return row


def device_per_rows() -> list[dict]:
    rows = []
    # The flagship shape, the headroom batch, and one mesh-spanning row.
    for batch, dp in ((256, None), (1024, None), (512, 8)):
        rows.append(device_per_point(batch, dp))
        print(json.dumps(rows[-1]), flush=True)
    return rows


def large_batch_point(all_rows: list[dict], *, scale: int = 8,
                      steps: int = 3) -> dict:
    """The ISSUE 16 flagship large-batch recipe row: the REAL
    ``--p-replay`` training shape — device-resident PER with the FUSED
    descent-in-scan tier (descent + loss as ONE Pallas program per scan
    step), bf16 compute, at the ``--batch-scale`` recipe's B/K (B=256·S,
    K=32/S, the exact shape ``train.py --batch-scale S`` dispatches).

    Three claims ride on this row, split by what a CPU can measure:

    * ``transfer_bytes_per_grad_step`` — 0 by construction, measured
      here and chip-independent (schema_check refuses nonzero);
    * the CPU-proxy ratios — this row vs the B=256 flagship recipe
      baseline, SAME fused data plane, measured in this run:
      ``transitions_per_sec_ratio`` is rows-consumed/s (steps/s ×
      batch), the amortization the recipe exists for;
    * ``mfu_onchip_proxy`` — the ≥2×-flagship-MFU claim, anchored to the
      committed ON-CHIP mlp256 rows at the same (width, batch, dtype)
      matmul shape (the model cost is the shared
      ``bench.model_flops_per_step`` oracle, so the proxy and a real
      on-chip rerun of this row cannot drift apart), plus ``recipe`` —
      the ready-to-run command for the on-chip number.
    """
    import jax

    base_batch, base_k = 256, 32
    batch, k = base_batch * scale, max(1, base_k // scale)
    fused = dict(
        placement="device", per=True, compute_dtype="bfloat16",
        projection_backend="pallas_fused", fused_descent=True,
    )
    base = bench_megastep(batch=base_batch, k=base_k, steps=steps, **fused)
    out = bench_megastep(batch=batch, k=k, steps=steps, **fused)
    row = {
        "bench": "mfu_sweep",
        "config": "large_batch_per_mlp256",
        "batch": batch,
        "batch_scale": scale,
        "k": k,
        "compute_dtype": "bfloat16",
        "backend": jax.default_backend(),
        "steps_per_sec": round(out["steps_per_sec"], 1),
        "baseline_steps_per_sec": round(base["steps_per_sec"], 1),
        "steps_per_sec_ratio": round(
            out["steps_per_sec"] / base["steps_per_sec"], 4
        ),
        "transitions_per_sec_ratio": round(
            out["steps_per_sec"] * batch
            / (base["steps_per_sec"] * base_batch), 2
        ),
        "transfer_bytes_per_grad_step": out["transfer_bytes_per_grad_step"],
    }
    for key, nd in (
        ("flops_per_grad_step", 0),
        ("achieved_tflops", 3),
        ("mfu", 5),
    ):
        if key in out:
            row[key] = round(out[key], nd) if nd else round(out[key])

    def _mlp256_mfu(b):
        for r in all_rows:
            if (r.get("config") == "mlp256" and r.get("batch") == b
                    and r.get("mfu")):
                return r["mfu"]
        return None

    flagship_mfu, shape_mfu = _mlp256_mfu(base_batch), _mlp256_mfu(batch)
    if flagship_mfu and shape_mfu:
        row["mfu_onchip_proxy"] = {
            "flagship_mfu": flagship_mfu,
            "shape_mfu": shape_mfu,
            "ratio_vs_flagship": round(shape_mfu / flagship_mfu, 2),
            "note": (
                f"committed on-chip mlp256 rows at B={base_batch} vs "
                f"B={batch}, bf16 — the same matmul shapes this recipe "
                "dispatches, costed by the same single-step oracle"
            ),
        }
    row["recipe"] = (
        "python train.py --env pendulum --p-replay "
        "--replay-placement device --device-tree-backend pallas "
        "--projection pallas_fused --compute-dtype bfloat16 "
        f"--steps-per-dispatch {base_k} --batch-scale {scale} "
        "--fused-descent --ingest-prefetch"
    )
    if jax.default_backend() == "cpu":
        row["note"] = (
            "CPU-interpret placeholder steps/s (not a chip row); the "
            "ratios + zero-transfer column are measured here, the MFU "
            "claim is the committed on-chip proxy — rerun "
            "benchmarks/mfu_sweep.py --large-batch-only on-chip for the "
            "direct number"
        )
    return row


def large_batch_rows(all_rows: list[dict]) -> list[dict]:
    rows = [large_batch_point(all_rows)]
    print(json.dumps(rows[-1]), flush=True)
    return rows


def _replace_family(rows: list[dict], prefix: str, new_rows: list[dict]) -> list[dict]:
    """Drop rows whose config starts with ``prefix`` and append the fresh
    ones — the committed on-chip rows for every OTHER family survive a
    partial regen (the --megastep-only precedent)."""
    kept = [r for r in rows if not str(r.get("config", "")).startswith(prefix)]
    return kept + new_rows


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if "--sharded-only" in argv:
        with open(RESULTS) as f:
            rows = _replace_family(json.load(f), "sharded_megastep", sharded_rows())
    elif "--large-batch-only" in argv:
        with open(RESULTS) as f:
            committed = json.load(f)
        rows = _replace_family(
            committed, "large_batch", large_batch_rows(committed)
        )
    elif "--device-per-only" in argv:
        with open(RESULTS) as f:
            rows = _replace_family(
                json.load(f), "device_per_megastep", device_per_rows()
            )
    elif "--megastep-only" in argv:
        # Keep the committed on-chip rows and replace only the megastep
        # family — sharded_megastep rows survive too (prefix-disjoint:
        # "megastep" filters on the exact family, not the substring).
        with open(RESULTS) as f:
            rows = [
                r for r in json.load(f)
                if not str(r.get("config", "")).startswith("megastep")
            ]
        rows.extend(megastep_rows())
    else:
        rows = []
        # 1. batch scaling on the flagship MLP
        for batch in (256, 512, 1024, 2048, 4096):
            rows.append(bench_point("mlp256", batch=batch, k_steps=256, measure=8))
            print(json.dumps(rows[-1]), flush=True)
        # 2. conv critic (pixel workload): fewer fused steps — each is ~100x
        #    the MLP's FLOPs; smaller pool so pixel rows fit HBM comfortably
        rows.append(
            bench_point("conv48", batch=256, pixel=True, k_steps=32, measure=4,
                        pool_rows=8_192)
        )
        print(json.dumps(rows[-1]), flush=True)
        # 3. MXU-shaped MLP: 1024-wide, batch 4096
        rows.append(
            bench_point("mlp1024", batch=4096, hidden=1024, k_steps=64, measure=4)
        )
        print(json.dumps(rows[-1]), flush=True)
        # 4. the megastep data plane at the headroom shapes
        rows.extend(megastep_rows())
        # 5. the sharded megastep at the wide shapes (opt-in on a full
        #    refresh: needs a multi-device backend)
        if "--sharded" in argv:
            rows.extend(sharded_rows())
        # 6. device-resident PER at the headroom shapes (opt-in: the dp
        #    row needs a multi-device backend)
        if "--device-per" in argv:
            rows.extend(device_per_rows())
        # 7. the large-batch recipe's REAL --p-replay shape (ISSUE 16):
        #    fused descent-in-scan tier, bf16, B=2048/K=4. Runs after the
        #    mlp256 family so the on-chip MFU proxy cites THIS refresh.
        if "--large-batch" in argv:
            rows.extend(large_batch_rows(rows))
    with open(RESULTS, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"[mfu_sweep] wrote {RESULTS}", file=sys.stderr)


if __name__ == "__main__":
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu" and (
        "--sharded" in sys.argv
        or "--sharded-only" in sys.argv
        or "--device-per" in sys.argv
        or "--device-per-only" in sys.argv
    ):
        # CPU virtual mesh for the sharded rows (before any jax backend
        # init — bench.py imports jax lazily inside its functions).
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    main()
