"""Chip-independent megastep-vs-host data-plane microbench (tier-1-safe).

The ROADMAP-item-1 claim — the device-resident ring + fused megastep
removes the per-grad-step H2D batch upload and D2H priority fetch of the
host path — must stay checkable without a chip. Two halves:

- **transfer bytes** are counted from the exact host arrays each loop
  stages/fetches (not estimated), so the before/after is chip-independent
  by construction: host path = full batch fields up + priorities down per
  dispatch; hybrid = [K, B] int32 indices + f32 IS weights up, [K, B]
  priorities down; device = ZERO;
- **steps/s** runs whatever backend is available (CPU interpret here) —
  on CPU the megastep still wins because the host path pays sampling +
  staging per dispatch on the same cores doing the math, but the number
  that matters is the on-chip one (recipe below).

Variants, all at the flagship learner shape (obs 17, act 6, 3×256 MLPs,
C51, batch 256, K=32 — the ``--steps-per-dispatch 32`` configuration the
host-pipeline bench pins):

- ``host_block_k32``   — the PR-2 host data plane (``sample_block`` +
  staged H2D batch), via ``bench.bench_host_pipeline``;
- ``hybrid_k32``       — host PER indices, on-device gather
  (``bench.bench_megastep(placement="hybrid")``) — the LEGACY PER
  placement since ISSUE 14, kept as the host-tree oracle row;
- ``device_k32``       — uniform in-kernel draw, zero transfers
  (``bench.bench_megastep(placement="device")``);
- ``device_per_k32``   — DEVICE-RESIDENT PER (ISSUE 14): the priority
  segment tree in HBM, descent + IS weights + write-back inside the
  fused megastep (``bench.bench_megastep(placement="device",
  per=True)``) — prioritized replay at the same ZERO transfer bytes
  per grad step as the uniform row, the finish line of the raw-speed
  arc (vs hybrid's [K, B] round-trip and host's full-batch traffic).

Run as a script to (re)generate ``benchmarks/megastep_microbench.json``:

    JAX_PLATFORMS=cpu python benchmarks/megastep_microbench.py

On-chip recipe: run the same script WITHOUT ``JAX_PLATFORMS=cpu`` on the
TPU VM, or take the sweep view —
``python benchmarks/mfu_sweep.py`` now includes the megastep points at
the mlp256/B≥512 shapes where ``mfu_sweep_results.json`` measured the
9% → 53% MFU headroom this data plane exists to reach. The training-run
form of the same claim: ``python train.py --replay-placement device
--steps-per-dispatch 32 --debug-guards`` (the transfer guard enforces the
zero-transfer budget at the dispatch site).

``tests/test_megastep_microbench.py`` runs the same function at smaller
shapes every tier-1 pass and pins the committed artifact's schema +
headline (megastep ≥ host steps/s, strictly lower transfer bytes).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_microbench(
    out_path: str | None = None,
    *,
    batch: int = 256,
    k: int = 32,
    hidden: int = 256,
    rows: int = 65_536,
    steps: int = 8,
    repeats: int = 2,
) -> dict:
    """Time host-block vs hybrid vs device paths at one (batch, k, model)
    shape; count per-grad-step transfer bytes for each.

    Same min-of-interleaved-repeats protocol as the host-pipeline
    microbench: the shared few-core bench host shows bursty interference,
    and min-of-repeats reads the machine's floor through it (all repeats
    kept under ``steps_per_sec_repeats``). Returns the artifact dict;
    writes it to ``out_path`` when given.
    """
    import jax

    from bench import bench_host_pipeline, bench_megastep

    out = {
        "metric": "megastep_microbench",
        "backend": jax.default_backend(),
        "batch": batch,
        "k": k,
        "hidden": hidden,
        "rows": rows,
        "steps": steps,
        "repeats": repeats,
        "on_chip_recipe": (
            "unset JAX_PLATFORMS and rerun on the TPU VM; sweep view: "
            "python benchmarks/mfu_sweep.py (megastep points); training "
            "form: python train.py --replay-placement device "
            "--steps-per-dispatch 32 --debug-guards"
        ),
    }
    variants = [
        (
            "host_block_k32",
            lambda: bench_host_pipeline(
                prefetch=False, sampler="block", steps=steps, batch=batch,
                k=k, hidden=hidden, rows=rows, compute_dtype="float32",
            ),
        ),
        (
            "hybrid_k32",
            lambda: bench_megastep(
                placement="hybrid", steps=steps, batch=batch, k=k,
                hidden=hidden, rows=rows,
            ),
        ),
        (
            "device_k32",
            lambda: bench_megastep(
                placement="device", steps=steps, batch=batch, k=k,
                hidden=hidden, rows=rows,
            ),
        ),
        (
            "device_per_k32",
            lambda: bench_megastep(
                placement="device", per=True, steps=steps, batch=batch,
                k=k, hidden=hidden, rows=rows,
            ),
        ),
    ]
    for _ in range(repeats):
        for name, fn in variants:
            r = fn()
            prev = out.get(name)
            r["steps_per_sec_repeats"] = (
                prev["steps_per_sec_repeats"] if prev else []
            ) + [round(r["steps_per_sec"], 1)]
            if prev is None or r["steps_per_sec"] > prev["steps_per_sec"]:
                out[name] = r
            else:
                prev["steps_per_sec_repeats"] = r["steps_per_sec_repeats"]
    host = out["host_block_k32"]
    for name in ("hybrid_k32", "device_k32", "device_per_k32"):
        if host["steps_per_sec"] > 0:
            out[f"{name}_steps_ratio"] = round(
                out[name]["steps_per_sec"] / host["steps_per_sec"], 4
            )
        if host["transfer_bytes_per_grad_step"] > 0:
            out[f"{name}_transfer_ratio"] = round(
                out[name]["transfer_bytes_per_grad_step"]
                / host["transfer_bytes_per_grad_step"],
                6,
            )
    if out_path:
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, out_path)
    return out


if __name__ == "__main__":
    artifact = os.path.join(
        os.path.dirname(__file__), "megastep_microbench.json"
    )
    print(json.dumps(run_microbench(artifact)))
