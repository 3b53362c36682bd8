"""Chip-independent sharded-megastep microbench (tier-1-safe).

The ROADMAP-item-2 claim — the partition-rule learner spans a dp mesh
with the PR-6 zero-transfer steady state intact, and the capacity it
unlocks (wide REDQ ensembles + MoG heads) actually trains at
sharding-load-bearing shapes — must stay checkable without a chip.
Three rows:

- ``megastep_dp1``   — the single-device uniform megastep (the PR-6
  baseline at this shape), via ``bench.bench_megastep``;
- ``megastep_dp8``   — the SAME shape over the 8-way mesh
  (``bench_megastep(dp=8)``: striped sharded ring, shard-local draws,
  deterministic grad mean). Transfer bytes are counted from the exact
  arrays staged/fetched and must be ZERO per grad step for every device
  row — the zero-transfer budget surviving scale-out is the headline
  here, not CPU steps/s (8 virtual devices time-slice ~2 real cores, so
  the dp8/dp1 ratio on this box measures thread thrash, not the mesh;
  the schema smoke pins the transfer claim and the artifact tags the
  backend);
- ``megastep_per_dp8`` — DEVICE-RESIDENT PER over the same 8-way mesh
  (ISSUE 14: ``bench_megastep(dp=8, per=True)`` — shard-local priority
  subtrees over the striped ring, descent/IS-weights/write-back inside
  the sharded megastep, root combine via the deterministic fixed-order
  reductions). The zero-bytes contract now covers PRIORITIZED replay:
  ``schema_check.check_shard_microbench`` refuses an artifact whose PER
  row pays any per-grad-step transfer;
- ``ensemble_mog_wide`` — the capacity row: an E-wide critic ensemble
  with the mixture-of-Gaussians head at an MXU-friendly width through
  the GSPMD dp×tp step, member stack sharded over "tp" via the rule
  registry's stack_axes declaration (``bench.bench_ensemble_capacity``).

Run as a script to (re)generate ``benchmarks/shard_microbench.json``:

    JAX_PLATFORMS=cpu python benchmarks/shard_microbench.py

On-chip recipe: run the same script
WITHOUT ``JAX_PLATFORMS=cpu`` on a multi-chip TPU VM (the virtual-mesh
flag is only applied for CPU runs); sweep view: ``python
benchmarks/mfu_sweep.py --sharded-only`` adds the sharded points at the
wide shapes while preserving the committed on-chip rows. The training-
run form of the same claim: ``python train.py --replay-placement device
--dp 8 --steps-per-dispatch 32 --debug-guards`` (the transfer guard
enforces the zero-transfer budget at the sharded dispatch site).

``tests/test_shard_microbench.py`` runs the same function at smaller
shapes every tier-1 pass and pins the committed artifact's schema +
headline (zero transfer bytes on both device rows, an ensemble row with
E >= 4 at width >= 512).
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
    k: int = 8,
    hidden: int = 256,
    rows: int = 16_384,
    steps: int = 4,
    dp: int = 8,
    repeats: int = 2,
    ens_hidden: int = 512,
    ens_batch: int = 256,
    ensemble: int = 4,
) -> dict:
    """Time dp=1 vs dp=N sharded megastep at one (batch, k, model) shape
    plus the ensemble/MoG capacity row; count per-grad-step transfer
    bytes (must stay 0 for device placement — the accounting is from the
    exact arrays staged, so the zero is chip-independent by construction).

    Same min-of-interleaved-repeats protocol as the sibling microbenches
    (all repeats kept under ``steps_per_sec_repeats``)."""
    import jax

    from bench import bench_ensemble_capacity, bench_megastep

    out = {
        "metric": "shard_microbench",
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "batch": batch,
        "k": k,
        "hidden": hidden,
        "rows": rows,
        "steps": steps,
        "repeats": repeats,
        "on_chip_recipe": (
            "unset JAX_PLATFORMS and rerun on a multi-chip TPU VM; sweep "
            "view: python benchmarks/mfu_sweep.py --sharded-only; training "
            "form: python train.py --replay-placement device --dp 8 "
            "--steps-per-dispatch 32 --debug-guards"
        ),
    }
    variants = [
        (
            "megastep_dp1",
            lambda: bench_megastep(
                placement="device", steps=steps, batch=batch, k=k,
                hidden=hidden, rows=rows,
            ),
        ),
        (
            f"megastep_dp{dp}",
            lambda: bench_megastep(
                placement="device", steps=steps, batch=batch, k=k,
                hidden=hidden, rows=rows, dp=dp,
            ),
        ),
        (
            f"megastep_per_dp{dp}",
            lambda: bench_megastep(
                placement="device", per=True, steps=steps, batch=batch,
                k=k, hidden=hidden, rows=rows, dp=dp,
            ),
        ),
        (
            "ensemble_mog_wide",
            lambda: bench_ensemble_capacity(
                ensemble=ensemble, hidden=ens_hidden, batch=ens_batch,
                dp=max(1, dp // 2), tp=2, steps=max(2, steps // 2),
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
    dp_key = f"megastep_dp{dp}"
    if out["megastep_dp1"]["steps_per_sec"] > 0:
        out["dp_steps_ratio"] = round(
            out[dp_key]["steps_per_sec"]
            / out["megastep_dp1"]["steps_per_sec"],
            4,
        )
    if out_path:
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, out_path)
    return out


if __name__ == "__main__":
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        # CPU virtual mesh for the sharded rows; on-chip runs (no
        # JAX_PLATFORMS override) use the real device topology as-is.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    artifact = os.path.join(
        os.path.dirname(__file__), "shard_microbench.json"
    )
    print(json.dumps(run_microbench(artifact)))
