"""Chip-independent host data-plane microbench (tier-1-safe).

The round-7 claim — the native batched replay gather/sample/write-back cuts
host time per dispatch vs the PR 1 legacy path — must stay measurable
without a chip: every timed stage here (PER descent, row gather,
staging, priority write-back) is HOST CPU work, so the before/after
comparison is chip-independent by construction; only the jitted train step
runs on whatever backend is available, and its time is reported separately
(``train_dispatch``) rather than folded into the host numbers.

Variants, all through ``bench.bench_host_pipeline``'s pinned loop:

- ``legacy_*``  — PR 1 data plane: per-batch ``sample()`` /
  ``sample_many`` + per-field fancy-index gathers + ``np.stack``;
- ``block_*``   — round-7 data plane: ``sample_block`` (one backend call
  into preallocated staging; with the native backend, one C call);
- ``*_numpy_*`` — NumPy-tree oracle baseline (native build unused).

Run as a script to (re)generate ``benchmarks/host_pipeline_microbench.json``:

    JAX_PLATFORMS=cpu python benchmarks/host_pipeline_microbench.py

``tests/test_host_pipeline_microbench.py`` runs the same function at
smaller shapes every tier-1 pass.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_microbench(
    out_path: str | None = None,
    *,
    batch: int = 128,
    rows: int = 16_384,
    steps: int = 80,
    hidden: int = 64,
    ks: tuple = (1, 8),
    backends: tuple = ("auto", "numpy"),
    repeats: int = 3,
) -> dict:
    """Time legacy vs block samplers per tree backend and dispatch width.

    Each variant runs ``repeats`` times INTERLEAVED (full variant sweep per
    repeat, not back-to-back) and keeps the repeat with the lowest
    ``host_ms_per_dispatch``: the shared few-core bench host shows bursty
    interference that inflates every stage — including the sampler-
    independent ``train_dispatch`` — by 2-3× for seconds at a time, and
    min-of-repeats is the standard way to read the machine's floor through
    that. All repeats' host-ms readings are kept under ``host_ms_repeats``
    so the spread stays visible.

    Returns the artifact dict; writes it to ``out_path`` when given.
    """
    import jax

    from bench import bench_host_pipeline

    out = {
        "metric": "host_pipeline_microbench",
        "backend": jax.default_backend(),
        "batch": batch,
        "rows": rows,
        "steps": steps,
        "hidden": hidden,
        "repeats": repeats,
    }
    variants = [
        (f"{sampler}_{tb}_k{k}", dict(tree_backend=tb, sampler=sampler, k=k))
        for k in ks
        for tb in backends
        for sampler in ("legacy", "block")
    ]
    for _ in range(repeats):
        for name, kw in variants:
            r = bench_host_pipeline(
                prefetch=False,
                steps=steps,
                batch=batch,
                rows=rows,
                hidden=hidden,
                compute_dtype="float32",
                **kw,
            )
            # the resolved backend ("auto" may degrade to numpy when g++
            # is unavailable) is inside r["tree_backend"]
            prev = out.get(name)
            r["host_ms_repeats"] = (
                prev["host_ms_repeats"] if prev else []
            ) + [r["host_ms_per_dispatch"]]
            if prev is None or (
                r["host_ms_per_dispatch"] < prev["host_ms_per_dispatch"]
            ):
                out[name] = r
            else:
                prev["host_ms_repeats"] = r["host_ms_repeats"]
    for k in ks:
        legacy = out[f"legacy_auto_k{k}"]["host_ms_per_dispatch"]
        block = out[f"block_auto_k{k}"]["host_ms_per_dispatch"]
        if legacy > 0:
            # the headline: host data-plane time per dispatch, after/before
            out[f"host_ms_ratio_k{k}"] = round(block / legacy, 4)
    if out_path:
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, out_path)
    return out


if __name__ == "__main__":
    artifact = os.path.join(
        os.path.dirname(__file__), "host_pipeline_microbench.json"
    )
    print(json.dumps(run_microbench(artifact)))
