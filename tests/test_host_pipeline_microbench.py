"""Tier-1-safe host data-plane microbench smoke.

Keeps the round-7 host-pipeline perf surface (legacy vs native-block
samplers, per-stage times) exercised every test pass, chip or no chip —
the committed artifact lives at
``benchmarks/host_pipeline_microbench.json`` (regenerate with
``JAX_PLATFORMS=cpu python benchmarks/host_pipeline_microbench.py``)."""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

from host_pipeline_microbench import run_microbench  # noqa: E402


def test_microbench_runs_and_records(tmp_path):
    out_path = str(tmp_path / "host_pipeline_microbench.json")
    out = run_microbench(
        out_path, batch=16, rows=512, steps=4, hidden=16, ks=(2,), repeats=1
    )
    with open(out_path) as f:
        on_disk = json.load(f)
    assert on_disk["metric"] == "host_pipeline_microbench"
    for name in ("legacy_auto_k2", "block_auto_k2", "legacy_numpy_k2", "block_numpy_k2"):
        v = out[name]
        assert v["host_ms_per_dispatch"] > 0 and np.isfinite(v["host_ms_per_dispatch"])
        for stage in ("sample", "h2d_stage", "train_dispatch", "priority_writeback"):
            assert v["stage_ms_per_dispatch"][stage] >= 0.0
        assert len(v["host_ms_repeats"]) == 1
    # the numpy rows really ran the numpy trees; auto resolved to SOME backend
    assert out["legacy_numpy_k2"]["tree_backend"] == "numpy"
    assert out["block_auto_k2"]["tree_backend"] in ("native", "numpy")
    assert "host_ms_ratio_k2" in out


def test_committed_artifact_is_current_schema():
    """The committed artifact must stay parseable and carry the per-stage
    before/after keys (schema drift would blind the host-perf guard)."""
    path = os.path.join(
        os.path.dirname(__file__), "..", "benchmarks",
        "host_pipeline_microbench.json",
    )
    with open(path) as f:
        art = json.load(f)
    assert art["metric"] == "host_pipeline_microbench"
    for k in (1, 8):
        for variant in (f"legacy_auto_k{k}", f"block_auto_k{k}"):
            v = art[variant]
            assert v["host_ms_per_dispatch"] > 0
            assert set(v["stage_ms_per_dispatch"]) >= {
                "sample", "h2d_stage", "train_dispatch", "priority_writeback"
            }
        assert f"host_ms_ratio_k{k}" in art
