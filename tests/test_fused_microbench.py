"""Tier-1-safe CPU microbench smoke: one fused vs one unfused step.

Keeps the fused-kernel program surface exercised every test pass, chip or
no chip — the committed artifact lives at
``benchmarks/cpu_microbench.json`` (regenerate with
``JAX_PLATFORMS=cpu python benchmarks/fused_microbench.py``)."""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

from fused_microbench import run_microbench  # noqa: E402


def test_microbench_runs_and_records(tmp_path):
    out_path = str(tmp_path / "cpu_microbench.json")
    out = run_microbench(out_path, batch=32, hidden=32, atoms=21, timed_steps=1)
    with open(out_path) as f:
        on_disk = json.load(f)
    assert on_disk["metric"] == "fused_vs_unfused_cpu_microbench"
    # both variants timed, both finite
    assert out["unfused_step_ms"] > 0 and np.isfinite(out["unfused_step_ms"])
    assert out["fused_step_ms"] > 0 and np.isfinite(out["fused_step_ms"])
    assert out["fused_over_unfused_time"] > 0
    # bytes proxy present whenever this backend exposes cost analysis
    if "unfused_bytes_accessed" in out:
        assert out["unfused_bytes_accessed"] > 0


def test_committed_artifact_is_current_schema():
    """The committed artifact must stay parseable and carry the regression
    keys (a schema drift here would silently blind the perf guard)."""
    path = os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "cpu_microbench.json"
    )
    with open(path) as f:
        art = json.load(f)
    assert art["metric"] == "fused_vs_unfused_cpu_microbench"
    for key in ("unfused_step_ms", "fused_step_ms", "fused_over_unfused_time"):
        assert key in art
