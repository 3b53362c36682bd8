"""The command as the driver runs it — a new process, the last line parsed —
rehearsed on the CPU at tiny sizes: each driver, the dp=4 mix on four
virtual devices, a cell made of added files only; and its refusals."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from cellbench import manifest as mf
from test_cellbench_manifest import copy_of_the_benchmark, add_files_and_entries

REPO = mf.CODE_ROOT
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _env(devices: int = 1) -> dict:
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def _run(*args, devices=1, cwd=REPO):
    return subprocess.run(
        [sys.executable, "-m", "cellbench.run", *args], cwd=cwd, env=_env(devices),
        capture_output=True, text=True, timeout=600)


def _json_lines(stdout: str) -> list:
    return [json.loads(ln) for ln in stdout.splitlines() if ln.startswith("{")]


def _check_rehearsal(p, devices=1):
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-3000:]
    report, line = _json_lines(p.stdout)[-2:]
    assert p.stdout.strip().splitlines()[-1].startswith('{"correct"')
    assert set(line) == LINE_KEYS and set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == devices
    assert line["metrics"] == {}, "a CPU run prints under no metric's name"
    assert "end_to_end" not in report and "grad_steps_per_s" not in report
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert all(c["ok"] for c in report["checks"].values()), report["checks"]
    assert report["checks"]["no_compilation_in_window"]["compiled"] == []
    return report


def test_learner_rehearsal():
    report = _check_rehearsal(_run(
        "--workload", "halfcheetah_b256.learn_per", "--seed", "3", "--seconds", "1",
        "--trace", "0", "--rehearsal"))
    assert set(report["checks"]) >= {
        "reference_step", "descent", "grad_steps_advanced", "tree_sums",
        "sampled_leaves_moved", "window_ran_under_transfer_guard"}
    out = os.path.join(REPO, "cellbench_out", "halfcheetah_b256.learn_per")
    assert os.path.isfile(os.path.join(out, "report.json"))


def test_uniform_rehearsal_on_a_cell_that_is_one_added_entry(tmp_path):
    """Mix ``learn_uniform`` has no cell in the manifest (PERF.md section 7);
    one entry on an existing configuration makes one."""
    path = add_files_and_entries(copy_of_the_benchmark(tmp_path))
    report = _check_rehearsal(_run(
        "--manifest", path, "--workload", "humanoid_b256.learn_uniform", "--seed", "3",
        "--seconds", "1", "--trace", "0", "--rehearsal"))
    assert "descent" not in report["checks"] and "tree_sums" not in report["checks"]


@pytest.mark.parametrize("cell, devices", [
    ("tiny_pallas.learn_per", 1), ("tiny_pendulum.learn_uniform_dp4", 4)])
def test_learner_branches_no_cell_takes_yet(tmp_path, cell, devices):
    """The fused Pallas tier (interpreted here) and uniform replay over a
    mesh, as cells made of added files: a later PR brings them as data, and
    the learner driver has run them before it does."""
    root = copy_of_the_benchmark(tmp_path)
    path = add_files_and_entries(root)
    report = _check_rehearsal(_run(
        "--manifest", path, "--workload", cell, "--seed", "3", "--seconds", "1",
        "--trace", "0", "--rehearsal", devices=devices), devices=devices)
    assert ("tree_sums" in report["checks"]) == (cell == "tiny_pallas.learn_per")
    assert report["sizes"]["lanes"] == devices


def test_dp4_rehearsal_on_four_virtual_devices_traced():
    report = _check_rehearsal(_run(
        "--workload", "humanoid_b256.learn_per_dp4", "--seed", "3", "--seconds", "1",
        "--trace", "1", "--rehearsal", devices=4), devices=4)
    assert report["sizes"]["lanes"] == 4
    assert report["sizes"]["capacity"] == 4 * 4096      # --rmsize rows per chip
    assert report["traced"]["dispatches"] > 0


def test_trainer_rehearsal_on_a_cell_made_of_added_files(tmp_path):
    """A new configuration, mix and per-layer metric dropped into a copy of
    the manifest's files run through the harness unchanged (ISSUE 22's
    acceptance: later cells are data)."""
    root = copy_of_the_benchmark(tmp_path)
    path = add_files_and_entries(root)
    report = _check_rehearsal(_run(
        "--manifest", path, "--workload", "tiny_pendulum.train_ratio2", "--seed", "3",
        "--seconds", "1", "--trace", "0", "--rehearsal"))
    assert set(report["checks"]) >= {
        "reference_step", "descent", "grad_steps_advanced", "tree_sums",
        "collection_kept_the_ratio", "train_returned"}
    assert report["window"]["env_steps"] > 0
    assert os.path.isfile(os.path.join(
        root, "cellbench_out", "tiny_pendulum.train_ratio2", "report.json"))


def test_refuses_to_run_without_the_device_asked_for():
    """No TPU and no --rehearsal: another exit code than 0 and no result."""
    p = _run("--workload", "halfcheetah_b256.learn_per", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not _json_lines(p.stdout), p.stdout[-500:]
    assert "needs 'tpu'" in p.stderr and "no\nCPU fallback" not in p.stdout


def test_refuses_fewer_devices_than_the_cell_asks_for():
    p = _run("--workload", "humanoid_b256.learn_per_dp4", "--seed", "1",
             "--seconds", "1", "--trace", "0", "--rehearsal", devices=2)
    assert p.returncode != 0 and not _json_lines(p.stdout)
    assert "needs 4 chips" in p.stderr


def test_alone_with_its_own_files_it_fails(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no program to measure: no result."""
    shutil.copy(mf.DEFAULT_MANIFEST, tmp_path)
    shutil.copytree(os.path.join(REPO, "cellbench"), tmp_path / "cellbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in _env().items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "-m", "cellbench.run", "--workload",
         "halfcheetah_b256.learn_per", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearsal"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and not _json_lines(p.stdout)


def test_unknown_cell_is_an_error():
    p = _run("--workload", "no.such_cell", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and not _json_lines(p.stdout)
