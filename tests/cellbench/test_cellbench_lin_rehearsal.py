"""The linear-attention cell as the driver runs it — a new process, the
last line parsed — rehearsed on the CPU at the configuration's tiny sizes."""

from __future__ import annotations

import json
import os
import subprocess
import sys

from cellbench import manifest as mf

REPO = mf.CODE_ROOT
CELL = "humanoid_qwen3next_ep32.learn_per_lin8k"


def _run(*args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    return subprocess.run(
        [sys.executable, "-m", "cellbench.run", *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=900)


def test_lin_cell_rehearsal_traced():
    p = _run("--workload", CELL, "--seed", "3000000019", "--seconds", "2", "--trace", "1",
             "--rehearsal")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-3000:]
    lines = [json.loads(ln) for ln in p.stdout.splitlines() if ln.startswith("{")]
    report, line = lines[-2:]
    assert p.stdout.strip().splitlines()[-1].startswith('{"correct"')
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert all(c["ok"] for c in report["checks"].values()), report["checks"]
    assert set(report["checks"]) >= {
        "reference_step", "choices", "routing", "descent", "grad_steps_advanced", "tree_sums",
        "sampled_leaves_moved", "window_ran_under_transfer_guard", "state_finite",
        "no_compilation_in_window"}
    assert report["checks"]["no_compilation_in_window"]["compiled"] == []
    routing, experts = report["checks"]["routing"], report["checks"]["choices"]["experts"]
    assert routing["dropped"] == 0 and routing["pairs_on_held_experts"] > 0
    assert experts["outside_the_band"] == 0 and experts["places"] > 0
    # the report line carries the band's counts
    said = next(ln for ln in p.stdout.splitlines() if "reference step checked" in ln)
    assert f"{experts['places']} places" in said and "in the band" in said
    assert report["window"]["grad_steps"] == report["window"]["dispatches"] > 0   # K = 1
    assert report["window"]["transitions"] == report["window"]["grad_steps"]      # B = 1
    assert report["traced"]["dispatches"] > 0
    assert "end_to_end" not in report          # a CPU run prints under no metric's name


def test_the_parent_program_refuses_the_cell_at_once():
    """A program without this torso (the parent commit, with this PR's
    benchmark files laid over it) must fail the cell cleanly: argparse exits
    2 on the preset it does not know, before anything is built."""
    from cellbench.drivers import Job, resolve_config
    import train

    cell = mf.cell(*mf.load(), CELL)
    assert cell.config["argv"][cell.config["argv"].index("--torso") + 1] == "qwen3_next"
    real = train.build_parser

    def old_parser():
        p = real()
        p._option_string_actions["--torso"].choices = [
            "glm47_flash", "glm47_flash_tiny", "keye_vl2", "keye_vl2_tiny"]
        return p

    train.build_parser = old_parser
    try:
        job = Job(cell, 0, 1.0, False, True, [], print)
        try:
            resolve_config(job)
        except SystemExit as e:
            assert e.code == 2
        else:
            raise AssertionError("the parent's parser took the new torso")
    finally:
        train.build_parser = real
