"""BENCHMARK.json and the files it names agree, and a later PR can add a
configuration, a mix, a per-layer metric and a cell as files and entries
only: discovery is by name, and no list of them lives in code."""

from __future__ import annotations

import glob
import json
import os
import re
import shutil

import pytest

from cellbench import manifest as mf

REPO = mf.CODE_ROOT


@pytest.fixture(scope="module")
def manifest():
    return mf.load()[0]


def test_manifest_and_files_are_consistent(manifest):
    assert mf.problems(manifest, REPO) == []
    assert os.path.getsize(mf.DEFAULT_MANIFEST) <= 64 * 1024
    assert manifest["command"][:3] == ["python3", "-m", "cellbench.run"]
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    for path in manifest["paths"]:
        assert os.path.isdir(os.path.join(REPO, path)), path


def test_every_cell_resolves_by_name(manifest):
    for w in manifest["workloads"]:
        cell = mf.cell(manifest, REPO, w["name"])
        assert callable(mf.driver(cell).run)
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "transitions_per_s"}
        for m in cell.per_layer:
            assert callable(mf.reducer(m["file"]["reducer"])), m["name"]
        assert cell.traffic["dispatch_module"]
        assert cell.out_dir == os.path.join(REPO, "cellbench_out", w["name"])


def test_a_metric_that_lists_cells_is_read_only_there(manifest):
    """``workloads`` on a metric: collectives exist only across chips."""
    for w in manifest["workloads"]:
        mine = {m["name"] for m in mf.cell(manifest, REPO, w["name"]).per_layer}
        assert ("parallel.collective_share" in mine) == (w["chips"] == 4)
        assert "device.idle_share" in mine


def test_config_file_states_what_the_program_resolves(manifest):
    """``resolved`` in a configuration file is what its argv gives through
    the program's own path, so the file is the configuration as it is run."""
    from train import build_parser, config_from_args

    for c in manifest["configs"]:
        body = mf._read(REPO, c["file"])
        cfg = config_from_args(build_parser().parse_args(body["argv"]))
        agent = cfg.agent
        got = {
            "obs_dim": agent.obs_dim, "action_dim": agent.action_dim,
            "hidden_sizes": list(agent.hidden_sizes),
            "num_atoms": agent.dist.num_atoms, "v_min": agent.dist.v_min,
            "v_max": agent.dist.v_max, "batch_size": cfg.batch_size,
            "n_step": agent.n_step, "gamma": agent.gamma, "tau": agent.tau,
            "lr_actor": agent.lr_actor, "lr_critic": agent.lr_critic,
            "per_alpha": agent.per_alpha, "per_beta0": agent.per_beta0,
            "per_beta_steps": agent.per_beta_steps,
            "replay_capacity": cfg.replay_capacity,
            "steps_per_dispatch": cfg.steps_per_dispatch,
            "compute_dtype": agent.compute_dtype,
            "projection_backend": agent.projection_backend,
            "device_tree_backend": cfg.device_tree_backend,
            "fused_descent": cfg.fused_descent, "prioritized": cfg.prioritized,
        }
        assert got == pytest.approx(body["resolved"]), c["name"]
        assert body["source"] == c["source"] and body["name"] == c["name"]


def copy_of_the_benchmark(tmp_path) -> str:
    root = str(tmp_path)
    shutil.copy(mf.DEFAULT_MANIFEST, root)
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "cellbench", sub),
                        os.path.join(root, "cellbench", sub))
    return root


def add_files_and_entries(root: str) -> str:
    """What a later PR does: a new configuration, a new mix of an existing
    kind, a new per-layer metric on an existing reducer, a cell on them, and
    a fifth cell on an existing configuration and mix. Nothing is edited."""
    def write(rel, obj):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)

    write("cellbench/configs/tiny_pendulum.json", {
        "name": "tiny_pendulum", "source": "https://example.org/tiny",
        "argv": ["--env", "pendulum", "--replay-placement", "device", "--p-replay",
                 "--steps-per-dispatch", "4", "--hidden-sizes", "16,16",
                 "--rmsize", "2048", "--bsize", "32", "--warmup", "64",
                 "--num-envs", "2"],
        "rehearsal_argv": [], "reference": "d4pg_step", "reduced": [],
    })
    write("cellbench/traffic/train_ratio2.json", {
        "driver": "trainer",
        "argv": ["--env-steps-per-train-step", "2", "--eval-interval", "1000000000",
                 "--checkpoint-interval", "1000000000"],
        "total_steps": 1000000000, "warm_dispatches": 16, "trace_seconds": 0.5,
        "dispatch_module": "^jit_lane",
    })
    # the two megastep branches no cell of the manifest takes yet
    write("cellbench/configs/tiny_pallas.json", {
        "name": "tiny_pallas", "source": "https://example.org/tiny-pallas",
        "argv": ["--env", "pendulum", "--replay-placement", "device", "--p-replay",
                 "--steps-per-dispatch", "4", "--hidden-sizes", "16,16",
                 "--rmsize", "2048", "--bsize", "32", "--projection", "pallas_fused",
                 "--device-tree-backend", "pallas", "--fused-descent"],
        "rehearsal_argv": [], "reference": "d4pg_step", "reduced": [],
    })
    write("cellbench/traffic/learn_uniform_dp4.json", {
        "driver": "learner", "argv": ["--no-p-replay"], "dp": 4, "ring_rows": "per_chip",
        "warm_dispatches": 4, "inflight": 4, "trace_seconds": 0.2,
        "slice_seconds": 0.05, "dispatch_module": "^jit_",
    })
    write("cellbench/layer_metrics/runtime.collect_window_share.json", {
        "reducer": "module_time", "args": {"module": "^jit_collect", "per": "window"},
    })
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"] += [
        {"name": "tiny_pendulum", "source": "https://example.org/tiny",
         "file": "cellbench/configs/tiny_pendulum.json", "reduced": [], "why": "test"},
        {"name": "tiny_pallas", "source": "https://example.org/tiny-pallas",
         "file": "cellbench/configs/tiny_pallas.json", "reduced": [], "why": "test"}]
    m["workloads"] += [
        {"name": "tiny_pallas.learn_per", "config": "tiny_pallas",
         "traffic": "learn_per", "chips": 1, "why": "the fused Pallas tier"},
        {"name": "tiny_pendulum.learn_uniform_dp4", "config": "tiny_pendulum",
         "traffic": "learn_uniform_dp4", "chips": 4, "why": "uniform replay over a mesh"},
        # an eighth cell, so that two of them may ask for four chips
        {"name": "tiny_pendulum.learn_per", "config": "tiny_pendulum",
         "traffic": "learn_per", "chips": 1, "why": "an existing mix on a new configuration"},
        {"name": "tiny_pendulum.train_ratio2", "config": "tiny_pendulum",
         "traffic": "train_ratio2", "chips": 1, "why": "test"},
        {"name": "humanoid_b256.learn_uniform", "config": "humanoid_b256",
         "traffic": "learn_uniform", "chips": 1, "why": "an existing pair, one entry"},
    ]
    m["per_layer"].append({
        "name": "runtime.collect_window_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "runtime", "moves": "transitions_per_s"})
    with open(path, "w") as f:
        json.dump(m, f)
    return path


def test_new_cells_need_only_files_and_entries(tmp_path):
    root = copy_of_the_benchmark(tmp_path)
    manifest, _ = mf.load(add_files_and_entries(root))
    assert mf.problems(manifest, root) == []
    cell = mf.cell(manifest, root, "tiny_pendulum.train_ratio2")
    assert cell.config["argv"][1] == "pendulum" and cell.traffic["driver"] == "trainer"
    assert mf.driver(cell).__name__ == "cellbench.drivers.trainer"
    assert "runtime.collect_window_share" in {m["name"] for m in cell.per_layer}
    assert cell.out_dir.startswith(root)
    fifth = mf.cell(manifest, root, "humanoid_b256.learn_uniform")
    assert fifth.config_name == "humanoid_b256" and fifth.traffic_name == "learn_uniform"


@pytest.mark.parametrize("break_it, complaint", [
    (lambda m: m["workloads"][0].update(chips=4), "four-chip"),
    (lambda m: m["workloads"][0].update(name="bad name"), "bad name"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "moves unknown"),
    (lambda m: m["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda m: m["workloads"].append(dict(m["workloads"][0])), "used twice"),
    (lambda m: m["per_layer"].pop(), "named by no per-layer metric"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
    (lambda m: m.update(extra=1), "manifest keys"),
    (lambda m: m["configs"][0].update(why="x" * 201), "why is over 200"),
    (lambda m: next(x for x in m["per_layer"] if "workloads" in x)["workloads"].append(
        "no.such"), "unknown cell"),
])
def test_problems_are_named(manifest, break_it, complaint):
    broken = json.loads(json.dumps(manifest))
    break_it(broken)
    assert any(complaint in p for p in mf.problems(broken, REPO)), mf.problems(broken, REPO)


# A list of the manifest read from its end: ``<list>[-n]`` or ``<list>[-n:]``
# on configs, workloads or per_layer, by key or by attribute.
FROM_THE_END = re.compile(
    r"""(?:\[\s*["'](?:configs|workloads|per_layer)["']\s*\]|\.(?:configs|workloads|per_layer))"""
    r"\s*\[\s*-\s*\d")


def test_no_test_reads_a_manifest_list_from_its_end():
    """New configurations, cells and per-layer metrics go at the end of
    their lists: a test that holds an entry to the tail fails the next PR
    that appends one, and a later PR may not edit a benchmark test."""
    hits = []
    for path in sorted(glob.glob(os.path.join(REPO, "tests", "cellbench", "*.py"))):
        with open(path) as f:
            for n, line in enumerate(f, 1):
                if FROM_THE_END.search(line):
                    hits.append(f"{os.path.basename(path)}:{n}: {line.strip()}")
    assert hits == []


@pytest.mark.parametrize("head, tail, reads_the_end", [
    ('m["configs"]', '[-1]["name"]', True),
    ("manifest['workloads']", "[-1]", True),
    ('{x["name"] for x in manifest["per_layer"]', "[-4:]}", True),
    ("cell.per_layer", "[ -2 ]", True),
    ('m["per_layer"]', "[0]", False),
    ("listed", "[-4:]", False),
    ('m["per_layer"]', ".index(entry)", False),
])
def test_the_guard_sees_a_read_from_the_end(head, tail, reads_the_end):
    """Each case in two halves, so that this file holds no such read."""
    assert bool(FROM_THE_END.search(head + tail)) == reads_the_end
