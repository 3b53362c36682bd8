"""BENCHMARK.json and the data files it names.

Discovery is by name, relative to the directory that holds the manifest:

    configuration  <its "file">                       (sizes, argv, reference)
    traffic mix    cellbench/traffic/<traffic>.json   (names its driver)
    driver         cellbench.drivers.<driver>         (module)
    layer metric   cellbench/layer_metrics/<name>.json (names its reducer)
    reducer        cellbench.reducers.<reducer>       (module)

No list of cells, mixes or metrics lives in code: a later PR adds a file and
a manifest entry and edits nothing.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import re

CODE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_MANIFEST = os.path.join(CODE_ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple      # manifest entries that apply to this cell
    per_layer: tuple       # manifest entries, each with its metric file under "file"
    root: str              # the directory that holds the manifest

    @property
    def out_dir(self) -> str:
        return os.path.join(self.root, "cellbench_out", self.name)


def load(path: str | None = None) -> tuple[dict, str]:
    path = os.path.abspath(path or DEFAULT_MANIFEST)
    with open(path) as f:
        return json.load(f), os.path.dirname(path)


def _read(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def traffic_file(name: str) -> str:
    return f"cellbench/traffic/{name}.json"


def metric_file(name: str) -> str:
    return f"cellbench/layer_metrics/{name}.json"


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def cell(manifest: dict, root: str, name: str) -> Cell:
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise KeyError(f"no workload {name!r} in the manifest (has: {known})")
    config = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    per_layer = tuple(
        {**m, "file": _read(root, metric_file(m["name"]))}
        for m in manifest["per_layer"] if _applies(m, name)
    )
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=config["name"],
        config=_read(root, config["file"]), traffic_name=entry["traffic"],
        traffic=_read(root, traffic_file(entry["traffic"])),
        end_to_end=tuple(m for m in manifest["end_to_end"] if _applies(m, name)),
        per_layer=per_layer, root=root,
    )


def driver(cell_: Cell):
    return importlib.import_module(f"cellbench.drivers.{cell_.traffic['driver']}")


def reducer(name: str):
    return importlib.import_module(f"cellbench.reducers.{name}").reduce


def problems(manifest: dict, root: str) -> list:
    """Everything about the manifest and its files that would stop a run or
    that the contract refuses, as a list of sentences (empty = sound)."""
    out = []
    if set(manifest) != KEYS:
        out.append(f"manifest keys {sorted(manifest)} != {sorted(KEYS)}")
        return out
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in manifest[k]]
    out += [f"bad name {n!r}" for n in names if not NAME.match(n)]
    out += [f"name {n!r} used twice" for n in sorted(set(names)) if names.count(n) > 1]
    configs = {c["name"]: c for c in manifest["configs"]}
    under_paths = lambda rel: any(  # noqa: E731
        rel.startswith(p.rstrip("/") + "/") for p in manifest["paths"])
    for c in manifest["configs"]:
        if not under_paths(c["file"]):
            out.append(f"config file {c['file']} is outside paths")
        elif not os.path.isfile(os.path.join(root, c["file"])):
            out.append(f"config file {c['file']} is missing")
        else:
            body = _read(root, c["file"])
            ref = body.get("reference", "")
            if not os.path.isfile(os.path.join(
                    CODE_ROOT, "cellbench", "reference", f"{ref}.py")):
                out.append(f"config {c['name']}: no plain reference {ref!r}")
            if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
                out.append(f"config {c['name']}: 'reduced' differs from its file")
        if c["name"] not in {w["config"] for w in manifest["workloads"]}:
            out.append(f"config {c['name']} is used by no cell")
        if len(c["why"]) > 200:
            out.append(f"config {c['name']}: why is over 200 characters")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("no setup_s among the end-to-end metrics")
    pairs = set()
    for w in manifest["workloads"]:
        if w["config"] not in configs:
            out.append(f"cell {w['name']}: unknown config {w['config']!r}")
        if w["name"] != f"{w['config']}.{w['traffic']}":
            out.append(f"cell {w['name']} is not <config>.<traffic>")
        if (w["config"], w["traffic"]) in pairs:
            out.append(f"cell {w['name']}: pair of config and traffic used twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            out.append(f"cell {w['name']}: chips must be 1 or 4")
        if len(w["why"]) > 200:
            out.append(f"cell {w['name']}: why is over 200 characters")
        tf = traffic_file(w["traffic"])
        if not os.path.isfile(os.path.join(root, tf)):
            out.append(f"cell {w['name']}: no traffic file {tf}")
            continue
        drv = _read(root, tf).get("driver", "")
        if not os.path.isfile(os.path.join(
                CODE_ROOT, "cellbench", "drivers", f"{drv}.py")):
            out.append(f"traffic {w['traffic']}: no driver {drv!r}")
        mine = [m for m in manifest["end_to_end"] if _applies(m, w["name"])]
        if len(mine) < 2 or "setup_s" not in {m["name"] for m in mine}:
            out.append(f"cell {w['name']}: needs setup_s and one more "
                       "end-to-end metric")
        layer = [m for m in manifest["per_layer"] if _applies(m, w["name"])]
        if not layer:
            out.append(f"cell {w['name']}: no per-layer metric")
        reported = {m["name"] for m in mine}
        out += [f"cell {w['name']}: {m['name']} moves {m['moves']}, which the "
                "cell does not report" for m in layer if m["moves"] not in reported]
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        out.append(f"{four} four-chip cells of {len(manifest['workloads'])}")
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["source"] not in SOURCES:
            out.append(f"metric {m['name']}: unknown source {m['source']!r}")
        out += [f"metric {m['name']}: unknown cell {c!r}"
                for c in m.get("workloads", []) if c not in cells]
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            out.append(f"end-to-end {m['name']}: source must be the benchmark's own")
        if not 0 < m["bound"] <= 0.1:
            out.append(f"end-to-end {m['name']}: bound {m['bound']} not in (0, 0.1]")
    for m in manifest["per_layer"]:
        if m["moves"] not in e2e:
            out.append(f"per-layer {m['name']}: moves unknown {m['moves']!r}")
        mf = metric_file(m["name"])
        if not os.path.isfile(os.path.join(root, mf)):
            out.append(f"per-layer {m['name']}: no file {mf}")
            continue
        red = _read(root, mf).get("reducer", "")
        if not os.path.isfile(os.path.join(
                CODE_ROOT, "cellbench", "reducers", f"{red}.py")):
            out.append(f"per-layer {m['name']}: no reducer {red!r}")
    listed = {m["name"] for m in manifest["per_layer"]}
    metric_dir = os.path.join(root, "cellbench", "layer_metrics")
    if os.path.isdir(metric_dir):
        out += [f"layer_metrics/{f} is named by no per-layer metric"
                for f in sorted(os.listdir(metric_dir))
                if f.endswith(".json") and f[:-5] not in listed]
    return out
