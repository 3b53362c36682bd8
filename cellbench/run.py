"""Run one cell once.

    python -m cellbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs the TPU the cell asks for and fails without it (no CPU fallback).
``--rehearsal`` is the one way onto the CPU: the cell's tiny rehearsal sizes,
``platform: cpu`` in the last line and no metric in it. Earlier lines are
progress and the report; the LAST line of stdout is the contract's object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, traced
``breakdown``, and last ``compared``: each number the reference comparison
held to a limit, beside it (also the last lines of stderr). With ``--trace
0`` the metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics. ``README.md`` says what is written where.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

from cellbench import manifest as mf
from cellbench import probe


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="tiny sizes on the CPU; prints platform cpu and no metric")
    p.add_argument("--manifest", default=None,
                   help="another BENCHMARK.json (tests, temporary copies)")
    return p.parse_args(argv)


def end_to_end(result, device) -> dict:
    """Every end-to-end value the harness can take; the cell's manifest
    entries pick which of them it reports."""
    w = result["window"]
    values = {
        "transitions_per_s": w["transitions"] / w["seconds"],
        "setup_s": result["setup_s"],
    }
    if "env_steps" in w:
        values["env_steps_per_s"] = w["env_steps"] / w["seconds"]
    if device["memory_peak_bytes"] is not None:
        values["peak_hbm_gib"] = device["memory_peak_bytes"] / 2.0 ** 30
    return values


def per_layer(cell, result, device, tr, compile_counts) -> dict:
    """The cell's per-layer metrics through their reducers; one that finds
    nothing to read is left out."""
    from cellbench import model_cost
    from cellbench.reducers import Context

    w = result["window"]
    values = {f"compile.{k}": v for k, v in compile_counts.items()}
    values.update({f"window.{k}": v for k, v in w.items() if k != "wall"})
    values["window.grad_steps_per_s"] = w["grad_steps"] / w["seconds"]
    values.update({f"cost.{k}": v for k, v in model_cost.cost_for(
        result["agent_cfg"], result["batch"]).items()})
    values["device.count"] = cell.chips
    if device["platform"] == "tpu":
        from cellbench import peaks

        values.update({f"peaks.{k}": v for k, v in
                       peaks.peaks_for(device["kind"]).items() if k != "source"})
    ctx = Context(tr, cell.traffic["dispatch_module"], result["k"], values)
    out = {}
    for m in cell.per_layer:
        value = mf.reducer(m["file"]["reducer"])(ctx, **m["file"].get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def traced_device(tr) -> dict:
    """``busy_s`` (an operation ran, averaged over the chips) and
    ``window_s`` of the traced window, from the trace alone."""
    from cellbench.trace import busy

    a, b = tr.window
    per_chip = [busy(d.ops, a, b) for d in tr.devices]
    return {"busy_s": sum(per_chip) / len(per_chip) / 1e9, "window_s": (b - a) / 1e9}


def breakdown(tr) -> dict:
    """Top device ops by self time (names as the trace prints them, first
    device) and idle time by where it falls: a gap between ops inside a
    program's execution goes to that program, a gap between programs to the
    innermost host annotation open at its middle."""
    import bisect

    from cellbench.trace import gaps, self_times, union

    dev = tr.devices[0]
    a, b = tr.window
    by_op: dict = {}
    for name, self_ns, cat in self_times(dev.ops):
        label = f"{name} [{cat}]" if cat else name
        by_op[label] = by_op.get(label, 0.0) + self_ns
    mods = sorted(dev.modules, key=lambda m: m[1])
    mod_starts = [m[1] for m in mods]
    spans = sorted((h for h in tr.host if h[1] != "cellbench/traced_window"),
                   key=lambda h: h[3])    # shortest first = innermost first
    by_host: dict = {}
    for s, e in gaps(union((o[1], o[1] + o[2]) for o in dev.ops), a, b):
        mid = (s + e) / 2
        i = bisect.bisect_right(mod_starts, mid) - 1
        if i >= 0 and mid <= mods[i][1] + mods[i][2]:
            owner = f"between ops inside {mods[i][0]}"
        else:
            owner = next((h[1] for h in spans if h[2] <= mid <= h[2] + h[3]),
                         "no host annotation open")
        by_host[owner] = by_host.get(owner, 0.0) + (e - s)
    top = lambda d: [[k, v / 1e9] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(by_op), "idle_gaps": top(by_host)}


def compared(checks) -> dict:
    """The numbers the reference comparison holds to a limit, each beside
    its limit: ``{"<check>.<number>": [value, limit]}``, a number passing
    where it is at most its limit. Relative errors and ulps of check 1 (a
    driver's own limits by name under ``tol_of`` / ``tol_ulp_of``), and the
    descent's mismatches, which must be none."""
    out = {}
    for check, c in checks.items():
        for errs, tol, tol_of in (("rel_err", "tol_rel", "tol_of"),
                                  ("ulp_err", "tol_ulp", "tol_ulp_of")):
            for name, value in (c.get(errs) or {}).items():
                out[f"{check}.{name}"] = [value, (c.get(tol_of) or {}).get(name, c[tol])]
        if "mismatches" in c:
            out[f"{check}.mismatches"] = [c["mismatches"], 0]
    return out


def read_trace(cell, xplane, say):
    """The traced window's events, clipped to the window annotation; leaves
    a slice of them next to the report, to read by hand (the form of the
    recorded traces the tests pin the reducers on)."""
    from cellbench import trace

    if not xplane:
        return None
    say(f"reading {xplane} ({os.path.getsize(xplane) / 2 ** 20:.1f} MiB)")
    full = trace.load(xplane)
    say(f"trace read: {[len(d.ops) for d in full.devices]} device ops, "
        f"{len(full.host)} host spans, window {full.window}")
    if not full.window:
        return full
    tr = full.clipped(*full.window)
    a = tr.window[0]
    trace.dump(tr.clipped(a, a + float(cell.traffic["slice_seconds"]) * 1e9),
               os.path.join(cell.out_dir, "trace_slice.json.gz"))
    return tr


def main(argv=None) -> int:
    args = parse(argv)
    manifest, root = mf.load(args.manifest)
    faults = mf.problems(manifest, root)
    if faults:
        raise SystemExit("BENCHMARK.json: " + "; ".join(faults))
    cell = mf.cell(manifest, root, args.workload)

    # The program decides where the compile cache lives (an exported
    # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache); the harness
    # only lowers the thresholds, in its own process, so that the
    # sub-second programs are cached too.
    from d4pg_tpu.utils.compile_cache import configure_compile_cache

    import jax

    cache_dir = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = probe.require_devices(cell.chips, args.rehearsal)
    reached_chip_s = probe.process_age_s()    # set-up counts from here
    compile_log = probe.CompileLog()
    shutil.rmtree(cell.out_dir, ignore_errors=True)
    os.makedirs(cell.out_dir)

    def say(*a):      # every progress line carries the process's age
        print(f"[cellbench +{probe.process_age_s():.1f}s]", *a, flush=True)

    from cellbench.drivers import Job

    say(f"{cell.name}: {len(devices)} x {devices[0].device_kind}, seed {args.seed}, "
        f"{args.seconds}s, trace {args.trace}, cache {cache_dir}")
    job = Job(cell, args.seed, args.seconds, bool(args.trace), args.rehearsal,
              devices, say, reached_chip_s)
    result = mf.driver(cell).run(job)

    compiled = compile_log.inside(*result["window"]["wall"])
    result["checks"]["no_compilation_in_window"] = {
        "ok": not compiled, "compiled": compiled}
    correct = result["failed"] == 0 and all(
        c["ok"] for c in result["checks"].values())
    # The driver says when the peak is the program's own (the learner reads
    # it right after the window, before its checks reduce over the tree);
    # otherwise the lifetime peak, now.
    device = probe.device_line(
        devices, jax.device_count(),
        result.get("memory_peak_bytes") or probe.peak_bytes(devices))
    compile_counts = compile_log.counters()

    e2e = end_to_end(result, device)
    report = {
        "cell": cell.name, "seed": args.seed, "correct": correct,
        "attempted": result["attempted"], "failed": result["failed"],
        "checks": result["checks"], "window": result["window"],
        "traced": result["traced"], "sizes": result["sizes"],
        "compile": compile_counts, "device": device,
    }
    if not args.rehearsal:   # a CPU run never prints under a metric's name
        report["end_to_end"] = e2e
        report["reach_chip_s"] = reached_chip_s
        report["grad_steps_per_s"] = (
            result["window"]["grad_steps"] / result["window"]["seconds"])
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": device}
    if args.trace and not args.rehearsal:
        tr = read_trace(cell, result["xplane"], say)
        if tr is not None and tr.devices and tr.window:
            device.update(traced_device(tr))
            line["breakdown"] = breakdown(tr)
        line["metrics"] = per_layer(cell, result, device, tr, compile_counts)
        report["per_layer"] = line["metrics"]
        for m in cell.per_layer:     # the check wants each one the cell declares
            if m["name"] not in line["metrics"]:
                print(f"cellbench: {m['name']} found nothing to read and is "
                      "left out of the line", file=sys.stderr, flush=True)
    elif not args.rehearsal:
        line["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in e2e}
    line["compared"] = compared(result["checks"])
    with open(os.path.join(cell.out_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)
    print(json.dumps(report, default=str), flush=True)
    for name, (value, limit) in line["compared"].items():
        print(f"compared: {name} {value:.6g} (limit {limit:g})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
