"""Every reducer pinned on recorded v5e traces (``cellbench/testdata``), the
loader on a trace made here, and the last line's traced parts.

The recorded slices are in the loader's normalised JSON form, cut from real
traced runs of PR 22 (see each file's ``note``): ``v5e_train_closed_slice``
holds two whole collect → ingest → megastep ×2 cycles on one chip,
``v5e_dp4_slice`` a few sharded megasteps on four. The pins are the
reducers' arithmetic on those events, checked by hand against the module
timeline when they were recorded; they are not claims about the chip."""

from __future__ import annotations

import os

import pytest

from cellbench import manifest as mf
from cellbench import run as cellrun
from cellbench import trace
from cellbench.reducers import Context

DATA = os.path.join(mf.CODE_ROOT, "cellbench", "testdata")
VALUES = {
    "compile.trace_lower_s": 25.0, "compile.backend_compile_s": 1.8,
    "cost.flops_per_grad_step": 700_317_696, "window.grad_steps_per_s": 428.79,
    "device.count": 1, "peaks.flops_per_s": 197e12,
}


@pytest.fixture(scope="module")
def closed():
    return trace.load(os.path.join(DATA, "v5e_train_closed_slice.json.gz"))


# The closed loop's own per-layer metrics. No cell of the manifest runs the
# trainer driver today (PERF.md section 7: it cannot start on a full ring),
# so these are not files under layer_metrics/; the cell that returns brings
# them as such. They keep ``host_span_ms`` and ``module_time`` pinned.
CLOSED_LOOP = {
    **{f"runtime.host_ms_per_dispatch.{stage}":
       {"reducer": "host_span_ms", "args": {"span": f"^host/{stage}$"}}
       for stage in ("env_step", "replay_insert", "ingest_chunk", "megastep_dispatch")},
    "replay.ingest_device_ms_per_dispatch":
        {"reducer": "module_time", "args": {"module": "^jit__ingest", "per": "dispatch"}},
}


# The Pallas tier's metric. Every cell of the manifest is XLA-tier, where it
# reads 0, and a metric no cell needs is not declared (the check wants each
# declared metric in every line): the first Pallas-tier cell brings this
# spec as a file, under its own ``"workloads"``. It keeps ``per="grad_step"``
# pinned.
CUSTOM_CALL = {"ops.custom_call_ms_per_step": {
    "reducer": "op_category_share",
    "args": {"ops": "custom-call tpu_custom_call", "per": "grad_step"}}}


def _metrics(cell_name, tr, k, values=VALUES, more=None):
    """The cell's per-layer metrics (and ``more``, specs as a metric file
    holds them) through their reducers."""
    manifest, root = mf.load()
    cell = mf.cell(manifest, root, cell_name)
    ctx = Context(tr, cell.traffic["dispatch_module"], k, values)
    specs = {m["name"]: m["file"] for m in cell.per_layer} | (more or {})
    return {name: mf.reducer(spec["reducer"])(ctx, **spec.get("args", {}))
            for name, spec in specs.items()}


def test_closed_loop_metrics_on_the_recorded_trace(closed):
    """The timeline of one cycle, by hand (ms from the slice's start):
    ingest 1.10+1.98 and 3.10+13.15, megastep 16.26+39.34 and 55.60+39.35,
    collect 94.97+50.56, next cycle's ingest at 149.76; host/env_step
    142.5 ms per cycle."""
    got = _metrics("halfcheetah_b256.learn_per", closed, 32,
                   more=CLOSED_LOOP | CUSTOM_CALL)
    want = {
        "entry.trace_lower_s": 25.0,
        "entry.backend_compile_s": 1.8,
        "runtime.dispatch_gap_share": 1.4665,
        "runtime.host_ms_per_dispatch.env_step": 71.3075,
        "runtime.host_ms_per_dispatch.replay_insert": 0.192125,
        "runtime.host_ms_per_dispatch.ingest_chunk": 1.50415,
        "runtime.host_ms_per_dispatch.megastep_dispatch": 0.95022,
        "replay.index_op_share": 96.6551,
        "replay.relayout_copy_share": 0.0239820,   # 9 µs of small copies a dispatch
        "replay.ingest_device_ms_per_dispatch": 7.5703,     # (1.98 + 13.15) / 2
        "agent.matmul_share": 1.27326,
        "agent.learner_mfu": 0.152431,
        "ops.other_fusion_share": 2.00677,
        # the collector's ops were left out of the file, so the op line is
        # empty under that program: an artefact of the recording
        "device.idle_share": 35.9949,
    }
    # an XLA-tier program ran and holds no such op: a measured 0, not nothing
    assert got.pop("ops.custom_call_ms_per_step") == 0.0
    assert set(got) == set(want)
    for name, value in want.items():
        assert got[name] == pytest.approx(value, rel=5e-5), name
    shares = ("replay.index_op_share", "replay.relayout_copy_share",
              "agent.matmul_share", "ops.other_fusion_share")
    assert sum(got[s] for s in shares) == pytest.approx(100.0, abs=0.1)


def test_a_cell_without_the_spans_leaves_those_metrics_out(closed):
    """A reader that finds nothing returns nothing: no CPU number, no zero."""
    empty = trace.Trace(devices=[], host=[], window=None)
    got = _metrics("halfcheetah_b256.learn_per", empty, 32, more=CLOSED_LOOP)
    assert {k for k, v in got.items() if v is not None} == {
        "entry.trace_lower_s", "entry.backend_compile_s", "agent.learner_mfu"}
    no_trace = _metrics("halfcheetah_b256.learn_per", None, 32,
                        {"compile.trace_lower_s": 1.0}, more=CLOSED_LOOP)
    assert {k for k, v in no_trace.items() if v is not None} == {"entry.trace_lower_s"}


RECORDED = {1: ("v5e_train_closed_slice.json.gz", 32), 4: ("v5e_dp4_slice.json.gz", 4)}


@pytest.mark.parametrize("cell_name", [w["name"] for w in mf.load()[0]["workloads"]])
def test_a_traced_line_holds_every_metric_the_cell_declares(cell_name):
    """The driver refuses a traced line that lacks a per-layer metric the
    manifest declares for the cell (PR 22's first check: a metric that read
    nothing in XLA-tier cells was declared for all of them). So on a recorded
    trace with as many chips, every declared metric reads a number."""
    manifest, root = mf.load()
    cell = mf.cell(manifest, root, cell_name)
    file, k = RECORDED[cell.chips]
    values = dict(VALUES, **{"device.count": cell.chips})
    got = _metrics(cell_name, trace.load(os.path.join(DATA, file)), k, values)
    assert set(got) == {m["name"] for m in cell.per_layer}
    assert [name for name, value in got.items() if value is None] == []


def test_traced_parts_of_the_last_line(closed):
    device = cellrun.traced_device(closed)
    assert device["window_s"] == pytest.approx(0.298123566)
    assert device["busy_s"] == pytest.approx(0.187580156)
    b = cellrun.breakdown(closed)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    name, seconds = b["device_ops"][0]
    assert name == "%fusion.93 f32[67108864] [fusion kCustom]"
    assert seconds == pytest.approx(0.003625424)
    assert [g[0] for g in b["idle_gaps"]][1] == "host/ingest_chunk"


def test_hlo_text_is_cut_into_name_and_category():
    cut = trace.hlo_category
    assert cut(
        "%fusion.94 = f32[67108864]{0:T(1024)} fusion(f32[67108864]{0:T(1024)} "
        "%fusion.93, s32[8192]{0:T(1024)S(1)} %bitcast.437), kind=kCustom, "
        "calls=%fused_computation.94") == ("%fusion.94 f32[67108864]", "fusion kCustom")
    assert cut(
        "%convolution_add_fusion.21 = f32[256,256]{1,0:T(8,128)S(1)} fusion(f32[256,256]"
        "{1,0:T(8,128)S(1)} %get-tuple-element.2970), kind=kOutput, calls=%fused_computation.3"
    )[1] == "fusion kOutput"
    assert cut(
        '%custom-call.97 = (f32[16,9,9]{2,1,0:T(8,128)}, s32[16,9]{1,0:T(8,128)}) '
        'custom-call(f32[16,9,9]{2,1,0:T(8,128)} %x), custom_call_target="LuDecompositionBlock"'
    )[1] == "custom-call LuDecompositionBlock"
    assert cut("%copy-start.50 = (f32[8192]{0:T(1024)}, f32[8192]{0:T(1024)S(1)}, "
               "u32[]{:S(2)}) copy-start(f32[8192]{0:T(1024)S(1)} %fusion)")[1] == "copy-start"
    assert cut("%all-reduce.3 = f32[256,256]{1,0:T(8,128)} all-reduce(f32[256,256]"
               "{1,0:T(8,128)} %p), replica_groups={{0,1,2,3}}, to_apply=%add")[1] == "all-reduce"
    assert cut("PjitFunction(lane)") == ("PjitFunction(lane)", "")


def test_loader_reads_an_xplane_made_here(tmp_path):
    """The .xplane.pb path on a trace this process records (CPU: host plane
    only): the window annotation and a ``host/`` span come back, on one
    clock, and survive the JSON round trip."""
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("host/megastep_dispatch"):
                step(x).block_until_ready()
    trace.stop()
    path = trace.newest_xplane(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    tr = trace.load(path)
    assert tr.devices == []                       # no TPU plane on the CPU
    names = [h[1] for h in tr.host]
    assert names.count("host/megastep_dispatch") == 3 and trace.WINDOW_SPAN in names
    a, b = tr.window
    assert all(a <= h[2] and h[2] + h[3] <= b for h in tr.host)
    out = os.path.join(tmp_path, "t.json.gz")
    trace.dump(tr, out)
    again = trace.load(out)
    assert again.host == tr.host and tuple(again.window) == tuple(tr.window)


def test_sharded_metrics_on_the_recorded_four_chip_trace():
    """Two executions of 8.394 ms and 8.397 ms on each of four chips. By
    hand, chip 0 per execution: gathers and scatters 7.64 ms, all-gather
    0.30 ms in 48 ops, all-reduce 0.12 ms (0.04 ms on the other chips, which
    wait less), dots 0.10 ms."""
    tr = trace.load(os.path.join(DATA, "v5e_dp4_slice.json.gz"))
    assert [d.name for d in tr.devices] == [f"/device:TPU:{i}" for i in range(4)]
    values = dict(VALUES, **{"device.count": 4, "window.grad_steps_per_s": 476.5,
                             "cost.flops_per_grad_step": 5_911_871_488})
    got = _metrics("humanoid_b256.learn_per_dp4", tr, 4, values, more=CUSTOM_CALL)
    want = {
        "runtime.dispatch_gap_share": 0.0074857,
        "replay.index_op_share": 91.9044,         # all-gather is not a gather
        "replay.relayout_copy_share": 0.0277584,
        "agent.matmul_share": 1.21165,
        "ops.other_fusion_share": 2.39855,
        "parallel.collective_share": 4.97257,        # chip 0, the worst
        "parallel.collective_exposed_share": 4.97257,  # synchronous ops: all exposed
        "device.idle_share": 0.246401,
        "agent.learner_mfu": 100 * 5_911_871_488 * 476.5 / (4 * 197e12),
    }
    for name, value in want.items():
        assert got[name] == pytest.approx(value, rel=5e-5), name
    assert got["ops.custom_call_ms_per_step"] == 0.0
    device = cellrun.traced_device(tr)
    assert device["busy_s"] == pytest.approx(0.01675801325)     # mean of the four
    assert device["window_s"] == pytest.approx(0.016832873)
