"""The reducer ``span_ms`` (whole ``ph:`` tokens, sub-phases and passes) on
raw ``.xplane.pb`` files written here byte by byte, on the recorded slices,
and with the arguments of the per-layer metrics the torso cells declare
through it.

The raw files hold one device plane whose op events carry the ``op_name``s
a lowered torso really has (``tests/test_phase_scopes.py`` holds those on
the compiled program); the pins are the reducer's arithmetic on them."""

from __future__ import annotations

import os

import pytest
from test_cellbench_phases import _field, _plane, _raw_path

from cellbench import manifest as mf
from cellbench import trace
from cellbench.reducers import Context, phase_time, span_ms

DATA = os.path.join(mf.CODE_ROOT, "cellbench", "testdata")
T3 = ("humanoid_glm47flash_ep8.learn_per_ctx32", "humanoid_keyevl2_ep8.learn_per_ctx8k",
      "humanoid_qwen3next_ep32.learn_per_lin8k")
K = 4
LANE = "jit(lane)/while/body/closed_call/"
BACK = LANE + "ph:agent.networks/transpose(jvp(ph:agent.networks))/jvp()/checkpoint/"
# event name (HLO text) -> (op_name, ns); one of each runs in a dispatch,
# BODY once a trip of the block loop it lies in
OPS = {
    "%f.1 = f32[8,64]{1,0} fusion(f32[8,64]{1,0} %p), kind=kOutput, calls=%c.1": (
        LANE + "ph:agent.networks/jvp(ph:agent.experts)/ph:agent.experts.route/dot_general:", 70),
    "%f.2 = f32[64,64]{1,0} fusion(f32[8,64]{1,0} %p), kind=kCustom, calls=%c.2": (
        BACK + "rematted_computation/ph:agent.experts/ph:agent.experts.dispatch/gather:", 110),
    "%f.3 = f32[8,64]{1,0} fusion(f32[8,64]{1,0} %p), kind=kLoop, calls=%c.3": (
        LANE + "ph:agent.networks/ph:agent.networks.target/checkpoint/ph:agent.experts/mul:", 30),
    "%f.4 = f32[8,64]{1,0} fusion(f32[8,64]{1,0} %p), kind=kOutput, calls=%c.4": (
        LANE + "ph:agent.networks/jvp()/closed_call/dot_general:", 50),
    "%f.5 = f32[8,51]{1,0} fusion(f32[8,51]{1,0} %p), kind=kLoop, calls=%c.5": (
        LANE + "ph:agent.networks/transpose(jvp(ph:ops.projection_loss))/mul:", 20),
    "%f.6 = f32[8,64]{1,0} fusion(f32[8,64]{1,0} %p), kind=kLoop, calls=%c.6": (
        LANE + "ph:agent.networks/ph:agent.networks.target/hidden_0/dot_general:", 40),
    "%f.7 = f32[64]{0} fusion(f32[64]{0} %p), kind=kLoop, calls=%c.7": (
        LANE + "ph:agent.optimizer/mul:", 60),
    "%copy.8 = bf16[64,64]{1,0} copy(bf16[64,64]{0,1} %p.2)": ("", 25),
}
WHILE = "%while.9 = (s32[], f32[64,64]{1,0}) while((s32[]) %tuple.1), condition=%c, body=%b"
BODY = ("%f.10 = f32[16,64]{1,0} fusion(f32[16,64]{1,0} %p), kind=kOutput, calls=%c.10",
        BACK + "ph:agent.experts/ph:agent.experts.blocks/while/body/dot_general:", 90)


def _write(path: str, trips: tuple, scoped: bool = True) -> tuple:
    """One execution of ``jit_lane`` a trip count in ``trips``: the eight
    ops back to back, then a ``while`` (no ``tf_op``, 10 ns of its own)
    around that many body ops. Returns the traced window."""
    start, modules, ops = 50_000, [], []
    at = start
    for n in trips:
        begin = at
        for name, (_, ns) in OPS.items():
            ops.append((name, at * 1000, ns * 1000))
            at += ns
        ops.append((WHILE, at * 1000, (10 + n * BODY[2]) * 1000))
        for i in range(n):
            ops.append((BODY[0], (at + 10 + i * BODY[2]) * 1000, BODY[2] * 1000))
        at += 10 + n * BODY[2]
        modules.append(("jit_lane(123)", begin * 1000, (at - begin) * 1000))
        at += 500
    names = {name: op_name for name, (op_name, _) in OPS.items() if op_name}
    names[BODY[0]] = BODY[1]
    device = _plane("/device:TPU:0", {"XLA Modules": (0, modules), "XLA Ops": (0, ops)},
                    names if scoped else {})
    host = _plane("/host:CPU", {"python": (start - 2_000, [
        (trace.WINDOW_SPAN, 1_000_000, (at - start + 2_000) * 1000)])}, {})
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(_field(1, device) + _field(1, host))
    return (float(start - 1_000), float(at + 1_000))


def _reader(tmp_path, cell: str, trips: tuple, scoped: bool = True):
    path = _raw_path(tmp_path, cell)
    window = _write(path, trips, scoped)
    full = trace.load(path)
    tr = full.clipped(*full.window)              # what run.py reduces: four columns
    assert tr.window == window and all(len(o) == 4 for o in tr.devices[0].ops)
    ctx = Context(tr, "^jit_lane", K, {})
    return ctx, lambda span, **kw: span_ms.reduce(ctx, span, root=str(tmp_path), **kw)


def test_a_phase_reads_what_scope_ms_reads_and_its_parts_sum_to_it(tmp_path):
    ctx, read = _reader(tmp_path, "a.cell", (2, 2))
    old = lambda phase: phase_time.reduce(ctx, phase, root=str(tmp_path))  # noqa: E731
    assert old("agent.experts") == pytest.approx((70 + 110 + 30 + 2 * 90) * 1e-6)
    for parent in ("agent.experts", "agent.optimizer", "ops.projection_loss"):
        assert read(parent) == old(parent), parent
    parts = [read(f"agent.experts.{part}") for part in ("route", "dispatch", "blocks")]
    assert parts == pytest.approx([70e-6, 110e-6, 180e-6])
    assert sum(parts) + read("agent.experts", own=True) == pytest.approx(read("agent.experts"))
    assert read("agent.experts", own=True) == pytest.approx(30e-6)
    # agent.networks nests the other phases: everything under it, where
    # scope_ms books only what is outside them (target pass included)
    assert old("agent.networks") == pytest.approx((50 + 40) * 1e-6)
    assert read("agent.networks", own=True) == pytest.approx(50e-6)
    everything = old("agent.networks") + old("agent.experts") + old("ops.projection_loss")
    assert read("agent.networks") == pytest.approx(everything)
    assert read("agent.experts", per="grad_step") == pytest.approx(read("agent.experts") / K)
    assert read("agent.experts", across="max") == read("agent.experts")
    # the while's own 10 ns carry no op_name: under no span, like the copy
    assert old("") == pytest.approx((25 + 10) * 1e-6)


def test_the_four_passes_partition_what_is_under_the_networks(tmp_path):
    _, read = _reader(tmp_path, "a.cell", (3,))
    by_pass = {p: read("agent.networks", passes=[p]) for p in span_ms.PASSES}
    assert by_pass == pytest.approx({
        "target": (30 + 40) * 1e-6,             # the expert layer's own op and the head's
        "recompute": 110e-6,                    # the dispatch gather run again
        "backward": (20 + 3 * 90) * 1e-6,       # the loss's and the block loop's bodies
        "forward": (70 + 50) * 1e-6})
    assert sum(by_pass.values()) == pytest.approx(read("agent.networks"))
    assert read("agent.networks", passes=list(span_ms.PASSES)) == pytest.approx(
        read("agent.networks"))
    assert read("agent.experts", passes=["recompute", "backward"]) == pytest.approx(380e-6)
    assert read("agent.experts.blocks", passes=["forward"]) == 0.0
    assert read("agent.optimizer", passes=["forward"]) == pytest.approx(60e-6)
    with pytest.raises(ValueError, match="passes"):
        read("agent.networks", passes=["sideways"])
    assert span_ms.pass_of(OPS[next(iter(OPS))][0]) == "forward"
    assert [span_ms.pass_of(BACK + tail) for tail in
            ("ph:agent.experts/add_any:", "rematted_computation/ph:agent.experts/mul:",
             "rematted_computation/ph:agent.networks.target/mul:")] == [
        "backward", "recompute", "target"]


@pytest.mark.parametrize("trips", [(2, 2), (5, 3)])
def test_events_count_a_loop_body_once_a_trip(tmp_path, trips):
    _, read = _reader(tmp_path, "a.cell", trips)
    per_dispatch = sum(trips) / len(trips)
    assert read("agent.experts.blocks", measure="events") == pytest.approx(per_dispatch)
    assert read("agent.experts.blocks") == pytest.approx(per_dispatch * 90e-6)
    assert read("agent.experts.dispatch", measure="events") == 1.0
    assert read("agent.experts", measure="events") == pytest.approx(3 + per_dispatch)
    with pytest.raises(ValueError, match="measure"):
        read("agent.experts", measure="bytes")


def test_a_program_without_the_span_gives_nothing(tmp_path):
    """The parent of the PR that brings a span runs under that PR's benchmark
    files: its trace has paths but no such token, and the metric is left out
    of the line; a trace with no paths at all reads as ``phase_time`` does."""
    _, read = _reader(tmp_path, "a.cell", (2,))
    assert read("agent.indexer.select") is None and read("agent.indexer") is None
    assert read("agent.experts.blocks") is not None
    _, bare = _reader(tmp_path, "b.cell", (2,), scoped=False)
    assert bare("agent.experts.blocks") is None
    assert bare("agent.networks", passes=["target"]) is None
    assert span_ms.reduce(Context(None, "^jit_lane", K, {}), "agent.experts") is None
    empty = trace.Trace(devices=[], host=[], window=None)
    assert span_ms.reduce(Context(empty, "^jit_lane", K, {}), "agent.experts") is None
    dev = trace.DeviceTrace("/device:TPU:0", [["jit_other(1)", 0.0, 10.0]],
                            [["%a f32[1]", 1.0, 2.0, "fusion kLoop", ""]])
    other = trace.Trace([dev], [], (0.0, 10.0))
    assert span_ms.reduce(Context(other, "^jit_lane", K, {}), "agent.experts") is None


def test_the_join_is_remade_for_another_trace(tmp_path):
    """The raw read is kept per file and the join per trace: a second trace,
    or the same cell run again, must not read the first one's."""
    _, first = _reader(tmp_path, "a.cell", (2,))
    assert first("agent.experts.blocks", measure="events") == 2.0
    _, second = _reader(tmp_path, "b.cell", (4,))
    assert second("agent.experts.blocks", measure="events") == 4.0
    assert first("agent.experts.blocks", measure="events") == 2.0


# ------------------------------------------- the torso cells' span metrics
# The per-layer metrics that read the torso's sub-phases and passes:
# (reducer, args, cells), each declared in ``BENCHMARK.json`` with a file of
# its own under ``cellbench/layer_metrics/``.
WANT = {
    "agent.torso_optimizer_ms": ("scope_ms", {"phase": "agent.optimizer"}, T3),
    "agent.torso_networks_rest_ms": ("scope_ms", {"phase": "agent.networks"}, T3),
    "device.torso_unscoped_ms": ("scope_ms", {"phase": ""}, T3),
    "agent.experts_route_ms": ("span_ms", {"span": "agent.experts.route"}, T3),
    "agent.experts_dispatch_ms": ("span_ms", {"span": "agent.experts.dispatch"}, T3),
    "agent.experts_blocks_ms": ("span_ms", {"span": "agent.experts.blocks"}, T3),
    "agent.experts_block_events": (
        "span_ms", {"span": "agent.experts.blocks", "measure": "events"}, T3),
    "agent.attention_scores_ms": ("span_ms", {"span": "agent.attention.scores"}, T3),
    "agent.indexer_scores_ms": ("span_ms", {"span": "agent.indexer.scores"}, T3[1:2]),
    "agent.indexer_select_ms": ("span_ms", {"span": "agent.indexer.select"}, T3[1:2]),
    "agent.lin_solve_ms": ("span_ms", {"span": "agent.linear_attention.solve"}, T3[2:]),
    "agent.lin_scan_ms": ("span_ms", {"span": "agent.linear_attention.scan"}, T3[2:]),
    "agent.torso_recompute_ms": (
        "span_ms", {"span": "agent.networks", "passes": ["recompute"]}, T3),
    "agent.torso_target_ms": ("span_ms", {"span": "agent.networks", "passes": ["target"]}, T3),
}


@pytest.mark.parametrize("name", WANT)
def test_a_torso_metric_to_be_names_an_opened_span_and_cells_that_could_report_it(name):
    from d4pg_tpu.utils.profiling import PHASES

    reducer, args, cells = WANT[name]
    manifest = mf.load()[0]
    assert mf.NAME.match(name) and callable(mf.reducer(reducer))
    opened = args.get("span", args.get("phase"))
    assert opened == "" or opened in PHASES
    # each cell is there and reports what the metric would move
    for cell_name in cells:
        reported = {m["name"] for m in mf.cell(manifest, mf.CODE_ROOT, cell_name).end_to_end}
        assert "transitions_per_s" in reported
    # the manifest declares it once, for those cells, from its own file
    (entry,) = (m for m in manifest["per_layer"] if m["name"] == name)
    spec = mf._read(mf.CODE_ROOT, mf.metric_file(name))
    assert entry["workloads"] == list(cells) and entry["source"] == "program_span"
    assert (spec["reducer"], spec["args"]) == (reducer, args)
    assert entry["moves"] == "transitions_per_s" and entry["better"] == "lower"
    assert entry["layer"] == name.split(".")[0]
    assert entry["unit"] == ("events" if args.get("measure") == "events" else "ms")


@pytest.mark.parametrize("cell_name", T3)
@pytest.mark.parametrize("recorded", ["v5e_phases_slice.json.gz", "v5e_train_closed_slice.json.gz"])
def test_on_a_recorded_slice_every_metric_to_be_reads_a_number(cell_name, recorded):
    """The five-column slice holds a program without the torso's scopes and
    the last two-component token alone; the four-column one no scope at all:
    a sub-phase, a pass and a torso phase read a measured 0 there, never
    nothing (the check wants every declared metric in a traced line, and
    ``test_a_traced_line_holds_every_metric_the_cell_declares`` runs each
    declared metric on these slices)."""
    manifest, root = mf.load()
    cell = mf.cell(manifest, root, cell_name)
    tr = trace.load(os.path.join(DATA, recorded))
    five = recorded == "v5e_phases_slice.json.gz"
    assert all(len(o) == (5 if five else 4) for o in tr.devices[0].ops)
    ctx = Context(tr, cell.traffic["dispatch_module"], 32, {})
    got = {name: mf.reducer(reducer)(ctx, **args)
           for name, (reducer, args, cells) in WANT.items() if cell_name in cells}
    assert len(got) == {T3[0]: 10, T3[1]: 12, T3[2]: 12}[cell_name]
    assert [name for name, value in got.items() if value is None] == []
    assert all(got[name] == 0.0 for name in got if WANT[name][0] == "span_ms")
    assert got["device.torso_unscoped_ms"] > 0.0
    assert (got["agent.torso_optimizer_ms"] > 0.0) == five
    # the fifth column is compared with a phase, as scope_ms compares it
    assert span_ms.reduce(ctx, "agent.optimizer") == got["agent.torso_optimizer_ms"]
    assert span_ms.reduce(ctx, "agent.optimizer", passes=["forward"]) == 0.0
