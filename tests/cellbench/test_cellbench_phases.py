"""The phase metrics (reducer ``phase_time`` over ``cellbench/scopes.py``):
pinned on two recorded five-column v5e slices, and on raw ``.xplane.pb``
files written here byte by byte, which ``jax.profiler.ProfileData`` and the
wire-format reader of ``scopes.py`` must read alike.

``v5e_phases_slice`` holds whole megasteps of ``humanoid_b256.learn_per`` on
one chip, ``v5e_phases_dp4_slice`` of ``humanoid_b256.learn_per_dp4`` on
four, cut by ``python -m cellbench.scopes`` from PR 24's traced runs of the
scoped program. The pins are the reducer's arithmetic on those events; they
are not claims about the chip."""

from __future__ import annotations

import os
import time

import pytest

from cellbench import manifest as mf
from cellbench import scopes, trace
from cellbench.reducers import Context, phase_time

DATA = os.path.join(mf.CODE_ROOT, "cellbench", "testdata")
RECORDED = {1: "v5e_phases_slice.json.gz", 4: "v5e_phases_dp4_slice.json.gz"}
OLD = {1: "v5e_train_closed_slice.json.gz", 4: "v5e_dp4_slice.json.gz"}
K = 32
PHASES = ("replay.draw", "replay.row_gather", "replay.write_back", "agent.networks",
          "ops.projection_loss", "agent.optimizer", "parallel.sync")
VALUES = {"compile.trace_lower_s": 7.0, "compile.backend_compile_s": 1.0,
          "cost.flops_per_grad_step": 700_317_696, "window.grad_steps_per_s": 1500.0,
          "device.count": 1, "peaks.flops_per_s": 197e12}

# ms per dispatch over the slice's two executions of 20.777 and 20.771 ms
PINS_1 = {
    "replay.draw": 1.4702725,           # 22 tree gathers (the descent)
    "replay.row_gather": 0.542007,      # 5 row gathers
    "replay.write_back": 3.7543415,     # 22 scatters, 44 repair gathers, 22 sorts
    "agent.networks": 0.628386,
    "ops.projection_loss": 0.051939,
    "agent.optimizer": 0.0599635,
    "parallel.sync": 0.0,
    "": 14.247445,      # 13.97 of it the two whole-ring copies, named ring.obs / ring.next_obs
}
# the four-chip slice holds one execution a chip, of 22.39 / 22.34 / 22.39 / 22.38 ms
PINS_4 = {
    "replay.draw_ms": 0.427087,         # 22 tree gathers of 2,048 and a scalar all-gather
    "replay.row_gather_ms": 0.139538,
    "replay.write_back_ms": 2.914463,   # the sharded scatters: twice one chip's each
    "agent.networks_ms": 0.60397475,
    "agent.optimizer_ms": 0.250643,
    "ops.projection_loss_ms": 0.038904,
    "parallel.sync_ms": 3.460471,       # the worst chip; the mean is 3.4594845
    # 13.9 of it the two copies; 0.35 the small gathers XLA rewrote as
    # update-slice + all-reduce under the while's name: sync time with no scope
    "device.unscoped_ms": 14.479664,
}


def _cells():
    return [w["name"] for w in mf.load()[0]["workloads"]]


def _metrics(cell_name, tr, reducer=None):
    """The cell's per-layer metrics through their reducers: all, or those
    of one reducer."""
    manifest, root = mf.load()
    cell = mf.cell(manifest, root, cell_name)
    ctx = Context(tr, cell.traffic["dispatch_module"], K,
                  dict(VALUES, **{"device.count": cell.chips}))
    return {m["name"]: mf.reducer(m["file"]["reducer"])(ctx, **m["file"].get("args", {}))
            for m in cell.per_layer if reducer in (None, m["file"]["reducer"])}


def _ctx(tr):
    return Context(tr, "^jit_lane", K, {})


def _alone(tr, dev):
    return trace.Trace([dev], tr.host, tr.window)


def _busy_ms_per_dispatch(dev):
    """One device's time inside the program less its idle gaps, by the
    interval arithmetic the idle share uses."""
    mods = [m for m in dev.modules if m[0].startswith("jit_lane")]
    inside = [o for o in dev.ops
              if any(m[1] <= o[1] and o[1] + o[2] <= m[1] + m[2] for m in mods)]
    return sum(trace.busy(inside, m[1], m[1] + m[2]) for m in mods) / 1e6 / len(mods)


# ------------------------------------------------- the recorded v5e slices
@pytest.mark.parametrize("cell_name", _cells())
def test_every_phase_metric_a_cell_declares_reads_a_number(cell_name):
    chips = next(w["chips"] for w in mf.load()[0]["workloads"] if w["name"] == cell_name)
    tr = trace.load(os.path.join(DATA, RECORDED[chips]))
    got = _metrics(cell_name, tr, reducer="phase_time")
    assert [name for name, value in got.items() if value is None] == []
    if got:     # PERF.md section 7: which cells declare them, and why not all
        assert set(got) >= {"replay.draw_ms", "replay.row_gather_ms",
                            "replay.write_back_ms", "agent.networks_ms",
                            "agent.optimizer_ms", "ops.projection_loss_ms",
                            "device.unscoped_ms"}
        assert ("parallel.sync_ms" in got) == (chips == 4)
        assert all(v > 0 for v in got.values()), got


@pytest.mark.parametrize("chips", [1, 4])
def test_phases_and_unscoped_sum_to_the_programs_busy_time(chips):
    tr = trace.load(os.path.join(DATA, RECORDED[chips]))
    assert all(len(o) == 5 for d in tr.devices for o in d.ops)
    for dev in tr.devices:
        parts = [phase_time.reduce(_ctx(_alone(tr, dev)), p) for p in PHASES + ("",)]
        assert sum(parts) == pytest.approx(_busy_ms_per_dispatch(dev), rel=1e-6)
    if chips == 1:      # no collective on one chip: an absent phase reads 0
        assert phase_time.reduce(_ctx(tr), "parallel.sync") == 0.0
    assert phase_time.reduce(_ctx(tr), "no.such_phase") == 0.0


def test_pins_on_the_one_chip_slice():
    tr = trace.load(os.path.join(DATA, RECORDED[1]))
    got = {p: phase_time.reduce(_ctx(tr), p) for p in PHASES + ("",)}
    assert got == pytest.approx(PINS_1, rel=5e-5)
    per_step = phase_time.reduce(_ctx(tr), "agent.networks", per="grad_step")
    assert per_step == pytest.approx(got["agent.networks"] / K)


def test_pins_on_the_four_chip_slice():
    tr = trace.load(os.path.join(DATA, RECORDED[4]))
    assert [d.name for d in tr.devices] == [f"/device:TPU:{i}" for i in range(4)]
    got = _metrics("humanoid_b256.learn_per_dp4", tr, reducer="phase_time")
    assert got == pytest.approx(PINS_4, rel=5e-5)
    mean = phase_time.reduce(_ctx(tr), "parallel.sync")
    assert mean <= got["parallel.sync_ms"]        # the metric is the worst chip's


@pytest.mark.parametrize("chips", [1, 4])
def test_a_five_column_file_goes_through_every_existing_reducer_unchanged(chips, tmp_path):
    """``trace.load`` does not check a row's length and every reducer
    indexes a row by position: the fifth column changes no old metric."""
    five = trace.load(os.path.join(DATA, RECORDED[chips]))
    four = trace.Trace.from_json(five.to_json())
    for dev in four.devices:
        dev.ops = [o[:4] for o in dev.ops]
    cell = "humanoid_b256.learn_per" if chips == 1 else "humanoid_b256.learn_per_dp4"
    old = lambda got: {k: v for k, v in got.items() if not k.endswith("_ms")}  # noqa: E731
    assert old(_metrics(cell, five)) == old(_metrics(cell, four))
    assert None not in old(_metrics(cell, five)).values()
    again = os.path.join(tmp_path, "again.json.gz")
    scopes.dump(five, again)
    assert scopes.load(again).to_json() == five.to_json()
    clipped = five.clipped(*five.window)
    assert [len(d.ops) for d in clipped.devices] == [len(d.ops) for d in five.devices]
    from cellbench import run as cellrun

    assert cellrun.breakdown(five) == cellrun.breakdown(four)
    assert cellrun.traced_device(five) == cellrun.traced_device(four)


@pytest.mark.parametrize("chips", [1, 4])
def test_a_trace_without_scopes_reads_all_of_it_unscoped(chips):
    """The old recorded traces have four columns and no raw trace to go back
    to: each phase is a measured 0 and ``device.unscoped_ms`` the whole
    program — on a live run, the alarm that the trace lost its scopes."""
    tr = trace.load(os.path.join(DATA, OLD[chips]))
    assert all(len(o) == 4 for d in tr.devices for o in d.ops)
    assert [phase_time.reduce(_ctx(tr), p) for p in PHASES] == [0.0] * len(PHASES)
    for dev in tr.devices:
        unscoped = phase_time.reduce(_ctx(_alone(tr, dev)), "")
        assert unscoped == pytest.approx(_busy_ms_per_dispatch(dev), rel=1e-6)
        assert unscoped > 1.0


def test_nothing_to_read_is_nothing():
    empty = trace.Trace(devices=[], host=[], window=None)
    assert phase_time.reduce(_ctx(None), "replay.draw") is None
    assert phase_time.reduce(_ctx(empty), "") is None
    dev = trace.DeviceTrace("/device:TPU:0", [["jit_other(1)", 0.0, 10.0]],
                            [["%a f32[1]", 1.0, 2.0, "fusion kLoop", ""]])
    assert phase_time.reduce(_ctx(trace.Trace([dev], [], (0.0, 10.0))), "") is None
    dev = trace.DeviceTrace("/device:TPU:0", [["jit_lane(1)", 0.0, 10.0]], [])
    assert phase_time.reduce(_ctx(trace.Trace([dev], [], (0.0, 10.0))), "") is None


# ----------------------------------------- raw .xplane.pb files, made here
def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        if n < 0x80:
            out.append(n)
            return bytes(out)
        out.append((n & 0x7F) | 0x80)
        n >>= 7


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(data)) + data


TF_OP = 7       # the stat metadata id of "tf_op" in the planes made here


def _plane(name: str, lines: dict, op_names: dict) -> bytes:
    """An ``XPlane``: ``lines`` = {line name: (timestamp_ns, [(event name,
    offset_ps, duration_ps)])}, ``op_names`` = {event name: tf_op}."""
    ids, body = {}, _field(2, name)
    for line_name, (at, events) in lines.items():
        line = _field(2, line_name) + _field(3, at)
        for event, offset, dur in events:
            key = ids.setdefault(event, len(ids) + 1)
            line += _field(4, _field(1, key) + _field(2, offset) + _field(3, dur))
        body += _field(3, line)
    for event, key in ids.items():
        meta = _field(1, key) + _field(2, event)
        if event in op_names:
            meta += _field(5, _field(1, TF_OP) + _field(5, op_names[event]))
        body += _field(4, _field(1, key) + _field(2, meta))
    body += _field(5, _field(1, TF_OP) + _field(2, _field(1, TF_OP) + _field(2, "tf_op")))
    return body


WHILE = "%while.5 = (s32[]{:T(128)}, f32[256]{0:T(256)}) while((s32[]) %tuple.1), condition=%c, body=%b"
DRAW = ("%fusion.1 = f32[8192]{0:T(1024)S(1)} fusion(f32[4194304]{0:T(1024)} %bitcast.3), "
        "kind=kCustom, calls=%fused_computation.1")
LOSS = ("%fusion.9 = f32[256,51]{1,0:T(8,128)} fusion(f32[256,51]{1,0:T(8,128)} %p), "
        "kind=kLoop, calls=%fused_computation.9")
COPY = "%copy.7 = bf16[2097152,376]{1,0:T(8,128)(2,1)} copy(bf16[2097152,376]{0,1:T(8,128)(2,1)} %p.2)"
SCATTER = ("%fusion.20 = f32[4194304]{0:T(1024)} fusion(f32[4194304]{0:T(1024)} %q), "
           "kind=kCustom, calls=%fused_computation.20")
OP_NAMES = {
    DRAW: "jit(lane)/ph:replay.draw/gather:",
    # backward of the loss: both tokens inside transpose(jvp(…)); the last wins
    LOSS: ("jit(lane)/while/body/closed_call/transpose(jvp(ph:agent.networks))/"
           "ph:ops.projection_loss/mul:"),
    COPY: "ring.obs:",                      # XLA's copy of an argument: no phase
    SCATTER: "jit(lane)/ph:replay.write_back/scatter:",
}                                           # the while itself has no tf_op


def _write_raw(path: str, start_ns: int, scoped: bool = True) -> tuple:
    """One execution of ``jit_lane`` of 1 µs at ``start_ns``: a ``while`` of
    900 ns around three ops of 300, 200 and 300 ns, then a scatter of 100 ns;
    picoseconds that are no whole nanoseconds. Returns the traced window."""
    ps = start_ns * 1000
    device = _plane("/device:TPU:0", {
        "XLA Modules": (0, [("jit_lane(123)", ps + 400, 1_002_900)]),
        "XLA Ops": (0, [(WHILE, ps + 1_400, 900_000), (DRAW, ps + 1_400, 300_700),
                        (LOSS, ps + 302_400, 200_300), (COPY, ps + 503_400, 300_999),
                        (SCATTER, ps + 901_400, 100_100)]),
        "Async XLA Ops": (0, [("%copy-start.1 = (f32[8]) copy-start(f32[8] %x)", ps, 5_000)]),
    }, OP_NAMES if scoped else {})
    host = _plane("/host:CPU", {"python": (start_ns - 2_000, [
        (trace.WINDOW_SPAN, 1_000_000, 5_000_000), ("host/megastep_dispatch", 1_500_000, 7_000)])}, {})
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(_field(1, device) + _field(1, host))
    return (float(start_ns - 1_000), float(start_ns + 4_000))


def _raw_path(root, cell, name="vm.xplane.pb"):
    return os.path.join(str(root), "cellbench_out", cell, "trace", "plugins",
                        "profile", "2026_09_29", name)


def test_the_wire_reader_and_profiledata_read_a_raw_trace_alike(tmp_path):
    path = _raw_path(tmp_path, "a.cell")
    window = _write_raw(path, 50_000)
    tr = trace.from_xplane(path)             # jax.profiler.ProfileData
    assert tr.window == window == scopes.window(path)
    rows = scopes.read(path)
    assert list(rows) == ["/device:TPU:0"] and len(rows["/device:TPU:0"]) == 5
    assert [r[:4] for r in rows["/device:TPU:0"]] == tr.devices[0].ops
    assert [r[4] for r in rows["/device:TPU:0"]] == [
        "", "replay.draw", "ops.projection_loss", "", "replay.write_back"]
    assert [r[3] for r in rows["/device:TPU:0"]] == [
        "while", "fusion kCustom", "fusion kLoop", "copy", "fusion kCustom"]
    loaded = scopes.load(path)
    assert loaded.devices[0].ops == rows["/device:TPU:0"]
    assert loaded.devices[0].modules == tr.devices[0].modules and loaded.host == tr.host
    assert scopes.by_scope(loaded) == pytest.approx({
        "": 400e-6, "replay.draw": 300e-6, "ops.projection_loss": 200e-6,
        "replay.write_back": 100e-6})


def test_last_token_wins():
    assert scopes.phase_of(OP_NAMES[LOSS]) == "ops.projection_loss"
    assert scopes.phase_of("jit(lane)/jvp(ph:agent.networks)/hidden_0/dot_general:") == "agent.networks"
    assert scopes.phase_of("jit(lane)/shard_map/ph:replay.draw/all_gather") == "replay.draw"
    assert scopes.phase_of("ring.obs:") == "" and scopes.phase_of("") == ""


def test_the_search_is_by_window_not_by_recency(tmp_path):
    """The harness hands the reducer four-column rows clipped to the traced
    window; the raw trace they were cut from is the one with that window,
    though a newer trace (a CPU rehearsal's, another cell's) and a file that
    is no trace lie beside it."""
    mine, newer, junk = (_raw_path(tmp_path, c) for c in ("a.cell", "b.cell", "c.cell"))
    window = _write_raw(mine, 50_000)
    other = _write_raw(newer, 90_000, scoped=False)
    os.makedirs(os.path.dirname(junk))
    with open(junk, "wb") as f:
        f.write(b"\x0f\xff not a trace")
    now = time.time()
    for age, path in ((30, mine), (20, newer), (10, junk)):
        os.utime(path, (now - age, now - age))
    assert phase_time.raw_trace(window, str(tmp_path)) == mine
    assert phase_time.raw_trace(other, str(tmp_path)) == newer
    assert phase_time.raw_trace((1.0, 2.0), str(tmp_path)) is None

    full = trace.load(mine)
    tr = full.clipped(*full.window)                  # what run.py reduces
    assert all(len(o) == 4 for o in tr.devices[0].ops)
    read = lambda p, **kw: phase_time.reduce(  # noqa: E731
        _ctx(tr), p, root=str(tmp_path), **kw)
    # a while's time is not counted twice: 900 ns, of which 800 its body's
    assert {p: read(p) for p in ("replay.draw", "ops.projection_loss",
                                 "replay.write_back", "agent.networks", "")} == pytest.approx({
        "replay.draw": 300e-6, "ops.projection_loss": 200e-6,
        "replay.write_back": 100e-6, "agent.networks": 0.0, "": 400e-6})
    assert read("replay.draw", per="grad_step") == pytest.approx(300e-6 / K)
    # the newer trace has no scopes: all of it is unscoped
    full = trace.load(newer)
    tr = full.clipped(*full.window)
    assert read("") == pytest.approx(1000e-6) and read("replay.draw") == 0.0
    # and with the raw trace gone, so is this one
    os.remove(mine)
    full_rows = trace.Trace.from_json(tr.to_json())
    full_rows.window = window
    assert phase_time.reduce(_ctx(full_rows), "replay.draw", root=str(tmp_path)) == 0.0
