"""The program's phase scopes, read back from the profiler's ``.xplane.pb``.

The megastep opens seven ``jax.named_scope``s (``d4pg_tpu/utils/profiling.py:
PHASES``); each puts one token, ``ph:<layer>.<phase>``, into the ``op_name``
of every HLO instruction traced under it. This module finds that ``op_name``
on the device's op events and adds it to the normalised rows of
``cellbench/trace.py`` as a fifth column:

    [short name, start_ns, dur_ns, category, scope]     scope "" = no phase

Where the v5e trace keeps ``op_name`` (PR 24's step 0, read by hand from a
traced run of ``humanoid_b256.learn_per`` before any scope existed; jax 0.9):
**not** on the op event — its own stats are ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``, and its name, the
instruction's HLO text, has no ``metadata={…}`` part — but on the event's
*metadata* record, which ``jax.profiler.ProfileData`` does not show: every
distinct instruction has one ``XEventMetadata`` with the stats
``hlo_category``, ``program_id``, ``symbol_id``, ``flops``, ``model_flops``,
``bytes_accessed``, ``memory_access_breakdown``, ``raw_bytes_accessed``,
``shape_with_layout``, ``source``, ``source_stack``, ``deduplicated_name``
and **``tf_op``** = ``<op_name>:`` (xprof's "<name>:<type>" with an empty
type), e.g. ``jit(lane)/while/body/closed_call/transpose(jvp(Critic))/
hidden_1/dot_general:``. 473 of that program's 972 instructions had one,
99.3% of its device time; the rest were the ``while`` itself, copy-start/
-done pairs and small layout copies. The whole-ring relayout copies XLA adds
before the row gather carry the name of the *argument* they copy
(``ring.obs:``), not of the gather they serve, so they stand under no phase.
(``/host:metadata`` also holds each program's whole ``HloProto``, with the
``op_name`` of the instructions inside a fusion; nothing here reads it.)

So the file is read as protobuf wire format, with the few field numbers of
``tsl/profiler/protobuf/xplane.proto`` that are needed and no dependency. An
instruction is booked to the LAST token of its ``op_name`` (the innermost
scope; a backward op carries it inside ``transpose(jvp(…))``), a fusion to
the ``op_name`` XLA gave the fusion instruction, which is its root's.
Times are cut exactly as ``ProfileData`` cuts them (whole nanoseconds), so
the first four columns equal ``trace.from_xplane``'s rows value for value.

The five-column JSON goes through ``trace.dump``/``trace.load`` and every
reducer unchanged: they index a row by position. ``python -m
cellbench.scopes <file.xplane.pb>`` prints device milliseconds by scope;
with ``<out.json.gz> <seconds> <note…>`` it writes the first seconds of the
traced window as a five-column slice, times from the slice's start (the
recorded traces of ``tests/cellbench/test_cellbench_phases.py``).
"""

from __future__ import annotations

import gzip
import json
import re
import sys

from cellbench import trace

TOKEN = re.compile(r"ph:([a-z_]+\.[a-z_]+)")
OP_NAME_STAT = "tf_op"


def phase_of(op_name: str) -> str:
    """The phase an instruction is booked to: the last token in its path."""
    tokens = TOKEN.findall(op_name)
    return tokens[-1] if tokens else ""


# ------------------------------------------------------- protobuf wire format
def _varint(buf, pos: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf, start: int, end: int):
    """``(field number, value)`` of one message: an int for a varint, a
    ``(start, end)`` pair into ``buf`` for a length-delimited field, nothing
    for the fixed-width ones (no field read here is one)."""
    pos = start
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
        elif wire in (1, 5):
            value, pos = None, pos + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _int64(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _map(buf, spans) -> dict:
    """A ``map<int64, Message>`` field's entries: key → the value's span."""
    out = {}
    for span in spans:
        entry = dict(_fields(buf, *span))
        out[_int64(entry.get(1, 0))] = entry[2]
    return out


class _Plane:
    """One ``XPlane`` (name 2, lines 3, event_metadata 4, stat_metadata 5),
    decoded only as far as it is asked."""

    def __init__(self, buf, span):
        self.buf, self.name, self.lines = buf, "", []
        self._events, self._stats = [], []
        for num, value in _fields(buf, *span):
            if num == 2:
                self.name = _text(buf, value)
            elif num == 3:
                self.lines.append(value)
            elif num == 4:
                self._events.append(value)
            elif num == 5:
                self._stats.append(value)

    def line(self, span, only: str | None = None) -> list:
        """``XLine``: name 2, timestamp_ns 3, events 4 → ``[(metadata id,
        start_ns, dur_ns)]``, empty for a line that is not the ``only`` one
        wanted. ``XEvent``: metadata_id 1, offset_ps 2, duration_ps 3."""
        buf, name, at, events = self.buf, "", 0, []
        for num, value in _fields(buf, *span):
            if num == 2:
                name = _text(buf, value)
            elif num == 3:
                at = _int64(value)
            elif num == 4:
                events.append(value)
        out = []
        for ev in events if only in (None, name) else ():
            f = {n: v for n, v in _fields(buf, *ev) if n in (1, 2, 3)}
            out.append((_int64(f.get(1, 0)),
                        float(at + _int64(f.get(2, 0)) // 1000),
                        float(_int64(f.get(3, 0)) // 1000)))
        return out

    def event_names(self) -> dict:
        """Event metadata id → ``(name, op_name)``. ``XEventMetadata``:
        name 2, stats 5; ``XStat``: metadata_id 1, str_value 5, ref_value 7
        (a string kept once, as a stat metadata's name); ``XStatMetadata``:
        name 2."""
        buf = self.buf
        stat_names = {}
        for key, span in _map(buf, self._stats).items():
            stat_names[key] = next(
                (_text(buf, v) for n, v in _fields(buf, *span) if n == 2), "")
        out = {}
        for key, span in _map(buf, self._events).items():
            name, op_name = "", ""
            for num, value in _fields(buf, *span):
                if num == 2:
                    name = _text(buf, value)
                elif num == 5:
                    stat = dict(_fields(buf, *value))
                    if stat_names.get(_int64(stat.get(1, 0))) == OP_NAME_STAT:
                        op_name = (_text(buf, stat[5]) if 5 in stat
                                   else stat_names.get(stat.get(7), ""))
            out[key] = (name, op_name)
        return out


def _planes(path: str):
    with open(path, "rb") as f:
        buf = f.read()
    for num, span in _fields(buf, 0, len(buf)):     # XSpace: planes 1
        if num == 1:
            yield _Plane(buf, span)


# ------------------------------------------------------------------- reading
def read(xplane_path: str) -> dict:
    """``{device plane name: [[short, start_ns, dur_ns, category, scope]]}``
    of the "XLA Ops" lines, in the file's order — ``trace.from_xplane``'s
    op rows with the phase of each as a fifth column."""
    out = {}
    for plane in _planes(xplane_path):
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        names, cut = plane.event_names(), {}
        rows = out.setdefault(plane.name, [])
        for span in plane.lines:
            for key, start, dur in plane.line(span, only=trace.OP_LINE):
                if key not in cut:      # one parse per distinct instruction
                    name, op_name = names.get(key, ("", ""))
                    cut[key] = (*trace.hlo_category(name), phase_of(op_name))
                short, category, scope = cut[key]
                rows.append([short, start, dur, category, scope])
    return out


def window(xplane_path: str) -> tuple | None:
    """``(start_ns, end_ns)`` of the file's ``cellbench/traced_window``
    annotation, as ``trace.from_xplane`` gives it, or nothing."""
    found = None
    for plane in _planes(xplane_path):
        if plane.name != trace.HOST_PLANE:
            continue
        names = plane.event_names()
        for span in plane.lines:
            for key, start, dur in plane.line(span):
                if names.get(key, ("",))[0] == trace.WINDOW_SPAN:
                    found = (start, start + dur)
    return found


def load(path: str) -> trace.Trace:
    """``trace.load`` with five-column op rows where the file has scopes to
    give: a ``.xplane.pb`` gets them from :func:`read`, a JSON has them or
    not as it was dumped."""
    tr = trace.load(path)
    if not path.endswith((".json", ".json.gz")):
        scoped = read(path)
        for dev in tr.devices:
            dev.ops = scoped[dev.name]
    return tr


dump = trace.dump


def by_scope(tr: trace.Trace) -> dict:
    """Device milliseconds of self time by scope, first device."""
    out: dict = {}
    ops = tr.devices[0].ops
    for op, (_, self_ns, _) in zip(ops, trace.self_times(ops)):
        scope = op[4] if len(op) > 4 else ""
        out[scope] = out.get(scope, 0.0) + self_ns / 1e6
    return out


def cut(tr: trace.Trace, seconds: float) -> trace.Trace:
    """The first ``seconds`` of the traced window, times from its start."""
    a = tr.window[0]
    part = tr.clipped(a, a + seconds * 1e9)
    for dev in part.devices:
        for rows in (dev.modules, dev.ops, dev.async_ops):
            rows[:] = [[r[0], r[1] - a, *r[2:]] for r in rows]
    part.host = [[h[0], h[1], h[2] - a, h[3]] for h in part.host]
    part.window = (0.0, seconds * 1e9)
    return part


if __name__ == "__main__":
    full = load(sys.argv[1])
    clipped = full.clipped(*full.window) if full.window else full
    if len(sys.argv) > 2:
        body = cut(clipped, float(sys.argv[3])).to_json()
        body["note"] = " ".join(sys.argv[4:])
        with gzip.open(sys.argv[2], "wt") as f:
            json.dump(body, f, separators=(",", ":"))
    else:
        for scope, ms in sorted(by_scope(clipped).items(), key=lambda kv: -kv[1]):
            print(f"{ms:12.3f} ms  {scope or '(no phase)'}")
