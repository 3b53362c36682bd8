"""From the profiler's ``.xplane.pb`` to the few event lists the reducers read.

A trace is normalised once (``load``) into plain lists, all on the trace's
own nanosecond clock:

    devices[i].modules  [name, start_ns, dur_ns]            "XLA Modules" line
    devices[i].ops      [name, start_ns, dur_ns, category]  "XLA Ops" line
    devices[i].async_ops  the same, "Async XLA Ops" line (copies and
                        collectives in flight beside the ops)
    host                [thread, name, start_ns, dur_ns]    annotations kept
    window              (start_ns, end_ns) of the ``cellbench/traced_window``
                        annotation the driver opens around the traced work

What a v5e trace holds (read by hand for PR 22, jax 0.9, libtpu 0.0.34): one
plane ``/device:TPU:<n>`` per chip with the lines ``Steps``, ``XLA Modules``
(one event per program execution, named ``jit_<fn>(<fingerprint>)``),
``XLA Ops`` (one event per executed HLO instruction, gapless inside a
program; a ``while`` encloses its body's ops), ``Async XLA Ops`` and ``TC
Overlay``; ``/host:CPU`` with one line per host thread (``python`` carries the
``TraceAnnotation``s, the others PJRT's and the runtime's own spans); and
``#Chip<n> …``, ``/host:metadata``, ``Task Environment`` planes that nothing
here reads. Device and host lines share one clock. An op event has no
``hlo_category`` stat: its *name* is the instruction's whole HLO text, so the
category is cut from that text (``hlo_category`` below) and the name
shortened to ``%instruction shape``.

A trace can be written to and read from JSON (``dump``/``load`` of a
``.json``/``.json.gz``): that is the form of the recorded trace the tests pin
the reducers on, and of the slice every traced run leaves in its output
directory. ``python -m cellbench.trace <file>`` prints a trace's inventory
(planes, lines, counts, stat keys) for reading one by hand.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import sys

WINDOW_SPAN = "cellbench/traced_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULE_LINE, OP_LINE, ASYNC_LINE = "XLA Modules", "XLA Ops", "Async XLA Ops"
KEEP_HOST = re.compile(r"^(host/|cellbench/)")
_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclasses.dataclass
class DeviceTrace:
    name: str
    modules: list
    ops: list
    async_ops: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    devices: list
    host: list
    window: tuple | None

    def to_json(self) -> dict:
        return {"devices": [dataclasses.asdict(d) for d in self.devices],
                "host": self.host, "window": self.window}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls(
            devices=[DeviceTrace(**d) for d in obj["devices"]],
            host=[list(h) for h in obj["host"]],
            window=tuple(obj["window"]) if obj.get("window") else None,
        )

    def clipped(self, a: float, b: float) -> "Trace":
        """The events that lie wholly inside [a, b]."""
        inside = lambda s, d: s >= a and s + d <= b  # noqa: E731
        return Trace(
            devices=[DeviceTrace(
                d.name,
                [m for m in d.modules if inside(m[1], m[2])],
                [o for o in d.ops if inside(o[1], o[2])],
                [o for o in d.async_ops if inside(o[1], o[2])])
                for d in self.devices],
            host=[h for h in self.host if inside(h[2], h[3])],
            window=(a, b),
        )


# ------------------------------------------------------------------ capture
def start(trace_dir: str) -> None:
    """Start the profiler with the Python tracer off (it records every
    Python call and slows the host loop it is meant to observe); host
    annotations (``TraceAnnotation``) and the device tracer stay on."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def newest_xplane(trace_dir: str) -> str | None:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


# --------------------------------------------------------------------- load
def hlo_category(text: str) -> tuple:
    """``(short name, category)`` of an op event named by its HLO text, e.g.
    ``%fusion.94 = f32[67108864]{0:T(1024)} fusion(…), kind=kCustom, calls=…``
    → ``("%fusion.94 f32[67108864]", "fusion kCustom")``. The category is
    the opcode, then a fusion's kind (on this TPU ``kOutput`` fusions hold the
    dots and convolutions, ``kCustom`` the gathers and scatters, ``kLoop``
    the elementwise rest) or a custom call's target."""
    head, sep, rest = text.partition(" = ")
    if not sep or not head.startswith("%"):
        return text, ""
    op = _OPCODE.search(rest)
    if op is None:
        return text[:80], ""
    parts = [op.group(1)]
    kind, target = _KIND.search(rest), _TARGET.search(rest)
    if kind:
        parts.append(kind.group(1))
    if target:
        parts.append(target.group(1))
    shape = rest[: op.start() + 1].split("{", 1)[0]
    return f"{head} {shape[:40]}", " ".join(parts)


def _ops(line) -> list:
    # One parse per distinct instruction, not per execution.
    seen: dict = {}
    out = []
    for e in line.events:
        text = e.name
        if text not in seen:
            short, cat = hlo_category(text)
            if not cat:
                cat = next((str(v) for k, v in e.stats if k == "hlo_category"), "")
            seen[text] = (short, cat)
        short, cat = seen[text]
        out.append([short, e.start_ns, e.duration_ns, cat])
    return out


def from_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, window = [], [], None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = DeviceTrace(plane.name, [], [])
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    dev.modules = [[e.name, e.start_ns, e.duration_ns]
                                   for e in line.events]
                elif line.name == OP_LINE:
                    dev.ops = _ops(line)
                elif line.name == ASYNC_LINE:
                    dev.async_ops = _ops(line)
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if KEEP_HOST.match(name):
                        host.append([line.name, name, e.start_ns, e.duration_ns])
                        if name == WINDOW_SPAN:
                            window = (e.start_ns, e.start_ns + e.duration_ns)
    devices.sort(key=lambda d: int(DEVICE_PLANE.match(d.name).group(1)))
    return Trace(devices, host, window)


def load(path: str) -> Trace:
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return Trace.from_json(json.load(f))
    if path.endswith(".json"):
        with open(path) as f:
            return Trace.from_json(json.load(f))
    return from_xplane(path)


def dump(trace: Trace, path: str) -> None:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump(trace.to_json(), f, separators=(",", ":"))


# ---------------------------------------------------------------- intervals
def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` pairs."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def measure(merged) -> float:
    return float(sum(b - a for a, b in merged))


def busy(events, a: float, b: float) -> float:
    """Time inside [a, b] covered by ``events`` (``[name, start, dur, …]``)."""
    return measure(union((max(e[1], a), min(e[1] + e[2], b)) for e in events
                         if e[1] < b and e[1] + e[2] > a))


def gaps(merged, a: float, b: float) -> list:
    """The ``(start, end)`` stretches of [a, b] that ``merged`` leaves free."""
    out, at = [], a
    for s, e in merged:
        if e <= a or s >= b:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < b:
        out.append((at, b))
    return out


def self_times(events) -> list:
    """``[name, self_ns, category]`` per event of one line, where an event
    that encloses others (a ``while`` around its body's ops) keeps only the
    time its children do not cover — so that sums over a line never count a
    nanosecond twice."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    out = [[e[0], float(e[2]), e[3] if len(e) > 3 else ""] for e in events]
    stack = []   # indices of open events
    for i in order:
        start, end = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            p_end = events[parent][1] + events[parent][2]
            out[parent][1] -= max(0.0, min(end, p_end) - start)
        stack.append(i)
    return out


def dispatch_count(trace: Trace, module_re: str) -> int:
    """Executions of the cell's dispatch program on the first device."""
    if not trace.devices:
        return 0
    pat = re.compile(module_re)
    return sum(1 for m in trace.devices[0].modules if pat.search(m[0]))


# ---------------------------------------------------------------- inventory
def inventory(path: str, out=sys.stdout) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r} stats={[k for k, _ in plane.stats][:12]}", file=out)
        for line in plane.lines:
            events = list(line.events)
            names: dict = {}
            for e in events:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            span = ((events[0].start_ns, events[-1].start_ns + events[-1].duration_ns)
                    if events else None)
            print(f"  LINE {line.name!r} events={len(events)} span_ns={span}", file=out)
            print(f"    most frequent: {top}", file=out)
            for e in events[:3]:
                print(f"    e.g. {e.name!r} start={e.start_ns} dur={e.duration_ns} "
                      f"stats={dict(e.stats)}", file=out)


if __name__ == "__main__":
    inventory(sys.argv[1])
