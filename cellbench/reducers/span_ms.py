"""Device milliseconds — or op events — per dispatch under one span of the
program: a phase, one of its sub-phases, or one pass through either.

``scope_ms`` reads the *last two-component* token of an instruction's
``op_name`` and throws the rest of the path away. This reads the whole path
(``d4pg_tpu/utils/profiling.py:PHASES``; PERF.md section 3 has the table):

**Spans.** A full token is ``ph:<layer>.<phase>`` or, one level deeper,
``ph:<layer>.<phase>.<part>``. An op is *under* ``span`` when some token on
its path is ``span`` or one of its sub-phases. A sub-phase is opened only
inside its own phase and no phase but ``agent.networks`` nests another
(``tests/test_phase_scopes.py`` holds both on every torso), so for every
other phase "under it" and "booked to it" are the same ops:
``span_ms(agent.experts)`` is ``scope_ms(agent.experts)`` to the digit, and a
phase's sub-phases and its own remainder (``own=True``: the ops whose last
token is ``span`` itself) sum to it. ``agent.networks`` means everything
under it, the mixers and the loss included.

**Passes** (``passes``: any of the four; they partition what is under
``agent.networks``). ``jax`` writes the pass on every instruction's path:

    target     …/ph:agent.networks/ph:agent.networks.target/…
    recompute  …/transpose(jvp(ph:agent.networks))/jvp(…)/checkpoint/rematted_computation/ph:agent.experts/…
    backward   …/transpose(jvp(ph:agent.networks))/jvp(…)/checkpoint/ph:agent.experts/…   (no rematted_computation)
    forward    …/jvp(ph:agent.experts)/…                                   (none of the three)

in that order: the first that fits. A scope opened inside a custom VJP's
``bwd`` comes after the forward call's.

**Measure.** ``"ms"``: ``phase_time``'s arithmetic — self time
(``trace.self_times``: a ``while`` does not count its body) of the matching
ops of the "XLA Ops" line inside executions of the dispatch program, per
execution (``per="dispatch"``) or grad step, the mean over the devices or
with ``across="max"`` the largest. ``"events"``: how many op events matched
instead. The line has one event per *executed* instruction, so the events
under a loop follow its trip count: ``agent.experts.blocks`` counts the live
blocks the seed's routing made, where its milliseconds also move with where
the heap put the buffers.

**Where the paths come from.** The harness's rows are ``[short, start,
dur, category]``; the raw ``.xplane.pb`` they were cut from is found as
``phase_time`` finds it (by the traced window), its events' ``tf_op`` read
with ``cellbench/scopes.py``'s wire-format reader and joined to the rows by
``(short, start, dur)``; the read is kept per file and the join per trace,
because a torso cell asks this module a dozen times in one run. A recorded
five-column slice keeps only the last two-component token: ``span`` is
compared with it, and a sub-phase or a pass — which that column cannot
tell — reads a measured 0, as every span does with no scopes to be had at
all (an old four-column recording). Nothing to read (``None``): no trace, no
execution of the program, no op line — or a raw trace none of whose
instructions carries the span's token (with ``passes=["target"]``: the
target's): the program that ran does not open it, as the parent of the PR
that brought a span does not.

``python -m cellbench.reducers.span_ms <file.xplane.pb> [module regex]``
prints device ms and events per dispatch by last full token and pass.
"""

from __future__ import annotations

import functools
import re
import sys

from cellbench import manifest, scopes, trace
from cellbench.reducers import phase_time
from cellbench.reducers.op_category_share import _inside

TOKEN = re.compile(r"ph:([a-z_]+(?:\.[a-z_]+)+)")
TARGET = "agent.networks.target"
PASSES = ("target", "recompute", "backward", "forward")


def spans_of(op_name: str) -> list:
    """The full tokens on an instruction's path, outermost first."""
    return TOKEN.findall(op_name)


def pass_of(op_name: str) -> str:
    if TARGET in spans_of(op_name):
        return "target"
    if "rematted_computation" in op_name:
        return "recompute"
    return "backward" if "transpose(" in op_name else "forward"


def _is(token: str, span: str) -> bool:
    return token == span or token.startswith(span + ".")


# ------------------------------------------------------------ the raw trace
@functools.lru_cache(maxsize=2)
def _paths(path: str, size: int, mtime: float) -> dict:
    """``{device: ({(short, start_ns, dur_ns): id}, [(tokens, pass) by id])}``
    of a raw trace's "XLA Ops" lines, read from the whole ``tf_op``: one
    entry a distinct instruction."""
    out = {}
    for plane in scopes._planes(path):
        if not trace.DEVICE_PLANE.match(plane.name):
            continue
        names, ids, marks, rows = plane.event_names(), {}, [], {}
        for span in plane.lines:
            for key, start, dur in plane.line(span, only=trace.OP_LINE):
                if key not in ids:
                    name, op_name = names.get(key, ("", ""))
                    ids[key] = (trace.hlo_category(name)[0], len(marks))
                    marks.append((spans_of(op_name), pass_of(op_name)))
                short, i = ids[key]
                rows[(short, start, dur)] = i
        out[plane.name] = (rows, marks)
    return out


_JOINED: list = [None, None]    # the last (trace, raw file, program) joined, and the join


def _joined(tr, module: str, raw: str | None) -> list | None:
    """Per device ``(self_ns, key, marks)``: the self times of the ops
    inside executions of ``module`` and what names each — an index into the
    raw trace's ``marks`` (-1: no such event there), or the row's fifth
    column with ``marks`` ``None`` — or nothing where a device has no
    execution or no op line."""
    stat = phase_time._stat(raw) if raw else None
    ident = (id(tr), tr.window, tuple(len(d.ops) for d in tr.devices), module, stat)
    if _JOINED[0] == ident:
        return _JOINED[1]
    by_device = _paths(*stat) if stat else {}
    mod_pat, devices = re.compile(module), []
    for dev in tr.devices:
        mods = trace.union((m[1], m[1] + m[2]) for m in dev.modules if mod_pat.search(m[0]))
        if not mods or not dev.ops:
            devices = None
            break
        starts, ends = [m[0] for m in mods], [m[1] for m in mods]
        mine = [o for o in dev.ops if _inside(starts, ends, o[1], o[1] + o[2])]
        selfs = [s[1] for s in trace.self_times(mine)]
        if raw:
            rows, marks = by_device.get(dev.name, ({}, []))
            keys = [rows.get((o[0], o[1], o[2]), -1) for o in mine]
        else:
            keys, marks = [o[4] if len(o) > 4 else "" for o in mine], None
        devices.append((selfs, keys, marks))
    _JOINED[:] = ident, devices
    return devices


def reduce(ctx, span: str, passes=None, own: bool = False, measure: str = "ms",
           per: str = "dispatch", across: str = "mean", root: str | None = None):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    n = trace.dispatch_count(tr, ctx.dispatch_module)
    if not n:
        return None
    unknown = set(passes or ()) - set(PASSES)
    if unknown or measure not in ("ms", "events"):
        raise ValueError(f"span_ms: passes {sorted(unknown)} / measure {measure!r}")
    have = all(len(d.ops[0]) > 4 for d in tr.devices if d.ops)
    raw = None if have or not tr.window else phase_time.raw_trace(
        tr.window, root or manifest.CODE_ROOT)
    devices = _joined(tr, ctx.dispatch_module, raw)
    if devices is None:
        return None
    want = TARGET if passes and set(passes) == {"target"} else span
    if raw and not any(_is(t, want) for _, _, marks in devices for tokens, _ in marks
                       for t in tokens):
        return None         # the program that ran opens no such span
    results = []
    for selfs, keys, marks in devices:
        if marks is None:       # the fifth column, or nothing: no path to read
            hit = [key == span and passes is None for key in keys]
        else:
            fits = [(tokens[-1:] == [span] if own else any(_is(t, span) for t in tokens))
                    and (passes is None or which in passes) for tokens, which in marks]
            hit = [key >= 0 and fits[key] for key in keys]
        total = (sum(s for s, h in zip(selfs, hit) if h) / 1e6 if measure == "ms"
                 else float(sum(hit)))
        results.append(total / (n * ctx.grad_steps_per_dispatch if per == "grad_step" else n))
    return max(results) if across == "max" else sum(results) / len(results)


# --------------------------------------------------------------- by hand
def table(path: str, module: str = "^jit_lane") -> list:
    """``[(last full token, pass, ms a dispatch, events a dispatch)]`` of the
    first device of a raw trace, inside its traced window."""
    full = trace.load(path)
    tr = full.clipped(*full.window) if full.window else full
    n = trace.dispatch_count(tr, module)
    devices = _joined(tr, module, path)
    if not n or not devices:
        return []
    selfs, keys, marks = devices[0]
    out: dict = {}
    for self_ns, key in zip(selfs, keys):
        tokens, which = marks[key] if key >= 0 else ([], "")
        under = any(_is(t, "agent.networks") for t in tokens)
        row = out.setdefault((tokens[-1] if tokens else "", which if under else "-"), [0.0, 0])
        row[0] += self_ns / 1e6 / n
        row[1] += 1 / n
    return sorted(((*k, *v) for k, v in out.items()), key=lambda r: -r[2])


if __name__ == "__main__":
    for token, which, ms, events in table(*sys.argv[1:3]):
        print(f"{ms:12.3f} ms {events:12.1f} events  {token or '(no phase)':34s} {which}")
