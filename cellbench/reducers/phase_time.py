"""Device milliseconds per dispatch inside one phase of the megastep.

The program opens seven ``jax.named_scope``s from draw to write-back
(``d4pg_tpu/utils/profiling.py:PHASES``); ``cellbench/scopes.py`` reads them
back from the trace. This is the self time (``trace.self_times``: a ``while``
does not count its body) of the ops of the "XLA Ops" line whose scope is
``phase`` — ``""``: the ops under no phase — inside executions of a program
matching ``module`` (default: the dispatch program), divided by the
executions of the dispatch program (``per="dispatch"``) or by its grad steps
(``per="grad_step"``); the mean over the devices, or with ``across="max"``
the device where it is largest. An op belongs to the last token in its
``op_name``, a fusion to the scope XLA gave the fusion instruction (its
root's). Over the phases and ``""`` the values sum to the program's device
time less its idle gaps.

Where the scopes come from: the fifth column of the trace's op rows, if they
have one (a recorded five-column slice). The harness's own rows have four —
``trace.from_xplane`` drops every stat, and the context has neither the
``.xplane.pb`` nor the cell — so otherwise the raw trace this one was cut
from is looked for: the ``*.xplane.pb`` under ``<root>/cellbench_out/*/
trace/`` (``root``: the manifest's checkout) whose ``cellbench/traced_window``
annotation equals the trace's window to the nanosecond, newest first. The
window is the trace's identity; recency is not (CPU rehearsals leave traces
in those directories too). The raw read is kept per file (path, size,
mtime): eight metrics call this in one run.

With no scopes to be had — an old recorded trace, a raw trace that is gone —
every op is under no phase: each phase reads 0 and ``""`` the whole program.
**On a live run that is the alarm**: the trace lost its scopes (an executable
from a compile cache filled before a scope changed, a renamed stat). Nothing
to read means no trace, no execution of the program or no op line.
"""

import functools
import glob
import os
import re

from cellbench import manifest, scopes
from cellbench.reducers.op_category_share import _inside
from cellbench.trace import dispatch_count, self_times, union


@functools.lru_cache(maxsize=64)
def _window(path: str, size: int, mtime: float):
    try:
        return scopes.window(path)
    except (ValueError, IndexError, KeyError):    # not a trace: pass it over
        return None


@functools.lru_cache(maxsize=2)
def _scopes(path: str, size: int, mtime: float) -> dict:
    """``{device: {(short, start_ns, dur_ns): scope}}`` of a raw trace."""
    return {name: {(r[0], r[1], r[2]): r[4] for r in rows}
            for name, rows in scopes.read(path).items()}


def _stat(path: str) -> tuple:
    st = os.stat(path)
    return path, st.st_size, st.st_mtime


def raw_trace(window, root: str) -> str | None:
    """The raw trace under ``root`` whose traced window is ``window``."""
    files = glob.glob(os.path.join(
        root, "cellbench_out", "*", "trace", "**", "*.xplane.pb"), recursive=True)
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        found = _window(*_stat(path))
        if found and all(abs(f - w) < 1 for f, w in zip(found, window)):
            return path
    return None


def reduce(ctx, phase: str, per: str = "dispatch", across: str = "mean",
           module: str | None = None, root: str | None = None):
    trace = ctx.trace
    if trace is None or not trace.devices:
        return None
    mod_pat = re.compile(module or ctx.dispatch_module)
    n = dispatch_count(trace, ctx.dispatch_module)
    if not n:
        return None
    have = all(len(d.ops[0]) > 4 for d in trace.devices if d.ops)
    raw = None if have or not trace.window else raw_trace(
        trace.window, root or manifest.CODE_ROOT)
    by_device = _scopes(*_stat(raw)) if raw else {}
    results = []
    for dev in trace.devices:
        mods = union((m[1], m[1] + m[2]) for m in dev.modules if mod_pat.search(m[0]))
        if not mods or not dev.ops:
            return None
        starts, ends = [m[0] for m in mods], [m[1] for m in mods]
        mine = [o for o in dev.ops if _inside(starts, ends, o[1], o[1] + o[2])]
        found = by_device.get(dev.name, {})
        time_ns = sum(
            s[1] for o, s in zip(mine, self_times(mine))
            if (o[4] if have else found.get((o[0], o[1], o[2]), "")) == phase)
        results.append(time_ns / 1e6 / (
            n * ctx.grad_steps_per_dispatch if per == "grad_step" else n))
    return max(results) if across == "max" else sum(results) / len(results)
