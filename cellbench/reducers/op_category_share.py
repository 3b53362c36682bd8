"""Device time of a class of operations inside the cell's dispatch program.

An op of the "XLA Ops" line belongs to the class when ``ops`` matches
``"<hlo category>|<op name>"`` (``invert`` takes the complement: the
remainder after the named classes). Only ops that run inside an execution
of a program matching ``module`` (default: the dispatch program) count, each
with its self time (a ``while`` does not count its body twice). The result
is the mean over the devices (``across="mean"``) or that of the device where
it is largest (``across="max"``: the worst chip, for collectives):

- ``per="module_time"``: percent of those programs' device time;
- ``per="grad_step"``: milliseconds per grad step.

``exposed=true`` keeps only the part of the class's time during which no
other op runs on that device (collectives not hidden behind compute).

Nothing to read means no trace, no execution of the program or no op line. A
class none of whose ops is in a program that ran is a measured 0: the check
wants every metric a cell declares in its line, so a metric may not vanish
when a later PR removes the last op of its class.
"""

import bisect
import re

from cellbench.trace import dispatch_count, measure, self_times, union


def _inside(starts, ends, s, e) -> bool:
    i = bisect.bisect_right(starts, s) - 1
    return i >= 0 and e <= ends[i]


def _subtract(a, b) -> float:
    """Measure of union ``a`` not covered by union ``b``."""
    covered = 0.0
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return measure(a) - covered


def reduce(ctx, ops: str, module: str | None = None, invert: bool = False,
           per: str = "module_time", exposed: bool = False, across: str = "mean"):
    trace = ctx.trace
    if trace is None or not trace.devices:
        return None
    mod_pat, op_pat = re.compile(module or ctx.dispatch_module), re.compile(ops)
    n = dispatch_count(trace, ctx.dispatch_module)
    results = []
    for dev in trace.devices:
        mods = union((m[1], m[1] + m[2]) for m in dev.modules if mod_pat.search(m[0]))
        if not mods or not dev.ops:
            return None
        starts, ends = [m[0] for m in mods], [m[1] for m in mods]
        mine = [o for o in dev.ops if _inside(starts, ends, o[1], o[1] + o[2])]
        hit = [bool(op_pat.search(f"{o[3]}|{o[0]}")) != invert for o in mine]
        selfs = self_times(mine)
        if exposed:
            # leaf ops only: an enclosing ``while`` overlaps its whole body
            leaf = [(o, h) for o, s, h in zip(mine, selfs, hit) if s[1] >= 0.5 * o[2]]
            cls = union((o[1], o[1] + o[2]) for o, h in leaf if h)
            rest = union((o[1], o[1] + o[2]) for o, h in leaf if not h)
            time_ns = _subtract(cls, rest)
        else:
            time_ns = sum(s[1] for s, h in zip(selfs, hit) if h)
        if per == "grad_step":
            if not n:
                return None
            results.append(time_ns / 1e6 / (n * ctx.grad_steps_per_dispatch))
        else:
            results.append(100.0 * time_ns / measure(mods))
    return max(results) if across == "max" else sum(results) / len(results)
