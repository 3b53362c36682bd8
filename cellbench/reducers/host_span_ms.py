"""Host milliseconds per dispatch under annotations whose name matches
``span`` (the program's ``host/<stage>`` spans of ``StageTimers``, on the
profiler's own clock), summed over threads, divided by the number of
executions of the cell's dispatch program in the traced window."""

import re

from cellbench.trace import dispatch_count


def reduce(ctx, span: str):
    if ctx.trace is None:
        return None
    n = dispatch_count(ctx.trace, ctx.dispatch_module)
    pat = re.compile(span)
    durations = [h[3] for h in ctx.trace.host if pat.search(h[1])]
    if not n or not durations:
        return None
    return sum(durations) / 1e6 / n
