"""Device time of the programs whose name matches ``module`` ("XLA Modules"
line), on the device that spends most: milliseconds per execution of the
cell's dispatch program (``per="dispatch"``) or percent of the traced window
(``per="window"``)."""

import re


from cellbench.trace import dispatch_count


def reduce(ctx, module: str, per: str = "dispatch"):
    trace = ctx.trace
    if trace is None or not trace.devices:
        return None
    pat = re.compile(module)
    totals = [sum(m[2] for m in d.modules if pat.search(m[0])) for d in trace.devices]
    if not any(pat.search(m[0]) for d in trace.devices for m in d.modules):
        return None
    if per == "window":
        if not trace.window:
            return None
        return 100.0 * max(totals) / (trace.window[1] - trace.window[0])
    n = dispatch_count(trace, ctx.dispatch_module)
    return max(totals) / 1e6 / n if n else None
