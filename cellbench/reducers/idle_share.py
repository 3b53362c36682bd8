"""Percent of the time in which nothing ran on the device, on the idlest
device: 1 − union of the intervals of ``line`` ("ops": single operations, so
gaps inside a program count as idle; "modules": whole program executions, so
only the gaps between programs do) ÷ the stretch from the first program
execution that lies wholly inside the traced window to the end of the last
one. Measuring between executions keeps the window's ragged edges (half an
execution on either side) out of the share."""

from cellbench.trace import busy


def reduce(ctx, line: str = "ops"):
    trace = ctx.trace
    if trace is None or not trace.devices:
        return None
    shares = []
    for dev in trace.devices:
        events = dev.ops if line == "ops" else dev.modules
        if not dev.modules or not events:
            return None
        a = min(m[1] for m in dev.modules)
        b = max(m[1] + m[2] for m in dev.modules)
        shares.append(100.0 * (1.0 - busy(events, a, b) / (b - a)))
    return max(shares)
