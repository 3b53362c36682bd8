"""A product of counted values over a product of counted values, times
``scale`` — e.g. FLOPs per grad step × grad steps per second ÷ (chips ×
peak FLOP/s) × 100. Any missing factor (a CPU rehearsal has no peak and no
rate) gives nothing."""

import math


def reduce(ctx, numerator: list, denominator: list, scale: float = 1.0):
    factors = [ctx.values.get(k) for k in numerator + denominator]
    if any(f is None for f in factors):
        return None
    below = math.prod(float(ctx.values[k]) for k in denominator)
    if below == 0:
        return None
    return scale * math.prod(float(ctx.values[k]) for k in numerator) / below
