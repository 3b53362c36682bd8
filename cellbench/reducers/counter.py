"""A number the harness or the program counted: ``key`` into the context's
flat values (``compile.trace_lower_s``, ``window.dispatches`` …)."""


def reduce(ctx, key: str, scale: float = 1.0):
    value = ctx.values.get(key)
    return None if value is None else scale * float(value)
