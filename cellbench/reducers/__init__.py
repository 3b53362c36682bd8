"""Reducers: ``reduce(ctx, **args) -> float | None``, one module each, found
by the name a layer-metric file gives under ``"reducer"``. ``ctx`` is a
:class:`Context`; a reducer that finds nothing to read returns ``None`` and
the harness leaves that metric out of the line."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Context:
    trace: object            # cellbench.trace.Trace, or None (no device trace)
    dispatch_module: str     # regex of the cell's dispatch program's name
    grad_steps_per_dispatch: int
    values: dict             # flat counters: "compile.*", "window.*", "cost.*",
                             # "peaks.*", "device.count"
