"""The one rule every entry point applies to the CPU backend: it is used
only when it was asked for. JAX comes up on ``cpu`` by itself when an
accelerator plugin fails to initialize; an entry point that then carried on
would report CPU timings under a device's name."""

from __future__ import annotations

import os


def cpu_requested() -> bool:
    """True iff ``JAX_PLATFORMS=cpu`` was exported — a deliberate CPU run
    (tests, rehearsals), as opposed to JAX falling back to the CPU."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
