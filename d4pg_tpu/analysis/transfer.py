"""Transfer guard: fail on implicit host↔device transfers in steady state.

An *implicit* transfer — a numpy array or python scalar handed straight
to a jitted call — silently re-uploads on every dispatch: a host→device
copy hiding inside a hot loop.  The repo's discipline is: the steady-state
dispatch consumes only device-resident operands; every host→device copy
is an *explicit* ``jax.device_put``/``jnp.asarray`` in a staging step
(replay ``_sample_staged``, the batcher's ``device_put`` of its staging
slot), which the guard deliberately exempts.

:func:`no_implicit_transfers` wraps exactly the dispatch call sites
(trainer train-step dispatch, batcher infer dispatch) behind
``--debug-guards``; any implicit transfer raises jax's
``Disallowed host-to-device transfer`` error at the offending operand
instead of slowly taxing every step. The context is thread-local (jax
config scopes), so the batcher device thread guards only itself.
"""

from __future__ import annotations

import contextlib


@contextlib.contextmanager
def no_implicit_transfers(enabled: bool = True):
    """Context: disallow implicit host→device transfers (explicit
    ``device_put`` stays allowed). No-op when ``enabled`` is False so
    call sites can wrap unconditionally."""
    if not enabled:
        yield
        return
    import jax

    with jax.transfer_guard_host_to_device("disallow"):
        yield


@contextlib.contextmanager
def no_transfers(enabled: bool = True):
    """Zero-transfer phase budget for the device-resident megastep
    dispatch (``replay_placement=device``/``hybrid``): the PR-4 budget —
    "explicit staging only" — tightened to "none".  Inside this scope even
    an *explicit* ``device_put`` raises (``disallow_explicit``), and any
    device→host fetch raises too: the megastep's contract is that state,
    ring, and key are already device-resident and nothing comes back but
    the dispatch handle, so per-grad-step transfer count is exactly zero
    — enforced, not asserted.  Explicit staging (ring ingest, hybrid's
    [K, B] index upload) happens *outside* this scope, in its own
    ``ingest_chunk``/``h2d_stage`` phase.

    The first dispatch of a program must run under the looser
    :func:`no_implicit_transfers` instead: compilation itself stages
    trace-time constants, which is warmup, not steady state.
    """
    if not enabled:
        yield
        return
    import jax

    with jax.transfer_guard_host_to_device("disallow_explicit"):
        with jax.transfer_guard_device_to_host("disallow"):
            yield


@contextlib.contextmanager
def explicit_transfer():
    """Escape hatch for a deliberate transfer *inside* a guarded region
    (prefer restructuring so staging happens outside the guard)."""
    import jax

    with jax.transfer_guard_host_to_device("allow"):
        yield
