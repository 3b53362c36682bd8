"""Profiling: XLA trace capture, named annotations, per-stage counters.

The reference's only timing is wall-clock deltas in train logs
(``main.py:250,359``; SURVEY.md §5 'tracing/profiling'). Here:

- :func:`start_trace` / :func:`stop_trace` are the one place the program
  starts the profiler (``Trainer``'s ``--profile-dir``): an XProf-viewable
  trace (HLO timelines, per-op device time) with the Python tracer off;
- :func:`phase` names a phase of the megastep on the DEVICE timeline: a
  ``jax.named_scope`` whose token lands in the ``op_name`` of every HLO
  instruction traced under it, so the trace can be cut by phase
  (:data:`PHASES`; ``cellbench/scopes.py`` reads them back);
- :func:`annotate` tags host-side phases (sample/dispatch/priority-writeback)
  so host stalls show up next to device ops in the trace viewer;
- :class:`StageTimers` keeps cumulative wall-time counters per host
  data-plane stage (env_step / replay_insert / sample / h2d_stage /
  train_dispatch / priority_writeback) that flow into ``metrics.jsonl``
  (via :class:`~d4pg_tpu.runtime.MetricsLogger`) and onto the trace as
  ``host/<stage>`` annotations — the schema is in docs/data_plane.md.

Throughput counters (grad-steps/sec, env-steps/sec, replay occupancy) are
emitted continuously by :class:`d4pg_tpu.runtime.MetricsLogger`.
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax
from d4pg_tpu.analysis import lockwitness


# The phases of one megastep dispatch, from draw to write-back. Each is one
# ``jax.named_scope`` (opened through :func:`phase`, nowhere else) and is
# read by exactly one per-layer metric of the benchmark, ``<name>_ms``
# (PERF.md section 3 has the span -> metric table). A scope is HLO metadata:
# it changes no instruction and costs nothing on the device.
#
# A name of three components, ``<layer>.<phase>.<part>``, is a sub-phase: a
# part of ``<layer>.<phase>``, opened only where that phase is already the
# innermost one. A reader of two-component tokens (``cellbench/scopes.py``)
# stops at the second dot and books every instruction as before; a reader of
# whole tokens (``cellbench/reducers/span_ms.py``) sees the parts, and what
# no part holds is the phase's own remainder.
PHASES = (
    "replay.draw",          # key split, stratified prefixes, descent, IS weights
    "replay.row_gather",    # agent.d4pg.gather_batches
    "replay.write_back",    # write_back_lane, max-priority reduce
    "agent.networks",       # target forwards, both losses forward and backward
    "agent.networks.target",    # the target encoder / torso, actor and critic forwards
    "ops.projection_loss",  # projection, cross-entropy, priority signal
    "agent.optimizer",      # both Adam updates, both Polyak updates
    "parallel.sync",        # train_step's _sync: det_pmean / pmean
    # inside agent.networks, a sequence torso's parts (models/torso.py)
    "agent.attention",      # projections, norms, rotary, scores, softmax, output
    "agent.attention.scores",   # q·k, scale, mask, softmax, P·v: the score tile's whole life
    "agent.experts",        # router, dispatch plan, expert blocks, shared expert, combine
    "agent.experts.route",      # router product, top-k, renormalisation, the dispatch plan
    "agent.experts.dispatch",   # every access over the padded_pairs-row buffer outside the block loops
    "agent.experts.blocks",     # the loops over live blocks and their bodies
    "agent.indexer",        # index projections and scores, the top-k, the alignment loss
    "agent.indexer.scores",     # each chunk's index scores and their recomputation
    "agent.indexer.select",     # the radix select and its tie rule
    "agent.linear_attention",   # Gated DeltaNet: projections, convolution, decays, the chunked scan, output gate
    "agent.linear_attention.solve",  # ops/gated_delta.py: each chunk's (I + L)^-1
    "agent.linear_attention.scan",   # ops/gated_delta.py: the lax.scan over chunks
)
# One path component of an instruction's ``op_name``, no "/" in it:
# ``jit(lane)/while/body/closed_call/jvp(ph:agent.networks)/...``. An
# instruction belongs to the LAST token in its path (innermost scope; the
# backward pass carries the token inside ``transpose(jvp(...))``).
PHASE_PREFIX = "ph:"


def phase(name: str):
    """``jax.named_scope`` of one of :data:`PHASES`."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r} (PHASES: {PHASES})")
    return jax.named_scope(PHASE_PREFIX + name)


def start_trace(log_dir: str) -> None:
    """Start a jax.profiler trace into ``log_dir``, Python tracer off: it
    records every Python call and slows the host loop it is meant to
    observe. Host annotations (:func:`annotate`) and the device tracer —
    where the :func:`phase` scopes show — stay on."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)


def stop_trace() -> None:
    jax.profiler.stop_trace()


def annotate(name: str):
    """Named region that appears on the host timeline of the trace."""
    return jax.profiler.TraceAnnotation(name)


class StageTimers:
    """Cumulative per-stage wall-time counters for the host data-plane.

    One instance per trainer/bench; ``stage(name)`` is a context manager
    that adds the enclosed wall time to the named counter (and, when
    ``annotate_prefix`` is set, also opens a :func:`annotate` region so the
    same stages line up on profiler traces). Thread-safe: the collector,
    learner, write-back, and evaluator threads all report into one set of
    counters, so the jsonl rows show TOTAL host-side time per stage —
    divide by ``stage_<name>_calls`` for per-call cost.

    The canonical stage names (the metrics.jsonl schema, docs/data_plane.md)
    are in :attr:`STAGES`; ``stage()`` accepts any name.
    """

    STAGES = (
        "env_step",            # acting forward + env/pool physics step
        "replay_insert",       # n-step writer emit + ring/tree insert
        "sample",              # PER descent + gather into staging buffers
        "h2d_stage",           # wire-format cast + device_put enqueue
        "train_dispatch",      # jitted train-step dispatch (async enqueue)
        "priority_writeback",  # D2H priority fetch + gen-filtered tree set
        "ingest_chunk",        # device-ring mirror flush (chunked H2D)
        "megastep_dispatch",   # device-resident megastep dispatch (enqueue)
    )

    def __init__(self, annotate_prefix: str | None = "host/"):
        self._prefix = annotate_prefix
        self._lock = lockwitness.named_lock("StageTimers._lock")
        self._acc: dict[str, float] = {}
        self._n: dict[str, int] = {}

    @contextlib.contextmanager
    def stage(self, name: str):
        ann = (
            annotate(self._prefix + name)
            if self._prefix
            else contextlib.nullcontext()
        )
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self._acc[name] = self._acc.get(name, 0.0) + dt
                self._n[name] = self._n.get(name, 0) + 1

    def ensure(self, name: str) -> None:
        """Pin a stage into the scalars at an explicit 0s/0-call count.

        Stages that a mode makes structurally impossible (``h2d_stage``
        under ``replay_placement=device``: there IS no per-dispatch batch
        upload) should read as an explicit zero in every metrics row, not
        be absent — absence is indistinguishable from "telemetry broke",
        and a reader diffing rows across placements would otherwise
        carry the last host-mode value forward as if it were current."""
        with self._lock:
            self._acc.setdefault(name, 0.0)
            self._n.setdefault(name, 0)

    def scalars(self) -> dict:
        """Flat metrics row: ``stage_<name>_s`` cumulative seconds plus
        ``stage_<name>_calls`` — per-stage rates fall out of successive
        jsonl rows by differencing."""
        with self._lock:
            out: dict = {}
            for k, v in self._acc.items():
                out[f"stage_{k}_s"] = v
                out[f"stage_{k}_calls"] = float(self._n[k])
            return out

    def summary_ms(self, per: int | None = None) -> dict:
        """Mean milliseconds per call (or per ``per`` units, e.g. per
        dispatch for stages that run once per dispatch)."""
        with self._lock:
            return {
                k: v * 1e3 / (per if per else max(self._n[k], 1))
                for k, v in self._acc.items()
            }
