"""Metrics: TensorBoard scalars + append-only JSONL.

Same scalar surface as the reference (``avg_test_reward``/``success_rate``
via ``SummaryWriter``, ``main.py:352-353``) plus the throughput counters the
BASELINE targets (grad-steps/sec, env-steps/sec, replay occupancy, per-step
losses). JSONL is the machine-readable log the reference's pickle dicts
(``main.py:255-265``) wanted to be.

Per-stage pipeline telemetry: ``log(..., timers=StageTimers)`` appends the
cumulative host data-plane counters — ``stage_<name>_s`` seconds and
``stage_<name>_calls`` for each of env_step / replay_insert / sample /
h2d_stage / train_dispatch / priority_writeback — to every row, so a
training run's metrics.jsonl carries the breakdown the trace shows as
``host/<stage>`` annotations (schema: docs/data_plane.md).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Mapping
from d4pg_tpu.analysis import lockwitness


def interval_crossed(prev_step: int, step: int, interval: int) -> bool:
    """True when advancing prev_step→step crossed a multiple of interval —
    the shared cadence predicate for eval/checkpoint/publish schedules (train
    loops advance in K-step dispatches, so exact multiples can be skipped
    over)."""
    return step // interval > prev_step // interval


class MetricsLogger:
    """``static`` (ISSUE 15): numeric identity columns stamped onto EVERY
    row — e.g. the league's ``variant_id``/``league_generation`` (the
    serve replica's ``replica_id`` precedent, centralized). Values must be
    numeric (the rows-are-numeric contract ``schema_check`` enforces);
    they ride the JSONL rows only, not TensorBoard (a constant per-step
    scalar chart is noise)."""

    def __init__(self, log_dir: str, use_tensorboard: bool = True,
                 static: Mapping[str, float] = None):
        self.log_dir = log_dir
        self._static = {k: float(v) for k, v in (static or {}).items()}
        os.makedirs(log_dir, exist_ok=True)
        self._jsonl = open(os.path.join(log_dir, "metrics.jsonl"), "a")
        self._tb = None
        if use_tensorboard:
            try:
                # Force tensorboard onto its TF-free stubs instead of lazily
                # importing the full TensorFlow runtime — that import
                # SEGFAULTS when a MuJoCo EGL context is already loaded in
                # the process (dm_control pixel envs; reproduced via
                # faulthandler inside tensorflow's preload_check), and the
                # event-file writer needs none of it. tensorboard switches
                # on the importability of `tensorboard.compat.notf` (a
                # bazel-only marker module absent from the pip package), so
                # provide it.
                import sys
                import types

                sys.modules.setdefault(
                    "tensorboard.compat.notf",
                    types.ModuleType("tensorboard.compat.notf"),
                )
                from torch.utils.tensorboard import SummaryWriter

                self._tb = SummaryWriter(log_dir)
            except Exception as e:
                # TensorBoard is an optional sink with many failure modes
                # (no torch, proto version skew, read-only dir); training
                # must proceed on JSONL alone — but say so, once.
                print(f"[metrics] tensorboard writer disabled ({e!r})")
                self._tb = None
        self._t0 = time.monotonic()
        # log() is called from the learner thread (replaced-request train
        # rows) AND the evaluator thread (completed evals); serialize so
        # jsonl lines never interleave mid-record.
        self._log_lock = lockwitness.named_lock("MetricsLogger._log_lock")

    def log(self, step: int, scalars: Mapping[str, float], timers=None) -> None:
        """``timers`` (a :class:`~d4pg_tpu.utils.profiling.StageTimers`)
        appends the per-stage cumulative counters to the row without
        polluting the caller's scalars dict (console prints stay clean)."""
        merged = {k: float(v) for k, v in scalars.items()}
        if timers is not None:
            merged.update(timers.scalars())
        rec = {"step": int(step), "t": time.monotonic() - self._t0}
        rec.update(self._static)
        rec.update(merged)
        with self._log_lock:
            self._jsonl.write(json.dumps(rec) + "\n")
            self._jsonl.flush()
            if self._tb is not None:
                for k, v in merged.items():
                    self._tb.add_scalar(k, float(v), int(step))

    def close(self) -> None:
        # Under the log lock so a concurrent log() can never be torn by the
        # file closing between its write and flush.
        with self._log_lock:
            self._jsonl.close()
            if self._tb is not None:
                self._tb.close()
