"""The closed loop users run: ``Trainer(cfg).train()`` — collection → host
buffer → ring ingest → megastep — with the window taken from outside.

No cell of the manifest uses this driver today: the loop starts on an empty
ring, and a cell has to open its window on a full one (PERF.md section 7 has
what ran on the chip and what brings the cell back). Tier-1 rehearses it on a
cell made of added files.

How the window is taken without touching the program: ``train()`` runs once,
with a step budget, eval and checkpoint intervals it never reaches (the mix's
argv), on the main thread. A watcher thread waits until ``warm_dispatches``
megasteps have been dispatched (by then the collector, ``ring_ingest``,
``tree_ingest`` and the megastep have compiled and the loop is in its
stride), then takes both edges of the window *on a tick of the loop*: it
polls ``Trainer.env_steps`` (``grad_steps`` where nothing is collected) every
half millisecond until it changes and reads the clock there. Between the
edges it sleeps ``--seconds`` in long naps — nothing of the harness runs
inside the window but those two short polls. So the window holds a whole
number of collect → ingest → megastep cycles and neither counter is cut
mid-cycle (a collect adds 512 env steps at once: a window cut anywhere
would jitter by a cycle, 1.5% of a 10 s window). It then traces a further
short window if asked and ends the run through
``Trainer.request_preemption()``, the program's own way out (a checkpoint,
no eval). The counters are the host's: the sync collector's ``device_get``
ties the host to the device once per cycle, so both edges sit at the same
point of the cycle and the lag cancels.

Surface into the program: ``train.build_parser``/``config_from_args``,
``Trainer(cfg)`` ``.train(n)`` / ``.close()`` / ``.request_preemption()`` /
``.grad_steps`` / ``.env_steps`` / ``.state`` / ``.buffer``, and, for the
tree's integrity after ingest and write-back have interleaved,
``Trainer._dev_per.tree``.
"""

from __future__ import annotations

import os
import shutil
import threading
import time

import jax
import jax.numpy as jnp

from cellbench import correctness, datagen, probe, trace
from cellbench.drivers import Job, resolve_config


def _sleep_until(deadline: float, stop: threading.Event) -> None:
    while not stop.is_set():
        left = deadline - time.perf_counter()
        if left <= 0:
            return
        # Long naps while far away, one exact sleep at the end.
        time.sleep(left if left < 0.5 else min(left - 0.25, 1.0))


def run(job: Job) -> dict:
    from d4pg_tpu.runtime import Trainer

    mix, say = job.cell.traffic, job.say
    log_dir = os.path.join(job.cell.out_dir, "run")
    shutil.rmtree(log_dir, ignore_errors=True)
    cfg = resolve_config(job, ["--log-dir", log_dir])
    agent, k, batch = cfg.agent, max(1, cfg.steps_per_dispatch), cfg.batch_size
    # The checks come before the program holds anything on the device and
    # keep nothing there: the peak read after the window is a lifetime
    # maximum, and a check's tree beside the ring would be read as the
    # program's.
    checks = {
        "reference_step": correctness.reference_check(
            agent, batch, job.seed, job.cell.config["reference"]),
    }
    if cfg.prioritized:
        checks["descent"] = correctness.descent_check(
            datagen.next_pow2(cfg.replay_capacity), job.seed)
    say(f"checked in set-up: { {n: c['ok'] for n, c in checks.items()} }")
    trainer = Trainer(cfg)
    say(f"trainer built: ring {cfg.replay_capacity} rows, K={k}, B={batch}")

    seen, done = {}, threading.Event()
    warm_steps = int(mix["warm_dispatches"]) * k

    def read():
        return probe.Clock(), trainer.grad_steps, trainer.env_steps

    def tick():
        """The clock and the counters at the loop's next tick."""
        counter = ((lambda: trainer.env_steps) if trainer.env_steps
                   else (lambda: trainer.grad_steps))
        last = counter()
        while counter() == last and not done.is_set():
            time.sleep(0.0005)
        return read()

    def watch():
        try:
            while trainer.grad_steps < warm_steps and not done.is_set():
                time.sleep(0.002)
            say("warmed; the window starts at the next tick")
            seen["start"] = tick()
            seen["setup_s"] = job.setup_s()
            _sleep_until(seen["start"][0].perf + job.seconds - job.trace_seconds, done)
            seen["end"] = tick()
            if job.trace and not done.is_set():
                trace_dir = os.path.join(job.cell.out_dir, "trace")
                trace.start(trace_dir)
                with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                    t0 = read()
                    _sleep_until(t0[0].perf + job.trace_seconds, done)
                    t1 = read()
                trace.stop()
                seen["traced"] = {"seconds": t1[0].perf - t0[0].perf,
                                  "dispatches": (t1[1] - t0[1]) // k}
                seen["xplane"] = trace.newest_xplane(trace_dir)
        finally:
            trainer.request_preemption()

    watcher = threading.Thread(target=watch, name="cellbench-watcher", daemon=True)
    error = None
    try:
        watcher.start()
        trainer.train(int(mix["total_steps"]))
    except Exception as e:  # noqa: BLE001 - a raised dispatch is a failed one
        error = e
        say(f"train() raised: {e!r}")
    finally:
        done.set()
        watcher.join()
        trainer.close()
    if "end" not in seen:
        raise RuntimeError(f"the loop ended before the window did: {error!r}")

    (c0, g0, e0), (c1, g1, e1) = seen["start"], seen["end"]
    n = (g1 - g0) // k
    finite = correctness.all_finite(trainer.state)
    failed = (0 if finite else n) + (error is not None)
    device_steps = int(jax.device_get(trainer.state.step))
    ratio = cfg.env_steps_per_train_step
    per_collect = cfg.num_envs * getattr(trainer, "segment_len", 32)
    checks["grad_steps_advanced"] = {
        "ok": device_steps == trainer.grad_steps and (g1 - g0) % k == 0,
        "device_step": device_steps, "host_grad_steps": trainer.grad_steps}
    checks["collection_kept_the_ratio"] = {
        "ok": abs((e1 - e0) - ratio * (g1 - g0)) <= 2 * per_collect,
        "env_steps": e1 - e0, "grad_steps": g1 - g0, "ratio": ratio}
    checks["state_finite"] = {"ok": finite}
    checks["train_returned"] = {"ok": error is None and trainer.preempted,
                                "error": repr(error) if error else None}
    if cfg.prioritized:
        tree = trainer._dev_per.tree
        sums = correctness.tree_sums_check(tree)
        rows = min(len(trainer.buffer), cfg.replay_capacity)
        sums["ok"] = sums["ok"] and sums["filled_leaves"] == rows
        sums["ring_rows"] = rows
        checks["tree_sums"] = sums
        half = tree.sums.shape[1] // 2
        leaves = tree.sums[:, half:]
        # New rows are seeded at the running maximum, which only a
        # write-back raises: a filled leaf below it means one happened.
        below = int(jnp.sum((leaves > 0) & (leaves < jnp.max(leaves))))
        checks["write_back_reached_the_tree"] = {"ok": below > 0, "leaves": below}

    seconds = c1.perf - c0.perf
    return {
        "setup_s": seen["setup_s"], "attempted": n + (error is not None),
        "failed": failed,
        "window": {
            "seconds": seconds, "dispatches": n, "grad_steps": g1 - g0,
            "transitions": (g1 - g0) * batch, "env_steps": e1 - e0,
            "wall": (c0.wall, c1.wall)},
        "traced": seen.get("traced"), "xplane": seen.get("xplane"),
        "checks": checks, "agent_cfg": agent, "batch": batch, "k": k,
        "sizes": {"capacity": cfg.replay_capacity, "lanes": 1},
    }
