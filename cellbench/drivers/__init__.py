"""Drivers: one module per *kind* of loop, found by the name a traffic file
gives under ``"driver"``. ``run(job) -> dict`` sets up, warms, measures and
checks one cell; :class:`Job` is what the harness hands it. A new mix of an
existing kind is a data file; only a new kind of loop is a new module.

What a driver returns:

    setup_s      chip reached → first measured dispatch (``job.setup_s()``)
    window       {"seconds", "dispatches", "grad_steps", "transitions",
                  "env_steps"?, "wall": (t0, t1)}
    attempted, failed
    checks       {name: {"ok": bool, ...}}     everything that decides correct
    xplane       path of the traced window's .xplane.pb, or None
    agent_cfg, batch, k
"""

from __future__ import annotations

import dataclasses

from cellbench import probe


@dataclasses.dataclass
class Job:
    cell: object            # cellbench.manifest.Cell
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    devices: list
    say: object             # print-like, for progress lines
    reached_chip_s: float = 0.0   # the process's age when JAX had its devices

    def setup_s(self) -> float:
        """Seconds of set-up so far: everything since the process held its
        chips. The 11-14 s (20 s on four chips) a new process takes to reach
        them are the machine's, drift by 3 s within minutes on a shared
        host (PERF.md section 6) and are reported apart as ``reach_chip_s``."""
        return probe.process_age_s() - self.reached_chip_s

    @property
    def trace_seconds(self) -> float:
        """The traced window is short (traces are large and tracing slows
        the host) and is taken out of ``--seconds``, after the timed one."""
        if not self.trace:
            return 0.0
        return min(float(self.cell.traffic["trace_seconds"]), self.seconds / 2)


def resolve_config(job: Job, extra_argv=()):
    """The cell's ``TrainConfig``, through the program's own path: the
    configuration file's ``train.py`` argv (+ the mix's, + the tiny sizes of
    a rehearsal) → ``build_parser`` → ``config_from_args`` → presets."""
    from train import build_parser, config_from_args

    argv = list(job.cell.config["argv"]) + list(job.cell.traffic.get("argv", []))
    if job.rehearsal:
        argv += list(job.cell.config["rehearsal_argv"])
        argv += list(job.cell.traffic.get("rehearsal_argv", []))
    argv += ["--seed", str(job.seed), *extra_argv]
    return config_from_args(build_parser().parse_args(argv))
