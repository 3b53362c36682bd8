"""Device milliseconds per dispatch under one of the program's named
scopes: ``phase_time``'s arithmetic under another reducer name.

``tests/cellbench/test_cellbench_phases.py`` asks every cell that declares a
``phase_time`` metric for all seven of PR 24's phases, each above zero on a
trace recorded before the torso's scopes existed. A cell whose scopes are
newer than that recording declares them through this module; on that
recording they read a measured 0 (the scope is not there), on a live run
what ``phase_time`` reads."""

from cellbench.reducers import phase_time


def reduce(ctx, phase: str, per: str = "dispatch", across: str = "mean"):
    return phase_time.reduce(ctx, phase, per=per, across=across)
