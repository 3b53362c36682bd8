"""Shared utilities: profiling, retry/backoff, signals, offline plotting.

Lazy re-exports (the `_lazy.py` contract): ``utils.retry`` and
``utils.signals`` are host-only — the JAX-free fleet actor hosts
(``d4pg_tpu/fleet``) import them — so an eager
``from .profiling import annotate`` here (profiling imports jax at top
level) would make ANY ``d4pg_tpu.utils.*`` import pay the full JAX
import and break the actor-host contract.
"""

from d4pg_tpu._lazy import lazy_exports

_EXPORTS = {
    "annotate": "d4pg_tpu.utils.profiling",
    # matplotlib-adjacent, kept off the training path
    "compare_runs": "d4pg_tpu.utils.plotting",
    "ewma": "d4pg_tpu.utils.plotting",
    "load_run": "d4pg_tpu.utils.plotting",
    "plot_run": "d4pg_tpu.utils.plotting",
}

__getattr__, __dir__ = lazy_exports(__name__, _EXPORTS)

__all__ = sorted(_EXPORTS)
