"""The one place that decides whether Pallas kernels compile or interpret.

Every ``pallas_call`` in ``ops/`` takes an ``interpret`` flag; the callers
that build training programs (``agent/d4pg.py:train_step``,
``runtime/megastep.py``) resolve it here, from the platform JAX actually
initialized — never from "not tpu, so interpret", which would let a run
that lost its chip (or landed on a GPU) limp along in the interpreter.
"""

from __future__ import annotations

import os

import jax

from d4pg_tpu.utils.backend import cpu_requested


def pallas_interpret() -> bool:
    """``False`` on platform ``tpu`` (Mosaic compiles the kernels), ``True``
    on a CPU backend that was ASKED for (``JAX_PLATFORMS=cpu`` — the Pallas
    interpreter, the test mode), and an error on anything else: a CPU that
    JAX fell back to because the TPU plugin failed to initialize, or a
    platform these kernels (written against the TPU memory spaces) have no
    lowering for."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu" and cpu_requested():
        return True
    raise RuntimeError(
        f"Pallas kernels in d4pg_tpu.ops compile on platform 'tpu' and are "
        f"interpreted under an exported JAX_PLATFORMS=cpu (tests); the "
        f"default JAX backend is {platform!r} and JAX_PLATFORMS is "
        f"{os.environ.get('JAX_PLATFORMS')!r}. Use --projection xla "
        "--device-tree-backend xla here, or find out why the TPU is missing."
    )
