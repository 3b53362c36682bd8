"""Where XLA's persistent compilation cache lives — decided in one place.

Every entry point that compiles (``train.py``, ``cellbench/run.py``,
``chip_smoke.py``, ``__graft_entry__.py``, ``python -m d4pg_tpu.serve``)
calls :func:`configure_compile_cache` first thing. Without it each process
recompiles the planar physics, the megastep and the eval rollout cold —
the largest share of a short run on a freshly provisioned machine.

The directory is part of the cache key's environment, so it must not move:
no pid, timestamp or tempdir in the path.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — next to the ``d4pg_tpu`` package,
    gitignored."""
    package_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(package_dir), ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX at the persistent compilation cache and return the
    directory in use.

    An exported ``JAX_COMPILATION_CACHE_DIR`` wins and the directory is
    not set in code (JAX reads the variable itself; the operator's
    placement — e.g. a volume that outlives the machine — is the whole
    point). Otherwise the cache goes to :func:`default_cache_dir`.

    Either way the cache KEY is ours: it keeps the HLO metadata
    (``jax_compilation_cache_include_metadata_in_key``). By default JAX
    strips debug info before it hashes a program, and a
    ``jax.named_scope`` (``utils.profiling.phase``) is debug info: a
    program that gained, lost or renamed a scope would be a cache HIT on
    the executable compiled before the change, and its trace would show
    the old scopes. The price: metadata holds source locations, so an
    edit that shifts the line numbers of traced code recompiles on the
    first run in a checkout (a fresh checkout does anyway), and because
    the locations are absolute paths a checkout moved to another path
    recompiles once too. A second run of the same files in the same
    place hits."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    exported = os.environ.get(ENV_VAR)
    if exported:
        return exported
    path = default_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
