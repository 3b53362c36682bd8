"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Multi-device DP/psum paths are tested without TPU hardware via
``--xla_force_host_platform_device_count=8`` (SURVEY.md §4).

FAST-TIER BUDGET (round-4 audit): ``pytest -m "not slow"`` must stay
under ~3 minutes on a 1-core host. JAX CPU compiles dominate test time,
so anything that compiles a physics step (planar/spatial dynamics — the
mass-matrix Hessian alone is tens of seconds), builds a full Trainer, or
traces a DP/TP shard_map belongs in ``slow`` unless it is THE smoke test
for its subsystem (one end-to-end Trainer test stays fast on purpose).
Measured 2026-08 (1-core host, a TPU training run sharing the core):
~18 min before the audit, 280 s after — the residual floor is JAX import
+ one small jit per test file; expect ≤2-3 min on an idle host. When
adding a test, check its wall time with ``--durations=0`` before leaving
it unmarked.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

import functools  # noqa: E402

import pytest  # noqa: E402


def clean_cpu_env(*, pythonpath_repo: bool = False) -> dict:
    """A child-process env on the plain CPU backend: ``JAX_PLATFORMS=cpu``
    and none of this harness's ``XLA_FLAGS`` (the 8-device virtual mesh is
    for in-process tests; a child gets the one real CPU device unless it
    asks otherwise). ONE copy here: every subprocess smoke (serve, fleet,
    dmc) and the EGL probe build their env the same way.
    ``pythonpath_repo=True`` pins PYTHONPATH to the repo root so the child
    can import d4pg_tpu from any working directory."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    if pythonpath_repo:
        env["PYTHONPATH"] = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        )
    return env


def assert_sharded_parity(mesh_tree, oracle_tree, ulps: float = 4.0) -> None:
    """The dp-mesh result vs the single-device vmap oracle, leaf by leaf.

    Integer leaves — step counters, PRNG keys — must be EQUAL: the two
    harnesses draw the same indices and take the same number of steps, and
    that is the placement contract. Float leaves must agree to ``ulps``
    f32 ulps at the leaf's own scale (``ulps · 2^-23 · max|leaf|``).

    Why not byte-equality (which these tests asserted up to jax 0.4.37):
    the per-shard body is the same jaxpr under ``shard_map`` and under
    ``vmap``, and det_pmean fixes the CROSS-shard order, but XLA is free to
    reduce WITHIN a shard in another order once the lane axis is batched
    (a batched dot is not the same loop nest as eight small ones). On the
    installed JAX the actor-gradient path does: measured for PR 21 over
    these tests' three dispatches, keys/steps/losses/tree sums identical,
    the critic side identical, and the actor params + Adam moments off by
    at most 1.43 such ulps (9e-13 absolute) — one rounding of reduction
    order, not a row on the wrong shard, which would move indices, keys
    and losses outright."""
    import numpy as np

    got = jax.tree_util.tree_leaves_with_path(jax.device_get(mesh_tree))
    want = jax.tree_util.tree_leaves(jax.device_get(oracle_tree))
    assert len(got) == len(want)
    for (path, x), y in zip(got, want):
        x, y = np.asarray(x), np.asarray(y)
        name = jax.tree_util.keystr(path)
        assert x.shape == y.shape and x.dtype == y.dtype, name
        if not np.issubdtype(x.dtype, np.floating):
            np.testing.assert_array_equal(x, y, err_msg=name)
            continue
        scale = max(float(np.abs(x).max(initial=0.0)),
                    float(np.abs(y).max(initial=0.0)))
        np.testing.assert_allclose(
            x, y, rtol=0.0, atol=ulps * 2.0 ** -23 * scale, err_msg=name
        )


@functools.lru_cache(maxsize=1)
def has_working_egl() -> bool:
    """True iff an EGL context can be created and a frame rendered, probed
    in a fresh interpreter with ``MUJOCO_GL=egl`` forced (cached per
    session). Subprocess on purpose: merely importing ``OpenGL.EGL`` can
    succeed on a box whose driver then fails at context creation, and a
    failed probe must not poison this process's GL/dm_control import
    state. Lazy on purpose: the hook below only calls this when an
    ``egl``-marked test is actually about to RUN, so a tier-1 pass that
    deselects them (they are all ``slow``) never pays the probe."""
    import os
    import subprocess
    import sys

    probe = (
        "import os; os.environ['MUJOCO_GL'] = 'egl'; "
        "from dm_control import suite; "
        "e = suite.load('cartpole', 'swingup'); e.reset(); "
        "e.physics.render(16, 16); print('EGL_OK')"
    )
    env = clean_cpu_env()
    env["MUJOCO_GL"] = "egl"
    try:
        p = subprocess.run(
            [sys.executable, "-c", probe],
            capture_output=True, text=True, timeout=180, env=env,
        )
    except (OSError, subprocess.TimeoutExpired):
        return False
    return "EGL_OK" in p.stdout


def pytest_runtest_setup(item):
    if item.get_closest_marker("egl") is not None and not has_working_egl():
        pytest.skip("no working EGL/GL stack on this image (capability probe)")
