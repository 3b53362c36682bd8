"""PR 21 (bring-up): the entry points say which device they ran on, and fail
when it is not the one they were asked to use.

- ``chip_smoke.py``: the default invocation fails without a TPU and prints
  no result; alone in a directory it fails too; the explicit CPU rehearsal
  runs every leg at tiny sizes and labels its output ``cpu``;
- the compile-cache helper leaves an exported ``JAX_COMPILATION_CACHE_DIR``
  alone and otherwise resolves to one fixed in-checkout path;
- the Pallas interpret switch compiles on ``tpu``, interprets on ``cpu`` and
  raises on anything else;
- ``__graft_entry__.py`` has no CPU fallback;
- a ring too large for a Pallas tree tier is refused by name at config
  validation.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest
from conftest import clean_cpu_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _json_lines(stdout: str) -> list:
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_default_invocation_fails_without_tpu():
    p = subprocess.run(
        [sys.executable, SMOKE], cwd=REPO, env=clean_cpu_env(),
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert not _json_lines(p.stdout), p.stdout[-500:]
    assert "needs 'tpu'" in p.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in clean_cpu_env().items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "chip_smoke.py", "--cpu-rehearsal"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert not _json_lines(p.stdout), p.stdout[-500:]


def test_cpu_rehearsal_passes_and_is_labelled_cpu():
    """Every leg of the chip command at tiny sizes, as the driver runs it:
    a subprocess, the last stdout lines parsed. (~1 min: two planar-physics
    trainers; the persistent compile cache makes the second one and any
    rerun cheaper.)"""
    p = subprocess.run(
        [sys.executable, SMOKE, "--cpu-rehearsal"], cwd=REPO,
        env=clean_cpu_env(), capture_output=True, text=True, timeout=840,
    )
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    verdict, out = (
        json.loads(ln) for ln in p.stdout.strip().splitlines()[:-3:-1]
    )
    # The driver's contract for the LAST line: exactly these keys.
    assert set(verdict) == {"ok", "device"}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["ok"] is True
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    # The line before it: the report.
    assert {k: out[k] for k in verdict} == verdict
    assert out["sizes"] == "rehearsal"
    assert out["speeds"] == "not measured" and out["claim"] is None
    assert set(out["legs"]) == {
        "guards", "kernels", "train_xla", "train_pallas"
    }
    assert all(leg["ok"] for leg in out["legs"].values())
    assert out["legs"]["kernels"]["detail"]["interpret"] is True
    for leg in ("train_xla", "train_pallas"):
        detail = out["legs"][leg]["detail"]
        assert detail["compiles"] == {
            "megastep": 1, "ring_ingest": 1, "tree_ingest": 1
        }
        assert detail["tree"]["leaves_moved"] > 0
    assert out["compile_cache"]["dir"] == (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(REPO, ".jax_cache")
    )


class TestCompileCacheHelper:
    def test_exported_dir_wins_and_nothing_is_set(self, monkeypatch):
        import jax

        from d4pg_tpu.utils import compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(compile_cache.ENV_VAR, "/x/placed/by/operator")
        assert (
            compile_cache.configure_compile_cache() == "/x/placed/by/operator"
        )
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_one_fixed_path_in_the_checkout(self, monkeypatch):
        import jax

        from d4pg_tpu.utils import compile_cache

        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            first = compile_cache.configure_compile_cache()
            second = compile_cache.configure_compile_cache()
            assert first == second == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == first
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


class TestPallasInterpretSwitch:
    @pytest.mark.parametrize(
        "platform,expected", [("tpu", False), ("cpu", True)]
    )
    def test_tpu_compiles_cpu_interprets(self, monkeypatch, platform, expected):
        import jax

        from d4pg_tpu.ops.pallas_mode import pallas_interpret

        monkeypatch.setenv("JAX_PLATFORMS", "cpu" if platform == "cpu" else "")
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert pallas_interpret() is expected

    def test_cpu_that_was_not_asked_for_is_an_error(self, monkeypatch):
        """JAX falls back to the CPU by itself when the TPU plugin fails
        to initialize: that run must not interpret quietly."""
        import jax

        from d4pg_tpu.ops.pallas_mode import pallas_interpret

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        with pytest.raises(RuntimeError, match="JAX_PLATFORMS"):
            pallas_interpret()

    def test_any_other_platform_is_an_error(self, monkeypatch):
        import jax

        from d4pg_tpu.ops.pallas_mode import pallas_interpret

        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            pallas_interpret()


class TestNoFallback:
    def test_dryrun_needs_the_devices_it_was_asked_for(self, monkeypatch):
        import __graft_entry__ as graft

        # not an explicit CPU run -> no virtual mesh is conjured up
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with pytest.raises(RuntimeError, match="dryrun_multichip"):
            graft.dryrun_multichip(4096)


def test_oversized_ring_refused_for_pallas_tree_tiers():
    from d4pg_tpu.replay.source import (
        PALLAS_TREE_MAX_LEAVES,
        RequestedCaps,
        negotiate,
    )

    too_big = 2 * PALLAS_TREE_MAX_LEAVES
    for ask in (
        dict(device_tree="pallas"),
        dict(fused_descent=True, projection="pallas_fused"),
    ):
        n = negotiate(RequestedCaps(
            placement="device", replay_capacity=too_big, **ask
        ))
        assert [g.code for g in n.gaps] == ["pallas_tree_too_many_leaves"]
        assert str(PALLAS_TREE_MAX_LEAVES) in n.gaps[0].message
    # the default ring fits; sharding brings a large one back under
    assert negotiate(RequestedCaps(
        placement="device", device_tree="pallas", replay_capacity=1_000_000
    )).verdict == "pass"
    assert negotiate(RequestedCaps(
        placement="device", device_tree="pallas", replay_capacity=too_big,
        dp=4,
    )).verdict == "pass"
    # the XLA descent has no such ceiling
    assert negotiate(RequestedCaps(
        placement="device", replay_capacity=too_big
    )).verdict == "pass"
