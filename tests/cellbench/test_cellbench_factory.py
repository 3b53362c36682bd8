"""The learner driver builds the megastep ``Trainer`` would build: the same
factory of ``runtime/megastep.py`` with the same arguments, for every cell of
the manifest and for the branches no cell takes yet (uniform replay, on one
device and over a mesh, and the fused Pallas tier). If the two selections drift apart, the
learn cells measure a program no user runs.

The factories are replaced by recorders, so nothing is traced or compiled,
and the environment is swapped for ``pendulum``: the choice reads the replay
flags, the mesh, K and the batch, never the environment, and MuJoCo is not
installed here."""

from __future__ import annotations

import json

import jax
import pytest

from cellbench import manifest as mf
from cellbench.drivers import learner

with open(mf.DEFAULT_MANIFEST) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
TINY = ["--env", "pendulum", "--replay-placement", "device", "--steps-per-dispatch",
        "4", "--hidden-sizes", "16,16", "--rmsize", "2048", "--bsize", "32"]
EXTRA = {
    "uniform": (TINY + ["--no-p-replay"], None),
    "fused_pallas_tier": (TINY + ["--p-replay", "--projection", "pallas_fused",
                                  "--device-tree-backend", "pallas", "--fused-descent"], None),
    "uniform_over_a_mesh": (TINY + ["--no-p-replay"], 4),
}


def _argv_of(case: str):
    if case in EXTRA:
        return EXTRA[case]
    cell = mf.cell(*mf.load(), case)
    argv = (list(cell.config["argv"]) + list(cell.traffic.get("argv", []))
            + list(cell.config["rehearsal_argv"]))
    argv = ["pendulum" if i and argv[i - 1] == "--env" else a for i, a in enumerate(argv)]
    return argv, cell.traffic.get("dp")


@pytest.mark.parametrize("case", CELLS + sorted(EXTRA))
def test_learner_picks_the_factory_trainer_picks(case, monkeypatch, tmp_path):
    from d4pg_tpu.parallel import make_mesh
    from d4pg_tpu.runtime import Trainer
    from d4pg_tpu.runtime import megastep as ms
    from train import build_parser, config_from_args

    calls = []

    def recorder(name):
        def make(agent, *args, **kw):
            args = tuple(dict(a.shape) if hasattr(a, "axis_names") else a for a in args)
            calls.append((name, agent, args, kw))
            return lambda *a, **k: None
        return make

    for name in dir(ms):
        if name.startswith("make_megastep_"):
            monkeypatch.setattr(ms, name, recorder(name))
    argv, dp = _argv_of(case)
    argv = argv + ["--log-dir", str(tmp_path)] + (["--dp", str(dp)] if dp else [])
    cfg = config_from_args(build_parser().parse_args(argv))
    mesh = make_mesh(dp=dp, tp=1, devices=jax.devices()[:dp]) if dp else None
    learner._megastep(cfg, max(1, cfg.steps_per_dispatch), mesh)
    Trainer(cfg).close()
    assert len(calls) == 2, [c[0] for c in calls]
    assert calls[0] == calls[1], (calls[0][0], calls[1][0])
