"""The plain reference against the program's agent step at a tiny size on
the CPU, and the comparisons that decide ``correct`` catching what they are
there to catch."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import correctness, datagen


@pytest.fixture(scope="module")
def agent_cfg():
    from train import build_parser, config_from_args

    argv = "--env halfcheetah --hidden-sizes 32,32 --n-step 3".split()
    return config_from_args(build_parser().parse_args(argv)).agent


def test_program_step_agrees_with_plain_reference(agent_cfg):
    r = correctness.reference_check(agent_cfg, 64, seed=5, reference="d4pg_step")
    assert r["ok"], r
    assert max(r["rel_err"].values()) <= correctness.TOL_REL
    assert max(r["ulp_err"].values()) <= correctness.TOL_ULP
    assert set(r["rel_err"]) >= {"critic_loss", "actor_loss", "priorities",
                                 "actor_grad", "critic_grad"}


def test_a_bfloat16_pass_fails_the_tolerance(agent_cfg):
    """What the tolerance is for: the same step computed in bfloat16 moves
    gradients and priorities by far more than 1e-5 and is refused."""
    low = dataclasses.replace(agent_cfg, compute_dtype="bfloat16")
    r = correctness.reference_check(low, 64, seed=5, reference="d4pg_step")
    assert not r["ok"]
    assert r["rel_err"]["critic_grad"] > 100 * correctness.TOL_REL


@pytest.mark.parametrize("change", [
    dict(tau=0.01), dict(lr_critic=2e-4), dict(lr_actor=2e-4), dict(adam_b2=0.99),
])
def test_a_changed_hyperparameter_fails(agent_cfg, change, monkeypatch):
    """The reference is given the configuration's numbers, the program is
    run with one of them changed: parameters or targets must disagree."""
    import cellbench.reference.d4pg_step as ref

    real = ref.step
    truth = {k: getattr(agent_cfg, k) for k in change}
    names = {"adam_b2": "b2"}

    def step(state, batch, hp):
        return real(state, batch, {**hp, **{names.get(k, k): v for k, v in truth.items()}})

    monkeypatch.setattr(ref, "step", step)
    changed = dataclasses.replace(agent_cfg, **change)
    r = correctness.reference_check(changed, 64, seed=5, reference="d4pg_step")
    assert not r["ok"], r


def test_projection_is_a_distribution_and_handles_edges():
    import cellbench.reference.d4pg_step as ref

    probs = jnp.full((4, 51), 1.0 / 51)
    reward = jnp.asarray([0.0, 2000.0, 10.0, 20.0])
    discount = jnp.asarray([0.97, 0.97, 0.0, 1.0])
    m = np.asarray(ref.project(probs, reward, discount, 0.0, 1000.0, 51))
    np.testing.assert_allclose(m.sum(axis=1), 1.0, rtol=1e-6)
    assert m[1, -1] == pytest.approx(1.0)          # all mass clipped to v_max
    assert m[2, 0] == pytest.approx(0.5) and m[2, 1] == pytest.approx(0.5)
    # r = Δ, γ = 1: every atom moves up exactly one atom, the top one stays
    np.testing.assert_allclose(m[3, 1:-1], 1.0 / 51, rtol=1e-5)
    assert m[3, 0] == pytest.approx(0.0) and m[3, -1] == pytest.approx(2.0 / 51)


def test_descent_agrees_with_numpy_and_a_wrong_one_is_caught(monkeypatch):
    assert correctness.descent_check(1 << 12, seed=2)["ok"]
    import d4pg_tpu.replay.device_per as dper

    real = dper.descend_prefix
    monkeypatch.setattr(dper, "descend_prefix",
                        lambda lane, pre: jnp.maximum(real(lane, pre) - 1, 0))
    bad = correctness.descent_check(1 << 12, seed=2)
    assert not bad["ok"] and bad["mismatches"] > 0


def test_tree_sums_check_catches_an_unrepaired_ancestor():
    from d4pg_tpu.replay.device_per import DevicePerTree

    leaves = datagen.priority_leaves(1, 1, 1000, 1024, 0.6, 1e-6, 4.0)
    sums = datagen.tree_levels(leaves)
    good = correctness.tree_sums_check(DevicePerTree(sums, jnp.float32(4.0)))
    assert good["ok"] and good["filled_leaves"] == int(jnp.sum(leaves > 0))
    broken = sums.at[0, 1024 + 5].add(0.5)     # a leaf written, ancestors not
    bad = correctness.tree_sums_check(DevicePerTree(broken, jnp.float32(4.0)))
    assert not bad["ok"] and not bad["every_parent_is_its_childrens_sum"]


def test_all_finite():
    assert correctness.all_finite({"a": jnp.ones(3), "n": jnp.arange(3)})
    assert not correctness.all_finite({"a": jnp.asarray([1.0, np.nan])})
