"""The yardstick's own arithmetic: peaks, the cost of a grad step from
shapes (hand-counted), seeded data, interval reductions."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import datagen, model_cost, peaks, trace


def test_v5e_peaks_and_unknown_device_raises():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_flops_hand_counted():
    """obs 3, act 2, hidden (4, 5), 7 atoms, batch 1, multiply-adds by hand.

    actor layers 3x4, 4x5, 5x2 = 12 + 20 + 10 = 42
    critic layers 3x4, (4+2)x5, 5x7 = 12 + 30 + 35 = 77
    targets: 42 + 77 = 119
    critic : fwd 77 + dW 77 + dX (h columns of layer 1: 4x5 = 20, layer 2: 35) = 209
    actor  : actor fwd 42 + critic fwd 77 + dX to the action (2x5 = 10, + 35)
             + actor dW 42 + actor dX (20 + 10) = 236
    """
    got = model_cost.flops_per_grad_step(1, 3, 2, (4, 5), 7)
    assert got == {"targets": 2 * 119, "critic": 2 * 209, "actor": 2 * 236,
                   "total": 2 * (119 + 209 + 236)}
    assert model_cost.flops_per_grad_step(8, 3, 2, (4, 5), 7)["total"] == 8 * got["total"]


def test_bytes_and_params_hand_counted():
    p = model_cost.param_count(3, 2, (4, 5), 7)
    assert p == {"actor": (12 + 4) + (20 + 5) + (10 + 2),
                 "critic": (12 + 4) + (30 + 5) + (35 + 7)}
    b = model_cost.bytes_per_grad_step(2, 3, 2, (4, 5), 7)
    row = (3 + 3 + 2 + 2) * 4
    assert b["param_state"] == 8 * (p["actor"] + p["critic"]) * 4
    assert b["batch_rows"] == 2 * (2 * row + 4)


def test_flagship_cost_hand_counted():
    """halfcheetah_b256, multiply-adds per sample:
    actor  17x256 + 2 x 256x256 + 256x6            = 136,960
    critic 17x256 + 262x256 + 256x256 + 256x51     = 150,016
    targets 286,976; critic 2 x 150,016 + 65,536 + 78,592 = 444,160;
    actor 136,960 + 150,016 + (1,536 + 78,592) + 136,960 + 132,608 = 636,672
    sum 1,367,808 -> x2 FLOPs x256 rows = 700,317,696 per grad step."""
    f = model_cost.flops_per_grad_step(256, 17, 6, (256, 256, 256), 51)
    assert f["total"] == 700_317_696
    assert f["targets"] == 2 * 256 * 286_976


def test_seeded_data_is_a_function_of_the_seed():
    a = np.asarray(datagen.uniform(3, 1, (64, 5)))
    assert np.array_equal(a, np.asarray(datagen.uniform(3, 1, (64, 5))))
    assert not np.array_equal(a, np.asarray(datagen.uniform(4, 1, (64, 5))))
    assert not np.array_equal(a, np.asarray(datagen.uniform(3, 2, (64, 5))))
    assert 0.0 <= a.min() and a.max() < 1.0 and abs(a.mean() - 0.5) < 0.1
    # position, not layout, decides the value: a row block of a bigger array
    big = np.asarray(jax.jit(lambda: datagen.uniform(3, 1, (128, 5)))())
    assert np.array_equal(big[:64], a)


def test_ring_and_tree_shapes_and_holes():
    ring = jax.jit(lambda: datagen.ring_fields(1, 4096, 17, 6, 0.97, 10.0))()
    assert ring["obs"].shape == (4096, 17) and ring["action"].shape == (4096, 6)
    assert float(ring["reward"].min()) >= 0 and float(ring["reward"].max()) < 10
    terminal = np.asarray(ring["discount"]) == 0
    assert 0 < terminal.mean() < 0.03
    leaves = np.asarray(datagen.priority_leaves(1, 2, 3000, 4096, 0.6, 1e-6, 4.0))
    assert leaves.shape == (2, 4096) and np.all(leaves[:, 3000:] == 0)
    holes = (leaves[:, :3000] == 0).mean()
    assert 0.01 < holes < 0.03
    tree = np.asarray(datagen.tree_levels(jnp.asarray(leaves)))
    assert tree.shape == (2, 8192)
    np.testing.assert_allclose(tree[:, 1], leaves.sum(axis=1), rtol=1e-5)
    assert np.all(tree[:, 1:4096] == tree[:, 2::2][:, :4095] + tree[:, 3::2])


def test_exact_leaves_have_exact_sums_at_any_width():
    for n in (1 << 10, 1 << 16, 1 << 20):
        leaves = np.asarray(datagen.exact_leaves(2, n), np.float64)
        assert leaves.sum() < 2 ** 24 and leaves.sum() > 0
        assert np.all(leaves == np.floor(leaves)) and np.all(leaves[-(n // 8):] == 0)


def test_interval_helpers():
    merged = trace.union([(0, 4), (2, 6), (10, 12), (11, 11.5)])
    assert merged == [[0, 6], [10, 12]] and trace.measure(merged) == 8
    assert trace.gaps(merged, -1, 15) == [(-1, 0), (6, 10), (12, 15)]
    assert trace.gaps(merged, 1, 11) == [(6, 10)]
    # a while [0, 100) around two body ops, then a flat op
    events = [["while.1", 0, 100, "while"], ["fusion.1", 10, 30, "loop fusion"],
              ["fusion.2", 50, 20, "loop fusion"], ["copy.3", 120, 5, "data formatting"]]
    assert [s[1] for s in trace.self_times(events)] == [50.0, 30.0, 20.0, 5.0]


def test_setup_counts_from_the_moment_the_chip_is_reached():
    """``setup_s`` leaves out the process's age when JAX had its devices
    (PERF.md section 2): the seconds to reach a chip are the machine's."""
    import time

    from cellbench import probe
    from cellbench.drivers import Job

    age = probe.process_age_s()
    job = Job(None, 0, 1.0, False, True, [], print, reached_chip_s=age)
    time.sleep(0.05)
    assert 0.03 <= job.setup_s() <= probe.process_age_s() - age + 0.02
    assert Job(None, 0, 1.0, False, True, [], print).setup_s() >= age
