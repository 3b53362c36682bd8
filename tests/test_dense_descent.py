"""The PER tree's descent with its top levels read by select
(``replay/device_per.py:descend_prefix``) against the all-gather walk it
replaced (``descend_prefix_gather``), LEAF FOR LEAF: the walk is the same —
one level a step, ``go_right = flat >= left`` in f32 — and only how ``left``
is read differs, so every input must reach the same leaf: zero-mass holes,
all-zero subtrees, prefixes on a node boundary, the ``nextafter`` clamp. And
the plan that engages it (``draw_plan``): by the draw's own size, so a small
draw traces the all-gather walk's very ops."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.replay import device_per as dper

WIDTHS = [2 ** k for k in (4, 5, 8, 11, 13, 16)]
DRAWS = [1, 7, 256, 2048, 8192]

descend = jax.jit(dper.descend_prefix, static_argnames="dense_levels")
descend_gather = jax.jit(dper.descend_prefix_gather)


def _depth(width: int) -> int:
    return (width // 2).bit_length() - 1


def _dense_choices(width: int) -> list:
    return [*range(min(_depth(width), 12) + 1), None]


def _lane(leaves: np.ndarray) -> jax.Array:
    return dper.tree_from_priorities(
        np.asarray(leaves, np.float32), leaves.shape[0]).sums[0]


def _holed_leaves(width: int, seed: int) -> np.ndarray:
    """Priorities with a tenth zero-mass holes, one all-zero quarter and a
    zero tail (never-ingested rows)."""
    half = width // 2
    r = np.random.default_rng(seed)
    leaves = r.uniform(0.01, 3.0, half).astype(np.float32)
    leaves[r.uniform(size=half) < 0.1] = 0.0
    leaves[half // 4: half // 2] = 0.0
    leaves[half - half // 8:] = 0.0
    leaves[0] = 0.5                      # never an all-zero tree
    return leaves


def _prefixes(lane: jax.Array, n: int, seed: int) -> jax.Array:
    """The megastep's own: stratified over the lane's mass, clamped."""
    k = 32 if n % 32 == 0 else 1
    return dper.stratified_prefixes(jax.random.PRNGKey(seed), k, n // k, lane[1])


@pytest.mark.parametrize("n", DRAWS)
@pytest.mark.parametrize("width", WIDTHS)
def test_every_dense_depth_reaches_the_all_gather_walks_leaves(width, n):
    lane = _lane(_holed_leaves(width, seed=width + n))
    pre = _prefixes(lane, n, seed=n)
    want = np.asarray(descend_gather(lane, pre))
    assert want.shape == pre.shape and want.dtype == np.int32
    for dense in _dense_choices(width):
        got = np.asarray(descend(lane, pre, dense_levels=dense))
        np.testing.assert_array_equal(got, want, err_msg=f"dense_levels={dense}")


def _exact_leaves(width: int, seed: int) -> np.ndarray:
    """Small integers (a third of them zero, one all-zero eighth): every
    partial sum is an integer under 2^24, so f32, f64 and every order of
    summation hold the same numbers."""
    half = width // 2
    r = np.random.default_rng(seed)
    leaves = r.integers(0, 8, half).astype(np.float32)
    leaves[r.uniform(size=half) < 0.33] = 0.0
    leaves[half // 8: half // 4] = 0.0
    leaves[half - 1 - half // 16] = 3.0
    return leaves


@pytest.mark.parametrize("dense", [0, 1, 2, 3, 5, 8, 10, 12, None])
@pytest.mark.parametrize("width", [2 ** 5, 2 ** 14])
def test_boundaries_and_the_clamp_against_numpy_f64(width, dense):
    """Check 2's own oracle (``cellbench/correctness.py:descent_check``): a
    NumPy f64 cumulative sum and ``searchsorted``. Prefixes sit exactly on
    every leaf's boundary (>= sends them to the next leaf with mass), just
    under it, at zero and at ``nextafter(total, 0)``."""
    leaves = _exact_leaves(width, seed=width)
    lane = _lane(leaves)
    cum = np.cumsum(leaves.astype(np.float64))
    total = np.float32(cum[-1])
    assert cum[-1] < 2 ** 24 and float(lane[1]) == cum[-1]
    on = cum[:-1][cum[:-1] < cum[-1]].astype(np.float32)
    under = np.nextafter(on[on > 0], np.float32(0.0))
    edge = np.asarray([0.0, np.nextafter(total, np.float32(0.0))], np.float32)
    pre = np.concatenate([on, under, edge])
    want = np.searchsorted(cum, pre.astype(np.float64), side="right")
    got = np.asarray(descend(lane, jnp.asarray(pre), dense_levels=dense))
    np.testing.assert_array_equal(got, want)
    assert np.all(leaves[got] > 0)       # never a zero-mass leaf
    np.testing.assert_array_equal(
        got, np.asarray(descend_gather(lane, jnp.asarray(pre))))


@pytest.mark.parametrize("shape", [(8192,), (32, 256), (1, 2048), (4, 8, 64), ()])
def test_prefixes_of_any_shape_keep_it(shape):
    width = 2 ** 12
    lane = _lane(_holed_leaves(width, seed=5))
    r = np.random.default_rng(len(shape))
    pre = jnp.asarray(r.uniform(0, float(lane[1]) * 0.999, shape).astype(np.float32))
    want = np.asarray(descend_gather(lane, pre))
    for dense in (3, 11, None):
        got = np.asarray(descend(lane, pre, dense_levels=dense))
        assert got.shape == shape
        np.testing.assert_array_equal(got, want)


def test_dense_levels_past_the_depth_are_the_depth():
    lane = _lane(_holed_leaves(2 ** 6, seed=1))
    pre = _prefixes(lane, 256, seed=2)
    np.testing.assert_array_equal(
        np.asarray(descend(lane, pre, dense_levels=40)),
        np.asarray(descend_gather(lane, pre)))


def test_vmapped_lanes_descend_their_own_tree():
    """Under the vmap oracle a lane is one row of ``[S, 2L]``."""
    width, lanes = 2 ** 10, 4
    sums = jnp.stack([_lane(_holed_leaves(width, seed=s)) for s in range(lanes)])
    pre = jnp.stack([_prefixes(sums[s], 2048, seed=s) for s in range(lanes)])
    got = jax.jit(jax.vmap(dper.descend_prefix))(sums, pre)
    for s in range(lanes):
        np.testing.assert_array_equal(
            np.asarray(got[s]), np.asarray(descend_gather(sums[s], pre[s])))


# --------------------------------------------------------------- the plan
MEASURED = dper.DENSE_DRAW_MAX_WORDS.bit_length()   # levels of 2^0 ... max words


@pytest.mark.parametrize("width,n,plan", [
    (2 ** 20, 256, (0, 19)),               # humanoid_glm47flash_ep8.learn_per_ctx32
    (2 ** 21, 1, (0, 20)),                 # humanoid_keyevl2_ep8.learn_per_ctx8k
    (2 ** 26, 8192, (MEASURED, 25 - MEASURED)),   # halfcheetah_b256.learn_per
    (2 ** 22, 8192, (MEASURED, 21 - MEASURED)),   # humanoid_b256.learn_per
    (2 ** 22, 2048, (MEASURED, 21 - MEASURED)),   # ... .learn_per_dp4, a chip's share
    (2 ** 26, dper.DENSE_DRAW_MIN_DRAWS - 1, (0, 25)),   # one draw under the threshold
    (16, 8192, (3, 0)),                    # clamped to the tree's depth
])
def test_draw_plan_at_the_cells_shapes(width, n, plan):
    assert dper.draw_plan(width, n) == plan
    assert dper.describe_draw(width, n) == {
        "tree_width": width, "draws": n, "dense_levels": plan[0],
        "gather_levels": plan[1], "max_words": dper.DENSE_DRAW_MAX_WORDS,
        "min_draws": dper.DENSE_DRAW_MIN_DRAWS}


def test_the_two_constants_are_where_the_issue_pins_them():
    assert 256 < dper.DENSE_DRAW_MIN_DRAWS <= 2048
    assert MEASURED == 14 and dper.DENSE_DRAW_MAX_WORDS == 2 ** 13


@pytest.mark.parametrize("n", [1, 255, 256, 511, 512, 2048, 8192, 2 ** 17])
def test_the_plan_covers_every_level_once(n):
    for k in range(2, 28):
        dense, gather = dper.draw_plan(2 ** k, n)
        assert dense >= 0 and gather >= 0 and dense + gather == k - 1
        assert (dense > 0) == (n >= dper.DENSE_DRAW_MIN_DRAWS)


def _lowered(fn, width: int, shape: tuple) -> str:
    text = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((width,), jnp.float32),
        jax.ShapeDtypeStruct(shape, jnp.float32)).as_text()
    return text.replace(fn.__name__, "walk")


def _gathers(text: str) -> int:
    return len(re.findall(r'"?stablehlo\.gather"?\(', text))


@pytest.mark.parametrize("width,shape", [
    (2 ** 20, (1, 256)),                   # the torso cells' draws
    (2 ** 21, (1, 1)),
])
def test_a_small_draw_traces_the_all_gather_walk_itself(width, shape):
    """Engagement is by shape: under the threshold ``descend_prefix`` lowers
    to the oracle's StableHLO, character for character — the torso cells'
    megasteps are the parent's."""
    new = _lowered(dper.descend_prefix, width, shape)
    old = _lowered(dper.descend_prefix_gather, width, shape)
    assert new == old
    assert _gathers(new) == _depth(width)
    assert "stablehlo.reduce" not in new


@pytest.mark.parametrize("width,shape", [
    (2 ** 26, (32, 256)), (2 ** 22, (32, 256)), (2 ** 22, (32, 64)), (2 ** 9, (8, 64)),
])
def test_a_large_draw_gathers_on_the_lower_levels_only(width, shape):
    dense, gather = dper.draw_plan(width, shape[0] * shape[1])
    assert dense > 0
    text = _lowered(dper.descend_prefix, width, shape)
    assert _gathers(text) == gather
    # one reduce a dense level, and no matmul (the default precision is one
    # bf16 pass: a one-hot product would round the sums)
    assert len(re.findall(r"stablehlo\.reduce\b", text)) == dense
    assert "dot_general" not in text
