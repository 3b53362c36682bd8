"""The PER tree's write path (``replay/device_per.py``: one leaf scatter, then
the ancestors rebuilt densely above ``repair_plan``'s threshold) against the
path it replaced, BIT FOR BIT: a leaf scatter followed by
``repair_ancestors`` on every level, and NumPy assignment order for the
duplicates. A parent is ``left + right`` in f32 either way, and a parent no
write touched already equals that sum, so the two trees must be equal to the
last bit — which is what lets the host-tree parity, frozen-stream and
sharded-vs-oracle tests pass unedited."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.replay import device_per as dper

WIDTHS = [2 ** k for k in (4, 5, 8, 11, 12, 13, 16, 17)]
COUNTS = [1, 7, 256, 8192]


def _capacity(width: int) -> int:
    """Not a power of two wherever the lane has room for one."""
    half = width // 2
    return half - 3 if half > 8 else half


def _seeded_lane(width: int, seed: int, lanes: int = 1) -> tuple[jax.Array, int]:
    """A consistent tree over ``capacity`` rows with zero-mass holes (rows
    never ingested) and a zero power-of-two tail."""
    cap = _capacity(width)
    r = np.random.default_rng(seed)
    pa = r.uniform(0.01, 3.0, cap * lanes).astype(np.float32)
    pa[r.uniform(size=pa.shape) < 0.1] = 0.0
    tree = dper.tree_from_priorities(pa, cap * lanes, n_shards=lanes)
    assert tree.sums.shape == (lanes, width)
    return tree.sums, cap


def _writes(cap: int, n: int, seed: int):
    """``n`` slots with heavy duplicates (a pool of at most ``n // 4 + 1``
    distinct rows) and a tenth of them pad slots ``>= capacity``."""
    r = np.random.default_rng(seed)
    pool = r.integers(0, cap, size=max(1, min(cap, n // 4 + 1)))
    idx = pool[r.integers(0, pool.size, size=n)].astype(np.int32)
    pads = r.uniform(size=n) < 0.1
    idx[pads] = cap + r.integers(0, 5, size=n)[pads]
    vals = r.uniform(0.01, 5.0, n).astype(np.float32)
    return idx, vals


def _last_wins(idx: np.ndarray, vals: np.ndarray, cap: int):
    """NumPy assignment order: the last write to a slot stays. Returns the
    distinct real slots and their surviving values."""
    leaves = np.full(cap, np.nan, np.float32)
    real = idx < cap
    leaves[idx[real]] = vals[real]
    slots = np.flatnonzero(~np.isnan(leaves)).astype(np.int32)
    return slots, leaves[slots]


def _oracle(sums_lane, slots, vals, cap: int):
    """The replaced path: the leaf scatter, then every level repaired
    position by position."""
    width = sums_lane.shape[0]
    pos = jnp.where(slots < cap, slots + width // 2, width).astype(jnp.int32)
    lane = sums_lane.at[pos].set(vals, mode="drop")
    return dper.repair_ancestors(lane, pos)


def _assert_bits_equal(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def _children_sum(sums: np.ndarray) -> None:
    half = sums.shape[-1] // 2
    np.testing.assert_array_equal(
        sums[..., 1:half], sums[..., 2:: 2] + sums[..., 3:: 2])


@pytest.mark.parametrize("n", COUNTS)
@pytest.mark.parametrize("width", WIDTHS)
def test_last_wins_write_equals_numpy_order_then_sparse_repair(width, n):
    """``update_leaves_last_wins`` (sort, one scatter, dense rebuild above
    the threshold) = NumPy's winners written by the old scatter + full
    ``repair_ancestors``."""
    sums, cap = _seeded_lane(width, seed=width + n)
    idx, vals = _writes(cap, n, seed=3 * width + n)
    got = jax.jit(
        lambda s, i, v: dper.update_leaves_last_wins(s, i, v, cap)
    )(sums[0], idx, vals)
    slots, winners = _last_wins(idx, vals, cap)
    want = jax.jit(lambda s, i, v: _oracle(s, i, v, cap))(sums[0], slots, winners)
    _assert_bits_equal(got, want)
    got = np.asarray(got)
    np.testing.assert_array_equal(got[width // 2 + slots], winners)
    _children_sum(got)


@pytest.mark.parametrize("width,n", [(16, 1), (256, 40), (4096, 1), (4096, 900),
                                     (2 ** 16, 5), (2 ** 17, 4096)])
def test_set_leaves_with_pads_and_a_scalar_seed_equals_sparse_repair(width, n):
    """The ingest's call: consecutive slots, pad slots at and past the
    capacity, one scalar value."""
    sums, cap = _seeded_lane(width, seed=n)
    start = (cap * 5) // 7
    slots = ((start + np.arange(n)) % cap).astype(np.int32)
    slots[n // 2:: 3] = cap + (n % 3)
    seed = jnp.float32(1.7) ** jnp.float32(0.6)
    got = jax.jit(lambda s, i: dper.set_leaves(s, i, seed, cap))(sums[0], slots)
    want = jax.jit(lambda s, i: _oracle(s, i, jnp.broadcast_to(seed, i.shape), cap))(
        sums[0], slots)
    _assert_bits_equal(got, want)


@pytest.mark.parametrize("ratio,width,n,plan", [
    (0, 4096, 64, (11, 0)),          # every level position by position
    (1, 4096, 7, (8, 3)),            # mixed: 8 sparse levels, then 3 dense
    (16, 2 ** 13, 3, (6, 6)),        # mixed, the dense part in both forms
    (16, 2 ** 17, 1, (11, 5)),
    (2048, 2 ** 17, 1, (4, 12)),     # the shipped constant, one slot
    (2048, 2 ** 17, 64, (0, 16)),    # ... and enough slots: all dense
])
def test_both_sides_of_the_threshold(monkeypatch, ratio, width, n, plan):
    monkeypatch.setattr(dper, "DENSE_REPAIR_RATIO", ratio)
    assert dper.repair_plan(width, n) == plan
    sums, cap = _seeded_lane(width, seed=ratio + n)
    idx, vals = _writes(cap, n, seed=width + ratio)
    slots, winners = _last_wins(idx, vals, cap)
    got = jax.jit(
        lambda s, i, v: dper.update_leaves_last_wins(s, i, v, cap)
    )(sums[0], idx, vals)
    want = jax.jit(lambda s, i, v: _oracle(s, i, v, cap))(sums[0], slots, winners)
    _assert_bits_equal(got, want)


@pytest.mark.parametrize("width,n,plan", [
    (2 ** 26, 8192, (0, 25)),        # halfcheetah_b256.learn_per
    (2 ** 22, 8192, (0, 21)),        # humanoid_b256.learn_per
    (2 ** 22, 2048, (0, 21)),        # ... .learn_per_dp4, a chip's share
    (2 ** 20, 256, (0, 19)),         # the torso cell
    (2 ** 26, 4096, (1, 24)),        # an ingest chunk into the large tree
    (2 ** 26, 1, (13, 12)),          # one slot does not pay for the tree
    (16, 8192, (0, 3)),
])
def test_repair_plan_at_the_cells_shapes(width, n, plan):
    assert dper.repair_plan(width, n) == plan
    described = dper.describe_repair(width, n)
    assert (described["sparse_levels"], described["dense_levels"]) == plan
    assert described["R"] == dper.DENSE_REPAIR_RATIO
    assert sum(plan) == (width // 2).bit_length() - 1


@pytest.mark.parametrize("width", [16, 2048, 4096, 2 ** 14, 2 ** 17])
@pytest.mark.parametrize("lanes", [1, 3])
def test_dense_rebuild_restores_a_tree_from_its_leaves(width, lanes):
    """Every parent garbage, the leaves kept: the dense pass over all the
    levels gives ``tree_from_priorities``' tree (NumPy's pairwise f32
    sums), on a lane and vmapped over ``[S, 2L]``."""
    sums, _ = _seeded_lane(width, seed=width, lanes=lanes)
    depth = (width // 2).bit_length() - 1
    broken = sums.at[:, 1: width // 2].multiply(-0.37)
    if lanes == 1:
        got = jax.jit(lambda s: dper.rebuild_ancestors(s, depth))(broken[0])[None]
    else:
        got = jax.jit(jax.vmap(lambda s: dper.rebuild_ancestors(s, depth)))(broken)
    _assert_bits_equal(got, sums)
    # ... and only the top levels when asked for fewer
    top = np.asarray(jax.jit(lambda s: dper.rebuild_ancestors(s, 2))(broken[0]))
    kept = np.asarray(broken[0])
    np.testing.assert_array_equal(top[4:], kept[4:])
    np.testing.assert_array_equal(top[2:4], kept[4:8:2] + kept[5:8:2])
    assert top[1] == top[2] + top[3] and top[0] == kept[0]


@pytest.mark.parametrize("width,n", [(64, 9), (4096, 300), (2 ** 14, 8192)])
def test_vmapped_lanes_equal_the_lanes_one_by_one(width, n):
    """Under the vmap oracle a lane is one row of ``[S, 2L]``: the write
    stays local to it."""
    lanes = 4
    sums, cap = _seeded_lane(width, seed=n, lanes=lanes)
    writes = [_writes(cap, n, seed=10 * n + lane) for lane in range(lanes)]
    idx = np.stack([w[0] for w in writes])
    vals = np.stack([w[1] for w in writes])
    got = jax.jit(jax.vmap(
        lambda s, i, v: dper.write_back_lane(s, i, v, 0.6, 1e-6, cap)[0]
    ))(sums, idx, vals)
    one = jax.jit(lambda s, i, v: dper.write_back_lane(s, i, v, 0.6, 1e-6, cap)[0])
    for lane in range(lanes):
        _assert_bits_equal(got[lane], one(sums[lane], idx[lane], vals[lane]))
    _children_sum(np.asarray(got))


def test_write_back_moves_no_other_leaf_and_keeps_the_root():
    width, n = 2 ** 12, 500
    sums, cap = _seeded_lane(width, seed=1)
    idx, vals = _writes(cap, n, seed=2)
    got, local_max = jax.jit(
        lambda s, i, v: dper.write_back_lane(s, i, v, 0.6, 1e-6, cap)
    )(sums[0], idx, vals)
    got, before = np.asarray(got), np.asarray(sums[0])
    touched = np.zeros(width // 2, bool)
    touched[idx[idx < cap]] = True
    np.testing.assert_array_equal(got[width // 2:][~touched], before[width // 2:][~touched])
    assert float(local_max) == np.float32(np.abs(vals).max() + np.float32(1e-6))
    # root = the leaves summed pairwise, level by level
    level = got[width // 2:]
    while level.size > 1:
        level = level[0::2] + level[1::2]
    assert got[1] == level[0]


def test_trainer_logs_the_tree_repair_and_the_draw_once(tmp_path, capsys):
    """Which way a write-back is repaired and a draw descends is static
    (lane width, positions a dispatch draws and writes), so the trainer
    prints both once at start-up, next to the ring's storage line."""
    import json

    from d4pg_tpu.runtime.trainer import Trainer
    from tests.test_megastep import _trainer_cfg

    cfg = _trainer_cfg("device", str(tmp_path / "d"))   # B=8, K=2, 512 rows
    t = Trainer(cfg)
    try:
        width = t._dev_per.tree.sums.shape[1]
        described = dper.describe_repair(width, 2 * 8)
        draw = dper.describe_draw(width, 2 * 8)
    finally:
        t.close()
    out = capsys.readouterr().out.splitlines()

    def logged(tag):
        lines = [line for line in out if line.startswith(tag)]
        assert len(lines) == 1, (tag, lines)
        return json.loads(lines[0].split(": ", 1)[1])

    assert logged("[replay] device tree repair: ") == described == {
        "tree_width": 1024, "positions": 16, "sparse_levels": 0,
        "dense_levels": 9, "R": dper.DENSE_REPAIR_RATIO}
    assert logged("[replay] device tree draw: ") == draw == {
        "tree_width": 1024, "draws": 16, "dense_levels": 0,
        "gather_levels": 9, "max_words": dper.DENSE_DRAW_MAX_WORDS,
        "min_draws": dper.DENSE_DRAW_MIN_DRAWS}
