"""``parallel.dp.det_pmean``: the tree crosses the chips as one packed buffer a
sync, reduced in fixed order by shards (ISSUE 30).

1. BIT-EQUALITY with the per-leaf formula the function was until PR 30
   (``all_gather``, unrolled sum shard 0 -> N-1, divide), kept here as a
   test-local reference: every leaf ``assert_array_equal``, on the CPU mesh
   under ``shard_map`` and under single-device ``vmap(axis_name="dp")``.
2. THE ORDER IS THE PROGRAM: no collective that adds in the lowered text of
   ``det_pmean`` or of the sharded PER megastep, and the scan body holds two
   syncs of two collectives each, a count a CPU run can make.
3. ``describe_sync``: the static counter, at the four-chip cell's shapes and
   at a two-buffer tree; ``Trainer`` logs it once under ``--dp``.
"""

from __future__ import annotations

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from d4pg_tpu.parallel import dp


# ------------------------------------------------------------------ trees
def _mlp(widths, out):
    """Gradient shapes of a flax MLP: ``hidden_<i>``/``out`` x kernel/bias."""
    shapes, fan_in = {}, widths[0]
    for i, w in enumerate(widths[1:]):
        shapes[f"hidden_{i}"] = {"bias": (w,), "kernel": (fan_in, w)}
        fan_in = w
    shapes["out"] = {"bias": (out,), "kernel": (fan_in, out)}
    return shapes


METRICS = {k: () for k in (
    "actor_loss", "critic_loss", "priority_mean", "q_mean", "q_support_frac")}
# Humanoid-v4 (376 observations, 17 actions), 3x256, C51-51: the shapes of
# humanoid_b256.learn_per_dp4. The critic takes the action at its second layer.
HUMANOID_CRITIC = _mlp((376, 256, 256, 256), 51)
HUMANOID_CRITIC["hidden_1"]["kernel"] = (256 + 17, 256)
HUMANOID_ACTOR = _mlp((376, 256, 256, 256), 17)

F32, BF16 = jnp.float32, jnp.bfloat16
TREES = {
    # name: (shapes, dtype of a leaf by its position, bucket cap or None)
    "humanoid_critic": (HUMANOID_CRITIC, lambda i: F32, None),
    "humanoid_actor_and_metrics": ((HUMANOID_ACTOR, METRICS), lambda i: F32, None),
    "odd_float_count": ({"a": (7,), "b": (3, 5), "c": ()}, lambda i: F32, None),
    "scalars_only": (METRICS, lambda i: F32, None),
    "leaf_over_the_cap": (
        {"a": (5,), "big": (40, 9), "c": (3, 4), "d": (), "e": (50,)},
        lambda i: F32, 64),
    "two_float_dtypes": (
        {"a": (33,), "b": (4, 5), "c": (), "d": (17, 3)},
        lambda i: (F32, BF16)[i % 2], None),
}


def _is_shape(s):
    return isinstance(s, tuple) and all(isinstance(d, int) for d in s)


def _make(name, size, seed=0):
    """``[size, ...]`` inputs a leaf, a shard a row, magnitudes spread over
    six decades so that another summation order would show."""
    shapes, dtype_at, cap = TREES[name]
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_shape)
    rng = np.random.default_rng(seed)
    arrays = [
        jnp.asarray(
            rng.standard_normal((size,) + s) * 10.0 ** rng.uniform(-3, 3, (size,) + s),
            dtype_at(i))
        for i, s in enumerate(leaves)
    ]
    return jax.tree.unflatten(treedef, arrays), cap


def per_leaf_mean(tree, axis_name, size):
    """``det_pmean`` as it was until PR 30, leaf by leaf."""

    def _mean(t):
        g = jax.lax.all_gather(t, axis_name)
        acc = g[0]
        for i in range(1, size):
            acc = acc + g[i]
        return acc / size

    return jax.tree.map(_mean, tree)


def _under(harness, fn, size):
    """``fn(tree of one shard) -> tree`` run over ``[size, ...]`` inputs;
    every shard's result comes back, a shard a row."""
    if harness == "vmap":
        return jax.jit(jax.vmap(fn, axis_name="dp"))
    mesh = Mesh(np.array(jax.devices()[:size]), ("dp",))
    first = lambda t: jax.tree.map(lambda x: x[0], t)           # noqa: E731
    row = lambda t: jax.tree.map(lambda x: x[None], t)          # noqa: E731
    return jax.jit(shard_map(
        lambda t: row(fn(first(t))), mesh=mesh, in_specs=P("dp"),
        out_specs=P("dp"), check_vma=False))


@pytest.mark.parametrize("harness", ["shard_map", "vmap"])
@pytest.mark.parametrize("size", [2, 4, 8])
@pytest.mark.parametrize("name", list(TREES))
def test_bit_equal_to_the_per_leaf_formula(monkeypatch, name, size, harness):
    tree, cap = _make(name, size)
    if cap is not None:
        monkeypatch.setattr(dp, "SYNC_BUCKET_FLOATS", cap)
        shard = jax.tree.map(lambda x: x[0], tree)
        assert len(dp.sync_buckets(jax.tree.leaves(shard), size)) >= 2
    got = _under(harness, lambda t: dp.det_pmean(t, "dp", size), size)(tree)
    want = _under(harness, lambda t: per_leaf_mean(t, "dp", size), size)(tree)
    got_leaves = jax.tree_util.tree_leaves_with_path(jax.device_get(got))
    want_leaves = jax.tree.leaves(jax.device_get(want))
    assert len(got_leaves) == len(want_leaves)
    for (path, x), y in zip(got_leaves, want_leaves):
        where = jax.tree_util.keystr(path)
        assert x.shape == y.shape and x.dtype == y.dtype, where
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=where)
        # every shard holds the same mean
        np.testing.assert_array_equal(
            np.asarray(x), np.broadcast_to(np.asarray(x)[:1], x.shape), err_msg=where)


# ------------------------------------------------- the order is the program
ADDING = r"stablehlo\.(all_reduce|reduce_scatter)|psum|all-reduce|reduce-scatter"
MOVING = ("all_to_all", "all_gather")
COLLECTIVES = MOVING + (
    "psum", "psum2", "pmax", "pmin", "reduce_scatter", "ppermute", "pbroadcast",
    "psum_invariant", "all_gather_invariant")


def _count_collectives(jaxpr, in_scan=False, counts=None):
    """``{(primitive, inside a scan): n}`` over a jaxpr and all it nests."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVES:
            counts[name, in_scan] = counts.get((name, in_scan), 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count_collectives(sub, in_scan or name == "scan", counts)
    return counts


@pytest.mark.parametrize("size", [2, 4, 8])
def test_det_pmean_lowers_to_no_collective_that_adds(size):
    tree, _ = _make("humanoid_actor_and_metrics", size)
    fn = _under("shard_map", lambda t: dp.det_pmean(t, "dp", size), size)
    text = fn.lower(tree).as_text()
    assert not re.search(ADDING, text)
    # one buffer: one exchange of shards, one gather of the slice means
    assert len(re.findall(r"stablehlo\.all_to_all", text)) == 1
    assert len(re.findall(r"stablehlo\.all_gather", text)) == 1


def test_sharded_per_megastep_scan_holds_two_syncs_and_no_adding_collective():
    """The sharded PER megastep at Humanoid's widths (a small ring, K=2):
    in the grad-step scan exactly 2 syncs x (1 buffer x 2 collectives), and
    outside it what the parent had outside ``_sync`` — the draw's gather of
    the min ratio and the write-back's gather of the max priority."""
    from d4pg_tpu.agent import D4PGConfig, create_train_state
    from d4pg_tpu.models.critic import DistConfig
    from d4pg_tpu.agent.d4pg import synced_trees
    from d4pg_tpu.parallel import make_mesh, shard_train_state
    from d4pg_tpu.replay import device_per as dper
    from d4pg_tpu.replay.device_ring import device_ring_init
    from d4pg_tpu.runtime.megastep import make_megastep_device_per_sharded

    D, C, k, b = 4, 128, 2, 16
    cfg = D4PGConfig(
        obs_dim=376, action_dim=17, hidden_sizes=(256, 256, 256),
        dist=DistConfig(num_atoms=51, v_min=0.0, v_max=1500.0), n_step=5)
    mesh = make_mesh(dp=D, tp=1, devices=jax.devices()[:D])
    state = shard_train_state(create_train_state(cfg, jax.random.PRNGKey(0)), mesh)
    ring = device_ring_init(C, 376, 17, mesh=mesh)
    tree = dper.DevicePerSync(C, cfg.per_alpha, mesh=mesh).tree
    key = jax.device_put(jax.random.PRNGKey(1), NamedSharding(mesh, P()))
    mega = make_megastep_device_per_sharded(cfg, k, b, mesh)

    per_sync = [dp.describe_sync(t, D)["collectives"] for t in synced_trees(cfg, state)]
    assert per_sync == [2, 2]

    text = mega.lower(state, ring, tree, key).as_text()
    assert not re.search(ADDING, text)
    counts = _count_collectives(
        jax.make_jaxpr(mega)(state, ring, tree, key).jaxpr)
    assert {kv for kv in counts if kv[0] not in MOVING} == set()
    in_scan = sum(n for (_, inside), n in counts.items() if inside)
    outside = sum(n for (_, inside), n in counts.items() if not inside)
    assert in_scan == sum(per_sync) == 4
    assert counts["all_to_all", True] == 2 and counts["all_gather", True] == 2
    assert outside == 2 and counts["all_gather", False] == 2


# ------------------------------------------------------------ describe_sync
def _shapes(shapes, dtype=F32):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, dtype), shapes, is_leaf=_is_shape)


def test_describe_sync_at_the_four_chip_cells_shapes():
    """``humanoid_b256.learn_per_dp4``: two syncs a grad step, one buffer
    each; a chip receives three foreign copies of its quarter, then three
    foreign quarter means — 2 x 3/4 of the buffer, not 3 x."""
    from d4pg_tpu.agent import D4PGConfig, create_train_state
    from d4pg_tpu.models.critic import DistConfig
    from d4pg_tpu.agent.d4pg import synced_trees

    cfg = D4PGConfig(
        obs_dim=376, action_dim=17, hidden_sizes=(256, 256, 256),
        dist=DistConfig(num_atoms=51, v_min=0.0, v_max=1500.0), n_step=5)
    state = jax.eval_shape(lambda: create_train_state(cfg, jax.random.PRNGKey(0)))
    critic, actor_and_metrics = (dp.describe_sync(t, 4) for t in synced_trees(cfg, state))
    assert critic == {
        "shards": 4, "leaves": 8, "buffers": 1, "floats": 245_555,
        "padding": 205, "collectives": 2,
        "bytes_received_per_chip": 2 * 3 * (245_760 // 4) * 4}
    assert actor_and_metrics == {
        "shards": 4, "leaves": 13, "buffers": 1, "floats": 232_465 + 5,
        "padding": 1002, "collectives": 2,
        "bytes_received_per_chip": 2 * 3 * (233_472 // 4) * 4}
    # against what the per-leaf gather received: 3 whole foreign copies
    assert critic["bytes_received_per_chip"] < 0.51 * (3 * 245_555 * 4)
    # the same trees, described from their shapes alone
    assert dp.describe_sync(_shapes(HUMANOID_CRITIC), 4) == critic
    assert dp.describe_sync(_shapes((HUMANOID_ACTOR, METRICS)), 4) == actor_and_metrics


def test_describe_sync_of_a_two_buffer_tree(monkeypatch):
    """Past the cap a tree takes more buffers: greedy, in tree order, a leaf
    over the cap alone, a buffer a dtype."""
    monkeypatch.setattr(dp, "SYNC_BUCKET_FLOATS", 64)
    shapes = {"a": (5,), "big": (40, 9), "c": (3, 4), "d": (), "e": (50,)}
    leaves = jax.tree.leaves(_shapes(shapes))
    align = 2 * dp._SLICE_ALIGN
    # a + c + d = 18 floats share a buffer; big (360) goes alone; e (50) would
    # take the open buffer past 64 and opens the next
    assert dp.sync_buckets(leaves, 2) == [
        ([0, 2, 3], 18, align - 18), ([1], 360, align - 360), ([4], 50, align - 50)]
    described = dp.describe_sync(_shapes(shapes), 2)
    assert described["buffers"] == 3 and described["collectives"] == 6
    assert described["floats"] == 5 + 360 + 12 + 1 + 50
    assert described["bytes_received_per_chip"] == 3 * 2 * 1 * dp._SLICE_ALIGN * 4
    monkeypatch.setattr(dp, "SYNC_BUCKET_FLOATS", 512)
    assert dp.describe_sync(_shapes(shapes), 2)["buffers"] == 1
    mixed = {"a": jax.ShapeDtypeStruct((5,), F32), "b": jax.ShapeDtypeStruct((6,), BF16),
             "c": jax.ShapeDtypeStruct((7,), F32)}
    assert [m for m, _, _ in dp.sync_buckets(jax.tree.leaves(mixed), 2)] == [[0, 2], [1]]


@pytest.mark.parametrize("dp_size", [4, 0])
def test_trainer_logs_the_gradient_sync_once_under_dp(tmp_path, capsys, dp_size):
    """How a sync crosses the chips is static (the gradient trees' shapes and
    the dp size), so the trainer prints it once at start-up under ``--dp``,
    and not at all without."""
    from d4pg_tpu.agent.d4pg import synced_trees
    from d4pg_tpu.runtime.trainer import Trainer
    from tests.test_megastep import _trainer_cfg

    cfg = _trainer_cfg("device", str(tmp_path / "d"), dp=dp_size)
    t = Trainer(cfg)
    try:
        described = [
            dp.describe_sync(tree, dp_size or 1)
            for tree in synced_trees(cfg.agent, t.state)]
    finally:
        t.close()
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("[parallel] gradient sync: ")]
    if not dp_size:
        assert lines == []
        return
    assert len(lines) == 1
    logged = json.loads(lines[0].split(": ", 1)[1])
    assert logged == described
    assert [d["buffers"] for d in logged] == [1, 1]
    assert [d["collectives"] for d in logged] == [2, 2]
    # actor gradients + the step metrics (five with the categorical head)
    actor_floats = sum(x.size for x in jax.tree.leaves(t.state.actor_params))
    assert logged[1]["floats"] == actor_floats + 5
