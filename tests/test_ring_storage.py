"""Lane-dense storage of wide ring rows (``replay/device_ring.py``).

A ``[C, W]`` row field whose width is neither under 128 nor a multiple of
it is STORED ``[C / P, P * W]`` (``P = 128 // gcd(W, 128)``), because the
TPU's default layout for the logical shape is feature-major and XLA then
copies the whole array before every row gather. The contracts here, all on
the CPU and bit for bit:

1. the rule is decided by the shape and nothing else, and construction is
   idempotent and lets what is not an array through;
2. ``ring.rows(name, idx)`` equals ``logical[idx]``;
3. ingest into stored form equals the logical
   ``at[slots].set(..., mode="drop")``: pad rows dropped, wrap-around,
   duplicate slots last-wins.

What the chip's compiler makes of it is ``test_ring_storage_v5e.py``'s.
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from d4pg_tpu.agent.d4pg import gather_batches  # noqa: E402
from d4pg_tpu.parallel.partition import ring_partition_specs  # noqa: E402
from d4pg_tpu.replay.device_ring import (  # noqa: E402
    ROW_FIELDS,
    DeviceRing,
    device_ring_init,
    ingest_body,
    rows_per_storage_row,
    sharded_ingest_body,
    storage_shape,
)

ACTION = 6
WIDTHS = {17: 1, 128: 1, 136: 16, 348: 32, 376: 16}   # width -> P
CAPACITIES = (64, 40)       # 64 divides by every P here; 40 by none above 1
SHAPES = pytest.mark.parametrize("lanes", (None, 2), ids=("flat", "lanes"))
GEOMETRY = pytest.mark.parametrize("capacity", CAPACITIES)
WIDTH = pytest.mark.parametrize("width", sorted(WIDTHS))


def _logical(capacity, width, lanes=None, seed=0):
    """The five row fields as plain ``[(lanes,) C, ...]`` numpy arrays."""
    r = np.random.default_rng(seed)
    lead = () if lanes is None else (lanes,)
    f32 = lambda *tail: r.normal(size=lead + (capacity,) + tail).astype(np.float32)  # noqa: E731
    return dict(obs=f32(width), action=f32(ACTION), reward=f32(),
                next_obs=f32(width), discount=f32())


def _ring(fields, size):
    return DeviceRing(size=jnp.int32(size),
                      **{k: jnp.asarray(v) for k, v in fields.items()})


def _p(capacity, width):
    return WIDTHS[width] if capacity % WIDTHS[width] == 0 else 1


@WIDTH
def test_rule_is_the_shape_alone(width):
    p = WIDTHS[width]
    assert rows_per_storage_row(width) == p
    assert (p * width) % 128 == 0 or width < 128
    assert storage_shape((64, width)) == (64 // p, p * width)
    assert storage_shape((3, 64, width)) == (3, 64 // p, p * width)
    # a stored shape is its own stored shape; odd capacities stay logical
    assert storage_shape(storage_shape((64, width))) == storage_shape((64, width))
    assert storage_shape((40, width)) == (40, width)
    assert storage_shape((2, width)) == (2, width)       # the spec templates
    assert storage_shape((64,)) == (64,)


@WIDTH
@GEOMETRY
@SHAPES
def test_construction_is_idempotent(width, capacity, lanes):
    fields = _logical(capacity, width, lanes)
    ring = _ring(fields, capacity)
    p = _p(capacity, width)
    lead = () if lanes is None else (lanes,)
    assert ring.obs.shape == lead + (capacity // p, p * width)
    assert ring.next_obs.shape == ring.obs.shape
    assert ring.action.shape == lead + (capacity, ACTION)
    assert ring.capacity == capacity
    assert ring.rows_packed("obs") == p
    assert ring.rows_packed("action") == 1
    assert ring.rows_packed("reward") == 1
    # re-wrapping, pytree round trip, _replace with a logical value: all safe
    again = DeviceRing(*ring)
    leaves, treedef = jax.tree_util.tree_flatten(ring)
    assert len(leaves) == 6
    flat = jax.tree_util.tree_unflatten(treedef, leaves)
    replaced = ring._replace(obs=jnp.asarray(fields["obs"]))
    for other in (again, flat, replaced):
        assert type(other) is DeviceRing
        for a, b in zip(other, ring):
            assert a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the logical view is the plain reshape back
    for name in ROW_FIELDS:
        np.testing.assert_array_equal(np.asarray(ring.logical(name)), fields[name])
    # and a traced construction stores the same way
    shapes = jax.eval_shape(lambda f: DeviceRing(size=jnp.int32(0), **f), fields)
    assert shapes.obs.shape == ring.obs.shape
    assert DeviceRing(*shapes).obs.shape == ring.obs.shape   # structs pass


@WIDTH
@GEOMETRY
def test_what_is_not_an_array_passes_through(width, capacity):
    ring = _ring(_logical(capacity, width), capacity)
    specs = ring_partition_specs(ring)
    assert type(specs) is DeviceRing
    assert specs.obs == P("dp", None) and specs.reward == P("dp") and specs.size == P()
    axes = DeviceRing(obs=0, action=0, reward=0, next_obs=0, discount=0, size=None)
    assert tuple(axes) == (0, 0, 0, 0, 0, None)
    mapped = jax.tree_util.tree_map(lambda x: x.shape, ring)
    assert mapped.obs == ring.obs.shape and mapped.size == ()
    described = ring.describe_storage()
    p = _p(capacity, width)
    assert described["obs"] == {
        "width": width, "rows_per_storage_row": p,
        "stored_shape": (capacity // p, p * width), "bytes": capacity * width * 4}
    assert described["action"]["rows_per_storage_row"] == 1
    assert described["reward"] == {
        "width": 1, "rows_per_storage_row": 1, "stored_shape": (capacity,),
        "bytes": capacity * 4}


@WIDTH
@GEOMETRY
def test_device_ring_init_is_stored_form(width, capacity):
    ring = device_ring_init(capacity, width, ACTION)
    p = _p(capacity, width)
    assert ring.obs.shape == (capacity // p, p * width)
    assert ring.capacity == capacity and int(ring.size) == 0


@WIDTH
@GEOMETRY
@SHAPES
def test_rows_equal_logical_index(width, capacity, lanes):
    fields = _logical(capacity, width, lanes, seed=1)
    ring = _ring(fields, capacity)
    r = np.random.default_rng(2)
    idx = r.integers(0, capacity, size=(3, 8)).astype(np.int32)
    idx[0, :3] = capacity - 1            # the last row, repeated
    idx[1, :2] = idx[1, 2]               # repeats inside a batch
    idx[2, 0] = 0
    gather = jax.jit(gather_batches)
    if lanes is None:
        got = gather(ring, jnp.asarray(idx))
        want = {k: v[idx] for k, v in fields.items()}
    else:
        axes = DeviceRing(obs=0, action=0, reward=0, next_obs=0, discount=0, size=None)
        got = jax.vmap(gather, in_axes=(axes, None))(ring, jnp.asarray(idx))
        want = {k: np.stack([v[lane][idx] for lane in range(lanes)])
                for k, v in fields.items()}
    for name in ROW_FIELDS:
        np.testing.assert_array_equal(np.asarray(got[name]), want[name])
        assert got[name].dtype == jnp.float32


def _chunk(n, width, seed):
    r = np.random.default_rng(seed)
    f32 = lambda *tail: r.normal(size=(n,) + tail).astype(np.float32)  # noqa: E731
    return dict(obs=f32(width), action=f32(ACTION), reward=f32(),
                next_obs=f32(width), discount=f32())


def _last_wins(field, slots, rows):
    """``at[slots].set(rows, mode="drop")`` written out: in order, pads
    (slot == capacity) dropped."""
    out = field.copy()
    for slot, row in zip(slots, rows):
        if slot < len(out):
            out[slot] = row
    return out


@WIDTH
@GEOMETRY
@pytest.mark.parametrize("body", ("ingest_body", "sharded_ingest_body"))
def test_ingest_equals_logical_scatter(width, capacity, body):
    fields = _logical(capacity, width, seed=3)
    ring = _ring(fields, 5)
    n = 24
    # wrap-around (…, C-2, C-1, 0, 1, …), duplicates (the later row wins,
    # one of them sharing a storage row with its neighbours), pad rows
    slots = (capacity - 6 + np.arange(n)) % capacity
    slots[9], slots[15] = slots[4], slots[4]
    slots[20:] = capacity
    slots = slots.astype(np.int32)
    chunk = _chunk(n, width, seed=4)
    if body == "ingest_body":
        fn = jax.jit(ingest_body, donate_argnums=(0,))
        out = fn(ring, chunk, jnp.asarray(slots), jnp.int32(capacity))
    else:   # the shard_map body sees its [1, n, ...] sub-chunk
        fn = jax.jit(sharded_ingest_body, donate_argnums=(0,))
        out = fn(ring, {k: v[None] for k, v in chunk.items()},
                 jnp.asarray(slots)[None], jnp.int32(capacity))
    assert type(out) is DeviceRing and int(out.size) == capacity
    assert out.obs.shape == storage_shape((capacity, width))
    for name in ROW_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(out.logical(name)),
            _last_wins(fields[name], slots, chunk[name]))
        # and it is what the logical scatter itself gives
        np.testing.assert_array_equal(
            np.asarray(out.logical(name)),
            np.asarray(jnp.asarray(fields[name]).at[slots].set(chunk[name], mode="drop")))


def test_sharded_ring_needs_whole_storage_rows_per_shard():
    from d4pg_tpu.parallel import make_mesh

    mesh = make_mesh(dp=4, tp=1)
    ring = device_ring_init(128, 136, ACTION, mesh=mesh)      # 8 storage rows
    assert {s.data.shape for s in ring.obs.addressable_shards} == {(2, 16 * 136)}
    assert {s.data.shape for s in ring.reward.addressable_shards} == {(32,)}
    with pytest.raises(ValueError, match="16 x dp=4"):
        device_ring_init(32, 136, ACTION, mesh=mesh)          # 2 storage rows
    device_ring_init(36, 136, ACTION, mesh=mesh)              # logical: 36 % 16
