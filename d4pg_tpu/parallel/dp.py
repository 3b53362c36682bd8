"""Explicit synchronous data parallelism: shard_map + pmean over ICI.

One function replaces the reference's Hogwild machinery (async gradient
aliasing ``ddpg.py:104-108``, shared Adam moments ``shared_adam.py:12-17``,
LR/n_workers rescale ``main.py:384-385``): every device holds replicated
params/optimizer state, computes gradients on its batch shard, and a single
``pmean`` AllReduce (riding ICI within a slice) synchronizes them — so all
replicas stay bit-identical and the reference's benign-by-design races
(SURVEY.md §5) are structurally impossible. No LR rescaling needed: pmean
averages, it does not sum.

The reference's staleness semantics are also available as an explicit
capability flag (SURVEY §2.2 DP row): :func:`make_hogwild_dp_train_step`
runs K grad steps per replica on its OWN diverging param copy with no
per-step sync, then one param/optimizer ``pmean`` resynchronizes — the
reference's workers likewise apply updates computed from stale params
(``ddpg.py:104-108``), except here the staleness is bounded by K and the
resync is deterministic instead of a lock-free race.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from d4pg_tpu.agent.d4pg import fused_train_scan, train_step
from d4pg_tpu.agent.state import D4PGConfig


def make_dp_train_step(config: D4PGConfig, mesh: Mesh, donate: bool = True):
    """Jitted (state, batch) → (state, metrics, priorities) over mesh axis "dp".

    State is replicated (spec ``P()``); batch rows are sharded over "dp";
    returned priorities come back fully assembled (spec ``P("dp")``) for the
    host-side PER write-back. Batch size must be divisible by mesh.shape["dp"].
    ``P("dp")`` is a pytree-PREFIX spec over the whole batch dict, so any key
    set works — uniform replay without IS weights included (the hardcoded
    six-key spec dict made PER's ``weights`` key load-bearing, VERDICT
    round-3 weak #3).
    """
    fn = partial(train_step, config, axis_name="dp")
    mapped = shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(), P("dp")),
        out_specs=(P(), P(), P("dp")),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def make_dp_fused_train_step(config: D4PGConfig, mesh: Mesh, donate: bool = True):
    """DP variant of ``fused_train_scan``: (state, batches [K, B, ...]) →
    (state, metrics [K], priorities [K, B]) — K grad steps per dispatch,
    batch rows sharded over "dp" within each scan step, one pmean per step
    riding ICI. The scan lives *inside* shard_map so the whole K-step chain
    is a single XLA program per device."""
    fn = partial(fused_train_scan, config, axis_name="dp")
    batch_spec = P(None, "dp")  # [K, B] — shard the batch axis, not the scan axis
    mapped = shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(), batch_spec),
        out_specs=(P(), P(), batch_spec),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


# What a sync costs on the v5e's 2x2 (PERF.md section 6, PR 30): a collective
# ~4.3 us whatever it moves, an ``all_to_all`` ~13 us and an ``all_gather``
# ~9 us a MiB of buffer on top; one buffer through both with its sum, 29 us
# at 1 MiB, 369 us at 16 MiB, 1.8 ms at 64 MiB.
#
# The most floats one packed buffer holds: leaves are packed greedily, in
# tree order, a dtype a buffer, and a larger leaf syncs alone, as its own
# buffer. A tree is not packed whole because packing holds a second copy of
# it; a full buffer (32 MiB of f32, ~0.8 ms) spends ~1% of its time on its
# two collectives' floors, so more buffers cost nothing that can be seen.
SYNC_BUCKET_FLOATS = 1 << 23
# A buffer crosses the chips as ``[size, rows, 128]``, a shard's slice whole
# (8, 128) tiles of f32: XLA:TPU keeps an ``all_gather`` of whole lanes as
# one and rewrites any other as an all-reduce of the WHOLE zero-filled
# buffer (twice the bytes), and the 1-D view of the same buffer
# (``[size, 1, n]``, (1, 128) tiles) read 6% slower in the four-chip cell.
_LANES = 128
_SLICE_ALIGN = 8 * _LANES


def sync_buckets(leaves, size: int) -> list[tuple[list[int], int, int]]:
    """How :func:`det_pmean` packs ``leaves`` (anything with ``shape`` and
    ``dtype``) for an axis of ``size`` shards: ``(leaf indices, floats,
    padding)`` a buffer, decided by the static shapes alone. Greedy in tree
    order, one open buffer a dtype, closed when the next leaf of that dtype
    would pass ``SYNC_BUCKET_FLOATS``; ``padding`` rounds the buffer up to
    ``size`` equal slices of whole ``_SLICE_ALIGN`` floats (each shard reduces
    one)."""
    buckets: list[list] = []           # [leaf indices, floats] a buffer
    open_by_dtype: dict = {}
    for i, leaf in enumerate(leaves):
        n = leaf.size
        cur = open_by_dtype.get(leaf.dtype)
        if n > SYNC_BUCKET_FLOATS:
            cur = [[], 0]              # alone: never the dtype's open buffer
            buckets.append(cur)
        elif cur is None or cur[1] + n > SYNC_BUCKET_FLOATS:
            cur = open_by_dtype[leaf.dtype] = [[], 0]
            buckets.append(cur)
        cur[0].append(i)
        cur[1] += n
    return [
        (members, floats, -floats % (size * _SLICE_ALIGN))
        for members, floats in buckets
    ]


def describe_sync(tree, size: int) -> dict:
    """The static line that says how one :func:`det_pmean` of ``tree`` over
    ``size`` shards crosses the chips (``Trainer`` logs it once under
    ``--dp``, next to the ring's ``describe_storage``): buffers, the floats
    and zero padding in them, collectives, and the bytes a chip receives —
    of every buffer the ``size - 1`` foreign copies of its own slice, then
    the ``size - 1`` foreign slice means."""
    leaves = jax.tree.leaves(tree)
    buckets = sync_buckets(leaves, size)
    received = sum(
        2 * (size - 1) * ((floats + pad) // size)
        * leaves[members[0]].dtype.itemsize
        for members, floats, pad in buckets
    )
    return {
        "shards": size,
        "leaves": len(leaves),
        "buffers": len(buckets),
        "floats": sum(floats for _, floats, _ in buckets),
        "padding": sum(pad for _, _, pad in buckets),
        "collectives": 2 * len(buckets),
        "bytes_received_per_chip": received,
    }


def _det_mean_buffer(x, axis_name: str, size: int):
    """The fixed-order mean of one ``[size * n]`` buffer, reduced by shards:
    shard ``d`` receives every shard's ``d``-th slice (``all_to_all``:
    exact), adds them shard 0 -> size-1 unrolled and divides, and the slice
    means are gathered back (``all_gather``: exact). Every element is
    ``(((x_0 + x_1) + ...) + x_{size-1}) / size`` in ``x``'s dtype, and a
    chip receives 2 (size-1)/size of the buffer, not ``size - 1`` of it."""
    g = jax.lax.all_to_all(x.reshape(size, -1, _LANES), axis_name, 0, 0)
    acc = g[0]
    for i in range(1, size):
        acc = acc + g[i]
    return jax.lax.all_gather(acc / size, axis_name, tiled=True).reshape(-1)


def det_pmean(tree, axis_name: str, size: int):
    """Deterministic cross-shard mean of a tree: packed into one buffer a
    sync, reduced in FIXED ORDER by shards, in place of ``pmean``.

    ``pmean`` lowers to the backend's AllReduce, whose f32 accumulation
    order is the backend's choice — measured on this container's XLA CPU
    it happens to accumulate in device order, but nothing pins that, and
    on real ICI it is a ring/tree. This combine makes the order part of
    the PROGRAM: the collectives only move data (``all_to_all``,
    ``all_gather``; never one that adds), the sum runs shard 0→N−1
    unrolled, so the identical function under a single-device ``vmap``
    with the same ``axis_name`` replays the sharded math BIT-EXACTLY — the
    byte-identity contract of the sharded megastep's parity oracle
    (runtime/megastep.py). ``size`` is the static axis size (the unroll
    bound; shard count, so single digits).

    How the tree crosses the chips: the leaves are ravelled and
    concatenated into one 1-D buffer a dtype, zero-padded to ``size`` slices
    of whole tiles (:func:`sync_buckets`; more than one buffer only past
    ``SYNC_BUCKET_FLOATS``), each buffer is reduced by
    :func:`_det_mean_buffer`, and the result is split and reshaped back.
    Element for element the arithmetic is the per-leaf ``all_gather`` +
    sum + divide this function was until PR 30 (tests pin bit-equality).

    Cost (``humanoid_b256.learn_per_dp4``, PERF.md section 6, PR 30): the
    per-leaf gathers were 57% of that cell's step — 14 collectives a grad
    step at ~3.5 us each whatever they moved, and every chip received three
    whole copies of the gradients; two buffers a step are 4 collectives
    and half the bytes, and the sync fell from 3.46 to 1.75 ms a dispatch.
    Packing alone (one ``all_gather`` of the whole buffer) did not: 3.63 ms.
    """
    leaves, treedef = jax.tree.flatten(tree)
    out = list(leaves)
    for members, _, pad in sync_buckets(leaves, size):
        group = [leaves[i] for i in members]
        buf, unravel = ravel_pytree(
            group + [jnp.zeros((pad,), group[0].dtype)]
        )
        means = unravel(_det_mean_buffer(buf, axis_name, size))
        for i, mean in zip(members, means):   # the padding's chunk is left
            out[i] = mean
    return jax.tree.unflatten(treedef, out)


def _pmean_floats(tree, axis_name: str):
    """pmean the float leaves; pass integer leaves (Adam's step count, the
    TrainState step counter) through unchanged — every replica advanced
    them identically, and pmean on ints would truncate the psum/n divide."""
    return jax.tree.map(
        lambda x: jax.lax.pmean(x, axis_name)
        if jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        tree,
    )


def make_hogwild_dp_train_step(config: D4PGConfig, mesh: Mesh, donate: bool = True):
    """Async-DP (Hogwild-staleness emulation, SURVEY §2.2): (state,
    batches [K, B, ...]) → (state, metrics [K], priorities [K, B]).

    Each replica scans its K batch shards with NO per-step gradient sync
    (``axis_name=None`` — params diverge within the window, exactly the
    staleness class the reference's lock-free workers accept), then ONE
    ``pmean`` over params + optimizer moments resynchronizes. Collective
    cost: 1 AllReduce per K steps instead of K — the Hogwild trade (staler
    updates for less synchronization) expressed as a capability flag
    instead of a race. At K=1 with identical shards this reduces exactly
    to the single-device step (tests/test_parallel.py)."""
    local = partial(fused_train_scan, config)  # axis_name=None: local steps

    def hogwild(state, batches):
        state, metrics, priorities = local(state, batches)
        state = _pmean_floats(state, "dp")
        metrics = _pmean_floats(metrics, "dp")
        return state, metrics, priorities

    batch_spec = P(None, "dp")
    mapped = shard_map(
        hogwild,
        mesh=mesh,
        in_specs=(P(), batch_spec),
        out_specs=(P(), P(), batch_spec),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def replicate(tree, mesh: Mesh):
    """Place a host pytree replicated across every device of the mesh."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)
