"""Device-resident PER: the priority structure lives in HBM (ROADMAP item 2).

Until this module, prioritized replay was the one scenario that still
tethered the learner to the host: ``--replay-placement device`` downgraded
PER to uniform, and ``hybrid`` shipped [K, B] index/IS-weight blocks from
the host sum-tree every dispatch — dragging the host lock and staging
machinery along. Here the sum tree itself moves on-chip: a log-depth
segment tree over the ring's ``[capacity]`` α-exponentiated priorities,
stored as the same flat ``[2L]`` array layout the host trees use
(``replay/segment_tree.py``: root at index 1, leaves at ``[L, 2L)`` with
``L = next_pow2(capacity)``), so stratified descent, IS-weight
computation, and post-step priority write-back all happen INSIDE the
fused megastep (``runtime/megastep.py:megastep_device_per_body``) with
zero host operands in steady state.

Layout and semantics mirror the host ``PrioritizedReplayBuffer`` exactly
— same stratified equal-mass segments, same round-robin block dealing,
same ``(|td| + ε)^α`` write-back, same max-priority seed for new rows —
but in f32 (device arithmetic) instead of the host trees' f64. The host
sum-tree stays the SEEDED PARITY ORACLE (the PR-6 discipline): the
device draw's prefixes are reproducible on host from the same key
(threefry is backend-deterministic), so tests descend the host tree with
the identical prefixes and pin identical index draws, f32-close IS
weights, and f32-close post-writeback priorities — frozen-literal-pinned
on both host tree backends (``tests/test_device_per.py``).

Sharding (dp): each shard owns a SHARD-LOCAL subtree over its
``capacity/dp`` striped ring rows (``device_ring.striped_perm`` — the
same layout the sharded ring uses, so tree row ``i`` of shard ``d`` IS
ring row ``i`` of shard ``d``), and the only cross-shard arithmetic is a
tiny replicated root combine — fixed-order reductions over the
``all_gather``-ed per-shard roots/minima, the PR-9 ``det_pmean``
discipline — which is what makes the 8-way mesh bit-exact against the
single-device vmap oracle. Each shard contributes ``batch/dp`` draws
proportional to its LOCAL mass (the fixed per-shard batch shape the
megastep needs); the true sampling probability of row ``i`` on shard
``d`` is therefore ``p_i / (D · T_d)`` and the IS weights correct for
exactly that two-level distribution, normalized by the GLOBAL max
weight. Striped ingest keeps shard masses statistically identical, so
the scheme converges to global-mass PER as priorities mix; at ``dp=1``
it reduces to the host formula term for term.

Backend ladder (the ``ops/pallas_projection.py`` convention): the jnp
log-depth descent here is the reference program; a Pallas
kernel that runs the same walk without the gathers
(``ops/pallas_tree.py``) is selectable via
``TrainConfig.device_tree_backend="pallas"`` with the XLA path kept as
its equivalence oracle.

Draws (``descend_prefix``) walk the tree one level a step and read each
step's left child by ONE gather for the whole batch — except on the top
levels, whose few words every draw of a dispatch shares: there a large
draw reads them by a fused compare-and-select over the level's static
slice (``left_by_select``), no gather (``draw_plan``: the static shapes
decide, no flag; a draw under ``DENSE_DRAW_MIN_DRAWS`` prefixes traces the
all-gather walk, ``descend_prefix_gather``, op for op). The walk and its
f32 arithmetic are the same either way, so the leaves are equal for every
input.

Writes (``set_leaves``: the post-step write-back and the ingest seed)
are ONE scatter of the leaves, then the ancestors: level ``d`` is rebuilt
whole — the pairwise f32 sum of the contiguous child level, in place in
the donated buffer, no gather and no scatter (``rebuild_ancestors``) —
when ``2^d <= n * DENSE_REPAIR_RATIO`` for ``n`` written positions, and
repaired position by position (``repair_ancestors``, two gathers and a
scatter a level) below that, so a handful of slots does not pay for the
whole tree (``repair_plan``: the static shapes decide, no flag). Either
way a parent is ``left + right`` in f32 and an untouched parent keeps its
value, so the two give the same tree to the bit. Duplicate draws resolve
last-wins by a two-key sort (``update_leaves_last_wins``).

The traced functions here are listed in d4pglint's ``MEGASTEP_FUNCTIONS``
manifest: host numpy / ``.item()`` inside them would smuggle a per-step
host sync into the zero-transfer loop.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class DevicePerTree(NamedTuple):
    """The device priority structure: ``sums`` is ``[S, 2L]`` f32 — one
    flat segment tree per dp shard lane (S = dp, or 1 unsharded), root at
    ``[lane, 1]``, leaves at ``[lane, L:2L)`` over the shard's LOCAL ring
    rows; ``max_priority`` is the replicated pre-α running maximum (the
    host buffer's ``_max_priority`` twin) that seeds newly ingested rows
    at ``max_priority**α``."""

    sums: jax.Array          # [S, 2L] f32
    max_priority: jax.Array  # scalar f32, replicated


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def tree_width(local_capacity: int) -> int:
    """Flat-array width of one lane's tree: ``2 * next_pow2(local_cap)``."""
    return 2 * next_pow2(local_capacity)


def device_per_init(
    capacity: int, *, n_shards: int = 1, mesh=None, max_priority: float = 1.0
) -> DevicePerTree:
    """A zero-mass tree for a ``capacity``-row ring. With ``mesh``, the
    lane axis is placed over "dp" (``parallel/partition.py:PER_TREE_RULES``)
    — shard-local subtrees, replicated max-priority scalar. device_put
    COMMITS the arrays for the same jit-cache-key reason as
    ``device_ring_init``."""
    if capacity % n_shards:
        raise ValueError(
            f"device PER tree: capacity {capacity} not divisible by "
            f"dp={n_shards}"
        )
    width = tree_width(capacity // n_shards)
    tree = DevicePerTree(
        sums=jnp.zeros((n_shards, width), jnp.float32),
        max_priority=jnp.float32(max_priority),
    )
    return _place_tree(tree, mesh)


def _place_tree(tree: DevicePerTree, mesh) -> DevicePerTree:
    """Commit a host-built tree to device: plain device_put unsharded, or
    per-leaf NamedSharding placement from ``PER_TREE_RULES`` on a mesh —
    THE one placement path (init and snapshot-restore share it, so the
    two can never place differently)."""
    if mesh is None:
        return jax.device_put(tree)
    from jax.sharding import NamedSharding

    from d4pg_tpu.parallel.partition import tree_partition_specs

    specs = tree_partition_specs(tree)
    if jax.process_count() > 1:
        # Collective-free multi-host placement (the host-built tree is
        # SPMD-identical on every process — same sidecar bytes / same
        # seeds): device_put onto non-addressable shardings fires a
        # per-leaf agreement broadcast that deadlocks against in-flight
        # transfer programs under gloo (distributed.stage_global).
        from d4pg_tpu.parallel.distributed import stage_global

        return DevicePerTree(
            *(stage_global(mesh, spec, leaf) for leaf, spec in zip(tree, specs))
        )
    return DevicePerTree(
        *(
            jax.device_put(leaf, NamedSharding(mesh, spec))
            for leaf, spec in zip(tree, specs)
        )
    )


# ----------------------------------------------------- per-lane traced ops
# How many streamed tree words one random tree access is worth on the chip:
# level ``d`` (``2^d`` parents) is rebuilt densely when ``2^d <= n * R`` for
# ``n`` written positions, else repaired position by position. Set from the
# v5e (PERF.md section 5, PR 28): a position costs ~130 ns a level in a
# 2^26-element tree (two gathers and a scatter) and a densely rebuilt parent
# 0.041 ns, so the two meet at ``2^d = 3,170 n``; in a 2^22-element tree
# (21-64 ns against 0.018) at 1,150-3,500 n.
DENSE_REPAIR_RATIO = 2048
# The dense pass pairs lanes of the ``[2L / 128, 128]`` view of the flat tree
# (a bitcast of its 1-D tiled layout); levels narrower than this many
# children take 1-D stride-2 slices instead (a few ops of ~1 us each).
_LANES = 128
_LANE_FORM_MIN_CHILDREN = 2048


def repair_plan(width: int, n: int) -> tuple[int, int]:
    """``(sparse_levels, dense_levels)`` of a write of ``n`` positions into
    a ``[width]`` tree lane: the lowest ``sparse_levels`` parent levels are
    repaired position by position (:func:`repair_ancestors`), the
    ``dense_levels`` above them rebuilt whole (:func:`rebuild_ancestors`).
    Decided by the two static shapes alone."""
    depth = (width // 2).bit_length() - 1
    # 2^d <= n R  <=>  d < bit_length(n R)
    dense = min(depth, (n * DENSE_REPAIR_RATIO).bit_length())
    return depth - dense, dense


def describe_repair(width: int, n: int) -> dict:
    """The static line that says which way a write of ``n`` positions into a
    ``[width]`` lane is repaired (``Trainer`` logs it once, next to the
    ring's ``describe_storage``)."""
    sparse, dense = repair_plan(width, n)
    return {
        "tree_width": width, "positions": n, "sparse_levels": sparse,
        "dense_levels": dense, "R": DENSE_REPAIR_RATIO,
    }


def repair_ancestors(
    sums_lane: jax.Array, pos: jax.Array, levels: int | None = None
) -> jax.Array:
    """Recompute the ancestors of the leaf positions ``pos`` (``[n]``
    int32; out-of-bounds entries ``>= 2L`` stay out of bounds and are
    dropped) on the lowest ``levels`` parent levels (default: all of them,
    up to the root), two gathers and one scatter of all ``n`` positions per
    level. Duplicate parents all write the identical children-derived
    value, so the scatter is deterministic. The sparse half of a tree
    write — what :func:`set_leaves` keeps for the levels too wide for ``n``
    (:func:`repair_plan`) — and the oracle the dense rebuild is held to,
    bit for bit (``tests/test_tree_rebuild.py``)."""
    width = sums_lane.shape[0]
    depth = (width // 2).bit_length() - 1
    for _ in range(depth if levels is None else levels):
        # Pads keep pointing past the end instead of dividing back into
        # range (capacity//2 would alias a real node).
        pos = jnp.where(pos < width, pos // 2, width)
        vals = sums_lane[2 * pos] + sums_lane[2 * pos + 1]
        sums_lane = sums_lane.at[pos].set(vals, mode="drop")
    return sums_lane


def rebuild_ancestors(sums_lane: jax.Array, levels: int) -> jax.Array:
    """Recompute the top ``levels`` parent levels whole, lowest first: level
    ``d`` is the pairwise f32 sum of the contiguous child level
    ``sums[2^(d+1) : 2^(d+2)]``, written in place to ``sums[2^d : 2^(d+1)]``
    — no gather and no scatter. A parent that no write touched already
    equals the f32 sum of its children (every path that builds or writes a
    tree keeps that), so it gets the value it has: the result is
    bit-identical to :func:`repair_ancestors` on the touched positions.

    What the v5e's compiler needs (PERF.md section 6, PR 28): the pairs are
    summed as a ``(1, 2)`` ``reduce_window`` over the ``[2L/128, 128]`` view
    of the whole lane, the child level picked by NEGATIVE row padding — a
    ``slice`` of it is materialised as a copy of the level, a stride-2
    ``slice`` de-interleaves at 16 GB/s, and a ``[.., 256]`` view is hoisted
    above the slice and relayouts the whole tree once a level."""
    for d in reversed(range(levels)):
        lo = 2 << d                    # children [lo, 2 lo), parents [lo/2, lo)
        if lo >= _LANE_FORM_MIN_CHILDREN:
            rows = sums_lane.reshape(-1, _LANES)
            first, last = lo // _LANES, 2 * lo // _LANES
            parents = jax.lax.reduce_window(
                rows, 0.0, jax.lax.add, (1, 2), (1, 2),
                padding=((-first, last - rows.shape[0]), (0, 0)),
            )
            rows = jax.lax.dynamic_update_slice(
                rows, parents.reshape(-1, _LANES), (first // 2, 0)
            )
            sums_lane = rows.reshape(-1)
        else:
            child = jax.lax.slice(sums_lane, (lo,), (2 * lo,))
            parents = (
                jax.lax.slice(child, (0,), (lo,), (2,))
                + jax.lax.slice(child, (1,), (lo,), (2,))
            )
            sums_lane = jax.lax.dynamic_update_slice(
                sums_lane, parents, (lo // 2,)
            )
    return sums_lane


def set_leaves(
    sums_lane: jax.Array, slots: jax.Array, values: jax.Array,
    local_capacity: int,
) -> jax.Array:
    """Assign leaf values at ring slots (``slots`` int32; pad entries
    ``>= local_capacity`` are dropped — the ring ingest's pad-slot
    convention) with ONE scatter, then bring the ancestors back in line:
    position by position on the levels too wide for ``len(slots)`` writes,
    densely above them (:func:`repair_plan`). ``values`` may be a scalar
    (the max-priority ingest seed) or ``[n]``."""
    width = sums_lane.shape[0]
    half = width // 2
    pos = jnp.where(slots < local_capacity, slots + half, width).astype(
        jnp.int32
    )
    vals = jnp.broadcast_to(values, pos.shape).astype(jnp.float32)
    sums_lane = sums_lane.at[pos].set(vals, mode="drop")
    sparse, dense = repair_plan(width, pos.shape[0])
    if sparse:
        sums_lane = repair_ancestors(sums_lane, pos, levels=sparse)
    return rebuild_ancestors(sums_lane, dense)


def update_leaves_last_wins(
    sums_lane: jax.Array, idx: jax.Array, values: jax.Array,
    local_capacity: int,
) -> jax.Array:
    """Leaf update with the HOST trees' duplicate semantics: when the same
    slot appears more than once in ``idx`` (one transition drawn into
    several rows of a [K, B] block), the LAST occurrence wins — numpy
    assignment order, which a bare XLA scatter does not guarantee. A
    two-key sort of the ``(slot, order)`` pairs puts each slot's
    occurrences side by side in draw order, so the winner is the last of
    its run; losers are routed out of bounds and dropped. (No
    capacity-sized scratch array: a scatter-max into one, its memset and
    its gather were 1.06 ms a dispatch at a 2^25-row ring.)"""
    idx = idx.reshape(-1).astype(jnp.int32)
    vals = values.reshape(-1).astype(jnp.float32)
    order = jnp.arange(idx.shape[0], dtype=jnp.int32)
    idx, _, vals = jax.lax.sort((idx, order, vals), num_keys=2)
    win = jnp.concatenate([idx[1:] != idx[:-1], jnp.ones((1,), bool)])
    slots = jnp.where(win, idx, local_capacity)
    return set_leaves(sums_lane, slots, vals, local_capacity)


def stratified_prefixes(
    key: jax.Array, k: int, batch: int, total: jax.Array
) -> jax.Array:
    """``[k, batch]`` prefix masses: one uniform per equal-mass segment of
    ``[0, total)``, segment ``j`` dealt to block ``[j % k, j // k]`` — the
    exact dealing `sample_block` uses, so batch ``i`` of a fused dispatch
    holds draws evenly spread across the WHOLE priority mass. The
    ``nextafter`` clamp guards the float edge where a prefix equal to
    ``total`` would fall off the last nonzero leaf (the host `_draw`
    guard, in f32)."""
    n = k * batch
    u = jax.random.uniform(key, (k, batch), jnp.float32)
    seg = (
        jnp.arange(n, dtype=jnp.float32)
        .reshape(batch, k)
        .T
    )
    pre = (seg + u) * (total / jnp.float32(n))
    return jnp.minimum(pre, jnp.nextafter(total, jnp.float32(0.0)))


# The draw's twin of ``DENSE_REPAIR_RATIO``: on the tree's top levels every
# draw of a dispatch reads the same few words, and a gather of them is the
# slowest the walk makes. Step ``l`` of the walk (``2^l`` candidate left
# children) is read by compare-and-select instead (:func:`left_by_select`)
# while ``2^l <= DENSE_DRAW_MAX_WORDS``. Set from the v5e
# (``scripts/tree_descent_levels.py``; PERF.md section 6, PR 33), 8,192
# draws: a select of 2^9 / 2^12 / 2^13 / 2^14 words costs 5.4 / 25 / 48 / 93
# us (it doubles a level: the vector unit's pace) against a gather's 58 us
# from the 2^22-word tree (which XLA holds in on-chip memory) and 231 us on
# the top levels of the 2^26-word one, 121 at 2^13 words, 113 at 2^14; the
# whole walk is shortest at 14 dense levels in the small tree (565 us, 1,249
# all-gather) and at 15 in the large (1,210, 3,920; 1,232 at 14), and at
# 13-14 for 512-2,048 draws.
DENSE_DRAW_MAX_WORDS = 8192
# ... and only for a draw of at least this many prefixes. The same runs: at
# 512 draws the walk with a dense top takes 69 against 102 us (2^22 words)
# and 95 against 222 (2^26). At 256 it would still win 17 and 57 us, of a
# step that draws so few for a model of 0.3-0.8 s a step: nothing — and
# those programs are then the all-gather walk's, op for op.
DENSE_DRAW_MIN_DRAWS = 512


def draw_plan(width: int, n: int) -> tuple[int, int]:
    """``(dense_levels, gather_levels)`` of a descent of ``n`` prefixes
    through a ``[width]`` tree lane: the top ``dense_levels`` steps of the
    walk read their left children by select (:func:`left_by_select`), the
    ``gather_levels`` below them by one gather a level. Decided by the two
    static shapes alone."""
    depth = (width // 2).bit_length() - 1
    dense = 0
    if n >= DENSE_DRAW_MIN_DRAWS:
        # 2^l <= W  <=>  l < bit_length(W)
        dense = min(depth, DENSE_DRAW_MAX_WORDS.bit_length())
    return dense, depth - dense


def describe_draw(width: int, n: int) -> dict:
    """The static line that says how a draw of ``n`` prefixes descends a
    ``[width]`` lane (``Trainer`` logs it once, beside
    :func:`describe_repair`)."""
    dense, gather = draw_plan(width, n)
    return {
        "tree_width": width, "draws": n, "dense_levels": dense,
        "gather_levels": gather, "max_words": DENSE_DRAW_MAX_WORDS,
        "min_draws": DENSE_DRAW_MIN_DRAWS,
    }


def descend_prefix_gather(
    sums_lane: jax.Array, prefixes: jax.Array
) -> jax.Array:
    """The all-gather descent: for each prefix mass, the leaf index ``i``
    with ``cumsum[0..i-1] <= prefix < cumsum[0..i]`` — one vector gather
    per tree level for the whole batch (the jnp twin of the host
    ``SumTree.find_prefixsum_idx``, >= semantics so zero-mass leaves are
    skipped and boundary prefixes select the next leaf). What
    :func:`descend_prefix` traces when no level is dense, and the oracle
    its dense top is held to, bit for bit
    (``tests/test_dense_descent.py``)."""
    width = sums_lane.shape[0]
    half = width // 2
    depth = half.bit_length() - 1
    flat = prefixes.reshape(-1)
    idx = jnp.ones(flat.shape, jnp.int32)
    for _ in range(depth):
        left = sums_lane[2 * idx]
        go_right = flat >= left
        flat = flat - jnp.where(go_right, left, jnp.float32(0.0))
        idx = 2 * idx + go_right.astype(jnp.int32)
    return (idx - half).reshape(prefixes.shape)


def left_by_select(
    sums_lane: jax.Array, idx: jax.Array, level: int
) -> jax.Array:
    """``sums_lane[2 * idx]`` for ``idx`` in ``[2^level, 2^(level+1))``
    without a gather: the candidates are the static stride-2 slice of the
    child level (``2^level`` words), each draw keeps its own by a compare
    against an iota, and the sum over the candidate axis adds ``+0.0`` for
    every other — exact. The ``[2^level, n]`` compare fuses into the reduce
    (the candidates on the major axis: an elementwise accumulate, no
    cross-lane step)."""
    lo = 2 << level                    # children [lo, 2 lo), parents [lo/2, lo)
    lefts = jax.lax.slice(sums_lane, (lo,), (2 * lo,), (2,))
    mine = idx - (lo // 2)
    which = jax.lax.broadcasted_iota(jnp.int32, (lo // 2, idx.shape[0]), 0)
    return jnp.sum(
        jnp.where(which == mine[None, :], lefts[:, None], jnp.float32(0.0)),
        axis=0,
    )


def descend_prefix(
    sums_lane: jax.Array, prefixes: jax.Array,
    dense_levels: int | None = None,
) -> jax.Array:
    """The XLA reference descent — :func:`descend_prefix_gather`'s walk,
    step for step and in f32, so the leaves are its leaves for every input
    — with the left child read by select instead of by gather on the top
    ``dense_levels`` steps (default: :func:`draw_plan`'s for this lane and
    this many prefixes; clamped to the tree's depth)."""
    width = sums_lane.shape[0]
    half = width // 2
    depth = half.bit_length() - 1
    flat = prefixes.reshape(-1)
    if dense_levels is None:
        dense_levels, _ = draw_plan(width, flat.shape[0])
    dense = min(dense_levels, depth)
    idx = jnp.ones(flat.shape, jnp.int32)
    for level in range(depth):
        if level < dense:
            left = left_by_select(sums_lane, idx, level)
        else:
            left = sums_lane[2 * idx]
        go_right = flat >= left
        flat = flat - jnp.where(go_right, left, jnp.float32(0.0))
        idx = 2 * idx + go_right.astype(jnp.int32)
    return (idx - half).reshape(prefixes.shape)


def lane_draw(
    sums_lane: jax.Array, key: jax.Array, k: int, batch: int,
    local_filled: jax.Array, *, tree_backend: str = "xla",
    interpret: bool = False,
):
    """One lane's stratified ``[k, batch]`` draw over its local mass.

    Returns ``(idx, p_leaf, total_local)`` — slot indices, their
    α-exponentiated leaf priorities, and this lane's root mass. The
    ``local_filled`` clamp mirrors the host ``_draw``'s ``size - 1``
    guard (at dp=1 ``local_filled`` IS the global fill count).
    ``tree_backend`` selects the descent implementation: "xla" is the
    reference log-depth gather descent, "pallas" the gather-free kernel
    (``ops/pallas_tree.py``) that runs the same walk and returns the same
    leaves."""
    width = sums_lane.shape[0]
    half = width // 2
    total = sums_lane[1]
    pre = stratified_prefixes(key, k, batch, total)
    if tree_backend == "pallas":
        from d4pg_tpu.ops.pallas_tree import find_prefix_pallas

        idx = find_prefix_pallas(sums_lane, pre, interpret=interpret)
    else:
        idx = descend_prefix(sums_lane, pre)
    idx = jnp.clip(idx, 0, jnp.maximum(local_filled - 1, 0))
    return idx, sums_lane[half + idx], total


def lane_min_leaf(sums_lane: jax.Array) -> jax.Array:
    """Minimum nonzero leaf priority of one lane — the host MinTree's
    root, computed on the fly (zero-mass leaves are never-ingested rows /
    pow2 padding; real priorities are always ``>= eps**α > 0``)."""
    half = sums_lane.shape[0] // 2
    leaves = sums_lane[half:]
    return jnp.min(jnp.where(leaves > 0, leaves, jnp.inf))


def beta_at(step: jax.Array, beta0: float, beta_steps: int) -> jax.Array:
    """``linear_schedule(step, beta_steps, beta0, 1.0)`` in-kernel: the β
    anneal as a pure function of the learner step (device scalar)."""
    frac = jnp.clip(
        step.astype(jnp.float32) / jnp.float32(max(beta_steps, 1)), 0.0, 1.0
    )
    return jnp.float32(beta0) + frac * jnp.float32(1.0 - beta0)


def importance_weights(
    p_leaf: jax.Array, total_local: jax.Array, min_ratio_global: jax.Array,
    n_global: jax.Array, n_shards: int, beta: jax.Array,
) -> jax.Array:
    """Max-normalized IS weights for the shard-stratified scheme: row
    ``i`` on shard ``d`` is drawn with probability ``p_i / (D · T_d)``
    (each shard contributes batch/D draws from its local mass), so
    ``w = (N · p)^{-β}`` normalized by the GLOBAL max weight
    ``(N · min_ratio_global)^{-β}``. At D=1 this is the host formula
    term for term."""
    p = p_leaf / (jnp.float32(n_shards) * total_local)
    w = (p * n_global.astype(jnp.float32)) ** (-beta)
    max_w = (min_ratio_global * n_global.astype(jnp.float32)) ** (-beta)
    return (w / max_w).astype(jnp.float32)


def write_back_lane(
    sums_lane: jax.Array, idx: jax.Array, priorities: jax.Array,
    alpha: float, eps: float, local_capacity: int,
):
    """Post-step priority write-back for one lane: ``(|td| + ε)^α`` into
    the leaves (duplicate draws resolve last-wins, the host semantics)
    plus this lane's contribution to the max-priority update. Returns
    ``(sums_lane', local_max_abs_priority)`` — the caller combines the
    local maxima across shards (an exact, order-independent reduce)."""
    mag = jnp.abs(priorities) + jnp.float32(eps)
    pa = mag ** jnp.float32(alpha)
    sums_lane = update_leaves_last_wins(sums_lane, idx, pa, local_capacity)
    return sums_lane, jnp.max(mag)


# -------------------------------------------------------------- tree ingest
def tree_ingest_lane_body(
    alpha: float, local_capacity: int, sums_lane: jax.Array,
    max_priority: jax.Array, slots: jax.Array,
) -> jax.Array:
    """Seed newly mirrored ring rows at ``max_priority**α`` — the
    ``add_batch`` contract, applied to exactly the slot chunk the ring
    ingest just scattered (pad slots ``>= local_capacity`` drop). In the
    d4pglint ``MEGASTEP_FUNCTIONS`` manifest: jit-traced, host coercions
    here would smuggle a per-flush sync into the device loop."""
    return set_leaves(
        sums_lane, slots, max_priority ** jnp.float32(alpha), local_capacity
    )


def make_tree_ingest(alpha: float, local_capacity: int, mesh=None):
    """The jitted donated-buffer tree-seed program: ``(tree, slots) ->
    tree``. One fixed slot-chunk shape (the ring sync's) → exactly one
    compile for the run (recompile-sentinel budget 1, the ``make_ingest``
    contract — a fresh wrapper per call so two trees never share a jit
    specialization cache).

    Unsharded: ``slots`` is the ring sync's ``[chunk_cap]`` int32 (pads =
    capacity). Sharded: ``slots`` is ``[dp, chunk_local]`` local slot ids
    (pads = local capacity), tree lanes and slot rows both split over
    "dp" by shard_map — seeding stays shard-local, no collectives."""
    if mesh is None:

        def _ingest(tree, slots):
            lane = tree_ingest_lane_body(
                alpha, local_capacity, tree.sums[0], tree.max_priority, slots
            )
            return DevicePerTree(lane[None], tree.max_priority)

        return jax.jit(_ingest, donate_argnums=(0,))

    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from d4pg_tpu.parallel.partition import tree_partition_specs

    n_shards = int(mesh.shape["dp"])
    template = DevicePerTree(
        sums=np.zeros((n_shards, 2), np.float32),
        max_priority=np.zeros((), np.float32),
    )
    tree_specs = tree_partition_specs(template)
    slots_spec = P("dp", None)

    def _lane(tree, slots):
        lane = tree_ingest_lane_body(
            alpha, local_capacity, tree.sums[0], tree.max_priority, slots[0]
        )
        return DevicePerTree(lane[None], tree.max_priority)

    mapped = shard_map(
        _lane,
        mesh=mesh,
        in_specs=(tree_specs, slots_spec),
        out_specs=tree_specs,
        check_vma=False,
    )
    to_sh = lambda s: jax.tree_util.tree_map(  # noqa: E731
        lambda x: NamedSharding(mesh, x), s,
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.jit(
        mapped,
        in_shardings=(to_sh(tree_specs), NamedSharding(mesh, slots_spec)),
        out_shardings=to_sh(tree_specs),
        donate_argnums=(0,),
    )


class DevicePerSync:
    """The trainer-side holder of the device tree between dispatches.

    Rides the ring sync's ``tree_hook`` seam
    (``device_ring.DeviceRingSync.flush``): every slot chunk the ring
    ingest ships is immediately seeded into the tree at
    ``max_priority**α`` from the SAME already-staged device slot array —
    zero extra H2D bytes, and the ring row and its priority leaf can
    never desynchronize. The megastep consumes ``self.tree`` (donated)
    and the trainer stores the returned tree back; ingest and dispatch
    both run on the learner thread, so the holder needs no lock.
    """

    def __init__(self, capacity: int, alpha: float, *, mesh=None,
                 max_priority: float = 1.0):
        self.capacity = int(capacity)
        self.alpha = float(alpha)
        self._mesh = mesh
        self.n_shards = int(mesh.shape["dp"]) if mesh is not None else 1
        self.local_capacity = self.capacity // self.n_shards
        self.tree = device_per_init(
            self.capacity, n_shards=self.n_shards, mesh=mesh,
            max_priority=max_priority,
        )
        self._ingest = make_tree_ingest(
            self.alpha, self.local_capacity, mesh=mesh
        )

    @property
    def ingest_fn(self):
        """The jitted tree-seed entry point (recompile-sentinel tracking)."""
        return self._ingest

    def on_chunk(self, slots_dev) -> None:
        """The ring sync's tree_hook target: seed this chunk's rows."""
        self.tree = self._ingest(self.tree, slots_dev)

    # ------------------------------------------------- snapshot / restore
    def snapshot_host(self) -> tuple[np.ndarray, float]:
        """Fetch the α-exponentiated leaf priorities in HOST slot order
        (``[capacity]`` f32) plus the pre-α max priority — the replay
        snapshot's priority sidecar (cold path: one D2H per checkpoint,
        never per step). On a process-spanning mesh the fetch routes
        through ``gather_global`` (a bare ``device_get`` raises on arrays
        spanning non-addressable devices), making this a COLLECTIVE there:
        every process must call it at the same point."""
        from d4pg_tpu.parallel.distributed import gather_global

        sums = gather_global(self.tree.sums)
        half = sums.shape[1] // 2
        lanes = sums[:, half: half + self.local_capacity]  # [S, local_cap]
        out = np.zeros(self.capacity, np.float32)
        from d4pg_tpu.replay.device_ring import striped_perm

        perm = striped_perm(self.capacity, self.n_shards)  # [S, local_cap]
        out[perm.reshape(-1)] = lanes.reshape(-1)
        return out, float(np.asarray(jax.device_get(self.tree.max_priority)))

    def restore_host(self, pa_host: np.ndarray, max_priority: float) -> None:
        """Rebuild the tree from snapshotted host-order α-exponentiated
        priorities (zeros stay zero-mass: rows the snapshot never
        covered). Setup path, never per step."""
        self.tree = tree_from_priorities(
            pa_host, self.capacity, n_shards=self.n_shards,
            max_priority=max_priority, mesh=self._mesh,
        )


def tree_from_priorities(
    pa_host: np.ndarray, capacity: int, *, n_shards: int = 1,
    max_priority: float = 1.0, mesh=None,
) -> DevicePerTree:
    """Build a :class:`DevicePerTree` from HOST-slot-order α-exponentiated
    priorities — the snapshot-restore path and the parity tests' oracle
    seeding. Plain numpy level-wise construction with the same f32
    pairwise sums the device repair computes, then one committed
    device_put (placed per ``PER_TREE_RULES`` when ``mesh`` is given)."""
    from d4pg_tpu.replay.device_ring import striped_perm

    pa_host = np.asarray(pa_host, np.float32)
    if pa_host.shape != (capacity,):
        raise ValueError(
            f"device PER tree: priorities shape {pa_host.shape} != "
            f"({capacity},)"
        )
    local_capacity = capacity // n_shards
    perm = striped_perm(capacity, n_shards)
    width = tree_width(local_capacity)
    half = width // 2
    sums = np.zeros((n_shards, width), np.float32)
    sums[:, half: half + local_capacity] = pa_host[perm]
    lo, hi = half, width
    while lo > 1:
        child = sums[:, lo:hi]
        parents = child[:, 0::2] + child[:, 1::2]
        lo, hi = lo // 2, lo
        sums[:, lo:hi] = parents
    tree = DevicePerTree(
        sums=jnp.asarray(sums), max_priority=jnp.float32(max_priority)
    )
    return _place_tree(tree, mesh)


# --------------------------------------------------------- host-side oracle
def host_prefixes(key, k: int, batch: int, total: float) -> np.ndarray:
    """The parity oracle's half of the RNG contract: reproduce the
    megastep's prefix draws on host from the same key (threefry is
    backend-deterministic — the ``draw_uniform_indices`` precedent).
    Feed these to the HOST tree's ``find_prefixsum_idx`` and the index
    draws must match the device descent exactly
    (tests/test_device_per.py pins the frozen literals)."""
    return np.asarray(
        stratified_prefixes(key, k, batch, jnp.float32(total))
    )
