"""Fused training megastep over the device-resident replay ring.

The host trainer's steady-state loop pays a full host→device batch
upload and a device→host priority fetch per dispatch, and the chip idles
on exactly that traffic.  The megastep is the Podracer/Anakin answer: ONE
donated-buffer jitted call runs ``lax.scan`` over K grad steps — batch
gather from the HBM ring (``replay/device_ring.py``), the PR-1 fused
Pallas projection+loss (when ``projection_backend="pallas_fused"``), both
Adam updates, Polyak, and priority computation — and returns only device
scalars (plus, in hybrid PER mode, the ``[K, B]`` new-priority block for
host write-back).  Zero H2D/D2H per grad step in steady state; the PR-4
transfer guard enforces it at the dispatch site with the tightened
zero-transfer budget (``analysis.transfer.no_transfers``).

Three megasteps, by ``TrainConfig.replay_placement`` and PER:

- ``device``, uniform — index draw **in-kernel** via ``jax.random.randint``
  from a device-resident key that the megastep splits and returns (no host
  operand at all: state, ring, key all live on device between dispatches);
- ``device``, PER — the priority tree lives on the device too
  (``replay/device_per.py``): descent, IS weights and write-back run inside
  the same call. The form every benchmark cell measures (PERF.md §5);
- ``hybrid`` — PER on the host sum-tree, which ships only the ``[K, B]``
  int32 index / f32 weight arrays; rows are gathered on-device, priorities
  come back as one ``[K, B]`` block per dispatch. ``host`` has no megastep.

The batch gather happens ONCE before the scan (``gather_batches``), not
per scan step (per-step PRNG + scattered HBM reads otherwise; its cost on
the chip is ``replay.row_gather_ms``); all inside the single jitted call.

The ``*_body`` functions here are jit-traced (see the makers below) and
listed in d4pglint's ``MEGASTEP_FUNCTIONS`` manifest: host numpy,
``.item()`` or ``__array__`` coercions inside them would smuggle a host
sync / transfer into the zero-transfer loop and are lint errors.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from d4pg_tpu.agent.d4pg import fused_train_scan, gather_batches, train_step
from d4pg_tpu.agent.state import D4PGConfig, TrainState
from d4pg_tpu.ops.pallas_mode import pallas_interpret
from d4pg_tpu.replay.device_ring import DeviceRing
from d4pg_tpu.utils.profiling import phase


def draw_uniform_indices(key: jax.Array, k: int, batch: int,
                         size: jax.Array) -> jax.Array:
    """The megastep's in-kernel uniform draw, exposed so the host parity
    oracle can reproduce the exact index block from the same key (threefry
    is backend-deterministic)."""
    return jax.random.randint(key, (k, batch), 0, size)


def megastep_uniform_body(
    config: D4PGConfig, k: int, batch: int,
    state: TrainState, ring: DeviceRing, key: jax.Array,
):
    """K grad steps on in-kernel uniform draws from the ring.

    Returns ``(state, key', metrics)`` — all device-resident; ``key'`` is
    the split-forward key the trainer threads into the next dispatch, so
    steady state needs no host operand whatsoever."""
    with phase("replay.draw"):
        key, k_idx = jax.random.split(key)
        idx = draw_uniform_indices(k_idx, k, batch, ring.size)
    batches = gather_batches(ring, idx, config.torso)
    # Determinism contract (tests/test_megastep.py pins it): uniform IS
    # weights are identically 1, so leave the key OUT and let train_step's
    # internal ones-constant supply them — measured on XLA CPU, a ones
    # constant folds IDENTICALLY in this program and the host oracle's
    # staged-batch program (byte-identical params), whereas ones-as-input
    # on one side and ones-as-constant on the other round the loss
    # reduction differently (~1e-9 drift per step).
    del batches["weights"]
    state, metrics, _ = fused_train_scan(config, state, batches)
    return state, key, jax.tree.map(lambda x: x.mean(), metrics)


def megastep_hybrid_body(
    config: D4PGConfig,
    state: TrainState, ring: DeviceRing,
    idx: jax.Array, weights: jax.Array,
):
    """K grad steps on host-descended PER indices, rows gathered on-device.

    ``idx``/``weights`` are the ``[K, B]`` blocks the host sum-tree
    produced — the only per-dispatch H2D traffic of hybrid placement.
    Returns ``(state, metrics, priorities[K, B])``; the priority block is
    the only per-dispatch D2H (fetched by the existing write-back path)."""
    batches = gather_batches(ring, idx)
    batches["weights"] = weights
    state, metrics, priorities = fused_train_scan(config, state, batches)
    return state, jax.tree.map(lambda x: x.mean(), metrics), priorities


def make_megastep_uniform(config: D4PGConfig, k: int, batch: int):
    """Jitted donated-buffer uniform megastep: ``(state, ring, key) ->
    (state, key', metrics)``. The state is donated (params/moments update
    in place); the ring is read-only here and stays resident."""
    return jax.jit(
        partial(megastep_uniform_body, config, k, batch), donate_argnums=(0,)
    )


def make_megastep_hybrid(config: D4PGConfig):
    """Jitted donated-buffer hybrid-PER megastep: ``(state, ring, idx,
    weights) -> (state, metrics, priorities)``. K/B come from the index
    block's shape (one compile per (K, B), budgeted by the sentinel)."""
    return jax.jit(
        partial(megastep_hybrid_body, config), donate_argnums=(0,)
    )


# ------------------------------------------------------------ sharded (dp)
def sharded_megastep_uniform_body(
    config: D4PGConfig, k: int, b_local: int, n_shards: int,
    state: TrainState, ring: DeviceRing, key: jax.Array,
):
    """The per-shard megastep: K grad steps on shard-LOCAL uniform draws,
    gradients combined with the deterministic mean (ROADMAP item 2 — the
    PR-6 megastep spanning a dp mesh).

    Runs under TWO harnesses with the SAME bits (tests pin it):

    - ``shard_map`` over the dp mesh (:func:`make_megastep_uniform_sharded`)
      — ``ring`` is this shard's ``[capacity/dp, ...]`` row slice, the
      gather is physically shard-local, ``all_gather``/``axis_index`` ride
      the mesh axis;
    - single-device ``vmap`` with the same axis name
      (:func:`make_megastep_uniform_oracle`) — the parity oracle: lanes
      are the striped host-slot slices (``striped_perm``), the axis
      primitives act on the lane axis.

    Byte-identity between the two holds because everything per-shard is
    identical math on identical rows and the ONLY cross-shard arithmetic
    is :func:`~d4pg_tpu.parallel.dp.det_pmean`'s fixed-order sum — which
    is why this body must never use ``pmean`` directly (the backend
    AllReduce's accumulation order is not part of the program).

    Per-shard draw: split the replicated key, ``fold_in`` the shard index,
    draw ``[k, b_local]`` rows from the shard's ``size // n_shards``
    mirrored local rows (striping guarantees every shard has exactly that
    many FULLY-synced rows whenever ``size >= n_shards``). The global
    batch is the concatenation of the shard batches — B = b_local · dp —
    and the returned key threads forward exactly like the unsharded body.
    """
    with phase("replay.draw"):
        shard = jax.lax.axis_index("dp")
        key, k_idx = jax.random.split(key)
        local_n = ring.size // n_shards
        idx = jax.random.randint(
            jax.random.fold_in(k_idx, shard), (k, b_local), 0, local_n
        )
    batches = gather_batches(ring, idx)
    # Same determinism contract as megastep_uniform_body: the uniform
    # path carries NO weights key on either side.
    del batches["weights"]
    from d4pg_tpu.parallel.dp import det_pmean

    sync = partial(det_pmean, axis_name="dp", size=n_shards)
    state, metrics, _ = fused_train_scan(config, state, batches, sync_fn=sync)
    return state, key, jax.tree.map(lambda x: x.mean(), metrics)


def make_megastep_uniform_sharded(
    config: D4PGConfig, k: int, batch: int, mesh, rules=None,
):
    """Jitted donated-buffer SHARDED uniform megastep over a dp mesh:
    ``(state, ring, key) -> (state, key', metrics)``, in/out shardings
    from the partition-rule registry.

    The state's shardings come from ``match_partition_rules`` over the
    param tree (ensemble stacks included via ``stack_axes_for``); the
    ring's from ``RING_RULES`` (rows over "dp"); key and metrics
    replicate. The mesh must be dp-only (tp=1): inside ``shard_map``
    every mesh axis is manual, and the megastep's manual axis is "dp" —
    compose tp via the GSPMD host path instead. Zero per-grad-step
    transfers survive scale-out: state, ring, and key all live sharded on
    the mesh between dispatches, and the dispatch site runs under the
    same ``no_transfers`` budget as the single-device megastep."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from d4pg_tpu.parallel.partition import (
        DEFAULT_RULES,
        _abstract_state,
        _state_specs,
        ring_partition_specs,
        stack_axes_for,
    )

    n_shards = int(mesh.shape["dp"])
    if int(mesh.shape.get("tp", 1)) != 1:
        raise ValueError(
            "sharded megastep mesh must be dp-only (tp=1); tensor "
            "parallelism composes via the GSPMD host path "
            f"(got tp={mesh.shape['tp']})"
        )
    if batch % n_shards:
        raise ValueError(
            f"sharded megastep: batch {batch} not divisible by dp={n_shards}"
        )
    dummy = jax.eval_shape(
        lambda kk: _abstract_state(config, kk), jax.random.PRNGKey(0)
    )
    state_specs = _state_specs(
        dummy, rules or DEFAULT_RULES, mesh, stack_axes_for(config)
    )
    ring_template = DeviceRing(
        obs=jnp.zeros((2, config.obs_dim)),
        action=jnp.zeros((2, config.action_dim)),
        reward=jnp.zeros((2,)),
        next_obs=jnp.zeros((2, config.obs_dim)),
        discount=jnp.zeros((2,)),
        size=jnp.zeros((), jnp.int32),
    )
    ring_specs = ring_partition_specs(ring_template)
    body = partial(
        sharded_megastep_uniform_body, config, k, batch // n_shards, n_shards
    )
    mapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(state_specs, ring_specs, P()),
        out_specs=(state_specs, P(), P()),
        check_vma=False,
    )
    to_shardings = lambda specs: jax.tree_util.tree_map(  # noqa: E731
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    key_sharding = NamedSharding(mesh, P())
    return jax.jit(
        mapped,
        in_shardings=(to_shardings(state_specs), to_shardings(ring_specs),
                      key_sharding),
        out_shardings=(to_shardings(state_specs), key_sharding,
                       NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )


# ------------------------------------------------------- device-resident PER
def megastep_device_per_body(
    config: D4PGConfig, k: int, b_local: int, n_shards: int,
    tree_backend: str, interpret: bool,
    state: TrainState, ring: DeviceRing, sums_lane: jax.Array,
    max_priority: jax.Array, key: jax.Array,
):
    """K grad steps on PER draws from the lane's device-resident segment
    tree (``replay/device_per.py``) — stratified descent, IS weights, and
    post-step priority write-back all inside the one jitted call, so
    steady state has ZERO host operands with prioritized replay ON (the
    draw that used to be the hybrid placement's host round-trip).

    Per-lane everything: the [k, b_local] draw comes from this shard's
    local mass (fold_in(shard) key, the sharded-uniform discipline), the
    gather and the write-back touch only local rows, and the ONLY
    cross-shard arithmetic is (a) gradients through ``det_pmean`` and
    (b) two exact order-independent reductions (global min weight ratio,
    global max |td|) over ``all_gather``-ed per-lane scalars — which is
    why the dp mesh is bit-exact vs the single-device vmap oracle
    (``make_megastep_device_per_oracle``), the PR-9 contract. At
    ``n_shards == 1`` the collectives compile away (static branch) and
    the sampling scheme reduces to the host ``PrioritizedReplayBuffer``
    formula term for term — the host-tree parity oracle rides that.

    Returns ``(state, sums_lane', max_priority', key', metrics)``.
    """
    from d4pg_tpu.replay import device_per as dper

    with phase("replay.draw"):
        if n_shards > 1:
            shard = jax.lax.axis_index("dp")
        else:
            shard = jnp.int32(0)
        key, k_draw = jax.random.split(key)
        # Shard-local fill count: striping lands host slot j on shard
        # j % D, so shard d holds ceil((size - d) / D) mirrored rows
        # (== size at D=1 — the host _draw's size-1 clamp).
        local_filled = (ring.size - shard + n_shards - 1) // n_shards
        idx, p_leaf, total_local = dper.lane_draw(
            sums_lane, jax.random.fold_in(k_draw, shard), k, b_local,
            local_filled, tree_backend=tree_backend, interpret=interpret,
        )
        min_ratio = dper.lane_min_leaf(sums_lane) / (
            jnp.float32(n_shards) * total_local
        )
        if n_shards > 1:
            # Exact order-independent reduce over the gathered lane
            # scalars (min is associative+commutative+exact in fp — no
            # fixed-order unroll needed for bit-parity, unlike the
            # gradient sum).
            min_ratio = jnp.min(jax.lax.all_gather(min_ratio, "dp"))
        beta = dper.beta_at(
            state.step, config.per_beta0, config.per_beta_steps
        )
        weights = dper.importance_weights(
            p_leaf, total_local, min_ratio, ring.size, n_shards, beta
        )
    batches = gather_batches(ring, idx, config.torso)
    batches["weights"] = weights
    if n_shards > 1:
        from d4pg_tpu.parallel.dp import det_pmean

        sync = partial(det_pmean, axis_name="dp", size=n_shards)
    else:
        sync = None
    state, metrics, priorities = fused_train_scan(
        config, state, batches, sync_fn=sync
    )
    with phase("replay.write_back"):
        sums_lane, mp_local = dper.write_back_lane(
            sums_lane, idx, priorities, config.per_alpha, config.per_eps,
            local_capacity=ring.capacity,
        )
        if n_shards > 1:
            mp_local = jnp.max(jax.lax.all_gather(mp_local, "dp"))
        max_priority = jnp.maximum(max_priority, mp_local)
    return (
        state, sums_lane, max_priority, key,
        jax.tree.map(lambda x: x.mean(), metrics),
    )


def megastep_device_per_fused_body(
    config: D4PGConfig, k: int, batch: int, interpret: bool,
    state: TrainState, ring: DeviceRing, sums_lane: jax.Array,
    max_priority: jax.Array, key: jax.Array,
):
    """The FUSED-TIER device-PER megastep (ISSUE 16): descent + loss as
    ONE Pallas program per scan step, software-pipelined.

    :func:`megastep_device_per_body` with ``tree_backend="pallas"`` runs
    the whole [K, B] descent as its own Pallas program before the scan,
    then K fused-loss programs inside it. The tree is CONSTANT during the
    scan (priorities write back after it, last-wins), so every step's
    prefixes are computable up front and the descents commute — which
    legalizes the pipeline: scan step ``t``'s fused program
    (``ops/pallas_fused_step.py``) computes loss(t) AND the descent for
    step ``t+1``'s prefixes; one small prologue descent
    (``find_prefix_pallas`` on ``pre[0]``) primes step 0. Steady state is
    one Pallas program per grad step.

    Byte-parity with the separate-programs oracle is structural, not
    approximate (tests/test_fused_descent.py pins whole-TrainState + tree
    equality): same PRNG stream (split → fold_in(0) → stratified
    prefixes), the descent tile is the standalone kernel's ``descend_tile``
    verbatim on the same tree (exact int32), the IS weights are the
    same elementwise formula on the same dispatch-start scalars
    (total/min_ratio/β), and the loss/backward tiles are the fused-loss
    kernel's own.

    Single-device only (the dp mesh keeps the separate-programs tier —
    ``replay/source.py`` negotiates the refusal). Returns
    ``(state, sums_lane', max_priority', key', metrics)``, the
    :func:`megastep_device_per_body` contract.
    """
    from d4pg_tpu.ops.pallas_tree import find_prefix_pallas
    from d4pg_tpu.replay import device_per as dper

    with phase("replay.draw"):
        key, k_draw = jax.random.split(key)
        local_filled = ring.size  # n_shards == 1: the global fill count
        half = sums_lane.shape[0] // 2
        leaves = sums_lane[half:]
        total = sums_lane[1]
        # The oracle's exact draw stream: lane_draw(fold_in(k_draw, 0), ...).
        pre = dper.stratified_prefixes(
            jax.random.fold_in(k_draw, jnp.int32(0)), k, batch, total
        )
        idx0 = jnp.clip(
            find_prefix_pallas(sums_lane, pre[0], interpret=interpret),
            0, jnp.maximum(local_filled - 1, 0),
        )
        # Dispatch-start scalars, shared by every step's IS weights —
        # exactly the separate-programs body's (one β per dispatch,
        # state.step before the scan).
        min_ratio = dper.lane_min_leaf(sums_lane) / (jnp.float32(1) * total)
        beta = dper.beta_at(
            state.step, config.per_beta0, config.per_beta_steps
        )

    def body(carry, pre_next):
        st, idx_t = carry
        with phase("replay.draw"):
            weights = dper.importance_weights(
                p_leaf=leaves[idx_t], total_local=total,
                min_ratio_global=min_ratio, n_global=ring.size, n_shards=1,
                beta=beta,
            )
        batches = gather_batches(ring, idx_t)
        batches["weights"] = weights
        # the NEXT step's descent runs inside this step's fused loss
        # program, so its time is booked to ops.projection_loss
        st, metrics, priorities, idx_raw = train_step(
            config, st, batches, descent=(sums_lane, pre_next)
        )
        with phase("replay.draw"):
            idx_next = jnp.clip(idx_raw, 0, jnp.maximum(local_filled - 1, 0))
        return (st, idx_next), (metrics, priorities, idx_t)

    # xs[t] = pre[t+1]: step t descends the NEXT step's prefixes. The last
    # step's descent output (of the rolled-around pre[0]) is discarded.
    (state, _), (metrics, priorities, idx_all) = jax.lax.scan(
        body, (state, idx0), jnp.roll(pre, -1, axis=0)
    )
    with phase("replay.write_back"):
        sums_lane, mp_local = dper.write_back_lane(
            sums_lane, idx_all, priorities, config.per_alpha,
            config.per_eps, local_capacity=ring.capacity,
        )
        max_priority = jnp.maximum(max_priority, mp_local)
    return (
        state, sums_lane, max_priority, key,
        jax.tree.map(lambda x: x.mean(), metrics),
    )


def _tree_interpret(tree_backend: str) -> bool:
    """The descent's ``interpret`` flag: only the Pallas tier asks
    :func:`pallas_interpret` (which raises off tpu/cpu); the XLA descent
    never reads the flag and runs anywhere."""
    return tree_backend == "pallas" and pallas_interpret()


def make_megastep_device_per(
    config: D4PGConfig, k: int, batch: int, tree_backend: str = "xla",
):
    """Jitted donated-buffer device-PER megastep, single device:
    ``(state, ring, tree, key) -> (state, tree', key', metrics)``. State
    and tree are donated (both update in place); the ring is read-only
    here and stays resident. One compiled program per (K, B) — the
    sentinel budgets it exactly like the uniform megastep."""
    return jax.jit(
        _device_per_lane_fn(config, k, batch, 1, tree_backend),
        donate_argnums=(0, 2),
    )


def make_megastep_device_per_fused(config: D4PGConfig, k: int, batch: int):
    """Jitted donated-buffer FUSED-TIER device-PER megastep, single
    device: ``(state, ring, tree, key) -> (state, tree', key', metrics)``
    — the :func:`make_megastep_device_per` signature, drop-in at the
    trainer's maker selection, same sentinel budget (one compile per
    (K, B))."""
    from d4pg_tpu.replay.device_per import DevicePerTree

    body = partial(
        megastep_device_per_fused_body, config, k, batch, pallas_interpret()
    )

    def lane(state, ring, tree, key):
        state, sums, mp, key, metrics = body(
            state, ring, tree.sums[0], tree.max_priority, key
        )
        return state, DevicePerTree(sums[None], mp), key, metrics

    return jax.jit(lane, donate_argnums=(0, 2))


def _device_per_lane_fn(config, k, b_local, n_shards, tree_backend):
    """The shared per-lane wrapper (tree pytree in/out) that both the
    shard_map mesh path and the vmap oracle run — same bits, two
    harnesses, the PR-9 byte-identity recipe."""
    from d4pg_tpu.replay.device_per import DevicePerTree

    body = partial(
        megastep_device_per_body, config, k, b_local, n_shards,
        tree_backend, _tree_interpret(tree_backend),
    )

    def lane(state, ring, tree, key):
        state, sums, mp, key, metrics = body(
            state, ring, tree.sums[0], tree.max_priority, key
        )
        return state, DevicePerTree(sums[None], mp), key, metrics

    return lane


def make_megastep_device_per_sharded(
    config: D4PGConfig, k: int, batch: int, mesh, tree_backend: str = "xla",
    rules=None,
):
    """Jitted donated-buffer SHARDED device-PER megastep over a dp mesh:
    ``(state, ring, tree, key) -> (state, tree', key', metrics)`` with
    in/out shardings from the rule registries (state:
    ``match_partition_rules``, ring: ``RING_RULES``, tree:
    ``PER_TREE_RULES``). Same mesh constraints as the uniform sharded
    megastep (dp-only, divisible batch)."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from d4pg_tpu.parallel.partition import (
        DEFAULT_RULES,
        _abstract_state,
        _state_specs,
        ring_partition_specs,
        stack_axes_for,
        tree_partition_specs,
    )
    from d4pg_tpu.replay.device_per import DevicePerTree

    n_shards = int(mesh.shape["dp"])
    if int(mesh.shape.get("tp", 1)) != 1:
        raise ValueError(
            "sharded megastep mesh must be dp-only (tp=1); tensor "
            "parallelism composes via the GSPMD host path "
            f"(got tp={mesh.shape['tp']})"
        )
    if batch % n_shards:
        raise ValueError(
            f"sharded megastep: batch {batch} not divisible by dp={n_shards}"
        )
    dummy = jax.eval_shape(
        lambda kk: _abstract_state(config, kk), jax.random.PRNGKey(0)
    )
    state_specs = _state_specs(
        dummy, rules or DEFAULT_RULES, mesh, stack_axes_for(config)
    )
    ring_template = DeviceRing(
        obs=jnp.zeros((2, config.obs_dim)),
        action=jnp.zeros((2, config.action_dim)),
        reward=jnp.zeros((2,)),
        next_obs=jnp.zeros((2, config.obs_dim)),
        discount=jnp.zeros((2,)),
        size=jnp.zeros((), jnp.int32),
    )
    ring_specs = ring_partition_specs(ring_template)
    tree_specs = tree_partition_specs(
        DevicePerTree(
            sums=jnp.zeros((2, 2), jnp.float32),
            max_priority=jnp.zeros((), jnp.float32),
        )
    )
    lane = _device_per_lane_fn(
        config, k, batch // n_shards, n_shards, tree_backend
    )
    mapped = shard_map(
        lane,
        mesh=mesh,
        in_specs=(state_specs, ring_specs, tree_specs, P()),
        out_specs=(state_specs, tree_specs, P(), P()),
        check_vma=False,
    )
    to_shardings = lambda specs: jax.tree_util.tree_map(  # noqa: E731
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    key_sharding = NamedSharding(mesh, P())
    return jax.jit(
        mapped,
        in_shardings=(
            to_shardings(state_specs), to_shardings(ring_specs),
            to_shardings(tree_specs), key_sharding,
        ),
        out_shardings=(
            to_shardings(state_specs), to_shardings(tree_specs),
            key_sharding, NamedSharding(mesh, P()),
        ),
        donate_argnums=(0, 2),
    )


def make_megastep_device_per_oracle(
    config: D4PGConfig, k: int, batch: int, n_shards: int,
    tree_backend: str = "xla",
):
    """The sharded device-PER megastep's SINGLE-DEVICE parity oracle: the
    same per-lane function under ``vmap(axis_name="dp")`` over striped
    ring lanes (``striped_lanes``) and tree lanes. ``(state, ring_lanes,
    tree, key) -> (state, tree', key', metrics)``; the TrainState is
    BYTE-IDENTICAL to the mesh path's (tests pin it) because the body's
    cross-lane arithmetic is det_pmean plus exact min/max reduces."""
    from d4pg_tpu.replay.device_per import DevicePerTree

    body = partial(
        megastep_device_per_body, config, k, batch // n_shards, n_shards,
        tree_backend, _tree_interpret(tree_backend),
    )
    lane_axes = DeviceRing(
        obs=0, action=0, reward=0, next_obs=0, discount=0, size=None
    )
    vm = jax.vmap(
        body, in_axes=(None, lane_axes, 0, None, None), out_axes=0,
        axis_name="dp",
    )

    def run(state, ring_lanes, tree, key):
        st, sums, mp, keys, metrics = vm(
            state, ring_lanes, tree.sums, tree.max_priority, key
        )
        # Lane outputs are det-synced identical (state/key/metrics/max);
        # lane 0 IS the result. The subtree lanes stay per-lane.
        first = lambda t: jax.tree.map(lambda x: x[0], t)  # noqa: E731
        return (
            first(st), DevicePerTree(sums, mp[0]), keys[0], first(metrics)
        )

    return jax.jit(run)


def make_megastep_uniform_oracle(config: D4PGConfig, k: int, batch: int,
                                 n_shards: int):
    """The sharded megastep's SINGLE-DEVICE parity oracle: the same
    :func:`sharded_megastep_uniform_body` under ``vmap(axis_name="dp")``
    over striped host-slot lanes (``replay.device_ring.striped_perm``).

    ``(state, ring_lanes, key) -> (state, key', metrics)`` where
    ``ring_lanes`` is a DeviceRing whose row fields carry a leading
    ``[n_shards]`` lane axis and whose ``size`` stays the global scalar.
    Because the body's only cross-shard arithmetic is ``det_pmean``
    (data-moving collectives + a fixed-order sum — exact under both
    harnesses), the
    oracle's TrainState is BYTE-IDENTICAL to the mesh path's, which is
    the acceptance contract tests/test_sharded_megastep.py pins."""
    body = partial(
        sharded_megastep_uniform_body, config, k, batch // n_shards, n_shards
    )
    lane_axes = DeviceRing(
        obs=0, action=0, reward=0, next_obs=0, discount=0, size=None
    )
    vm = jax.vmap(body, in_axes=(None, lane_axes, None), out_axes=0,
                  axis_name="dp")

    def run(state, ring_lanes, key):
        st, keys, metrics = vm(state, ring_lanes, key)
        # Every lane's outputs are identical (det_pmean-synced); lane 0
        # IS the result.
        first = lambda t: jax.tree.map(lambda x: x[0], t)  # noqa: E731
        return first(st), keys[0], first(metrics)

    return jax.jit(run)
