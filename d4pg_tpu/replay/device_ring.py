"""Device-resident replay shard: an HBM ring mirroring the host buffer.

ROADMAP item 1 (the Podracer/Anakin move, Hessel et al. 2021): keep the
whole sample→train→write-back lifecycle on-device so the only steady-state
host traffic is fresh experience trickling in.  The host buffer stays the
source of truth for *writes* (n-step writers, HER relabeling, PER trees,
generation stamps, snapshots all keep working unchanged); this module
mirrors its ring rows into device HBM so the learner's megastep
(``d4pg_tpu.runtime.megastep``) can gather batches without a host→device
batch upload per grad step.

Three pieces:

- :class:`DeviceRing` — the transition fields as a pytree of device
  arrays of ``capacity`` rows plus a device-resident ``size`` scalar. Wide
  row fields are stored lane-dense, ``P`` rows to a storage row
  (:func:`storage_shape`: the TPU's default layout of ``[capacity, 376]``
  is feature-major, and XLA copied the whole array before every gather);
  rows are read and written through ``rows`` / ``set_rows``;
- :func:`ingest_body` / :func:`make_ingest` — the jit-compiled,
  donated-buffer chunk writer: a fixed-shape ``[chunk_cap, ...]`` chunk
  scatters into the ring at explicit slot indices (pad rows carry slot
  ``capacity``, dropped by the out-of-bounds scatter mode), so ONE
  compiled program covers every flush regardless of fill level or ring
  wrap;
- :class:`DeviceRingSync` — the host-side flusher: tracks the host
  buffer's monotone write counter and ships only the rows written since
  the last flush, in large infrequent chunks (the ``ingest_chunk`` stage),
  never per step and never per grad step.

:meth:`DeviceRingSync.stage` adds the ISSUE-16 double buffer on top: the
trainer calls it right after dispatching a megastep, so the NEXT flush's
first chunk gathers and ships H2D while the device is busy computing —
the transfer overlaps compute instead of serializing before the next
dispatch. ``flush`` consumes the staged chunk first (iff its base write
counter is still current), then ships the remainder in write order, so
last-write-wins is preserved even when the collector overwrote staged
rows in between.

Deliberate non-goals: the chunk gather allocates fresh host arrays per
flush (ingest is the infrequent cold path — reusing staging here would
buy nothing and re-open the ledger-hold question the hot paths needed;
``stage`` preallocates only its index buffers, since it runs once per
dispatch on the hot path);
pixel (uint8-quantized) buffers are not mirrored (a 100k-row pixel ring
is ~0.9 GB of HBM better spent on batch size — the trainer rejects the
combination loudly).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class _StagedChunk(NamedTuple):
    """One pre-staged ingest chunk (``DeviceRingSync.stage``): the gather
    + H2D of the next flush's FIRST chunk, done while the device runs the
    megastep so the transfer overlaps compute instead of serializing
    before the next dispatch (ISSUE 16's double-buffer leg)."""

    synced_at: int        # self._synced when staged (consume iff unchanged)
    covers: int           # global write index this chunk syncs through
    dev_chunk: dict       # device-resident row fields
    slots_dev: jax.Array  # device-resident [chunk_cap] slot indices
    new_size_dev: jax.Array  # ring fill count consistent at `covers`
    nbytes: int


def rows_per_storage_row(width: int) -> int:
    """``P``: how many logical rows of ``width`` floats share one storage
    row. The TPU's default layout for a ``[rows, width]`` array is decided
    by the SHAPE alone: where row-major would pad the lane dimension (376 →
    384) it puts the rows on the 128 lanes instead (feature-major), and XLA
    then copies the whole array to row-major before every row gather.
    ``P * width`` is the smallest multiple of 128, so ``[rows / P, P *
    width]`` is row-major and unpadded by default. Narrow fields (< 128:
    they gather from feature-major storage without a copy) and fields
    already a multiple of 128 wide keep ``P`` = 1."""
    if width < 128 or width % 128 == 0:
        return 1
    return 128 // math.gcd(width, 128)


def storage_shape(shape: tuple) -> tuple:
    """The stored shape of a ``[..., R, W]`` row field: ``[..., R // P,
    P * W]`` — the plain row-major reshape, logical row ``r`` at storage
    row ``r // P``, lanes ``(r % P) * W … + W``. ``R % P != 0`` (two-row
    templates, odd toy capacities) stays ``[..., R, W]``: correct, only
    slow. A stored shape is its own stored shape (``P * W % 128 == 0``)."""
    shape = tuple(shape)
    if len(shape) < 2:
        return shape
    p = rows_per_storage_row(shape[-1])
    if shape[-2] % p:
        return shape
    return shape[:-2] + (shape[-2] // p, p * shape[-1])


def _to_storage(value):
    """Array-like field values (numpy, jax arrays, tracers) go to storage
    form; what a DeviceRing-shaped pytree otherwise carries — PartitionSpecs,
    shardings, vmap axes, ``None``, ShapeDtypeStructs (which come out of
    ``eval_shape`` stored already) — passes through."""
    reshape = getattr(value, "reshape", None)
    if reshape is None:
        return value
    stored = storage_shape(value.shape)
    return value if stored == tuple(value.shape) else reshape(stored)


class _RingFields(NamedTuple):
    obs: jax.Array        # [C/P, P*O] f32 (P = 1: [C, O])
    action: jax.Array     # [C, A] f32
    reward: jax.Array     # [C]    f32
    next_obs: jax.Array   # [C/P, P*O] f32
    discount: jax.Array   # [C]    f32
    size: jax.Array       # scalar int32


ROW_FIELDS = ("obs", "action", "reward", "next_obs", "discount")
VECTOR_FIELDS = ("obs", "action", "next_obs")   # [..., C, W]; the others [..., C]


class DeviceRing(_RingFields):
    """Transition fields as device-resident arrays of ``capacity`` rows.

    Field names match the batch-dict keys every train path consumes, so
    :func:`d4pg_tpu.agent.d4pg.gather_batches` works on it directly.
    ``size`` is the filled-row count (int32 scalar, device-resident so the
    megastep's in-kernel uniform draw needs no host operand).

    A wide row field is STORED lane-dense, ``P`` logical rows to a storage
    row (:func:`storage_shape`); construction brings ``[C, W]`` values to
    that form, so it is idempotent and safe under pytree unflattening,
    ``_replace`` and re-wrapping. ``ring.obs.shape[0]`` is therefore NOT
    the capacity: read :attr:`capacity`, and rows through :meth:`rows` /
    :meth:`set_rows`. The geometry is recovered from the ring's own
    (local) shapes, so it holds under ``vmap`` and ``shard_map`` too."""

    __slots__ = ()

    def __new__(cls, obs, action, reward, next_obs, discount, size):
        return super().__new__(
            cls, _to_storage(obs), _to_storage(action), reward,
            _to_storage(next_obs), discount, size,
        )

    @classmethod
    def _make(cls, iterable):  # what ``_replace`` rebuilds through
        return cls(*iterable)

    @property
    def capacity(self) -> int:
        """Logical rows held (of this shard, inside ``shard_map``)."""
        return self.reward.shape[-1]

    def rows_packed(self, name: str) -> int:
        """``P`` of one field: logical rows to a storage row, from the
        ring's own shapes."""
        if name not in VECTOR_FIELDS:   # a scalar a row: [..., C] as it is
            return 1
        return self.capacity // getattr(self, name).shape[-2]

    def rows(self, name: str, idx: jax.Array) -> jax.Array:
        """``logical[idx]`` of one field, bit for bit. A packed field
        gathers the storage rows and selects each row's window from ``P``
        static lane slices: exact, and the only form XLA:TPU compiles
        without touching the whole store (one ``lax.gather`` with ``(row,
        lane)`` start indices becomes a ``convert`` of all of it)."""
        field = getattr(self, name)
        p = self.rows_packed(name)
        if p == 1:
            return field[idx]
        w = field.shape[-1] // p
        block, sub = field[idx // p], (idx % p)[..., None]
        out = block[..., :w]
        for k in range(1, p):
            out = jnp.where(sub == k, block[..., k * w:(k + 1) * w], out)
        return out

    def set_rows(self, chunk: dict, slots: jax.Array,
                 new_size: jax.Array) -> "DeviceRing":
        """``logical.at[slots].set(chunk[field], mode="drop")`` for every
        row field (slot == capacity: a pad row, dropped), and the new fill
        count. A packed field takes each row as a ``[1, W]`` window at
        ``(slot // P, (slot % P) * W)`` — in place on a donated store."""
        out = {}
        for name in ROW_FIELDS:
            field, p = getattr(self, name), self.rows_packed(name)
            if p == 1:
                out[name] = field.at[slots].set(chunk[name], mode="drop")
                continue
            w = field.shape[-1] // p
            out[name] = jax.lax.scatter(
                field,
                jnp.stack([slots // p, (slots % p) * w], axis=-1),
                chunk[name].astype(field.dtype),
                jax.lax.ScatterDimensionNumbers(
                    update_window_dims=(1,), inserted_window_dims=(0,),
                    scatter_dims_to_operand_dims=(0, 1),
                ),
                mode=jax.lax.GatherScatterMode.FILL_OR_DROP,
            )
        return DeviceRing(size=new_size, **out)

    def logical(self, name: str):
        """The whole field as ``[..., capacity, W]``. On the device this is
        the relayout the storage exists to avoid: for hosts (snapshots,
        numpy copies) and tests only, never inside a dispatch."""
        field, p = getattr(self, name), self.rows_packed(name)
        if p == 1:
            return field
        return field.reshape(
            field.shape[:-2] + (self.capacity, field.shape[-1] // p)
        )

    def describe_storage(self) -> dict:
        """Per row field: logical width, ``P``, stored shape, bytes — what
        the trainer logs once at start-up and the tests read to see that
        the lane-dense form engaged."""
        out = {}
        for name in ROW_FIELDS:
            field, p = getattr(self, name), self.rows_packed(name)
            out[name] = {
                "width": field.shape[-1] // p if name in VECTOR_FIELDS else 1,
                "rows_per_storage_row": p,
                "stored_shape": tuple(field.shape),
                "bytes": math.prod(field.shape) * field.dtype.itemsize,
            }
        return out


def device_ring_init(
    capacity: int, obs_dim: int, action_dim: int, mesh=None
) -> DeviceRing:
    # device_put COMMITS the fresh arrays: an uncommitted jnp.zeros ring
    # and the committed output of the first ingest would be distinct jit
    # cache keys — two compiles of the same program, tripping the
    # recompile sentinel's budget of 1.
    #
    # With ``mesh``, fields are placed SHARDED over "dp" on the capacity
    # axis per the partition registry (parallel/partition.py:RING_RULES):
    # each dp shard owns capacity/dp rows, in the STRIPED host↔device row
    # mapping (see ShardedDeviceRingSync) so every shard fills evenly from
    # the first rows of experience.
    #
    # Fields are made in storage form directly: a [C, W] zeros array only
    # to reshape it would hold a wide field twice at start-up.
    ring = DeviceRing(
        obs=jnp.zeros(storage_shape((capacity, obs_dim)), jnp.float32),
        action=jnp.zeros(storage_shape((capacity, action_dim)), jnp.float32),
        reward=jnp.zeros((capacity,), jnp.float32),
        next_obs=jnp.zeros(storage_shape((capacity, obs_dim)), jnp.float32),
        discount=jnp.zeros((capacity,), jnp.float32),
        size=jnp.zeros((), jnp.int32),
    )
    if mesh is None:
        return jax.device_put(ring)
    from jax.sharding import NamedSharding

    from d4pg_tpu.parallel.partition import ring_partition_specs

    n_shards = int(mesh.shape["dp"])
    if capacity % n_shards:
        raise ValueError(
            f"sharded ring: capacity {capacity} not divisible by dp="
            f"{n_shards}"
        )
    for name in VECTOR_FIELDS:
        if getattr(ring, name).shape[0] % n_shards:
            p = ring.rows_packed(name)
            raise ValueError(
                f"sharded ring: {name} is stored {p} rows to a storage row "
                f"(replay/device_ring.py:storage_shape), so capacity "
                f"{capacity} must be divisible by {p} x dp={n_shards}"
            )
    specs = ring_partition_specs(ring)
    if jax.process_count() > 1:
        # Collective-free placement (parallel/distributed.stage_global):
        # device_put onto non-addressable shardings fires a per-leaf
        # agreement broadcast that deadlocks against in-flight transfer
        # programs under the gloo CPU backend.
        from d4pg_tpu.parallel.distributed import stage_global

        return DeviceRing(
            *(stage_global(mesh, spec, leaf) for leaf, spec in zip(ring, specs))
        )
    return DeviceRing(
        *(
            jax.device_put(leaf, NamedSharding(mesh, spec))
            for leaf, spec in zip(ring, specs)
        )
    )


def ingest_body(ring: DeviceRing, chunk: dict, slots: jax.Array,
                new_size: jax.Array) -> DeviceRing:
    """Scatter one fixed-shape chunk of rows into the ring (donated).

    ``slots`` is ``[chunk_cap]`` int32: real rows carry their host ring
    slot index, pad rows carry ``capacity`` — out of bounds, dropped by
    ``mode="drop"`` — so partial chunks and ring wrap need no second
    program. In the d4pglint ``MEGASTEP_FUNCTIONS`` manifest: this body is
    jit-traced, so host numpy / ``.item()`` coercions here would smuggle a
    per-flush host sync into the device loop."""
    return ring.set_rows(chunk, slots, new_size)


def make_ingest():
    """The jitted donated-buffer ingest: ONE compiled program per chunk
    shape (DeviceRingSync uses a single fixed ``chunk_cap``, so exactly
    one compile for the run — the recompile sentinel budgets it).

    Each call returns a jit of a FRESH wrapper function, not of
    ``ingest_body`` itself: ``jax.jit`` wrappers of the same underlying
    function object share one specialization cache, so a second ring
    (another trainer or bench in the same process, at another chunk
    shape) would inflate this ring's ``_cache_size()`` and false-trip the
    sentinel's budget of 1."""

    def _ingest(ring, chunk, slots, new_size):
        return ingest_body(ring, chunk, slots, new_size)

    return jax.jit(_ingest, donate_argnums=(0,))


class DeviceRingSync:
    """Host-side flusher keeping a :class:`DeviceRing` mirroring a host
    :class:`~d4pg_tpu.replay.uniform.ReplayBuffer`'s ring slots.

    ``flush(ring)`` ships every row written to the host buffer since the
    last flush (by its monotone ``total_added`` counter) as ≤ ``chunk_cap``
    -row chunks: slot indices are reconstructed from the host write head,
    rows are gathered with the buffer's own locked :meth:`gather` (so a
    concurrent collector thread can never hand us a torn row), and the
    explicit ``device_put`` + donated ingest dispatch are the ONLY
    steady-state host→device traffic of the device-resident data plane.
    More than ``capacity`` pending writes collapse to one full-ring resync
    (the overwritten intermediates no longer exist to ship).
    """

    def __init__(self, buffer, chunk_cap: int = 4096):
        self._buffer = buffer
        self.capacity = int(buffer.capacity)
        self.chunk_cap = int(min(chunk_cap, self.capacity))
        self._synced = 0  # host buffer total_added already mirrored
        self._ingest = make_ingest()
        # H2D bytes shipped, for telemetry/bench accounting (host-side
        # counter of exactly the bytes the explicit device_puts staged).
        self.bytes_ingested = 0
        self.chunks_ingested = 0
        # Device-PER seam (replay/device_per.py:DevicePerSync.on_chunk):
        # called with each chunk's ALREADY-STAGED device slot array so the
        # priority tree seeds the same rows the ring just mirrored — zero
        # extra H2D, and ring row vs priority leaf can never desync.
        self.tree_hook = None
        # Double-buffer staging (stage()): the next flush's first chunk,
        # pre-gathered + device_put while the device runs the megastep.
        # Slot/gather index buffers are preallocated so the hot-path
        # stage() call allocates no fresh host staging per dispatch
        # (device_put copies out of them before returning).
        self._staged: Optional[_StagedChunk] = None
        self._stage_slots = np.full(self.chunk_cap, self.capacity, np.int32)
        self._stage_gidx = np.zeros(self.chunk_cap, np.int64)

    def stage(self) -> bool:
        """Pre-stage the next flush's FIRST chunk: gather ≤ ``chunk_cap``
        pending rows and ``device_put`` them NOW, so the H2D transfer
        overlaps the in-flight megastep's compute instead of serializing
        in front of the next dispatch (the ``ingest_stage`` timer stage).

        Safe to call at any time: a no-op if a chunk is already staged or
        nothing is pending, and :meth:`flush` consumes the staged chunk
        only while its base write counter still matches — rows the
        collector overwrites AFTER staging are re-shipped by the flush's
        remainder loop, which runs after the staged scatter, so host write
        order (last-write-wins) is preserved end to end.

        Returns True iff a chunk is staged on exit."""
        if self._staged is not None:
            return True
        buf = self._buffer
        total = buf.total_added
        n_pending = min(total - self._synced, self.capacity)
        if n_pending <= 0:
            return False
        first = total - n_pending
        n = min(n_pending, self.chunk_cap)
        slots = self._stage_slots
        slots.fill(self.capacity)
        slots[:n] = (first + np.arange(n)) % self.capacity
        gidx = self._stage_gidx
        gidx.fill(0)
        gidx[:n] = slots[:n]
        chunk = dict(buf.gather(gidx))  # locked: never a torn row
        covers = first + n
        # Fill count consistent at `covers` writes — the remainder loop
        # (or a later flush) advances it to the final value.
        new_size = np.int32(min(covers, self.capacity))
        dev_chunk = jax.device_put(chunk)  # explicit staging (exempt)
        slots_dev = jax.device_put(slots)
        nbytes = (
            sum(v.nbytes for v in chunk.values())
            + slots.nbytes + new_size.nbytes
        )
        self._staged = _StagedChunk(
            synced_at=self._synced,
            covers=covers,
            dev_chunk=dev_chunk,
            slots_dev=slots_dev,
            new_size_dev=jax.device_put(new_size),
            nbytes=nbytes,
        )
        return True

    @property
    def ingest_fn(self):
        """The jitted ingest entry point (for recompile-sentinel tracking)."""
        return self._ingest

    def pending(self) -> int:
        return min(self._buffer.total_added - self._synced, self.capacity)

    def flush(self, ring: DeviceRing) -> DeviceRing:
        """Mirror all pending host writes into ``ring``; returns the
        updated ring (the argument is consumed — donated)."""
        buf = self._buffer
        staged, self._staged = self._staged, None
        if staged is not None and staged.synced_at == self._synced:
            # Consume the pre-staged chunk: its transfer already happened
            # under the previous dispatch. Rows written (or overwritten)
            # since staging fall into [covers, total) and ship below, in
            # write order, so the staged scatter can never shadow a newer
            # row.
            ring = self._ingest(
                ring, staged.dev_chunk, staged.slots_dev,
                staged.new_size_dev,
            )
            if self.tree_hook is not None:
                self.tree_hook(staged.slots_dev)
            self.bytes_ingested += staged.nbytes
            self.chunks_ingested += 1
            self._synced = staged.covers
        total = buf.total_added
        n_pending = min(total - self._synced, self.capacity)
        if n_pending <= 0:
            return ring
        # Slots of the last n_pending writes, oldest first: the host write
        # head has advanced `total` writes from slot 0, so write j (0-based,
        # global) landed at slot j % capacity.
        first = total - n_pending
        new_size = np.int32(min(total, self.capacity))
        for lo in range(0, n_pending, self.chunk_cap):
            hi = min(lo + self.chunk_cap, n_pending)
            n = hi - lo
            slots = np.full(self.chunk_cap, self.capacity, np.int32)
            slots[:n] = (first + lo + np.arange(n)) % self.capacity
            # Pad index rows re-read slot 0 so gather() returns the full
            # fixed shape; their scatter targets are out of bounds and
            # dropped, so the garbage never lands.
            gidx = np.zeros(self.chunk_cap, np.int64)
            gidx[:n] = slots[:n]
            chunk = dict(buf.gather(gidx))  # locked: never a torn row
            dev_chunk = jax.device_put(chunk)  # explicit staging (exempt)
            slots_dev = jax.device_put(slots)
            ring = self._ingest(
                ring, dev_chunk, slots_dev, jax.device_put(new_size),
            )
            if self.tree_hook is not None:
                self.tree_hook(slots_dev)
            self.bytes_ingested += sum(v.nbytes for v in chunk.values())
            self.bytes_ingested += slots.nbytes + new_size.nbytes
            self.chunks_ingested += 1
        self._synced = total
        return ring


# --------------------------------------------------------- sharded variant
def striped_perm(capacity: int, n_shards: int) -> np.ndarray:
    """``[n_shards, capacity // n_shards]`` host-slot indices per shard
    lane: lane ``d`` local row ``i`` holds host slot ``i * n_shards + d``.

    This is the sharded ring's row layout contract, shared by the flusher
    (mirror mapping), the megastep's parity oracle (lane construction from
    the host buffer), and the tests. STRIPED rather than block-contiguous
    on purpose: host writes land round-robin across shards, so every
    shard's slice fills evenly from the first rows of experience — with
    contiguous blocks, shard ``d`` would stay EMPTY until a fraction d/D
    of capacity had ever been written, and the shard-local uniform draw
    would have nothing to sample."""
    cl = capacity // n_shards
    return (np.arange(cl)[None, :] * n_shards + np.arange(n_shards)[:, None])


def striped_lanes(buffer, n_shards: int) -> DeviceRing:
    """Build the parity oracle's lane view of a HOST buffer: a DeviceRing
    whose row fields carry a leading ``[n_shards]`` lane axis laid out by
    :func:`striped_perm` — lane ``d`` holds exactly the rows shard ``d``
    of a sharded ring mirrors, in the same local order. ``size`` is the
    global fill count (replicated in the oracle's vmap)."""
    perm = striped_perm(int(buffer.capacity), n_shards)
    return DeviceRing(
        obs=jnp.asarray(buffer.obs[perm]),
        action=jnp.asarray(buffer.action[perm]),
        reward=jnp.asarray(buffer.reward[perm]),
        next_obs=jnp.asarray(buffer.next_obs[perm]),
        discount=jnp.asarray(buffer.discount[perm]),
        size=jnp.int32(min(buffer.total_added, int(buffer.capacity))),
    )


def sharded_ingest_body(ring: DeviceRing, chunk: dict, slots: jax.Array,
                        new_size: jax.Array) -> DeviceRing:
    """Per-shard chunk scatter (the shard_map body of the sharded ingest).

    ``ring`` is the shard's LOCAL slice (``[capacity/dp, ...]`` rows);
    ``chunk``/``slots`` arrive ``[1, chunk_local, ...]`` (the leading
    shard axis shard_map split to 1): real rows carry their LOCAL slot
    index, pad rows carry ``capacity/dp`` — out of the local bounds,
    dropped by ``mode="drop"``. One fixed compiled shape per shard covers
    every flush, exactly like the unsharded ingest. In the d4pglint
    ``MEGASTEP_FUNCTIONS`` manifest: jit-traced, so host numpy or
    ``.item()`` here would smuggle a per-flush host sync into the device
    loop."""
    return ring.set_rows(
        {name: rows[0] for name, rows in chunk.items()}, slots[0], new_size
    )


def sharded_chunk_specs():
    """PartitionSpecs for a flush chunk's fields (leading axis = the shard
    axis, placed ``P("dp", ...)`` so each dp shard receives exactly its
    sub-chunk). ONE definition on purpose: the jitted ingest's in_shardings
    and the flusher's explicit ``device_put`` staging must agree, or every
    flush silently reshards — the phantom-transfer class the sentinel
    budgets exist to catch."""
    from jax.sharding import PartitionSpec as P

    return {
        "obs": P("dp", None, None),
        "action": P("dp", None, None),
        "reward": P("dp", None),
        "next_obs": P("dp", None, None),
        "discount": P("dp", None),
    }


def make_sharded_ingest(mesh, chunk_local: int, obs_dim: int, action_dim: int):
    """The jitted donated-buffer SHARDED ingest: one compiled program per
    (mesh, chunk shape) — the flusher uses a single fixed ``chunk_local``,
    so exactly one compile for the run (sentinel budget 1, same contract
    as :func:`make_ingest`). In/out shardings come from the partition-rule
    registry (``RING_RULES`` via ``ring_partition_specs``); the chunk's
    leading axis is the shard axis, placed ``P("dp", ...)`` so each dp
    shard receives exactly its sub-chunk — ingest stays shard-local, no
    collectives in the lowered program."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from d4pg_tpu.parallel.partition import ring_partition_specs

    template = DeviceRing(
        obs=np.zeros((2, obs_dim), np.float32),
        action=np.zeros((2, action_dim), np.float32),
        reward=np.zeros((2,), np.float32),
        next_obs=np.zeros((2, obs_dim), np.float32),
        discount=np.zeros((2,), np.float32),
        size=np.zeros((), np.int32),
    )
    ring_specs = ring_partition_specs(template)
    chunk_specs = sharded_chunk_specs()
    mapped = shard_map(
        sharded_ingest_body,
        mesh=mesh,
        in_specs=(ring_specs, chunk_specs, P("dp", None), P()),
        out_specs=ring_specs,
        check_vma=False,
    )
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        (ring_specs, chunk_specs, P("dp", None), P()),
        is_leaf=lambda x: isinstance(x, P),
    )
    return jax.jit(
        mapped,
        in_shardings=shardings,
        out_shardings=shardings[0],
        donate_argnums=(0,),
    )


class ShardedDeviceRingSync:
    """The dp-sharded flusher: mirrors a host buffer's ring slots into a
    :class:`DeviceRing` whose rows are sharded over "dp" (ROADMAP item 2 —
    the scale-out of :class:`DeviceRingSync`).

    Layout is STRIPED (:func:`striped_perm`): host slot ``j`` lives on
    shard ``j % dp`` at local row ``j // dp``, so collection fills every
    shard evenly and the megastep's shard-local uniform draw over
    ``[0, size // dp)`` rows is always backed by mirrored data. Each flush
    ships ONE fixed-shape ``[dp, chunk_local, ...]`` chunk per round —
    every shard's sub-chunk padded to the same ``chunk_local`` (pad slot
    = local capacity, dropped by the scatter) — placed per-shard with an
    explicit ``NamedSharding`` ``device_put``; the donated shard_map
    ingest then scatters locally. Same contract as the unsharded sync:
    one compiled ingest program ever, explicit staging is the only
    steady-state H2D, more than ``capacity`` pending writes collapse to a
    full resync.
    """

    def __init__(self, buffer, mesh, chunk_cap: int = 4096):
        self._buffer = buffer
        self._mesh = mesh
        self.n_shards = int(mesh.shape["dp"])
        self.capacity = int(buffer.capacity)
        if self.capacity % self.n_shards:
            raise ValueError(
                f"sharded ring: capacity {self.capacity} not divisible "
                f"by dp={self.n_shards}"
            )
        self.local_capacity = self.capacity // self.n_shards
        self.chunk_local = int(
            min(max(1, chunk_cap // self.n_shards), self.local_capacity)
        )
        self._synced = 0
        obs_dim = buffer.obs.shape[1]
        act_dim = buffer.action.shape[1]
        self._ingest = make_sharded_ingest(
            mesh, self.chunk_local, obs_dim, act_dim
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        # Built from the SAME spec dict the jitted ingest's in_shardings
        # use (sharded_chunk_specs) — staging and program can never drift.
        self._chunk_sharding = {
            k: NamedSharding(mesh, s) for k, s in sharded_chunk_specs().items()
        }
        self._slots_sharding = NamedSharding(mesh, P("dp", None))
        self._scalar_sharding = NamedSharding(mesh, P())
        self.bytes_ingested = 0
        self.chunks_ingested = 0
        # Device-PER seam: same contract as DeviceRingSync.tree_hook, with
        # the [dp, chunk_local] LOCAL-slot layout this sync stages (pad =
        # local capacity) — exactly what the sharded tree ingest expects.
        self.tree_hook = None

    @property
    def ingest_fn(self):
        """The jitted ingest entry point (recompile-sentinel tracking)."""
        return self._ingest

    def pending(self) -> int:
        return min(self._buffer.total_added - self._synced, self.capacity)

    def flush(self, ring: DeviceRing) -> DeviceRing:
        """Mirror all pending host writes into the sharded ``ring``;
        returns the updated ring (the argument is consumed — donated)."""
        buf = self._buffer
        total = buf.total_added
        n_pending = min(total - self._synced, self.capacity)
        if n_pending <= 0:
            return ring
        first = total - n_pending
        new_size = np.int32(min(total, self.capacity))
        D, cl = self.n_shards, self.chunk_local
        # Pending host slots in write order, dealt to their owner shards.
        pend = (first + np.arange(n_pending)) % self.capacity
        by_shard = [pend[pend % D == d] // D for d in range(D)]
        rounds = max(1, -(-max(len(b) for b in by_shard) // cl))
        for r in range(rounds):
            slots = np.full((D, cl), self.local_capacity, np.int32)
            gidx = np.zeros((D, cl), np.int64)
            for d in range(D):
                part = by_shard[d][r * cl:(r + 1) * cl]
                slots[d, : len(part)] = part
                # Pad index rows re-read the shard's slot 0 so gather()
                # returns the fixed shape; their scatter targets are out
                # of local bounds and dropped.
                gidx[d, : len(part)] = part * D + d
            chunk = {
                k: np.asarray(v).reshape((D, cl) + v.shape[1:])
                for k, v in dict(buf.gather(gidx.ravel())).items()
            }
            # Explicit per-shard staging (exempt from the transfer guard):
            # the NamedSharding device_put hands each dp shard exactly its
            # sub-chunk.
            dev_chunk = {
                k: jax.device_put(v, self._chunk_sharding[k])
                for k, v in chunk.items()
            }
            slots_dev = jax.device_put(slots, self._slots_sharding)
            ring = self._ingest(
                ring,
                dev_chunk,
                slots_dev,
                jax.device_put(new_size, self._scalar_sharding),
            )
            if self.tree_hook is not None:
                self.tree_hook(slots_dev)
            self.bytes_ingested += sum(v.nbytes for v in chunk.values())
            self.bytes_ingested += slots.nbytes + new_size.nbytes
            self.chunks_ingested += 1
        self._synced = total
        return ring


# ------------------------------------------------------ multi-host variant
class MultihostRingSync:
    """Per-host flusher for a PROCESS-SPANNING sharded ring (ISSUE 17).

    Same striped layout and the same compiled ingest program as
    :class:`ShardedDeviceRingSync`, but the mesh's ``dp`` shards live on
    ``P = jax.process_count()`` processes and each process owns a
    process-LOCAL host :class:`~d4pg_tpu.replay.uniform.ReplayBuffer` of
    capacity ``C/P`` — its own ingest servers/collectors feed it, nothing
    crosses hosts on the write path. The layout algebra that makes this
    exact: with process-major device order, process ``p`` owns global
    shards ``[p*L, (p+1)*L)`` (``L`` local devices, ``D = P*L`` total), so
    a LOCAL buffer striped over ``L`` lanes is precisely the global striped
    ring restricted to ``p``'s shards — local slot ``m`` IS global slot
    ``(m//L)*D + p*L + (m%L)``, and host ``p``'s ``k``-th local write is
    global write ``(k//L)*D + p*L + (k%L)`` of the interleaved stream.

    Every flush is a COLLECTIVE: the ingest program scatters into all
    ``D`` shards, so all processes must dispatch it the same number of
    times with the same fill count. Cross-host cursor agreement does that
    with one small host all-gather per flush — each process contributes
    ``(local total_added, local rounds needed)``; everyone runs
    ``max(rounds)`` rounds (processes with nothing pending ship all-pad
    chunks, dropped by the scatter) and commits the agreed global fill
    count, the largest gapless prefix of the interleaved global write
    stream derivable from the gathered cursors. Chunk staging uses
    ``jax.make_array_from_callback``: the callback runs only for this
    process's ADDRESSABLE shards, so each host stages exactly its local
    sub-chunks — per-host ingest H2D, no cross-host replay bytes ever.
    """

    def __init__(self, buffer, mesh, chunk_cap: int = 4096):
        from d4pg_tpu.parallel.distributed import local_shard_span

        self._buffer = buffer
        self._mesh = mesh
        self.n_shards = int(mesh.shape["dp"])            # D (global)
        self.n_processes = int(jax.process_count())      # P
        lo, hi = local_shard_span(mesh, "dp")
        self.shard_lo = lo
        self.local_shards = hi - lo                      # L
        self.host_capacity = int(buffer.capacity)        # C/P (local buffer)
        self.capacity = self.host_capacity * self.n_processes  # C (global)
        if self.capacity % self.n_shards:
            raise ValueError(
                f"multihost ring: global capacity {self.capacity} not "
                f"divisible by dp={self.n_shards}"
            )
        if self.host_capacity % max(self.local_shards, 1):
            raise ValueError(
                f"multihost ring: local capacity {self.host_capacity} not "
                f"divisible by local shard count {self.local_shards}"
            )
        self.local_capacity = self.capacity // self.n_shards  # rows/shard
        self.chunk_local = int(
            min(max(1, chunk_cap // self.n_shards), self.local_capacity)
        )
        self._synced = 0
        obs_dim = buffer.obs.shape[1]
        act_dim = buffer.action.shape[1]
        self._ingest = make_sharded_ingest(
            mesh, self.chunk_local, obs_dim, act_dim
        )
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._chunk_sharding = {
            k: NamedSharding(mesh, s) for k, s in sharded_chunk_specs().items()
        }
        self._slots_sharding = NamedSharding(mesh, P("dp", None))
        self._scalar_sharding = NamedSharding(mesh, P())
        # Per-HOST H2D accounting: only the bytes this process staged for
        # its local shards (the bench sums hosts for the aggregate).
        self.bytes_ingested = 0
        self.chunks_ingested = 0
        self.tree_hook = None

    @property
    def ingest_fn(self):
        """The jitted ingest entry point (recompile-sentinel tracking)."""
        return self._ingest

    def pending(self) -> int:
        return min(self._buffer.total_added - self._synced, self.host_capacity)

    def _stage(self, local_rows: np.ndarray, sharding):
        """Stage ``[L, chunk_local, ...]`` local lane rows as the global
        ``[D, chunk_local, ...]`` chunk array: the callback materializes
        only this process's addressable shard slices."""
        base = self.shard_lo
        shape = (self.n_shards,) + local_rows.shape[1:]

        def cb(idx):
            d = idx[0].start if idx[0].start is not None else 0
            return local_rows[d - base:d - base + 1]

        return jax.make_array_from_callback(shape, sharding, cb)

    def _stage_scalar(self, value):
        """Replicated scalar via the same collective-free callback path —
        ``device_put`` onto a process-spanning replicated sharding fires
        an agreement broadcast per call (see distributed.stage_global)."""
        arr = np.asarray(value)
        return jax.make_array_from_callback(
            arr.shape, self._scalar_sharding, lambda idx: arr[idx]
        )

    def _gapless_total(self, totals: np.ndarray) -> int:
        """The largest global write count ``T`` such that every one of the
        first ``T`` interleaved global writes has landed, given each host's
        local ``total_added``: host ``p``'s cursor ``t_p`` means its shard
        ``p*L + s`` has received ``ceil((t_p - s)/L)`` writes, and the
        gapless prefix ends at the first shard still short —
        ``min_d(writes_d * D + d)``. Exact under the lock-step deal (equals
        the true total); conservative under skewed per-host feeds (never
        counts a row some host has not written)."""
        D, L = self.n_shards, self.local_shards
        best = None
        for p in range(self.n_processes):
            t = int(totals[p])
            for s in range(L):
                d = p * L + s
                writes = max(-(-(t - s) // L), 0)
                cand = writes * D + d
                if best is None or cand < best:
                    best = cand
        return int(best)

    def flush(self, ring: DeviceRing) -> DeviceRing:
        """Mirror pending LOCAL host writes into this process's shards of
        the global ``ring`` (consumed — donated). Collective: every
        process of the mesh must call this at the same point; the embedded
        cursor all-gather agrees on rounds and fill count."""
        from d4pg_tpu.parallel.distributed import host_allgather_i64

        buf = self._buffer
        L, cl = self.local_shards, self.chunk_local
        total = buf.total_added
        n_pending = min(total - self._synced, self.host_capacity)
        first = total - n_pending
        pend = (first + np.arange(n_pending)) % self.host_capacity
        by_lane = [pend[pend % L == s] // L for s in range(L)]
        my_rounds = (
            -(-max(len(b) for b in by_lane) // cl) if n_pending > 0 else 0
        )
        agreed = host_allgather_i64([total, my_rounds])   # [P, 2]
        rounds = int(agreed[:, 1].max())
        if rounds == 0:
            return ring
        new_size = np.int32(
            min(self._gapless_total(agreed[:, 0]), self.capacity)
        )
        for r in range(rounds):
            slots = np.full((L, cl), self.local_capacity, np.int32)
            gidx = np.zeros((L, cl), np.int64)
            for s in range(L):
                part = by_lane[s][r * cl:(r + 1) * cl]
                # LOCAL lane rows are GLOBAL shard rows: lane s row i is
                # local slot i*L + s = global slot i*D + (base + s), i.e.
                # shard (base+s) local row i — identical row index, so the
                # local deal needs no re-mapping.
                slots[s, : len(part)] = part
                gidx[s, : len(part)] = part * L + s
            chunk = {
                k: np.asarray(v).reshape((L, cl) + v.shape[1:])
                for k, v in dict(buf.gather(gidx.ravel())).items()
            }
            dev_chunk = {
                k: self._stage(v, self._chunk_sharding[k])
                for k, v in chunk.items()
            }
            slots_dev = self._stage(slots, self._slots_sharding)
            ring = self._ingest(
                ring,
                dev_chunk,
                slots_dev,
                self._stage_scalar(new_size),
            )
            if self.tree_hook is not None:
                self.tree_hook(slots_dev)
            self.bytes_ingested += sum(v.nbytes for v in chunk.values())
            self.bytes_ingested += slots.nbytes + new_size.nbytes
            self.chunks_ingested += 1
        self._synced = total
        return ring

    # ---------------------------------------------------------- snapshots
    def gather_snapshot(self, ring: DeviceRing) -> dict:
        """Assemble the GLOBAL ring into the exact
        :meth:`~d4pg_tpu.replay.uniform.ReplayBuffer.snapshot` npz layout
        (rows ``[0, size)`` in global slot order + ``pos``/``size``), so a
        multi-host checkpoint restores onto ANY topology — single-process
        ``ReplayBuffer.restore`` included. Collective (the per-field
        gathers all-gather across processes): every process must call it;
        process 0 writes the file. Call after :meth:`flush` so unmirrored
        local rows are not silently dropped from the snapshot."""
        from d4pg_tpu.parallel.distributed import (
            gather_global,
            host_allgather_i64,
        )

        totals = host_allgather_i64([self._buffer.total_added])[:, 0]
        T = self._gapless_total(totals)
        size = int(min(T, self.capacity))
        pos = int(T % self.capacity)
        D = self.n_shards
        perm = striped_perm(self.capacity, D).reshape(-1)
        out = {"pos": np.asarray(pos), "size": np.asarray(size)}
        for name in ("obs", "action", "reward", "next_obs", "discount"):
            # gathered as stored, read as rows: a numpy view on the host
            stored = gather_global(getattr(ring, name))
            lanes = stored.reshape((self.capacity, -1)[: stored.ndim])
            host = np.empty_like(lanes)
            host[perm] = lanes
            out[name] = host[:size]
        return out

    def deal_snapshot(self, data) -> int:
        """Restore this process's share of a GLOBAL replay snapshot (the
        :meth:`gather_snapshot` / single-process ``ReplayBuffer.snapshot``
        layout) into the LOCAL host buffer; returns the local row count.
        Inverse of the striped deal: global total ``T`` puts
        ``t_p = (T//D)*L + clip(T%D - p*L, 0, L)`` writes on host ``p``,
        and local slot ``m`` reads global slot ``(m//L)*D + p*L + (m%L)``.
        Host-local (no collective); resets ``_synced`` so the next flush
        re-mirrors the restored rows."""
        size = int(np.asarray(data["size"]).item())
        pos = int(np.asarray(data["pos"]).item())
        # Same lifetime-counter reconstruction rule as ReplayBuffer.restore.
        T = pos + self.capacity if size == self.capacity else size
        D, L, base = self.n_shards, self.local_shards, self.shard_lo
        t_p = (T // D) * L + int(np.clip(T % D - base, 0, L))
        n_local = min(t_p, self.host_capacity)
        m = np.arange(n_local)
        j = (m // L) * D + base + (m % L)
        local = {
            name: np.asarray(data[name])[j]
            for name in ("obs", "action", "reward", "next_obs", "discount")
        }
        local["pos"] = np.asarray(t_p % self.host_capacity)
        local["size"] = np.asarray(n_local)
        buf = self._buffer
        with buf._lock:
            buf._restore_arrays(local)
            # _restore_arrays reconstructs the lifetime counter as
            # pos+capacity on a full local ring — pin the exact cursor we
            # derived instead, so the next cursor agreement sees the same
            # T on every host.
            buf._total_added = t_p
        self._synced = 0
        return n_local
