"""The saturated learner: a full ring and a seeded priority tree on the
device, the megastep from the factory ``Trainer`` would pick, dispatched back
to back. No env, no collection, no eval, no checkpoint.

Mix parameters (``cellbench/traffic/<mix>.json``): ``argv`` (appended to the
configuration's), ``dp`` (chips the mesh spans; absent = one device, no
mesh), ``ring_rows`` ("global": the configuration's ``--rmsize`` is the whole
ring; "per_chip": each chip holds that many rows), ``warm_dispatches``,
``inflight`` (dispatches the host may run ahead of the device),
``trace_seconds``, ``dispatch_module``.

Surface into the program: ``train.build_parser``/``config_from_args``,
``agent.create_train_state``, the ``make_megastep_*`` factories,
``DeviceRing``, ``DevicePerTree``, ``parallel.make_mesh`` /
``shard_train_state`` / ``ring_partition_specs`` / ``tree_partition_specs``.
"""

from __future__ import annotations

import collections
import gc
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import correctness, datagen, probe, trace
from cellbench.drivers import Job, resolve_config

P_MAX = 4.0   # seeded pre-α priorities lie in (0, P_MAX]: the scale the
              # write-back's |cross-entropy| of a 51-atom critic lives on


def _megastep(cfg, k, mesh):
    """The factory ``Trainer.__init__`` picks for this configuration;
    ``test_learner_picks_the_factory_trainer_picks`` holds the two together,
    one case for each branch."""
    from d4pg_tpu.runtime import megastep as ms

    agent, batch = cfg.agent, cfg.batch_size
    if not cfg.prioritized:
        if mesh is not None:
            return ms.make_megastep_uniform_sharded(agent, k, batch, mesh)
        return ms.make_megastep_uniform(agent, k, batch)
    if mesh is not None:
        return ms.make_megastep_device_per_sharded(
            agent, k, batch, mesh, tree_backend=cfg.device_tree_backend)
    if cfg.fused_descent:
        return ms.make_megastep_device_per_fused(agent, k, batch)
    return ms.make_megastep_device_per(
        agent, k, batch, tree_backend=cfg.device_tree_backend)


def _shardings(mesh, specs):
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def makers(job: Job, cfg, lanes: int):
    """The cell's sizes and the pure functions that make its device state
    from the seed (each is one jitted call with the seed as its argument, so
    one compiled program serves every seed; ``jax.eval_shape`` of them gives
    the shapes without a device)."""
    from d4pg_tpu.agent.d4pg import create_train_state
    from d4pg_tpu.replay.device_per import DevicePerTree
    from d4pg_tpu.replay.device_ring import DeviceRing

    agent, dist = cfg.agent, cfg.agent.dist
    per_chip = job.cell.traffic.get("ring_rows", "global") == "per_chip"
    capacity = cfg.replay_capacity * (lanes if per_chip else 1)
    lane_capacity = capacity // lanes
    lane_leaves = datagen.next_pow2(lane_capacity)

    def make_state(seed):
        return create_train_state(agent, jax.random.PRNGKey(seed))

    def make_ring(seed):
        fields = datagen.ring_fields(
            seed, capacity, agent.obs_dim, agent.action_dim,
            agent.gamma ** agent.n_step, (dist.v_max - dist.v_min) / 100.0)
        return DeviceRing(size=jnp.int32(capacity), **fields)

    def make_leaves(seed):
        return datagen.priority_leaves(
            seed, lanes, lane_capacity, lane_leaves, agent.per_alpha,
            agent.per_eps, P_MAX)

    def make_tree(leaves):
        return DevicePerTree(datagen.tree_levels(leaves), jnp.float32(P_MAX))

    sizes = dict(capacity=capacity, lanes=lanes, lane_leaves=lane_leaves)
    return sizes, make_state, make_ring, make_leaves, make_tree


def build(job: Job, cfg, mesh):
    """State, tree, ring and key on the device(s), each from one jitted call
    keyed by the seed. The tree comes before the ring: what making a tree
    needs beside it (its leaves, the levels before they are joined) is freed
    before the ring is there, so the lifetime peak the harness reads is the
    cell's own state and not its set-up's."""
    lanes = int(mesh.shape["dp"]) if mesh is not None else 1
    sizes, make_state, make_ring, make_leaves, make_tree = makers(job, cfg, lanes)
    seed = jnp.uint32(job.seed)
    state = jax.jit(make_state)(seed)
    key = jax.random.PRNGKey(job.seed + 1)
    if mesh is None:
        ring_fn = jax.jit(make_ring)
        leaves_fn = jax.jit(make_leaves)
        tree_fn = jax.jit(make_tree)
        key = jax.device_put(key)
    else:
        from jax.sharding import NamedSharding, PartitionSpec

        from d4pg_tpu.parallel import (
            ring_partition_specs, shard_train_state, stack_axes_for)
        from d4pg_tpu.parallel.partition import tree_partition_specs

        state = shard_train_state(state, mesh, stack_axes=stack_axes_for(cfg.agent))
        ring_sh = _shardings(mesh, ring_partition_specs(jax.eval_shape(make_ring, seed)))
        ring_fn = jax.jit(make_ring, out_shardings=ring_sh)
        leaves_sh = NamedSharding(mesh, PartitionSpec("dp", None))
        leaves_fn = jax.jit(make_leaves, out_shardings=leaves_sh)
        tree_sh = _shardings(mesh, tree_partition_specs(
            jax.eval_shape(make_tree, jax.eval_shape(make_leaves, seed))))
        tree_fn = jax.jit(make_tree, out_shardings=tree_sh)
        key = jax.device_put(key, NamedSharding(mesh, PartitionSpec()))
    job.say("state made")
    tree = None
    if cfg.prioritized:
        tree = jax.block_until_ready(tree_fn(leaves_fn(seed)))
        job.say("tree made")
    ring = ring_fn(seed)
    job.say("ring made")
    return state, ring, tree, key, lambda: leaves_fn(seed), sizes


def compile_for(job: Job, cfg, devices):
    """Lower and compile the cell's megastep at its real shapes for
    ``devices`` (attached, or only described: ``jax.experimental.topologies``)
    without making a single array. Returns the compiled program."""
    from jax.sharding import SingleDeviceSharding

    mesh = _mesh(job, devices)
    lanes = int(mesh.shape["dp"]) if mesh is not None else 1
    _, make_state, make_ring, make_leaves, make_tree = makers(job, cfg, lanes)
    seed = jax.ShapeDtypeStruct((), jnp.uint32)
    args = [jax.eval_shape(make_state, seed), jax.eval_shape(make_ring, seed)]
    if cfg.prioritized:
        args.append(jax.eval_shape(make_tree, jax.eval_shape(make_leaves, seed)))
    args.append(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    if mesh is None:   # the sharded factories carry their own in_shardings
        one = SingleDeviceSharding(devices[0])
        args = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), args)
    k = max(1, cfg.steps_per_dispatch)
    return _megastep(cfg, k, mesh).lower(*args).compile()


def _mesh(job: Job, devices):
    if not job.cell.traffic.get("dp"):
        return None
    from d4pg_tpu.parallel import make_mesh

    return make_mesh(dp=int(job.cell.traffic["dp"]), tp=1, devices=devices)


class Loop:
    """Back-to-back dispatches with the host held at most ``inflight``
    dispatches ahead of the device (by waiting on an older dispatch's loss,
    never by fetching it), so that a deadline on the host's clock ends the
    window within a few dispatches of the device's work."""

    def __init__(self, mega, state, ring, tree, key, inflight: int):
        self.mega, self.ring, self.inflight = mega, ring, inflight
        self.state, self.tree, self.key = state, tree, key
        self.error = None

    def _dispatch(self):
        if self.tree is None:
            self.state, self.key, metrics = self.mega(self.state, self.ring, self.key)
        else:
            self.state, self.tree, self.key, metrics = self.mega(
                self.state, self.ring, self.tree, self.key)
        return metrics["critic_loss"]

    def run(self, seconds: float | None = None, dispatches: int | None = None):
        """Dispatch until the deadline (or a count); returns ``(clock at the
        first dispatch, clock after the last one's state is ready, losses)``.
        A dispatch that raises ends the loop and is kept in ``error``."""
        pending, losses = collections.deque(), []
        jax.block_until_ready(self.state.step)
        gc.disable()
        try:
            c0 = probe.Clock()
            while (len(losses) < dispatches if dispatches is not None
                   else time.perf_counter() - c0.perf < seconds):
                try:
                    loss = self._dispatch()
                except Exception as e:  # noqa: BLE001 - counted as a failed dispatch
                    self.error = e
                    break
                losses.append(loss)
                pending.append(loss)
                if len(pending) > self.inflight:
                    jax.block_until_ready(pending.popleft())
            jax.block_until_ready(self.state.step)
            c1 = probe.Clock()
        finally:
            gc.enable()
        return c0, c1, losses


def run(job: Job) -> dict:
    mix, say = job.cell.traffic, job.say
    cfg = resolve_config(job)
    agent, k, batch = cfg.agent, max(1, cfg.steps_per_dispatch), cfg.batch_size
    mesh = _mesh(job, job.devices)
    lanes = int(mesh.shape["dp"]) if mesh is not None else 1
    sizes = makers(job, cfg, lanes)[0]

    # Checks 1 and 2 come before anything of the cell is on the device, and
    # keep nothing there: peak_bytes_in_use is a lifetime maximum, and a
    # check's tree beside the cell's ring would be read as the program's.
    checks = {
        "reference_step": correctness.reference_check(
            agent, batch, job.seed, job.cell.config["reference"]),
    }
    say(f"reference step checked: {checks['reference_step']['ok']}")
    if cfg.prioritized:
        checks["descent"] = correctness.descent_check(sizes["lane_leaves"], job.seed)
        say(f"descent checked: {checks['descent']['ok']}")

    state, ring, tree, key, leaves_fn, sizes = build(job, cfg, mesh)
    mega = _megastep(cfg, k, mesh)
    jax.block_until_ready((ring, tree))
    say(f"built: {sizes}, K={k}, B={batch}")

    loop = Loop(mega, state, ring, tree, key, int(mix["inflight"]))
    loop.run(dispatches=int(mix["warm_dispatches"]))
    if loop.error is not None:
        raise loop.error
    step0 = int(jax.device_get(loop.state.step))
    say("warmed; the window starts")

    setup_s = job.setup_s()
    with jax.transfer_guard("disallow"):
        c0, c1, losses = loop.run(seconds=job.seconds - job.trace_seconds)
    # The program's own peak: read before the checks below put their
    # reductions over the tree beside it.
    peak = probe.peak_bytes(job.devices)
    n = len(losses)
    losses = np.asarray(jax.device_get(losses), np.float64)
    failed = int(np.sum(~np.isfinite(losses))) + (loop.error is not None)
    attempted = n + (loop.error is not None)

    steps = int(jax.device_get(loop.state.step)) - step0
    checks["grad_steps_advanced"] = {
        "ok": steps == n * k, "advanced": steps, "dispatches_x_k": n * k}
    checks["window_ran_under_transfer_guard"] = {
        "ok": loop.error is None, "error": repr(loop.error) if loop.error else None}
    checks["state_finite"] = {"ok": correctness.all_finite(loop.state)}
    if tree is not None and loop.error is None:
        checks["tree_sums"] = correctness.tree_sums_check(loop.tree)
        half = loop.tree.sums.shape[1] // 2
        moved = int(jnp.sum(loop.tree.sums[:, half:] != leaves_fn()))
        checks["sampled_leaves_moved"] = {
            "ok": 0 < moved <= (n + int(mix["warm_dispatches"])) * k * batch,
            "moved": moved}

    xplane, traced = None, None
    if job.trace and loop.error is None:
        trace_dir = os.path.join(job.cell.out_dir, "trace")
        trace.start(trace_dir)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            t0, t1, traced_losses = loop.run(seconds=job.trace_seconds)
        trace.stop()
        xplane = trace.newest_xplane(trace_dir)
        traced = {"seconds": t1.perf - t0.perf, "dispatches": len(traced_losses)}

    window = {
        "seconds": c1.perf - c0.perf, "dispatches": n, "grad_steps": n * k,
        "transitions": n * k * batch, "wall": (c0.wall, c1.wall)}
    return {
        "setup_s": setup_s, "attempted": attempted, "failed": failed,
        "window": window, "memory_peak_bytes": peak,
        "traced": traced, "xplane": xplane, "checks": checks,
        "agent_cfg": agent, "batch": batch, "k": k, "sizes": sizes,
    }
