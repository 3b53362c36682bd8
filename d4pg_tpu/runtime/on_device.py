"""Fully on-device training: rollout + replay + learn as ONE XLA program.

BASELINE.json config 5 ("Brax on-device envs: rollout + learn both on TPU,
end-to-end jit"). Where the reference round-trips host↔framework on every
single transition and train step (``utils.py:7-10``, ``ddpg.py:214``), here
one jitted ``train_iteration``:

  1. rolls a [num_envs, segment_len] exploration segment with ``lax.scan``
     (auto-reset, noise-state threading),
  2. collapses it to n-step transitions with truncation-exact windows
     (:func:`d4pg_tpu.ops.nstep_returns`, vmapped over envs),
  3. appends them to a device-resident uniform ring buffer
     (``lax.dynamic_update_slice`` — static shapes, no host),
  4. runs K train steps on uniform samples (``lax.scan`` over
     :func:`d4pg_tpu.agent.train_step`).

The host only orchestrates iteration counts and reads metrics.

Prioritized replay runs on device too (``config.prioritized``) — not with
segment trees (sequential descent is SIMD-hostile) but the TPU-native way:
proportional sampling is an O(C) ``cumsum`` + vectorized binary search
(``searchsorted``), which at HBM bandwidth is microseconds for a 10^5-slot
ring; priorities update by scatter after the train scan, stale within one
iteration exactly like the host fused path (and far fresher than the
reference's Hogwild staleness).
"""

from __future__ import annotations

import json
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map

from d4pg_tpu.agent import TrainState
from d4pg_tpu.agent.d4pg import fused_train_scan, gather_batches, make_noise
from d4pg_tpu.agent.state import D4PGConfig
from d4pg_tpu.runtime.collect import make_segment_collector


class DeviceReplay(NamedTuple):
    """Device-resident ring buffer (columnar, static shapes).

    ``priority`` holds α-exponentiated priorities (0 = empty slot; used only
    when the trainer is prioritized). ``max_priority`` is the running max of
    raw priorities, matching the host PER's new-sample seeding rule."""

    obs: jax.Array        # [C, O]
    action: jax.Array     # [C, A]
    reward: jax.Array     # [C]
    next_obs: jax.Array   # [C, O]
    discount: jax.Array   # [C]
    priority: jax.Array   # [C] — p_i^α, 0 where empty
    max_priority: jax.Array  # scalar f32
    pos: jax.Array        # scalar int32 — next write slot
    size: jax.Array       # scalar int32 — filled entries


def device_replay_init(
    capacity: int, obs_dim: int, action_dim: int, obs_dtype=jnp.float32
) -> DeviceReplay:
    """``obs_dtype=jnp.uint8`` stores observations quantized ×255 (pixel
    envs with [0,1] float frames) — 4× less HBM per ring row, mirroring the
    host buffer's uint8 storage (``replay/uniform.py``)."""
    return DeviceReplay(
        obs=jnp.zeros((capacity, obs_dim), obs_dtype),
        action=jnp.zeros((capacity, action_dim), jnp.float32),
        reward=jnp.zeros((capacity,), jnp.float32),
        next_obs=jnp.zeros((capacity, obs_dim), obs_dtype),
        discount=jnp.zeros((capacity,), jnp.float32),
        priority=jnp.zeros((capacity,), jnp.float32),
        max_priority=jnp.ones((), jnp.float32),
        pos=jnp.zeros((), jnp.int32),
        size=jnp.zeros((), jnp.int32),
    )


def _encode_obs(x: jax.Array, obs_dtype, scale: float = 255.0) -> jax.Array:
    """Same contract as the host ``ReplayBuffer._encode_obs``
    (``replay/uniform.py``): store ``clip(rint(x·scale), 0, 255)`` —
    ``scale`` is 255 for [0,1]-float envs, 1.0 for byte-image envs.
    ``bfloat16`` stores flat observations at half the HBM bytes — the row
    gather is a large share of a wide-row dispatch (PERF.md §5; this loop
    itself never ran on a chip), so row bytes are a throughput lever; 8 bits
    of mantissa cost ~1e-2 relative obs noise, the same magnitude as the
    exploration noise already injected on purpose."""
    if obs_dtype == jnp.uint8:
        return jnp.clip(jnp.round(x * scale), 0.0, 255.0).astype(jnp.uint8)
    if obs_dtype == jnp.bfloat16:
        return x.astype(jnp.bfloat16)
    return x


def _decode_obs(x: jax.Array, obs_dtype) -> jax.Array:
    """Decoded batches are always floats in the env's scale (host
    convention: [0,1] for uint8-quantized pixel rings)."""
    if obs_dtype == jnp.uint8:
        return x.astype(jnp.float32) / 255.0
    if obs_dtype == jnp.bfloat16:
        return x.astype(jnp.float32)
    return x


def _append(
    replay: DeviceReplay, batch: dict, count: int, alpha: float,
    obs_scale: float = 255.0,
) -> DeviceReplay:
    """Write ``count`` rows at the ring position. Requires capacity % count
    == 0 so a write never wraps mid-block (enforced by the factory). New
    rows enter at max_priority^α (reference ``prioritized_replay_memory.py:251-256``)."""
    p = replay.pos
    obs_dtype = replay.obs.dtype
    new_prio = jnp.full((count,), replay.max_priority**alpha, jnp.float32)
    return replay._replace(
        obs=jax.lax.dynamic_update_slice(
            replay.obs, _encode_obs(batch["obs"], obs_dtype, obs_scale), (p, 0)
        ),
        action=jax.lax.dynamic_update_slice(replay.action, batch["action"], (p, 0)),
        reward=jax.lax.dynamic_update_slice(replay.reward, batch["reward"], (p,)),
        next_obs=jax.lax.dynamic_update_slice(
            replay.next_obs, _encode_obs(batch["next_obs"], obs_dtype, obs_scale), (p, 0)
        ),
        discount=jax.lax.dynamic_update_slice(
            replay.discount, batch["discount"], (p,)
        ),
        priority=jax.lax.dynamic_update_slice(replay.priority, new_prio, (p,)),
        pos=(p + count) % replay.obs.shape[0],
        size=jnp.minimum(replay.size + count, replay.obs.shape[0]),
    )


def make_on_device_trainer(
    config: D4PGConfig,
    env,
    num_envs: int = 64,
    segment_len: int = 32,
    replay_capacity: int = 131_072,
    batch_size: int = 256,
    train_steps_per_iter: int = 32,
    mesh=None,
    axis_name: str = "dp",
    obs_uint8: bool = False,
    obs_scale: float = 255.0,
    obs_bf16: bool = False,
):
    """Build (init_fn, warmup_fn, iterate_fn) for the fully-jitted loop.

    ``init_fn(state, key) -> carry``; ``warmup_fn(carry, noise_scale) ->
    carry`` collects one num_envs×segment_len exploration segment into the
    device replay WITHOUT training (the reference's replay pre-fill,
    ``main.py:200-207``); ``iterate_fn(carry, noise_scale) -> (carry,
    metrics)`` = one segment + train_steps_per_iter grad steps, entirely on
    device. ``noise_scale`` is a traced scalar multiplying exploration
    noise — drive it with a schedule (the host trainer's ε-decay) without
    retracing.

    With ``mesh``, the whole loop runs data-parallel under ``shard_map``
    over ``axis_name`` — BASELINE config 5 at pod scale. ``num_envs``,
    ``replay_capacity`` and ``batch_size`` are GLOBAL and divided across
    the axis: each device rolls its env shard, owns its shard of the
    replay ring (distributed PER — proportional sampling over the local
    shard, the standard distributed-replay approximation), and trains on
    its batch shard; one ``pmean`` per grad step (inside
    :func:`~d4pg_tpu.agent.d4pg.train_step`) rides ICI, so params stay
    replicated and bit-identical. Per-device PRNG streams come from
    folding ``axis_index`` into the replicated carry key; ``pos``/``size``
    evolve identically everywhere and stay replicated; ``max_priority`` is
    ``pmax``-synced each iteration.
    """
    D = 1
    if mesh is not None:
        D = int(mesh.shape[axis_name])
        for name, val in (
            ("num_envs", num_envs),
            ("replay_capacity", replay_capacity),
            ("batch_size", batch_size),
        ):
            if val % D != 0:
                raise ValueError(
                    f"{name} ({val}) must be divisible by mesh axis "
                    f"{axis_name!r} size {D}"
                )
        num_envs //= D
        replay_capacity //= D
        batch_size //= D
    axis = axis_name if mesh is not None else None
    if obs_uint8 and obs_scale != 255.0:
        # Mirror of ReplayBuffer's guard: _decode_obs always maps to [0,1],
        # so acting on raw env frames and training on decoded batches only
        # agree when the env itself emits [0,1] floats (scale 255).
        raise ValueError(
            "obs_scale must be 255.0 (env emits [0,1] floats); byte-image "
            "envs should normalize observations at the env boundary"
        )
    n_new = num_envs * segment_len
    if replay_capacity % n_new != 0:
        raise ValueError(
            f"replay_capacity ({replay_capacity * D}) must be a multiple of "
            f"num_envs*segment_len ({n_new * D}"
            + (f" — both are per-device ÷{D})" if D > 1 else ")")
        )
    noise_init, noise_sample, noise_reset = make_noise(config)
    if obs_uint8 and obs_bf16:
        raise ValueError("obs_uint8 and obs_bf16 are mutually exclusive")
    obs_dtype = (
        jnp.uint8 if obs_uint8 else jnp.bfloat16 if obs_bf16 else jnp.float32
    )

    def _decode_batches(b: dict) -> dict:
        b["obs"] = _decode_obs(b["obs"], obs_dtype)
        b["next_obs"] = _decode_obs(b["next_obs"], obs_dtype)
        return b

    def _fold_local(key):
        """Distinct per-device stream from the replicated carry key."""
        if axis is None:
            return key
        return jax.random.fold_in(key, jax.lax.axis_index(axis))

    def init_body(state: TrainState, key: jax.Array):
        k_reset = _fold_local(jax.random.fold_in(key, 0))
        k_carry = jax.random.fold_in(key, 1)  # replicated; folded per use
        reset_keys = jax.random.split(k_reset, num_envs)
        env_states, obs = jax.vmap(env.reset)(reset_keys)
        noise_states = jax.vmap(lambda _: noise_init())(jnp.arange(num_envs))
        replay = device_replay_init(
            replay_capacity, config.obs_dim, config.action_dim,
            obs_dtype=obs_dtype,
        )
        return (state, env_states, obs, noise_states, replay, k_carry)

    # Steps 1-2 (vmapped exploration rollout + n-step collapse) are the
    # shared jitted collector; step 3 (ring append) is ours.
    segment_collect = make_segment_collector(
        config, env, num_envs, segment_len,
        noise_fns=(noise_init, noise_sample, noise_reset),
    )

    def _collect(state, env_states, obs, noise_states, replay, k_roll, scale):
        env_states, obs, noise_states, flat, traj = segment_collect(
            state.actor_params, env_states, obs, noise_states,
            _fold_local(k_roll), scale,
        )
        replay = _append(replay, flat, n_new, config.per_alpha, obs_scale)
        return env_states, obs, noise_states, replay, traj

    def warmup_body(carry, noise_scale):
        state, env_states, obs, noise_states, replay, key = carry
        key, k_roll = jax.random.split(key)
        env_states, obs, noise_states, replay, _ = _collect(
            state, env_states, obs, noise_states, replay, k_roll, noise_scale
        )
        return (state, env_states, obs, noise_states, replay, key)

    def iterate_body(carry, noise_scale):
        state, env_states, obs, noise_states, replay, key = carry
        key, k_roll, k_train = jax.random.split(key, 3)
        k_train = _fold_local(k_train)
        env_states, obs, noise_states, replay, traj = _collect(
            state, env_states, obs, noise_states, replay, k_roll, noise_scale
        )

        # ---- 4. K train steps ----------------------------------------------
        K, B = train_steps_per_iter, batch_size
        if config.prioritized:
            # Device PER: O(C) cumsum + vectorized binary search replaces
            # the host's segment trees — streaming a 10^5-slot priority
            # array is HBM-trivial, sequential tree descent is not.
            prio = replay.priority
            cums = jnp.cumsum(prio)
            total = cums[-1]
            u = jax.random.uniform(k_train, (K, B)) * total
            idx = jnp.clip(jnp.searchsorted(cums, u), 0, replay.size - 1)
            p = prio[idx] / total
            frac = jnp.clip(
                state.step.astype(jnp.float32) / max(config.per_beta_steps, 1),
                0.0,
                1.0,
            )
            beta = config.per_beta0 + frac * (1.0 - config.per_beta0)
            size_f = replay.size.astype(jnp.float32)
            weights = (p * size_f) ** (-beta)
            min_p = jnp.min(jnp.where(prio > 0, prio, jnp.inf)) / total
            weights = weights / ((min_p * size_f) ** (-beta))
            batches = _decode_batches(gather_batches(replay, idx))
            batches["weights"] = weights
            state, metrics, new_pri = fused_train_scan(
                config, state, batches, axis_name=axis
            )
            # ordered write-back: later steps win on duplicate indices,
            # matching the host loop's sequential update_priorities calls
            pa = (jnp.abs(new_pri) + config.per_eps) ** config.per_alpha

            def upd(k, pr):
                return pr.at[idx[k]].set(pa[k])

            prio = jax.lax.fori_loop(0, K, upd, prio)
            max_priority = jnp.maximum(
                replay.max_priority, jnp.max(jnp.abs(new_pri) + config.per_eps)
            )
            if axis is not None:
                # keep the replicated scalar identical across shards
                max_priority = jax.lax.pmax(max_priority, axis)
            replay = replay._replace(priority=prio, max_priority=max_priority)
        else:
            idx = jax.random.randint(k_train, (K, B), 0, replay.size)
            state, metrics, _ = fused_train_scan(
                config, state, _decode_batches(gather_batches(replay, idx)),
                axis_name=axis,
            )
        metrics = jax.tree_util.tree_map(jnp.mean, metrics)
        # TRAIN-time diagnostic, not an evaluation return: exploration
        # reward collected this segment divided by episode boundaries seen
        # this segment. With few/no boundaries in a segment the denominator
        # clamps to 1 and the value can exceed any true episode return by a
        # large factor — compare trends only, never against eval_return_mean
        # (VERDICT round-2 weak #6: the old name read as a return).
        proxy = jnp.sum(traj.reward) / jnp.maximum(
            jnp.sum(jnp.maximum(traj.terminated, traj.truncated)), 1.0
        )
        if axis is not None:
            proxy = jax.lax.pmean(proxy, axis)
        metrics["train_reward_per_episode_boundary"] = proxy
        return (state, env_states, obs, noise_states, replay, key), metrics

    if mesh is None:
        return jax.jit(init_body), jax.jit(warmup_body), jax.jit(iterate_body)

    from jax.sharding import PartitionSpec as P

    rep, shd = P(), P(axis_name)
    replay_spec = DeviceReplay(
        obs=shd, action=shd, reward=shd, next_obs=shd, discount=shd,
        priority=shd, max_priority=rep, pos=rep, size=rep,
    )
    carry_spec = (rep, shd, shd, shd, replay_spec, rep)
    init_fn = jax.jit(
        shard_map(
            init_body, mesh=mesh, in_specs=(rep, rep), out_specs=carry_spec,
            check_vma=False,
        )
    )
    warmup_fn = jax.jit(
        shard_map(
            warmup_body, mesh=mesh, in_specs=(carry_spec, rep),
            out_specs=carry_spec, check_vma=False,
        )
    )
    iterate_fn = jax.jit(
        shard_map(
            iterate_body, mesh=mesh, in_specs=(carry_spec, rep),
            out_specs=(carry_spec, rep), check_vma=False,
        )
    )
    return init_fn, warmup_fn, iterate_fn


def run_on_device(config, preempt_event=None) -> dict:
    """CLI driver for the fully on-device loop (``train.py --on-device``).

    Wraps (init_fn, iterate_fn) with the same periphery the host
    :class:`~d4pg_tpu.runtime.trainer.Trainer` provides — greedy eval on the
    eval cadence, EWMA return, TensorBoard/JSONL metrics, Orbax checkpoints,
    ``--resume`` — while the training loop itself never leaves the device:
    metrics stay as device arrays between evals (a fetch per iteration would
    be a link round-trip), and one iteration = ``num_envs × 32`` env steps
    plus ``round(num_envs × 32 / env_steps_per_train_step)`` grad steps, so
    the collect:train ratio is honored exactly like the host loop.

    Pure-JAX envs only. The device replay ring is rebuilt on ``--resume``
    and re-warmed with ``warmup_steps`` of fresh exploration (ring contents
    are not checkpointed). Exploration noise follows the same env-step
    schedule as the host trainer (``noise_decay_steps``/``noise_scale_final``;
    constant when decay is 0 — the reference's effective behavior, SURVEY.md
    quirk #10) and warmup collects at 3× scale, matching the host warmup.
    """
    import time

    if getattr(config, "obs_norm", False):
        # Guard at the entry point, not just the CLI: a programmatic
        # TrainConfig(obs_norm=True) must not be silently ignored (the
        # on-device path keeps observations inside jit).
        raise ValueError(
            "obs_norm is a host data-boundary feature; the on-device path "
            "does not support it"
        )

    from d4pg_tpu.agent import create_train_state
    from d4pg_tpu.envs import make_env
    from d4pg_tpu.replay import noise_scale_schedule
    from d4pg_tpu.runtime.checkpoint import (
        CheckpointManager,
        best_eval_path,
        invalidate_best_eval,
        load_trainer_meta,
        save_best_eval,
        save_trainer_meta,
    )
    from d4pg_tpu.runtime.evaluator import evaluate
    from d4pg_tpu.runtime.metrics import MetricsLogger, interval_crossed
    from d4pg_tpu.runtime.trainer import _reconcile_config, _rss_gb

    env = make_env(config.env, config.max_episode_steps, config.action_repeat)
    if hasattr(env, "last_goal_obs"):
        raise ValueError(
            "--on-device needs a pure-JAX env (pendulum, pixel_pendulum, "
            "pointmass_goal); host gymnasium envs use the actor pool instead"
        )
    config = _reconcile_config(config, env)
    agent_cfg = config.agent
    segment_len = 32
    n_new = config.num_envs * segment_len
    K = max(1, round(n_new / max(config.env_steps_per_train_step, 1e-9)))
    capacity = max(n_new, (config.replay_capacity // n_new) * n_new)
    if capacity != config.replay_capacity:
        print(
            f"replay capacity {config.replay_capacity} adjusted to {capacity} "
            f"(device ring must be a multiple of num_envs×segment_len = {n_new})"
        )
    mesh = None
    if config.dp:
        from d4pg_tpu.parallel import make_mesh

        mesh = make_mesh(dp=config.dp, tp=1)
    init_fn, warmup_fn, iterate_fn = make_on_device_trainer(
        agent_cfg,
        env,
        num_envs=config.num_envs,
        segment_len=segment_len,
        replay_capacity=capacity,
        batch_size=config.batch_size,
        train_steps_per_iter=K,
        mesh=mesh,
        # Pixel frames store uint8-quantized in the HBM ring — the same 4×
        # saving and obs_scale convention as the host buffer
        # (replay/uniform.py: envs emit [0,1] floats, scale is always 255;
        # byte-image envs must normalize at the env boundary — the factory
        # guard rejects anything else; decoded batches are always [0,1]).
        obs_uint8=bool(agent_cfg.pixel_shape),
        obs_scale=getattr(env, "obs_scale", None) or 255.0,
        # Flat-obs rings optionally store bf16 rows (--ring-dtype
        # bfloat16): half the gather bytes on the workload the roofline
        # shows is bandwidth-bound, for ~1e-2 relative obs noise.
        obs_bf16=(
            config.ring_dtype == "bfloat16" and not agent_cfg.pixel_shape
        ),
    )

    key = jax.random.PRNGKey(config.seed)
    key, k_state = jax.random.split(key)
    state = create_train_state(agent_cfg, k_state)
    if mesh is not None:
        from d4pg_tpu.parallel.dp import replicate

        state = replicate(state, mesh)
    ckpt = CheckpointManager(f"{config.log_dir}/checkpoints")
    # Eval-selected keep-best: late-training policy collapse (observed on
    # Walker2d, VERDICT round-2 weak #2 — peak 2,674 → final 21) would
    # otherwise leave the artifact's only checkpoint holding the collapsed
    # policy. The best-eval params are snapshotted separately so the
    # headline policy survives whatever happens afterwards.
    best_ckpt = CheckpointManager(f"{config.log_dir}/checkpoints_best", max_to_keep=1)
    env_steps = 0
    ewma = None
    best_eval = None
    if config.resume and ckpt.latest_step() is not None:
        state = ckpt.restore(state)
        meta = load_trainer_meta(config.log_dir)
        env_steps = int(meta.get("env_steps", 0))
        ewma = meta.get("ewma_return")
        # Without this a resumed leg's first (worse) eval would clobber the
        # best-params snapshot from the previous leg. Only preloaded when a
        # checkpoints_best snapshot actually backs it — a leftover
        # best_eval.json from a HOST-trainer run in the same dir (which
        # writes best_actor.npz, never checkpoints_best/) must not preload
        # a score this driver never persisted; corrupt JSON starts fresh.
        best_json = best_eval_path(config.log_dir)
        if best_ckpt.latest_step() is not None and os.path.exists(best_json):
            try:
                with open(best_json) as f:
                    best_eval = float(json.load(f)["eval_return_mean"])
            except (OSError, ValueError, KeyError):
                pass
    grad_steps = int(jax.device_get(state.step))
    # Distinct key stream per resumed leg — replaying PRNGKey(seed) would
    # repeat the original run's exact exploration/eval sequence every leg.
    key = jax.random.fold_in(key, grad_steps)
    key, k_init = jax.random.split(key)
    carry = init_fn(state, k_init)
    logger = MetricsLogger(config.log_dir)
    last: dict = {}
    # --total-steps is a PER-INVOCATION budget, exactly like Trainer.train
    # (`while grad_steps_done < total`): a resumed leg runs `total_steps`
    # MORE grad steps on top of the restored counter. Supervisor loops
    # pass the remainder each leg; with a global interpretation a
    # restored step >= the remainder
    # would make every leg eval-only and livelock the supervisor loop.
    total = grad_steps + config.total_steps
    t0 = time.monotonic()
    grad_steps_done = 0
    env_steps_done = 0
    def _noise_scale() -> float:
        return noise_scale_schedule(
            env_steps, agent_cfg.noise_decay_steps, agent_cfg.noise_scale_final
        )

    try:
        # Replay pre-fill without training (reference warmup, main.py:200-207)
        # at 3× noise like the host warmup. Needed after resume too: the
        # device ring starts empty every run. Skipped when the checkpoint
        # already satisfies total_steps — the eval-only path below never
        # samples the ring.
        while grad_steps < total and env_steps_done < max(
            config.warmup_steps, config.batch_size
        ):
            carry = warmup_fn(carry, 3.0)
            env_steps_done += n_new
            env_steps += n_new

        def _eval_and_log(m) -> dict:
            nonlocal ewma, last, key, best_eval
            key, ek = jax.random.split(key)
            scalars = {k: float(v) for k, v in jax.device_get(m).items()} if m else {}
            scalars.update(
                evaluate(
                    agent_cfg, env, carry[0].actor_params, ek,
                    config.eval_episodes,
                )
            )
            ewma = (
                scalars["eval_return_mean"]
                if ewma is None
                else (1 - config.ewma_alpha) * ewma
                + config.ewma_alpha * scalars["eval_return_mean"]
            )
            if best_eval is None or scalars["eval_return_mean"] > best_eval:
                best_eval = scalars["eval_return_mean"]
                # A resumed leg can re-cross the same grad_steps a previous
                # leg already saved at (Orbax raises on an existing step) —
                # with DIFFERENT params, so the old save must be deleted and
                # replaced: skipping the save while updating the JSON left
                # best_eval.json attesting a score the persisted params
                # never achieved (ADVICE round-3). The JSON is invalidated
                # BEFORE the delete: a crash inside the replacement window
                # then reads as 'no best recorded', never as an attestation
                # of params that no longer exist. prev > grad_steps needs
                # the same treatment (a leg resumed from an OLDER main
                # checkpoint): Orbax retention keeps the highest step, so
                # saving a lower one would be garbage-collected immediately
                # while the JSON attested it.
                prev = best_ckpt.latest_step()
                if prev is not None:
                    # Invalidate in BOTH branches: even when prev <
                    # grad_steps (no explicit delete), Orbax max_to_keep=1
                    # garbage-collects the prev step during save(), so a
                    # crash between that GC and save_best_eval would leave
                    # the JSON attesting deleted params with a stale lower
                    # score — and a later mediocre eval could then overwrite
                    # the true champion (ADVICE round-4).
                    invalidate_best_eval(config.log_dir)
                    if prev >= grad_steps:
                        best_ckpt.delete(prev)
                best_ckpt.save(grad_steps, carry[0])
                # Orbax saves are async: wait before recording the score so
                # a crash can never leave best_eval.json claiming params
                # that were never persisted (same ordering as _save below).
                best_ckpt.wait()
                save_best_eval(config.log_dir, grad_steps, best_eval, env_steps)
            scalars["best_eval_return"] = best_eval
            dt = time.monotonic() - t0
            scalars.update(
                avg_test_reward_ewma=ewma,
                noise_scale=_noise_scale(),
                grad_steps_per_sec=grad_steps_done / dt,
                env_steps_per_sec=env_steps_done / dt,
                # carry[4].size is the per-shard counter (identical on every
                # device); report the GLOBAL fill to match --rmsize
                replay_size=int(jax.device_get(carry[4].size)) * (config.dp or 1),
                env_steps=env_steps,
            )
            logger.log(grad_steps, scalars)
            print(
                f"[step {grad_steps}] "
                + " ".join(
                    f"{k}={v:.3f}"
                    for k, v in scalars.items()
                    if k != "replay_size"
                )
            )
            last = scalars
            return scalars

        def _save():
            ckpt.save(grad_steps, carry[0])
            # Orbax write finishes before the meta file, so a crash between
            # them never leaves meta newer than the newest checkpoint.
            ckpt.wait()
            save_trainer_meta(config.log_dir, env_steps, ewma)

        if grad_steps >= total:
            # Zero per-invocation budget: report instead of silently no-opping.
            print(
                f"--total-steps {config.total_steps} leaves no budget at "
                f"step {grad_steps}; running final eval only"
            )
            _eval_and_log(None)
            return last
        while grad_steps < total:
            if preempt_event is not None and preempt_event.is_set():
                # SIGTERM/SIGINT path (train.py handlers set the event):
                # same checkpoint + exit-75 contract as the RSS watchdog.
                _save()
                print(
                    f"[preempt] stop requested: checkpointed at step "
                    f"{grad_steps}; exiting for a --resume restart"
                )
                last = dict(last)
                last["_preempted"] = True
                break
            carry, m = iterate_fn(carry, _noise_scale())
            prev = grad_steps
            grad_steps += K
            grad_steps_done += K
            env_steps += n_new
            env_steps_done += n_new
            if interval_crossed(prev, grad_steps, config.eval_interval) or (
                grad_steps >= total
            ):
                _eval_and_log(m)
            saved = interval_crossed(
                prev, grad_steps, config.checkpoint_interval
            ) or (grad_steps >= total)
            if saved:
                _save()
            if (
                config.max_rss_gb > 0
                and grad_steps < total
                and interval_crossed(prev, grad_steps, config.eval_interval)
                and _rss_gb() > config.max_rss_gb
            ):
                if not saved:
                    _save()
                print(
                    f"[rss-watchdog] RSS {_rss_gb():.1f} GB > "
                    f"--max-rss-gb {config.max_rss_gb}: checkpointed at "
                    f"step {grad_steps}; exiting for a --resume restart"
                )
                last = dict(last)
                last["_preempted"] = True
                break
    finally:
        ckpt.wait()
        logger.close()
        ckpt.close()
        best_ckpt.close()
    return last
