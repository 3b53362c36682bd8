"""D4PG algorithm core: one fused, jittable SGD step.

Everything the reference does between ``sample()`` and
``update_priorities`` (``ddpg.py:200-255``, SURVEY.md §3.2) — two target
forwards, the categorical Bellman projection, critic CE loss with PER
importance weights, actor −E[Q] loss, both Adam updates, the Polyak target
update, and new priorities — compiles into ONE XLA computation with no
host↔device hops (the reference round-trips through NumPy every step at
``ddpg.py:214`` and ``utils.py:7-10``).

The functions are pure: (state, batch) → (state, metrics, priorities). Data
parallelism wraps them unchanged (``d4pg_tpu.parallel``).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import optax

from d4pg_tpu.agent.state import D4PGConfig, TrainState
from d4pg_tpu.models import Actor, Critic
from d4pg_tpu.ops import (
    CategoricalSupport,
    categorical_projection,
    categorical_td_loss,
    expected_value,
    gaussian_noise_init,
    gaussian_noise_sample,
    make_support,
    ou_noise_init,
    ou_noise_reset,
    ou_noise_sample,
    polyak_update,
)
from d4pg_tpu.models.critic import mixture_gaussian_mean
from d4pg_tpu.models.torso import torso_apply, torso_init
from d4pg_tpu.utils.profiling import phase


def _dtype(config: D4PGConfig):
    return jnp.bfloat16 if config.compute_dtype == "bfloat16" else jnp.float32


def build_networks(config: D4PGConfig) -> tuple[Actor, Critic]:
    pixel_shape = tuple(config.pixel_shape) if config.pixel_shape else None
    actor = Actor(
        action_dim=config.action_dim,
        hidden_sizes=tuple(config.hidden_sizes),
        dtype=_dtype(config),
        pixel_shape=pixel_shape,
        encoder_embed_dim=config.encoder_embed_dim,
    )
    critic = Critic(
        dist=config.dist,
        hidden_sizes=tuple(config.hidden_sizes),
        dtype=_dtype(config),
        pixel_shape=pixel_shape,
        encoder_embed_dim=config.encoder_embed_dim,
    )
    return actor, critic


def make_optimizers(config: D4PGConfig):
    adam = partial(optax.adam, b1=config.adam_b1, b2=config.adam_b2)
    return adam(config.lr_actor), adam(config.lr_critic)


def support_of(config: D4PGConfig) -> CategoricalSupport:
    return make_support(config.dist.v_min, config.dist.v_max, config.dist.num_atoms)


def _stacked_critics(config: D4PGConfig) -> int:
    """Leading critic-stack size: 2 (twin), E (ensemble), or 0 (single).

    Twin and ensemble are mutually exclusive — the ensemble subsumes the
    twin (E=2, M=2 is exactly clipped double-Q with a per-step subset
    redraw that happens to always pick both)."""
    if config.critic_ensemble:
        if config.twin_critic:
            raise ValueError(
                "critic_ensemble and twin_critic are mutually exclusive: "
                "an E=2, ensemble_min_targets=2 ensemble IS the twin"
            )
        if config.critic_ensemble < 2:
            raise ValueError(
                f"critic_ensemble must be >= 2 (got "
                f"{config.critic_ensemble}); 0 disables"
            )
        if not 1 <= config.ensemble_min_targets <= config.critic_ensemble:
            raise ValueError(
                f"ensemble_min_targets must be in [1, critic_ensemble="
                f"{config.critic_ensemble}], got {config.ensemble_min_targets}"
            )
        return config.critic_ensemble
    return 2 if config.twin_critic else 0


def _check_torso(config: D4PGConfig) -> None:
    """What a torso composes with today; the rest is refused by name."""
    if config.twin_critic or config.critic_ensemble:
        raise ValueError(
            "a torso is owned by ONE critic: --twin-critic / "
            "--critic-ensemble would stack it (not supported)")
    if config.pixel_shape:
        raise ValueError("a torso reads flat observation rows, not pixels")
    if config.dist.kind != "categorical" or config.projection_backend == "pallas_fused":
        raise ValueError(
            "a torso trains the categorical head on the xla or pallas "
            "projection (the fused tier's descent is not wired through it)")


# what a torso pass reports of its choices (models/torso.py): under an indexer
# all four, in the hybrid stack all but the keys (it chooses none)
CHOICES = ("keys", "experts", "load", "dropped")


def _encoders(config: D4PGConfig, batch, emit_choices: bool = False):
    """``(encode, head_of)``: how ``train_step`` turns critic parameters
    and a batch's observations into what the actor and critic networks
    read, ``encode(params, obs) -> (features, extras)``. The MLP/conv
    networks read the rows themselves (both are the identity: no op is added
    to their program). A torso is part of the critic's parameters and reads
    ``[B, T, O]`` windows under the batch's ``mask``; its routing counts are
    dropped here, and ``extras`` keeps what an indexer adds: its
    ``index_loss`` and, with ``emit_choices``, the pass's :data:`CHOICES`."""
    if config.torso is None:
        return (lambda params, obs: (obs, {})), (lambda params: params)

    def encode(params, obs):
        h, stats = torso_apply(config.torso, params["torso"], obs, batch["mask"],
                               emit_choices)
        keep = ("index_loss",) + (CHOICES if emit_choices else ())
        return h, {k: stats[k] for k in keep if k in stats}

    return encode, (lambda params: params["head"])


def acting_params(config: D4PGConfig, state: TrainState):
    """The parameters a policy needs to act: the actor's, and with a torso
    the critic's torso beside them (the actor is a head on its output)."""
    if config.torso is None:
        return state.actor_params
    return {"head": state.actor_params, "torso": state.critic_params["torso"]}


def create_train_state(config: D4PGConfig, key: jax.Array) -> TrainState:
    """Initialize params, hard-copy targets (reference ``ddpg.py:57-64,92-94``).

    With ``config.twin_critic`` the critic pytree carries a leading [2]
    axis (two independent inits); Adam moments and Polyak targets stack
    along with it, and :func:`train_step` vmaps the critic over it.
    ``config.critic_ensemble`` generalizes the same stacking to E
    independent inits (REDQ).
    """
    actor, critic = build_networks(config)
    k_actor, k_critic, k_state = jax.random.split(key, 3)
    # what actor and critic networks read: the rows, or a torso's output
    width = config.obs_dim if config.torso is None else config.torso.hidden_size
    obs = jnp.zeros((1, width))
    action = jnp.zeros((1, config.action_dim))
    actor_params = actor.init(k_actor, obs)
    n_stack = _stacked_critics(config)
    if config.torso is not None:
        _check_torso(config)
        k_torso, k_critic = jax.random.split(k_critic)
        critic_params = {
            "torso": torso_init(config.torso, k_torso, config.obs_dim),
            "head": critic.init(k_critic, obs, action),
        }
    elif n_stack:
        stack_keys = jax.random.split(k_critic, n_stack)
        critic_params = jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves),
            *[critic.init(k, obs, action) for k in stack_keys],
        )
    else:
        critic_params = critic.init(k_critic, obs, action)
    actor_opt, critic_opt = make_optimizers(config)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        actor_params=actor_params,
        critic_params=critic_params,
        target_actor_params=jax.tree_util.tree_map(jnp.copy, actor_params),
        target_critic_params=jax.tree_util.tree_map(jnp.copy, critic_params),
        actor_opt_state=actor_opt.init(actor_params),
        critic_opt_state=critic_opt.init(critic_params),
        key=k_state,
    )


def act(
    config: D4PGConfig,
    actor_params: Any,
    obs: jax.Array,
    key: jax.Array,
    noise_scale: jax.Array | float = 1.0,
) -> jax.Array:
    """Stateless exploration policy: tanh actor + scaled Gaussian noise,
    clipped to [−1, 1] (reference ``main.py:145-147``). jit/vmap-able.

    OU noise is stateful; use :func:`make_noise` + a stateful rollout policy
    for it (``config.noise_kind`` is honored there, not here).
    """
    actor, _ = build_networks(config)
    a = actor.apply(actor_params, obs)
    noise = gaussian_noise_sample(
        gaussian_noise_init(config.noise_epsilon),
        key,
        a.shape,
        sigma=config.noise_sigma,
    )
    return jnp.clip(a + noise_scale * noise, -1.0, 1.0)


def make_noise(config: D4PGConfig):
    """Noise process selected by ``config.noise_kind`` as an (init, sample,
    reset) triple of pure functions over an explicit state.

    The reference hardcodes Gaussian and parses-but-ignores the ``ou_*``
    flags (SURVEY.md quirk #13); here both are first-class:

      - ``init() -> state``
      - ``sample(state, key, shape) -> (noise, state)``
      - ``reset(state) -> state``  (per-episode; applies the ε-decay the
        reference defines but never triggers — quirk #10)
    """
    if config.noise_kind == "gaussian":
        base = gaussian_noise_init(config.noise_epsilon)

        def init():
            return base

        def sample(state, key, shape):
            return (
                gaussian_noise_sample(state, key, shape, sigma=config.noise_sigma),
                state,
            )

        def reset(state):
            return state  # ε-decay handled by the trainer's noise_scale schedule

    elif config.noise_kind == "ou":

        def init():
            return ou_noise_init(config.action_dim, epsilon=config.noise_epsilon)

        def sample(state, key, shape):
            x, state = ou_noise_sample(
                state,
                key,
                theta=config.ou_theta,
                mu=config.ou_mu,
                sigma=config.ou_sigma,
            )
            return jnp.broadcast_to(x, shape), state

        def reset(state):
            return ou_noise_reset(state, decay=0.0)

    else:
        raise ValueError(f"unknown noise kind: {config.noise_kind}")
    return init, sample, reset


def act_deterministic(config: D4PGConfig, actor_params: Any, obs: jax.Array) -> jax.Array:
    """Greedy policy for evaluation (reference ``main.py:122,324``)."""
    actor, _ = build_networks(config)
    return actor.apply(actor_params, obs)


def act_on_window(config: D4PGConfig, params: Any, window: jax.Array,
                  valid: jax.Array) -> jax.Array:
    """Greedy action of a torso policy on ``[B, T, O]`` windows of each
    stream's last observations (``valid [B, T]``: False before the episode's
    start); ``params`` are :func:`acting_params`."""
    actor, _ = build_networks(config)
    h, _ = torso_apply(config.torso, params["torso"], window, valid)
    return actor.apply(params["head"], h)


def push_observation(window, count, obs):
    """What a torso policy carries of one stream: the ``[T, O]`` window with
    ``obs`` as its newest row, the new count of rows that belong to the
    running episode, and which rows are valid (the newest ``count``)."""
    t = window.shape[0]
    window = jnp.concatenate([window[1:], obs[None]], axis=0)
    count = jnp.minimum(count + 1, t)
    return window, count, jnp.arange(t) >= t - count


def noisy_explore(config: D4PGConfig, noise_sample, a, key, nstate, scale):
    """Shared collection-action builder used by EVERY collection path
    (host/pool/HER closures in runtime/trainer.py and the segment collector
    in runtime/collect.py): additive noise + clip, then the ε-uniform
    mixture. Key discipline: the mixture key is split off ONLY when
    random_eps > 0, so eps=0 configs keep the exact pre-round-5 noise
    stream — seed-for-seed reproducibility against recorded baselines."""
    if config.random_eps:
        key, ke = jax.random.split(key)
    n, nstate = noise_sample(nstate, key, a.shape)
    a = jnp.clip(a + scale * n, -1.0, 1.0)
    if config.random_eps:
        a = exploration_mixture(config, ke, a)
    return a, nstate


def exploration_mixture(config: D4PGConfig, key: jax.Array, a: jax.Array) -> jax.Array:
    """ε-uniform action mixture for collection (HER-DDPG, Andrychowicz et
    al. 2017 §4.4): with probability ``config.random_eps`` the WHOLE action
    vector is replaced by a uniform draw from the box. Complements Gaussian
    noise, which cannot escape a saturated tanh corner (clip pins most of
    its mass there). Identity when random_eps == 0 (every non-goal config).
    Broadcasting: ``a`` is [..., act_dim]; one Bernoulli per action vector."""
    if not config.random_eps:
        return a
    ku, kb = jax.random.split(key)
    u = jax.random.uniform(ku, a.shape, minval=-1.0, maxval=1.0)
    take = jax.random.bernoulli(kb, config.random_eps, a.shape[:-1] + (1,))
    return jnp.where(take, u, a)


def _critic_value(config: D4PGConfig, support, head: jax.Array) -> jax.Array:
    """E[Z] under whichever head the critic is configured with."""
    kind = config.dist.kind
    if kind == "categorical":
        return expected_value(support, jax.nn.softmax(head, axis=-1))
    if kind == "scalar":
        return head[..., 0]
    if kind == "mixture_gaussian":
        return mixture_gaussian_mean(head, config.dist.num_mixtures)
    raise ValueError(kind)


def _step_metrics(
    config: D4PGConfig, critic_loss, actor_loss, priorities, batch_q_mean
) -> dict:
    """A grad step's logged scalars, before any cross-shard mean."""
    n_stack = _stacked_critics(config)
    step_metrics = {
        # Per-critic scale: the stacked loss SUMS its members (right for
        # the gradient), but the logged metric must stay comparable to
        # single-critic runs.
        "critic_loss": critic_loss / n_stack if n_stack else critic_loss,
        "actor_loss": actor_loss,
        "priority_mean": jnp.mean(priorities),
        # From the loss aux, NOT -actor_loss: with action_l2 the loss
        # carries the penalty term and would understate E[Q].
        "q_mean": batch_q_mean,
    }
    if config.dist.kind == "categorical":
        # Support-saturation monitor: fraction of the categorical support
        # [v_min, v_max] the mean Q occupies. The Humanoid v1500 study
        # (runs/humanoid_ondevice_v1500) found q_mean pinned at v_max
        # costing ~15% of final return — and nothing in the curves showed
        # it. Values creeping toward 1.0 mean the support is clipping the
        # value distribution; widen v_max. Categorical head only: the
        # scalar and MoG heads are unbounded, so the ratio would be an
        # alarm with no referent there.
        step_metrics["q_support_frac"] = (batch_q_mean - config.dist.v_min) / (
            config.dist.v_max - config.dist.v_min
        )
    return step_metrics


def synced_trees(config: D4PGConfig, state: TrainState) -> tuple:
    """The two trees a grad step hands to its cross-shard sync, as far as
    their shapes go: the critic's gradients, then the actor's with the step
    metrics (``parallel.dp.describe_sync`` reads them; ``Trainer`` logs the
    result once under ``--dp``)."""
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    metrics = jax.eval_shape(
        partial(_step_metrics, config), scalar, scalar,
        jax.ShapeDtypeStruct((1,), jnp.float32), scalar,
    )
    return state.critic_params, (state.actor_params, metrics)


def train_step(
    config: D4PGConfig,
    state: TrainState,
    batch: Mapping[str, jax.Array],
    axis_name: str | None = None,
    sync_fn=None,
    descent=None,
    emit_choices: bool = False,
):
    """One full D4PG SGD step (the reference §3.2 hot loop, fused).

    Args:
      config: static hyperparameters (close over it or mark static in jit).
      state: complete learner state.
      batch: obs [B,O], action [B,A], reward [B], next_obs [B,O],
        discount [B] (= γ^m·(1−terminal), from the n-step writer), and
        optionally weights [B] (PER importance weights; absent → ones).
      axis_name: when running under ``shard_map`` over a device mesh, the
        mesh axis to ``pmean`` gradients/metrics over. This single hook is
        the synchronous-DP replacement for the reference's entire
        shared-memory gradient scheme (``ddpg.py:104-108``,
        ``shared_adam.py``): each device computes grads on its batch shard,
        one AllReduce over ICI averages them, every replica applies the same
        Adam update. ``None`` → single-device semantics.
      sync_fn: overrides the cross-shard combine entirely (a ``tree ->
        tree`` callable). The sharded megastep passes the DETERMINISTIC
        mean (``parallel.dp.det_pmean``: the tree packed into one buffer,
        shards exchanged, summed in fixed order, gathered back),
        whose bits a single-device vmap oracle can replay exactly —
        ``pmean``'s backend AllReduce cannot be (its accumulation order is
        the backend's choice). ``None`` keeps the pmean/axis_name path.
      descent: ``(sums_lane [2L], next_prefixes [B])`` — the fused-tier
        pipelining seam (ISSUE 16, ``ops/pallas_fused_step.py``): the
        step's fused-loss Pallas program ALSO descends the device-PER
        segment tree for the NEXT scan step's stratified prefixes, so the
        megastep's steady state runs one program per step instead of a
        separate descent program per dispatch. Requires the categorical
        head with ``projection_backend="pallas_fused"`` (raises
        otherwise). When set, the return grows a fourth element:
        ``next_idx [B] int32`` (unclamped-to-fill leaf indices; the
        megastep body applies ``lane_draw``'s fill clamp). Under stacked
        critics every member computes the identical descent; member 0's
        is returned.
      emit_choices: static, and only the benchmark's comparison sets it
        (the megastep never does): with a torso whose attention runs under
        an indexer, the same step returns a fourth element, what its two
        torso passes chose — ``{"keys": [2, L, B, T, T] bool, "experts":
        [2, L, B·T, k] int32, "load": [2, L, held], "dropped": [2, L]}``,
        the critic's pass on s first, the target's on s′ second — read from
        inside the step (a separate forward is another fusion and may round
        a near-tie the other way).

    Returns:
      (new_state, metrics, priorities[B] — local shard under shard_map),
      plus ``next_idx [B]`` when ``descent`` is given.
    """
    if descent is not None and not (
        config.dist.kind == "categorical"
        and config.projection_backend == "pallas_fused"
    ):
        raise ValueError(
            "descent= (the fused descent-in-scan tier) requires the "
            "categorical head with projection_backend='pallas_fused' "
            f"(got kind={config.dist.kind!r}, "
            f"backend={config.projection_backend!r})"
        )

    def _sync(tree):
        with phase("parallel.sync"):
            if sync_fn is not None:
                return sync_fn(tree)
            if axis_name is None:
                return tree
            return jax.lax.pmean(tree, axis_name)

    actor, critic = build_networks(config)
    actor_opt, critic_opt = make_optimizers(config)
    support = support_of(config)
    encode, head_of = _encoders(config, batch, emit_choices)

    # ---- bf16 hot-path dtype policy ----
    # Master weights, Adam moments, Polyak targets and every loss reduction
    # stay float32 (the nets cast their head back to f32, so losses/metrics
    # accumulate in f32 regardless of compute dtype). Under bfloat16 the
    # TARGET networks — forward-only, never differentiated — are cast to
    # bf16 ONCE here, so all target-path matmuls read 2-byte params from
    # HBM instead of converting f32 reads per layer; the flax modules see
    # params already in their compute dtype and skip the promotion. The
    # ONLINE params are left f32 and cast per-op inside the loss closures:
    # value_and_grad must differentiate w.r.t. the f32 masters.
    tgt_actor_params = state.target_actor_params
    tgt_critic_params = state.target_critic_params
    if _dtype(config) == jnp.bfloat16:
        def _to_bf16(tree):
            return jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16)
                if x.dtype == jnp.float32
                else x,
                tree,
            )

        with phase("agent.networks"):
            tgt_actor_params = _to_bf16(tgt_actor_params)
            tgt_critic_params = _to_bf16(tgt_critic_params)
    weights = batch.get("weights")
    if weights is None:
        weights = jnp.ones_like(batch["reward"])

    # DrQ random shift on pixel batches (ops/augment.py): the one
    # regularizer that makes Q-learning from images train at all. Keys come
    # from the TrainState's PRNG slot, so the scan/jit stays pure and every
    # step shifts differently.
    new_key = state.key
    if config.pixel_shape and config.augment_pad > 0:
        from d4pg_tpu.ops.augment import random_shift

        k_obs, k_next, new_key = jax.random.split(state.key, 3)
        shape = tuple(config.pixel_shape)
        batch = dict(batch)
        batch["obs"] = random_shift(
            batch["obs"], k_obs, shape, config.augment_pad
        )
        batch["next_obs"] = random_shift(
            batch["next_obs"], k_next, shape, config.augment_pad
        )

    # ---- target: y = Φ(r + γ_eff · Z_target(s', μ_target(s'))) ----
    with phase("agent.networks"), phase("agent.networks.target"):
        next_feat, target_extras = encode(tgt_critic_params, batch["next_obs"])
        next_action = actor.apply(tgt_actor_params, next_feat)
        if config.critic_ensemble:
            # REDQ in-target minimization, distributionally: back up whichever
            # member of a per-step RANDOM SUBSET of M target critics has the
            # smallest expected value, per sample — the whole distribution of
            # the argmin member, same rationale as the twin branch below
            # (an elementwise min of probs would not be a distribution).
            E = config.critic_ensemble
            M = config.ensemble_min_targets
            heads = jax.vmap(
                lambda p: critic.apply(p, next_feat, next_action)
            )(tgt_critic_params)                                    # [E, B, H]
            vals = jax.vmap(lambda h: _critic_value(config, support, h))(heads)
            k_subset, new_key = jax.random.split(new_key)
            subset = jax.random.permutation(k_subset, E)[:M]        # [M]
            sub_vals = vals[subset]                                 # [M, B]
            sub_heads = heads[subset]                               # [M, B, H]
            which = jnp.argmin(sub_vals, axis=0)                    # [B]
            target_head = jnp.take_along_axis(
                sub_heads, which[None, :, None], axis=0
            )[0]                                                    # [B, H]
        elif config.twin_critic:
            # Clipped double-Q, distributionally: back up whichever target
            # critic's WHOLE distribution has the smaller mean, per sample —
            # the distributional analogue of TD3's min(Q1, Q2) (taking an
            # elementwise min of probs would not be a distribution).
            heads = jax.vmap(
                lambda p: critic.apply(p, next_feat, next_action)
            )(tgt_critic_params)
            vals = jax.vmap(lambda h: _critic_value(config, support, h))(heads)
            target_head = jnp.where(
                (vals[0] <= vals[1])[..., None], heads[0], heads[1]
            )
        else:
            target_head = critic.apply(
                head_of(tgt_critic_params), next_feat, next_action
            )

    if config.dist.kind == "categorical":
        # Atom-layout audit: every per-atom op below (softmax, projection,
        # CE, E[Z]) reduces/broadcasts over the LAST axis of a [B, A]
        # tensor — atoms live in the 128-lane dimension, so the critic-head
        # "gathers" are contiguous lane reads, never a strided HBM walk.
        # Keep it that way: any new head-side op must put atoms last.
        with phase("ops.projection_loss"):
            target_probs = jax.nn.softmax(target_head, axis=-1)
        if config.projection_backend == "pallas_fused":
            # Projection + log-softmax CE + IS/priority signals in ONE
            # Pallas kernel: the projected target distribution is never
            # materialized in HBM (fwd or bwd — the VJP recomputes Φ in
            # VMEM). The XLA branch below stays the reference oracle.
            from d4pg_tpu.ops.pallas_mode import pallas_interpret
            from d4pg_tpu.ops.pallas_projection import fused_categorical_loss

            fused_target_probs = jax.lax.stop_gradient(target_probs)
            interpret = pallas_interpret()

            def critic_loss_fn(critic_params):
                pred = critic.apply(critic_params, batch["obs"], batch["action"])
                with phase("ops.projection_loss"):
                    if descent is not None:
                        from d4pg_tpu.ops.pallas_fused_step import (
                            fused_categorical_loss_descent,
                        )

                        sums_lane, next_prefixes = descent
                        ce, overlap, next_idx = fused_categorical_loss_descent(
                            support,
                            pred,
                            fused_target_probs,
                            batch["reward"],
                            batch["discount"],
                            next_prefixes,
                            sums_lane,
                            interpret,
                        )
                    else:
                        next_idx = None
                        ce, overlap = fused_categorical_loss(
                            support,
                            pred,
                            fused_target_probs,
                            batch["reward"],
                            batch["discount"],
                            interpret,
                        )
                    # f32 weighted reduction on [B] vectors — byte-trivial.
                    loss = jnp.mean(weights * ce)
                per_sample = (
                    overlap if config.priority_kind == "overlap" else ce
                )
                if descent is not None:
                    return loss, (per_sample, next_idx)
                return loss, per_sample

        elif config.projection_backend == "pallas":
            from d4pg_tpu.ops.pallas_mode import pallas_interpret
            from d4pg_tpu.ops.pallas_projection import categorical_projection_pallas

            with phase("ops.projection_loss"):
                proj = categorical_projection_pallas(
                    support,
                    target_probs,
                    batch["reward"],
                    batch["discount"],
                    pallas_interpret(),
                )
        else:
            with phase("ops.projection_loss"):
                proj = categorical_projection(
                    support, target_probs, batch["reward"], batch["discount"]
                )
        if config.projection_backend != "pallas_fused":
            proj = jax.lax.stop_gradient(proj)

            def critic_loss_fn(critic_params):
                feat, extras = encode(critic_params, batch["obs"])
                pred = critic.apply(head_of(critic_params), feat, batch["action"])
                with phase("ops.projection_loss"):
                    loss, per_sample_ce = categorical_td_loss(pred, proj, weights)
                    if config.priority_kind == "overlap":
                        # Reference-compatible surrogate |−Σ m·p|
                        # (ddpg.py:220-222).
                        per_sample = jnp.abs(
                            -jnp.sum(
                                proj * jax.nn.softmax(pred, axis=-1), axis=-1
                            )
                        )
                    else:
                        per_sample = per_sample_ce
                if "index_loss" in extras:
                    # the indexer's alignment loss, weight 1: its gradient
                    # reaches the indexer's leaves alone, and theirs is it
                    loss = loss + extras["index_loss"]
                if config.torso is not None:    # the actor reads this pass's features
                    return loss, (per_sample, jax.lax.stop_gradient(feat), extras)
                return loss, per_sample
    elif config.dist.kind == "scalar":
        # Plain DDPG TD(0)/TD(n) target (BASELINE.json config 1).
        with phase("ops.projection_loss"):
            y = batch["reward"] + batch["discount"] * target_head[..., 0]
            y = jax.lax.stop_gradient(y)

        def critic_loss_fn(critic_params):
            pred = critic.apply(critic_params, batch["obs"], batch["action"])[..., 0]
            with phase("ops.projection_loss"):
                td = pred - y
                loss = jnp.mean(weights * jnp.square(td))
                return loss, jnp.abs(td)
    elif config.dist.kind == "mixture_gaussian":
        # TRUE distributional MoG Bellman backup (the D4PG paper's
        # alternative head; reference declares but never implements it,
        # ddpg.py:48-50). The target DISTRIBUTION is the affine transform
        # T Z' = r + γ_eff·Z' of the target-critic mixture — each component
        # N(m_j, s_j) maps to N(r + d·m_j, d·s_j) — and the loss is the
        # cross-entropy H(T Z', Z_online), evaluated per target component
        # with Gauss–Hermite quadrature (deterministic, differentiable, no
        # PRNG; M components × Q nodes of log-density evaluations vectorize
        # to one fused elementwise block on the MXU path). Terminal
        # transitions (d=0) collapse every component onto the point mass at
        # r; the std floor keeps the quadrature nodes finite there.
        from d4pg_tpu.ops.mog import mog_bellman_targets, mog_cross_entropy

        M = config.dist.num_mixtures
        with phase("ops.projection_loss"):
            y_nodes, node_w = mog_bellman_targets(
                target_head, batch["reward"], batch["discount"], M,
                config.dist.quadrature_points,
            )
            # Scalar TD magnitude for PER priorities (the CE of a
            # continuous density can be negative, which scrambles
            # |·|-based rankings).
            y_mean = batch["reward"] + batch["discount"] * _critic_value(
                config, support, target_head
            )
            y_mean = jax.lax.stop_gradient(y_mean)

        def critic_loss_fn(critic_params):
            head = critic.apply(critic_params, batch["obs"], batch["action"])
            with phase("ops.projection_loss"):
                ce = mog_cross_entropy(head, y_nodes, node_w, M)
                td = jnp.abs(y_mean - mixture_gaussian_mean(head, M))
                return jnp.mean(weights * ce), td
    else:
        raise ValueError(config.dist.kind)

    if config.twin_critic or config.critic_ensemble:
        # Every stacked critic (twin pair or E-wide ensemble) regresses
        # the same min target; one vmap over the stacked params turns the
        # single-critic loss into all of them. PER priority = mean of the
        # stack's TD magnitudes (less noisy than any one member).
        _single_loss_fn = critic_loss_fn

        def critic_loss_fn(stacked_params):
            losses, per_sample = jax.vmap(_single_loss_fn)(stacked_params)
            if descent is not None:
                # Every member ran the identical descent (same tree,
                # same prefixes, exact int32) — member 0 IS the result.
                per_sample, next_idx = per_sample
                return jnp.sum(losses), (
                    jnp.mean(per_sample, axis=0), next_idx[0]
                )
            return jnp.sum(losses), jnp.mean(per_sample, axis=0)

    with phase("agent.networks"):
        (critic_loss, loss_aux), critic_grads = jax.value_and_grad(
            critic_loss_fn, has_aux=True
        )(state.critic_params)
    # What the actor reads: the rows themselves, or a torso's output on them
    # as the critic's loss pass just computed it (the torso BEFORE this
    # step's update, under stop_gradient; the head it ascends is updated).
    feat = batch["obs"]
    if descent is not None:
        priorities, descent_idx = loss_aux
    elif config.torso is not None:
        priorities, feat, extras = loss_aux
    else:
        priorities = loss_aux
    critic_grads = _sync(critic_grads)
    with phase("agent.optimizer"):
        critic_updates, critic_opt_state = critic_opt.update(
            critic_grads, state.critic_opt_state
        )
        critic_params = optax.apply_updates(
            state.critic_params, critic_updates
        )

    # ---- actor: maximize E[Q(s, μ(s))] against the UPDATED critic ----
    # (critic 0 under twin critics — TD3 convention; the ensemble-MEAN
    # value under REDQ — averaging E critics' gradients is what lets the
    # aggressive min-subset target stay trainable)
    actor_critic_params = (
        jax.tree_util.tree_map(lambda x: x[0], critic_params)
        if config.twin_critic
        else critic_params
    )

    def actor_loss_fn(actor_params):
        a = actor.apply(actor_params, feat)
        if config.critic_ensemble:
            heads = jax.vmap(
                lambda p: critic.apply(p, feat, a)
            )(critic_params)                                    # [E, B, H]
            q = jax.vmap(lambda h: _critic_value(config, support, h))(heads)
            q_mean = jnp.mean(q)          # mean over members AND batch
        else:
            head = critic.apply(head_of(actor_critic_params), feat, a)
            q_mean = jnp.mean(_critic_value(config, support, head))
        loss = -q_mean
        if config.action_l2:
            # HER-DDPG action regularizer (Andrychowicz et al. 2017, §4.4:
            # the "square of the preactivations" penalty): counters the
            # tanh-corner collapse sparse goal tasks induce — the critic's
            # dQ/da rarely flips sign early, so unregularized ascent
            # saturates the actor (observed on FetchReach round 5: constant
            # [-1,1,-1,-1] policy fleeing the goal). Penalizing post-tanh
            # squares is equivalent in effect near the corners.
            loss = loss + config.action_l2 * jnp.mean(jnp.square(a))
        # aux carries the UNpenalized E[Q]: q_mean / q_support_frac metrics
        # must stay comparable across action_l2 settings.
        return loss, q_mean

    with phase("agent.networks"):
        (actor_loss, batch_q_mean), actor_grads = jax.value_and_grad(
            actor_loss_fn, has_aux=True
        )(state.actor_params)
    step_metrics = _step_metrics(
        config, critic_loss, actor_loss, priorities, batch_q_mean
    )
    if config.torso is not None and "index_loss" in extras:
        step_metrics["index_loss"] = extras["index_loss"]    # part of critic_loss
    # One sync for both: every step metric is known when the actor's
    # gradients are, so they ride in the same buffer (the values are what a
    # sync of their own gave).
    actor_grads, metrics = _sync((actor_grads, step_metrics))
    with phase("agent.optimizer"):
        actor_updates, actor_opt_state = actor_opt.update(
            actor_grads, state.actor_opt_state
        )
        actor_params = optax.apply_updates(state.actor_params, actor_updates)

        # ---- Polyak target updates (reference ddpg.py:250 → 110-116) ----
        target_actor_params = polyak_update(
            state.target_actor_params, actor_params, config.tau
        )
        target_critic_params = polyak_update(
            state.target_critic_params, critic_params, config.tau
        )
    new_state = state.replace(
        step=state.step + 1,
        key=new_key,
        actor_params=actor_params,
        critic_params=critic_params,
        target_actor_params=target_actor_params,
        target_critic_params=target_critic_params,
        actor_opt_state=actor_opt_state,
        critic_opt_state=critic_opt_state,
    )
    if descent is not None:
        return new_state, metrics, priorities, descent_idx
    if emit_choices:
        return new_state, metrics, priorities, {
            k: jnp.stack([extras[k], target_extras[k]]) for k in CHOICES if k in extras}
    return new_state, metrics, priorities


def jit_train_step(config: D4PGConfig, donate: bool = True):
    """The train step specialized + jitted for a fixed config, with the state
    buffer donated so params/moments update in place on device."""
    fn = partial(train_step, config)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def gather_batches(store, idx: jax.Array, torso=None) -> dict:
    """Bulk-gather [K, B] batches from a columnar store (device replay or
    pool) in ONE op per field. Doing this before the train scan instead of
    per-step inside it measured ~2.2x on v5e (per-step RBG PRNG + scattered
    HBM reads dominate otherwise). With a ``torso`` (``D4PGConfig.torso``)
    the observations are windows: :func:`gather_windows`."""
    from d4pg_tpu.replay.device_ring import ROW_FIELDS, DeviceRing

    if torso is not None:
        return gather_windows(store, idx, torso.window, torso.row_stride, torso.span)
    with phase("replay.row_gather"):
        def rows(k):
            if isinstance(store, DeviceRing):  # wide fields are stored packed
                return store.rows(k, idx)
            return (store[k] if isinstance(store, dict) else getattr(store, k))[idx]

        batches = {k: rows(k) for k in ROW_FIELDS}
        batches["weights"] = jnp.ones(idx.shape, jnp.float32)
    return batches


def gather_windows(store, idx: jax.Array, window: int, stride: int,
                   span: str = "episode") -> dict:
    """[K, B] batches whose observations are the WINDOW of the ``window``
    ring rows that end at each drawn slot: ``obs`` / ``next_obs`` ``[K, B,
    T, O]`` (rows ``idx − (T−1−j)·stride``, ``stride`` = the writer's env
    interleave), ``mask [K, B, T]``, the other fields of the drawn row.

    A position is masked when its row lies before the ring's first row
    (``DeviceRing`` carries its fill, which is also the write cursor of a
    ring that has not wrapped; a drawn slot is below it, so only the rows
    *before* row 0 can be beyond it — they are never wrapped around to), or
    when a row between it and the window's end, the end excluded, has
    ``discount == 0``: an episode ended there, the position belongs to the
    episode before. The last position is always valid. With ``span ==
    "stream"`` an episode's end cuts nothing: the window is the stream's
    last ``window`` rows, whatever episodes they belong to, and only the
    rows before the ring's first are masked."""
    from d4pg_tpu.replay.device_ring import ROW_FIELDS

    with phase("replay.row_gather"):
        back = (jnp.arange(window, dtype=idx.dtype) - (window - 1)) * stride
        pos = idx[..., None] + back                              # [K, B, T]
        inside = pos >= 0
        pos = jnp.maximum(pos, 0)
        batches = {k: store.rows(k, pos if k in ("obs", "next_obs") else idx)
                   for k in ROW_FIELDS}
        if span == "stream":
            batches["mask"] = inside
        else:
            ended = (store.rows("discount", pos) == 0.0) & inside
            ended = ended.at[..., -1].set(False)
            later_end = jnp.flip(jnp.cumsum(jnp.flip(ended, -1), -1), -1) > 0
            batches["mask"] = inside & ~later_end
        batches["weights"] = jnp.ones(idx.shape, jnp.float32)
    return batches


def fused_train_scan(
    config: D4PGConfig,
    state: TrainState,
    batches: dict,
    axis_name: str | None = None,
    sync_fn=None,
):
    """Scan ``train_step`` over pre-gathered [K, B] batches — the shared
    inner loop of the on-device trainer, the benchmark, and the host
    trainer's ``steps_per_dispatch`` mode (one dispatch per K grad steps
    amortizes per-call overhead).
    ``axis_name``/``sync_fn`` thread through to each step's gradient
    combine (DP under shard_map; the sharded megastep's deterministic
    mean). Returns (state, metrics pytree with leading K axis,
    priorities [K, B])."""

    def body(st, batch):
        st, metrics, priorities = train_step(
            config, st, batch, axis_name=axis_name, sync_fn=sync_fn
        )
        return st, (metrics, priorities)

    state, (metrics, priorities) = jax.lax.scan(body, state, batches)
    return state, metrics, priorities
