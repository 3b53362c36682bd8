"""Train state and algorithm configuration.

The reference scatters algorithm state across a ``DDPG`` object, two local
Adams, two ``SharedAdam``s, a shared counter tensor, and three global RNGs
(``ddpg.py:18-89``, ``main.py:382-386``). Here ALL mutable training state is
one immutable pytree — params, targets, optimizer moments, step counter, PRNG
key — so it jits, shards, donates, and checkpoints as a unit (SURVEY.md §5
'checkpoint/resume' and 'distributed comm backend').
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
from flax import struct

from d4pg_tpu.models.critic import DistConfig
from d4pg_tpu.models.torso import TorsoConfig


@dataclass(frozen=True)
class D4PGConfig:
    """Static algorithm hyperparameters.

    Covers every in-code default the reference hides (SURVEY.md §5 'config'):
    lrs (``ddpg.py:19``), tau (``main.py:40``), gamma, n-step, PER α/β/ε
    (``ddpg.py:81-87``), Adam betas (``shared_adam.py:4``), noise scale
    (``random_process.py:13``), support (``main.py:373-376``).
    """

    obs_dim: int = 3
    action_dim: int = 1
    hidden_sizes: tuple = (256, 256, 256)
    # Pixel observations (BASELINE.json config 4): when set to (H, W, C),
    # obs arrive flattened with obs_dim == H·W·C and both networks conv-encode
    # them (d4pg_tpu/models/encoders.py) in front of the MLP trunk.
    pixel_shape: tuple | None = None
    encoder_embed_dim: int = 50
    # DrQ random-shift augmentation of pixel batches inside the train step
    # (ops/augment.py). Effectively required: without it the conv critic
    # overfits and pixel tasks sit at random-policy return indefinitely
    # (measured on pixel_pendulum). 0 disables.
    augment_pad: int = 4
    dist: DistConfig = field(default_factory=DistConfig)
    gamma: float = 0.99
    n_step: int = 1
    tau: float = 0.001
    lr_actor: float = 1e-4
    lr_critic: float = 1e-4
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    noise_kind: str = "gaussian"  # "gaussian" | "ou"
    noise_epsilon: float = 0.3
    noise_sigma: float = 1.0
    ou_theta: float = 0.15
    ou_sigma: float = 0.2
    ou_mu: float = 0.0
    # exploration-scale annealing over env steps (0 = constant, the
    # reference's effective behavior — its ε-decay never fires, quirk #10)
    noise_decay_steps: int = 0
    noise_scale_final: float = 0.1
    # HER-DDPG additions (Andrychowicz et al. 2017, §4.4) — both default
    # OFF so every non-goal config is byte-identical to before:
    # with probability random_eps a collection action is replaced by a
    # uniform draw from the action box (the anti-corner-collapse mixture),
    # and action_l2 penalizes mean(a^2) in the actor loss.
    random_eps: float = 0.0
    action_l2: float = 0.0
    # PER
    prioritized: bool = True
    per_alpha: float = 0.6
    per_beta0: float = 0.4
    per_beta_steps: int = 100_000
    per_eps: float = 1e-6
    # priority signal: "ce" (true distributional TD) or "overlap"
    # (reference-compatible surrogate, ddpg.py:220-222)
    priority_kind: str = "ce"
    # compute dtype for network matmuls ("float32" | "bfloat16"). The
    # bf16 policy is: fp32 master weights / Adam moments / Polyak targets
    # and fp32 loss accumulation always; bf16 activations through the
    # actor/critic trunks; target-network params cast to bf16 once per
    # train step (forward-only — halves target-path param bytes; what
    # that buys on the chip is not measured, PERF.md §7).
    compute_dtype: str = "float32"
    # categorical projection implementation, an oracle ladder:
    #   "xla"          — one-hot matmul reference (ops/categorical.py);
    #   "pallas"       — hand-written projection kernel, XLA loss
    #                    (d4pg_tpu/ops/pallas_projection.py);
    #   "pallas_fused" — ONE kernel for projection + log-softmax CE +
    #                    priority signals; the projected distribution never
    #                    touches HBM (fwd or bwd). Each rung is validated
    #                    against the one above it in tests.
    projection_backend: str = "xla"
    # Twin critics with a clipped-min target (TD3's fix for the DDPG-family
    # overestimation spiral, applied distributionally: the Bellman backup
    # uses whichever target critic's distribution has the SMALLER expected
    # value, per sample). Beyond-reference capability: measured necessary
    # for Hopper/Walker2d-class tasks, where single-critic D4PG plateaus at
    # ~2000 while the true policy ceiling is ~3000+ (runs/hopper_ondevice_*
    # hyperparameter study, round 3). Critic params/targets/opt-state gain
    # a leading [2] axis; the actor trains against critic 0 (TD3
    # convention); PER priorities average the two critics' TD magnitudes.
    twin_critic: bool = False
    # REDQ-style critic ensemble (Chen et al. 2021), the capacity arc the
    # sharded learner unlocks (ROADMAP item 2): E independent critics
    # stacked on a leading [E] axis (params/targets/opt-state — the twin
    # stack generalized), each Bellman target taking the min over a RANDOM
    # SUBSET of ``ensemble_min_targets`` target critics (redrawn per grad
    # step from the TrainState key), the actor ascending the ensemble-MEAN
    # value. 0 disables (the single/twin paths are byte-unchanged); E >= 2
    # enables and is mutually exclusive with twin_critic (the ensemble
    # subsumes it). The stack axis is a first-class mesh-shardable dim in
    # the partition rules (parallel/partition.py:stack_axes_for), so wide
    # ensembles shard members across the mesh instead of replicating E×
    # the params.
    critic_ensemble: int = 0
    # Size M of the random target subset (REDQ's in-target minimization):
    # min over M of E controls the under/overestimation trade — M=2 is
    # the paper's setting; M=E recovers "min over all".
    ensemble_min_targets: int = 2
    # A sequence torso over a window of the last ``torso.window`` ring rows
    # (models/torso.py). The critic owns it (``critic_params = {"torso",
    # "head"}``; its target copy is the target critic's), actor and critic
    # MLPs become heads on its output, the actor reads it under
    # stop_gradient. None = the MLP/conv networks, byte-unchanged.
    torso: TorsoConfig | None = None


class TrainState(struct.PyTreeNode):
    """The complete learner state as a single pytree."""

    step: jax.Array
    actor_params: Any
    critic_params: Any
    target_actor_params: Any
    target_critic_params: Any
    actor_opt_state: Any
    critic_opt_state: Any
    key: jax.Array
