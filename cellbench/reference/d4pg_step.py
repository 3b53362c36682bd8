"""Plain reference: one D4PG grad step in straight ``jax.numpy``, float32,
every matrix multiplication at ``highest`` precision.

Written from the equations of D4PG (Barth-Maron et al. 2018, Algorithm 1)
and C51 (Bellemare et al. 2017, Algorithm 1), not from ``agent/d4pg.py``:

  target   Y   = Φ( r + γ_eff · z ),  Z'(s', π'(s')) on atoms z
  critic   L_c = mean_b  w_b · H( Y_b , Z(s_b, a_b) )        (cross-entropy)
  priority p_b = H( Y_b , Z(s_b, a_b) )
  actor    L_a = − mean_b  E[ Z(s_b, π(s_b)) ]
  Adam on both, then θ' ← (1−τ)θ' + τθ.

Φ is the categorical projection written as its closed form, the hat
function: mass p_i at clip(Tz_i) goes to atom j with weight
max(0, 1 − |clip(Tz_i) − z_j| / Δ).

Departures from the paper, which are the program's and which the reference
follows so that the two can be compared:
- γ_eff = γ^m·(1−terminal) comes per row from the n-step writer;
- the actor's gradient is taken through the critic *after* its update
  (the paper computes both gradients from the same parameters);
- the critic emits logits, the loss uses log-softmax;
- networks: ReLU MLPs, tanh on the actor's output, the action joins the
  critic at its second layer.

A parameter set is a list of ``(W [in, out], b [out])``; an Adam state is
``{"count", "m", "v"}`` with ``m``/``v`` shaped like the parameters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ADAM_EPS = 1e-8


def actor_forward(params, obs):
    x = obs
    for w, b in params[:-1]:
        x = jax.nn.relu(x @ w + b)
    w, b = params[-1]
    return jnp.tanh(x @ w + b)


def critic_forward(params, obs, action):
    (w0, b0), rest = params[0], params[1:]
    x = jax.nn.relu(obs @ w0 + b0)
    x = jnp.concatenate([x, action], axis=-1)
    for w, b in rest[:-1]:
        x = jax.nn.relu(x @ w + b)
    w, b = rest[-1]
    return x @ w + b


def project(probs, reward, discount, v_min, v_max, atoms):
    z = jnp.linspace(v_min, v_max, atoms)
    delta = (v_max - v_min) / (atoms - 1)
    tz = jnp.clip(reward[:, None] + discount[:, None] * z[None, :], v_min, v_max)
    hat = jnp.clip(1.0 - jnp.abs(tz[:, :, None] - z[None, None, :]) / delta, 0.0, 1.0)
    return jnp.sum(probs[:, :, None] * hat, axis=1)


def adam(params, grads, state, lr, b1, b2):
    count = state["count"] + 1
    t = count.astype(jnp.float32)
    upd = lambda f, *trees: jax.tree_util.tree_map(f, *trees)  # noqa: E731
    m = upd(lambda m_, g: b1 * m_ + (1.0 - b1) * g, state["m"], grads)
    v = upd(lambda v_, g: b2 * v_ + (1.0 - b2) * g * g, state["v"], grads)
    step = upd(
        lambda m_, v_: lr * (m_ / (1.0 - b1 ** t))
        / (jnp.sqrt(v_ / (1.0 - b2 ** t)) + ADAM_EPS),
        m, v,
    )
    new = upd(lambda p, s: p - s, params, step)
    return new, {"count": count, "m": m, "v": v}


def step(state, batch, hp):
    """``state``: dict of actor, critic, target_actor, target_critic,
    actor_adam, critic_adam. ``hp``: v_min, v_max, atoms, lr_actor,
    lr_critic, b1, b2, tau. Returns ``(new_state, out)`` with ``out`` the
    critic loss, actor loss and ``[B]`` priorities."""
    with jax.default_matmul_precision("highest"):
        z = jnp.linspace(hp["v_min"], hp["v_max"], hp["atoms"])
        next_action = actor_forward(state["target_actor"], batch["next_obs"])
        next_probs = jax.nn.softmax(
            critic_forward(state["target_critic"], batch["next_obs"], next_action)
        )
        target = project(
            next_probs, batch["reward"], batch["discount"],
            hp["v_min"], hp["v_max"], hp["atoms"],
        )

        def critic_loss(critic):
            logits = critic_forward(critic, batch["obs"], batch["action"])
            ce = -jnp.sum(target * jax.nn.log_softmax(logits), axis=-1)
            return jnp.mean(batch["weights"] * ce), ce

        (loss_c, priorities), grad_c = jax.value_and_grad(critic_loss, has_aux=True)(
            state["critic"]
        )
        critic, critic_adam = adam(
            state["critic"], grad_c, state["critic_adam"],
            hp["lr_critic"], hp["b1"], hp["b2"],
        )

        def actor_loss(actor):
            logits = critic_forward(critic, batch["obs"], actor_forward(actor, batch["obs"]))
            return -jnp.mean(jax.nn.softmax(logits) @ z)

        loss_a, grad_a = jax.value_and_grad(actor_loss)(state["actor"])
        actor, actor_adam = adam(
            state["actor"], grad_a, state["actor_adam"],
            hp["lr_actor"], hp["b1"], hp["b2"],
        )
        polyak = lambda t, o: jax.tree_util.tree_map(  # noqa: E731
            lambda t_, o_: (1.0 - hp["tau"]) * t_ + hp["tau"] * o_, t, o
        )
        new_state = {
            "actor": actor, "critic": critic,
            "target_actor": polyak(state["target_actor"], actor),
            "target_critic": polyak(state["target_critic"], critic),
            "actor_adam": actor_adam, "critic_adam": critic_adam,
        }
    return new_state, {
        "critic_loss": loss_c, "actor_loss": loss_a, "priorities": priorities,
    }
