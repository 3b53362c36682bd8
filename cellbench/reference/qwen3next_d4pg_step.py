"""Plain reference: one D4PG grad step whose critic owns a torso of
Qwen3-Next-80B-A3B blocks over a window of observations, in straight
``jax.numpy``, float32, every matrix multiplication at ``highest``
precision. **The recurrence runs token by token**: one ``lax.scan`` step a
position, the state decayed, written and read as the three lines below say.
8,192 states of 2 MB a layer cannot be held for the backward pass, so the
scan is two nested ones — 64 positions inside, a ``jax.checkpoint`` around
each run of them — and keeps T/64 states, recomputing the rest: blocks so
that it fits, not another form. Attention is plain softmax, its queries in
blocks of ``hp["query_block"]`` rows; every held expert runs on every token
and is gated; the gradients are ``jax.grad``'s.

Written from the published configuration (``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct, ``model_type`` ``qwen3_next``; the sizes
arrive in ``hp["torso"]``) and the source's published description (Gated
DeltaNet, arXiv 2412.06464; Gated Attention, arXiv 2505.06708), not from
``d4pg_tpu/models`` or ``d4pg_tpu/ops``; the D4PG around it is
``d4pg_step.py``'s.

Tokens are timesteps, ``x_t = o_t W_in + b_in``, positions 0…T−1, ``valid
[B, T]`` says which positions exist (a stream window: all but a prefix).

  norms     n(x) = x / rms(x) · (1 + w), eps inside the root ("zero-centred")
  block i   x ← x + mixer_i(n₁(x));  x ← x + MoE(n₂(x));  mixer_i is Attn
            where (i + 1) mod ``full_attention_interval`` = 0, else DeltaNet
  DeltaNet  u = n₁(x), zero where the position is not valid
            (q, k, v, z) = u W_qkvz, laid out key head by key head as [q(dk),
            k(dk), v(r·dv), z(r·dv)] (r value heads a key head); (b, a) = u
            W_ba, likewise [b(r), a(r)]
            (q, k, v) ← silu(conv(q ‖ k ‖ v)): per channel, y_t = Σ_j c_j
            u_{t−(W−1)+j}, zeros before the window
            value head h reads key head h // r:  q̂ = q/√(‖q‖² + 1e-6) · dk^-½,
            k̂ = k/√(‖k‖² + 1e-6),  β = σ(b),  g = −exp(A_log) · softplus(a + dt_bias)
            S₀ = 0;  S ← e^{g_t} S;  S ← S + k̂_t ⊗ β_t (v_t − Sᵀ k̂_t);  o_t = Sᵀ q̂_t
            y = (w_o ⊙ o / rms(o)) ⊙ silu(z) per head, then W_out
  Attn      (q, gate)_h = (u W_q)_h  (H heads of 2d: query, then gate)
            q_h = rope(n(q_h)),  k_g = rope(n((u W_k)_g)),  v_g = (u W_v)_g;  rope
            turns the first ``partial_rotary_factor``·d dims: pair (i, i + R/2)
            by t·θ^(−2i/R);  head h reads group h // (H/G)
            P_h[t, ·] = softmax over valid s ≤ t of q_h[t]·k_g[s] / √d
            out = (concat_h(P_h v_g) ⊙ σ(gate)) W_o
  MoE       π = softmax(x W_r);  chosen = top-k of π;  g_i = π_i / Σ_chosen π
            y = Σ_{i ∈ chosen ∩ held} g_i E_i(x) + σ(x·w_sg) E_shared(x),
            E(x) = (silu(x W_g) ⊙ x W_u) W_d
  output    h = n(x)[T−1]

**The expert choices are an argument**, as in ``keyevl2_d4pg_step``: two
correct programs that round differently flip a near-tie, so :func:`step`
routes by the sets the program chose (``choices["experts"] [2, L, B·T, k]``,
the critic's pass on s first, the target's on s′ second) and computes
everything smooth itself; beside that it chooses its own experts from its
own probabilities and reports how the two sides' sets differ and whether
each difference lies within ``hp["router_margin"]`` of its own boundary.

Departures, the program's, which the reference follows (the configuration
file lists them): only ``held`` of the router's experts exist here and what
the others would add is left out; no vocabulary, no output head, no
multi-token-prediction layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from cellbench.reference import d4pg_step as mlp
from cellbench.reference.keyevl2_d4pg_step import NEG, _least_preactivation, _sets_differ, rope

INNER = 64     # positions of the recurrence between two kept states


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def delta_rule(q, k, v, g, beta):
    """The recurrence on ``q, k [B, T, H, dk]``, ``v [B, T, H, dv]``, ``g,
    beta [B, T, H]``, one position a step: ``o [B, T, H, dv]``."""
    b, t, h, dk = q.shape
    inner = INNER if t % INNER == 0 else t

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[:, :, None, None]
        error = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + k_t[..., :, None] * (beta_t[..., None] * error)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    @jax.checkpoint
    def run(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = [jnp.moveaxis(a, 1, 0).reshape((t // inner, inner) + a.shape[:1] + a.shape[2:])
          for a in (q, k, v, g, beta)]
    _, out = jax.lax.scan(run, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(out.reshape((t,) + out.shape[2:]), 0, 1)


def delta_net(p, u, valid, s):
    """The Gated DeltaNet mixer on the block's normed input ``u [B, T, D]``."""
    b, t, _ = u.shape
    hk, hv, dk, dv, width = (s["linear_num_key_heads"], s["linear_num_value_heads"],
                             s["linear_key_head_dim"], s["linear_value_head_dim"],
                             s["linear_conv_kernel_dim"])
    r = hv // hk
    u = u * valid[..., None]
    by_head = (u @ p["w_qkvz"]).reshape(b, t, hk, 2 * dk + 2 * r * dv)
    q, k, v, z = jnp.split(by_head, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    b_a = (u @ p["w_ba"]).reshape(b, t, hk, 2 * r)
    beta = jax.nn.sigmoid(b_a[..., :r].reshape(b, t, hv))
    a = b_a[..., r:].reshape(b, t, hv)
    channels = jnp.concatenate([q.reshape(b, t, -1), k.reshape(b, t, -1), v.reshape(b, t, -1)], -1)
    conv = jnp.zeros_like(channels)
    for j in range(width):                      # tap j reads the position W−1−j back
        back = width - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(channels[:, :back]), channels[:, :t - back]], axis=1)
        conv = conv + shifted * p["conv"][:, j]
    conv = jax.nn.silu(conv)
    q = conv[..., :hk * dk].reshape(b, t, hk, dk)
    k = conv[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk)
    v = conv[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(x, r, axis=2) for x in (q, k))       # value head h reads key head h // r
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + s["rms_norm_eps"]) * p["o_norm"]
    return (o * jax.nn.silu(z.reshape(b, t, hv, dv))).reshape(b, t, hv * dv) @ p["w_out"]


def attention(p, u, valid, s, hp):
    """Gated grouped-query attention on the block's normed input ``u``."""
    b, t, _ = u.shape
    h, g, d, eps = (s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"],
                    s["rms_norm_eps"])
    turned = int(d * s["partial_rotary_factor"])
    turn = lambda x: jnp.concatenate(  # noqa: E731
        [rope(x[..., :turned], s["rope_theta"]), x[..., turned:]], axis=-1)
    q_gate = (u @ p["wq"]).reshape(b, t, h, 2 * d)
    q = turn(norm(q_gate[..., :d], p["q_norm"], eps))
    gate = q_gate[..., d:].reshape(b, t, h * d)
    k = turn(norm((u @ p["wk"]).reshape(b, t, g, d), p["k_norm"], eps))
    v = (u @ p["wv"]).reshape(b, t, g, d)
    k, v = (jnp.repeat(a, h // g, axis=2) for a in (k, v))
    rows = min(hp.get("query_block", t), t)
    positions = jnp.arange(t)

    @jax.checkpoint
    def block(xs):
        q_b, pos_b = xs
        see = (positions[None, None, :] <= pos_b[None, :, None]) & valid[:, None, :]
        logits = jnp.einsum("bqhd,bshd->bhqs", q_b, k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(see[:, None], logits, NEG), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", probs, v).reshape(b, -1, h * d)

    out = jax.lax.map(block, (
        jnp.moveaxis(q.reshape(b, t // rows, rows, h, d), 1, 0), positions.reshape(-1, rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, h * d)
    return (out * jax.nn.sigmoid(gate)) @ p["wo"]


def _expert(w, e, x):
    return (jax.nn.silu(x @ w["w_gate"][e]) * (x @ w["w_up"][e])) @ w["w_down"][e]


def moe(p, x, valid, s, given, hp):
    """``(y, report, load)`` on tokens ``x [N, D]``: every held expert on
    every token, one after the other, gated by the ``given [N, k]`` experts
    of each token; the shared expert behind its sigmoid gate. The sets are
    compared on the ``valid [N]`` tokens."""
    k, first, n_experts = s["num_experts_per_tok"], s["experts_first"], s["n_routed_experts"]
    held = p["experts"]["w_gate"].shape[0]
    prob = jax.nn.softmax(x @ p["w_router"], axis=-1)
    top, idx = jax.lax.top_k(jax.lax.stop_gradient(prob), k + 1)
    own = jnp.sum(jax.nn.one_hot(idx[:, :k], n_experts), axis=1) > 0
    chosen = jnp.sum(jax.nn.one_hot(given, n_experts), axis=1) > 0
    boundary = 0.5 * (top[:, k - 1:k] + top[:, k:k + 1])
    distance = jnp.abs(jnp.log(jax.lax.stop_gradient(prob)) - jnp.log(boundary))
    report = _sets_differ(distance, jnp.broadcast_to(valid[:, None], own.shape), own, chosen,
                          jnp.float32(hp["router_margin"]))
    gate = prob * chosen / jnp.sum(prob * chosen, axis=-1, keepdims=True)
    y = jax.nn.sigmoid(x @ p["w_shared_gate"]) * (
        (jax.nn.silu(x @ p["shared"]["w_gate"]) * (x @ p["shared"]["w_up"]))
        @ p["shared"]["w_down"])
    for e in range(held):
        y = y + gate[:, first + e, None] * _expert(p["experts"], e, x)
    return y, report, jnp.sum(chosen[:, first:first + held], axis=0, dtype=jnp.int32)


def torso_forward(torso, obs, valid, s, experts, hp):
    """``(h [B, D], report)``; ``experts [L, B·T, k]`` is what one pass of
    the program chose; ``report`` is a dict of ``[L]`` arrays
    (``experts_*``, ``load [L, held]``)."""
    assert s["first_k_dense_replace"] == 0 and s["n_shared_experts"] == 1
    b, t, _ = obs.shape
    x = obs @ torso["w_in"] + torso["b_in"]
    reports = []
    for i, p in enumerate(torso["layers"]):
        full = (i + 1) % s["full_attention_interval"] == 0

        @jax.checkpoint
        def layer(x, p, given, full=full):
            u = norm(x, p["norm1"], s["rms_norm_eps"])
            x = x + (attention(p, u, valid, s, hp) if full else delta_net(p, u, valid, s))
            y, report, load = moe(
                p, norm(x, p["norm2"], s["rms_norm_eps"]).reshape(b * t, -1),
                valid.reshape(b * t), s, given, hp)
            report = {f"experts_{n}": v for n, v in report.items()}
            return x + y.reshape(b, t, -1), dict(report, load=load)

        x, report = layer(x, p, experts[i])
        reports.append(report)
    report = {n: jnp.stack([r[n] for r in reports]) for n in reports[0]}
    return norm(x, torso["norm_f"], s["rms_norm_eps"])[:, -1], report


def step(state, batch, choices, hp):
    """``state``: actor, critic ``{"torso", "head"}``, target_actor,
    target_critic, actor_adam, critic_adam (``d4pg_step.py``'s forms; a
    torso is a dict). ``batch``: ``obs`` / ``next_obs [B, T, O]``, ``mask
    [B, T]``, action, reward, discount, weights. ``choices``: the experts
    the program's step chose (the module's note). ``hp``: ``d4pg_step``'s
    and ``torso`` (the sizes), ``query_block``, ``router_margin``. Returns
    ``(new_state, out)``; ``out`` holds the two losses, ``[B]`` priorities,
    ``relu [B]`` (the smallest head pre-activation a window meets in the
    critic's and the actor's loss passes) and ``report``, the two passes'
    comparison of sets, a dict of ``[2, L]`` arrays."""
    s = hp["torso"]
    size = batch["reward"].shape[0]
    with jax.default_matmul_precision("highest"):
        z = jnp.linspace(hp["v_min"], hp["v_max"], hp["atoms"])
        h_next, report_target = torso_forward(
            state["target_critic"]["torso"], batch["next_obs"], batch["mask"], s,
            choices["experts"][1], hp)
        probs = jax.nn.softmax(mlp.critic_forward(
            state["target_critic"]["head"], h_next,
            mlp.actor_forward(state["target_actor"], h_next)))
        target = mlp.project(probs, batch["reward"], batch["discount"],
                             hp["v_min"], hp["v_max"], hp["atoms"])

        def critic_loss(critic):
            h, report = torso_forward(
                critic["torso"], batch["obs"], batch["mask"], s, choices["experts"][0], hp)
            logits = mlp.critic_forward(critic["head"], h, batch["action"])
            ce = -jnp.sum(target * jax.nn.log_softmax(logits), axis=-1)
            return jnp.sum(batch["weights"] * ce) / size, (ce, h, report)

        (loss_c, (ce, h, report)), grad_c = jax.value_and_grad(
            critic_loss, has_aux=True)(state["critic"])
        h = jax.lax.stop_gradient(h)
        relu = _least_preactivation(state["critic"]["head"], h, batch["action"])
        relu = jnp.minimum(relu, _least_preactivation(state["actor"], h))
        relu = jnp.minimum(relu, _least_preactivation(
            state["critic"]["head"], h, mlp.actor_forward(state["actor"], h)))
        critic, critic_adam = mlp.adam(
            state["critic"], grad_c, state["critic_adam"],
            hp["lr_critic"], hp["b1"], hp["b2"])

        def actor_loss(actor):
            logits = mlp.critic_forward(critic["head"], h, mlp.actor_forward(actor, h))
            return -jnp.mean(jax.nn.softmax(logits) @ z)

        loss_a, grad_a = jax.value_and_grad(actor_loss)(state["actor"])
        actor, actor_adam = mlp.adam(
            state["actor"], grad_a, state["actor_adam"],
            hp["lr_actor"], hp["b1"], hp["b2"])
        polyak = lambda t, o: jax.tree_util.tree_map(  # noqa: E731
            lambda t_, o_: (1.0 - hp["tau"]) * t_ + hp["tau"] * o_, t, o)
        new_state = {
            "actor": actor, "critic": critic,
            "target_actor": polyak(state["target_actor"], actor),
            "target_critic": polyak(state["target_critic"], critic),
            "actor_adam": actor_adam, "critic_adam": critic_adam,
        }
    return new_state, {
        "critic_loss": loss_c, "actor_loss": loss_a, "priorities": ce, "relu": relu,
        "report": {n: jnp.stack([report[n], report_target[n]]) for n in report},
    }
