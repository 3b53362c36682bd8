"""Plain reference: one D4PG grad step whose critic owns a torso of
Keye-VL-2.0-30B-A3B blocks over a window of observations, in straight
``jax.numpy``, float32, every matrix multiplication at ``highest``
precision. Every held expert runs on every token and is masked; every query
meets every key of the window and the keys outside its set are masked; the
gradients are ``jax.grad``'s. One window's ``[heads, T, T]`` scores do not
fit a chip at T = 8,192, so the attention takes its queries in blocks of
``hp["query_block"]`` rows, one after the other, each block and each layer
recomputed in the backward pass: the same arithmetic, held a block at a time.

Written from the published configuration (``config.json`` of
Kwai-Keye/Keye-VL-2.0-30B-A3B, ``model_type`` ``KeyeVL2``: a Qwen3-MoE
decoder block with ``sa_config``'s indexer; the sizes arrive in
``hp["torso"]``) and from two published descriptions — Qwen3's block
(arXiv 2505.09388: grouped-query attention with per-head RMSNorm on q and k,
a softmax router whose top-k probabilities are renormalised, no shared
expert) and DeepSeek-V3.2's sparse attention (the lightning indexer and its
alignment loss) — not from ``d4pg_tpu/models``; the D4PG around it is
``d4pg_step.py``'s.

Tokens are timesteps, ``x_t = o_t W_in + b_in``, positions 0…T−1, ``valid
[B, T]`` says which positions exist (a stream window: all of them but the
rows before the ring's first).

  block     x ← x + Attn(RMSNorm(x));  x ← x + MoE(RMSNorm(x))
  Attn      q_h = rope(RMSNorm_h(x W_q)_h)   (H heads of d)
            k_g = rope(RMSNorm_h(x W_k)_g),  v_g = (x W_v)_g   (G groups; head
            h reads group h // (H/G));  rope: pair (i, i + d/2) turns by
            t·θ^(−2i/d)
            P_h[t, ·] = softmax over s ∈ S_t of q_h[t]·k_g[s] / √d
            out = concat_h(P_h v_g) W_o
  indexer   on x̄ = stop_gradient(RMSNorm(x)), the block's normed input:
            q^I_j = rope((x̄ W^I_q)_j)  (J heads of e),  k^I = rope(LayerNorm(x̄ W^I_k)),
            w_j = (x̄ W^I_w)_j · J^(−1/2) · e^(−1/2)
            I[t, s] = Σ_j w_j[t] · ReLU(q^I_j[t]·k^I[s])
            S_t = the ``index_topk`` keys of largest I[t, s] among s ≤ t, s
            valid (``jax.lax.top_k``'s order; all of them when fewer)
  L^I       p[t, ·] = Σ_h P_h[t, ·] normalised over S_t, under stop_gradient
            L^I = mean over valid t of KL(p[t, ·] ‖ softmax_{s ∈ S_t} I[t, s]),
            added over the layers, to the critic's loss with weight 1
  MoE       π = softmax(x W_r);  chosen = top-k of π;  g_i = π_i / Σ_chosen π
            y = Σ_{i ∈ chosen ∩ held} g_i E_i(x),  E(x) = (silu(x W_g) ⊙ x W_u) W_d
  output    h = RMSNorm(x)[T−1]

**The choices are an argument.** A step makes two discrete choices a token
and layer (k experts, ``index_topk`` keys) and two correct programs that
round differently flip the near-ties, after which everything downstream
differs by far more than rounding. So :func:`step` is *given* the sets the
program chose (``choices``: ``keys [2, L, B, T, T]`` bool and ``experts [2,
L, B·T, k]``, the critic's pass on s first, the target's on s′ second),
routes and masks by them, and computes everything smooth itself. Beside
that it computes its own scores and its own sets from them, layer by layer
on the same activations, and reports how the two sides' sets differ and
whether each difference lies within ``hp["index_margin"]`` /
``hp["router_margin"]`` of the reference's own boundary (the mean of the
last score in and the first score out): ``report``.

Departures, the program's, which the reference follows (the configuration
file lists them): only ``held`` of the router's experts exist here and what
the others would add is left out; no vision tower, no vocabulary, no output
head; with no image tokens the three rotary position ids of ``mrope`` are
equal and its sections fall away.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from cellbench.reference import d4pg_step as mlp

NEG = -1e30


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def rope(x, theta):
    """``x [B, T, ..., R]``: pair (i, i + R/2) turns by ``t · theta^(−2i/R)``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None, :]
    angle = angle.reshape((1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle), b * jnp.cos(angle) + a * jnp.sin(angle)],
        axis=-1)


def _sets_differ(distance, see, own, given, margin):
    """How two sides' sets differ, and whether within the band: ``distance``
    is each element's distance from the reference's boundary (inf where a
    row has no boundary: fewer candidates than places)."""
    differ = (own ^ given) & see
    band = (distance < margin) & see
    worst = jnp.max(jnp.where(differ, jnp.where(see, distance, jnp.inf), 0.0), initial=0.0)
    return {
        "differ": jnp.sum(differ, dtype=jnp.int32),
        "out_of_band": jnp.sum(differ & ~band, dtype=jnp.int32),
        "in_band": jnp.sum(band, dtype=jnp.int32),
        "worst": worst / margin,
        "places": jnp.sum(own & see, dtype=jnp.int32),
    }


def own_keys(index, see, k, margin):
    """The reference's own choice on its own index scores ``[..., T]``:
    ``(set, distance from the boundary, margin as a number)``. The margin is
    relative to the scores' root mean square over what may be seen."""
    t = index.shape[-1]
    masked = jnp.where(see, index, -jnp.inf)
    if t <= k:
        return see, jnp.full(index.shape, jnp.inf), jnp.float32(1.0)
    top = jax.lax.top_k(masked, k + 1)[0]
    last_in, first_out = top[..., k - 1:k], top[..., k:k + 1]
    above, ties = masked > last_in, masked == last_in
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    own = (above | (ties & (jnp.cumsum(ties, axis=-1) <= room))) & see
    boundary = 0.5 * (last_in + first_out)            # -inf where all candidates fit
    distance = jnp.where(jnp.isfinite(boundary), jnp.abs(index - boundary), jnp.inf)
    rms = jnp.sqrt(jnp.sum(jnp.where(see, index, 0.0) ** 2) / jnp.maximum(jnp.sum(see), 1))
    return own, distance, margin * rms


def attention(p, x, valid, s, given, hp):
    """``(out [B, T, D], L^I, report)`` on the block's normed input ``x``;
    ``given [B, T, T]`` bool are the keys each query attends to."""
    b, t, _ = x.shape
    h, g, d, eps = (s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"],
                    s["rms_norm_eps"])
    j, e, topk = s["index_n_heads"], s["index_head_dim"], s["index_topk"]
    q = rope(rms_norm((x @ p["wq"]).reshape(b, t, h, d), p["q_norm"], eps), s["rope_theta"])
    k = rope(rms_norm((x @ p["wk"]).reshape(b, t, g, d), p["k_norm"], eps), s["rope_theta"])
    v = (x @ p["wv"]).reshape(b, t, g, d)
    k, v = (jnp.repeat(a, h // g, axis=2) for a in (k, v))       # head h reads group h // (H/G)
    cut = jax.lax.stop_gradient(x)
    q_i = rope((cut @ p["idx_wq"]).reshape(b, t, j, e), s["rope_theta"])
    k_i = rope(layer_norm(cut @ p["idx_wk"], p["idx_k_scale"], p["idx_k_bias"], eps),
               s["rope_theta"])
    w_i = (cut @ p["idx_ww"]) * (j ** -0.5) * (e ** -0.5)

    rows = min(hp.get("query_block", t), t)
    positions = jnp.arange(t)

    @jax.checkpoint
    def block(xs):
        q_b, qi_b, w_b, given_b, pos_b, valid_b = xs            # a block of queries, B inside
        see = (positions[None, None, :] <= pos_b[None, :, None]) & valid[:, None, :]
        index = jnp.einsum("bqj,bqjs->bqs", w_b, jax.nn.relu(
            jnp.einsum("bqje,bse->bqjs", qi_b, k_i)))
        index = jnp.where(index == 0.0, 0.0, index)
        own, distance, margin = own_keys(jax.lax.stop_gradient(index), see, topk,
                                         hp["index_margin"])
        report = _sets_differ(distance, see, own, given_b, margin)
        logits = jnp.einsum("bqhd,bshd->bhqs", q_b, k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(given_b[:, None], logits, NEG), axis=-1)
        out = jnp.einsum("bhqs,bshd->bqhd", probs, v).reshape(b, -1, h * d)
        target = jax.lax.stop_gradient(jnp.sum(probs, axis=1))
        target = target / jnp.sum(target, axis=-1, keepdims=True)
        log_i = jax.nn.log_softmax(jnp.where(given_b, index, NEG), axis=-1)
        kl = jnp.sum(jnp.where(target > 0, target * (
            jnp.log(jnp.where(target > 0, target, 1.0)) - log_i), 0.0), axis=-1)
        return out, jnp.sum(jnp.where(valid_b, kl, 0.0)), report

    cut_rows = lambda a, axis=1: jnp.moveaxis(  # noqa: E731
        a.reshape(a.shape[:axis] + (t // rows, rows) + a.shape[axis + 1:]), axis, 0)
    out, kl, report = jax.lax.map(block, (
        cut_rows(q), cut_rows(q_i), cut_rows(w_i), cut_rows(given), cut_rows(positions, 0),
        cut_rows(valid)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, h * d) @ p["wo"]
    report = {name: (jnp.max(v) if name == "worst" else jnp.sum(v))
              for name, v in report.items()}
    return out, jnp.sum(kl) / jnp.maximum(jnp.sum(valid), 1), report


def moe(p, x, valid, s, given, hp):
    """``(y, report, load)`` on tokens ``x [N, D]``: every held expert on
    every token, gated by the ``given [N, k]`` experts of each token. The
    sets are compared on the ``valid [N]`` tokens: what a position outside
    the window holds is free, and reaches nothing."""
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
    e = p["experts"]
    hidden = jax.nn.silu(jnp.einsum("nd,edf->nef", x, e["w_gate"])) * jnp.einsum(
        "nd,edf->nef", x, e["w_up"])
    routed = jnp.einsum("nef,efd->ned", hidden, e["w_down"])
    y = jnp.einsum("ne,ned->nd", gate[:, first:first + held], routed)
    return y, report, jnp.sum(chosen[:, first:first + held], axis=0, dtype=jnp.int32)


def torso_forward(torso, obs, valid, s, choice, hp):
    """``(h [B, D], L^I, report)``; ``choice`` holds one pass's ``keys [L, B,
    T, T]`` and ``experts [L, B·T, k]``; ``report`` is a dict of ``[L]``
    arrays (``keys_*``, ``experts_*``, ``load [L, held]``)."""
    assert s["first_k_dense_replace"] == 0 and s["n_shared_experts"] == 0
    b, t, _ = obs.shape
    x = obs @ torso["w_in"] + torso["b_in"]
    loss, reports = 0.0, []

    for i, p in enumerate(torso["layers"]):
        @jax.checkpoint
        def layer(x, p, keys, experts):
            out, part, r_keys = attention(
                p, rms_norm(x, p["norm1"], s["rms_norm_eps"]), valid, s, keys, hp)
            x = x + out
            y, r_experts, load = moe(
                p, rms_norm(x, p["norm2"], s["rms_norm_eps"]).reshape(b * t, -1),
                valid.reshape(b * t), s, experts, hp)
            report = {f"keys_{n}": v for n, v in r_keys.items()}
            report.update({f"experts_{n}": v for n, v in r_experts.items()}, load=load)
            return x + y.reshape(b, t, -1), part, report

        x, part, report = layer(x, p, choice["keys"][i], choice["experts"][i])
        loss = loss + part
        reports.append(report)
    report = {n: jnp.stack([r[n] for r in reports]) for n in reports[0]}
    return rms_norm(x, torso["norm_f"], s["rms_norm_eps"])[:, -1], loss, report


def _least_preactivation(layers, x, action=None):
    """The smallest |pre-activation| of any hidden unit of an MLP head, per
    row: how near its nearest ReLU is to its kink. ``action`` joins at the
    second layer (the critic)."""
    least = jnp.full(x.shape[:1], jnp.inf)
    for i, (w, b) in enumerate(layers[:-1]):
        if i == 1 and action is not None:
            x = jnp.concatenate([x, action], axis=-1)
        pre = x @ w + b
        least = jnp.minimum(least, jnp.min(jnp.abs(pre), axis=-1))
        x = jax.nn.relu(pre)
    return least


def step(state, batch, choices, hp):
    """``state``: actor, critic ``{"torso", "head"}``, target_actor,
    target_critic, actor_adam, critic_adam (``d4pg_step.py``'s forms; a
    torso is a dict). ``batch``: ``obs`` / ``next_obs [B, T, O]``, ``mask
    [B, T]``, action, reward, discount, weights. ``choices``: what the
    program's step chose (the module's note). ``hp``: ``d4pg_step``'s and
    ``torso`` (the sizes), ``query_block``, ``index_margin``,
    ``router_margin``. Returns ``(new_state, out)``; ``out`` holds the two
    losses, the alignment loss, ``[B]`` priorities, ``relu [B]`` (the
    smallest head pre-activation a window meets in the critic's and the
    actor's loss passes) and ``report``, the two passes' comparison of
    sets, a dict of ``[2, L]`` arrays."""
    s = hp["torso"]
    size = batch["reward"].shape[0]
    pick = lambda i: {"keys": choices["keys"][i], "experts": choices["experts"][i]}  # noqa: E731
    with jax.default_matmul_precision("highest"):
        z = jnp.linspace(hp["v_min"], hp["v_max"], hp["atoms"])
        h_next, _, report_target = torso_forward(
            state["target_critic"]["torso"], batch["next_obs"], batch["mask"], s, pick(1), hp)
        probs = jax.nn.softmax(mlp.critic_forward(
            state["target_critic"]["head"], h_next,
            mlp.actor_forward(state["target_actor"], h_next)))
        target = mlp.project(probs, batch["reward"], batch["discount"],
                             hp["v_min"], hp["v_max"], hp["atoms"])

        def critic_loss(critic):
            h, align, report = torso_forward(
                critic["torso"], batch["obs"], batch["mask"], s, pick(0), hp)
            logits = mlp.critic_forward(critic["head"], h, batch["action"])
            ce = -jnp.sum(target * jax.nn.log_softmax(logits), axis=-1)
            return jnp.sum(batch["weights"] * ce) / size + align, (ce, h, align, report)

        (loss_c, (ce, h, align, report)), grad_c = jax.value_and_grad(
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
        "critic_loss": loss_c, "actor_loss": loss_a, "index_loss": align,
        "priorities": ce, "relu": relu,
        "report": {n: jnp.stack([report[n], report_target[n]]) for n in report},
    }
