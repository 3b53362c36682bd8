"""Plain reference: one D4PG grad step whose critic owns a GLM-4.7-Flash
torso over a window of observations, in straight ``jax.numpy``, float32,
every matrix multiplication at ``highest`` precision. No cache, no grouping,
no recomputation: every held expert runs on every token and is masked.

Written from the published configuration (``config.json`` of
zai-org/GLM-4.7-Flash, ``model_type`` ``glm4_moe_lite``; the sizes arrive in
``hp["torso"]`` under the config's own key names) and from DeepSeek-V3's
description of the layers (arXiv 2412.19437, sections 2.1.1 and 2.1.2), not
from ``d4pg_tpu/models``; the D4PG around it is ``d4pg_step.py``'s.

Tokens are timesteps. A window is the T observations that end at a drawn
row; ``x_t = o_t W_in + b_in``; positions 0…T−1; ``valid [B, T]`` says which
positions belong to the drawn row's episode.

  block     x ← x + MLA(RMSNorm(x));  x ← x + FFN(RMSNorm(x))
  MLA       c_q = RMSNorm(x W_qa);  [q_nope | q_rope]_h = c_q W_qb
            [c_kv | k_rope] = x W_kva;  c_kv ← RMSNorm(c_kv)
            [k_nope | v]_h = c_kv W_kvb;  RoPE on q_rope and on k_rope (one
            rotary key for all heads)
            P_h = softmax((q_nope·k_nope + q_rope·k_rope)/√(nope+rope) + M)
            out = concat_h(P_h v_h) W_o;  M lets t see s ≤ t, s valid
  FFN       layer < first_k_dense_replace:  SwiGLU(intermediate_size)
            else  s = sigmoid(x W_r);  chosen = top-k of s + b
                  g_i = scale · s_i / Σ_{j ∈ chosen} s_j
                  y = Σ_{i ∈ chosen ∩ held} g_i E_i(x) + E_shared(x)
            E(x) = (silu(x W_g) ⊙ x W_u) W_d
  output    h = RMSNorm(x)[T−1]

D4PG: the critic is ``{torso, head}``; actor and critic MLPs read ``h``;
target on the s′ windows through the target critic's torso; the actor reads
the ``h`` of the critic's loss pass (the torso *before* this step's update,
no gradient into it) and ascends the *updated* head; Adam and Polyak on
every leaf.

Departures, the program's, which the reference follows (the configuration
file lists them): only ``held`` of the router's experts exist here and what
the others would add is left out; the selection bias ``b`` is a constant
leaf (its gradient is zero, so Adam leaves it; Polyak moves the target's
toward it as any leaf); rotary pairs are (i, i + rope/2); no vocabulary, no
output head, no multi-token-prediction module.

``kink_gaps`` is what the comparison needs beside the step: with random
weights the k-th and (k+1)-th scores of some token, or some ReLU's
pre-activation and zero, lie closer than two roundings differ, and the
choice then flips between two correct programs.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from cellbench.reference import d4pg_step as mlp

NEG = -1e30


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


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


def attention(p, x, valid, s):
    b, t, _ = x.shape
    h, nope, r, vd = (s["num_attention_heads"], s["qk_nope_head_dim"],
                      s["qk_rope_head_dim"], s["v_head_dim"])
    c_q = rms_norm(x @ p["wq_a"], p["q_norm"], s["rms_norm_eps"])
    q = (c_q @ p["wq_b"]).reshape(b, t, h, nope + r)
    kv_a = x @ p["wkv_a"]
    c_kv = rms_norm(kv_a[..., :s["kv_lora_rank"]], p["kv_norm"], s["rms_norm_eps"])
    kv = (c_kv @ p["wkv_b"]).reshape(b, t, h, nope + vd)
    q_rope = rope(q[..., nope:], s["rope_theta"])
    k_rope = rope(kv_a[..., s["kv_lora_rank"]:], s["rope_theta"])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope])
    logits = logits + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope)
    see = (jnp.arange(t)[None, :] <= jnp.arange(t)[:, None])[None] & valid[:, None, :]
    logits = logits / math.sqrt(nope + r) + jnp.where(see, 0.0, NEG)[:, None]
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, axis=-1), kv[..., nope:])
    return out.reshape(b, t, h * vd) @ p["wo"]


def expert(w_gate, w_up, w_down, x):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe(p, x, s):
    """``(y, gap, load)``: the layer's output, each token's distance between
    its k-th and (k+1)-th biased score, and the tokens sent to each held
    expert. Every held expert runs on every token (one batched product over
    the experts); the gate of an expert a token did not choose is zero."""
    k, first = s["num_experts_per_tok"], s["experts_first"]
    held = p["experts"]["w_gate"].shape[0]
    score = jax.nn.sigmoid(x @ p["w_router"])
    top, idx = jax.lax.top_k(jax.lax.stop_gradient(score + p["e_bias"]), k + 1)
    chosen = jnp.sum(jax.nn.one_hot(idx[..., :k], score.shape[-1]), axis=-2)   # [..., E] 0/1
    gate = s["routed_scaling_factor"] * score * chosen / jnp.sum(
        score * chosen, axis=-1, keepdims=True)
    e = p["experts"]
    hidden = jax.nn.silu(jnp.einsum("...d,edf->...ef", x, e["w_gate"])) * jnp.einsum(
        "...d,edf->...ef", x, e["w_up"])
    routed = jnp.einsum("...ef,efd->...ed", hidden, e["w_down"])
    y = expert(p["shared"]["w_gate"], p["shared"]["w_up"], p["shared"]["w_down"], x)
    y = y + jnp.einsum("...e,...ed->...d", gate[..., first:first + held], routed)
    load = jnp.sum(chosen[..., first:first + held].reshape(-1, held), axis=0)
    return y, top[..., k - 1] - top[..., k], load


def torso_forward(torso, obs, valid, s):
    """``(h [B, D], gap [B], load [L_moe, held])``; ``gap`` is the smallest
    routing gap of any token of the window in any expert layer."""
    x = obs @ torso["w_in"] + torso["b_in"]
    gap = jnp.full(obs.shape[:1], jnp.inf)
    loads = []
    for i, p in enumerate(torso["layers"]):
        x = x + attention(p, rms_norm(x, p["norm1"], s["rms_norm_eps"]), valid, s)
        z = rms_norm(x, p["norm2"], s["rms_norm_eps"])
        if i < s["first_k_dense_replace"]:
            x = x + expert(p["w_gate"], p["w_up"], p["w_down"], z)
        else:
            y, g, load = moe(p, z, s)
            x = x + y
            gap = jnp.minimum(gap, jnp.min(g, axis=-1))
            loads.append(load)
    return rms_norm(x, torso["norm_f"], s["rms_norm_eps"])[:, -1], gap, jnp.stack(loads)


def window_mask(discount):
    """``[B, T]`` discounts of a window's rows → which positions are of the
    last row's episode: a zero discount ends an episode *after* its row."""
    t = discount.shape[-1]
    ends_after = jnp.concatenate(
        [discount[..., :-1] == 0.0, jnp.zeros(discount.shape[:-1] + (1,), bool)], axis=-1)
    later = jnp.stack(
        [jnp.any(ends_after[..., j:], axis=-1) for j in range(t)], axis=-1)
    return ~later


def _blocks(tree, n):
    return jax.tree_util.tree_map(
        lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), tree)


def _unblock(x):
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


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


def kink_gaps(critic, actor, obs, mask, action, hp):
    """Per window of one pass, how far the step is from its two kinds of
    discontinuity: ``routing`` — the smallest gap between the k-th and
    (k+1)-th biased router score of any of its tokens in any expert layer —
    and ``relu`` — the smallest |pre-activation| of any hidden unit of the
    heads on the window's ``h``, the critic on ``action`` and, where an
    ``actor`` is given, the actor and the critic on the actor's action (the
    actor's loss pass). A flipped choice or a ReLU on the other side of its
    kink moves a gradient by 1e-3 and more; two correct programs that round
    differently flip them. ``hp["blocks"]`` as in :func:`step`."""
    s, n = hp["torso"], hp.get("blocks", 1)
    with jax.default_matmul_precision("highest"):
        def one(blk):
            obs, mask, action = blk
            h, gap, _ = torso_forward(critic["torso"], obs, mask, s)
            relu = _least_preactivation(critic["head"], h, action)
            if actor is not None:
                relu = jnp.minimum(relu, _least_preactivation(actor, h))
                relu = jnp.minimum(relu, _least_preactivation(
                    critic["head"], h, mlp.actor_forward(actor, h)))
            return gap, relu
        gap, relu = jax.lax.map(one, _blocks((obs, mask, action), n))
        return {"routing": _unblock(gap), "relu": _unblock(relu)}


def step(state, batch, hp):
    """``state``: actor, critic ``{"torso", "head"}``, target_actor,
    target_critic, actor_adam, critic_adam (``d4pg_step.py``'s forms; a
    torso is a dict). ``batch``: ``obs`` / ``next_obs [B, T, O]``, ``mask
    [B, T]``, action, reward, discount, weights. ``hp``: ``d4pg_step``'s and
    ``torso`` (the sizes), ``blocks`` (the batch is taken in so many equal
    parts, one after the other, and the parts' gradients added: the loss is
    a mean over windows). Returns ``(new_state, out)``; ``out`` holds the two
    losses, ``[B]`` priorities, the windows' routing ``gap`` and the online
    pass's ``load [L_moe, held]``."""
    s, n = hp["torso"], hp.get("blocks", 1)
    size = batch["reward"].shape[0]
    with jax.default_matmul_precision("highest"):
        z = jnp.linspace(hp["v_min"], hp["v_max"], hp["atoms"])

        def targets(blk):
            h, gap, _ = torso_forward(
                state["target_critic"]["torso"], blk["next_obs"], blk["mask"], s)
            probs = jax.nn.softmax(mlp.critic_forward(
                state["target_critic"]["head"], h,
                mlp.actor_forward(state["target_actor"], h)))
            return mlp.project(probs, blk["reward"], blk["discount"],
                               hp["v_min"], hp["v_max"], hp["atoms"]), gap

        target, gap_next = jax.lax.map(targets, _blocks(batch, n))

        def critic_loss(critic, blk, target):
            h, gap, load = torso_forward(critic["torso"], blk["obs"], blk["mask"], s)
            logits = mlp.critic_forward(critic["head"], h, blk["action"])
            ce = -jnp.sum(target * jax.nn.log_softmax(logits), axis=-1)
            return jnp.sum(blk["weights"] * ce) / size, (ce, h, gap, load)

        def part(grads, xs):
            (loss, aux), g = jax.value_and_grad(critic_loss, has_aux=True)(
                state["critic"], *xs)
            return jax.tree_util.tree_map(jnp.add, grads, g), (loss, aux)

        grad_c, (losses, (ce, h, gap, load)) = jax.lax.scan(
            part, jax.tree_util.tree_map(jnp.zeros_like, state["critic"]),
            (_blocks(batch, n), target))
        h = jax.lax.stop_gradient(_unblock(h))
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
        "critic_loss": jnp.sum(losses), "actor_loss": loss_a,
        "priorities": _unblock(ce),
        "gap": jnp.minimum(_unblock(gap), _unblock(gap_next)),
        "load": jnp.sum(load, axis=0),
    }
