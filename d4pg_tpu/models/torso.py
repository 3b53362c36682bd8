"""A sequence torso over a window of observations: MLA attention blocks
with a dense SwiGLU layer first and routed-expert layers after it.

Tokens are timesteps: ``x_t = o_t W_in + b_in`` stands where a language
model's embedding stands, positions are 0…T−1 of the window, attention is
causal, and the torso's output is the last position's state after the final
norm. The layer equations are the DeepSeek-V3 ones at whatever widths
:class:`TorsoConfig` gives (pre-norm residual blocks, multi-head latent
attention with a decoupled rotary key shared by the heads, sigmoid router
scores with a selection bias, top-k, renormalised and scaled gates, a shared
expert beside the routed ones).

The expert layer is **told which experts it holds** (``experts_first``,
``experts_held``): it routes over all ``n_routed_experts``, keeps the pairs
that land on its own experts, and computes those — one chip's share of an
expert-parallel layer, without the exchange. Nothing is dropped: the pairs
are laid out by expert in blocks of ``expert_block_rows`` rows (each held
expert's group padded to whole blocks, the buffer sized for the worst case)
and a loop runs over the *live* blocks only, so the matrix products follow
the routed pairs. No ``lax.ragged_dot`` (a ``tpu_custom_call`` on the v5e)
and no scatter of wide rows (XLA:TPU expands it into a ``while`` of one-row
updates): dispatch, combine and both of their transposes are gathers, which
is why the routed part carries its own VJP.

Pure functions over a plain dict of arrays, one entry a block, each block
under ``jax.checkpoint``. The blocks are a Python loop and not a
``lax.scan`` over stacked parameters: with the scan XLA:TPU converts the
whole stack to bfloat16 ahead of the loop (its default matmul precision,
propagated back through the slice), 1 GB a parameter set at the published
widths that a chip holding four sets cannot spare.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from d4pg_tpu.utils.profiling import phase

MASKED = -1e30   # finite: a window position with no valid key stays finite


@dataclasses.dataclass(frozen=True)
class TorsoConfig:
    """Static sizes of the torso. Field names follow the published
    ``config.json`` keys of the architecture where there is one."""

    name: str = "glm47_flash"
    hidden_size: int = 2048
    num_hidden_layers: int = 47        # every block, the leading dense ones included
    first_k_dense_replace: int = 1
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1_000_000.0
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64         # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    rms_norm_eps: float = 1e-5
    # the share of each expert layer this learner holds
    experts_first: int = 0
    experts_held: int = 64
    # the history window and the ring's stride between a stream's rows
    window: int = 32
    row_stride: int = 1
    # rows of one block of the by-expert layout: the grain at which the
    # expert loop's work follows the routed pairs (1,024-row blocks were
    # slower on the chip and no steadier over seeds, PERF.md section 6)
    expert_block_rows: int = 256
    batch_chunks: int = 4         # attention and the dense SwiGLU run on B/4 windows at a time

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def padded_pairs(self, tokens: int) -> int:
        """Rows of the dispatch buffer: every pair that can land on a held
        expert (a token picks an expert at most once), each expert's group
        rounded up to whole blocks."""
        worst = tokens * min(self.num_experts_per_tok, self.experts_held)
        blocks = -(-worst // self.expert_block_rows) + self.experts_held
        return blocks * self.expert_block_rows


# The published widths (zai-org/GLM-4.7-Flash config.json, model_type
# glm4_moe_lite) and a toy of the same structure for CPU tests and
# rehearsals. Depth, the experts held and the window are flags of train.py.
TORSO_PRESETS = {
    "glm47_flash": TorsoConfig(),
    "glm47_flash_tiny": TorsoConfig(
        name="glm47_flash_tiny", hidden_size=32, num_hidden_layers=3,
        num_attention_heads=2, q_lora_rank=12, kv_lora_rank=8,
        qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=8,
        intermediate_size=48, moe_intermediate_size=16, n_routed_experts=8,
        num_experts_per_tok=2, experts_held=8, window=4, expert_block_rows=8,
    ),
}


def validate(cfg: TorsoConfig) -> None:
    if not 0 <= cfg.experts_first <= cfg.experts_first + cfg.experts_held <= cfg.n_routed_experts:
        raise ValueError(
            f"torso holds experts [{cfg.experts_first}, "
            f"{cfg.experts_first + cfg.experts_held}) of {cfg.n_routed_experts}")
    if cfg.experts_held < 1 or cfg.num_moe_layers < 1 or cfg.first_k_dense_replace < 1:
        raise ValueError("torso needs a dense layer, an expert layer and a held expert")
    if cfg.qk_rope_head_dim % 2:
        raise ValueError("qk_rope_head_dim must be even")
    if cfg.n_shared_experts != 1:
        raise ValueError("one shared expert is what the layer computes")


# ------------------------------------------------------------------ init
def _uniform(key, shape, fan_in):
    bound = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def _block_init(cfg: TorsoConfig, key, moe: bool) -> dict:
    d, h = cfg.hidden_size, cfg.num_attention_heads
    ks = iter(jax.random.split(key, 16))
    attn = {
        "q_a": _uniform(next(ks), (d, cfg.q_lora_rank), d),
        "q_a_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
        "q_b": _uniform(next(ks), (cfg.q_lora_rank, h * cfg.qk_head_dim), cfg.q_lora_rank),
        "kv_a": _uniform(next(ks), (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), d),
        "kv_a_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
        "kv_b": _uniform(
            next(ks), (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            cfg.kv_lora_rank),
        "o": _uniform(next(ks), (h * cfg.v_head_dim, d), h * cfg.v_head_dim),
    }

    def swiglu(width, lead=()):
        return {
            "gate": _uniform(next(ks), lead + (d, width), d),
            "up": _uniform(next(ks), lead + (d, width), d),
            "down": _uniform(next(ks), lead + (width, d), width),
        }

    if moe:
        ffn = {
            "router": _uniform(next(ks), (d, cfg.n_routed_experts), d),
            # the selection bias of noaux_tc: a buffer that enters the choice
            # only. Held at its initial value (its update rate is not in the
            # published config); it takes no gradient.
            "router_bias": jnp.zeros((cfg.n_routed_experts,), jnp.float32),
            "experts": swiglu(cfg.moe_intermediate_size, (cfg.experts_held,)),
            "shared": swiglu(cfg.moe_intermediate_size * cfg.n_shared_experts),
        }
    else:
        ffn = swiglu(cfg.intermediate_size)
    return {
        "attn_norm": jnp.ones((d,), jnp.float32),
        "ffn_norm": jnp.ones((d,), jnp.float32),
        "attn": attn, "ffn": ffn,
    }


def torso_init(cfg: TorsoConfig, key, obs_dim: int) -> dict:
    """``embed`` (the observation projection), ``layers`` (one dict a block,
    the leading dense ones first), ``final_norm``."""
    validate(cfg)
    k_in, k_b, k_layers = jax.random.split(key, 3)
    return {
        "embed": {
            "kernel": _uniform(k_in, (obs_dim, cfg.hidden_size), obs_dim),
            "bias": _uniform(k_b, (cfg.hidden_size,), obs_dim),
        },
        "layers": [
            _block_init(cfg, k, moe=i >= cfg.first_k_dense_replace)
            for i, k in enumerate(jax.random.split(k_layers, cfg.num_hidden_layers))],
        "final_norm": jnp.ones((cfg.hidden_size,), jnp.float32),
    }


# --------------------------------------------------------------- pieces
def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def rope_tables(cfg: TorsoConfig, positions: int):
    """cos/sin ``[T, rope/2]``: frequency ``theta^(-2i/rope)`` for pair i."""
    half = cfg.qk_rope_head_dim // 2
    inv = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(positions, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def apply_rope(x, cos, sin):
    """Rotate ``[B, T, ..., rope]`` by position: pair i is (x[i], x[i +
    rope/2]), the rotate-half convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    shape = (1, cos.shape[0]) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention_bias(valid):
    """``[B, T, T]`` additive mask: query t sees key s iff s ≤ t and
    position s is valid."""
    t = valid.shape[-1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    return jnp.where(causal[None] & valid[:, None, :], 0.0, MASKED)


def mla(cfg: TorsoConfig, p: dict, x, bias, cos, sin):
    """Multi-head latent attention on ``[B, T, D]``."""
    b, t, _ = x.shape
    h, nope, rope, vd = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    c_q = rms_norm(x @ p["q_a"], p["q_a_norm"], cfg.rms_norm_eps)
    q = (c_q @ p["q_b"]).reshape(b, t, h, nope + rope)
    q_nope, q_rope = q[..., :nope], apply_rope(q[..., nope:], cos, sin)
    kv_a = x @ p["kv_a"]
    c_kv = rms_norm(kv_a[..., :cfg.kv_lora_rank], p["kv_a_norm"], cfg.rms_norm_eps)
    k_rope = apply_rope(kv_a[..., cfg.kv_lora_rank:], cos, sin)     # one for all heads
    kv = (c_kv @ p["kv_b"]).reshape(b, t, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scores = (jnp.einsum("bthd,bshd->bhts", q_nope, k_nope)
              + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope))
    scores = scores / math.sqrt(nope + rope) + bias[:, None]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bshd->bthd", probs, v).reshape(b, t, h * vd)
    return out @ p["o"]


def swiglu(p: dict, x):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def in_chunks(fn, chunks: int, *rows):
    """``fn(*rows)`` where every array of ``rows`` has the same leading axis
    and ``fn`` treats its entries independently: ``chunks`` slices of that
    axis one after the other, each recomputed in the backward pass, so the
    intermediates of a wide layer never exist for the whole batch."""
    n = rows[0].shape[0]
    if chunks <= 1 or n % chunks:
        return fn(*rows)
    split = [r.reshape((chunks, n // chunks) + r.shape[1:]) for r in rows]
    out = jax.lax.map(lambda part: jax.checkpoint(fn)(*part), split)
    return out.reshape((n,) + out.shape[2:])


# ------------------------------------------------------ the routed experts
def route(cfg: TorsoConfig, p: dict, x):
    """``(chosen [N, k] int32, gates [N, k])``: sigmoid scores, the top-k of
    score + bias, gates = scale · score / Σ chosen scores."""
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(
        jax.lax.stop_gradient(scores + p["router_bias"]), cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = cfg.routed_scaling_factor * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen, gates


def dispatch_plan(cfg: TorsoConfig, chosen):
    """Where each (token, choice) pair goes in the by-expert layout.

    Returns ``slot [N, k]`` (row of the dispatch buffer, or ``P`` — one past
    its end — for a pair whose expert is not held here), ``slot_token [P]``
    (the token of each row; ``N`` for an empty row), ``slot_choice [P]``,
    ``block_expert [P / rows]`` (held-expert index of each block),
    ``live_blocks`` (a scalar: the blocks that hold a pair) and ``load
    [held]`` (pairs per held expert)."""
    n, k = chosen.shape
    rows = cfg.expert_block_rows
    total = cfg.padded_pairs(n)
    local = chosen - cfg.experts_first                       # [N, k]
    held = (local >= 0) & (local < cfg.experts_held)
    one_hot = (local[..., None] == jnp.arange(cfg.experts_held)) & held[..., None]
    per_token = jnp.sum(one_hot, axis=1, dtype=jnp.int32)    # [N, held], 0 or 1
    rank = jnp.cumsum(per_token, axis=0) - per_token         # pairs of e before token n
    load = jnp.sum(per_token, axis=0)
    group_blocks = -(-load // rows)
    group_start = (jnp.cumsum(group_blocks) - group_blocks) * rows
    place = group_start[None, :] + rank                      # [N, held]
    slot = jnp.sum(jnp.where(one_hot, place[:, None, :], 0), axis=-1)
    slot = jnp.where(held, slot, total).astype(jnp.int32)
    token = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None], (n, k))
    choice = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32)[None, :], (n, k))
    # narrow int32 scatters: one word a pair (wide rows are never scattered)
    slot_token = jnp.full((total,), n, jnp.int32).at[slot.reshape(-1)].set(
        token.reshape(-1), mode="drop")
    slot_choice = jnp.zeros((total,), jnp.int32).at[slot.reshape(-1)].set(
        choice.reshape(-1), mode="drop")
    live_blocks = jnp.sum(group_blocks)
    block_id = jnp.arange(total // rows, dtype=jnp.int32)
    block_expert = jnp.clip(
        jnp.searchsorted(jnp.cumsum(group_blocks), block_id, side="right"),
        0, cfg.experts_held - 1).astype(jnp.int32)
    return slot, slot_token, slot_choice, block_expert, live_blocks, load


def _rows(x, idx):
    """``x[idx]`` with one row of zeros past the end (the sentinel row)."""
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


def _expert_weights(w, e):
    """Held expert ``e``'s three matrices out of the stacked ``w``."""
    return {name: jax.lax.dynamic_index_in_dim(w[name], e, keepdims=False)
            for name in ("gate", "up", "down")}


def _expert_blocks(rows, xs, block_expert, live_blocks, w):
    """``ys [P, D]``: each live block of ``xs`` through its expert."""
    def body(i, ys):
        x = jax.lax.dynamic_slice_in_dim(xs, i * rows, rows)
        y = swiglu(_expert_weights(w, block_expert[i]), x)
        return jax.lax.dynamic_update_slice_in_dim(ys, y, i * rows, 0)

    return jax.lax.fori_loop(0, live_blocks, body, jnp.zeros_like(xs))


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def routed_experts(rows, x, gates, w, plan):
    """``y[n] = Σ_k gates[n, k] · E_{chosen[n, k]}(x[n])`` over the pairs
    whose expert is held; ``plan`` is :func:`dispatch_plan`'s first five."""
    return _routed_fwd(rows, x, gates, w, plan)[0]


def _routed_fwd(rows, x, gates, w, plan):
    slot, slot_token, _, block_expert, live_blocks = plan
    xs = _rows(x, slot_token)
    ys = _expert_blocks(rows, xs, block_expert, live_blocks, w)
    y = jnp.zeros_like(x)
    for k in range(gates.shape[1]):       # one [N, D] gather a choice, not [N, k, D]
        y = y + gates[:, k, None] * _rows(ys, slot[:, k])
    return y, (xs, gates, w, plan)


def _routed_bwd(rows, res, dy):
    """Gathers both ways: a row of the dispatch buffer holds one pair, so
    what autodiff would scatter-add is read back by index instead. The
    blocks' hidden activations are recomputed, not kept."""
    xs, gates, w, plan = res
    slot, slot_token, slot_choice, block_expert, live_blocks = plan
    k = gates.shape[1]
    slot_gate = _rows(gates.reshape(-1), slot_token * k + slot_choice)
    dy_rows = _rows(dy, slot_token)                           # [P, D], not yet gated

    def body(i, carry):
        d_xs, d_slot_gate, d_w = carry
        e = block_expert[i]
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * rows, rows)  # noqa: E731
        x, g_out, gate = cut(xs), cut(dy_rows), cut(slot_gate)
        gate_w, up_w, down_w = (_expert_weights(w, e)[n] for n in ("gate", "up", "down"))
        a, u = x @ gate_w, x @ up_w
        sig = jax.nn.sigmoid(a)
        s = a * sig                                           # silu(a)
        hidden = s * u
        d_gate = jnp.sum(g_out * (hidden @ down_w), axis=-1)  # <dy, E(x)> of each pair
        g_y = gate[:, None] * g_out
        g_h = g_y @ down_w.T
        g_a, g_u = g_h * u * sig * (1.0 + a * (1.0 - sig)), g_h * s
        add = lambda name, g: jax.lax.dynamic_update_index_in_dim(  # noqa: E731
            d_w[name], jax.lax.dynamic_index_in_dim(d_w[name], e, keepdims=False) + g,
            e, 0)
        d_w = {"gate": add("gate", x.T @ g_a), "up": add("up", x.T @ g_u),
               "down": add("down", hidden.T @ g_y)}
        g_x = g_a @ gate_w.T + g_u @ up_w.T
        return (jax.lax.dynamic_update_slice_in_dim(d_xs, g_x, i * rows, 0),
                jax.lax.dynamic_update_slice_in_dim(d_slot_gate, d_gate, i * rows, 0),
                d_w)

    d_xs, d_slot_gate, d_w = jax.lax.fori_loop(
        0, live_blocks, body,
        (jnp.zeros_like(xs), jnp.zeros_like(slot_gate),
         jax.tree_util.tree_map(jnp.zeros_like, w)))
    dx = jnp.zeros_like(dy)
    for j in range(k):
        dx = dx + _rows(d_xs, slot[:, j])
    return dx, _rows(d_slot_gate, slot), d_w, None


routed_experts.defvjp(_routed_fwd, _routed_bwd)


def expert_layer(cfg: TorsoConfig, p: dict, x):
    """The whole expert layer on ``[N, D]`` tokens: held routed experts +
    the shared expert. Also returns ``(load [held], dropped)``."""
    chosen, gates = route(cfg, p, x)
    *plan, load = dispatch_plan(cfg, chosen)
    slot, slot_token = plan[:2]
    held = slot < slot_token.shape[0]              # the pair's expert is held here
    y = routed_experts(cfg.expert_block_rows, x, jnp.where(held, gates, 0.0),
                       p["experts"], tuple(plan))
    placed = jnp.sum(slot_token < x.shape[0], dtype=jnp.int32)
    dropped = jnp.sum(held, dtype=jnp.int32) - placed
    return y + swiglu(p["shared"], x), (load, dropped)


# ------------------------------------------------------------- the torso
def _block(cfg: TorsoConfig, moe: bool, x, p, bias, cos, sin):
    b, t, d = x.shape
    with phase("agent.attention"):
        x = x + in_chunks(
            lambda xc, bc: mla(cfg, p["attn"], xc, bc, cos, sin), cfg.batch_chunks,
            rms_norm(x, p["attn_norm"], cfg.rms_norm_eps), bias)
    normed = rms_norm(x, p["ffn_norm"], cfg.rms_norm_eps)
    if not moe:
        return x + in_chunks(partial(swiglu, p["ffn"]), cfg.batch_chunks, normed), None
    with phase("agent.experts"):
        y, stats = expert_layer(cfg, p["ffn"], normed.reshape(b * t, d))
    return x + y.reshape(b, t, d), stats


def torso_apply(cfg: TorsoConfig, params: dict, obs, valid):
    """``obs [B, T, O]``, ``valid [B, T]`` bool → ``(h [B, D], stats)``:
    the last position's state after the final norm, and the expert layers'
    routing counts ``{"load": [L, held] int32, "dropped": [L] int32}``."""
    x = obs @ params["embed"]["kernel"] + params["embed"]["bias"]
    bias = attention_bias(valid)
    cos, sin = rope_tables(cfg, obs.shape[1])

    stats = []
    for i, p in enumerate(params["layers"]):
        moe = i >= cfg.first_k_dense_replace
        x, layer_stats = jax.checkpoint(partial(_block, cfg, moe))(x, p, bias, cos, sin)
        if moe:
            stats.append(layer_stats)
    load, dropped = (jnp.stack(part) for part in zip(*stats))
    h = rms_norm(x[:, -1], params["final_norm"], cfg.rms_norm_eps)
    return h, {"load": load, "dropped": dropped}
