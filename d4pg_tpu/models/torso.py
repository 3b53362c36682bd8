"""A sequence torso over a window of observations, made of stated parts.

Tokens are timesteps: ``x_t = o_t W_in + b_in`` stands where a language
model's embedding stands, positions are 0…T−1 of the window, attention is
causal, and the torso's output is the last position's state after the final
norm. Every block is pre-norm residual: ``x += Attn(RMSNorm(x))``, ``x +=
FFN(RMSNorm(x))``. What a block is made of is stated by its configuration,
one preset a published model:

  attention   ``latent`` — multi-head latent attention with a decoupled
              rotary key shared by the heads (DeepSeek-V3's; ``TorsoConfig``)
              ``grouped_query_indexed`` — grouped-query attention whose keys
              are chosen per query by a learned indexer, which has a loss of
              its own (DeepSeek-V3.2's sparse attention at a Qwen3-MoE
              block's sizes; ``IndexedTorsoConfig``)
              ``gated_delta_hybrid`` — a mixer chosen by the layer's index:
              Gated DeltaNet (a causal depthwise convolution, then the gated
              delta rule's state along the time axis, ``ops/gated_delta.py``)
              and, every ``full_attention_interval``-th layer, grouped-query
              attention with an output gate and a partial rotary turn; norms
              are zero-centred (Qwen3-Next's; ``HybridTorsoConfig``)
  router      ``sigmoid_bias`` — sigmoid scores, a selection bias, top-k,
              renormalised and scaled gates
              ``softmax`` — softmax over all experts, top-k, renormalised
  FFN         ``first_k_dense_replace`` leading dense SwiGLU layers (0…n),
              routed-expert layers after them, ``n_shared_experts`` (0 | 1)
              beside the routed ones, behind a sigmoid gate of its own where
              the kind says so

The attention kinds have widths of their own, so each has its class and
states what it needs (``check_parts``); what they share is ``TorsoShape``.
(``TorsoConfig`` keeps its name and its 24 fields: the accepted benchmark's
configuration file states them.)

The expert layer is **told which experts it holds** (``experts_first``,
``experts_held``): it routes over all ``n_routed_experts``, keeps the pairs
that land on its own experts, and computes those — one chip's share of an
expert-parallel layer, without the exchange. Nothing is dropped: the pairs
are laid out by expert in blocks of ``expert_block_rows`` rows (each held
expert's group padded to whole blocks, the buffer sized for the worst case)
and a loop runs over the *live* blocks only, each gathering its own rows of
the input (and, backward, of the cotangent), so the matrix products and the
rows moved into them follow the routed pairs. No ``lax.ragged_dot`` (a
``tpu_custom_call`` on the v5e) and no scatter of wide rows (XLA:TPU expands
it into a ``while`` of one-row updates): dispatch, combine and both of their
transposes are gathers, which is why the routed part carries its own VJP.

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
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from d4pg_tpu.ops.gated_delta import gated_delta_chunked
from d4pg_tpu.utils.profiling import phase

MASKED = -1e30   # finite: a window position with no valid key stays finite
# One window's score tile — heads x a query chunk x every key, float32 — may
# not pass this: :func:`validate` refuses the combination that would build
# it, whatever the batch chunking does (``in_chunks`` splits B, and B = 1
# falls through it).
MAX_SCORE_TILE_BYTES = 2 ** 30
# What an indexed block under ``jax.checkpoint`` keeps of its forward pass:
# the discrete choices (experts a token, keys a query) — recomputed, a choice
# may flip on a rounding and the backward pass would differentiate another
# function than the forward pass ran — and each query chunk's attention
# output, so that the block's recomputation skips the chunk's forward (its
# own backward recomputes it once, not twice).
KEPT = "torso_kept"


@dataclasses.dataclass(frozen=True, kw_only=True)
class TorsoShape:
    """What every torso states, whatever its attention. Field names follow
    the published ``config.json`` keys of the architecture where it has one."""

    name: str
    hidden_size: int
    num_hidden_layers: int             # every block, the leading dense ones included
    first_k_dense_replace: int
    num_attention_heads: int
    rope_theta: float
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int              # the router's width
    n_shared_experts: int
    num_experts_per_tok: int
    rms_norm_eps: float
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
    batch_chunks: int = 4         # latent attention and the dense SwiGLU run on B/4 windows at a time

    zero_centred_norms: ClassVar[bool] = False   # n(x) = x/rms(x) · (1 + w), w from 0
    shared_expert_gate: ClassVar[bool] = False   # σ(x · w_sg) in front of the shared expert

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def shared_width(self) -> int:
        return self.moe_intermediate_size * self.n_shared_experts

    def mixer(self, layer: int) -> str:
        """The kind of layer ``layer``'s token mixer."""
        return self.attention

    def score_tile_bytes(self) -> int:
        """One window's largest float32 score tile: heads x a query chunk x
        every key, of the layers that build one."""
        return 4 * self.num_attention_heads * (self.window // self.query_chunks) * self.window

    def check_parts(self) -> None:
        raise ValueError(
            f"{type(self).__name__} states no attention: a torso is a TorsoConfig, an "
            "IndexedTorsoConfig or a HybridTorsoConfig")

    def padded_pairs(self, tokens: int) -> int:
        """Rows of the dispatch buffer: every pair that can land on a held
        expert (a token picks an expert at most once), each expert's group
        rounded up to whole blocks."""
        worst = tokens * min(self.num_experts_per_tok, self.experts_held)
        blocks = -(-worst // self.expert_block_rows) + self.experts_held
        return blocks * self.expert_block_rows


@dataclasses.dataclass(frozen=True, kw_only=True)
class TorsoConfig(TorsoShape):
    """Latent attention under a sigmoid router with a selection bias."""

    attention: ClassVar[str] = "latent"
    router: ClassVar[str] = "sigmoid_bias"
    span: ClassVar[str] = "episode"        # a window ends where its episode began
    query_chunks: ClassVar[int] = 1        # one window's scores are one tile

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    routed_scaling_factor: float

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def check_parts(self) -> None:
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")


@dataclasses.dataclass(frozen=True, kw_only=True)
class IndexedTorsoConfig(TorsoShape):
    """Grouped-query attention over the keys a learned indexer chooses,
    under a softmax router."""

    attention: ClassVar[str] = "grouped_query_indexed"
    router: ClassVar[str] = "softmax"

    num_key_value_heads: int
    head_dim: int
    index_n_heads: int
    index_head_dim: int
    index_topk: int                 # keys a query attends to: its ``index_topk`` best
    # queries of one window are taken ``window / query_chunks`` at a time,
    # each chunk against the keys up to its end (the source's q_chunk_size)
    query_chunks: int = 1
    # "episode": a window ends where its episode began. "stream": it spans
    # the episodes of its stream (the context is the stream's history).
    span: str = "episode"
    batch_chunks: int = 1

    def check_parts(self) -> None:
        if self.head_dim % 2 or self.index_head_dim % 2:
            raise ValueError("head_dim and index_head_dim must be even")
        if self.num_attention_heads % self.num_key_value_heads or self.index_topk < 1:
            raise ValueError("query heads in whole groups a key-value head, index_topk >= 1")


@dataclasses.dataclass(frozen=True, kw_only=True)
class HybridTorsoConfig(TorsoShape):
    """Gated DeltaNet layers with a gated grouped-query attention layer every
    ``full_attention_interval``-th, under a softmax router with a gated
    shared expert."""

    attention: ClassVar[str] = "gated_delta_hybrid"
    router: ClassVar[str] = "softmax"
    zero_centred_norms: ClassVar[bool] = True
    shared_expert_gate: ClassVar[bool] = True

    full_attention_interval: int
    # the attention layers
    num_key_value_heads: int
    head_dim: int
    partial_rotary_factor: float
    # the Gated DeltaNet layers
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    shared_expert_intermediate_size: int
    # tokens of one chunk of the delta rule's scan (ops/gated_delta.py)
    delta_chunk: int = 64
    query_chunks: int = 1
    span: str = "episode"
    batch_chunks: int = 1

    @property
    def shared_width(self) -> int:
        return self.shared_expert_intermediate_size * self.n_shared_experts

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def mixer(self, layer: int) -> str:
        return "attention" if (layer + 1) % self.full_attention_interval == 0 else "linear"

    def score_tile_bytes(self) -> int:
        """A DeltaNet layer has no score tile; the attention layers' is
        ``[heads, T / query_chunks, T]`` — where the depth kept holds one."""
        if "attention" not in map(self.mixer, range(self.num_hidden_layers)):
            return 0
        return super().score_tile_bytes()

    def check_parts(self) -> None:
        if self.first_k_dense_replace:
            raise ValueError("the hybrid stack has no leading dense layer")
        if self.full_attention_interval < 1 or self.window % self.delta_chunk:
            raise ValueError(
                f"window {self.window} in whole chunks of {self.delta_chunk} tokens, an "
                f"attention layer every {self.full_attention_interval}-th")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"the rotary turn covers {self.rotary_dim} of {self.head_dim} head dims: "
                "an even count, at most all")
        if (self.num_attention_heads % self.num_key_value_heads
                or self.linear_num_value_heads % self.linear_num_key_heads):
            raise ValueError(
                "query heads in whole groups a key-value head, value heads a key head")
        if self.linear_conv_kernel_dim < 1:
            raise ValueError("linear_conv_kernel_dim >= 1")


# One preset a published model (its widths under its own key names) and a
# toy of the same structure for CPU tests and rehearsals. Depth, the experts
# held, the window and its span are flags of train.py.
TORSO_PRESETS = {
    # zai-org/GLM-4.7-Flash config.json, model_type glm4_moe_lite
    "glm47_flash": TorsoConfig(
        name="glm47_flash", hidden_size=2048, num_hidden_layers=47,
        first_k_dense_replace=1, num_attention_heads=20, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        rope_theta=1_000_000.0, intermediate_size=10240, moe_intermediate_size=1536,
        n_routed_experts=64, n_shared_experts=1, num_experts_per_tok=4,
        routed_scaling_factor=1.8, rms_norm_eps=1e-5,
    ),
    "glm47_flash_tiny": TorsoConfig(
        name="glm47_flash_tiny", hidden_size=32, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=12, kv_lora_rank=8,
        qk_nope_head_dim=6, qk_rope_head_dim=4, v_head_dim=8, rope_theta=1_000_000.0,
        intermediate_size=48, moe_intermediate_size=16, n_routed_experts=8,
        n_shared_experts=1, num_experts_per_tok=2, routed_scaling_factor=1.8,
        rms_norm_eps=1e-5, experts_held=8, window=4, expert_block_rows=8,
    ),
    # Kwai-Keye/Keye-VL-2.0-30B-A3B config.json, model_type KeyeVL2: the
    # language model's block (num_experts -> n_routed_experts, sa_config's
    # indexer_* and topk -> index_*); no dense layer, no shared expert
    "keye_vl2": IndexedTorsoConfig(
        name="keye_vl2", hidden_size=2048, num_hidden_layers=48,
        first_k_dense_replace=0, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, rope_theta=10_000_000.0, intermediate_size=6144,
        moe_intermediate_size=768, n_routed_experts=128, n_shared_experts=0,
        num_experts_per_tok=8, rms_norm_eps=1e-6, index_n_heads=16, index_head_dim=64,
        index_topk=2048, experts_held=128, window=8192, query_chunks=16,
        # experts of width 768: a 256-row block is as much slicing of its
        # expert's weights and read-modify-write of their gradients as matrix
        # products; 512 rows were faster on the chip and steadier over seeds
        # (PERF.md section 6)
        expert_block_rows=512,
    ),
    # index_topk smaller than the window, so the choice is live
    "keye_vl2_tiny": IndexedTorsoConfig(
        name="keye_vl2_tiny", hidden_size=32, num_hidden_layers=2,
        first_k_dense_replace=0, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, rope_theta=10_000_000.0, intermediate_size=48,
        moe_intermediate_size=12, n_routed_experts=16, n_shared_experts=0,
        num_experts_per_tok=4, rms_norm_eps=1e-6, index_n_heads=8, index_head_dim=4,
        index_topk=6, experts_held=16, window=16, query_chunks=4, expert_block_rows=8,
    ),
    # Qwen/Qwen3-Next-80B-A3B-Instruct config.json, model_type qwen3_next
    # (num_experts -> n_routed_experts; one shared expert behind its gate)
    "qwen3_next": HybridTorsoConfig(
        name="qwen3_next", hidden_size=2048, num_hidden_layers=48,
        first_k_dense_replace=0, full_attention_interval=4, num_attention_heads=16,
        num_key_value_heads=2, head_dim=256, partial_rotary_factor=0.25,
        rope_theta=10_000_000.0, linear_num_key_heads=16, linear_num_value_heads=32,
        linear_key_head_dim=128, linear_value_head_dim=128, linear_conv_kernel_dim=4,
        intermediate_size=5120, moe_intermediate_size=512, n_routed_experts=512,
        n_shared_experts=1, shared_expert_intermediate_size=512, num_experts_per_tok=10,
        rms_norm_eps=1e-6, experts_held=512, window=8192, query_chunks=16,
        # 160 rows an expert are live (8,192 x 10 / 512): the expert layer's
        # forward and backward took 29.15 / 28.20 / 29.31 ms at 128 / 256 /
        # 512 rows a block on the chip (PERF.md section 6, PR 34)
        expert_block_rows=256,
    ),
    # three DeltaNet layers and the attention layer; four chunks a window
    "qwen3_next_tiny": HybridTorsoConfig(
        name="qwen3_next_tiny", hidden_size=32, num_hidden_layers=4,
        first_k_dense_replace=0, full_attention_interval=4, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, partial_rotary_factor=0.25,
        rope_theta=10_000_000.0, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=8, linear_conv_kernel_dim=4,
        intermediate_size=48, moe_intermediate_size=12, n_routed_experts=16,
        n_shared_experts=1, shared_expert_intermediate_size=12, num_experts_per_tok=4,
        rms_norm_eps=1e-6, experts_held=16, window=16, query_chunks=4, delta_chunk=4,
        expert_block_rows=8,
    ),
}


def validate(cfg: TorsoShape) -> None:
    if not 0 <= cfg.experts_first <= cfg.experts_first + cfg.experts_held <= cfg.n_routed_experts:
        raise ValueError(
            f"torso holds experts [{cfg.experts_first}, "
            f"{cfg.experts_first + cfg.experts_held}) of {cfg.n_routed_experts}")
    if cfg.experts_held < 1 or cfg.num_moe_layers < 1 or cfg.first_k_dense_replace < 0:
        raise ValueError("torso needs an expert layer and a held expert")
    if cfg.n_shared_experts not in (0, 1):
        raise ValueError("no shared expert or one is what the layer computes")
    cfg.check_parts()             # what the kind's own fields must hold
    if cfg.window % cfg.query_chunks or cfg.span not in ("episode", "stream"):
        raise ValueError(
            f"window {cfg.window} in {cfg.query_chunks} query chunks, span {cfg.span!r}")
    if cfg.score_tile_bytes() > MAX_SCORE_TILE_BYTES:
        raise ValueError(
            f"one window's score tile would be {cfg.score_tile_bytes() / 2 ** 30:.1f} GiB "
            f"({cfg.num_attention_heads} heads x {cfg.window // cfg.query_chunks} queries "
            f"x {cfg.window} keys, float32): batch chunks do not split a window, "
            "raise query_chunks" + (
                "" if cfg.attention != "latent" else " (latent attention has none)"))


def describe_mixers(cfg: TorsoShape) -> dict:
    """What the stack is made of, from the configuration alone: the layers'
    mixers in order and, where the delta rule runs, its chunk length, the
    chunks a window and the bytes of state a window carries through them."""
    mixers = [cfg.mixer(i) for i in range(cfg.num_hidden_layers)]
    out = {"mixers": mixers, "window": cfg.window}
    if "linear" in mixers:
        state = 4 * (cfg.linear_num_value_heads * cfg.linear_key_head_dim
                     * cfg.linear_value_head_dim)
        out.update(chunk=cfg.delta_chunk, chunks_per_window=cfg.window // cfg.delta_chunk,
                   state_bytes_per_window=state * mixers.count("linear"))
    return out


def describe_experts(cfg: TorsoShape, tokens: int) -> dict:
    """The expert layer's by-expert layout for ``tokens`` tokens, from the
    configuration alone: the dispatch buffer's rows (``padded_pairs``), the
    rows of a block, the blocks of the worst case, and the rows each live
    block gathers inside the loop — what a pass moves is
    :func:`gathered_rows` of its ``load``, not the buffer."""
    rows = cfg.expert_block_rows
    return {"tokens": tokens, "padded_pairs": cfg.padded_pairs(tokens), "block_rows": rows,
            "worst_case_blocks": cfg.padded_pairs(tokens) // rows,
            "rows_gathered_a_block": rows}


def gathered_rows(cfg: TorsoShape, load) -> np.ndarray:
    """Rows the block loop gathers a pass, ``Σₑ ⌈loadₑ / R⌉ · R``, from the
    pairs per held expert ``load [..., held]`` (``torso_apply``'s
    ``stats["load"]``, on the host): one figure a layer, against
    ``padded_pairs``."""
    rows = cfg.expert_block_rows
    return np.sum(-(-np.asarray(load) // rows) * rows, axis=-1)


# ------------------------------------------------------------------ init
def _uniform(key, shape, fan_in):
    bound = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def _attention_init(cfg: TorsoShape, ks) -> dict:
    d, h = cfg.hidden_size, cfg.num_attention_heads
    if cfg.attention == "latent":
        return {"attn": {
            "q_a": _uniform(next(ks), (d, cfg.q_lora_rank), d),
            "q_a_norm": jnp.ones((cfg.q_lora_rank,), jnp.float32),
            "q_b": _uniform(next(ks), (cfg.q_lora_rank, h * cfg.qk_head_dim), cfg.q_lora_rank),
            "kv_a": _uniform(next(ks), (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), d),
            "kv_a_norm": jnp.ones((cfg.kv_lora_rank,), jnp.float32),
            "kv_b": _uniform(
                next(ks), (cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                cfg.kv_lora_rank),
            "o": _uniform(next(ks), (h * cfg.v_head_dim, d), h * cfg.v_head_dim),
        }}
    kv, hd, ih, ihd = (cfg.num_key_value_heads, cfg.head_dim, cfg.index_n_heads,
                       cfg.index_head_dim)
    return {
        "attn": {
            "q": _uniform(next(ks), (d, h * hd), d),
            "q_norm": jnp.ones((hd,), jnp.float32),
            "k": _uniform(next(ks), (d, kv * hd), d),
            "k_norm": jnp.ones((hd,), jnp.float32),
            "v": _uniform(next(ks), (d, kv * hd), d),
            "o": _uniform(next(ks), (h * hd, d), h * hd),
        },
        # the indexer: its inputs are cut from the graph, so these leaves take
        # their gradient from its own loss alone (Adam and Polyak as any leaf)
        "indexer": {
            "q": _uniform(next(ks), (d, ih * ihd), d),
            "k": _uniform(next(ks), (d, ihd), d),
            "k_norm": {"scale": jnp.ones((ihd,), jnp.float32),
                       "bias": jnp.zeros((ihd,), jnp.float32)},
            "w": _uniform(next(ks), (d, ih), d),
        },
    }


def _hybrid_mixer_init(cfg: HybridTorsoConfig, ks, kind: str) -> dict:
    """A Gated DeltaNet layer's leaves under ``lin`` (the source's names:
    in_proj_qkvz, in_proj_ba, conv1d, A_log, dt_bias, norm, out_proj) or a
    gated attention layer's under ``attn`` (q carries the output gate)."""
    d = cfg.hidden_size
    if kind == "attention":
        h, kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        return {"attn": {
            "q": _uniform(next(ks), (d, h * hd * 2), d),
            "q_norm": _norm_init(cfg, hd),
            "k": _uniform(next(ks), (d, kv * hd), d),
            "k_norm": _norm_init(cfg, hd),
            "v": _uniform(next(ks), (d, kv * hd), d),
            "o": _uniform(next(ks), (h * hd, d), h * hd),
        }}
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    key_dim, value_dim = hk * cfg.linear_key_head_dim, hv * cfg.linear_value_head_dim
    width = cfg.linear_conv_kernel_dim
    # the source's: A = U(0, 16) and a step dt log-uniform in [1e-3, 1e-1],
    # stored through the inverse of the softplus that reads it
    a = jax.random.uniform(next(ks), (hv,), jnp.float32, 1e-6, 16.0)
    dt = jnp.exp(jax.random.uniform(next(ks), (hv,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {"lin": {
        "in_qkvz": _uniform(next(ks), (d, 2 * key_dim + 2 * value_dim), d),
        "in_ba": _uniform(next(ks), (d, 2 * hv), d),
        "conv": _uniform(next(ks), (2 * key_dim + value_dim, width), width),
        "A_log": jnp.log(a),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "norm": jnp.ones((cfg.linear_value_head_dim,), jnp.float32),
        "out": _uniform(next(ks), (value_dim, d), value_dim),
    }}


def _block_init(cfg: TorsoShape, key, moe: bool, layer: int = 0) -> dict:
    d = cfg.hidden_size
    ks = iter(jax.random.split(key, 16))
    if cfg.attention == "gated_delta_hybrid":
        attention = _hybrid_mixer_init(cfg, ks, cfg.mixer(layer))
    else:
        attention = _attention_init(cfg, ks)

    def swiglu(width, lead=()):
        return {
            "gate": _uniform(next(ks), lead + (d, width), d),
            "up": _uniform(next(ks), lead + (d, width), d),
            "down": _uniform(next(ks), lead + (width, d), width),
        }

    if moe:
        ffn = {"router": _uniform(next(ks), (d, cfg.n_routed_experts), d)}
        if cfg.router == "sigmoid_bias":
            # the selection bias of noaux_tc: a buffer that enters the choice
            # only. Held at its initial value (its update rate is not in the
            # published config); it takes no gradient.
            ffn["router_bias"] = jnp.zeros((cfg.n_routed_experts,), jnp.float32)
        ffn["experts"] = swiglu(cfg.moe_intermediate_size, (cfg.experts_held,))
        if cfg.n_shared_experts:
            ffn["shared"] = swiglu(cfg.shared_width)
            if cfg.shared_expert_gate:
                ffn["shared_gate"] = _uniform(next(ks), (d, 1), d)
    else:
        ffn = swiglu(cfg.intermediate_size)
    return {
        "attn_norm": _norm_init(cfg, d), "ffn_norm": _norm_init(cfg, d),
        **attention, "ffn": ffn,
    }


def _norm_init(cfg: TorsoShape, width: int):
    """A block norm's weight where training starts: the scale is ``w``, or
    ``1 + w`` where the kind's norms are zero-centred."""
    return (jnp.zeros if cfg.zero_centred_norms else jnp.ones)((width,), jnp.float32)


def torso_init(cfg: TorsoShape, key, obs_dim: int) -> dict:
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
            _block_init(cfg, k, moe=i >= cfg.first_k_dense_replace, layer=i)
            for i, k in enumerate(jax.random.split(k_layers, cfg.num_hidden_layers))],
        "final_norm": _norm_init(cfg, cfg.hidden_size),
    }


# --------------------------------------------------------------- pieces
def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight


def block_norm(cfg: TorsoShape, x, weight):
    """The kind's RMSNorm: scale ``w``, or ``1 + w`` where zero-centred."""
    return rms_norm(x, 1.0 + weight if cfg.zero_centred_norms else weight, cfg.rms_norm_eps)


def rope_tables(theta: float, rope: int, positions: int):
    """cos/sin ``[T, rope/2]``: frequency ``theta^(-2i/rope)`` for pair i."""
    half = rope // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
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
    with phase("agent.attention.scores"):
        scores = (jnp.einsum("bthd,bshd->bhts", q_nope, k_nope)
                  + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope))
        scores = scores / math.sqrt(nope + rope) + bias[:, None]
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhts,bshd->bthd", probs, v).reshape(b, t, h * vd)
    return out @ p["o"]


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def index_scores(q, k, w):
    """``I[b, t, s] = Σ_j w[b, t, j] · ReLU(q[b, t, j] · k[b, s])``: the
    lightning indexer's score of key s for query t (``q [B, Tq, J, d]``, one
    key ``k [B, Ts, d]`` for all index heads). Exact zeros are made +0."""
    hit = jax.nn.relu(jnp.einsum("btjd,bsd->btjs", q, k))
    scores = jnp.einsum("btj,btjs->bts", w, hit)
    return jnp.where(scores == 0.0, 0.0, scores)


def kth_largest(scores, k: int):
    """The k-th largest entry of each row of ``scores [..., n]`` (n ≥ k),
    exactly: a radix select on the floats' bits, most significant first —
    32 counting passes over the row, no sort (PERF.md section 6 has what a
    row sort and ``lax.top_k`` cost at ``[512, 8192]``)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    sign = jnp.uint32(0x80000000)
    order = jnp.where(bits >= sign, ~bits, bits | sign)     # unsigned order = float order

    def body(i, prefix):
        trial = prefix | (sign >> i.astype(jnp.uint32))
        enough = jnp.sum(order >= trial[..., None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, trial, prefix)

    kth = jax.lax.fori_loop(0, 32, body, jnp.zeros(scores.shape[:-1], jnp.uint32))
    return jax.lax.bitcast_convert_type(
        jnp.where(kth >= sign, kth & ~sign, ~kth), jnp.float32)


def choose_keys(scores, see, k: int):
    """``[B, Tq, Ts]`` bool: for each query the ``k`` keys of largest score
    among those it may ``see`` — all of them when there are fewer — as
    ``jax.lax.top_k`` orders them (ties to the lower position)."""
    if scores.shape[-1] <= k:
        return see
    scores = jnp.where(see, scores, -jnp.inf)
    kth = kth_largest(scores, k)[..., None]
    above, ties = scores > kth, scores == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    return (above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room))) & see


def _chunk_attention(kv: int, q, k, v, member):
    """Grouped-query softmax attention of one query chunk ``q [B, Tq, H,
    d]`` over the keys ``member [B, Tq, Ts]`` admits (``k, v [B, Ts, kv,
    d]``): ``(out [B, Tq, H·d], probs [B, kv, H/kv, Tq, Ts])``."""
    b, tq, h, hd = q.shape
    with phase("agent.attention"), phase("agent.attention.scores"):
        logits = jnp.einsum("btkgd,bskd->bkgts", q.reshape(b, tq, kv, h // kv, hd), k)
        logits = jnp.where(member[:, None, None], logits / math.sqrt(hd), MASKED)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgts,bskd->btkgd", probs, v).reshape(b, tq, h * hd)
    return out, probs


def _attend(cfg: IndexedTorsoConfig, q, k, v, member, scores, valid_q):
    """One query chunk against the keys up to its end: softmax over the
    chosen keys only, and the chunk's part of the indexer's alignment loss —
    ``KL(p ‖ softmax over the chosen keys of the index scores)`` summed over
    its valid queries, ``p`` the attention's probabilities summed over the
    heads and normalised, cut from the graph."""
    out, probs = _chunk_attention(cfg.num_key_value_heads, q, k, v, member)
    with phase("agent.indexer"):
        p = jax.lax.stop_gradient(jnp.sum(probs, axis=(1, 2)))
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        log_index = jax.nn.log_softmax(jnp.where(member, scores, MASKED), axis=-1)
        kl = jnp.sum(jnp.where(p > 0.0, p * (jnp.log(jnp.where(p > 0.0, p, 1.0)) - log_index),
                               0.0), axis=-1)
        loss = jnp.sum(jnp.where(valid_q, kl, 0.0))
    return out, loss


def indexed_attention(cfg: IndexedTorsoConfig, p: dict, index: dict, x, valid, emit: bool):
    """Grouped-query attention on ``[B, T, D]`` over the keys the indexer
    chooses: ``(out [B, T, D], index_loss, keys [B, T, T] bool | None)``.

    The queries are taken ``T / query_chunks`` at a time against the keys up
    to the chunk's end (block-causal: a window's ``[heads, T, T]`` scores
    never exist), every chunk dense with the keys outside the query's choice
    at ``MASKED`` — no row is moved. The indexer reads its input cut from
    the graph; the choice (kept by the block's checkpoint, never recomputed)
    carries no gradient; ``index_loss`` is the mean over valid queries of the
    alignment loss, whose gradient reaches ``index`` alone."""
    b, t, _ = x.shape
    h, kv, hd, eps = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.rms_norm_eps
    with phase("agent.attention"):
        cos, sin = rope_tables(cfg.rope_theta, hd, t)
        q = apply_rope(rms_norm((x @ p["q"]).reshape(b, t, h, hd), p["q_norm"], eps), cos, sin)
        k = apply_rope(rms_norm((x @ p["k"]).reshape(b, t, kv, hd), p["k_norm"], eps), cos, sin)
        v = (x @ p["v"]).reshape(b, t, kv, hd)
    with phase("agent.indexer"):
        cut = jax.lax.stop_gradient(x)
        j, d = cfg.index_n_heads, cfg.index_head_dim
        cos, sin = rope_tables(cfg.rope_theta, d, t)
        q_i = apply_rope((cut @ index["q"]).reshape(b, t, j, d), cos, sin)
        k_i = apply_rope(layer_norm(cut @ index["k"], index["k_norm"], eps), cos, sin)
        w_i = (cut @ index["w"]) * (j ** -0.5 * d ** -0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    size = t // cfg.query_chunks
    outs, masks, loss = [], [], 0.0
    for lo in range(0, t, size):
        hi = lo + size
        see = causal[lo:hi, :hi][None] & valid[:, None, :hi]
        with phase("agent.indexer"):
            with phase("agent.indexer.scores"):
                scores = jax.checkpoint(index_scores)(q_i[:, lo:hi], k_i[:, :hi], w_i[:, lo:hi])
            with phase("agent.indexer.select"):
                member = checkpoint_name(
                    choose_keys(jax.lax.stop_gradient(scores), see, cfg.index_topk), KEPT)
        out, part = jax.checkpoint(partial(_attend, cfg))(
            q[:, lo:hi], k[:, :hi], v[:, :hi], member, scores, valid[:, lo:hi])
        outs.append(checkpoint_name(out, KEPT))
        loss = loss + part
        if emit:
            masks.append(jnp.pad(member, ((0, 0), (0, 0), (0, t - hi))))
    with phase("agent.attention"):
        out = jnp.concatenate(outs, axis=1) @ p["o"]
    with phase("agent.indexer"):
        loss = loss / jnp.maximum(jnp.sum(valid), 1)
    return out, loss, jnp.concatenate(masks, axis=1) if emit else None


def gated_attention(cfg: HybridTorsoConfig, p: dict, x, valid):
    """Grouped-query attention on ``[B, T, D]`` whose query projection
    carries an output gate: ``(q, gate) = x W_q`` per head, zero-centred
    norms on q and k per head, the rotary turn on the first ``rotary_dim``
    head dims, causal softmax over the valid keys, ``(attn ⊙ σ(gate)) W_o``.
    The queries run ``T / query_chunks`` at a time through the indexed
    kind's chunk path, every causal valid key a member."""
    b, t, _ = x.shape
    h, kv, hd, rot = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.rotary_dim
    with phase("agent.attention"):
        cos, sin = rope_tables(cfg.rope_theta, rot, t)
        turn = lambda y: jnp.concatenate(  # noqa: E731
            [apply_rope(y[..., :rot], cos, sin), y[..., rot:]], axis=-1)
        q_gate = (x @ p["q"]).reshape(b, t, h, 2 * hd)
        q = turn(block_norm(cfg, q_gate[..., :hd], p["q_norm"]))
        gate = q_gate[..., hd:].reshape(b, t, h * hd)
        k = turn(block_norm(cfg, (x @ p["k"]).reshape(b, t, kv, hd), p["k_norm"]))
        v = (x @ p["v"]).reshape(b, t, kv, hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    size = t // cfg.query_chunks
    outs = []
    for lo in range(0, t, size):
        hi = lo + size
        see = causal[lo:hi, :hi][None] & valid[:, None, :hi]
        out = jax.checkpoint(lambda *a: _chunk_attention(kv, *a)[0])(
            q[:, lo:hi], k[:, :hi], v[:, :hi], see)
        outs.append(checkpoint_name(out, KEPT))
    with phase("agent.attention"):
        return (jnp.concatenate(outs, axis=1) * jax.nn.sigmoid(gate)) @ p["o"]


@jax.custom_vjp
def causal_conv(x, weight):
    """Depthwise convolution over time of ``x [B, T, C]`` with ``weight [C,
    W]``, zeros before the window: ``y_t = Σ_j weight[:, j] · x_{t−(W−1)+j}``.
    Its backward pass is written out (the same shifted sum run forward in
    time, and one reduction a tap): autodiff's pads each tap's ``[T, C]``
    product by itself, W of them held at once."""
    t, width = x.shape[1], weight.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * weight[:, j] for j in range(width))


def _conv_fwd(x, weight):
    return causal_conv(x, weight), (x, weight)


def _conv_bwd(res, dy):
    x, weight = res
    t, width = x.shape[1], weight.shape[1]
    ahead = jnp.pad(dy, ((0, 0), (0, width - 1), (0, 0)))
    dx = sum(ahead[:, width - 1 - j:width - 1 - j + t] * weight[:, j] for j in range(width))
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    d_weight = jnp.stack(
        [jnp.sum(padded[:, j:j + t] * dy, axis=(0, 1)) for j in range(width)], axis=1)
    return dx, d_weight


causal_conv.defvjp(_conv_fwd, _conv_bwd)


def gated_delta_net(cfg: HybridTorsoConfig, p: dict, x, valid):
    """Gated DeltaNet on the block's normed input ``[B, T, D]``: ``(q, k, v,
    z) = x W_qkvz`` laid out by key head, ``(b, a) = x W_ba``; ``(q, k, v) ←
    SiLU(conv(q ‖ k ‖ v))``; per value head (key head ``h // r``) ``q̂ =
    q/‖q‖ · dk^-½``, ``k̂ = k/‖k‖``, ``β = σ(b)``, ``g = −exp(A_log) ·
    softplus(a + dt_bias)``, the gated delta rule in chunks; then ``y = (w ⊙
    o / rms(o)) ⊙ SiLU(z)`` per head and ``W_out``. Positions outside the
    window (a prefix) have their input set to zero: they write nothing."""
    b, t, _ = x.shape
    hk, hv, dk, dv = (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
                      cfg.linear_key_head_dim, cfg.linear_value_head_dim)
    r = hv // hk
    with phase("agent.linear_attention"):
        x = jnp.where(valid[..., None], x, 0.0)
        qkvz = (x @ p["in_qkvz"]).reshape(b, t, hk, 2 * dk + 2 * r * dv)
        ba = (x @ p["in_ba"]).reshape(b, t, hk, 2 * r)
        z = qkvz[..., 2 * dk + r * dv:].reshape(b, t, hv, dv)
        mixed = jnp.concatenate(
            [qkvz[..., :dk].reshape(b, t, hk * dk), qkvz[..., dk:2 * dk].reshape(b, t, hk * dk),
             qkvz[..., 2 * dk:2 * dk + r * dv].reshape(b, t, hv * dv)], axis=-1)
        mixed = jax.nn.silu(causal_conv(mixed, p["conv"]))
        unit = lambda y: y * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)
        q = unit(mixed[..., :hk * dk].reshape(b, t, hk, dk)) * dk ** -0.5
        k = unit(mixed[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk))
        v = mixed[..., 2 * hk * dk:].reshape(b, t, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :r].reshape(b, t, hv))
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., r:].reshape(b, t, hv) + p["dt_bias"])
        out, _ = gated_delta_chunked(
            jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v, g, beta, cfg.delta_chunk)
        y = rms_norm(out, p["norm"], cfg.rms_norm_eps) * jax.nn.silu(z)
        return y.reshape(b, t, hv * dv) @ p["out"]


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
def route(cfg: TorsoShape, p: dict, x):
    """``(chosen [N, k] int32, gates [N, k])``. ``sigmoid_bias``: sigmoid
    scores, the top-k of score + bias, gates = scale · score / Σ chosen
    scores. ``softmax``: probabilities over all experts, their top-k, gates =
    probability / Σ chosen probabilities."""
    if cfg.router == "softmax":
        scores = jax.nn.softmax(x @ p["router"], axis=-1)
        _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores), cfg.num_experts_per_tok)
        chosen = checkpoint_name(chosen, KEPT)
        picked = jnp.take_along_axis(scores, chosen, axis=-1)
        return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(
        jax.lax.stop_gradient(scores + p["router_bias"]), cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = cfg.routed_scaling_factor * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen, gates


def dispatch_plan(cfg: TorsoShape, chosen):
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


def _expert_blocks(rows, x, slot_token, block_expert, live_blocks, w):
    """``ys [P, D]``: each live block through its expert, its ``rows`` rows
    of ``x`` gathered by the loop itself — the by-expert layout of ``x`` is
    never made whole."""
    def body(i, ys):
        with phase("agent.experts.dispatch"):
            xb = _rows(x, jax.lax.dynamic_slice_in_dim(slot_token, i * rows, rows))
        with phase("agent.experts.blocks"):
            y = swiglu(_expert_weights(w, block_expert[i]), xb)
            return jax.lax.dynamic_update_slice_in_dim(ys, y, i * rows, 0)

    with phase("agent.experts.dispatch"):
        ys = jnp.zeros((slot_token.shape[0], x.shape[1]), x.dtype)
    return jax.lax.fori_loop(0, live_blocks, body, ys)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def routed_experts(rows, x, gates, w, plan):
    """``y[n] = Σ_k gates[n, k] · E_{chosen[n, k]}(x[n])`` over the pairs
    whose expert is held; ``plan`` is :func:`dispatch_plan`'s first five."""
    return _routed_fwd(rows, x, gates, w, plan)[0]


def _routed_fwd(rows, x, gates, w, plan):
    slot, slot_token, _, block_expert, live_blocks = plan
    ys = _expert_blocks(rows, x, slot_token, block_expert, live_blocks, w)
    with phase("agent.experts.dispatch"):
        y = jnp.zeros_like(x)
        for k in range(gates.shape[1]):   # one [N, D] gather a choice, not [N, k, D]
            y = y + gates[:, k, None] * _rows(ys, slot[:, k])
    return y, (x, gates, w, plan)


def _routed_bwd(rows, res, dy):
    """Gathers both ways: a row of the dispatch buffer holds one pair, so
    what autodiff would scatter-add is read back by index instead. Each live
    block gathers its own rows of ``x`` and ``dy``; the blocks' hidden
    activations are recomputed, not kept."""
    x, gates, w, plan = res
    slot, slot_token, slot_choice, block_expert, live_blocks = plan
    k = gates.shape[1]
    with phase("agent.experts.dispatch"):
        slot_gate = _rows(gates.reshape(-1), slot_token * k + slot_choice)   # [P], one word a row

    def body(i, carry):
        d_xs, d_slot_gate, d_w = carry
        e = block_expert[i]
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * rows, rows)  # noqa: E731
        with phase("agent.experts.dispatch"):
            token = cut(slot_token)
            xb, g_out = _rows(x, token), _rows(dy, token)     # g_out not yet gated
        with phase("agent.experts.blocks"):
            gate = cut(slot_gate)
            gate_w, up_w, down_w = (_expert_weights(w, e)[n] for n in ("gate", "up", "down"))
            a, u = xb @ gate_w, xb @ up_w
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
            d_w = {"gate": add("gate", xb.T @ g_a), "up": add("up", xb.T @ g_u),
                   "down": add("down", hidden.T @ g_y)}
            g_x = g_a @ gate_w.T + g_u @ up_w.T
            return (jax.lax.dynamic_update_slice_in_dim(d_xs, g_x, i * rows, 0),
                    jax.lax.dynamic_update_slice_in_dim(d_slot_gate, d_gate, i * rows, 0),
                    d_w)

    with phase("agent.experts.dispatch"):
        empty = jnp.zeros((slot_token.shape[0], x.shape[1]), x.dtype), jnp.zeros_like(slot_gate)
    with phase("agent.experts.blocks"):
        d_w = jax.tree_util.tree_map(jnp.zeros_like, w)
    d_xs, d_slot_gate, d_w = jax.lax.fori_loop(0, live_blocks, body, (*empty, d_w))
    with phase("agent.experts.dispatch"):
        dx = jnp.zeros_like(dy)
        for j in range(k):
            dx = dx + _rows(d_xs, slot[:, j])
        return dx, _rows(d_slot_gate, slot), d_w, None


routed_experts.defvjp(_routed_fwd, _routed_bwd)


def expert_layer(cfg: TorsoShape, p: dict, x, chosen_too: bool = False):
    """The whole expert layer on ``[N, D]`` tokens: held routed experts +
    the shared expert where there is one. Also returns ``(load [held],
    dropped)`` — and the experts each token chose ``[N, k]``, if asked."""
    with phase("agent.experts.route"):
        chosen, gates = route(cfg, p, x)
        *plan, load = dispatch_plan(cfg, chosen)
    slot, slot_token = plan[:2]
    held = slot < slot_token.shape[0]              # the pair's expert is held here
    y = routed_experts(cfg.expert_block_rows, x, jnp.where(held, gates, 0.0),
                       p["experts"], tuple(plan))
    placed = jnp.sum(slot_token < x.shape[0], dtype=jnp.int32)
    dropped = jnp.sum(held, dtype=jnp.int32) - placed
    if cfg.shared_expert_gate and cfg.n_shared_experts:
        y = y + jax.nn.sigmoid(x @ p["shared_gate"]) * swiglu(p["shared"], x)
    elif cfg.n_shared_experts:
        y = y + swiglu(p["shared"], x)
    return y, ((load, dropped, chosen) if chosen_too else (load, dropped))


# ------------------------------------------------------------- the torso
def _ffn(cfg: TorsoShape, moe: bool, x, p, chosen_too: bool = False):
    b, t, d = x.shape
    normed = block_norm(cfg, x, p["ffn_norm"])
    if not moe:
        return x + in_chunks(partial(swiglu, p["ffn"]), cfg.batch_chunks, normed), None
    with phase("agent.experts"):
        y, stats = expert_layer(cfg, p["ffn"], normed.reshape(b * t, d), chosen_too)
    return x + y.reshape(b, t, d), stats


def _block(cfg: TorsoConfig, moe: bool, x, p, bias, cos, sin):
    with phase("agent.attention"):
        x = x + in_chunks(
            lambda xc, bc: mla(cfg, p["attn"], xc, bc, cos, sin), cfg.batch_chunks,
            rms_norm(x, p["attn_norm"], cfg.rms_norm_eps), bias)
    return _ffn(cfg, moe, x, p)


def _indexed_block(cfg: IndexedTorsoConfig, moe: bool, emit: bool, x, p, valid):
    with phase("agent.attention"):
        normed = rms_norm(x, p["attn_norm"], cfg.rms_norm_eps)
    out, index_loss, keys = indexed_attention(cfg, p["attn"], p["indexer"], normed, valid, emit)
    x, stats = _ffn(cfg, moe, x + out, p, chosen_too=True)
    load_dropped, experts = (stats[:2], stats[2]) if moe else (None, None)
    return x, (load_dropped, index_loss, keys, experts if emit else None)


def _hybrid_mixer(cfg: HybridTorsoConfig, kind: str, x, p, valid):
    """``x + mixer(n₁(x))``; ``p`` holds ``attn_norm`` and the mixer's leaves."""
    normed = block_norm(cfg, x, p["attn_norm"])
    if kind == "attention":
        return x + gated_attention(cfg, p["attn"], normed, valid)
    return x + gated_delta_net(cfg, p["lin"], normed, valid)


def _hybrid_ffn(cfg: HybridTorsoConfig, emit: bool, x, p):
    x, (load, dropped, experts) = _ffn(cfg, True, x, p, chosen_too=True)
    return x, ((load, dropped), experts if emit else None)


def torso_apply(cfg: TorsoShape, params: dict, obs, valid, emit_choices: bool = False):
    """``obs [B, T, O]``, ``valid [B, T]`` bool → ``(h [B, D], stats)``:
    the last position's state after the final norm, and the expert layers'
    routing counts ``{"load": [L, held] int32, "dropped": [L] int32}``.
    Under an indexer ``stats`` also holds ``index_loss`` (the layers'
    alignment losses added) and, with ``emit_choices``, what the pass chose:
    ``keys [L, B, T, T]`` bool and ``experts [L_moe, B·T, k]`` int32. The
    hybrid stack has no index loss and no keys to choose: with
    ``emit_choices`` it adds ``experts`` alone."""
    x = obs @ params["embed"]["kernel"] + params["embed"]["bias"]
    indexed = cfg.attention == "grouped_query_indexed"
    hybrid = cfg.attention == "gated_delta_hybrid"
    if not (indexed or hybrid):
        bias = attention_bias(valid)
        cos, sin = rope_tables(cfg.rope_theta, cfg.qk_rope_head_dim, obs.shape[1])

    stats, index_loss, keys, experts = [], 0.0, [], []
    for i, p in enumerate(params["layers"]):
        moe = i >= cfg.first_k_dense_replace
        if indexed:
            x, (layer_stats, loss, layer_keys, layer_experts) = jax.checkpoint(
                partial(_indexed_block, cfg, moe, emit_choices),
                policy=jax.checkpoint_policies.save_only_these_names(KEPT))(x, p, valid)
            index_loss = index_loss + loss
            keys.append(layer_keys)
            experts += [layer_experts] if moe else []
        elif hybrid:
            # Two checkpoints a block, the mixer's and the expert layer's:
            # each is recomputed once, as one around both would be, and the
            # backward pass holds one part's intermediates at a time (a
            # DeltaNet layer's and the dispatch buffers together pass a
            # chip). They keep the expert choices and the attention chunks'
            # outputs (KEPT), as the indexed block's checkpoint does.
            keep = jax.checkpoint_policies.save_only_these_names(KEPT)
            mixer = {k: v for k, v in p.items() if k not in ("ffn", "ffn_norm")}
            x = jax.checkpoint(partial(_hybrid_mixer, cfg, cfg.mixer(i)), policy=keep)(
                x, mixer, valid)
            x, (layer_stats, layer_experts) = jax.checkpoint(
                partial(_hybrid_ffn, cfg, emit_choices), policy=keep)(
                    x, {"ffn": p["ffn"], "ffn_norm": p["ffn_norm"]})
            experts.append(layer_experts)
        else:
            x, layer_stats = jax.checkpoint(partial(_block, cfg, moe))(x, p, bias, cos, sin)
        if moe:
            stats.append(layer_stats)
    load, dropped = (jnp.stack(part) for part in zip(*stats))
    h = block_norm(cfg, x[:, -1], params["final_norm"])
    stats = {"load": load, "dropped": dropped}
    if hybrid and emit_choices:
        stats["experts"] = jnp.stack(experts)
    if indexed:
        stats["index_loss"] = index_loss
        if emit_choices:
            stats.update(keys=jnp.stack(keys), experts=jnp.stack(experts))
    return h, stats
