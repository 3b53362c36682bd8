"""What one grad step of a torso configuration needs, computed from shapes.

Matrix-multiply FLOPs only (2 per multiply-add), what the forward and
backward passes *require*: nothing recomputed under ``jax.checkpoint``, the
causal half of the attention scores, the routed experts at the pairs a
uniform router sends to the experts held here (``tokens · k · held / E``).
Elementwise work, norms, the router's top-k and the rotary turn are left out.

A pass over a token, in multiply-adds (names are the published config's):

  embed      obs_dim · hidden
  MLA        hidden·q_lora + q_lora·H·(nope+rope) + hidden·(kv_lora+rope)
             + kv_lora·H·(nope+v) + H·v·hidden
             + (T+1)/2 · H · ((nope+rope) + v)       scores and P·v, causal
  dense FFN  3 · hidden · intermediate_size
  expert FFN hidden·E  +  3·hidden·moe_intermediate · (shared + k·held/E)

A grad step (``agent/d4pg.py:train_step`` with a torso): the target torso
forward on s′; the critic's torso forward on s, and backward — the weights'
gradients once more and the inputs' once more, less the embedding's input;
the actor reads the critic pass's output, so no third pass. The heads are
``model_cost``'s networks reading ``hidden`` features.
"""

from __future__ import annotations

from cellbench import model_cost


def macs_per_token(t: dict, obs_dim: int, window: int) -> dict:
    """Multiply-adds of one forward pass over one token, by part; ``t`` is
    the configuration file's ``torso``."""
    d, h = t["hidden_size"], t["num_attention_heads"]
    qk = t["qk_nope_head_dim"] + t["qk_rope_head_dim"]
    attention = (
        d * t["q_lora_rank"] + t["q_lora_rank"] * h * qk
        + d * (t["kv_lora_rank"] + t["qk_rope_head_dim"])
        + t["kv_lora_rank"] * h * (t["qk_nope_head_dim"] + t["v_head_dim"])
        + h * t["v_head_dim"] * d
    )
    scores = (window + 1) / 2 * h * (qk + t["v_head_dim"])
    routed = t["num_experts_per_tok"] * t["experts_held"] / t["n_routed_experts"]
    expert_ffn = d * t["n_routed_experts"] + 3 * d * t["moe_intermediate_size"] * (
        t["n_shared_experts"] + routed)
    dense, moe = t["first_k_dense_replace"], t["num_hidden_layers"] - t["first_k_dense_replace"]
    return {
        "embed": obs_dim * d,
        "attention": (dense + moe) * (attention + scores),
        "dense_ffn": dense * 3 * d * t["intermediate_size"],
        "expert_ffn": moe * expert_ffn,
    }


def flops_per_grad_step(config: dict) -> dict:
    """``config``: a configuration file (``resolved``, ``torso``)."""
    r, t = config["resolved"], config["torso"]
    per_token = macs_per_token(t, r["obs_dim"], t["window"])
    forward = sum(per_token.values())
    tokens = r["batch_size"] * t["window"]
    heads = model_cost.flops_per_grad_step(
        r["batch_size"], t["hidden_size"], r["action_dim"], tuple(r["hidden_sizes"]),
        r["num_atoms"])["total"]
    parts = {
        "target_forward": 2 * tokens * forward,
        "critic_forward": 2 * tokens * forward,
        "critic_backward": 2 * tokens * (2 * forward - per_token["embed"]),
        "heads": heads,
    }
    parts["total"] = sum(parts.values())
    return parts
