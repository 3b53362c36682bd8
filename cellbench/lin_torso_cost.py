"""What one grad step of the hybrid torso (Gated DeltaNet layers with a
gated attention layer every ``full_attention_interval``-th) needs, computed
from shapes.

Matrix-multiply FLOPs only (2 per multiply-add), what the forward and
backward passes *require*: the delta rule in its recurrent form (a token
reads the state twice and writes it once, ``3 · dk · dv`` a value head —
the chunked form the program runs spends more: its Gram products, the
triangular solve and the products with its inverse are not required), the
causal half of the attention scores, the routed experts at the pairs a
uniform router sends to the experts held here (``tokens · k · held / E``),
nothing recomputed under ``jax.checkpoint``. Elementwise work — the
convolution's four taps a channel, norms, decays, gates, the rotary turn,
the top-k — is left out.

A pass over a token, in multiply-adds (names are the program's
``HybridTorsoConfig``, which follow the published config's):

  embed      obs_dim · hidden
  DeltaNet   hidden·(2·Hk·dk + 2·Hv·dv) (q, k, v, z) + hidden·2·Hv (b, a)
             + Hv·dv·hidden (out)  +  3·Hv·dk·dv            the recurrence
  attention  hidden·2·H·d (q and its gate) + 2·hidden·G·d (k, v) + H·d·hidden (o)
             + (T + 1)/2 · H · 2d                          scores and P·v, causal
  experts    hidden·E (router) + 3·hidden·shared + hidden (shared expert, its gate)
             + 3·hidden·moe_intermediate · k·held/E

A grad step (``agent/d4pg.py:train_step``): the target torso forward on s′;
the critic's torso forward on s, and backward — the weights' gradients once
more and the inputs' once more, less the embedding's input; the actor reads
the critic pass's output, so no third pass. The heads are ``model_cost``'s
networks reading ``hidden`` features.
"""

from __future__ import annotations

from cellbench import model_cost


def macs_per_token(t: dict, obs_dim: int) -> dict:
    """Multiply-adds of one forward pass over one token, by part; ``t`` is
    the configuration file's ``torso``."""
    d, h, g, hd = (t["hidden_size"], t["num_attention_heads"], t["num_key_value_heads"],
                   t["head_dim"])
    hk, hv, dk, dv = (t["linear_num_key_heads"], t["linear_num_value_heads"],
                      t["linear_key_head_dim"], t["linear_value_head_dim"])
    assert t["first_k_dense_replace"] == 0 and t["n_shared_experts"] == 1
    layers = t["num_hidden_layers"]
    full = layers // t["full_attention_interval"]       # layer i where (i + 1) % interval == 0
    linear = layers - full
    routed = t["num_experts_per_tok"] * t["experts_held"] / t["n_routed_experts"]
    return {
        "embed": obs_dim * d,
        "delta_projections": linear * (d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d),
        "delta_recurrence": linear * 3 * hv * dk * dv,
        "attention_projections": full * (d * 2 * h * hd + 2 * d * g * hd + h * hd * d),
        "attention_scores": full * (t["window"] + 1) / 2 * h * 2 * hd,
        "experts": layers * (d * t["n_routed_experts"]
                             + 3 * d * t["shared_expert_intermediate_size"] + d
                             + 3 * d * t["moe_intermediate_size"] * routed),
    }


def flops_per_grad_step(config: dict) -> dict:
    """``config``: a configuration file (``resolved``, ``torso``)."""
    r, t = config["resolved"], config["torso"]
    per_token = macs_per_token(t, r["obs_dim"])
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
