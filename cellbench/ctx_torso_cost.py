"""What one grad step of a torso whose attention runs under an indexer
needs, computed from shapes.

Matrix-multiply FLOPs only (2 per multiply-add), what the forward and
backward passes *require*: the keys a query attends to (its ``index_topk``
best, or the ``t + 1`` it can see), the causal half of the index scores,
the routed experts at the pairs a uniform router sends to the experts held
here (``tokens · k · held / E``), nothing recomputed under
``jax.checkpoint`` and nothing of a masked form's dense scores. Elementwise
work, norms, the two top-k's and the rotary turns are left out.

A pass over a token, in multiply-adds (names are the program's
``IndexedTorsoConfig``, which follow the published config's):

  embed      obs_dim · hidden
  attention  hidden·H·d (q) + 2·hidden·G·d (k, v) + H·d·hidden (o)
             + mean_t min(t + 1, topk) · H · 2d        scores and P·v, chosen keys
  indexer    hidden·J·e (q) + hidden·e (k) + hidden·J (w)
             + (T + 1)/2 · J · e                         index scores, causal
  experts    hidden·E (router) + 3·hidden·moe_intermediate · k·held/E

A grad step (``agent/d4pg.py:train_step``): the target torso forward on s′;
the critic's torso forward on s, and backward — the weights' gradients once
more and the inputs' once more, less the embedding's input and the
indexer's projections' (its input is cut from the graph); the choice has
no backward pass; the actor reads the critic pass's output, so no third
pass. The heads are ``model_cost``'s networks reading ``hidden`` features.
"""

from __future__ import annotations

from cellbench import model_cost


def mean_keys(window: int, topk: int) -> float:
    """Mean over queries t = 0…T−1 of ``min(t + 1, topk)``."""
    full = min(window, topk)
    return (full * (full + 1) / 2 + (window - full) * topk) / window


def macs_per_token(t: dict, obs_dim: int) -> dict:
    """Multiply-adds of one forward pass over one token, by part; ``t`` is
    the configuration file's ``torso``."""
    d, h, g, hd = (t["hidden_size"], t["num_attention_heads"], t["num_key_value_heads"],
                   t["head_dim"])
    j, e, layers = t["index_n_heads"], t["index_head_dim"], t["num_hidden_layers"]
    assert t["first_k_dense_replace"] == 0 and t["n_shared_experts"] == 0
    routed = t["num_experts_per_tok"] * t["experts_held"] / t["n_routed_experts"]
    return {
        "embed": obs_dim * d,
        "attention_projections": layers * (2 * d * h * hd + 2 * d * g * hd),
        "attention_scores": layers * mean_keys(t["window"], t["index_topk"]) * h * 2 * hd,
        "indexer_projections": layers * (d * j * e + d * e + d * j),
        "index_scores": layers * (t["window"] + 1) / 2 * j * e,
        "experts": layers * (d * t["n_routed_experts"]
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
    no_input_gradient = per_token["embed"] + per_token["indexer_projections"]
    parts = {
        "target_forward": 2 * tokens * forward,
        "critic_forward": 2 * tokens * forward,
        "critic_backward": 2 * tokens * (2 * forward - no_input_gradient),
        "heads": heads,
    }
    parts["total"] = sum(parts.values())
    return parts
