"""What one D4PG grad step needs, computed from shapes.

FLOPs are the matrix multiplications of the four networks, forward and
backward, nothing recomputed: 2·B·in·out per layer forward, the same again
for each of dW and dX where the gradient is needed. Elementwise work
(softmax, projection, cross-entropy, Adam, Polyak) is left out: it is a few
operations per parameter or per atom and has no matrix-unit peak to be held
against. Bytes are the least HBM traffic of the parameter state plus the
batch rows, for the bandwidth side of the same question.

The networks (``d4pg_tpu/models``): actor obs→H…→act; critic obs→H, then
[h, action]→H, →H…, →atoms.
"""

from __future__ import annotations


def actor_layers(obs_dim: int, act_dim: int, hidden: tuple) -> list:
    dims = [obs_dim, *hidden, act_dim]
    return list(zip(dims[:-1], dims[1:]))


def critic_layers(obs_dim: int, act_dim: int, hidden: tuple, atoms: int) -> list:
    layers = [(obs_dim, hidden[0]), (hidden[0] + act_dim, hidden[1])]
    layers += list(zip(hidden[1:-1], hidden[2:]))
    return layers + [(hidden[-1], atoms)]


def _mm(layers) -> int:
    """Multiply-adds of one pass over ``layers``, per sample."""
    return sum(i * o for i, o in layers)


def param_count(obs_dim, act_dim, hidden, atoms) -> dict:
    count = lambda layers: sum(i * o + o for i, o in layers)  # noqa: E731
    return {
        "actor": count(actor_layers(obs_dim, act_dim, hidden)),
        "critic": count(critic_layers(obs_dim, act_dim, hidden, atoms)),
    }


def flops_per_grad_step(
    batch: int, obs_dim: int, act_dim: int, hidden: tuple, atoms: int
) -> dict:
    """Matrix-multiply FLOPs of one grad step, by part.

    - targets: target actor and target critic, forward on s';
    - critic: forward on (s, a), dW for every layer, dX for every layer but
      the first (obs needs no gradient) — and of layer 1 only the h columns
      (the action is data here);
    - actor: actor forward on s, critic forward on (s, μ(s)); back through
      the critic to the action only (dX of layers 2…, of layer 1 only the
      action columns, no critic dW, nothing through layer 0), then actor dW
      for every layer and dX for every layer but the first.
    """
    a = actor_layers(obs_dim, act_dim, hidden)
    c = critic_layers(obs_dim, act_dim, hidden, atoms)
    width1 = c[1][1]
    parts = {
        "targets": _mm(a) + _mm(c),
        "critic": 2 * _mm(c) + hidden[0] * width1 + _mm(c[2:]),
        "actor": (_mm(a) + _mm(c) + act_dim * width1 + _mm(c[2:])
                  + _mm(a) + _mm(a[1:])),
    }
    parts = {k: 2 * batch * v for k, v in parts.items()}
    parts["total"] = sum(parts.values())
    return parts


def bytes_per_grad_step(
    batch: int, obs_dim: int, act_dim: int, hidden: tuple, atoms: int,
    bytes_per_el: int = 4,
) -> dict:
    """Least HBM traffic of one grad step: each online net reads θ, m, v and
    writes them back (6P), each target reads and writes θ' (2P); the batch
    is gathered from the ring once and read once (rows of obs, action,
    reward, next_obs, discount, plus the IS weight)."""
    p = param_count(obs_dim, act_dim, hidden, atoms)
    params = 8 * (p["actor"] + p["critic"]) * bytes_per_el
    row = (2 * obs_dim + act_dim + 2) * bytes_per_el
    rows = batch * (2 * row + bytes_per_el)
    return {"param_state": params, "batch_rows": rows, "total": params + rows}


def cost_for(agent_cfg, batch: int) -> dict:
    """The numbers the ``arithmetic`` reducer reads, from the program's own
    resolved agent configuration (sizes only)."""
    shape = (
        batch, agent_cfg.obs_dim, agent_cfg.action_dim,
        tuple(agent_cfg.hidden_sizes), agent_cfg.dist.num_atoms,
    )
    return {
        "flops_per_grad_step": flops_per_grad_step(*shape)["total"],
        "bytes_per_grad_step": bytes_per_grad_step(*shape)["total"],
    }
