"""The comparisons that decide ``correct``. Checks 1 and 2 run in set-up at
the cell's own widths; the drivers add what can only be seen over the
window (step counters, the tree's sums, compilations, transfers).

Surface into the program: ``agent.d4pg.train_step`` / ``create_train_state``,
the flax parameter layout (``params/hidden_i|out/kernel|bias``), the optax
Adam state ``(ScaleByAdamState(count, mu, nu), EmptyState())``, and
``replay.device_per.descend_prefix``.
"""

from __future__ import annotations

import importlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import datagen

# Losses, priorities and gradients: program and reference are both float32
# with every matrix multiplication at "highest", so they differ by rounding
# in sums of up to batch x width terms taken in another order, a few 1e-7
# relative (measured: see PERF.md section 6). One bfloat16 pass anywhere in
# the step moves them by 1e-3 or more. 1e-5 sits between.
TOL_REL = 1e-5
# Parameters and targets are compared after the update, where a float32 of
# size |θ| cannot show less than its own rounding: 4 ulp at the leaf's scale.
# A wrong learning rate, τ, sign or Adam term moves them by ~lr·0.1 = 1e-5,
# fifty times more.
TOL_ULP = 4.0
ADAM_COUNT = 1000
N_PREFIXES = 8192


def _layers(flax_params) -> list:
    p = flax_params["params"]
    names = sorted((n for n in p if n.startswith("hidden_")),
                   key=lambda n: int(n.split("_")[1])) + ["out"]
    return [(p[n]["kernel"], p[n]["bias"]) for n in names]


def _adam(opt_state) -> dict:
    s = opt_state[0]
    return {"count": s.count, "m": _layers(s.mu), "v": _layers(s.nu)}


def to_reference_state(state) -> dict:
    """The program's ``TrainState`` as the plain reference's dict of lists."""
    return {
        "actor": _layers(state.actor_params),
        "critic": _layers(state.critic_params),
        "target_actor": _layers(state.target_actor_params),
        "target_critic": _layers(state.target_critic_params),
        "actor_adam": _adam(state.actor_opt_state),
        "critic_adam": _adam(state.critic_opt_state),
    }


def seeded_state(agent_cfg, seed):
    """A mid-training state from the seed: four different unit-scale weight
    sets, Adam first moments zero (so the new first moment is 0.1·gradient,
    bit for bit), second moments positive at gradient scale and the count
    far from zero, so that sqrt(v) dwarfs Adam's ε and no update is a bare
    sign."""
    from d4pg_tpu.agent.d4pg import create_train_state

    state = create_train_state(agent_cfg, jax.random.PRNGKey(seed))
    weights = lambda stream, tree: datagen.like(  # noqa: E731
        seed, stream, tree, datagen.fan_in_scale
    )

    def adam(stream, opt_state):
        s = opt_state[0]
        u = datagen.like(seed, stream, s.nu, lambda _: 1.0)
        nu = jax.tree_util.tree_map(lambda x: 1e-6 * (1.0 + 0.5 * x), u)
        s = s._replace(count=jnp.asarray(ADAM_COUNT, s.count.dtype), nu=nu)
        return (s,) + tuple(opt_state[1:])

    return state.replace(
        actor_params=weights(1, state.actor_params),
        critic_params=weights(2, state.critic_params),
        target_actor_params=weights(3, state.target_actor_params),
        target_critic_params=weights(4, state.target_critic_params),
        actor_opt_state=adam(5, state.actor_opt_state),
        critic_opt_state=adam(6, state.critic_opt_state),
    )


def _worst(got, want, scale_of):
    """max over leaves of max|got − want| / scale_of(want leaf)."""
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        if g.shape != w.shape:
            return float("inf")
        err = float(np.max(np.abs(g - w), initial=0.0))
        worst = max(worst, err / max(scale_of(w), 1e-30))
    return worst


def reference_check(agent_cfg, batch_size: int, seed: int, reference: str,
                    precision: str | None = "highest") -> dict:
    """Check 1: one grad step of the program's agent layer against the plain
    reference, on a seeded state and batch. ``precision`` wraps the
    program's call only (``None`` = the program's own default, for probing
    what the tolerance catches; ``correct`` always uses "highest")."""
    from d4pg_tpu.agent.d4pg import train_step

    ref = importlib.import_module(f"cellbench.reference.{reference}")
    dist = agent_cfg.dist
    gamma_n = agent_cfg.gamma ** agent_cfg.n_step

    @jax.jit
    def make(seed):    # the seed is an argument: one program for every seed
        return seeded_state(agent_cfg, seed), datagen.batch(
            seed, batch_size, agent_cfg.obs_dim, agent_cfg.action_dim,
            gamma_n, reward_max=(dist.v_max - dist.v_min) / 20.0,
        )

    state, batch = make(jnp.uint32(seed))
    hp = dict(
        v_min=dist.v_min, v_max=dist.v_max, atoms=dist.num_atoms,
        lr_actor=agent_cfg.lr_actor, lr_critic=agent_cfg.lr_critic,
        b1=agent_cfg.adam_b1, b2=agent_cfg.adam_b2, tau=agent_cfg.tau,
    )
    want_state, want = jax.jit(partial(ref.step, hp=hp))(
        to_reference_state(state), batch
    )
    step = jax.jit(partial(train_step, agent_cfg))
    if precision is None:
        got_state, metrics, priorities = step(state, batch)
    else:
        with jax.default_matmul_precision(precision):
            got_state, metrics, priorities = step(state, batch)
    got_state = to_reference_state(got_state)

    rel = lambda w: float(np.max(np.abs(w), initial=0.0))  # noqa: E731
    ulp = lambda w: 2.0 ** -23 * rel(w)  # noqa: E731
    errs = {
        "critic_loss": _worst(metrics["critic_loss"], want["critic_loss"], rel),
        "actor_loss": _worst(metrics["actor_loss"], want["actor_loss"], rel),
        "priorities": _worst(priorities, want["priorities"], rel),
    }
    for net in ("actor", "critic"):
        # m' = (1−b1)·gradient: the gradients themselves, to rounding.
        errs[f"{net}_grad"] = _worst(
            got_state[f"{net}_adam"]["m"], want_state[f"{net}_adam"]["m"], rel)
        errs[f"{net}_adam_v"] = _worst(
            got_state[f"{net}_adam"]["v"], want_state[f"{net}_adam"]["v"], rel)
    ulps = {
        name: _worst(got_state[name], want_state[name], ulp)
        for name in ("actor", "critic", "target_actor", "target_critic")
    }
    ok = all(e <= TOL_REL for e in errs.values()) and all(
        u <= TOL_ULP for u in ulps.values()
    )
    return {
        "ok": bool(ok), "precision": precision or "program default",
        "batch": batch_size, "rel_err": errs, "ulp_err": ulps,
        "tol_rel": TOL_REL, "tol_ulp": TOL_ULP,
    }


def descent_check(lane_leaves: int, seed: int) -> dict:
    """Check 2: the program's tree descent over a tree as wide as the cell's
    lane returns, for 8,192 stratified prefixes, the leaf that a NumPy f64
    cumulative sum and ``searchsorted`` return. The leaves have exactly
    representable sums, so the two hold the same numbers and every index
    must be equal."""
    from d4pg_tpu.replay.device_per import descend_prefix

    @jax.jit
    def make(seed):
        leaves = datagen.exact_leaves(seed, lane_leaves)
        lane = datagen.tree_levels(leaves[None])[0]
        return leaves, lane, datagen.stratified_prefixes(seed, N_PREFIXES, lane[1])

    leaves, lane, prefixes = make(jnp.uint32(seed))
    got = np.asarray(jax.jit(descend_prefix)(lane, prefixes))
    cum = np.cumsum(np.asarray(leaves, np.float64))
    exact = bool(cum[-1] < 2 ** 24 and float(lane[1]) == cum[-1])
    want = np.searchsorted(cum, np.asarray(prefixes, np.float64), side="right")
    mismatches = int(np.sum(got != want))
    return {
        "ok": exact and mismatches == 0, "leaves": lane_leaves,
        "prefixes": N_PREFIXES, "mismatches": mismatches,
        "sums_exact": exact, "total_mass": float(cum[-1]),
    }


def tree_sums_check(tree) -> dict:
    """Each lane's root against the f32 sum of its leaves. The root is built
    from pairwise sums, the check sums in XLA's own order: 1e-4 relative is
    a thousand roundings of room over millions of leaves, and a lost or
    double-counted write-back of one typical leaf in a 2^20 tree is 1e-6 of
    the mass — so this is a coarse check on the repair, not a fine one; the
    ancestors of every written leaf are checked exactly below."""
    sums = tree.sums
    half = sums.shape[1] // 2

    @jax.jit
    def reduce(s):
        leaves = s[:, half:]
        # Exact: every parent equals the f32 sum of its two children.
        parents_ok = jnp.all(s[:, 1:half] == s[:, 2::2][:, : half - 1] + s[:, 3::2])
        return s[:, 1], jnp.sum(leaves, axis=1), parents_ok, jnp.sum(leaves > 0)

    root, total, parents_ok, filled = jax.device_get(reduce(sums))
    rel = float(np.max(np.abs(root - total) / np.maximum(np.abs(total), 1e-30)))
    return {
        "ok": bool(parents_ok) and rel <= 1e-4 and bool(np.all(np.isfinite(root))),
        "root_vs_leaf_sum_rel": rel, "every_parent_is_its_childrens_sum":
        bool(parents_ok), "filled_leaves": int(filled),
    }


def all_finite(tree) -> bool:
    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(x.dtype, jnp.floating)]
    return bool(jax.jit(lambda xs: jnp.all(jnp.stack([jnp.all(jnp.isfinite(x)) for x in xs])))(leaves))
