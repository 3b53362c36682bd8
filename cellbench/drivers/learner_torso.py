"""The saturated learner of a configuration whose critic owns a sequence
torso: ``learner``'s loop (full seeded ring, seeded tree, the PER megastep
back to back) with the comparison against the plain reference done the way a
2 GB parameter set allows.

``correctness.reference_check`` cannot serve here: it reads ``params/
hidden_i|out`` only (a torso would be dropped in silence), holds program and
reference states side by side (16 GB at this configuration's widths) and has
no answer to a router whose top-k flips on rounding. This driver's check 1:

1. the seeded state is a pure function of the seed, made twice — once for
   the reference, once for the program — and each step donates it, so the
   check never holds more than one state on the device (``peak_bytes_in_use``
   is a lifetime maximum: the cell's own state + ring must stay above it);
2. the reference steps first (the batch in blocks of 32 windows, gradients
   added), its new state goes to the host, and comes back one leaf at a time
   to be compared with the program's on the device;
3. **kink margins**: with random weights some token's k-th and (k+1)-th
   router scores, or some ReLU's pre-activation and zero, lie closer than
   program and reference round apart; the choice flips, and a gradient moves
   by far more than any rounding. The reference measures every candidate
   window's smallest routing gap (online pass on s, target pass on s′, every
   position) and smallest head pre-activation (the critic's and the actor's
   loss passes) and the batch is the first B of 1.5·B seeded candidates clear
   of ``MARGIN`` and ``RELU_MARGIN``; how many were passed over is reported,
   and more than ``MAX_PASSED_OVER`` of them fails. For this to be a property
   of a window and not of the batch, the actor's loss pass must see the
   critic head it was measured with: the seeded second moments of the
   critic *head* are large, so that its update (lr·m̂/√v̂ ≈ 1e-6·gradient)
   moves no pre-activation by more than a tenth of the margin.

Mix parameters: ``learner``'s. Surface into the program beside ``learner``'s:
``agent.d4pg.train_step`` on window batches (``obs [B, T, O]``, ``mask``),
``models.torso.torso_apply`` and its parameter names, ``critic_params =
{"torso", "head"}``.
"""

from __future__ import annotations

import dataclasses
import importlib
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import correctness, datagen, probe, trace
from cellbench.correctness import TOL_REL, TOL_ULP, _layers
from cellbench.drivers import Job, resolve_config
from cellbench.drivers.learner import Loop, _megastep, _mesh, build, makers

# A router score is a sigmoid of a 2048-term float32 sum at "highest": the
# two sides' scores differ by a few 1e-7 (rounding in another order, grown
# through up to five blocks). 1e-5 is thirty times that; a gap under it is
# found in about one token-layer in a thousand.
MARGIN = 1e-5
# A head's pre-activation is a 2048- or 273-term sum on an ``h`` the two
# sides agree on to about 1e-6: they differ by a few 1e-6. 3e-5 is ten times
# that; of the 2,304 hidden units a window meets in the critic's and the
# actor's loss passes one lies under it in about one window in twenty.
RELU_MARGIN = 3e-5
HEAD_NU_SCALE = 1e8     # seeded critic-head second moments: 1e-6 → 1e2
# 256 positions x expert layers a window and pass: 20-30% of random windows
# hold one. Over half means the margin or the scores are not what they were.
MAX_PASSED_OVER = 0.5
BLOCK = 32      # windows a block of the reference's step


def reference_torso(t: dict) -> dict:
    """The program's torso parameters under the reference's names."""
    def layer(p):
        a, f = p["attn"], p["ffn"]
        out = {"norm1": p["attn_norm"], "norm2": p["ffn_norm"], "wq_a": a["q_a"],
               "q_norm": a["q_a_norm"], "wq_b": a["q_b"], "wkv_a": a["kv_a"],
               "kv_norm": a["kv_a_norm"], "wkv_b": a["kv_b"], "wo": a["o"]}
        ffn = lambda w: {"w_gate": w["gate"], "w_up": w["up"], "w_down": w["down"]}  # noqa: E731
        if "router" in f:
            return {**out, "w_router": f["router"], "e_bias": f["router_bias"],
                    "experts": ffn(f["experts"]), "shared": ffn(f["shared"])}
        return {**out, **ffn(f)}

    return {"w_in": t["embed"]["kernel"], "b_in": t["embed"]["bias"],
            "layers": [layer(p) for p in t["layers"]], "norm_f": t["final_norm"]}


def _critic(params) -> dict:
    return {"torso": reference_torso(params["torso"]), "head": _layers(params["head"])}


def to_reference_state(state) -> dict:
    """``TrainState`` → the reference's dict (``glm47flash_d4pg_step.step``)."""
    a, c = state.actor_opt_state[0], state.critic_opt_state[0]
    return {
        "actor": _layers(state.actor_params), "critic": _critic(state.critic_params),
        "target_actor": _layers(state.target_actor_params),
        "target_critic": _critic(state.target_critic_params),
        "actor_adam": {"count": a.count, "m": _layers(a.mu), "v": _layers(a.nu)},
        "critic_adam": {"count": c.count, "m": _critic(c.mu), "v": _critic(c.nu)},
    }


def seeded_state(agent_cfg, seed):
    """``correctness.seeded_state`` with norm weights around one (around
    zero they would switch every block off) and the critic head's second
    moments large (the module's note on kink margins says why)."""
    state = correctness.seeded_state(agent_cfg, seed)
    lift = lambda tree: jax.tree_util.tree_map_with_path(  # noqa: E731
        lambda path, x: x + 1.0 if "norm" in jax.tree_util.keystr(path) else x, tree)
    adam = state.critic_opt_state[0]
    nu = dict(adam.nu, head=jax.tree_util.tree_map(
        lambda v: v * HEAD_NU_SCALE, adam.nu["head"]))
    return state.replace(
        critic_params=lift(state.critic_params),
        target_critic_params=lift(state.target_critic_params),
        critic_opt_state=(adam._replace(nu=nu),) + tuple(state.critic_opt_state[1:]))


def candidates(agent_cfg, seed, n):
    """``n`` seeded windows: ``datagen.batch``'s fields with ``[n, T, O]``
    observations and each window's ``[n, T]`` row discounts (one in a
    hundred zero), from which the mask follows."""
    t = agent_cfg.torso.window
    out = datagen.batch(seed, n, agent_cfg.obs_dim, agent_cfg.action_dim,
                        agent_cfg.gamma ** agent_cfg.n_step,
                        (agent_cfg.dist.v_max - agent_cfg.dist.v_min) / 20.0)
    shape = (n, t, agent_cfg.obs_dim)
    out["obs"] = 2.0 * datagen.uniform(seed, 21, shape) - 1.0
    out["next_obs"] = 2.0 * datagen.uniform(seed, 22, shape) - 1.0
    out["row_discount"] = jnp.where(datagen.uniform(seed, 23, (n, t)) < 0.01, 0.0, 1.0)
    return out


@jax.jit
def _leaf_err(got, want):
    return jnp.max(jnp.abs(got - want), initial=0.0), jnp.max(jnp.abs(want), initial=0.0)


def _compare(got, want_host, scale_of):
    """``correctness._worst`` with ``want`` on the host, a leaf at a time on
    the device: max over leaves of max|got − want| / scale_of(max|want|)."""
    worst = 0.0
    got, want_host = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want_host)
    if len(got) != len(want_host):
        return float("inf")
    for g, w in zip(got, want_host):
        if g.shape != w.shape:
            return float("inf")
        e, s = (float(v) for v in _leaf_err(g, jnp.asarray(w)))
        worst = max(worst, e / max(scale_of(s), 1e-30))
    return worst


def reference_check(agent_cfg, batch_size: int, seed: int, reference: str, say=print,
                    precision: str | None = "highest") -> dict:
    """Check 1 and the routing check, at the cell's widths and sizes. The
    reference is handed the torso's sizes as the program resolved them (a
    test holds the configuration file's ``torso`` to the same)."""
    from d4pg_tpu.agent.d4pg import train_step
    from d4pg_tpu.models.torso import torso_apply

    ref = importlib.import_module(f"cellbench.reference.{reference}")
    dist, torso = agent_cfg.dist, agent_cfg.torso
    blocks = lambda n: n // BLOCK if n % BLOCK == 0 else 1  # noqa: E731
    hp = dict(
        v_min=dist.v_min, v_max=dist.v_max, atoms=dist.num_atoms,
        lr_actor=agent_cfg.lr_actor, lr_critic=agent_cfg.lr_critic,
        b1=agent_cfg.adam_b1, b2=agent_cfg.adam_b2, tau=agent_cfg.tau,
        torso=dataclasses.asdict(torso),
    )
    n_candidates = batch_size + batch_size // 2
    make_state = jax.jit(lambda s: seeded_state(agent_cfg, s))

    @jax.jit
    def make_candidates(s):
        c = candidates(agent_cfg, s, n_candidates)
        c["mask"] = ref.window_mask(c.pop("row_discount"))
        return c

    seed_ = jnp.uint32(seed)
    pool = make_candidates(seed_)
    state = to_reference_state(make_state(seed_))
    # one program for both passes: the same shapes, other parameters
    gaps_of = jax.jit(partial(ref.kink_gaps, hp={**hp, "blocks": blocks(n_candidates)}))
    online = gaps_of(state["critic"], state["actor"], pool["obs"], pool["mask"],
                     pool["action"])
    target = gaps_of(state["target_critic"], state["target_actor"], pool["next_obs"],
                     pool["mask"], pool["action"])     # values only: its ReLUs do not count
    routing_gap = np.minimum(np.asarray(online["routing"]), np.asarray(target["routing"]))
    under = (routing_gap < MARGIN) | (np.asarray(online["relu"]) < RELU_MARGIN)
    clear = np.flatnonzero(~under)
    enough = len(clear) >= batch_size
    passed_over = int(np.sum(under[: clear[batch_size - 1] + 1] if enough else under))
    routing = {"margin": MARGIN, "relu_margin": RELU_MARGIN, "candidates": n_candidates,
               "passed_over": passed_over,
               "passed_over_for_routing": int(np.sum(routing_gap < MARGIN)),
               "passed_over_share": passed_over / n_candidates,
               "max_passed_over_share": MAX_PASSED_OVER}
    if not enough or passed_over / n_candidates > MAX_PASSED_OVER:
        del state
        return {"reference_step": {"ok": False, "why": "too few windows clear of the "
                                   "kink margins", **routing},
                "routing": {"ok": False, **routing}}
    pick = jnp.asarray(clear[:batch_size])
    batch = jax.tree_util.tree_map(lambda x: x[pick], pool)
    say(f"check 1: {passed_over} of {n_candidates} candidate windows passed over "
        f"(routing gap under {MARGIN} or a head pre-activation under {RELU_MARGIN})")

    want_state, want = jax.jit(
        partial(ref.step, hp={**hp, "blocks": blocks(batch_size)}), donate_argnums=0)(
            state, batch)
    del state
    want = jax.device_get(want)
    want_state = jax.device_get(want_state)     # to the host: one state on the device
    say("check 1: reference stepped")

    state = make_state(seed_)
    with jax.default_matmul_precision("highest"):
        _, stats = jax.jit(partial(torso_apply, torso))(
            state.critic_params["torso"], batch["obs"], batch["mask"])
    stats = jax.device_get(stats)
    step = jax.jit(partial(train_step, agent_cfg), donate_argnums=0)
    if precision is None:
        got_state, metrics, priorities = step(state, batch)
    else:
        with jax.default_matmul_precision(precision):
            got_state, metrics, priorities = step(state, batch)
    del state
    got_state = to_reference_state(got_state)
    say("check 1: program stepped")

    rel = lambda s: s  # noqa: E731
    ulp = lambda s: 2.0 ** -23 * s  # noqa: E731
    errs = {
        "critic_loss": _compare(metrics["critic_loss"], want["critic_loss"], rel),
        "actor_loss": _compare(metrics["actor_loss"], want["actor_loss"], rel),
        "priorities": _compare(priorities, want["priorities"], rel),
    }
    ulps = {}
    for net in ("actor", "critic"):
        # m' = (1−b1)·gradient: the gradients themselves, to rounding.
        errs[f"{net}_grad"] = _compare(
            got_state[f"{net}_adam"]["m"], want_state[f"{net}_adam"]["m"], rel)
        errs[f"{net}_adam_v"] = _compare(
            got_state[f"{net}_adam"]["v"], want_state[f"{net}_adam"]["v"], rel)
        for name in (net, f"target_{net}"):
            ulps[name] = _compare(got_state[name], want_state[name], ulp)
    del got_state
    ok = all(e <= TOL_REL for e in errs.values()) and all(
        u <= TOL_ULP for u in ulps.values())
    load, ref_load = np.asarray(stats["load"]), np.asarray(want["load"])
    routing.update(
        # the step's own reading of the gaps (other blocks, another fusion)
        # may differ from the selection's by a rounding: half the margin
        ok=bool(np.array_equal(load, ref_load) and not stats["dropped"].any()
                and float(np.min(want["gap"])) >= MARGIN / 2),
        pairs_on_held_experts=int(load.sum()), reference_pairs=int(ref_load.sum()),
        load_min=int(load.min()), load_max=int(load.max()),
        dropped=int(stats["dropped"].sum()), smallest_gap=float(np.min(want["gap"])),
        tokens=int(batch_size * torso.window), expert_layers=int(load.shape[0]))
    return {
        "reference_step": {
            "ok": bool(ok), "precision": precision or "program default",
            "batch": batch_size, "window": torso.window, "rel_err": errs, "ulp_err": ulps,
            "tol_rel": TOL_REL, "tol_ulp": TOL_ULP},
        "routing": routing,
    }


def run(job: Job) -> dict:
    mix, say = job.cell.traffic, job.say
    cfg = resolve_config(job)
    agent, k, batch = cfg.agent, max(1, cfg.steps_per_dispatch), cfg.batch_size
    mesh = _mesh(job, job.devices)
    lanes = int(mesh.shape["dp"]) if mesh is not None else 1
    sizes = makers(job, cfg, lanes)[0]

    checks = reference_check(agent, batch, job.seed, job.cell.config["reference"], say)
    say(f"reference step checked: {checks['reference_step']['ok']}, "
        f"routing: {checks['routing']['ok']}")
    checks["descent"] = correctness.descent_check(sizes["lane_leaves"], job.seed)
    say(f"descent checked: {checks['descent']['ok']}")
    # peak_bytes_in_use is a lifetime maximum: what the checks reached has to
    # stay under what the cell's own state and ring will hold.
    checks["reference_step"]["peak_bytes_after_checks"] = probe.peak_bytes(job.devices)

    state, ring, tree, key, leaves_fn, sizes = build(job, cfg, mesh)
    mega = _megastep(cfg, k, mesh)
    jax.block_until_ready((ring, tree))
    say(f"built: {sizes}, K={k}, B={batch}, T={agent.torso.window}")

    loop = Loop(mega, state, ring, tree, key, int(mix["inflight"]))
    loop.run(dispatches=int(mix["warm_dispatches"]))
    if loop.error is not None:
        raise loop.error
    step0 = int(jax.device_get(loop.state.step))
    say("warmed; the window starts")

    setup_s = job.setup_s()
    with jax.transfer_guard("disallow"):
        c0, c1, losses = loop.run(seconds=job.seconds - job.trace_seconds)
    peak = probe.peak_bytes(job.devices)    # before the checks below allocate
    n = len(losses)
    losses = np.asarray(jax.device_get(losses), np.float64)
    failed = int(np.sum(~np.isfinite(losses))) + (loop.error is not None)
    attempted = n + (loop.error is not None)

    steps = int(jax.device_get(loop.state.step)) - step0
    checks["grad_steps_advanced"] = {
        "ok": steps == n * k, "advanced": steps, "dispatches_x_k": n * k}
    checks["window_ran_under_transfer_guard"] = {
        "ok": loop.error is None, "error": repr(loop.error) if loop.error else None}
    checks["state_finite"] = {"ok": correctness.all_finite(loop.state)}
    if loop.error is None:
        checks["tree_sums"] = correctness.tree_sums_check(loop.tree)
        half = loop.tree.sums.shape[1] // 2
        moved = int(jnp.sum(loop.tree.sums[:, half:] != leaves_fn()))
        checks["sampled_leaves_moved"] = {
            "ok": 0 < moved <= (n + int(mix["warm_dispatches"])) * k * batch,
            "moved": moved}

    xplane, traced = None, None
    if job.trace and loop.error is None:
        trace_dir = os.path.join(job.cell.out_dir, "trace")
        trace.start(trace_dir)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            t0, t1, traced_losses = loop.run(seconds=job.trace_seconds)
        trace.stop()
        xplane = trace.newest_xplane(trace_dir)
        traced = {"seconds": t1.perf - t0.perf, "dispatches": len(traced_losses)}

    window = {
        "seconds": c1.perf - c0.perf, "dispatches": n, "grad_steps": n * k,
        "transitions": n * k * batch, "wall": (c0.wall, c1.wall)}
    return {
        "setup_s": setup_s, "attempted": attempted, "failed": failed,
        "window": window, "memory_peak_bytes": peak,
        "traced": traced, "xplane": xplane, "checks": checks,
        "agent_cfg": agent, "batch": batch, "k": k, "sizes": sizes,
    }
