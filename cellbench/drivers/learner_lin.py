"""The saturated learner of a configuration whose critic owns the hybrid
torso — Gated DeltaNet layers with a gated attention layer among them:
``learner``'s loop (full seeded ring, seeded tree, the PER megastep back to
back) over stream windows, with a check 1 that **compares a chunked scan
with a recurrence**.

The program runs the gated delta rule in chunks (``ops/gated_delta.py``:
cumulative decays, a unit-triangular solve a chunk, a ``lax.scan`` over
chunks); the reference runs it token by token. The two are one function
and check 1 holds them to each other through the whole step — loss,
priorities, every gradient leaf, the stepped state — at the published
widths and the timed window. The stack makes one discrete choice a token
and layer (top-10 of 512 experts), so the comparison is ``learner_ctx``'s,
for experts alone, and both parts decide ``correct``:

(i)  **expert choices, as sets outside a band.** The program's step, run
     with ``emit_choices=True``, returns the experts its two torso passes
     chose, from inside the step. The reference chooses its own on the same
     activations; where the sets differ the expert must lie within
     ``ROUTER_MARGIN`` of the reference's own boundary. ``learner_ctx``'s
     share limits.
(ii) **the smooth part, given the program's choices.** The reference routes
     by them and computes gates, the recurrence, attention and every
     gradient itself; then ``learner_torso``'s comparison, the program's
     step wrapped in ``highest``.

The head-ReLU margin is ``learner_torso``'s; the batch is the first of
``CANDIDATES`` seeded ones clear of it. The program steps first (the
reference needs its choices), its new state goes to the host, the reference
steps on a state made anew (both donate), and the comparison runs a leaf at
a time on the device.

Mix parameters: ``learner``'s. Surface into the program beside ``learner``'s:
``agent.d4pg.train_step(..., emit_choices=True)`` on window batches,
``models.torso``'s parameter names, ``critic_params = {"torso", "head"}``.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from cellbench import correctness, probe, trace
from cellbench.correctness import TOL_REL, TOL_ULP, _layers
from cellbench.drivers import Job, resolve_config
from cellbench.drivers.learner import Loop, _megastep, _mesh, build, makers
from cellbench.drivers.learner_ctx import CANDIDATES, MAX_BAND_SHARE, MAX_DIFFER_SHARE, candidates
from cellbench.drivers.learner_torso import HEAD_NU_SCALE, RELU_MARGIN, _compare, _leaf_err

# A router probability is a softmax over 512 logits, each a 2048-term sum:
# the two sides' log-probabilities differ by about 1e-6 (float32 at
# "highest", rounded in another order and grown through up to four blocks).
# 2e-5 is twenty times that; the tenth and eleventh of 512 probabilities
# lie nearer than that about once in four hundred tokens (PERF.md section 6
# has the counts read on the chip).
ROUTER_MARGIN = 2e-5
# The limits of part (ii), each between two readings taken on the chip at the
# published widths (PERF.md section 6, PR 34, has every run's): the step
# wrapped in "highest", and the same step at the program's default precision
# (one bfloat16 pass), which is not correct by every one of them.
#
# Losses and priorities keep ``TOL_REL`` 1e-5 (read 1.2e-7 against 1.4e-4).
# The gradients do not: the forward passes agree to 1e-7, but a gradient
# element is a sum over 8,192 positions whose terms reach back through up to
# 8,192 decayed state updates, and the chunked form (a triangular solve a
# chunk, products with e^{γ_i − γ_j}) rounds them in another order than 64
# rank-one steps do. Over nine seeds the critic's leaves read 2.2e-5 … 5.9e-5
# (DeltaNet 1.5e-5 … 2.9e-5, attention 9.8e-6 … 1.2e-5, experts, routers and
# gates up to 5.9e-5) against 0.2 … 0.7; no DeltaNet leaf asks for a limit of
# its own.
TOL_CRITIC_GRAD = 1e-3
# The heads' gradients inherit the torso's rounding through h: the actor's
# gradient and second moments read 5.4e-6 … 1.6e-5 (Keye's cell: 1e-6)
# against 0.19, and its parameters after the Adam step 12.2 … 30.1 ulp against
# 63,327 — Adam divides by sqrt(v) ≈ 1e-3, so an error of 5e-6 of a gradient
# of 0.3 is 1.5e-7 of a weight of 0.1. A wrong learning rate, τ, sign or Adam
# term moves a parameter by 800 ulp or more.
TOL_HEAD = 1e-4
TOL_ULP_ACTOR = 128.0
LIMITS = {"critic_grad": TOL_CRITIC_GRAD, "critic_adam_v": TOL_HEAD,
          "actor_grad": TOL_HEAD, "actor_adam_v": TOL_HEAD}
ULP_LIMITS = {"actor": TOL_ULP_ACTOR}
# The loss is on the window's last position, so the LAST layer's router and
# shared-expert gate take their gradient from one token: s · x with s made of
# inner products <dy, E(x)> of two 2048-vectors. Two correct programs round
# such a product apart by ε / |cos θ| of itself — unbounded as the vectors
# happen to stand orthogonal (read: 1.6e-3 of the leaf's own scale on the
# first seed on the chip, 1.4e-4 of a 3.7e-6 scale at the tiny CPU size),
# while the absolute error stays what every other leaf's is. These two
# leaves are therefore held to the scale of the same leaf one layer below,
# which sums over all 8,192 tokens, under the same limit.
ONE_TOKEN_LEAVES = ("w_router", "w_shared_gate")
DT_BIAS_SHIFT = -3.0      # seeded dt_bias: a step of softplus(a − 3) ≈ 0.05


def reference_torso(t: dict) -> dict:
    """The program's torso parameters under the reference's names."""
    def layer(p):
        f = p["ffn"]
        swiglu = lambda w: {"w_gate": w["gate"], "w_up": w["up"], "w_down": w["down"]}  # noqa: E731
        out = {"norm1": p["attn_norm"], "norm2": p["ffn_norm"], "w_router": f["router"],
               "experts": swiglu(f["experts"]), "shared": swiglu(f["shared"]),
               "w_shared_gate": f["shared_gate"]}
        if "lin" in p:
            m = p["lin"]
            return {**out, "w_qkvz": m["in_qkvz"], "w_ba": m["in_ba"], "conv": m["conv"],
                    "a_log": m["A_log"], "dt_bias": m["dt_bias"], "o_norm": m["norm"],
                    "w_out": m["out"]}
        a = p["attn"]
        return {**out, "wq": a["q"], "q_norm": a["q_norm"], "wk": a["k"],
                "k_norm": a["k_norm"], "wv": a["v"], "wo": a["o"]}

    return {"w_in": t["embed"]["kernel"], "b_in": t["embed"]["bias"],
            "layers": [layer(p) for p in t["layers"]], "norm_f": t["final_norm"]}


def _critic(params) -> dict:
    return {"torso": reference_torso(params["torso"]), "head": _layers(params["head"])}


def to_reference_state(state) -> dict:
    """``TrainState`` → the reference's dict (``qwen3next_d4pg_step.step``)."""
    a, c = state.actor_opt_state[0], state.critic_opt_state[0]
    return {
        "actor": _layers(state.actor_params), "critic": _critic(state.critic_params),
        "target_actor": _layers(state.target_actor_params),
        "target_critic": _critic(state.target_critic_params),
        "actor_adam": {"count": a.count, "m": _layers(a.mu), "v": _layers(a.nu)},
        "critic_adam": {"count": c.count, "m": _critic(c.mu), "v": _critic(c.nu)},
    }


def seeded_state(agent_cfg, seed):
    """``correctness.seeded_state`` made a state this stack can be checked
    on. The block norms are zero-centred and stay around zero (a scale of 1
    ± 0.1); the DeltaNet's output norm is not and is lifted to around one.
    The convolution's taps are scaled to its fan-in of four (a ``[C, 4]``
    leaf reads as fan-in C to the seeding). ``dt_bias`` is moved to around
    −3, so that a token decays the state by e^-0.05 or so and a write
    outlives its chunk: the scan over chunks carries something. The critic
    head's second moments are large (``learner_torso`` says why)."""
    state = correctness.seeded_state(agent_cfg, seed)

    def fit(tree):
        def one(path, x):
            path = jax.tree_util.keystr(path)
            if path.endswith("['lin']['norm']"):
                return x + 1.0
            if path.endswith("['lin']['dt_bias']"):
                return x + DT_BIAS_SHIFT
            if path.endswith("['lin']['conv']"):
                return x * math.sqrt(x.shape[0] / x.shape[1])
            return x
        return jax.tree_util.tree_map_with_path(one, tree)

    adam = state.critic_opt_state[0]
    nu = dict(adam.nu, head=jax.tree_util.tree_map(
        lambda v: v * HEAD_NU_SCALE, adam.nu["head"]))
    return state.replace(
        critic_params=fit(state.critic_params),
        target_critic_params=fit(state.target_critic_params),
        critic_opt_state=(adam._replace(nu=nu),) + tuple(state.critic_opt_state[1:]))


def choices_check(report: dict) -> dict:
    """Part (i) from the reference's ``report`` (``[2, L]`` arrays: the
    critic's pass and the target's, layer by layer)."""
    get = lambda name: np.asarray(report[f"experts_{name}"])  # noqa: E731
    differ, outside, band, places = (
        int(get(n).sum()) for n in ("differ", "out_of_band", "in_band", "places"))
    ok = (outside == 0 and differ <= MAX_DIFFER_SHARE * max(band, 1)
          and band <= MAX_BAND_SHARE * places)
    return {"ok": ok, "max_differ_share": MAX_DIFFER_SHARE, "max_band_share": MAX_BAND_SHARE,
            "experts": {
                "ok": ok, "margin": ROUTER_MARGIN, "places": places, "in_band": band,
                "disagreements": differ, "outside_the_band": outside,
                # the farthest disagreement from its boundary, in margins
                "worst_disagreement": float(get("worst").max()),
                "by_pass_and_layer": get("differ").tolist()}}


def reference_check(agent_cfg, batch_size: int, seed: int, reference: str, say=print,
                    precision: str | None = "highest") -> dict:
    """Check 1 in its two parts, and the routing counters, at the cell's
    widths and sizes. The reference is handed the torso's sizes as the
    program resolved them (a test holds the configuration file's ``torso``
    to the same)."""
    from d4pg_tpu.agent.d4pg import train_step

    ref = importlib.import_module(f"cellbench.reference.{reference}")
    dist, torso = agent_cfg.dist, agent_cfg.torso
    t = torso.window
    hp = dict(
        v_min=dist.v_min, v_max=dist.v_max, atoms=dist.num_atoms,
        lr_actor=agent_cfg.lr_actor, lr_critic=agent_cfg.lr_critic,
        b1=agent_cfg.adam_b1, b2=agent_cfg.adam_b2, tau=agent_cfg.tau,
        torso=dataclasses.asdict(torso), router_margin=ROUTER_MARGIN,
        query_block=256 if t % 256 == 0 else t // 2 if t % 2 == 0 else t,
    )
    make_state = jax.jit(lambda s: seeded_state(agent_cfg, s))
    pool = jax.jit(lambda s: candidates(agent_cfg, s, CANDIDATES * batch_size))(
        jnp.uint32(seed))
    seed_ = jnp.uint32(seed)
    step = jax.jit(partial(train_step, agent_cfg, emit_choices=True), donate_argnums=0)
    ref_step = jax.jit(partial(ref.step, hp=hp), donate_argnums=0)

    passed_over, relu = 0, []
    for i in range(CANDIDATES):
        batch = jax.tree_util.tree_map(
            lambda x: x[i * batch_size:(i + 1) * batch_size], pool)
        state = make_state(seed_)
        if precision is None:
            got_state, metrics, priorities, choices = step(state, batch)
        else:
            with jax.default_matmul_precision(precision):
                got_state, metrics, priorities, choices = step(state, batch)
        del state
        got_state = jax.device_get(to_reference_state(got_state))   # one state on the device
        got = jax.device_get({"critic_loss": metrics["critic_loss"],
                              "actor_loss": metrics["actor_loss"], "priorities": priorities})
        say(f"check 1: program stepped on candidate {i}")
        want_state, want = ref_step(
            to_reference_state(make_state(seed_)), batch, {"experts": choices["experts"]})
        relu.append(float(jnp.min(want["relu"])))
        say(f"check 1: reference stepped; smallest head pre-activation {relu[-1]:.3g}")
        if relu[-1] >= RELU_MARGIN:
            break
        passed_over += 1
        del want_state, want, got_state, choices
    else:
        why = {"ok": False, "why": "no candidate batch clear of the heads' ReLU margin",
               "relu_margin": RELU_MARGIN, "smallest_preactivations": relu}
        return {"reference_step": why, "choices": why, "routing": why}

    rel = lambda s: s  # noqa: E731
    ulp = lambda s: 2.0 ** -23 * s  # noqa: E731
    errs = {name: _compare(want[name], got[name], rel)
            for name in ("critic_loss", "actor_loss", "priorities")}
    ulps = {}
    # m' = (1−b1)·gradient: the gradients themselves, to rounding.
    errs["actor_grad"] = _compare(
        want_state["actor_adam"]["m"], got_state["actor_adam"]["m"], rel)
    for net in ("actor", "critic"):
        errs[f"{net}_adam_v"] = _compare(
            want_state[f"{net}_adam"]["v"], got_state[f"{net}_adam"]["v"], rel)
        for name in (net, f"target_{net}"):
            ulps[name] = _compare(want_state[name], got_state[name], ulp)
    # the critic's gradient by layer and part (what reads highest is reported),
    # the last layer's one-token leaves on the scale of the layer below
    by_part = {}
    m_want, m_got = want_state["critic_adam"]["m"], got_state["critic_adam"]["m"]
    layers = list(zip(m_want["torso"]["layers"], m_got["torso"]["layers"]))
    for i, (w, g) in enumerate(layers):
        kind = "delta_net" if "w_qkvz" in w else "attention"
        ffn = ("experts", "shared") + ONE_TOKEN_LEAVES
        mixer = lambda d: {k: v for k, v in d.items() if k not in ffn}  # noqa: E731
        experts = lambda d: {k: d[k] for k in ("experts", "shared")}  # noqa: E731
        by_part[f"layer{i}.{kind}"] = _compare(mixer(w), mixer(g), rel)
        by_part[f"layer{i}.experts"] = _compare(experts(w), experts(g), rel)
        for name in ONE_TOKEN_LEAVES:
            err, scale = (float(v) for v in _leaf_err(w[name], jnp.asarray(g[name])))
            if i == len(layers) - 1 and i > 0:
                by_part[f"layer{i}.{name}.own_scale"] = err / max(scale, 1e-30)
                scale = max(scale, float(jnp.max(jnp.abs(layers[i - 1][0][name]))))
            by_part[f"layer{i}.{name}"] = err / max(scale, 1e-30)
    outside = lambda m: [  # noqa: E731
        m["head"], {k: v for k, v in m["torso"].items() if k != "layers"}]
    by_part["head_embed_final_norm"] = _compare(outside(m_want), outside(m_got), rel)
    errs["critic_grad"] = max(v for k, v in by_part.items() if not k.endswith(".own_scale"))
    del want_state, got_state, m_want, m_got
    ok = all(e <= LIMITS.get(name, TOL_REL) for name, e in errs.items()) and all(
        u <= ULP_LIMITS.get(name, TOL_ULP) for name, u in ulps.items())

    report = jax.device_get(want["report"])
    load, dropped = (np.asarray(jax.device_get(choices[k])) for k in ("load", "dropped"))
    routing = {
        # the reference counted the given experts on the held ones itself
        "ok": bool(np.array_equal(load, np.asarray(report["load"])) and not dropped.any()),
        "pairs_on_held_experts": int(load.sum()), "load_min": int(load.min()),
        "load_max": int(load.max()), "dropped": int(dropped.sum()),
        "tokens": int(batch_size * t), "passes_x_layers": int(load.shape[0] * load.shape[1])}
    return {
        "reference_step": {
            "ok": bool(ok), "precision": precision or "program default",
            "batch": batch_size, "window": t, "rel_err": errs, "ulp_err": ulps,
            "critic_grad_by_part": by_part,
            "tol_rel": TOL_REL, "tol_of": LIMITS, "tol_ulp": TOL_ULP, "tol_ulp_of": ULP_LIMITS,
            "relu_margin": RELU_MARGIN,
            "candidates_passed_over": passed_over, "smallest_preactivations": relu},
        "choices": choices_check(report),
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
    band = checks["choices"].get("experts", {})
    say(f"reference step checked: {checks['reference_step']['ok']}, "
        f"choices: {checks['choices']['ok']} (experts: {band.get('places')} places, "
        f"{band.get('in_band')} in the band, {band.get('disagreements')} disagreements, "
        f"{band.get('outside_the_band')} outside it), routing: {checks['routing']['ok']}")
    checks["descent"] = correctness.descent_check(sizes["lane_leaves"], job.seed)
    say(f"descent checked: {checks['descent']['ok']}")
    # peak_bytes_in_use is a lifetime maximum: what the checks reached has to
    # stay under what the cell's own state and ring will hold.
    checks["reference_step"]["peak_bytes_after_checks"] = probe.peak_bytes(job.devices)

    state, ring, tree, key, leaves_fn, sizes = build(job, cfg, mesh)
    mega = _megastep(cfg, k, mesh)
    jax.block_until_ready((ring, tree))
    say(f"built: {sizes}, K={k}, B={batch}, T={agent.torso.window}, {agent.torso.span}")

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
