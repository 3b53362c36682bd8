"""The saturated learner of a configuration whose critic owns a torso that
**chooses**: ``learner``'s loop (full seeded ring, seeded tree, the PER
megastep back to back) over stream windows, with a check 1 that can live
with two discrete choices a token (k experts, ``index_topk`` keys).

``learner_torso``'s check passes windows over whose router has a near-tie.
Here no window is clear: at T = 8,192 a window makes 65,536 expert choices
and 49,152 key choices with more candidates than places, and a few hundred
of them lie within rounding of their boundary (PERF.md section 2). So the
comparison is in two parts, and both decide ``correct``:

(i)  **choices, as sets outside a band.** The program's step, run with
     ``emit_choices=True`` (the same code with further outputs), returns
     the experts and the keys its two torso passes chose, from inside the
     step. The reference computes its own scores on the same activations
     and its own sets; where the two sides' sets differ, the element must
     lie within a stated margin of the reference's own boundary. The count
     in the band, the disagreements and the worst of them are reported;
     one disagreement outside the band fails, and so do disagreements over
     ``MAX_DIFFER_SHARE`` of the band or a band over ``MAX_BAND_SHARE`` of
     the places.
(ii) **the smooth part, given the program's choices.** The reference's
     step takes those sets as an argument, routes and masks by them, and
     computes gates, probabilities, the alignment loss and every gradient
     itself; then ``learner_torso``'s comparison with its two tolerances,
     but for the critic's gradients, whose two limits are set from this
     cell's own readings (``LIMITS``). The indexer's gradients read
     highest: an index score sums ReLUs, 6e8 of them a layer and pass, and
     the few hundred that lie within rounding of their kink cannot be
     passed over as a head's can.

The head-ReLU margin is ``learner_torso``'s (a window in twenty has a
pre-activation within it): the batch is the first of ``CANDIDATES`` seeded
ones clear of it. Because the reference needs the program's choices, the
program steps first here: its new state goes to the host, the reference
steps on a state made anew (both donate), and the comparison runs a leaf at
a time on the device. ``peak_bytes_in_use`` is a lifetime maximum: what the
check holds (one state, the choices) stays under the cell's state + ring.

Mix parameters: ``learner``'s. Surface into the program beside ``learner``'s:
``agent.d4pg.train_step(..., emit_choices=True)`` on window batches,
``models.torso``'s parameter names, ``critic_params = {"torso", "head"}``.
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
from cellbench.drivers.learner_torso import HEAD_NU_SCALE, RELU_MARGIN, _compare

# An index score is a 16-term sum of ReLUs of 64-term products on an input
# the two sides agree on to about 1e-6 (a float32 at "highest", rounded in
# another order and grown through up to four blocks): the two sides' scores
# differ by under 1e-6 of their root mean square (measured on the chip,
# PERF.md section 6: the farthest of 42 disagreements lay 0.025 margins =
# 5e-7 root mean squares from its boundary). 2e-5 is twenty to forty times
# that. About one query-layer in seven has a candidate that near its boundary
# (8,192 scores spread over one root mean square): 9,560 keys a window.
INDEX_MARGIN = 2e-5
# A router probability is a softmax over 128 logits, each a 2048-term sum:
# the two sides' log-probabilities differ by about 1e-6. 2e-5 is twenty
# times that; a token's boundary gap is under it about once in seven hundred
# (measured: 93 of 65,536 token-layer-passes, no disagreement among them).
ROUTER_MARGIN = 2e-5
# The gradient of the alignment loss reaches the indexer's leaves through
# I = sum_j w_j ReLU(q_j . k). A window and layer evaluates 16 x 8192 x ~4600
# = 6e8 of those ReLUs (2.7e8 of them on chosen keys); a pre-activation (a
# 64-term product, root mean square 4.6) differs between the two sides by
# about 5e-6, so a few hundred a layer lie nearer their kink than the sides
# round apart, and each one that flips moves an element of dL/dW_q by
# (1/2048 / 8192) . w . k . x = 1e-9 - against a leaf whose largest element is
# 6e-5 (the loss is a mean over 8,192 queries), 2e-5 of its scale. Measured on
# the chip over twelve runs of eight seeds (PERF.md section 6): 4.4e-5 to
# 3.8e-4 on the worst of the indexer's leaves (a handful of flips on its
# smallest leaf: a count, and a leaf scale, that vary by the seed - the two
# largest readings came last, from fresh seeds); one bfloat16 pass moves
# them by 2e-1. 1e-2 is twenty-six times the largest reading and twenty
# times under the other.
TOL_INDEXER = 1e-2
# Every other critic gradient: the leaves' own scales go down to 1e-5 (the
# loss is one window's; an expert's rows see the tokens routed to it) and a
# gradient element is a sum over 8,192 queries x 2,048 keys rounded in
# another order. Measured on the chip over twelve runs of eight seeds: 3.4e-6
# to 5.9e-6 in nine of them, 8.5e-6, 1.1e-5 and 1.7e-5 on the worst leaf -
# PR 27's 1e-5 has no room above that - against 3.7e-1 after one bfloat16
# pass. 1e-4 is six times the largest reading and four thousand times under
# the other. Losses, priorities, the
# actor's gradients and every second moment keep 1e-5 (largest reading 1.7e-6).
TOL_CRITIC_GRAD = 1e-4
LIMITS = {"indexer_grad": TOL_INDEXER, "critic_grad": TOL_CRITIC_GRAD}
MAX_DIFFER_SHARE = 0.5    # disagreements / elements in the band: rounding gives ~1/20
MAX_BAND_SHARE = 0.01     # elements in the band / places: a wider band is no band
CANDIDATES = 4            # seeded batches; the first clear of RELU_MARGIN is compared


def reference_torso(t: dict) -> dict:
    """The program's torso parameters under the reference's names."""
    def layer(p):
        a, i, f = p["attn"], p["indexer"], p["ffn"]
        e = f["experts"]
        return {
            "norm1": p["attn_norm"], "norm2": p["ffn_norm"], "wq": a["q"],
            "q_norm": a["q_norm"], "wk": a["k"], "k_norm": a["k_norm"], "wv": a["v"],
            "wo": a["o"], "idx_wq": i["q"], "idx_wk": i["k"],
            "idx_k_scale": i["k_norm"]["scale"], "idx_k_bias": i["k_norm"]["bias"],
            "idx_ww": i["w"], "w_router": f["router"],
            "experts": {"w_gate": e["gate"], "w_up": e["up"], "w_down": e["down"]}}

    return {"w_in": t["embed"]["kernel"], "b_in": t["embed"]["bias"],
            "layers": [layer(p) for p in t["layers"]], "norm_f": t["final_norm"]}


def _critic(params) -> dict:
    return {"torso": reference_torso(params["torso"]), "head": _layers(params["head"])}


def to_reference_state(state) -> dict:
    """``TrainState`` → the reference's dict (``keyevl2_d4pg_step.step``)."""
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
    zero they would switch every block off; a norm's bias stays around
    zero) and the critic head's second moments large, so that its update
    moves no pre-activation the actor's loss pass meets by more than a
    tenth of ``RELU_MARGIN`` (``learner_torso`` says why)."""
    state = correctness.seeded_state(agent_cfg, seed)

    def lift(tree):
        def one(path, x):
            path = jax.tree_util.keystr(path)
            return x + 1.0 if "norm" in path and "bias" not in path else x
        return jax.tree_util.tree_map_with_path(one, tree)

    adam = state.critic_opt_state[0]
    nu = dict(adam.nu, head=jax.tree_util.tree_map(
        lambda v: v * HEAD_NU_SCALE, adam.nu["head"]))
    return state.replace(
        critic_params=lift(state.critic_params),
        target_critic_params=lift(state.target_critic_params),
        critic_opt_state=(adam._replace(nu=nu),) + tuple(state.critic_opt_state[1:]))


def candidates(agent_cfg, seed, n):
    """``n`` seeded stream windows: ``datagen.batch``'s fields with ``[n, T,
    O]`` observations and the ``[n, T]`` mask of a stream window — every row
    but, in window i, the first ``(i mod 4) · T/16``: a window drawn near the
    ring's first row, so that the check meets the mask too."""
    t = agent_cfg.torso.window
    out = datagen.batch(seed, n, agent_cfg.obs_dim, agent_cfg.action_dim,
                        agent_cfg.gamma ** agent_cfg.n_step,
                        (agent_cfg.dist.v_max - agent_cfg.dist.v_min) / 20.0)
    shape = (n, t, agent_cfg.obs_dim)
    out["obs"] = 2.0 * datagen.uniform(seed, 21, shape) - 1.0
    out["next_obs"] = 2.0 * datagen.uniform(seed, 22, shape) - 1.0
    before = (jnp.arange(n) % 4) * (t // 16)
    out["mask"] = jnp.arange(t)[None, :] >= before[:, None]
    return out


@partial(jax.jit, static_argnums=2)
def selection_counters(keys, valid, topk: int):
    """What the program's key choices are whatever the scores: ``keys [2, L,
    B, T, T]``. Each query must hold exactly ``min(candidates, topk)`` keys
    and none it may not see (future, or masked)."""
    t = keys.shape[-1]
    see = jnp.tril(jnp.ones((t, t), bool))[None] & valid[:, None, :]
    want = jnp.minimum(jnp.sum(see, axis=-1, dtype=jnp.int32), topk)
    count = jnp.sum(keys, axis=-1, dtype=jnp.int32)
    return {"queries_with_a_wrong_count": jnp.sum(count != want),
            "unseen_keys_chosen": jnp.sum(keys & ~see),
            "keys_chosen": jnp.sum(count), "keys_wanted": 2 * keys.shape[1] * jnp.sum(want)}


def choices_check(report: dict) -> dict:
    """Part (i) from the reference's ``report`` (``[2, L]`` arrays: the
    critic's pass and the target's, layer by layer)."""
    out, ok = {"max_differ_share": MAX_DIFFER_SHARE, "max_band_share": MAX_BAND_SHARE}, True
    for kind, margin in (("keys", INDEX_MARGIN), ("experts", ROUTER_MARGIN)):
        get = lambda name: np.asarray(report[f"{kind}_{name}"])  # noqa: E731
        differ, outside, band, places = (
            int(get(n).sum()) for n in ("differ", "out_of_band", "in_band", "places"))
        fine = (outside == 0 and differ <= MAX_DIFFER_SHARE * max(band, 1)
                and band <= MAX_BAND_SHARE * places)
        ok = ok and fine
        out[kind] = {
            "ok": fine, "margin": margin, "places": places, "in_band": band,
            "disagreements": differ, "outside_the_band": outside,
            # the farthest disagreement from its boundary, in margins
            "worst_disagreement": float(get("worst").max()),
            "by_pass_and_layer": get("differ").tolist()}
    return {"ok": ok, **out}


def reference_check(agent_cfg, batch_size: int, seed: int, reference: str, say=print,
                    precision: str | None = "highest") -> dict:
    """Check 1 in its two parts, and the routing and selection counters, at
    the cell's widths and sizes. The reference is handed the torso's sizes
    as the program resolved them (a test holds the configuration file's
    ``torso`` to the same)."""
    from d4pg_tpu.agent.d4pg import train_step

    ref = importlib.import_module(f"cellbench.reference.{reference}")
    dist, torso = agent_cfg.dist, agent_cfg.torso
    t = torso.window
    hp = dict(
        v_min=dist.v_min, v_max=dist.v_max, atoms=dist.num_atoms,
        lr_actor=agent_cfg.lr_actor, lr_critic=agent_cfg.lr_critic,
        b1=agent_cfg.adam_b1, b2=agent_cfg.adam_b2, tau=agent_cfg.tau,
        torso=dataclasses.asdict(torso), index_margin=INDEX_MARGIN,
        router_margin=ROUTER_MARGIN,
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
                              "actor_loss": metrics["actor_loss"],
                              "index_loss": metrics["index_loss"], "priorities": priorities})
        say(f"check 1: program stepped on candidate {i}")
        want_state, want = ref_step(
            to_reference_state(make_state(seed_)), batch,
            {"keys": choices["keys"], "experts": choices["experts"]})
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
            for name in ("critic_loss", "actor_loss", "index_loss", "priorities")}
    ulps = {}
    # m' = (1−b1)·gradient: the gradients themselves, to rounding. The
    # indexer's apart (TOL_INDEXER): critic_grad is every other leaf's.
    apart = lambda tree, keep: [  # noqa: E731
        [v for k, v in sorted(layer.items()) if k.startswith("idx_") == keep]
        for layer in tree["torso"]["layers"]]
    rest = lambda m: [apart(m, False), m["head"],  # noqa: E731
                      {k: v for k, v in m["torso"].items() if k != "layers"}]
    m_want, m_got = want_state["critic_adam"]["m"], got_state["critic_adam"]["m"]
    errs["indexer_grad"] = _compare(apart(m_want, True), apart(m_got, True), rel)
    errs["critic_grad"] = _compare(rest(m_want), rest(m_got), rel)
    errs["actor_grad"] = _compare(
        want_state["actor_adam"]["m"], got_state["actor_adam"]["m"], rel)
    for net in ("actor", "critic"):
        errs[f"{net}_adam_v"] = _compare(
            want_state[f"{net}_adam"]["v"], got_state[f"{net}_adam"]["v"], rel)
        for name in (net, f"target_{net}"):
            ulps[name] = _compare(want_state[name], got_state[name], ulp)
    del want_state, got_state, m_want, m_got
    ok = all(e <= LIMITS.get(name, TOL_REL) for name, e in errs.items()) and all(
        u <= TOL_ULP for u in ulps.values())

    report = jax.device_get(want["report"])
    counters = {k: int(v) for k, v in jax.device_get(selection_counters(
        choices["keys"], batch["mask"], torso.index_topk)).items()}
    load, dropped = (np.asarray(jax.device_get(choices[k])) for k in ("load", "dropped"))
    routing = {
        # the reference counted the given experts on the held ones itself
        "ok": bool(np.array_equal(load, np.asarray(report["load"])) and not dropped.any()
                   and counters["queries_with_a_wrong_count"] == 0
                   and counters["unseen_keys_chosen"] == 0),
        "pairs_on_held_experts": int(load.sum()), "load_min": int(load.min()),
        "load_max": int(load.max()), "dropped": int(dropped.sum()),
        "tokens": int(batch_size * t), "passes_x_layers": int(load.shape[0] * load.shape[1]),
        "index_topk": torso.index_topk, **counters}
    return {
        "reference_step": {
            "ok": bool(ok), "precision": precision or "program default",
            "batch": batch_size, "window": t, "rel_err": errs, "ulp_err": ulps,
            "tol_rel": TOL_REL, "tol_of": LIMITS, "tol_ulp": TOL_ULP,
            "relu_margin": RELU_MARGIN,
            "candidates_passed_over": passed_over, "smallest_preactivations": relu,
            "index_loss": float(got["index_loss"])},
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
    say(f"reference step checked: {checks['reference_step']['ok']}, "
        f"choices: {checks['choices']['ok']}, routing: {checks['routing']['ok']}")
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
