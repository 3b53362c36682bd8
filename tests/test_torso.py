"""The sequence torso (``models/torso.py``) and what carries it: the expert
layer that is told which experts it holds, history windows over the device
ring, acting on a window through the normal entry point, and the refusals.
Tiny sizes on the CPU; what the chip measured is PERF.md's."""

from __future__ import annotations

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.agent.d4pg import (
    act_on_window, acting_params, create_train_state, gather_batches, gather_windows,
    train_step,
)
from d4pg_tpu.agent.state import D4PGConfig, DistConfig
from d4pg_tpu.models import torso as T
from d4pg_tpu.replay.device_ring import DeviceRing

TINY = T.TORSO_PRESETS["glm47_flash_tiny"]


def _layer_params(cfg, seed=0):
    return T._block_init(cfg, jax.random.PRNGKey(seed), moe=True)["ffn"]


def _shared(cfg, p, x):
    """The shared expert's part, behind its gate where the kind has one."""
    if not cfg.n_shared_experts:
        return jnp.zeros_like(x)
    gate = jax.nn.sigmoid(x @ p["shared_gate"]) if cfg.shared_expert_gate else 1.0
    return gate * T.swiglu(p["shared"], x)


def _masked_dense(cfg, p, x):
    """Every held expert on every token, masked: what the grouped products
    must equal."""
    chosen, gates = T.route(cfg, p, x)
    y = _shared(cfg, p, x)
    for e in range(cfg.experts_held):
        gate = jnp.sum(jnp.where(chosen == e + cfg.experts_first, gates, 0.0), -1)
        w = jax.tree_util.tree_map(lambda a: a[e], p["experts"])
        y = y + gate[:, None] * T.swiglu(w, x)
    return y


# The routed layer as it stood until PR 38: the by-expert layout of x (and,
# backward, of dy) gathered whole, [padded_pairs, D], before the block loop.
# The oracle the in-loop gathers are held to, bit for bit.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gather_first(rows, x, gates, w, plan):
    return _gather_first_fwd(rows, x, gates, w, plan)[0]


def _gather_first_fwd(rows, x, gates, w, plan):
    slot, slot_token, _, block_expert, live_blocks = plan
    xs = T._rows(x, slot_token)

    def body(i, ys):
        xb = jax.lax.dynamic_slice_in_dim(xs, i * rows, rows)
        y = T.swiglu(T._expert_weights(w, block_expert[i]), xb)
        return jax.lax.dynamic_update_slice_in_dim(ys, y, i * rows, 0)

    ys = jax.lax.fori_loop(0, live_blocks, body, jnp.zeros_like(xs))
    y = jnp.zeros_like(x)
    for k in range(gates.shape[1]):
        y = y + gates[:, k, None] * T._rows(ys, slot[:, k])
    return y, (xs, gates, w, plan)


def _gather_first_bwd(rows, res, dy):
    xs, gates, w, plan = res
    slot, slot_token, slot_choice, block_expert, live_blocks = plan
    k = gates.shape[1]
    slot_gate = T._rows(gates.reshape(-1), slot_token * k + slot_choice)
    dy_rows = T._rows(dy, slot_token)

    def body(i, carry):
        d_xs, d_slot_gate, d_w = carry
        e = block_expert[i]
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, i * rows, rows)  # noqa: E731
        x, g_out, gate = cut(xs), cut(dy_rows), cut(slot_gate)
        gate_w, up_w, down_w = (T._expert_weights(w, e)[n] for n in ("gate", "up", "down"))
        a, u = x @ gate_w, x @ up_w
        sig = jax.nn.sigmoid(a)
        s = a * sig
        hidden = s * u
        d_gate = jnp.sum(g_out * (hidden @ down_w), axis=-1)
        g_y = gate[:, None] * g_out
        g_h = g_y @ down_w.T
        g_a, g_u = g_h * u * sig * (1.0 + a * (1.0 - sig)), g_h * s
        add = lambda name, g: jax.lax.dynamic_update_index_in_dim(  # noqa: E731
            d_w[name], jax.lax.dynamic_index_in_dim(d_w[name], e, keepdims=False) + g, e, 0)
        d_w = {"gate": add("gate", x.T @ g_a), "up": add("up", x.T @ g_u),
               "down": add("down", hidden.T @ g_y)}
        g_x = g_a @ gate_w.T + g_u @ up_w.T
        return (jax.lax.dynamic_update_slice_in_dim(d_xs, g_x, i * rows, 0),
                jax.lax.dynamic_update_slice_in_dim(d_slot_gate, d_gate, i * rows, 0), d_w)

    d_xs, d_slot_gate, d_w = jax.lax.fori_loop(
        0, live_blocks, body, (jnp.zeros_like(xs), jnp.zeros_like(slot_gate),
                               jax.tree_util.tree_map(jnp.zeros_like, w)))
    dx = jnp.zeros_like(dy)
    for j in range(k):
        dx = dx + T._rows(d_xs, slot[:, j])
    return dx, T._rows(d_slot_gate, slot), d_w, None


_gather_first.defvjp(_gather_first_fwd, _gather_first_bwd)

KEYE_TINY, QWEN_TINY = T.TORSO_PRESETS["keye_vl2_tiny"], T.TORSO_PRESETS["qwen3_next_tiny"]
# (preset, experts_first, experts_held, expert_block_rows, skew): a skew
# {expert: logit} makes those experts' router columns logit · x₀ with every
# token's x₀ ≥ 1, so that the tokens pile onto them
EXPERT_CASES = {
    # the sigmoid router (GLM's kind), shares of the eight experts
    "sigmoid-0of8": (TINY, 0, 8, 8, None),          # every expert held
    "sigmoid-2of4": (TINY, 2, 4, 8, None),
    "sigmoid-6of2": (TINY, 6, 2, 8, None),
    "sigmoid-3of1": (TINY, 3, 1, 8, None),
    "sigmoid-rows4": (TINY, 0, 8, 4, None),
    # several blocks on one expert
    "sigmoid-skewed": (TINY, 0, 8, 8, {1: 10.0}),
    # a group of 33 pairs in 8-row blocks: its last block one pair, seven sentinels
    "sigmoid-last-block-sentinels": (TINY, 0, 4, 8, {2: 10.0}),
    # the softmax router (Keye's and Qwen's kind), with and without a gated
    # shared expert; held experts no token picks
    "softmax-16of16": (KEYE_TINY, 0, 16, 8, None),  # every expert held
    "softmax-4of8-rows16": (KEYE_TINY, 4, 8, 16, None),
    "softmax-skewed-no-pairs": (KEYE_TINY, 0, 8, 8, {3: 20.0, 12: 20.0, 13: 20.0, 14: 20.0}),
    "softmax-gated-shared": (QWEN_TINY, 0, 16, 8, None),
    "softmax-gated-shared-skewed": (QWEN_TINY, 8, 8, 4, {9: 20.0}),
}


def _expert_case(name, tokens=33):
    preset, first, held, rows, skew = EXPERT_CASES[name]
    cfg = dataclasses.replace(preset, experts_first=first, experts_held=held,
                              expert_block_rows=rows)
    p = _layer_params(cfg)
    if cfg.router == "sigmoid_bias":
        p["router_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(9), (cfg.n_routed_experts,))
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, cfg.hidden_size))
    if skew:
        x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 1.0)
    for e, logit in (skew or {}).items():
        p["router"] = p["router"].at[:, e].set(0.0).at[0, e].set(logit)
    return cfg, p, x


@pytest.mark.parametrize("name", EXPERT_CASES)
def test_expert_layer_equals_gather_first_to_the_bit_and_masked_dense(name):
    """The routed part against the gather-first form, bit for bit — ``y``
    and the cotangents of ``x``, the gates and the experts' weights — and
    the whole layer against every held expert on every token, masked."""
    cfg, p, x = _expert_case(name)
    chosen, gates = T.route(cfg, p, x)
    *plan, load = T.dispatch_plan(cfg, chosen)
    held = plan[0] < plan[1].shape[0]
    gates = jnp.where(held, gates, 0.0)
    dy = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    outs = []
    for f in (T.routed_experts, _gather_first):
        y, vjp = jax.vjp(lambda x, g, w: f(cfg.expert_block_rows, x, g, w, tuple(plan)),
                         x, gates, p["experts"])
        outs.append((y, *vjp(dy)))
    for got, want in zip(jax.tree_util.tree_leaves(outs[0]), jax.tree_util.tree_leaves(outs[1])):
        np.testing.assert_array_equal(got, want)
    assert int(load.sum()) == int(held.sum())       # dropless
    rows = cfg.expert_block_rows
    if EXPERT_CASES[name][4]:                       # what the skew was for
        assert int(load.max()) > 2 * rows, load
    if "no-pairs" in name:
        assert int(jnp.sum(load == 0)) >= 1, load
    if name == "sigmoid-last-block-sentinels":
        assert int(load.max()) % rows == 1, load

    y, (load, dropped) = T.expert_layer(cfg, p, x)
    np.testing.assert_allclose(y, _masked_dense(cfg, p, x), atol=2e-6)
    assert int(dropped) == 0 and load.shape == (cfg.experts_held,)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))  # noqa: E731
    got = jax.grad(loss(lambda p, x: T.expert_layer(cfg, p, x)[0]), argnums=(0, 1))(p, x)
    want = jax.grad(loss(lambda p, x: _masked_dense(cfg, p, x)), argnums=(0, 1))(p, x)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=5e-6)
    if cfg.router == "sigmoid_bias":
        assert float(jnp.abs(got[0]["router_bias"]).max()) == 0.0    # a buffer: no gradient


# "%gather.5 = f32[8,1,32]{2,1,0} gather(…), …, metadata={op_name="…"": the
# rows (first dimension), the row width (last) and where it was traced
GATHER = re.compile(r"= \w+\[(\d+),(?:\d+,)*(\d+)\]\S* gather\(.*op_name=\"([^\"]*)\"")


@pytest.mark.parametrize("preset", [KEYE_TINY, QWEN_TINY], ids=["indexed", "hybrid"])
def test_no_gather_of_the_whole_dispatch_buffer(preset):
    """The layer's forward and backward, compiled: no ``[padded_pairs, D]``
    gather anywhere (what the forward rule keeps is ``x``, not its by-expert
    layout; the backward rule reads ``dy`` by block), ``[R, D]`` gathers in
    the loops' bodies."""
    cfg = dataclasses.replace(preset, experts_first=2, experts_held=8)
    p = _layer_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, cfg.hidden_size))
    big, block = cfg.padded_pairs(40), cfg.expert_block_rows
    assert big not in (40, block)

    def loss(p, x):
        return jnp.sum(jnp.sin(T.expert_layer(cfg, p, x)[0]))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile().as_text()
    wide = [(int(rows), name) for rows, width, name in GATHER.findall(text)
            if int(width) == cfg.hidden_size]
    assert not [name for rows, name in wide if rows == big], wide
    in_loops = [name for rows, name in wide if rows == block]
    assert len(in_loops) == 3 and all("while/body" in name for name in in_loops), wide
    chosen, gates = T.route(cfg, p, x)
    plan = T.dispatch_plan(cfg, chosen)[:5]
    _, residual = T._routed_fwd(block, x, gates, p["experts"], plan)
    assert all(np.shape(a)[:1] != (big,) or np.ndim(a) < 2
               for a in jax.tree_util.tree_leaves(residual))


def test_the_layout_is_described_and_the_rows_gathered_follow_the_load():
    full = dataclasses.replace(T.TORSO_PRESETS["qwen3_next"], num_hidden_layers=4,
                               experts_held=16)
    assert T.describe_experts(full, 8192) == {
        "tokens": 8192, "padded_pairs": 86016, "block_rows": 256,
        "worst_case_blocks": 336, "rows_gathered_a_block": 256}
    keye = dataclasses.replace(T.TORSO_PRESETS["keye_vl2"], num_hidden_layers=4,
                               experts_held=16)
    assert T.describe_experts(keye, 8192)["padded_pairs"] == 73728
    load = np.array([[0, 1, 256, 257], [512, 0, 0, 3]])
    assert T.gathered_rows(full, load).tolist() == [256 + 256 + 512, 512 + 256]
    cfg, p, x = _expert_case("sigmoid-skewed")
    chosen, _ = T.route(cfg, p, x)
    *_, live_blocks, load = T.dispatch_plan(cfg, chosen)
    assert int(T.gathered_rows(cfg, load)) == int(live_blocks) * cfg.expert_block_rows


def test_the_selection_bias_enters_the_choice_only():
    cfg = dataclasses.replace(TINY, experts_held=8)
    p = _layer_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.hidden_size))
    chosen0, gates0 = T.route(cfg, p, x)
    p["router_bias"] = jnp.zeros(8).at[5].set(10.0)       # expert 5 is now always chosen
    chosen1, gates1 = T.route(cfg, p, x)
    assert bool(jnp.all(jnp.any(chosen1 == 5, axis=-1)))
    scores = jax.nn.sigmoid(x @ p["router"])
    picked = jnp.take_along_axis(scores, chosen1, axis=-1)     # gates from the scores alone
    np.testing.assert_allclose(
        gates1, cfg.routed_scaling_factor * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(gates0.sum(-1), cfg.routed_scaling_factor, rtol=1e-6)


def test_dropless_under_a_skewed_router():
    """Every token sends a pair to the same held expert (and the worst-case
    buffer takes them): nothing dropped, the result the masked dense one."""
    cfg = dataclasses.replace(TINY, experts_first=2, experts_held=2)
    p = _layer_params(cfg)
    p["router_bias"] = jnp.zeros(8).at[2].set(10.0).at[3].set(9.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (37, cfg.hidden_size))     # not a block multiple
    chosen, _ = T.route(cfg, p, x)
    *_, live_blocks, load = T.dispatch_plan(cfg, chosen)
    assert load.tolist() == [37, 37]                    # k = 2: both held experts, every token
    assert int(live_blocks) * cfg.expert_block_rows <= cfg.padded_pairs(37)
    y, (_, dropped) = T.expert_layer(cfg, p, x)
    assert int(dropped) == 0
    np.testing.assert_allclose(y, _masked_dense(cfg, p, x), atol=2e-6)
    # and with no pair on a held expert the shared expert alone answers
    p["router_bias"] = jnp.zeros(8).at[6].set(10.0).at[7].set(9.0)
    y, (load, dropped) = T.expert_layer(cfg, p, x)
    assert load.tolist() == [0, 0] and int(dropped) == 0
    np.testing.assert_allclose(y, T.swiglu(p["shared"], x), atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each, their routed parts added and the
    shared expert counted once, against the plain reference's layer with all
    eight experts held (the model-configs guide, section 4)."""
    from cellbench.reference import glm47flash_d4pg_step as ref

    whole = dataclasses.replace(TINY, experts_first=0, experts_held=8)
    p = _layer_params(whole, seed=3)
    p["router_bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (8,))
    x = jax.random.normal(jax.random.PRNGKey(5), (48, whole.hidden_size))
    shared = T.swiglu(p["shared"], x)
    total = shared
    for i in range(4):
        share = dataclasses.replace(TINY, experts_first=2 * i, experts_held=2)
        part = dict(p, experts=jax.tree_util.tree_map(lambda a: a[2 * i:2 * i + 2], p["experts"]))
        total = total + T.expert_layer(share, part, x)[0] - shared
    names = lambda w: {"w_gate": w["gate"], "w_up": w["up"], "w_down": w["down"]}  # noqa: E731
    want, _, load = ref.moe(
        {"w_router": p["router"], "e_bias": p["router_bias"],
         "experts": names(p["experts"]), "shared": names(p["shared"])},
        x, dict(dataclasses.asdict(whole)))
    np.testing.assert_allclose(total, want, atol=5e-6)
    assert int(load.sum()) == 48 * whole.num_experts_per_tok


def test_chunked_blocks_equal_the_whole_batch():
    cfg = dataclasses.replace(TINY, experts_first=2, experts_held=4)
    params = T.torso_init(cfg, jax.random.PRNGKey(0), 5)
    obs = jax.random.normal(jax.random.PRNGKey(1), (8, cfg.window, 5))
    valid = jnp.ones((8, cfg.window), bool).at[0, :2].set(False).at[3, :3].set(False)
    h4, _ = T.torso_apply(cfg, params, obs, valid)
    h1, _ = T.torso_apply(dataclasses.replace(cfg, batch_chunks=1), params, obs, valid)
    np.testing.assert_allclose(h4, h1, atol=1e-6)
    # a masked position changes nothing downstream: its content is free
    obs2 = obs.at[0, :2].set(7.0).at[3, :3].set(-3.0)
    np.testing.assert_allclose(T.torso_apply(cfg, params, obs2, valid)[0], h4, atol=1e-6)
    assert bool(jnp.all(jnp.isfinite(h4)))


# ------------------------------------------------------------ the windows
def _ring(capacity, obs_dim, act_dim, size, terminal_rows, seed=0):
    rng = np.random.default_rng(seed)
    fields = dict(
        obs=rng.standard_normal((capacity, obs_dim)).astype(np.float32),
        action=rng.standard_normal((capacity, act_dim)).astype(np.float32),
        reward=rng.standard_normal((capacity,)).astype(np.float32),
        next_obs=rng.standard_normal((capacity, obs_dim)).astype(np.float32),
        discount=np.full((capacity,), 0.95, np.float32))
    fields["discount"][list(terminal_rows)] = 0.0
    ring = DeviceRing(size=jnp.int32(size), **{k: jnp.asarray(v) for k, v in fields.items()})
    return ring, fields


def _mask_by_loop(discount, idx, window, stride):
    """Per sample, in plain Python: position j holds row idx − (T−1−j)·stride;
    it is valid when that row exists and no row from it up to (not
    including) the drawn row ended an episode."""
    out = np.zeros(idx.shape + (window,), bool)
    for where in np.ndindex(idx.shape):
        for j in range(window):
            row = int(idx[where]) - (window - 1 - j) * stride
            if row < 0:
                continue
            between = range(row, int(idx[where]), stride)
            out[where + (j,)] = all(discount[q] != 0.0 for q in between)
    return out


@pytest.mark.parametrize("obs_dim, capacity, size, stride, window", [
    (376, 64, 64, 1, 8),      # packed storage (16 rows to a storage row), full ring
    (376, 64, 40, 2, 5),      # two interleaved streams, ring not full
    (17, 50, 3, 1, 6),        # fill < T: every window starts before the first row
    (136, 64, 64, 4, 4),      # another packed width, four streams
])
def test_window_gather_equals_the_logical_rows_and_a_python_loop(
        obs_dim, capacity, size, stride, window):
    ring, fields = _ring(capacity, obs_dim, 3, size, terminal_rows=(5, 6, 17, 30, 31, 39))
    rng = np.random.default_rng(1)
    idx = rng.integers(0, size, (3, 16))
    idx[0, :6] = [0, 1, size - 1, 5 % size, 6 % size, 7 % size]     # the edges, on purpose
    got = jax.jit(lambda r, i: gather_windows(r, i, window, stride))(ring, jnp.asarray(idx))
    pos = idx[..., None] + (np.arange(window) - (window - 1)) * stride
    want_mask = _mask_by_loop(fields["discount"], idx, window, stride)
    np.testing.assert_array_equal(np.asarray(got["mask"]), want_mask)
    assert np.asarray(got["mask"])[..., -1].all()               # the drawn row is always valid
    for name in ("obs", "next_obs"):
        rows = fields[name][np.maximum(pos, 0)]
        valid = want_mask[..., None]
        np.testing.assert_array_equal(np.where(valid, np.asarray(got[name]), 0.0),
                                      np.where(valid, rows, 0.0))
        assert got[name].shape == idx.shape + (window, obs_dim)
    for name in ("action", "reward", "discount"):
        np.testing.assert_array_equal(np.asarray(got[name]), fields[name][idx])
    assert got["weights"].shape == idx.shape
    if obs_dim >= 128:
        assert ring.rows_packed("obs") > 1, "the packed storage did not engage"


def test_gather_batches_switches_on_the_torso_and_not_otherwise():
    ring, fields = _ring(32, 5, 2, 32, terminal_rows=())
    idx = jnp.asarray([[3, 9]])
    plain = gather_batches(ring, idx)
    assert plain["obs"].shape == (1, 2, 5) and "mask" not in plain
    windows = gather_batches(ring, idx, dataclasses.replace(TINY, window=3, row_stride=2))
    assert windows["obs"].shape == (1, 2, 3, 5) and windows["mask"].shape == (1, 2, 3)
    np.testing.assert_array_equal(np.asarray(windows["obs"])[0, 1], fields["obs"][[5, 7, 9]])


# ------------------------------------------------------ the agent around it
def _agent(**kw) -> D4PGConfig:
    torso = dataclasses.replace(TINY, experts_first=2, experts_held=4, **kw)
    return D4PGConfig(obs_dim=5, action_dim=2, hidden_sizes=(16, 16),
                      dist=DistConfig(num_atoms=11, v_min=-5.0, v_max=5.0), torso=torso)


def test_the_critic_owns_the_torso_and_the_actor_reads_it_under_stop_gradient():
    cfg = _agent()
    state = create_train_state(cfg, jax.random.PRNGKey(0))
    assert set(state.critic_params) == {"torso", "head"}
    assert set(state.target_critic_params) == {"torso", "head"}
    assert state.actor_params["params"]["hidden_0"]["kernel"].shape[0] == cfg.torso.hidden_size
    assert len(state.critic_params["torso"]["layers"]) == cfg.torso.num_hidden_layers
    key = jax.random.PRNGKey(1)
    b, t = 8, cfg.torso.window
    batch = dict(
        obs=jax.random.normal(key, (b, t, 5)), next_obs=jax.random.normal(key, (b, t, 5)) + 1.0,
        mask=jnp.ones((b, t), bool).at[0, :2].set(False),
        action=jnp.zeros((b, 2)), reward=jnp.ones((b,)), discount=jnp.full((b,), 0.9),
        weights=jnp.ones((b,)))
    new, metrics, priorities = jax.jit(lambda s, x: train_step(cfg, s, x))(state, batch)
    assert priorities.shape == (b,) and bool(jnp.isfinite(metrics["critic_loss"]))
    moved = lambda a, b_: max(float(jnp.abs(x - y).max()) for x, y in zip(  # noqa: E731
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b_)))
    assert moved(new.critic_params["torso"], state.critic_params["torso"]) > 0
    assert moved(new.target_critic_params["torso"], state.target_critic_params["torso"]) > 0
    assert moved(new.actor_params, state.actor_params) > 0
    bias = lambda s: [p["ffn"]["router_bias"] for p in s.critic_params["torso"]["layers"][1:]]  # noqa: E731
    assert moved(bias(new), bias(state)) == 0.0         # the buffer stays
    # acting: the actor head on the torso's output of the window
    params = acting_params(cfg, state)
    assert set(params) == {"head", "torso"}
    a = act_on_window(cfg, params, batch["obs"], batch["mask"])
    assert a.shape == (b, 2) and bool(jnp.all(jnp.abs(a) <= 1.0))
    assert acting_params(dataclasses.replace(cfg, torso=None), state) is state.actor_params


@pytest.mark.parametrize("kw, match", [
    (dict(twin_critic=True), "ONE critic"),
    (dict(critic_ensemble=3), "ONE critic"),
    (dict(projection_backend="pallas_fused"), "categorical head"),
    (dict(dist=DistConfig(kind="scalar")), "categorical head"),
])
def test_what_a_torso_does_not_compose_with_is_refused_by_name(kw, match):
    with pytest.raises(ValueError, match=match):
        create_train_state(dataclasses.replace(_agent(), **kw), jax.random.PRNGKey(0))


def test_a_share_outside_the_router_is_refused():
    with pytest.raises(ValueError, match="holds experts"):
        T.validate(dataclasses.replace(TINY, experts_first=6, experts_held=4))


def test_negotiation_refuses_the_paths_that_keep_no_window():
    from d4pg_tpu.replay.source import RequestedCaps, negotiate

    ok = RequestedCaps(placement="device", torso=True, is_jax_env=True)
    assert negotiate(ok).ok
    for change, code in [
        (dict(placement="host"), "torso_device_placement_only"),
        (dict(dp=4, batch_size=256), "torso_single_device"),
        (dict(her=True), "torso_sync_jax_collection_only"),
        (dict(async_collect=True), "torso_sync_jax_collection_only"),
        (dict(is_jax_env=False), "torso_sync_jax_collection_only"),
        (dict(fused_descent=True, projection="pallas_fused"), "torso_no_fused_descent"),
    ]:
        n = negotiate(dataclasses.replace(ok, **change))
        assert code in {g.code for g in n.gaps}, (change, [g.code for g in n.gaps])
    # and without a torso none of them is uttered
    assert not any(g.code.startswith("torso") for g in negotiate(
        dataclasses.replace(ok, torso=False, placement="host")).gaps)


# --------------------------------------------- through the normal entry point
ARGV = ["--env", "pendulum", "--torso", "glm47_flash_tiny", "--torso-experts-held", "2:4",
        "--replay-placement", "device", "--p-replay", "--n-step", "1",
        "--steps-per-dispatch", "2", "--total-steps", "8", "--warmup", "128",
        "--num-envs", "2", "--bsize", "16", "--rmsize", "1024", "--hidden-sizes", "16,16",
        "--eval-interval", "8", "--eval-episodes", "1", "--checkpoint-interval", "1000000"]


def test_flags_resolve_to_the_preset_and_the_share():
    from train import build_parser, config_from_args

    cfg = config_from_args(build_parser().parse_args(
        ARGV + ["--torso-layers", "4", "--torso-window", "6"]))
    t = cfg.agent.torso
    assert (t.name, t.num_hidden_layers, t.window) == ("glm47_flash_tiny", 4, 6)
    assert (t.experts_first, t.experts_held, t.n_routed_experts) == (2, 4, 8)
    assert t.row_stride == 2                              # the writer's env interleave
    assert config_from_args(build_parser().parse_args(["--env", "pendulum"])).agent.torso is None


def test_acting_on_a_window_through_train_main(tmp_path):
    """``train.py --torso …`` on pendulum: the collector's policies carry each
    env's last T observations, the rows of one env's stream lie ``num_envs``
    apart in the ring, the megastep trains on windows, eval acts on a window."""
    import train

    trainer = train.main(ARGV + ["--log-dir", str(tmp_path)])
    assert trainer.grad_steps == 8 and trainer.env_steps >= 128
    noise, window, count = trainer.noise_states
    t = trainer.config.agent.torso.window
    assert window.shape == (2, t, 3) and count.shape == (2,)
    assert int(count.max()) == t                         # full windows after 64 steps an env
    buf, n = trainer.buffer, len(trainer.buffer)
    assert n >= 128
    # n-step 1: a row's next_obs is the observation two rows on (same env),
    # wherever the episode did not end in between
    same = np.all(buf.next_obs[: n - 2] == buf.obs[2:n], axis=1)
    assert same.mean() > 0.9
    assert not np.all(buf.next_obs[: n - 1] == buf.obs[1:n], axis=1).any()


def test_export_bundle_refuses_a_torso_actor(tmp_path):
    import train

    with pytest.raises(SystemExit, match="stateful serving sessions"):
        train.main(ARGV + ["--log-dir", str(tmp_path), "--export-bundle", str(tmp_path / "b")])
    from d4pg_tpu.serve.bundle import actor_template

    with pytest.raises(ValueError, match="stateless actor"):
        actor_template(_agent())
