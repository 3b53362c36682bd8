"""The torso's third kind (``models/torso.py``: ``HybridTorsoConfig``):
Gated DeltaNet layers — the gated delta rule's state along the time axis,
``ops/gated_delta.py`` — with a gated grouped-query attention layer every
fourth, zero-centred norms, a softmax router with a gated shared expert.
Tiny sizes on the CPU; what the chip measured is PERF.md's."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench.reference import qwen3next_d4pg_step as ref
from d4pg_tpu.agent.d4pg import create_train_state, train_step
from d4pg_tpu.agent.state import D4PGConfig, DistConfig
from d4pg_tpu.models import torso as T
from d4pg_tpu.ops import gated_delta as gd

TINY = T.TORSO_PRESETS["qwen3_next_tiny"]
SIZES = dataclasses.asdict(TINY)


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _rule_inputs(b=2, t=16, h=3, dk=8, dv=5, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def _window(cfg=TINY, b=3, obs_dim=5, seed=1):
    obs = jax.random.normal(jax.random.PRNGKey(seed), (b, cfg.window, obs_dim))
    valid = jnp.ones((b, cfg.window), bool).at[0, :4].set(False)     # a masked prefix
    return obs, valid


# ------------------------------------------------------------ the delta rule
# T = 16 never reaches the blocked inverse (chunks ≤ 16 keep the row form);
# T = 128 in chunks of 32 and 64 does, with one and two join levels, and the
# scan hands a state across four and two chunks. The longer window gets 5e-6
# where the short one has 2e-6: the final state sums 128 decayed writes and
# not 16, in another order than the recurrence (read on the CPU: state
# 1.2e-6 at chunk 64 against 2.4e-7 at T = 16, outputs 2.4e-7 against 6.7e-8).
# The gradients keep their limit: 1.3e-6 of the largest element read, 4.5e-7 at
# T = 16, against 2e-5.
@pytest.mark.parametrize("chunk, t", [(1, 16), (4, 16), (16, 16), (32, 128), (64, 128)])
def test_chunked_scan_is_the_recurrence_values_and_gradients(chunk, t):
    args = _rule_inputs(t=t)
    atol = 2e-6 if t == 16 else 5e-6
    out, state = gd.gated_delta_recurrent(*args)
    got, got_state = gd.gated_delta_chunked(*args, chunk=chunk)
    np.testing.assert_allclose(got, out, atol=atol)
    np.testing.assert_allclose(got_state, state, atol=atol)

    def loss(form):
        def f(*a):
            o, s = form(*a)
            return jnp.sum(jnp.sin(o)) + jnp.sum(jnp.square(s))
        return f

    want = jax.grad(loss(gd.gated_delta_recurrent), argnums=(0, 1, 2, 3, 4))(*args)
    grads = jax.grad(loss(lambda *a: gd.gated_delta_chunked(*a, chunk=chunk)),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for w, g in zip(want, grads):
        assert float(jnp.abs(w).max()) > 1e-3
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()) + 1e-6)


def test_the_rule_is_its_three_lines_by_hand():
    """One head, three tokens, in numpy: decay, delta-rule write, read."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in _rule_inputs(b=1, t=3, h=1))
    state, outs = np.zeros((8, 5)), []
    for t in range(3):
        state = np.exp(g[0, t, 0]) * state
        state = state + np.outer(k[0, t, 0], beta[0, t, 0] * (v[0, t, 0] - state.T @ k[0, t, 0]))
        outs.append(state.T @ q[0, t, 0])
    got, got_state = gd.gated_delta_chunked(*_rule_inputs(b=1, t=3, h=1), chunk=3)
    np.testing.assert_allclose(got[0, :, 0], np.stack(outs), atol=1e-6)
    np.testing.assert_allclose(got_state[0, 0], state, atol=1e-6)


# ------------------------------------------------- the chunk's (I + L)⁻¹
def _parent_row_form(lower):
    """PR 34's ``unit_lower_inverse``, to the letter: the oracle of the bits."""
    c = lower.shape[-1]
    eye = jnp.eye(c, dtype=lower.dtype)

    def row(i, inverse):
        l_i = jax.lax.dynamic_index_in_dim(lower, i, axis=-2, keepdims=False)
        x_i = eye[i] - jnp.sum(l_i[..., :, None] * inverse, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(inverse, x_i, i, axis=-2)

    return jax.lax.fori_loop(0, c, row, jnp.zeros_like(lower))


def _systems(c, lead=(3, 2), seed=0):
    return jnp.tril(jax.random.normal(jax.random.PRNGKey(seed), lead + (c, c)), -1) * c ** -0.5


@pytest.mark.parametrize("c", [1, 3, 6, 16, 32, 64])
def test_unit_lower_inverse_and_its_backward_pass(c):
    """Values and the custom backward pass against a float64 inverse."""
    lower = _systems(c)
    inverse = gd.unit_lower_inverse(lower)
    want = np.linalg.inv(np.eye(c) + np.asarray(lower, np.float64))
    scale = np.abs(want).max()
    np.testing.assert_allclose(inverse, want, atol=2e-6 * scale)
    if c >= 32:       # exactly unit lower-triangular, to the bit: the joins keep the zeros
        assert np.array_equal(np.triu(inverse, 1), np.zeros_like(inverse))
        assert not np.signbit(np.triu(inverse, 1)).any()
        assert np.array_equal(np.diagonal(inverse, axis1=-2, axis2=-1),
                              np.ones(inverse.shape[:-1], np.float32))
    # f = Σ sin X: X̄ = cos X, and L̄ = −Xᵀ X̄ Xᵀ on the strict lower triangle
    t = np.swapaxes(want, -1, -2)
    want_grad = np.tril(-t @ np.cos(want) @ t, -1)
    got = jax.grad(lambda x: jnp.sum(jnp.sin(gd.unit_lower_inverse(x))))(lower)
    np.testing.assert_allclose(got, want_grad, atol=2e-5 * max(np.abs(want_grad).max(), 1.0))


def test_a_window_that_is_not_whole_chunks_is_refused():
    with pytest.raises(ValueError, match="whole chunks"):
        gd.gated_delta_chunked(*_rule_inputs(), chunk=5)


@pytest.mark.parametrize("c, plan", [(1, (1, 0)), (4, (4, 0)), (6, (6, 0)), (16, (16, 0)),
                                     (32, (16, 1)), (48, (48, 0)), (64, (16, 2))])
def test_the_inverse_plan_follows_the_size_alone(c, plan):
    """The statement that the blocked form engaged: static, by shape."""
    assert gd.inverse_plan(c) == plan


@pytest.mark.parametrize("c", [1, 4, 6, 16])
def test_small_systems_keep_the_row_form_to_the_bit(c):
    """One block: the tiny preset (chunk 4) and every chunk ≤ 16 trace the
    parent's loop, and the blocked sizes' base case is that loop too."""
    lower = _systems(c, seed=c)
    want = _parent_row_form(lower)
    assert np.array_equal(gd.unit_lower_inverse(lower), want)
    assert np.array_equal(gd.inverse_by_rows(lower), want)


def _product_form(lower):
    """``(I − L)(I + L²)(I + L⁴)…``: exact in exact arithmetic (``L`` is
    nilpotent), and what the blocked form must never be simplified to."""
    c = lower.shape[-1]
    eye = jnp.eye(c, dtype=lower.dtype)
    inverse, power = eye - lower, lower @ lower
    while c > 2:
        inverse, power, c = inverse @ (eye + power), power @ power, c // 2
    return inverse


def test_the_blocked_inverse_on_correlated_keys_where_the_product_form_fails():
    """Adjacent rows of one stream: unit keys a few degrees apart, β ≈ 0.95,
    slow decay, so every entry of ``L`` is ≈ 0.9 and its powers grow to
    1e+17 before they cancel. The blocked form reads the row form's error
    (3.5e-7 and 3.1e-7 of the inverse's scale); the product form 3e+10."""
    c, ks = 64, jax.random.split(jax.random.PRNGKey(5), 3)
    k = jax.random.normal(ks[0], (8, 1, 128)) + 0.1 * jax.random.normal(ks[1], (8, c, 128))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = 0.95 + 0.04 * jax.random.uniform(ks[2], (8, c))
    gamma = jnp.cumsum(jnp.full((8, c), -1e-3), axis=-1)
    decay = jnp.exp(gamma[..., :, None] - gamma[..., None, :])
    lower = jnp.tril(jnp.einsum("sik,sjk->sij", k * beta[..., None], k) * decay, -1)
    assert float(lower[:, 1, 0].min()) > 0.85
    want = np.linalg.inv(np.eye(c) + np.asarray(lower, np.float64))
    error = lambda x: np.abs(np.asarray(x, np.float64) - want).max() / np.abs(want).max()  # noqa: E731
    assert error(gd.unit_lower_inverse(lower)) <= 2e-6
    assert error(gd.inverse_by_rows(lower)) <= 2e-6
    assert error(_product_form(lower)) > 1e3


def _loop_trips(jaxpr) -> list:
    """Trip counts of every loop in a jaxpr, nested calls included (a
    ``fori_loop`` of static bounds is a ``scan``; a ``while`` counts as ∞)."""
    trips = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            trips.append(eqn.params["length"])
        elif eqn.primitive.name == "while":
            trips.append(float("inf"))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    trips += _loop_trips(inner)
    return trips


def test_the_blocked_inverse_holds_no_loop_over_the_whole_system():
    """At 64 × 64 the only sequential steps are the base case's 16 (the
    parent's jaxpr holds one loop of 64)."""
    lower = _systems(64, lead=(2,))
    assert _loop_trips(jax.make_jaxpr(_parent_row_form)(lower).jaxpr) == [64]
    trips = _loop_trips(jax.make_jaxpr(gd.unit_lower_inverse)(lower).jaxpr)
    assert trips and max(trips) <= 16, trips
    grad = jax.make_jaxpr(jax.grad(lambda x: jnp.sum(gd.unit_lower_inverse(x))))(lower)
    assert max(_loop_trips(grad.jaxpr)) <= 16          # the loop is not differentiated


# ------------------------------------------- the two mixers against the reference
def _lin_as_reference(p):
    return {"w_qkvz": p["in_qkvz"], "w_ba": p["in_ba"], "conv": p["conv"], "a_log": p["A_log"],
            "dt_bias": p["dt_bias"], "o_norm": p["norm"], "w_out": p["out"]}


@pytest.mark.parametrize("chunk", [1, 4, 16])
def test_the_deltanet_mixer_is_the_references_over_a_masked_prefix(chunk):
    cfg = dataclasses.replace(TINY, delta_chunk=chunk)
    p = T._block_init(cfg, jax.random.PRNGKey(0), moe=True, layer=0)["lin"]
    p = dict(p, dt_bias=p["dt_bias"] + 2.0)                 # a decay worth the name
    x, valid = _window(obs_dim=cfg.hidden_size)
    want_fn = lambda p, x: ref.delta_net(_lin_as_reference(p), x, valid, SIZES)  # noqa: E731
    got_fn = lambda p, x: T.gated_delta_net(cfg, p, x, valid)  # noqa: E731
    want, got = want_fn(p, x), got_fn(p, x)
    assert got.shape == x.shape and float(jnp.abs(want).max()) > 1e-2
    np.testing.assert_allclose(got, want, atol=2e-6)
    g_want = jax.grad(lambda p, x: jnp.sum(jnp.sin(3.0 * want_fn(p, x))), argnums=(0, 1))(p, x)
    g_got = jax.grad(lambda p, x: jnp.sum(jnp.sin(3.0 * got_fn(p, x))), argnums=(0, 1))(p, x)
    for (path, w), g in zip(jax.tree_util.tree_leaves_with_path(g_want),
                            jax.tree_util.tree_leaves(g_got)):
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()), err_msg=str(path))
    # a masked position writes nothing: what it holds is free, whatever follows it
    x2 = x.at[0, :4].set(7.0)
    np.testing.assert_array_equal(np.asarray(got_fn(p, x2))[0, 4:], np.asarray(got)[0, 4:])
    assert float(jnp.abs(g_got[1][0, :4]).max()) == 0.0


def test_the_convolution_is_causal_and_reads_zeros_before_the_window():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 6, 3))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 4))
    got = np.asarray(T.causal_conv(x, w))
    xn, wn = np.asarray(x), np.asarray(w)
    for t in range(6):
        want = sum(wn[:, j] * (xn[:, t - 3 + j] if t - 3 + j >= 0 else 0.0) for j in range(4))
        np.testing.assert_allclose(got[:, t], want, atol=1e-6)     # the last tap is the token itself


def test_the_gated_attention_mixer_is_the_references():
    p = T._block_init(TINY, jax.random.PRNGKey(0), moe=True, layer=3)["attn"]
    p = dict(p, q_norm=p["q_norm"] + 0.3, k_norm=p["k_norm"] - 0.2)
    x, valid = _window(obs_dim=TINY.hidden_size)
    names = {"wq": p["q"], "q_norm": p["q_norm"], "wk": p["k"], "k_norm": p["k_norm"],
             "wv": p["v"], "wo": p["o"]}
    want = ref.attention(names, x, valid, SIZES, {"query_block": 8})
    for chunks in (1, 4, 16):
        cfg = dataclasses.replace(TINY, query_chunks=chunks)
        got = T.gated_attention(cfg, p, x, valid)
        np.testing.assert_allclose(np.asarray(got)[0, 4:], np.asarray(want)[0, 4:], atol=2e-6)
        np.testing.assert_allclose(got[1:], want[1:], atol=2e-6)
    g_want = jax.grad(lambda p: jnp.sum(jnp.sin(ref.attention(
        {**names, "wq": p["q"], "wo": p["o"]}, x, valid, SIZES, {})[1:])))(p)
    g_got = jax.grad(lambda p: jnp.sum(jnp.sin(T.gated_attention(TINY, p, x, valid)[1:])))(p)
    for name in ("q", "o"):        # the gate rides in q's columns
        np.testing.assert_allclose(g_got[name], g_want[name], atol=2e-6)


def test_rotary_turns_a_quarter_of_the_head():
    assert TINY.rotary_dim == 2 and T.TORSO_PRESETS["qwen3_next"].rotary_dim == 64
    p = T._block_init(TINY, jax.random.PRNGKey(0), moe=True, layer=3)["attn"]
    x, valid = _window(obs_dim=TINY.hidden_size)
    p = dict(p, q=4.0 * p["q"], k=4.0 * p["k"])
    # a query's scores depend on position through the turned dims alone: with
    # them zeroed in q (its norm's weight at -1 there: 1 + w = 0) the last
    # position, which sees every key, cannot tell the order of the others
    flat = dict(p, q_norm=p["q_norm"].at[:TINY.rotary_dim].set(-1.0))
    full = jnp.ones_like(valid)
    mixed = jnp.concatenate([x[:, -2::-1], x[:, -1:]], axis=1)        # all but the last, reversed
    last = lambda p, x: T.gated_attention(TINY, p, x, full)[:, -1]  # noqa: E731
    np.testing.assert_allclose(last(flat, mixed), last(flat, x), atol=1e-6)
    assert float(jnp.abs(last(p, mixed) - last(p, x)).max()) > 1e-4   # the turn live: it can
    cos, sin = T.rope_tables(TINY.rope_theta, 64, 8)
    assert cos.shape == (8, 32)       # 64 of 256 dims: 32 pairs, θ^(−2i/64)
    np.testing.assert_allclose(cos[1, 1], np.cos(1e7 ** (-1 / 32)), rtol=1e-6)


def test_zero_centred_norms():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 32)) * 3.0
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    rms = jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + TINY.rms_norm_eps)
    np.testing.assert_allclose(T.block_norm(TINY, x, w), x / rms * (1.0 + w), rtol=1e-6)
    np.testing.assert_allclose(T.block_norm(TINY, x, w), ref.norm(x, w, 1e-6), rtol=1e-6)
    glm = T.TORSO_PRESETS["glm47_flash_tiny"]
    np.testing.assert_allclose(T.block_norm(glm, x, w), x / jnp.sqrt(
        jnp.mean(x * x, -1, keepdims=True) + glm.rms_norm_eps) * w, rtol=1e-6)
    params = T.torso_init(TINY, jax.random.PRNGKey(0), 5)
    first, last = params["layers"][0], params["layers"][3]
    for zero in (first["attn_norm"], first["ffn_norm"], last["attn"]["q_norm"],
                 last["attn"]["k_norm"], params["final_norm"]):
        assert float(jnp.abs(zero).max()) == 0.0
    assert float(first["lin"]["norm"].min()) == 1.0         # the gated norm is not zero-centred
    assert set(first) == {"attn_norm", "ffn_norm", "lin", "ffn"}
    assert set(last) == {"attn_norm", "ffn_norm", "attn", "ffn"}
    a, dt = jnp.exp(first["lin"]["A_log"]), jax.nn.softplus(first["lin"]["dt_bias"])
    assert 0 < float(a.min()) and float(a.max()) <= 16.0
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.001


# ------------------------------------------------------------ the expert layer
def _layer_params(cfg, seed=0):
    return T._block_init(cfg, jax.random.PRNGKey(seed), moe=True, layer=0)["ffn"]


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0:8 + 8:8 of the 16-expert tiny layer, their routed parts
    added and the gated shared expert counted once, against the plain
    reference's layer with all sixteen held (model-configs guide, section 4)."""
    whole = dataclasses.replace(TINY, experts_first=0, experts_held=16)
    p = _layer_params(whole, seed=3)
    assert set(p) == {"router", "experts", "shared", "shared_gate"}
    x = jax.random.normal(jax.random.PRNGKey(5), (48, whole.hidden_size))
    shared = jax.nn.sigmoid(x @ p["shared_gate"]) * T.swiglu(p["shared"], x)
    assert float(jnp.abs(shared).max()) > 1e-3
    total = -shared                                        # two shares carry it twice
    for first in (0, 8):
        share = dataclasses.replace(TINY, experts_first=first, experts_held=8)
        part = dict(p, experts=jax.tree_util.tree_map(lambda a: a[first:first + 8], p["experts"]))
        y, (load, dropped) = T.expert_layer(share, part, x)
        assert int(dropped) == 0
        total = total + y
    chosen, gates = T.route(whole, p, x)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)       # norm_topk_prob
    swiglu = lambda w: {"w_gate": w["gate"], "w_up": w["up"], "w_down": w["down"]}  # noqa: E731
    want, report, load = ref.moe(
        {"w_router": p["router"], "experts": swiglu(p["experts"]), "shared": swiglu(p["shared"]),
         "w_shared_gate": p["shared_gate"]}, x, jnp.ones((48,), bool),
        dataclasses.asdict(whole), chosen, {"router_margin": 1e-6})
    np.testing.assert_allclose(total, want, atol=5e-6)
    assert int(load.sum()) == 48 * whole.num_experts_per_tok and int(report["differ"]) == 0


# ------------------------------------------------------------ the stack
def test_the_mixer_is_a_function_of_the_layer_index():
    full = T.TORSO_PRESETS["qwen3_next"]
    kinds = [full.mixer(i) for i in range(full.num_hidden_layers)]
    assert kinds.count("attention") == 12 and kinds[:4] == ["linear"] * 3 + ["attention"]
    assert all((k == "attention") == ((i + 1) % 4 == 0) for i, k in enumerate(kinds))
    said = T.describe_mixers(dataclasses.replace(full, num_hidden_layers=4, experts_held=16))
    assert said == {"mixers": ["linear"] * 3 + ["attention"], "window": 8192, "chunk": 64,
                    "chunks_per_window": 128, "state_bytes_per_window": 3 * 32 * 128 * 128 * 4}
    assert T.describe_mixers(T.TORSO_PRESETS["keye_vl2_tiny"]) == {
        "mixers": ["grouped_query_indexed"] * 2, "window": 16}
    assert (full.attention, full.router, full.n_shared_experts, full.first_k_dense_replace) == (
        "gated_delta_hybrid", "softmax", 1, 0)
    assert (full.shared_width, full.expert_block_rows) == (512, 256)


def test_the_stack_emits_its_expert_choices_and_the_checkpoint_keeps_them():
    from jax._src.ad_checkpoint import saved_residuals

    params = T.torso_init(TINY, jax.random.PRNGKey(0), 5)
    obs, valid = _window()
    h, stats = T.torso_apply(TINY, params, obs, valid, emit_choices=True)
    n = 3 * TINY.window
    assert h.shape == (3, TINY.hidden_size)
    assert set(stats) == {"load", "dropped", "experts"}           # no keys, no index loss
    assert stats["experts"].shape == (4, n, TINY.num_experts_per_tok)
    assert stats["load"].shape == (4, 16) and not np.asarray(stats["dropped"]).any()
    assert set(T.torso_apply(TINY, params, obs, valid)[1]) == {"load", "dropped"}
    # a masked position changes nothing downstream: its content is free
    np.testing.assert_allclose(
        T.torso_apply(TINY, params, obs.at[0, :4].set(9.0), valid)[0], h, atol=2e-6)
    # what the backward pass is handed of each block: the experts each token
    # chose, by name — a recomputation cannot flip one
    saved = saved_residuals(lambda p: jnp.sum(T.torso_apply(TINY, p, obs, valid)[0]), params)
    chosen = [aval for aval, why in saved if f"named '{T.KEPT}'" in why]
    assert [(a.shape, a.dtype) for a in chosen] == [((n, TINY.num_experts_per_tok), jnp.int32)] * 4
    # and the attention layer's chunk outputs, so the block's recomputation skips them
    chunks = [aval for aval, why in saved if "reduce_precision" in why and "gated_attention" in why]
    assert [a.shape for a in chunks] == [(3, TINY.window // TINY.query_chunks, 32)] * TINY.query_chunks


# ------------------------------------------------------ the agent around it
def _agent(**kw) -> D4PGConfig:
    torso = dataclasses.replace(TINY, experts_first=4, experts_held=8, span="stream", **kw)
    return D4PGConfig(obs_dim=5, action_dim=2, hidden_sizes=(16, 16),
                      dist=DistConfig(num_atoms=11, v_min=-5.0, v_max=5.0), torso=torso)


def test_the_whole_train_step_is_the_reference_step():
    """Check 1 of the cell's driver at the tiny size: the program's step
    (chunked scan) against the plain reference's (token by token), loss,
    priorities, every gradient leaf and the stepped state."""
    from cellbench.drivers import learner_lin as ll

    r = ll.reference_check(_agent(), 2, 7, "qwen3next_d4pg_step", say=lambda *_: None)
    step = r["reference_step"]
    assert step["ok"] and r["choices"]["ok"] and r["routing"]["ok"], r
    assert 0 < step["rel_err"]["critic_grad"] <= ll.TOL_CRITIC_GRAD
    parts = step["critic_grad_by_part"]
    assert all(0 < v for k, v in parts.items() if "delta_net" in k)
    assert {k for k in parts if "delta_net" in k or "attention" in k} == {
        "layer0.delta_net", "layer1.delta_net", "layer2.delta_net", "layer3.attention"}


def test_train_step_emits_the_experts_alone():
    cfg = _agent()
    state = create_train_state(cfg, jax.random.PRNGKey(0))
    t = cfg.torso.window
    batch = dict(
        obs=jax.random.normal(jax.random.PRNGKey(1), (2, t, 5)),
        next_obs=jax.random.normal(jax.random.PRNGKey(2), (2, t, 5)),
        mask=jnp.ones((2, t), bool).at[0, :4].set(False),
        action=jnp.zeros((2, 2)), reward=jnp.ones((2,)), discount=jnp.full((2,), 0.9),
        weights=jnp.ones((2,)))
    new, metrics, priorities, choices = jax.jit(
        lambda s, x: train_step(cfg, s, x, emit_choices=True))(state, batch)
    assert set(choices) == {"experts", "load", "dropped"}
    assert choices["experts"].shape == (2, 4, 2 * t, cfg.torso.num_experts_per_tok)
    assert "index_loss" not in metrics and priorities.shape == (2,)
    lin = lambda s: [p["lin"] for p in s["torso"]["layers"][:3]]  # noqa: E731
    moved = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(lin(new.critic_params)),
        jax.tree_util.tree_leaves(lin(state.critic_params))))
    assert moved > 0


# ------------------------------------------------------------ what is refused
def test_each_kind_states_what_it_needs():
    real = dataclasses.replace(T.TORSO_PRESETS["qwen3_next"], num_hidden_layers=4, experts_held=16)
    T.validate(real)
    assert real.score_tile_bytes() == 4 * 16 * 512 * 8192           # the attention layer's tile
    assert dataclasses.replace(real, num_hidden_layers=3).score_tile_bytes() == 0   # DeltaNet alone
    T.validate(dataclasses.replace(real, num_hidden_layers=3, query_chunks=1))
    with pytest.raises(ValueError, match="score tile would be 4.0 GiB"):
        T.validate(dataclasses.replace(real, query_chunks=1))
    with pytest.raises(ValueError, match="whole chunks of 48"):
        T.validate(dataclasses.replace(real, delta_chunk=48))
    with pytest.raises(ValueError, match="no leading dense layer"):
        T.validate(dataclasses.replace(real, first_k_dense_replace=1))
    with pytest.raises(ValueError, match="rotary turn covers 3 of 8"):
        T.validate(dataclasses.replace(TINY, partial_rotary_factor=0.4))
    with pytest.raises(ValueError, match="value heads a key head"):
        T.validate(dataclasses.replace(TINY, linear_num_value_heads=3))
    with pytest.raises(ValueError, match="span"):
        T.validate(dataclasses.replace(TINY, span="forever"))

    # a shape that is none of the kinds is refused with a sentence
    bare = T.TorsoShape(**{f.name: getattr(TINY, f.name) for f in dataclasses.fields(T.TorsoShape)})
    with pytest.raises(ValueError, match="states no attention"):
        T.validate(bare)


def test_flags_resolve_to_the_preset_and_train_main_runs_it(tmp_path):
    """``train.py --torso qwen3_next_tiny --torso-span stream --bsize 1`` on
    pendulum: the collector's policy keeps its T-row history and re-runs the
    window, the PER megastep trains on one stream window a grad step."""
    import train

    argv = ["--env", "pendulum", "--torso", "qwen3_next_tiny", "--torso-span", "stream",
            "--torso-experts-held", "4:8", "--replay-placement", "device", "--p-replay",
            "--num-envs", "1", "--bsize", "1"]
    t = train.config_from_args(train.build_parser().parse_args(argv)).agent.torso
    assert (t.name, t.span, t.experts_first, t.experts_held) == ("qwen3_next_tiny", "stream", 4, 8)
    assert isinstance(t, T.HybridTorsoConfig) and t.num_hidden_layers == 4
    trainer = train.main(argv + [
        "--n-step", "1", "--steps-per-dispatch", "2", "--total-steps", "8", "--warmup", "128",
        "--rmsize", "1024", "--hidden-sizes", "16,16", "--eval-interval", "8",
        "--eval-episodes", "1", "--checkpoint-interval", "1000000", "--log-dir", str(tmp_path)])
    assert trainer.grad_steps == 8 and trainer.env_steps >= 128
    _, window, count = trainer.noise_states
    assert window.shape == (1, t.window, 3) and int(count.max()) == t.window
