"""The torso's second set of parts (``models/torso.py``): grouped-query
attention under a learned indexer with a loss of its own, a softmax router
without a shared expert, windows that span their stream. Tiny sizes on the
CPU; what the chip measured is PERF.md's."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.agent.d4pg import create_train_state, gather_windows, train_step
from d4pg_tpu.agent.state import D4PGConfig, DistConfig
from d4pg_tpu.models import torso as T
from d4pg_tpu.replay.device_ring import DeviceRing

TINY = T.TORSO_PRESETS["keye_vl2_tiny"]


def _window(cfg, b=3, obs_dim=5, seed=1):
    obs = jax.random.normal(jax.random.PRNGKey(seed), (b, cfg.window, obs_dim))
    valid = jnp.ones((b, cfg.window), bool).at[0, :3].set(False)
    return obs, valid


# ------------------------------------------------------------ the selection
@pytest.mark.parametrize("k", [1, 5, 20, 50])
def test_kth_largest_is_the_sorted_rows_kth(k):
    s = jax.random.normal(jax.random.PRNGKey(3), (7, 50))
    s = s.at[:, 5].set(0.0).at[:, 6].set(-0.0).at[:, 7].set(-jnp.inf).at[2, 9:30].set(0.25)
    want = jnp.sort(s, axis=-1)[:, ::-1][:, k - 1]
    np.testing.assert_array_equal(np.asarray(T.kth_largest(s, k)), np.asarray(want))


def test_choose_keys_is_top_k_with_ties_to_the_lower_position():
    scores = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 12)).round(1)   # many ties
    see = jnp.tril(jnp.ones((12, 12), bool))[None] & jnp.ones((2, 1, 12), bool).at[1, :, :2].set(False)
    got = np.asarray(T.choose_keys(scores, see, 4))
    _, idx = jax.lax.top_k(jnp.where(see, scores, -jnp.inf), 4)
    for b in range(2):
        for t in range(12):
            n = int(see[b, t].sum())
            want = set(np.asarray(idx[b, t][: min(n, 4)]).tolist()) if n else set()
            assert set(np.flatnonzero(got[b, t]).tolist()) == want, (b, t)
    assert np.array_equal(np.asarray(T.choose_keys(scores[..., :4], see[..., :4], 4)),
                          np.asarray(see[..., :4]))          # fewer keys than places: all


def test_each_query_holds_exactly_its_best_keys_and_none_it_may_not_see():
    cfg = TINY
    params = T.torso_init(cfg, jax.random.PRNGKey(0), 5)
    obs, valid = _window(cfg)
    _, stats = T.torso_apply(cfg, params, obs, valid, emit_choices=True)
    keys = np.asarray(stats["keys"])                       # [L, B, T, T]
    t = cfg.window
    see = np.tril(np.ones((t, t), bool))[None] & np.asarray(valid)[:, None, :]
    assert keys.shape == (cfg.num_hidden_layers, 3, t, t)
    assert not (keys & ~see[None]).any()                   # none masked, none future
    want = np.minimum(see.sum(-1), cfg.index_topk)
    assert (keys.sum(-1) == want[None]).all()              # min(t + 1, k), exactly
    assert cfg.index_topk < t and (want == cfg.index_topk).any() and (want < cfg.index_topk).any()
    assert stats["experts"].shape == (cfg.num_moe_layers, 3 * t, cfg.num_experts_per_tok)
    assert float(stats["index_loss"]) > 0 and not np.asarray(stats["dropped"]).any()


def test_query_chunked_attention_equals_the_whole():
    params = T.torso_init(TINY, jax.random.PRNGKey(0), 5)
    obs, valid = _window(TINY)
    outs = {}
    for chunks in (1, 2, 4, 16):
        cfg = dataclasses.replace(TINY, query_chunks=chunks)
        h, stats = T.torso_apply(cfg, params, obs, valid, emit_choices=True)
        outs[chunks] = (h, stats["index_loss"], stats["keys"])
    for chunks in (2, 4, 16):
        np.testing.assert_allclose(outs[chunks][0], outs[1][0], atol=2e-6)
        np.testing.assert_allclose(outs[chunks][1], outs[1][1], rtol=1e-5)
        assert np.array_equal(np.asarray(outs[chunks][2]), np.asarray(outs[1][2]))
    # a masked position changes nothing downstream: its content is free
    obs2 = obs.at[0, :3].set(9.0)
    np.testing.assert_allclose(T.torso_apply(TINY, params, obs2, valid)[0], outs[4][0], atol=2e-6)


def test_the_alignment_loss_reaches_the_indexer_and_nothing_else_reaches_it():
    """``∂L^I/∂(any other leaf) = 0`` and ``∂(everything else)/∂(the
    indexer's leaves) = 0``: the indexer's input and its target are cut from
    the graph, and the choice carries no gradient."""
    params = T.torso_init(TINY, jax.random.PRNGKey(0), 5)
    obs, valid = _window(TINY)

    def both(p):
        h, stats = T.torso_apply(TINY, p, obs, valid)
        return jnp.sum(jnp.sin(h)), stats["index_loss"]

    g_out = jax.grad(lambda p: both(p)[0])(params)
    g_align = jax.grad(lambda p: both(p)[1])(params)
    peak = lambda tree: max(float(jnp.abs(x).max()) for x in jax.tree_util.tree_leaves(tree))  # noqa: E731
    for layer_out, layer_align in zip(g_out["layers"], g_align["layers"]):
        assert peak(layer_out["indexer"]) == 0.0
        assert min(float(jnp.abs(x).max()) for x in
                   jax.tree_util.tree_leaves(layer_align["indexer"])) > 0.0
        assert peak({k: v for k, v in layer_align.items() if k != "indexer"}) == 0.0
        assert peak(layer_out["attn"]) > 0.0
    assert peak(g_align["embed"]) == 0.0 and peak(g_align["final_norm"]) == 0.0


# ------------------------------------------------------------ the expert layer
def _layer_params(cfg, seed=0):
    return T._block_init(cfg, jax.random.PRNGKey(seed), moe=True)["ffn"]


def _masked_dense(cfg, p, x):
    chosen, gates = T.route(cfg, p, x)
    y = jnp.zeros_like(x)
    for e in range(cfg.experts_held):
        gate = jnp.sum(jnp.where(chosen == e + cfg.experts_first, gates, 0.0), -1)
        y = y + gate[:, None] * T.swiglu(jax.tree_util.tree_map(lambda a: a[e], p["experts"]), x)
    return y


def test_softmax_router_gates_are_renormalised_probabilities():
    p = _layer_params(TINY)
    assert set(p) == {"router", "experts"}               # no bias, no shared expert
    x = jax.random.normal(jax.random.PRNGKey(1), (64, TINY.hidden_size))
    chosen, gates = T.route(TINY, p, x)
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    _, want = jax.lax.top_k(probs, TINY.num_experts_per_tok)
    assert np.array_equal(np.asarray(chosen), np.asarray(want))
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    np.testing.assert_allclose(gates, picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)


def test_dropless_under_a_skewed_softmax_router_at_top_8():
    """Every token sends a pair to each of the 8 held experts (and the
    worst-case buffer takes them): nothing dropped, the masked dense result."""
    cfg = dataclasses.replace(TINY, num_experts_per_tok=8, experts_first=4, experts_held=8)
    p = _layer_params(cfg)
    p["router"] = p["router"] * 0.01
    x = jax.random.normal(jax.random.PRNGKey(2), (37, cfg.hidden_size))
    x = x.at[:, 0].set(50.0)                               # one feature drives the router
    p["router"] = p["router"].at[0, 4:12].set(1.0)
    y, (load, dropped) = T.expert_layer(cfg, p, x)
    assert load.tolist() == [37] * 8 and int(dropped) == 0
    np.testing.assert_allclose(y, _masked_dense(cfg, p, x), atol=5e-5, rtol=1e-5)
    # a token none of whose experts is held gets nothing from the layer
    p["router"] = p["router"].at[0, :].set(0.0).at[0, 12:].set(1.0).at[0, :4].set(1.0)
    y, (load, dropped) = T.expert_layer(cfg, p, x)
    assert load.tolist() == [0] * 8 and int(dropped) == 0 and float(jnp.abs(y).max()) == 0.0


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight shares of two experts each, their routed parts added — there is
    no shared expert to count once — against the plain reference's layer
    with all sixteen held (the model-configs guide, section 4)."""
    from cellbench.reference import keyevl2_d4pg_step as ref

    whole = dataclasses.replace(TINY, experts_first=0, experts_held=16)
    p = _layer_params(whole, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(5), (48, whole.hidden_size))
    total = jnp.zeros_like(x)
    for i in range(8):
        share = dataclasses.replace(TINY, experts_first=2 * i, experts_held=2)
        part = dict(p, experts=jax.tree_util.tree_map(lambda a: a[2 * i:2 * i + 2], p["experts"]))
        total = total + T.expert_layer(share, part, x)[0]
    chosen, _ = T.route(whole, p, x)
    names = {"w_gate": p["experts"]["gate"], "w_up": p["experts"]["up"],
             "w_down": p["experts"]["down"]}
    want, report, load = ref.moe(
        {"w_router": p["router"], "experts": names}, x, jnp.ones((48,), bool),
        dataclasses.asdict(whole), chosen, {"router_margin": 1e-6})
    np.testing.assert_allclose(total, want, atol=5e-6)
    assert int(load.sum()) == 48 * whole.num_experts_per_tok and int(report["differ"]) == 0


# ------------------------------------------------------------ the windows
def _ring(capacity, obs_dim, size, terminal_rows):
    rng = np.random.default_rng(0)
    fields = dict(
        obs=rng.normal(size=(capacity, obs_dim)).astype(np.float32),
        action=rng.normal(size=(capacity, 2)).astype(np.float32),
        reward=rng.normal(size=(capacity,)).astype(np.float32),
        next_obs=rng.normal(size=(capacity, obs_dim)).astype(np.float32),
        discount=np.full((capacity,), 0.9, np.float32))
    fields["discount"][list(terminal_rows)] = 0.0
    return DeviceRing(size=jnp.int32(size), **{k: jnp.asarray(v) for k, v in fields.items()}), fields


@pytest.mark.parametrize("stride", [1, 2])
def test_a_stream_window_is_cut_by_the_rings_first_row_alone(stride):
    ring, fields = _ring(64, 5, 64, terminal_rows=(3, 9, 10, 30))
    idx = jnp.asarray([[0, 5, 11, 31, 63]])
    window = 6
    stream = gather_windows(ring, idx, window, stride, span="stream")
    episode = gather_windows(ring, idx, window, stride)
    for j, slot in enumerate(np.asarray(idx)[0]):             # a per-sample Python loop
        rows = [slot - (window - 1 - i) * stride for i in range(window)]
        assert np.asarray(stream["mask"])[0, j].tolist() == [r >= 0 for r in rows]
        for i, r in enumerate(rows):
            if r >= 0:
                np.testing.assert_array_equal(np.asarray(stream["obs"])[0, j, i], fields["obs"][r])
    assert np.asarray(stream["mask"]).sum() > np.asarray(episode["mask"]).sum()
    for name in ("obs", "next_obs", "action", "reward", "discount"):
        np.testing.assert_array_equal(np.asarray(stream[name]), np.asarray(episode[name]))


def test_a_stream_policy_keeps_its_history_across_resets():
    from d4pg_tpu.agent.d4pg import make_noise, push_observation
    from d4pg_tpu.runtime.collect import policy_state_fns

    def agent(span):
        torso = dataclasses.replace(TINY, experts_first=4, experts_held=8, span=span)
        return D4PGConfig(obs_dim=5, action_dim=2, hidden_sizes=(16, 16),
                          dist=DistConfig(num_atoms=11, v_min=-5.0, v_max=5.0), torso=torso)

    for span, kept in (("stream", True), ("episode", False)):
        cfg = agent(span)
        init, reset = policy_state_fns(cfg, make_noise(cfg))
        noise, window, count = init()
        history = []                                         # the loop a stream is
        for step in range(5):
            obs = jnp.full((5,), float(step + 1))
            window, count, valid = push_observation(window, count, obs)
            history.append(step + 1)
        _, window2, count2 = reset((noise, window, count))
        assert (int(count2) == 5) == kept
        want = ([0.0] * (TINY.window - 5) + [float(h) for h in history]) if kept else [0.0] * TINY.window
        assert np.asarray(window2)[:, 0].tolist() == want


# ------------------------------------------------------ the agent around it
def _agent(**kw) -> D4PGConfig:
    torso = dataclasses.replace(TINY, experts_first=4, experts_held=8, span="stream", **kw)
    return D4PGConfig(obs_dim=5, action_dim=2, hidden_sizes=(16, 16),
                      dist=DistConfig(num_atoms=11, v_min=-5.0, v_max=5.0), torso=torso)


def _batch(cfg, b):
    key, t = jax.random.PRNGKey(1), cfg.torso.window
    return dict(
        obs=jax.random.normal(key, (b, t, 5)), next_obs=jax.random.normal(key, (b, t, 5)) + 1.0,
        mask=jnp.ones((b, t), bool).at[0, :2].set(False),
        action=jnp.zeros((b, 2)), reward=jnp.ones((b,)), discount=jnp.full((b,), 0.9),
        weights=jnp.ones((b,)))


def test_the_indexer_trains_on_its_own_loss_inside_the_critics_objective():
    cfg = _agent()
    state = create_train_state(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, 2)
    new, metrics, priorities, choices = jax.jit(
        lambda s, x: train_step(cfg, s, x, emit_choices=True))(state, batch)
    plain = jax.jit(lambda s, x: train_step(cfg, s, x))(state, batch)
    assert len(plain) == 3 and priorities.shape == (2,)
    np.testing.assert_array_equal(np.asarray(plain[2]), np.asarray(priorities))
    assert float(metrics["index_loss"]) > 0
    assert float(metrics["critic_loss"]) > float(metrics["index_loss"])     # it is inside
    t, layers = cfg.torso.window, cfg.torso.num_hidden_layers
    assert choices["keys"].shape == (2, layers, 2, t, t) and choices["keys"].dtype == jnp.bool_
    assert choices["experts"].shape == (2, layers, 2 * t, cfg.torso.num_experts_per_tok)
    assert choices["load"].shape == (2, layers, 8) and choices["dropped"].shape == (2, layers)
    moved = lambda a, b: max(float(jnp.abs(x - y).max()) for x, y in zip(  # noqa: E731
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    idx = lambda s: [p["indexer"] for p in s["torso"]["layers"]]  # noqa: E731
    assert moved(idx(new.critic_params), idx(state.critic_params)) > 0       # Adam
    assert moved(idx(new.target_critic_params), idx(state.target_critic_params)) > 0   # Polyak
    # the first moment of the indexer's leaves is the alignment loss's gradient alone
    def align(p):
        return T.torso_apply(cfg.torso, p, batch["obs"], batch["mask"])[1]["index_loss"]
    want = jax.grad(align)(state.critic_params["torso"])
    got = new.critic_opt_state[0].mu["torso"]
    for w, g in zip(idx({"torso": want}), idx({"torso": got})):
        for a, b in zip(jax.tree_util.tree_leaves(w), jax.tree_util.tree_leaves(g)):
            np.testing.assert_allclose(b, 0.1 * a, rtol=2e-4, atol=1e-9)


def test_the_per_megastep_at_a_batch_of_one_window():
    """B = 1: one stratum a draw, one leaf written back."""
    from d4pg_tpu.replay.device_per import DevicePerTree
    from d4pg_tpu.runtime import megastep as ms

    cfg = _agent()
    capacity = 64
    ring, _ = _ring(capacity, 5, capacity, terminal_rows=(7, 20))
    leaves = jnp.ones((1, capacity), jnp.float32)
    levels = [leaves]
    while levels[0].shape[1] > 1:
        levels.insert(0, levels[0].reshape(1, -1, 2).sum(-1))
    sums = jnp.concatenate([jnp.zeros((1, 1))] + levels, axis=1)
    tree = DevicePerTree(sums, jnp.float32(1.0))
    state = create_train_state(cfg, jax.random.PRNGKey(0))
    mega = ms.make_megastep_device_per(cfg, 1, 1)
    state, tree2, _, metrics = mega(state, ring, tree, jax.random.PRNGKey(1))
    assert int(state.step) == 1 and bool(jnp.isfinite(metrics["critic_loss"]))
    changed = np.flatnonzero(np.asarray(tree2.sums[0, capacity:]) != 1.0)
    assert len(changed) == 1                                 # one leaf written back
    np.testing.assert_allclose(float(tree2.sums[0, 1]), float(tree2.sums[0, capacity:].sum()),
                               rtol=1e-6)


# ------------------------------------------------------------ what is refused
def test_validate_refuses_a_windows_full_score_tensor():
    real = dataclasses.replace(T.TORSO_PRESETS["keye_vl2"], num_hidden_layers=4, experts_held=16)
    T.validate(real)                                         # 32 x 512 x 8192 x 4 B = 512 MiB
    with pytest.raises(ValueError, match="score tile would be 8.0 GiB"):
        T.validate(dataclasses.replace(real, query_chunks=1))
    with pytest.raises(ValueError, match="query chunks"):
        T.validate(dataclasses.replace(real, query_chunks=3))
    with pytest.raises(ValueError, match="latent attention has none"):
        T.validate(dataclasses.replace(T.TORSO_PRESETS["glm47_flash"], window=8192))
    with pytest.raises(ValueError, match="span"):
        T.validate(dataclasses.replace(TINY, span="forever"))
    with pytest.raises(ValueError, match="whole groups"):
        T.validate(dataclasses.replace(TINY, num_key_value_heads=3))


def test_the_parts_are_stated_by_the_preset():
    glm, keye = T.TORSO_PRESETS["glm47_flash"], T.TORSO_PRESETS["keye_vl2"]
    assert (glm.attention, glm.router, glm.span, glm.query_chunks) == (
        "latent", "sigmoid_bias", "episode", 1)
    assert (keye.attention, keye.router) == ("grouped_query_indexed", "softmax")
    assert (keye.first_k_dense_replace, keye.n_shared_experts) == (0, 0)
    assert (glm.first_k_dense_replace, glm.n_shared_experts) == (1, 1)
    assert keye.window // keye.query_chunks == 512           # the source's q_chunk_size
    assert TINY.index_topk < TINY.window
    # a leading dense layer and a shared expert compose with the new parts too
    mixed = dataclasses.replace(TINY, first_k_dense_replace=1, n_shared_experts=1,
                                num_hidden_layers=3)
    params = T.torso_init(mixed, jax.random.PRNGKey(0), 5)
    assert set(params["layers"][0]["ffn"]) == {"gate", "up", "down"}
    assert set(params["layers"][1]["ffn"]) == {"router", "experts", "shared"}
    h, stats = T.torso_apply(mixed, params, *_window(mixed))
    assert h.shape == (3, mixed.hidden_size) and stats["load"].shape == (2, 16)


def test_flags_resolve_to_the_preset_the_share_and_the_span():
    from train import build_parser, config_from_args

    argv = ["--env", "pendulum", "--torso", "keye_vl2_tiny", "--torso-experts-held", "4:8",
            "--replay-placement", "device", "--p-replay", "--num-envs", "1", "--bsize", "1"]
    t = config_from_args(build_parser().parse_args(argv + ["--torso-span", "stream"])).agent.torso
    assert (t.name, t.span, t.experts_first, t.experts_held) == ("keye_vl2_tiny", "stream", 4, 8)
    assert config_from_args(build_parser().parse_args(argv)).agent.torso.span == "episode"
    glm = ["glm47_flash_tiny" if a == "keye_vl2_tiny" else a for a in argv]
    with pytest.raises(SystemExit, match="states no span"):
        config_from_args(build_parser().parse_args(glm + ["--torso-span", "stream"]))


def test_the_indexed_torso_through_train_main_at_a_batch_of_one(tmp_path):
    """``train.py --torso keye_vl2_tiny --torso-span stream --bsize 1`` on
    pendulum: the collector's policy keeps its T-row history, the PER
    megastep trains on one stream window a grad step, eval acts on a window,
    and the alignment loss is a logged metric."""
    import train

    trainer = train.main([
        "--env", "pendulum", "--torso", "keye_vl2_tiny", "--torso-span", "stream",
        "--torso-experts-held", "4:8", "--replay-placement", "device", "--p-replay",
        "--n-step", "1", "--steps-per-dispatch", "2", "--total-steps", "8", "--warmup", "128",
        "--num-envs", "1", "--bsize", "1", "--rmsize", "1024", "--hidden-sizes", "16,16",
        "--eval-interval", "8", "--eval-episodes", "1", "--checkpoint-interval", "1000000",
        "--log-dir", str(tmp_path)])
    assert trainer.grad_steps == 8 and trainer.env_steps >= 128
    _, window, count = trainer.noise_states
    t = trainer.config.agent.torso.window
    assert window.shape == (1, t, 3) and int(count.max()) == t
    assert trainer.config.agent.torso.span == "stream" and trainer.config.batch_size == 1
