"""The sequence torso (``models/torso.py``) and what carries it: the expert
layer that is told which experts it holds, history windows over the device
ring, acting on a window through the normal entry point, and the refusals.
Tiny sizes on the CPU; what the chip measured is PERF.md's."""

from __future__ import annotations

import dataclasses

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


def _masked_dense(cfg, p, x):
    """Every held expert on every token, masked: what the grouped products
    must equal."""
    chosen, gates = T.route(cfg, p, x)
    y = T.swiglu(p["shared"], x)
    for e in range(cfg.experts_held):
        gate = jnp.sum(jnp.where(chosen == e + cfg.experts_first, gates, 0.0), -1)
        w = jax.tree_util.tree_map(lambda a: a[e], p["experts"])
        y = y + gate[:, None] * T.swiglu(w, x)
    return y


@pytest.mark.parametrize("first, held", [(0, 8), (2, 4), (6, 2), (3, 1)])
def test_expert_layer_equals_masked_dense_forward_and_backward(first, held):
    cfg = dataclasses.replace(TINY, experts_first=first, experts_held=held)
    p = _layer_params(cfg)
    p["router_bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(9), (cfg.n_routed_experts,))
    x = jax.random.normal(jax.random.PRNGKey(1), (40, cfg.hidden_size))
    y, (load, dropped) = T.expert_layer(cfg, p, x)
    np.testing.assert_allclose(y, _masked_dense(cfg, p, x), atol=2e-6)
    assert int(dropped) == 0 and load.shape == (held,)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))  # noqa: E731
    got = jax.grad(loss(lambda p, x: T.expert_layer(cfg, p, x)[0]), argnums=(0, 1))(p, x)
    want = jax.grad(loss(lambda p, x: _masked_dense(cfg, p, x)), argnums=(0, 1))(p, x)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=5e-6)
    assert float(jnp.abs(got[0]["router_bias"]).max()) == 0.0    # a buffer: no gradient


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
