"""The torso configuration's benchmark parts at a tiny size on the CPU: the
program's step against its plain reference (and the comparison catching a
bfloat16 pass), the routing margin, the FLOP count by hand, the two reducers,
and the configuration file against the program and against its source."""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import manifest as mf
from cellbench import torso_cost
from cellbench.drivers import learner_torso as lt
from cellbench.reducers import Context, scope_ms, step_mfu

REPO = mf.CODE_ROOT
CONFIG = "humanoid_glm47flash_ep8"
CELL = f"{CONFIG}.learn_per_ctx32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def body():
    return mf._read(REPO, f"cellbench/configs/{CONFIG}.json")


@pytest.fixture(scope="module")
def tiny_agent(body):
    from train import build_parser, config_from_args

    argv = [a if a != "Humanoid-v4" else "pendulum" for a in body["argv"]] + body["rehearsal_argv"]
    return config_from_args(build_parser().parse_args(argv)).agent


@pytest.fixture(scope="module")
def checked(tiny_agent, body):
    return lt.reference_check(tiny_agent, 64, 5, body["reference"], say=lambda *_: None)


def test_program_step_agrees_with_the_plain_reference(checked):
    r = checked["reference_step"]
    assert r["ok"], r
    assert max(r["rel_err"].values()) <= lt.TOL_REL and max(r["ulp_err"].values()) <= lt.TOL_ULP
    assert set(r["rel_err"]) == {"critic_loss", "actor_loss", "priorities", "actor_grad",
                                 "actor_adam_v", "critic_grad", "critic_adam_v"}
    assert set(r["ulp_err"]) == {"actor", "critic", "target_actor", "target_critic"}
    assert r["rel_err"]["critic_grad"] > 0          # two programs, not one compared with itself


def test_routing_check_counts_what_the_reference_counts(checked, tiny_agent):
    r = checked["routing"]
    assert r["ok"], r
    t = tiny_agent.torso
    assert r["pairs_on_held_experts"] == r["reference_pairs"] > 0 and r["dropped"] == 0
    assert r["tokens"] == 64 * t.window and r["expert_layers"] == t.num_moe_layers
    assert r["pairs_on_held_experts"] <= r["tokens"] * t.num_experts_per_tok * r["expert_layers"]
    assert r["load_min"] <= r["load_max"] and r["smallest_gap"] >= lt.MARGIN
    assert r["candidates"] == 96 and 0 <= r["passed_over"] <= 32


def test_a_bfloat16_pass_fails_the_tolerance(tiny_agent, body):
    """The same step with its matrix products in one bfloat16 pass (what the
    chip's default precision does to float32 operands; on the CPU, a
    bfloat16 compute dtype in the heads) is refused by a gradient."""
    low = dataclasses.replace(tiny_agent, compute_dtype="bfloat16")
    r = lt.reference_check(low, 64, 5, body["reference"], say=lambda *_: None)
    assert not r["reference_step"]["ok"]
    assert r["reference_step"]["rel_err"]["critic_grad"] > 100 * lt.TOL_REL


def test_a_window_under_the_margin_is_passed_over(tiny_agent, body, monkeypatch):
    """The routing margin raised until a fifth of the candidates fall under
    it: they are passed over, counted, and over the stated share the check
    fails."""
    import cellbench.reference.glm47flash_d4pg_step as ref

    @jax.jit
    def gaps(seed):
        state = lt.to_reference_state(lt.seeded_state(tiny_agent, seed))
        pool = lt.candidates(tiny_agent, seed, 96)
        mask = ref.window_mask(pool.pop("row_discount"))
        hp = {"torso": dataclasses.asdict(tiny_agent.torso), "blocks": 3}
        on = ref.kink_gaps(state["critic"], state["actor"], pool["obs"], mask,
                           pool["action"], hp)
        off = ref.kink_gaps(state["target_critic"], None, pool["next_obs"], mask,
                            pool["action"], hp)
        return jnp.minimum(on["routing"], off["routing"]), on["relu"]

    routing, relu = (np.asarray(g) for g in gaps(jnp.uint32(5)))
    assert relu.shape == (96,) and np.all(relu >= 0) and np.all(np.isfinite(routing))
    g = np.sort(routing)
    monkeypatch.setattr(lt, "RELU_MARGIN", 0.0)
    monkeypatch.setattr(lt, "MARGIN", float(g[20] + g[21]) / 2)      # 21 of 96 under it
    r = lt.reference_check(tiny_agent, 64, 5, body["reference"], say=lambda *_: None)
    assert r["reference_step"]["ok"] and r["routing"]["ok"], r
    assert 0 < r["routing"]["passed_over"] <= 21
    assert r["routing"]["passed_over_for_routing"] == 21
    monkeypatch.setattr(lt, "MARGIN", float(g[60]))                  # 60 under it: 36 left
    r = lt.reference_check(tiny_agent, 64, 5, body["reference"], say=lambda *_: None)
    assert not r["reference_step"]["ok"] and not r["routing"]["ok"]
    # and the heads' margin alone passes windows over too
    monkeypatch.setattr(lt, "MARGIN", 0.0)
    monkeypatch.setattr(lt, "RELU_MARGIN", float(np.sort(relu)[10]))
    r = lt.reference_check(tiny_agent, 64, 5, body["reference"], say=lambda *_: None)
    assert r["reference_step"]["ok"], r
    assert 0 < r["routing"]["passed_over"] <= 10 and r["routing"]["passed_over_for_routing"] == 0


def test_the_seeded_critic_heads_update_moves_no_kink(tiny_agent):
    """What makes a window's ReLU margin a property of the window: the
    critic head the actor's loss pass sees is the one that was measured."""
    state = jax.jit(lambda s: lt.seeded_state(tiny_agent, s))(jnp.uint32(5))
    nu = state.critic_opt_state[0].nu
    assert float(min(jnp.min(v) for v in jax.tree_util.tree_leaves(nu["head"]))) >= 49.0
    assert float(max(jnp.max(v) for v in jax.tree_util.tree_leaves(nu["torso"]))) <= 2e-6
    norms = [p["attn_norm"] for p in state.critic_params["torso"]["layers"]]
    assert all(0.85 < float(jnp.min(n)) and float(jnp.max(n)) < 1.15 for n in norms)


def test_window_mask_of_the_reference_by_hand():
    import cellbench.reference.glm47flash_d4pg_step as ref

    d = jnp.asarray([[.9, .9, .9, .9], [.9, 0., .9, .9], [.9, .9, 0., .9],
                     [.9, .9, .9, 0.], [0., 0., .9, 0.]])
    assert np.asarray(ref.window_mask(d)).tolist() == [
        [True, True, True, True], [False, False, True, True], [False, False, False, True],
        [True, True, True, True], [False, False, True, True]]


# ------------------------------------------------------------ cost, reducers
def test_torso_cost_counted_by_hand(body):
    t, r = body["torso"], body["resolved"]
    per = torso_cost.macs_per_token(t, r["obs_dim"], t["window"])
    mla = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert mla == 21_757_952
    scores = 16.5 * 20 * (256 + 256)                    # (T+1)/2 keys, 20 heads, qk 256 + v 256
    assert per == {
        "embed": 376 * 2048,
        "attention": 5 * (mla + scores),
        "dense_ffn": 3 * 2048 * 10240,
        "expert_ffn": 4 * (2048 * 64 + 3 * 2048 * 1536 * (1 + 4 * 8 / 64)),
    }
    forward = sum(per.values())
    assert forward == pytest.approx(230.5e6, rel=2e-3)          # multiply-adds a token a pass
    parts = torso_cost.flops_per_grad_step(body)
    tokens = 256 * 32
    assert parts["target_forward"] == parts["critic_forward"] == 2 * tokens * forward
    assert parts["critic_backward"] == 2 * tokens * (2 * forward - 376 * 2048)
    assert parts["total"] == pytest.approx(15.09e12, rel=1e-3)
    assert parts["heads"] < 1e-3 * parts["total"]


def test_the_mfu_metrics_argument_is_the_cost_of_the_configuration(body):
    spec = mf._read(REPO, mf.metric_file("agent.step_mfu"))
    assert spec["reducer"] == "step_mfu"
    assert spec["args"]["flops_per_grad_step"] == torso_cost.flops_per_grad_step(body)["total"]


def test_step_mfu_and_scope_ms_reduce():
    values = {"window.grad_steps_per_s": 4.0, "device.count": 1, "peaks.flops_per_s": 197e12}
    ctx = Context(None, "^jit_lane", 1, values)
    assert step_mfu.reduce(ctx, flops_per_grad_step=15e12) == pytest.approx(100 * 60 / 197)
    assert step_mfu.reduce(Context(None, "^jit_lane", 1, {}), flops_per_grad_step=15e12) is None
    assert scope_ms.reduce(ctx, "agent.attention") is None        # no trace: nothing
    from cellbench import trace

    tr = trace.load(os.path.join(REPO, "cellbench", "testdata", "v5e_phases_slice.json.gz"))
    ctx = Context(tr, "^jit_lane", 32, values)
    assert scope_ms.reduce(ctx, "replay.row_gather") == pytest.approx(0.542007, rel=5e-5)
    assert scope_ms.reduce(ctx, "agent.experts") == 0.0           # recorded before the scope


def test_the_cell_declares_the_four_metrics_and_the_old_ones(body):
    cell = mf.cell(*mf.load(), CELL)
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"agent.attention_ms", "agent.experts_ms", "replay.window_gather_ms",
                     "agent.step_mfu", "agent.learner_mfu", "device.idle_share"}
    assert not names & {"replay.draw_ms", "parallel.sync_ms"}
    assert cell.traffic["driver"] == "learner_torso" and cell.chips == 1
    others = [w["name"] for w in mf.load()[0]["workloads"] if w["name"] != CELL]
    for other in others:          # and no other cell reads them
        mine = {m["name"] for m in mf.cell(*mf.load(), other).per_layer}
        assert not mine & {"agent.attention_ms", "agent.experts_ms", "agent.step_mfu"}


# ------------------------------------------------------- the configuration file
def test_config_file_holds_the_programs_preset(body):
    """``torso`` in the file is what its argv resolves to through the
    program's own path, and that is the published preset cut as stated."""
    from d4pg_tpu.models.torso import TORSO_PRESETS
    from train import build_parser, config_from_args

    cfg = config_from_args(build_parser().parse_args(body["argv"]))
    # the file's keys as stated; a field it does not state at its default
    got = dataclasses.asdict(cfg.agent.torso)
    assert {key: got[key] for key in body["torso"]} == body["torso"]
    assert {f.name: got[f.name] for f in dataclasses.fields(cfg.agent.torso)
            if f.name not in body["torso"]} == {
        f.name: f.default for f in dataclasses.fields(cfg.agent.torso)
        if f.name not in body["torso"]}
    cut = dataclasses.replace(
        TORSO_PRESETS["glm47_flash"], num_hidden_layers=5, experts_held=8, window=32,
        row_stride=1)
    assert cfg.agent.torso == cut
    full = mf.cell(*mf.load(), CELL)
    argv = full.config["argv"] + full.traffic["argv"]
    run = config_from_args(build_parser().parse_args(argv))
    assert (run.steps_per_dispatch, run.batch_size, run.agent.torso.window) == (1, 256, 32)
    assert run.replay_capacity == 2 ** 19 == body["replay_capacity"]


def test_no_width_differs_from_the_source(body):
    """Every key of the source's config.json is in the file at its published
    value, but the ones ``reduced`` names; and the program's preset has the
    same widths under the same names."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-4.7-Flash")
    assert row["source_url"] == body["source"]
    differs = {k for k, v in row["config"].items() if body.get(k, "absent") != v}
    assert differs == set(body["reduced"]) - {"replay_capacity"}
    assert {k: body["published"][k] for k in differs} == {k: row["config"][k] for k in differs}
    assert not [k for k in body["reduced"] if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok", "n_shared_experts",
                "first_k_dense_replace", "routed_scaling_factor", "rms_norm_eps", "rope_theta"):
        assert body["torso"][key] == row["config"][key], key
    assert body["torso"]["n_routed_experts"] == 64      # the router keeps its width
    assert body["torso"]["experts_held"] == body["n_routed_experts"] == 8
    assert set(body) >= {"assumed", "departures", "deployment", "published", "held_here"}
