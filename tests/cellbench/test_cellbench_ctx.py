"""The stream-window configuration's benchmark parts at a tiny size on the
CPU: the program's step against its plain reference given the program's
choices (and the comparison catching a bfloat16 pass), the choice check
catching a bfloat16 indexer by itself, the FLOP count by hand, the reducers'
new names, and the configuration file against the program and its source."""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import ctx_torso_cost, manifest as mf
from cellbench.drivers import learner_ctx as lc
from cellbench.reducers import Context, scope_ms

REPO = mf.CODE_ROOT
CONFIG = "humanoid_keyevl2_ep8"
CELL = f"{CONFIG}.learn_per_ctx8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"agent.indexer_ms", "agent.ctx_attention_ms", "agent.ctx_experts_ms",
       "agent.ctx_step_mfu"}


@pytest.fixture(scope="module")
def body():
    return mf._read(REPO, f"cellbench/configs/{CONFIG}.json")


@pytest.fixture(scope="module")
def tiny_agent(body):
    from train import build_parser, config_from_args

    argv = [a if a != "Humanoid-v4" else "pendulum" for a in body["argv"]] + body["rehearsal_argv"]
    agent = config_from_args(build_parser().parse_args(argv)).agent
    assert agent.torso.index_topk < agent.torso.window       # the choice is live
    return agent


@pytest.fixture(scope="module")
def checked(tiny_agent, body):
    return lc.reference_check(tiny_agent, 2, 5, body["reference"], say=lambda *_: None)


def test_program_step_agrees_with_the_plain_reference_given_its_choices(checked):
    r = checked["reference_step"]
    assert r["ok"], r
    assert all(e <= lc.LIMITS.get(n, lc.TOL_REL) for n, e in r["rel_err"].items())
    assert max(r["ulp_err"].values()) <= lc.TOL_ULP
    assert set(r["rel_err"]) == {"critic_loss", "actor_loss", "index_loss", "priorities",
                                 "actor_grad", "actor_adam_v", "critic_grad", "critic_adam_v",
                                 "indexer_grad"}
    assert 0 < r["rel_err"]["indexer_grad"]
    assert lc.TOL_REL < lc.TOL_CRITIC_GRAD < lc.TOL_INDEXER <= 1e-2
    assert set(r["ulp_err"]) == {"actor", "critic", "target_actor", "target_critic"}
    assert r["rel_err"]["critic_grad"] > 0          # two programs, not one compared with itself
    assert r["index_loss"] > 0 and r["candidates_passed_over"] == 0


def test_the_two_sides_sets_agree_outside_the_band(checked, tiny_agent):
    c, r = checked["choices"], checked["routing"]
    assert c["ok"] and r["ok"], (c, r)
    t = tiny_agent.torso
    for kind in ("keys", "experts"):
        assert c[kind]["outside_the_band"] == 0
        assert c[kind]["disagreements"] <= lc.MAX_DIFFER_SHARE * max(c[kind]["in_band"], 1)
    # two passes x layers x tokens x k experts; the keys: sum over valid t of min(t+1, topk)
    assert c["experts"]["places"] <= 2 * t.num_hidden_layers * 2 * t.window * t.num_experts_per_tok
    assert c["keys"]["places"] == r["keys_chosen"] == r["keys_wanted"] > 0
    assert r["queries_with_a_wrong_count"] == 0 and r["unseen_keys_chosen"] == 0
    assert r["dropped"] == 0 and 0 < r["pairs_on_held_experts"] <= (
        r["tokens"] * t.num_experts_per_tok * r["passes_x_layers"])


def test_a_bfloat16_pass_fails_the_tolerance(tiny_agent, body):
    """The same step with its matrix products in one bfloat16 pass (what the
    chip's default precision does to float32 operands; on the CPU, a
    bfloat16 compute dtype in the heads) is refused by a gradient."""
    low = dataclasses.replace(tiny_agent, compute_dtype="bfloat16")
    r = lc.reference_check(low, 2, 5, body["reference"], say=lambda *_: None)
    assert not r["reference_step"]["ok"]
    assert r["reference_step"]["rel_err"]["critic_grad"] > 100 * lc.TOL_CRITIC_GRAD


@pytest.mark.parametrize("part", ["indexer", "router"])
def test_a_bfloat16_indexer_or_router_fails_the_choice_check_by_itself(
        tiny_agent, body, monkeypatch, part):
    """The scores that decide a choice rounded to bfloat16 (what one pass
    through the matrix unit at the chip's default precision gives): sets
    differ far outside the band, and part (i) alone refuses the step."""
    from d4pg_tpu.models import torso as T

    low = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    if part == "indexer":
        real = T.index_scores
        monkeypatch.setattr(T, "index_scores", lambda q, k, w: real(low(q), low(k), w))
    else:
        real = T.route

        def route(cfg, p, x):
            return real(cfg, dict(p, router=low(p["router"])), low(x))
        monkeypatch.setattr(T, "route", route)
    # 8 windows for the router: a bfloat16 logit flips about one top-4 of 16 in thirty
    r = lc.reference_check(tiny_agent, 2 if part == "indexer" else 8, 5, body["reference"],
                           say=lambda *_: None)
    kind = "keys" if part == "indexer" else "experts"
    assert not r["choices"]["ok"] and not r["choices"][kind]["ok"]
    assert r["choices"][kind]["outside_the_band"] > 0
    assert r["choices"][kind]["worst_disagreement"] > 10.0       # margins


def test_the_seeded_state_lifts_the_norms_and_not_their_bias(tiny_agent):
    state = jax.jit(lambda s: lc.seeded_state(tiny_agent, s))(jnp.uint32(5))
    layer = state.critic_params["torso"]["layers"][0]
    for norm in (layer["attn_norm"], layer["attn"]["q_norm"], layer["indexer"]["k_norm"]["scale"]):
        assert 0.7 < float(jnp.min(norm)) and float(jnp.max(norm)) < 1.3
    assert float(jnp.abs(layer["indexer"]["k_norm"]["bias"]).max()) < 0.6
    nu = state.critic_opt_state[0].nu
    assert float(min(jnp.min(v) for v in jax.tree_util.tree_leaves(nu["head"]))) >= 49.0


# ------------------------------------------------------------ cost, reducers
def test_ctx_torso_cost_counted_by_hand(body):
    t, r = body["torso"], body["resolved"]
    assert ctx_torso_cost.mean_keys(8192, 2048) == (2048 * 2049 / 2 + 6144 * 2048) / 8192
    assert ctx_torso_cost.mean_keys(16, 64) == 8.5
    per = ctx_torso_cost.macs_per_token(t, r["obs_dim"])
    assert per == {
        "embed": 376 * 2048,
        "attention_projections": 4 * (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048),
        "attention_scores": 4 * (2098176 + 12582912) / 8192 * 32 * 256,
        "indexer_projections": 4 * (2048 * 1024 + 2048 * 64 + 2048 * 16),
        "index_scores": 4 * 4096.5 * 16 * 64,
        "experts": 4 * (2048 * 128 + 3 * 2048 * 768 * 1.0),
    }
    assert per["attention_projections"] / 4 == 18_874_368 and per["indexer_projections"] / 4 == 2_260_992
    forward = sum(per.values())
    assert forward == pytest.approx(4 * 44.99e6 + 0.77e6, rel=1e-3)     # multiply-adds a token a pass
    parts = ctx_torso_cost.flops_per_grad_step(body)
    tokens = 8192
    assert parts["target_forward"] == parts["critic_forward"] == 2 * tokens * forward
    assert parts["critic_backward"] == 2 * tokens * (
        2 * forward - 376 * 2048 - per["indexer_projections"])
    assert parts["total"] == pytest.approx(11.68e12, rel=1e-3)
    assert parts["heads"] < 1e-5 * parts["total"]
    scores = per["attention_scores"] + per["index_scores"]
    assert 0.40 < scores / forward < 0.45           # required; executed, the dense form doubles it


def test_the_mfu_metrics_argument_is_the_cost_of_the_configuration(body):
    spec = mf._read(REPO, mf.metric_file("agent.ctx_step_mfu"))
    assert spec["reducer"] == "step_mfu"
    assert spec["args"]["flops_per_grad_step"] == ctx_torso_cost.flops_per_grad_step(body)["total"]


def test_the_new_scope_reads_through_scope_ms():
    from cellbench import trace

    for name, phase in (("agent.indexer_ms", "agent.indexer"),
                        ("agent.ctx_attention_ms", "agent.attention"),
                        ("agent.ctx_experts_ms", "agent.experts")):
        spec = mf._read(REPO, mf.metric_file(name))
        assert spec["reducer"] == "scope_ms" and spec["args"] == {"phase": phase}
    ctx = Context(None, "^jit_lane", 1, {})
    assert scope_ms.reduce(ctx, "agent.indexer") is None          # no trace: nothing
    tr = trace.load(os.path.join(REPO, "cellbench", "testdata", "v5e_phases_slice.json.gz"))
    assert scope_ms.reduce(Context(tr, "^jit_lane", 32, {}), "agent.indexer") == 0.0


def test_the_cell_declares_its_four_metrics_and_no_other_cell_reads_them():
    cell = mf.cell(*mf.load(), CELL)
    names = {m["name"] for m in cell.per_layer}
    assert names >= NEW | {"agent.learner_mfu", "device.idle_share", "agent.matmul_share"}
    assert not names & {"replay.draw_ms", "parallel.sync_ms", "agent.attention_ms",
                        "agent.step_mfu", "replay.window_gather_ms"}
    assert cell.traffic["driver"] == "learner_ctx" and cell.chips == 1
    for other in (w["name"] for w in mf.load()[0]["workloads"] if w["name"] != CELL):
        assert not NEW & {m["name"] for m in mf.cell(*mf.load(), other).per_layer}


# ------------------------------------------------------- the configuration file
def test_config_file_holds_the_programs_preset(body):
    """``torso`` in the file is what its argv resolves to through the
    program's own path, and that is the published preset cut as stated."""
    from d4pg_tpu.models.torso import TORSO_PRESETS
    from train import build_parser, config_from_args

    cfg = config_from_args(build_parser().parse_args(body["argv"]))
    assert dataclasses.asdict(cfg.agent.torso) == body["torso"]
    cut = dataclasses.replace(
        TORSO_PRESETS["keye_vl2"], num_hidden_layers=4, experts_held=16, window=8192,
        row_stride=1, span="stream")
    assert cfg.agent.torso == cut
    full = mf.cell(*mf.load(), CELL)
    run = config_from_args(build_parser().parse_args(full.config["argv"] + full.traffic["argv"]))
    assert (run.steps_per_dispatch, run.batch_size) == (1, 1)
    assert (run.agent.torso.window, run.agent.torso.span) == (8192, "stream")
    assert run.replay_capacity == body["replay_capacity"] == 2 ** 20
    assert "T = 8192" in full.traffic["what"] and "B = 1" in full.traffic["what"]
    assert "K = 1" in full.traffic["what"] and "stream" in full.traffic["what"]


def test_no_width_differs_from_the_source(body):
    """Every key of the source's config.json is in the file at its published
    value, but the ones ``reduced`` names; and the program's preset has the
    same widths under its names."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert row["source_url"] == body["source"]
    differs = {k for k, v in row["config"].items() if body.get(k, "absent") != v}
    assert differs == set(body["reduced"]) - {"replay_capacity"}
    assert {k: body["published"][k] for k in differs} == {k: row["config"][k] for k in differs}
    assert not [k for k in body["reduced"] if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    t, sa = body["torso"], row["config"]["sa_config"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
                "rms_norm_eps", "rope_theta"):
        assert t[key] == row["config"][key], key
    assert (t["index_n_heads"], t["index_head_dim"], t["index_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert t["window"] // t["query_chunks"] == sa["q_chunk_size"]
    assert t["n_routed_experts"] == row["config"]["num_experts"] == 128   # the router keeps its width
    assert t["experts_held"] == body["num_experts"] == body["num_local_experts"] == 16
    assert (t["first_k_dense_replace"], t["n_shared_experts"]) == (0, 0)
    assert row["config"]["mlp_only_layers"] == [] and row["config"]["decoder_sparse_step"] == 1
    assert set(body) >= {"assumed", "departures", "deployment", "published", "held_here"}


def test_held_here_is_the_parameter_count(body):
    from train import build_parser, config_from_args
    from d4pg_tpu.agent.d4pg import create_train_state

    cfg = config_from_args(build_parser().parse_args(body["argv"]))
    shapes = jax.eval_shape(lambda k: create_train_state(cfg.agent, k), jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))  # noqa: E731
    total = count(shapes.critic_params) + count(shapes.actor_params)
    assert total == pytest.approx(389.6e6, rel=1e-3) and "389.6 M" in body["held_here"]
    layer = count(shapes.critic_params["torso"]["layers"][0])
    assert layer == pytest.approx(96.90e6, rel=1e-3)
