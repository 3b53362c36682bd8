"""The linear-attention hybrid configuration's benchmark parts at a tiny
size on the CPU: the program's step (a chunked scan) against its plain
reference (a token-by-token recurrence) given the program's expert choices;
the comparison catching a swapped expert, a chunked scan that drops the
decay, and a bfloat16 pass; the FLOP count by hand; the reducers' new names;
the configuration file against the program and its source."""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import lin_torso_cost, manifest as mf
from cellbench.drivers import learner_lin as ll
from cellbench.reducers import Context, scope_ms

REPO = mf.CODE_ROOT
CONFIG = "humanoid_qwen3next_ep32"
CELL = f"{CONFIG}.learn_per_lin8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"agent.lin_attention_ms", "agent.lin_softmax_attention_ms", "agent.lin_experts_ms",
       "agent.lin_step_mfu"}
quiet = lambda *_: None  # noqa: E731


@pytest.fixture(scope="module")
def body():
    return mf._read(REPO, f"cellbench/configs/{CONFIG}.json")


@pytest.fixture(scope="module")
def tiny_agent(body):
    from train import build_parser, config_from_args

    argv = [a if a != "Humanoid-v4" else "pendulum" for a in body["argv"]] + body["rehearsal_argv"]
    agent = config_from_args(build_parser().parse_args(argv)).agent
    t = agent.torso
    assert t.window // t.delta_chunk == 4 and t.experts_held < t.n_routed_experts
    return agent


@pytest.fixture(scope="module")
def checked(tiny_agent, body):
    return ll.reference_check(tiny_agent, 2, 5, body["reference"], say=quiet)


def test_program_step_agrees_with_the_plain_reference_given_its_choices(checked):
    r = checked["reference_step"]
    assert r["ok"], r
    assert all(e <= ll.LIMITS.get(n, ll.TOL_REL) for n, e in r["rel_err"].items())
    assert max(r["ulp_err"].values()) <= ll.TOL_ULP
    assert set(r["rel_err"]) == {"critic_loss", "actor_loss", "priorities", "actor_grad",
                                 "actor_adam_v", "critic_grad", "critic_adam_v"}
    assert set(r["ulp_err"]) == {"actor", "critic", "target_actor", "target_critic"}
    assert r["rel_err"]["critic_grad"] > 0          # two programs, not one compared with itself
    assert set(ll.LIMITS) <= set(r["rel_err"]) and ll.TOL_REL <= min(ll.LIMITS.values())
    assert set(ll.ULP_LIMITS) <= set(r["ulp_err"]) and r["tol_ulp_of"] == ll.ULP_LIMITS
    parts = r["critic_grad_by_part"]
    assert set(parts) == {
        "layer0.delta_net", "layer1.delta_net", "layer2.delta_net", "layer3.attention",
        "head_embed_final_norm", "layer3.w_router.own_scale", "layer3.w_shared_gate.own_scale",
        *(f"layer{i}.{k}" for i in range(4) for k in ("experts",) + ll.ONE_TOKEN_LEAVES)}
    # the last layer's one-token leaves are held to the scale of the layer below
    for name in ll.ONE_TOKEN_LEAVES:
        assert 0 <= parts[f"layer3.{name}"] <= parts[f"layer3.{name}.own_scale"]
    assert r["rel_err"]["critic_grad"] == max(
        v for k, v in parts.items() if not k.endswith(".own_scale"))
    assert r["candidates_passed_over"] == 0


def test_the_two_sides_expert_sets_agree_outside_the_band(checked, tiny_agent):
    c, r = checked["choices"], checked["routing"]
    assert c["ok"] and r["ok"], (c, r)
    t = tiny_agent.torso
    e = c["experts"]
    assert e["outside_the_band"] == 0 and e["margin"] == ll.ROUTER_MARGIN
    assert e["disagreements"] <= ll.MAX_DIFFER_SHARE * max(e["in_band"], 1)
    # two passes x layers x valid tokens x k experts
    assert 0 < e["places"] <= 2 * t.num_hidden_layers * 2 * t.window * t.num_experts_per_tok
    assert np.asarray(e["by_pass_and_layer"]).shape == (2, t.num_hidden_layers)
    assert r["dropped"] == 0 and 0 < r["pairs_on_held_experts"] <= (
        r["tokens"] * t.num_experts_per_tok * r["passes_x_layers"])
    assert r["passes_x_layers"] == 2 * t.num_hidden_layers


def test_a_swapped_expert_fails_the_check(tiny_agent, body, monkeypatch):
    """The program routes one token in nine to the expert after the one its
    router chose: the sets differ far outside the band (part (i) refuses
    the step), and held against the reference's own routing nothing smooth
    can agree either."""
    from d4pg_tpu.models import torso as T

    real = T.route

    def route(cfg, p, x):
        chosen, gates = real(cfg, p, x)
        rows = (jnp.arange(chosen.shape[0]) % 9 == 0)[:, None] & (jnp.arange(chosen.shape[1]) == 0)
        runner_up = jax.lax.top_k(jax.nn.softmax(x @ p["router"], -1), chosen.shape[1] + 3)[1][:, -1:]
        return jnp.where(rows, runner_up, chosen), gates

    monkeypatch.setattr(T, "route", route)
    r = ll.reference_check(tiny_agent, 2, 5, body["reference"], say=quiet)
    assert not r["choices"]["ok"] and r["choices"]["experts"]["outside_the_band"] > 0
    assert r["choices"]["experts"]["worst_disagreement"] > 10.0          # margins


def test_a_chunked_scan_that_drops_the_decay_fails_the_check(tiny_agent, body, monkeypatch):
    """The state handed from chunk to chunk without its decay (``S ·
    e^{γ_C}`` left out: right inside a chunk, wrong across chunks): every
    choice is the reference's still, and part (ii) refuses the step."""
    from d4pg_tpu.models import torso as T
    from d4pg_tpu.ops import gated_delta as gd

    def leaky(q, k, v, g, beta, chunk=64):
        n = q.shape[1] // chunk
        outs, state = [], None
        for i in range(n):              # chunk by chunk, each from the last one's state undecayed
            cut = lambda x: x[:, i * chunk:(i + 1) * chunk]  # noqa: E731
            out, fresh = gd.gated_delta_recurrent(*(cut(x) for x in (q, k, v, g, beta)))
            if state is not None:       # the read of the carried state, as if g had been 0 since
                out = out + jnp.einsum("bthk,bhkv->bthv", cut(q), state)
            state = fresh if state is None else state + fresh
            outs.append(out)
        return jnp.concatenate(outs, axis=1), state

    monkeypatch.setattr(T, "gated_delta_chunked", leaky)
    r = ll.reference_check(tiny_agent, 2, 5, body["reference"], say=quiet)
    assert not r["reference_step"]["ok"]
    worst = max(v for k, v in r["reference_step"]["critic_grad_by_part"].items() if "delta" in k)
    assert worst > 10 * ll.TOL_CRITIC_GRAD


def test_a_bfloat16_pass_fails_the_tolerance(tiny_agent, body):
    """The same step with its matrix products in one bfloat16 pass (what the
    chip's default precision does to float32 operands; on the CPU, a
    bfloat16 compute dtype in the heads) is refused by a gradient."""
    low = dataclasses.replace(tiny_agent, compute_dtype="bfloat16")
    r = ll.reference_check(low, 2, 5, body["reference"], say=quiet)
    assert not r["reference_step"]["ok"]
    assert r["reference_step"]["rel_err"]["critic_grad"] > 10 * ll.TOL_CRITIC_GRAD


def test_the_seeded_state_fits_the_stack(tiny_agent):
    state = jax.jit(lambda s: ll.seeded_state(tiny_agent, s))(jnp.uint32(5))
    first, last = (state.critic_params["torso"]["layers"][i] for i in (0, 3))
    for zero_centred in (first["attn_norm"], first["ffn_norm"], last["attn"]["q_norm"],
                         state.critic_params["torso"]["final_norm"]):
        assert float(jnp.abs(zero_centred).max()) <= 0.1            # a scale of 1 ± 0.1
    lin = first["lin"]
    assert 0.9 <= float(lin["norm"].min()) and float(lin["norm"].max()) <= 1.1
    assert float(lin["dt_bias"].max()) <= ll.DT_BIAS_SHIFT + 0.1
    assert 0.5 < float(jnp.abs(lin["conv"]).max()) <= np.sqrt(3 / 4) + 1e-6    # fan-in 4
    nu = state.critic_opt_state[0].nu
    assert float(min(jnp.min(v) for v in jax.tree_util.tree_leaves(nu["head"]))) >= 49.0
    ref_state = ll.to_reference_state(state)
    kinds = ["w_qkvz" in layer for layer in ref_state["critic"]["torso"]["layers"]]
    assert kinds == [True, True, True, False]


# ------------------------------------------------------------ cost, reducers
def test_lin_torso_cost_counted_by_hand(body):
    t, r = body["torso"], body["resolved"]
    per = lin_torso_cost.macs_per_token(t, r["obs_dim"])
    assert per == {
        "embed": 376 * 2048,
        "delta_projections": 3 * (2048 * 12288 + 2048 * 64 + 4096 * 2048),
        "delta_recurrence": 3 * 3 * 32 * 128 * 128,
        "attention_projections": 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048,
        "attention_scores": 4096.5 * 16 * 512,
        "experts": 4 * (2048 * 512 + 3 * 2048 * 512 + 2048 + 3 * 2048 * 512 * 10 * 16 / 512),
    }
    assert per["delta_projections"] / 3 == 33_685_504 and per["attention_projections"] == 27_262_976
    forward = sum(per.values())
    tokens = 8192
    parts = lin_torso_cost.flops_per_grad_step(body)
    assert parts["target_forward"] == parts["critic_forward"] == 2 * tokens * forward
    assert parts["target_forward"] == pytest.approx(3.08e12, rel=2e-3)
    assert parts["critic_backward"] == 2 * tokens * (2 * forward - 376 * 2048)
    assert parts["total"] == pytest.approx(12.31e12, rel=1e-3)
    assert parts["heads"] < 1e-5 * parts["total"]
    # a DeltaNet layer 0.58 T a pass (0.03 of it the recurrence), the attention layer 1.00 T
    assert 2 * tokens * (per["delta_projections"] + per["delta_recurrence"]) / 3 == pytest.approx(
        0.578e12, rel=2e-3)
    assert per["delta_recurrence"] / (per["delta_projections"] + per["delta_recurrence"]) < 0.05
    assert 2 * tokens * (per["attention_projections"] + per["attention_scores"]) == pytest.approx(
        0.9965e12, rel=2e-3)


def test_the_mfu_metrics_argument_is_the_cost_of_the_configuration(body):
    spec = mf._read(REPO, mf.metric_file("agent.lin_step_mfu"))
    assert spec["reducer"] == "step_mfu"
    assert spec["args"]["flops_per_grad_step"] == lin_torso_cost.flops_per_grad_step(body)["total"]


def test_the_scopes_read_through_scope_ms():
    from cellbench import trace
    from d4pg_tpu.utils.profiling import PHASES

    for name, phase in (("agent.lin_attention_ms", "agent.linear_attention"),
                        ("agent.lin_softmax_attention_ms", "agent.attention"),
                        ("agent.lin_experts_ms", "agent.experts")):
        spec = mf._read(REPO, mf.metric_file(name))
        assert spec["reducer"] == "scope_ms" and spec["args"] == {"phase": phase}
        assert phase in PHASES
    ctx = Context(None, "^jit_lane", 1, {})
    assert scope_ms.reduce(ctx, "agent.linear_attention") is None      # no trace: nothing
    # a program without the scope (the parent's, any recording before it): a measured 0
    tr = trace.load(os.path.join(REPO, "cellbench", "testdata", "v5e_phases_slice.json.gz"))
    assert scope_ms.reduce(Context(tr, "^jit_lane", 32, {}), "agent.linear_attention") == 0.0


def test_the_cell_declares_its_four_metrics_and_no_other_cell_reads_them():
    manifest, root = mf.load()
    cell = mf.cell(manifest, root, CELL)
    names = {m["name"] for m in cell.per_layer}
    assert names >= NEW | {"agent.learner_mfu", "device.idle_share", "agent.matmul_share"}
    assert not names & {"replay.draw_ms", "parallel.sync_ms", "agent.attention_ms",
                        "agent.step_mfu", "agent.ctx_step_mfu", "agent.indexer_ms",
                        "agent.ctx_attention_ms", "agent.ctx_experts_ms"}
    assert cell.traffic["driver"] == "learner_lin" and cell.chips == 1
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "transitions_per_s"
    for other in (w["name"] for w in manifest["workloads"] if w["name"] != CELL):
        assert not NEW & {m["name"] for m in mf.cell(manifest, root, other).per_layer}
    # each entry once, and the four together after the entries that came
    # before them: nothing put first or in the middle, whatever is appended
    assert [c["name"] for c in manifest["configs"]].count(CONFIG) == 1
    assert [w["name"] for w in manifest["workloads"]].count(CELL) == 1
    listed = [m["name"] for m in manifest["per_layer"]]
    after = listed.index("agent.ctx_step_mfu") + 1
    assert set(listed[after:after + len(NEW)]) == NEW


def test_the_mix_is_ctx8ks_to_the_letter_under_its_own_driver():
    mine = mf._read(REPO, mf.traffic_file("learn_per_lin8k"))
    theirs = mf._read(REPO, mf.traffic_file("learn_per_ctx8k"))
    same = lambda d: {k: v for k, v in d.items() if k not in ("driver", "what")}  # noqa: E731
    assert same(mine) == same(theirs) and mine["driver"] == "learner_lin"


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
        TORSO_PRESETS["qwen3_next"], num_hidden_layers=4, experts_held=16, window=8192,
        row_stride=1, span="stream")
    assert cfg.agent.torso == cut
    full = mf.cell(*mf.load(), CELL)
    run = config_from_args(build_parser().parse_args(full.config["argv"] + full.traffic["argv"]))
    assert (run.steps_per_dispatch, run.batch_size) == (1, 1)
    assert (run.agent.torso.window, run.agent.torso.span) == (8192, "stream")
    assert run.replay_capacity == body["replay_capacity"] == body["resolved"]["replay_capacity"]
    assert "T = 8192" in full.traffic["what"] and "B = 1" in full.traffic["what"]
    assert "K = 1" in full.traffic["what"] and "stream" in full.traffic["what"]
    assert set(body) >= {"what", "argv", "rehearsal_argv", "reference", "resolved", "torso",
                         "published", "held_here", "matmul_precision", "reduced", "assumed",
                         "departures", "deployment"}
    assert len(body["resolved"]) == 22


def test_no_width_differs_from_the_source(body):
    """Every key of the source's config.json is in the file at its published
    value, but the ones ``reduced`` names; and the program's preset has the
    same widths under its names."""
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert row["source_url"] == body["source"]
    differs = {k for k, v in row["config"].items() if body.get(k, "absent") != v}
    assert differs == set(body["reduced"]) - {"replay_capacity"}
    assert {k: body["published"][k] for k in differs} == {k: row["config"][k] for k in differs}
    assert not [k for k in body["reduced"] if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    t, src = body["torso"], row["config"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads", "num_experts_per_tok",
                "rms_norm_eps", "rope_theta", "partial_rotary_factor", "full_attention_interval",
                "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim",
                "shared_expert_intermediate_size"):
        assert t[key] == src[key], key
    assert t["n_routed_experts"] == src["num_experts"] == 512        # the router keeps its width
    assert t["experts_held"] == body["num_experts"] == 16
    assert (t["first_k_dense_replace"], t["n_shared_experts"]) == (0, 1)
    assert src["mlp_only_layers"] == [] and src["decoder_sparse_step"] == 1
    assert src["norm_topk_prob"] is True and body["num_hidden_layers"] == 4
    assert "32 chips" in body["deployment"] and "5,120" in body["deployment"]


def test_held_here_is_the_parameter_count(body):
    from train import build_parser, config_from_args
    from d4pg_tpu.agent.d4pg import create_train_state

    cfg = config_from_args(build_parser().parse_args(body["argv"]))
    shapes = jax.eval_shape(lambda k: create_train_state(cfg.agent, k), jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))  # noqa: E731
    total = count(shapes.critic_params) + count(shapes.actor_params)
    assert total == 348_655_492 and "348.7 M" in body["held_here"]
    layers = shapes.critic_params["torso"]["layers"]
    assert [count(p) for p in layers] == [88_250_560] * 3 + [81_795_584]
    assert count(layers[0]["lin"]) == 33_718_464 and count(layers[3]["attn"]) == 27_263_488
    assert count(layers[0]["ffn"]) == 54_528_000
    assert layers[0]["lin"]["in_qkvz"].shape == (2048, 12288)
    assert layers[0]["lin"]["conv"].shape == (8192, 4)
    assert layers[3]["attn"]["q"].shape == (2048, 8192)
    assert layers[0]["ffn"]["router"].shape == (2048, 512)
