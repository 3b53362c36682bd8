"""The megastep's phase scopes (``utils.profiling.PHASES``) are in the
compiled program of every megastep ``Trainer`` can pick, on the instructions
that carry the time, and the persistent compile cache keeps them in its key.

A scope is HLO metadata: each variant is lowered and compiled here on the
CPU at rehearsal sizes from shapes alone (nothing runs), and the compiled
text's ``op_name``s are read by the benchmark's own reader
(``cellbench.scopes.phase_of``: an instruction belongs to the LAST ``ph:``
token in its path), so the program's tokens and the reader cannot drift
apart. What the scopes read on a chip is PERF.md's; the byte-parity tests
hold that they change no bit.
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from cellbench.reducers.span_ms import PASSES, pass_of, spans_of
from cellbench.scopes import TOKEN, phase_of
from d4pg_tpu.agent.d4pg import create_train_state
from d4pg_tpu.agent.state import D4PGConfig, DistConfig
from d4pg_tpu.parallel import make_mesh
from d4pg_tpu.replay.device_per import DevicePerTree
from d4pg_tpu.replay.device_ring import DeviceRing
from d4pg_tpu.runtime import megastep as ms
from d4pg_tpu.utils import compile_cache
from d4pg_tpu.utils.profiling import PHASE_PREFIX, PHASES, phase

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K, B, C, DP = 2, 8, 64, 4
# "%name = <shape> opcode(operands…), …, metadata={op_name="…" …}"
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*?[\])}] ([a-z][a-z\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
# the instructions that carry a megastep's time, which must not be unscoped
TIMED = {"gather", "scatter", "dot", "all-gather", "all-reduce",
         "all-gather-start", "all-reduce-start"}
COMMON = {"replay.draw", "replay.row_gather", "agent.networks",
          "ops.projection_loss", "agent.optimizer"}
TORSO = {"agent.attention", "agent.experts"}     # opened by models/torso.py only
INDEXER = {"agent.indexer"}                      # and only where attention runs under one
LINEAR = {"agent.linear_attention"}              # and only in the hybrid stack's DeltaNet layers
# <layer>.<phase>.<part>: parts of a phase, which the two-component reader
# books to the phase (utils/profiling.py)
SUB_PHASES = tuple(p for p in PHASES if p.count(".") == 2)
MAIN_PHASES = set(PHASES) - set(SUB_PHASES)


def _cfg(**kw) -> D4PGConfig:
    return D4PGConfig(obs_dim=3, action_dim=1, hidden_sizes=(16, 16),
                      dist=DistConfig(num_atoms=11, v_min=-5.0, v_max=5.0), **kw)


def _shapes(cfg, lanes: int, per: bool) -> list:
    """(state, ring[, tree], key) as shapes: nothing is made."""
    rows = C * lanes
    ring = lambda: DeviceRing(  # noqa: E731
        obs=jnp.zeros((rows, cfg.obs_dim)), action=jnp.zeros((rows, cfg.action_dim)),
        reward=jnp.zeros((rows,)), next_obs=jnp.zeros((rows, cfg.obs_dim)),
        discount=jnp.zeros((rows,)), size=jnp.zeros((), jnp.int32))
    tree = lambda: DevicePerTree(  # noqa: E731
        jnp.zeros((lanes, 2 * C), jnp.float32), jnp.zeros((), jnp.float32))
    key = jax.random.PRNGKey(0)
    args = [jax.eval_shape(lambda k: create_train_state(cfg, k), key),
            jax.eval_shape(ring)]
    if per:
        args.append(jax.eval_shape(tree))
    return args + [jax.eval_shape(lambda: key)]


def _uniform():
    cfg = _cfg()
    return ms.make_megastep_uniform(cfg, K, B), _shapes(cfg, 1, per=False)


def _device_per():
    cfg = _cfg()
    return ms.make_megastep_device_per(cfg, K, B), _shapes(cfg, 1, per=True)


def _device_per_fused():
    cfg = _cfg(projection_backend="pallas_fused")
    return ms.make_megastep_device_per_fused(cfg, K, B), _shapes(cfg, 1, per=True)


def _device_per_torso(preset="glm47_flash_tiny"):
    import dataclasses

    from d4pg_tpu.models.torso import TORSO_PRESETS

    torso = dataclasses.replace(TORSO_PRESETS[preset], experts_first=2, experts_held=4)
    cfg = _cfg(torso=torso)
    return ms.make_megastep_device_per(cfg, K, B), _shapes(cfg, 1, per=True)


def _uniform_sharded():
    cfg = _cfg()
    mesh = make_mesh(dp=DP, tp=1)
    return (ms.make_megastep_uniform_sharded(cfg, K, B * DP, mesh),
            _shapes(cfg, DP, per=False))


def _device_per_sharded():
    cfg = _cfg()
    mesh = make_mesh(dp=DP, tp=1)
    return (ms.make_megastep_device_per_sharded(cfg, K, B * DP, mesh),
            _shapes(cfg, DP, per=True))


# every megastep Trainer.__init__ can pick (cellbench's learner picks the same)
VARIANTS = {
    "uniform": (_uniform, COMMON),
    "device_per": (_device_per, COMMON | {"replay.write_back"}),
    "device_per_fused": (_device_per_fused, COMMON | {"replay.write_back"}),
    "uniform_sharded": (_uniform_sharded, COMMON | {"parallel.sync"}),
    "device_per_sharded": (_device_per_sharded, MAIN_PHASES - TORSO - INDEXER - LINEAR),
    "device_per_torso": (_device_per_torso, COMMON | {"replay.write_back"} | TORSO),
    "device_per_indexed_torso": (
        lambda: _device_per_torso("keye_vl2_tiny"),
        COMMON | {"replay.write_back"} | TORSO | INDEXER),
    "device_per_hybrid_torso": (
        lambda: _device_per_torso("qwen3_next_tiny"),
        COMMON | {"replay.write_back"} | TORSO | LINEAR),
}


def _instructions(text: str) -> list:
    """``(opcode, op_name, line)`` of every instruction of the compiled
    text, the fused computations' included."""
    out = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            name = OP_NAME.search(line)
            out.append((m.group(1), name.group(1) if name else "", line.strip()))
    return out


@functools.lru_cache(maxsize=None)
def _compiled(variant: str) -> list:
    """A variant's instructions: one compile for every test that reads them."""
    mega, shapes = VARIANTS[variant][0]()
    return _instructions(mega.lower(*shapes).compile().as_text())


@pytest.mark.parametrize("variant", VARIANTS)
def test_every_variant_holds_its_phases(variant):
    want = VARIANTS[variant][1]
    instructions = _compiled(variant)
    assert len(instructions) > 100, "the pattern no longer reads the compiled text"
    held = {phase_of(name) for _, name, _ in instructions} - {""}
    assert held == want
    timed = [i for i in instructions if i[0] in TIMED
             or (i[0] == "custom-call" and "tpu_custom_call" in i[2])]
    assert {op for op, _, _ in timed} >= {"gather", "dot"}
    if "parallel.sync" in want:
        assert any(op.startswith("all-") for op, _, _ in timed)
    if "replay.write_back" in want:
        assert any(op == "scatter" for op, _, _ in timed)
    assert [line[:200] for _, name, line in timed if not phase_of(name)] == []
    # the loss nests in the networks' scope, forward and backward, and the
    # innermost (last) token is the one an instruction is booked to
    nested = [name for _, name, _ in instructions
              if f"{PHASE_PREFIX}agent.networks" in name
              and f"{PHASE_PREFIX}ops.projection_loss" in name]
    assert nested and {phase_of(n) for n in nested} == {"ops.projection_loss"}
    assert any("transpose(jvp(" in n for n in nested)
    if TORSO <= want:
        # the torso's parts nest in the networks' scope too, forward,
        # recomputed under jax.checkpoint and backward, and hold its dots
        for part in want & (TORSO | INDEXER):
            names = [n for op, n, _ in instructions if phase_of(n) == part]
            assert any(f"{PHASE_PREFIX}agent.networks" in n for n in names), part
            assert any("transpose(jvp(" in n for n in names), part
            assert any("rematted_computation" in n or "checkpoint" in n for n in names), part
            assert any(op == "dot" and phase_of(n) == part for op, n, _ in instructions), part


# What each torso's compiled program must hold of the sub-phases, beside
# ``agent.networks.target`` (every megastep's): the parts of its own mixers.
EXPERT_PARTS = {"agent.experts.route", "agent.experts.dispatch", "agent.experts.blocks"}
SUB_PHASES_OF = {
    "device_per": set(),
    "device_per_torso": EXPERT_PARTS | {"agent.attention.scores"},
    "device_per_indexed_torso": EXPERT_PARTS | {
        "agent.attention.scores", "agent.indexer.scores", "agent.indexer.select"},
    "device_per_hybrid_torso": EXPERT_PARTS | {
        "agent.attention.scores", "agent.linear_attention.solve",
        "agent.linear_attention.scan"},
}


@pytest.mark.parametrize("variant", SUB_PHASES_OF)
def test_sub_phases_lie_inside_their_phase_and_every_pass_is_told(variant):
    """Whole tokens, as ``cellbench/reducers/span_ms.py`` reads them: each
    sub-phase is on some instruction's path, right inside its own phase; no
    phase but ``agent.networks`` nests another (so "under a span" and
    "booked to it" are the same instructions); and the pass is written on
    the path — also through the expert layer's custom VJP."""
    instructions = [(op, name) for op, name, _ in _compiled(variant) if spans_of(name)]
    held = {t for _, name in instructions for t in spans_of(name)}
    assert held & set(SUB_PHASES) == SUB_PHASES_OF[variant] | {"agent.networks.target"}
    for _, name in instructions:
        tokens = spans_of(name)
        for outer, inner in zip(tokens, tokens[1:]):
            if inner in SUB_PHASES:         # opened where its phase is the innermost
                assert outer.startswith(inner.rsplit(".", 1)[0]), name
        mains = {t if t in MAIN_PHASES else t.rsplit(".", 1)[0] for t in tokens}
        assert mains <= {"agent.networks", phase_of(name)}, name
    under = lambda span: [n for _, n in instructions if span in spans_of(n)]  # noqa: E731
    assert {pass_of(n) for n in under("agent.networks")} == (
        set(PASSES) if variant != "device_per" else set(PASSES) - {"recompute"})
    assert all(pass_of(n) == "target" for n in under("agent.networks.target"))
    assert any(op == "dot" for op, n in instructions if "agent.networks.target" in spans_of(n))
    if variant == "device_per":
        return
    # through the custom VJP: the dispatch gathers run in all four passes
    # (the checkpoint runs the forward rule again for its residual, the
    # gathered rows); the block loop forward, for the target and backward —
    # its recomputed copy feeds nothing the backward rule reads, and XLA
    # removes it
    assert {pass_of(n) for n in under("agent.experts.dispatch")} == set(PASSES)
    assert {pass_of(n) for n in under("agent.experts.blocks")} == set(PASSES) - {"recompute"}
    assert any(op == "dot" and pass_of(n) == "backward" and "while/body" in n
               for op, n in instructions if "agent.experts.blocks" in spans_of(n))
    assert {pass_of(n) for n in under("agent.attention.scores")} == set(PASSES)
    if variant == "device_per_indexed_torso":    # chosen once, kept by the checkpoint
        assert {pass_of(n) for n in under("agent.indexer.select")} == {"target", "forward"}
    if variant == "device_per_hybrid_torso":     # the inverse's backward is two products
        assert {pass_of(n) for n in under("agent.linear_attention.solve")} == (
            set(PASSES) - {"backward"})
        assert {pass_of(n) for n in under("agent.linear_attention.scan")} == set(PASSES)


@pytest.mark.parametrize("sub", SUB_PHASES)
def test_the_two_component_reader_books_as_before(sub):
    """``scopes.phase_of`` of a path with sub-phase tokens equals that of the
    same path without them: every accepted ``…_ms`` metric reads as before."""
    parent = sub.rsplit(".", 1)[0]
    assert parent in MAIN_PHASES and TOKEN.match(PHASE_PREFIX + sub).group(1) == parent
    outer = "" if parent == "agent.networks" else f"{PHASE_PREFIX}agent.networks/"
    for path in (
            f"jit(lane)/while/body/closed_call/{outer}jvp({PHASE_PREFIX}{parent})/"
            f"{PHASE_PREFIX}{sub}/while/body/dot_general:",
            f"jit(lane)/{PHASE_PREFIX}agent.networks/transpose(jvp({PHASE_PREFIX}agent.networks))"
            f"/jvp()/checkpoint/rematted_computation/{PHASE_PREFIX}{parent}/{PHASE_PREFIX}{sub}/mul:",
            f"jit(lane)/{PHASE_PREFIX}{parent}/{PHASE_PREFIX}{sub}/checkpoint/"
            f"{PHASE_PREFIX}ops.projection_loss/mul:"):
        without = path.replace(f"{PHASE_PREFIX}{sub}/", "")
        assert PHASE_PREFIX + sub not in without
        assert phase_of(path) == phase_of(without) != "", path
        assert spans_of(path)[-1] in (sub, "ops.projection_loss")


def test_phases_lists_what_the_sources_open_and_nothing_else():
    """Every name in PHASES is opened somewhere in the program, through
    ``phase`` only, and ``phase`` refuses a name that is not listed."""
    opened = set()
    for base, _, files in os.walk(os.path.join(REPO, "d4pg_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(base, f)) as src:
                    text = src.read()
                opened |= set(re.findall(r'\bphase\("([^"]+)"\)', text))
                if f != "profiling.py":
                    assert "named_scope(" not in text, f
    assert opened == set(PHASES) and len(set(PHASES)) == len(PHASES)
    assert all(TOKEN.fullmatch(PHASE_PREFIX + p) and "/" not in p for p in MAIN_PHASES)
    # a sub-phase's first two components are a listed phase: all the
    # two-component reader sees of it
    assert all(TOKEN.match(PHASE_PREFIX + p).group(1) in MAIN_PHASES and "/" not in p
               and p.count(".") == 2 for p in SUB_PHASES)
    with pytest.raises(ValueError, match="unknown phase"):
        phase("replay.drew")
    with pytest.raises(ValueError, match="unknown phase"):
        phase("agent.experts.combine")


@pytest.mark.parametrize("exported", [None, "/x/placed/by/operator"])
def test_the_compile_cache_key_keeps_the_scopes(monkeypatch, exported):
    """JAX strips metadata from the persistent cache's key by default, so a
    program whose scopes changed would hit the executable compiled before
    the change: the key is ours even when the place is the operator's."""
    key = "jax_compilation_cache_include_metadata_in_key"
    before = (jax.config.jax_compilation_cache_dir, getattr(jax.config, key))
    if exported:
        monkeypatch.setenv(compile_cache.ENV_VAR, exported)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    jax.config.update(key, False)
    try:
        compile_cache.configure_compile_cache()
        assert getattr(jax.config, key) is True
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update(key, before[1])


def test_the_one_place_that_starts_a_trace(tmp_path):
    """``start_trace``/``stop_trace`` (``Trainer``'s ``--profile-dir``): the
    trace lands where it was asked for and holds the host annotations."""
    from jax.profiler import ProfileData

    from d4pg_tpu.utils.profiling import annotate, start_trace, stop_trace

    @jax.jit
    def step(x):
        with phase("agent.networks"):
            return jnp.tanh(x @ x).sum()

    x = jnp.ones((16, 16))
    step(x).block_until_ready()
    start_trace(str(tmp_path))
    with annotate("host/megastep_dispatch"):
        step(x).block_until_ready()
    stop_trace()
    files = [os.path.join(base, f) for base, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    assert len(files) == 1
    names = {e.name for plane in ProfileData.from_file(files[0]).planes
             for line in plane.lines for e in line.events}
    assert "host/megastep_dispatch" in names
