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

import os
import re

import jax
import jax.numpy as jnp
import pytest

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
    "device_per_sharded": (_device_per_sharded, set(PHASES) - TORSO - INDEXER - LINEAR),
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


@pytest.mark.parametrize("variant", VARIANTS)
def test_every_variant_holds_its_phases(variant):
    make, want = VARIANTS[variant]
    mega, shapes = make()
    instructions = _instructions(mega.lower(*shapes).compile().as_text())
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


# Plain scopes: marks inside a phase for a reader of ``op_name`` (PERF.md
# section 3), opened with ``jax.named_scope`` itself so that they carry no
# ``ph:`` token and no phase metric books an instruction differently.
PLAIN_SCOPES = {"gated_delta.py": ("delta_solve",)}


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
                for mark in PLAIN_SCOPES.get(f, ()):
                    text = text.replace(f'jax.named_scope("{mark}")', "")
                if f != "profiling.py":
                    assert "named_scope(" not in text, f
    assert opened == set(PHASES) and len(set(PHASES)) == len(PHASES)
    assert all(TOKEN.fullmatch(PHASE_PREFIX + p) and "/" not in p for p in PHASES)
    with pytest.raises(ValueError, match="unknown phase"):
        phase("replay.drew")
    for mark in sum(PLAIN_SCOPES.values(), ()):
        inside = f"jit(lane)/{PHASE_PREFIX}agent.linear_attention/{mark}/while/body/mul"
        assert not TOKEN.search(mark) and phase_of(inside) == "agent.linear_attention"


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
