"""What the chip's compiler makes of the ring's storage: compiled here for a
*described* TPU v5e 2x2, no chip attached (the on-chip-measurement guide,
section 2; the fixture pattern of ``tests/cellbench/test_cellbench_compile_v5e
.py``: the topology is described inside a fixture, never at import).

The guard that would have caught ISSUE 25's bottleneck: at Humanoid's width
(376) the default layout of a ``[C, 376]`` array is feature-major, and XLA
copied the whole array to row-major before every row gather — two copies of
the ring's observations in every dispatch, two thirds of the device's time.
Stored lane-dense (``DeviceRing``: ``[C/16, 6016]``) the megastep and the
ingest hold no instruction of the store's size but their parameters (and
the ingest's in-place update). At HalfCheetah's width (17) the ring must
compile to exactly what a plain ``field[idx]`` gives."""

from __future__ import annotations

import os
import re
from typing import NamedTuple

import jax
import jax.numpy as jnp
import pytest

from d4pg_tpu.agent import D4PGConfig
from d4pg_tpu.agent.d4pg import gather_batches
from d4pg_tpu.models.critic import DistConfig
from d4pg_tpu.replay.device_ring import DeviceRing, make_ingest

# The cell's own 2^21 rows, not a toy ring: nothing is allocated, and what
# the compiler does with a store depends on its size. A 2^14-row field (24
# MB) fits on-chip memory and is prefetched whole (`copy-start/-done ...
# S(1)`); at 2^18 rows the ingest relayouts the NARROW action field through a
# 128 MiB row-major copy, which it does not at 2^21.
CAPACITY, WIDE, NARROW, ACTION = 2 ** 21, 376, 17, 17
K, B, CHUNK = 2, 8, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent cache but
    # cannot be read back without a chip: keep these out of it.
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(desc.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def _ring_shapes(make, width, capacity=CAPACITY):
    f32 = lambda *shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
    return jax.eval_shape(lambda: make(
        obs=f32(capacity, width), action=f32(capacity, ACTION),
        reward=f32(capacity), next_obs=f32(capacity, width),
        discount=f32(capacity), size=jnp.zeros((), jnp.int32)))


INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", re.M)


def _store_sized(text, allowed):
    """Instructions whose result has the wide field's logical or stored
    shape, other than the ``allowed`` opcodes."""
    full = {f"{CAPACITY},{WIDE}", f"{CAPACITY // 16},{16 * WIDE}"}
    return [m.group(0).strip() for m in INSTRUCTION.finditer(text)
            if m.group(1) in full and m.group(2) not in allowed]


def _small_temporaries(compiled):
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.01 * memory.argument_size_in_bytes, (
        memory.temp_size_in_bytes, memory.argument_size_in_bytes)


def test_per_megastep_never_touches_the_whole_wide_store(one_chip):
    from d4pg_tpu.agent import create_train_state
    from d4pg_tpu.replay.device_per import DevicePerTree, tree_width
    from d4pg_tpu.runtime.megastep import make_megastep_device_per

    cfg = D4PGConfig(obs_dim=WIDE, action_dim=ACTION, hidden_sizes=(64, 64),
                     dist=DistConfig(num_atoms=51, v_min=0.0, v_max=1500.0))
    ring = _ring_shapes(DeviceRing, WIDE)
    assert ring.obs.shape == (CAPACITY // 16, 16 * WIDE)
    args = _on(one_chip, (
        jax.eval_shape(lambda: create_train_state(cfg, jax.random.PRNGKey(0))),
        ring,
        DevicePerTree(jnp.zeros((1, tree_width(CAPACITY)), jnp.float32),
                      jnp.zeros((), jnp.float32)),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))))
    compiled = make_megastep_device_per(cfg, K, B).lower(*args).compile()
    assert _store_sized(compiled.as_text(), {"parameter"}) == []
    _small_temporaries(compiled)


def test_ingest_updates_the_wide_store_in_place(one_chip):
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    chunk = dict(obs=f32(CHUNK, WIDE), action=f32(CHUNK, ACTION), reward=f32(CHUNK),
                 next_obs=f32(CHUNK, WIDE), discount=f32(CHUNK))
    args = _on(one_chip, (
        _ring_shapes(DeviceRing, WIDE), chunk,
        jax.ShapeDtypeStruct((CHUNK,), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32)))
    compiled = make_ingest().lower(*args).compile()
    # the donated store passes through the update and nothing else: no copy,
    # convert, transpose or fusion makes a second one
    in_place = {"parameter", "get-tuple-element", "dynamic-update-slice"}
    assert _store_sized(compiled.as_text(), in_place) == []
    _small_temporaries(compiled)


class PlainRing(NamedTuple):
    """Today's ``[C, W]`` fields with no storage rule: ``gather_batches``
    reads it with ``field[idx]``."""

    obs: jax.Array
    action: jax.Array
    reward: jax.Array
    next_obs: jax.Array
    discount: jax.Array
    size: jax.Array


def _program(text):
    """Optimized HLO without what names source lines: the ``metadata`` of
    each instruction and the tables of files and stack frames."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    head, _, rest = text.partition("\nFileNames\n")
    return head + rest[re.search(r"\n(?=%[\w.\-]+ \()", rest).start():] if rest else head


def test_narrow_rows_compile_to_the_plain_index(one_chip):
    idx = jax.ShapeDtypeStruct((K, B), jnp.int32)
    texts = []
    for make in (DeviceRing, PlainRing):
        store = _ring_shapes(make, NARROW)
        assert store.obs.shape == (CAPACITY, NARROW)
        lowered = jax.jit(gather_batches).lower(*_on(one_chip, (store, idx)))
        texts.append(_program(lowered.compile().as_text()))
    assert "gather" in texts[0]
    assert texts[0] == texts[1]


# ------------------------------------------------ the PER tree's write-back
# ISSUE 28: the ancestors of a write-back's leaves are rebuilt densely
# (``replay/device_per.py:rebuild_ancestors``). At the cells' own tree sizes
# the compiled write-back phase holds ONE scatter into the tree and no gather
# from it, the rebuild updates the donated buffer in place (no ``copy`` of
# the tree, no second buffer of its size), and its temporaries stay within a
# tree's internal half.
WRITE_BACK_K, WRITE_BACK_B = 32, 256    # 8,192 positions a dispatch: the cells'


def _per_megastep(sharding, tree_elements, n_rows):
    from d4pg_tpu.agent import create_train_state
    from d4pg_tpu.replay.device_per import DevicePerTree, tree_width
    from d4pg_tpu.runtime.megastep import make_megastep_device_per

    cfg = D4PGConfig(obs_dim=NARROW, action_dim=ACTION, hidden_sizes=(64, 64),
                     dist=DistConfig(num_atoms=51, v_min=0.0, v_max=1000.0))
    assert tree_width(n_rows) == tree_elements
    args = _on(sharding, (
        jax.eval_shape(lambda: create_train_state(cfg, jax.random.PRNGKey(0))),
        _ring_shapes(DeviceRing, NARROW, capacity=n_rows),
        DevicePerTree(jnp.zeros((1, tree_elements), jnp.float32),
                      jnp.zeros((), jnp.float32)),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))))
    return make_megastep_device_per(
        cfg, WRITE_BACK_K, WRITE_BACK_B).lower(*args).compile()


@pytest.mark.parametrize("tree_elements", [2 ** 26, 2 ** 22])
def test_write_back_is_one_scatter_and_an_in_place_dense_rebuild(
        one_chip, tree_elements):
    from d4pg_tpu.replay import device_per as dper

    half = tree_elements // 2
    levels = half.bit_length() - 1
    assert dper.repair_plan(tree_elements, WRITE_BACK_K * WRITE_BACK_B) == (0, levels)
    # levels whose child level is too narrow for the lane form: 1-D slices
    narrow = dper._LANE_FORM_MIN_CHILDREN.bit_length() - 2
    compiled = _per_megastep(one_chip, tree_elements, n_rows=half)
    text = compiled.as_text()
    tree = rf"f32\[(?:1,)?{tree_elements}\]"
    phase = [line for line in text.splitlines()
             if "ph:replay.write_back" in line and " = " in line]
    assert phase, "the write-back lost its scope"
    scatters = [line for line in phase if re.search(r" scatter\(", line)]
    assert len(scatters) == 1 and re.search(rf"= {tree}", scatters[0]), scatters
    assert not [line for line in phase if re.search(r" gather\(", line)]
    # one reduce-window a lane-form level, one in-place update every level
    assert len([line for line in phase if " reduce-window(" in line]) == levels - narrow
    updates = [line for line in phase if re.search(r" dynamic-update-slice\(", line)]
    assert len(updates) == levels
    # no copy of the tree anywhere in the program, and no room for a second
    # one: the largest temporary is the lowest level's lane-padded sums
    assert not re.findall(rf"= {tree}\S* copy\(", text)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= 4 * half + (16 << 20), (
        memory.temp_size_in_bytes, 4 * half)


# ------------------------------------------------------ the PER tree's draw
# ISSUE 33: the top levels of the descent, which every draw of a dispatch
# shares, are read by a fused compare-and-select over the level's static
# slice (``replay/device_per.py:left_by_select``). At the cells' own tree
# sizes the compiled draw phase gathers from the tree once a level below
# ``draw_plan``'s dense top and once more for the drawn leaves, every select
# is a fusion whose result is the draws' ``f32[8192]`` — the ``[2^l, 8192]``
# compare never leaves it — and the program holds no ``tpu_custom_call``.
@pytest.mark.parametrize("tree_elements", [2 ** 26, 2 ** 22])
def test_draw_gathers_only_below_the_dense_top(one_chip, tree_elements):
    from d4pg_tpu.replay import device_per as dper

    draws = WRITE_BACK_K * WRITE_BACK_B
    dense, gather = dper.draw_plan(tree_elements, draws)
    assert dense > 0
    compiled = _per_megastep(one_chip, tree_elements, n_rows=tree_elements // 2)
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    fusions = [line for line in text.splitlines()
               if "ph:replay.draw" in line and " fusion(" in line]
    assert fusions, "the draw lost its scope"
    gathers = [line for line in fusions
               if "kind=kCustom" in line and re.search(rf"= f32\[{draws}\]", line)]
    assert len(gathers) == gather + 1, (len(gathers), gather)
    selects = [line for line in fusions
               if "reduce_sum" in line and re.search(rf"= f32\[{draws}\]", line)]
    assert len(selects) >= dense - 1          # level 0 is one word: a broadcast
    wide = [line for line in fusions if re.search(rf"\[\d+,{draws}\]", line.split(" fusion(")[0])]
    assert not wide, wide[:3]
