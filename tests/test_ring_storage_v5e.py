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


def _ring_shapes(make, width):
    f32 = lambda *shape: jnp.zeros(shape, jnp.float32)  # noqa: E731
    return jax.eval_shape(lambda: make(
        obs=f32(CAPACITY, width), action=f32(CAPACITY, ACTION),
        reward=f32(CAPACITY), next_obs=f32(CAPACITY, width),
        discount=f32(CAPACITY), size=jnp.zeros((), jnp.int32)))


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
