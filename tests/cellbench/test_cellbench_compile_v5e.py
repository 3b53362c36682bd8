"""Every cell's megastep compiles at its real shapes for a *described* TPU
v5e 2x2 — the compiler the chip would run, no chip attached — so a shape,
a layout or a size that stops compiling (or stops fitting a chip's 16 GB) is
caught here, at no chip time. One file on purpose, and the topology is
described inside a fixture, never at import (the on-chip-measurement guide,
section 2: only one process may hold the TPU library)."""

from __future__ import annotations

import json
import os
import re

import jax
import pytest

from cellbench import manifest as mf

with open(mf.DEFAULT_MANIFEST) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent cache but
    # cannot be read back without a chip: keep these out of it.
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.mark.parametrize("name", CELLS)
def test_megastep_compiles_for_v5e(topo, name):
    from cellbench.drivers import Job, learner, resolve_config

    manifest, root = mf.load()
    cell = mf.cell(manifest, root, name)
    devices = list(topo.devices)[: cell.chips]
    job = Job(cell, 0, 10.0, False, False, devices, print)
    cfg = resolve_config(job)
    # A trainer cell builds the same program from the same factory with
    # the same shapes (Trainer.__init__), so the learner's plan stands for it.
    compiled = learner.compile_for(job, cfg, devices)
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert 0.25 * HBM_BYTES <= held <= 0.95 * HBM_BYTES, (
        f"{name}: the megastep holds {held / 1e9:.2f} GB per chip; a cell "
        "should fill a quarter of a chip and must fit it")
    text = compiled.as_text()
    collectives = len(re.findall(r" all-(reduce|gather)", text))
    assert (collectives > 0) == (cell.chips > 1), (name, collectives)
    assert "tpu_custom_call" not in text      # these are XLA-tier cells
