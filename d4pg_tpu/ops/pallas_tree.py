"""Pallas TPU kernel for the device-PER stratified descent.

The XLA reference (``replay/device_per.py:descend_prefix``) walks the
segment tree level by level: log2(L) dependent gathers of [n] dynamic
indices per dispatch — correct, but every level is a scattered HBM/VMEM
gather the VPU cannot coalesce. This kernel runs THE SAME walk — the same
node sums, the same ``prefix >= left`` comparison, the same f32
subtraction, level by level — and replaces only the gather: a draw's left
child sum is picked out of its level by a one-hot compare-and-select over
that level's nodes, then lane-reduced. Exactly one term of that reduction
is nonzero, so the picked value is the node's own bits and the returned
leaf is the XLA descent's leaf for every draw, on any priorities
(``tests/test_device_per.py`` pins it on CPU, ``chip_smoke.py`` on the chip
at the flagship ring's 2^20 leaves of random f32 priorities).

Only left children are ever read, and the left child of node ``i`` is
``sums[2i]``: the even half of the flat tree, ``sums[0::2]``, indexed by
the parent's id. That ``[L]`` array is laid out dense as ``[L/128, 128]``
f32 and stays resident in VMEM, single-buffered, for the whole grid (4 MiB
at L = 2^20 — a ``[1, L]`` row would pad every 128 entries to a full
8-sublane tile, 32 MiB). Level ``l`` owns ids ``[2^l, 2^(l+1))``: levels
0..9 sit in the first ``[8, 128]`` vreg, every deeper level is a run of
whole vregs swept by a ``fori_loop``. Per 128-draw tile the sweep touches
each of the L entries once — compare, select, add on a ``[128, 128]`` tile
— which is VPU work only: no matmul (so no MXU pass precision to get
wrong), no dynamic gather, no vector→scalar carry.

Selectable via ``TrainConfig.device_tree_backend="pallas"``; the XLA
descent stays the shipping default and the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE_D = 128   # draws per grid step
_BLOCK_L = 128  # node sums per row (the lane axis)
_ROWS = 8       # rows loaded per inner iteration: one f32 vreg


def left_rows(sums_lane: jax.Array) -> jax.Array:
    """One lane's flat ``[2L]`` tree → the kernel's operand: the left-child
    sums ``sums[0::2]`` (entry ``i`` is the left child of node ``i``) as a
    dense ``[rows, 128]`` f32 array of whole ``[8, 128]`` vregs."""
    lefts = sums_lane[0::2].astype(jnp.float32)
    n = lefts.shape[0]
    rows = pl.cdiv(n, _ROWS * _BLOCK_L) * _ROWS
    return jnp.pad(lefts, (0, rows * _BLOCK_L - n)).reshape(rows, _BLOCK_L)


def tree_depth(sums_lane: jax.Array) -> int:
    """log2(L) of a flat ``[2L]`` tree lane (static)."""
    return (sums_lane.shape[0] // 2).bit_length() - 1


def vmem_limit_bytes(n_rows: int) -> int:
    """Scoped-VMEM request for a kernel holding ``[n_rows, 128]`` f32 node
    sums: the resident block (counted twice — Mosaic may still
    double-buffer it) plus headroom for the draw/loss tiles and spills."""
    return 2 * n_rows * _BLOCK_L * 4 + (16 << 20)


def _pick_rows(lefts_ref, sel, row0, n_rows):
    """``picked[d, :]`` = row ``sel``-matched entries of rows
    ``[row0, row0 + n_rows)``: zero everywhere except the one lane of the
    one row whose id ``sel[d, lane]`` names. ``row0``/``n_rows`` are
    multiples of 8."""

    def body(g, picked):
        base = pl.multiple_of(row0 + g * _ROWS, _ROWS)
        tile = lefts_ref[pl.ds(base, _ROWS), :]
        for r in range(_ROWS):
            picked = picked + jnp.where(sel == base + r, tile[r : r + 1, :], 0.0)
        return picked

    return jax.lax.fori_loop(
        0, n_rows // _ROWS, body, jnp.zeros(sel.shape, jnp.float32)
    )


def descend_tile(depth, lefts_ref, pref):
    """The tree descent for one tile of draws — the body shared VERBATIM by
    the standalone descent kernel and the fused loss+descent kernel
    (``ops/pallas_fused_step.py``), so the two tiers can never drift.

    ``lefts_ref`` [rows, 128] f32 VMEM ref (:func:`left_rows`), ``pref``
    [TD, 1] f32 tile. Returns [TD, 1] int32 leaf indices in ``[0, 2^depth)``
    — ``descend_prefix``'s, step for step."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (pref.shape[0], _BLOCK_L), 1)
    head = lefts_ref[0:_ROWS, :]  # levels 0..9
    node = jnp.ones(pref.shape, jnp.int32)
    for level in range(depth):
        row0 = (1 << level) // _BLOCK_L  # first row of this level's ids
        n_rows = max(row0, 1)
        # sel[d, lane] = the row holding draw d's node, on that node's lane
        sel = jnp.where(
            lane == (node & (_BLOCK_L - 1)), jnp.right_shift(node, 7), -1
        )
        if n_rows < _ROWS:
            picked = jnp.zeros(sel.shape, jnp.float32)
            for r in range(row0, row0 + n_rows):
                picked = picked + jnp.where(sel == r, head[r : r + 1, :], 0.0)
        else:
            picked = _pick_rows(lefts_ref, sel, row0, n_rows)
        # one nonzero term: the sum IS sums[2 * node], bit for bit
        left = jnp.sum(picked, axis=1, keepdims=True)
        go_right = pref >= left
        pref = pref - jnp.where(go_right, left, 0.0)
        node = 2 * node + jnp.where(go_right, 1, 0)
    return node - (1 << depth)


def _descend_kernel(depth, lefts_ref, pref_ref, out_ref):
    """Standalone descent kernel: ``lefts_ref`` [rows, 128] f32,
    ``pref_ref`` [TILE_D, 1] f32, ``out_ref`` [TILE_D, 1] i32."""
    out_ref[:] = descend_tile(depth, lefts_ref, pref_ref[:])


@functools.partial(jax.jit, static_argnums=(2,))
def find_prefix_pallas(
    sums_lane: jax.Array, prefixes: jax.Array, interpret: bool = False
) -> jax.Array:
    """Drop-in for :func:`~d4pg_tpu.replay.device_per.descend_prefix`:
    ``sums_lane`` [2L] f32 (one lane's flat tree), ``prefixes`` any shape
    f32 → int32 leaf indices of the same shape. ``interpret=True`` runs the
    Pallas interpreter (CPU tests). Draws are zero-padded to whole tiles
    internally and sliced off."""
    shape = prefixes.shape
    flat = prefixes.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    npad = pl.cdiv(n, _TILE_D) * _TILE_D
    lefts = left_rows(sums_lane)
    n_rows = lefts.shape[0]
    pref2 = jnp.pad(flat, (0, npad - n))[:, None]
    kernel = functools.partial(_descend_kernel, tree_depth(sums_lane))
    idx = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((npad, 1), jnp.int32),
        grid=(npad // _TILE_D,),
        in_specs=[
            pl.BlockSpec(
                (n_rows, _BLOCK_L), lambda i: (0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (_TILE_D, 1), lambda i: (i, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (_TILE_D, 1), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit_bytes(n_rows),
        ),
        interpret=interpret,
    )(lefts, pref2)
    return idx[:n, 0].reshape(shape)
