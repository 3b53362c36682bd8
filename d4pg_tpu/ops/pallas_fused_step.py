"""The fused-tier Pallas kernel: categorical loss + NEXT-step tree descent
in ONE program per scan step (ISSUE 16).

The device-PER megastep's Pallas tier used to run two programs per
dispatch on the loss-side critical path: ``ops/pallas_tree.py``'s descent
over the whole [K, B] prefix block, then K fused-loss programs inside the
scan. The descent's data dependency (descent → idx → gather → forward →
loss) forbids fusing a step's OWN descent into its loss — but the tree is
constant for the whole scan (priorities write back post-scan, last-wins),
so every step's prefixes are known up front and the descents are
order-independent. That makes the classic software-pipelining move legal:
the step-``t`` loss program also computes the descent for step
``t+1``'s prefixes, with one small prologue descent
(:func:`~d4pg_tpu.ops.pallas_tree.find_prefix_pallas`) covering step 0.
Steady state then runs ONE Pallas program per scan step — the tree's node
sums ride the same VMEM residency as the loss tiles instead of paying
their own kernel launch + HBM sweep.

Byte-parity with the separate-programs oracle is by construction, not by
tolerance: the loss tile is :func:`~d4pg_tpu.ops.pallas_projection
.loss_tile` and the descent tile is :func:`~d4pg_tpu.ops.pallas_tree
.descend_tile` — the literal functions the separate kernels run — on
identical inputs (same tree, same prefix values, same grid tiling), and
the descent output is exact int32. ``tests/test_fused_descent.py`` pins
the whole-TrainState equality across multi-dispatch runs.

The backward pass is unchanged from the fused-loss kernel: the VJP
recomputes Φ in VMEM via the SAME ``_fused_loss_grad_kernel`` program
(descent has no gradient — the index output's cotangent is structurally
zero), so gradients are bit-identical to the non-descent fused tier.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from d4pg_tpu.ops.categorical import CategoricalSupport
from d4pg_tpu.ops.pallas_projection import (
    _TILE_B,
    _fused_call,
    _fused_loss_grad_kernel,
    _pad_batch,
    loss_tile,
)
from d4pg_tpu.ops.pallas_tree import (
    _BLOCK_L,
    descend_tile,
    left_rows,
    tree_depth,
    vmem_limit_bytes,
)


def _fused_step_kernel(
    num_atoms, v_min, v_max, depth,
    q_ref, p_ref, r_ref, d_ref, pref_ref, lefts_ref,
    ce_ref, ov_ref, idx_ref,
):
    """One [TILE_B] batch tile: loss for THIS step + descent for the NEXT.

    ``q_ref``/``p_ref`` [TB, A], ``r_ref``/``d_ref``/``pref_ref`` [TB, 1],
    ``lefts_ref`` [rows, 128] (the tree's left-child sums, VMEM-resident
    across the grid), outputs ce/ov [TB, 1] f32 and idx [TB, 1] i32 (leaf
    indices)."""
    ce_ref[:], ov_ref[:] = loss_tile(
        num_atoms, v_min, v_max, q_ref[:], p_ref[:], r_ref[:], d_ref[:]
    )
    idx_ref[:] = descend_tile(depth, lefts_ref, pref_ref[:])


def _fused_step_call(support, interpret, pred_logits, target_probs,
                     rewards, discounts, next_prefixes, sums_lane):
    B, A = target_probs.shape
    padded, (pred_logits, target_probs), cols1d = _pad_batch(
        [pred_logits, target_probs], [rewards, discounts, next_prefixes]
    )
    cols = [a[:, None].astype(jnp.float32) for a in cols1d]
    lefts = left_rows(sums_lane)
    n_rows = lefts.shape[0]
    kernel = functools.partial(
        _fused_step_kernel, A, support.v_min, support.v_max,
        tree_depth(sums_lane),
    )
    row_spec = pl.BlockSpec((_TILE_B, A), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    col_spec = pl.BlockSpec((_TILE_B, 1), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    tree_spec = pl.BlockSpec((n_rows, _BLOCK_L), lambda i: (0, 0),
                             memory_space=pltpu.VMEM)
    ce, ov, idx = pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((padded, 1), jnp.float32),
            jax.ShapeDtypeStruct((padded, 1), jnp.float32),
            jax.ShapeDtypeStruct((padded, 1), jnp.int32),
        ],
        grid=(padded // _TILE_B,),
        in_specs=[row_spec, row_spec] + [col_spec] * 3 + [tree_spec],
        out_specs=[col_spec, col_spec, col_spec],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit_bytes(n_rows),
        ),
        interpret=interpret,
    )(pred_logits.astype(jnp.float32), target_probs.astype(jnp.float32),
      *cols, lefts)
    return ce[:B, 0], ov[:B, 0], idx[:B, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_step(support, interpret, pred_logits, target_probs, rewards,
                discounts, next_prefixes, sums_lane):
    return _fused_step_call(
        support, interpret, pred_logits, target_probs, rewards, discounts,
        next_prefixes, sums_lane,
    )


def _fused_step_fwd(support, interpret, pred_logits, target_probs, rewards,
                    discounts, next_prefixes, sums_lane):
    out = _fused_step(support, interpret, pred_logits, target_probs,
                      rewards, discounts, next_prefixes, sums_lane)
    # Residuals are all pre-existing arrays (the fused-loss discipline):
    # the backward kernel recomputes Φ in VMEM and never needs the tree.
    return out, (pred_logits, target_probs, rewards, discounts)


def _fused_step_bwd(support, interpret, residuals, cotangents):
    pred_logits, target_probs, rewards, discounts = residuals
    g_ce, g_ov, _g_idx = cotangents  # idx is int32: cotangent structurally 0
    _, A = target_probs.shape
    # The EXACT backward program of the non-descent fused tier
    # (_fused_loss_grad_kernel) — gradients are bit-identical between the
    # two tiers by sharing it. Prefixes/tree take no gradient: the draw
    # is sampling, not a differentiable path (matching stop_gradient on
    # the target side).
    (dq,) = _fused_call(
        support, interpret, _fused_loss_grad_kernel, (A,),
        pred_logits, target_probs, rewards, discounts,
        extra_cols=(g_ce, g_ov),
    )
    return dq, None, None, None, None, None


_fused_step.defvjp(_fused_step_fwd, _fused_step_bwd)


def fused_categorical_loss_descent(
    support: CategoricalSupport,
    pred_logits: jax.Array,
    target_probs: jax.Array,
    rewards: jax.Array,
    discounts: jax.Array,
    next_prefixes: jax.Array,
    sums_lane: jax.Array,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused Φ-projection + CE loss for THIS scan step, plus the segment-
    tree descent for the NEXT step's stratified prefixes — one Pallas
    program (see module docstring for the pipelining argument).

    Loss outputs are exactly :func:`~d4pg_tpu.ops.pallas_projection
    .fused_categorical_loss`'s; the descent output is exactly
    ``find_prefix_pallas(sums_lane, next_prefixes)`` over one lane's flat
    ``[2L]`` tree (the caller applies ``lane_draw``'s fill clamp on top,
    like the megastep body does for the standalone kernel).

    Returns:
      (ce [B] f32, overlap [B] f32, next_idx [B] int32).
    """
    return _fused_step(
        support, bool(interpret), pred_logits, target_probs, rewards,
        discounts, next_prefixes, sums_lane,
    )
