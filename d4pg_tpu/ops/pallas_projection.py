"""Pallas TPU kernels for the categorical Bellman projection — and the
fully fused projection + cross-entropy loss.

Same math as :func:`d4pg_tpu.ops.categorical_projection` (cites reference
``ddpg.py:122-185``), but as hand-written VMEM-resident kernels using the
gather ("hat function") identity instead of a scatter:

    m[b, i] = Σ_j p[b, j] · max(0, 1 − |bfrac[b, j] − i|)

where ``bfrac`` is the fractional atom index of the Bellman-mapped source
atom. The linear split onto floor/ceil neighbors (including the l == u
fixup) is exactly the triangular hat evaluated at integer dst atoms, so no
scatter/one-hot materialization is needed: the kernel is A source-atom
passes of [TB, A] VPU work per batch tile, everything staged in VMEM once.

Two entry points:

- :func:`categorical_projection_pallas` — drop-in projection Φ only (the
  round-4 kernel, kept as the intermediate rung of the backend ladder).
- :func:`fused_categorical_loss` — the HBM-roofline kernel: projection Φ,
  log-softmax and the cross-entropy / overlap reductions fused into ONE
  kernel, so the projected target distribution ``m`` is NEVER materialized
  in HBM, in either the forward or the backward pass. The XLA path writes
  a [B, A_src, A_dst] one-hot weight tensor plus the [B, A] projection per
  step (≈2.7 MB at the flagship B=256, A=51 — the single largest loss-side
  HBM tensor of the train step; no benchmark cell runs this tier, so what
  it buys on the chip is not measured, PERF.md §7); it reads the four [B, A]/
  [B] inputs and writes two [B] vectors. The backward pass REcomputes Φ in
  VMEM (A passes of VPU work — cheap; the workload is bytes-bound, not
  flops-bound) instead of saving it, so the only residuals are arrays that
  already exist. Gradients flow to ``pred_logits`` only: the target side
  is stop-gradient by construction, exactly as the XLA path stops the
  projection's gradient in ``agent/d4pg.py:train_step``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from d4pg_tpu.ops.categorical import CategoricalSupport

_TILE_B = 128


def _atom_grid(num_atoms):
    """Destination-atom index row [1, A] as f32 (TPU iota is integer-typed)."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, num_atoms), dimension=1).astype(
        jnp.float32
    )


def _project_tile(num_atoms, v_min, v_max, p, r, d):
    """Φ(r + d·z) for one [TB, A] tile, entirely in registers/VMEM.

    ``p`` [TB, A] target probs, ``r``/``d`` [TB, 1]. Returns m [TB, A].
    Shared by the projection-only kernel and both fused-loss kernels so the
    three can never drift apart numerically.
    """
    delta = (v_max - v_min) / (num_atoms - 1)
    col = _atom_grid(num_atoms)
    z = v_min + col * delta
    tz = jnp.clip(r + d * z, v_min, v_max)  # [TB, A]
    bfrac = (tz - v_min) / delta            # [TB, A]
    acc = jnp.zeros_like(p)
    for j in range(num_atoms):
        # contribution of source atom j to every dst atom (hat function)
        w = jnp.maximum(0.0, 1.0 - jnp.abs(bfrac[:, j : j + 1] - col))  # [TB, A]
        acc = acc + p[:, j : j + 1] * w
    return acc


def _projection_kernel(num_atoms, v_min, v_max, p_ref, r_ref, d_ref, out_ref):
    out_ref[:] = _project_tile(
        num_atoms, v_min, v_max, p_ref[:], r_ref[:], d_ref[:]
    )


def _pad_batch(arrs_2d, arrs_1d):
    """Pad batch to the 128-row tile; returns (padded_B, 2d list, 1d list)."""
    B = arrs_2d[0].shape[0]
    padded = pl.cdiv(B, _TILE_B) * _TILE_B
    if padded != B:
        pad = padded - B
        arrs_2d = [jnp.pad(a, ((0, pad), (0, 0))) for a in arrs_2d]
        arrs_1d = [jnp.pad(a, (0, pad)) for a in arrs_1d]
    return padded, arrs_2d, arrs_1d


@functools.partial(jax.jit, static_argnums=(0, 4))
def categorical_projection_pallas(
    support: CategoricalSupport,
    target_probs: jax.Array,
    rewards: jax.Array,
    discounts: jax.Array,
    interpret: bool = False,
) -> jax.Array:
    """Drop-in replacement for :func:`categorical_projection` on TPU.

    ``interpret=True`` runs the kernel in the Pallas interpreter (for CPU
    tests). Batch is padded to the 128-row tile internally.
    """
    B, A = target_probs.shape
    padded, (target_probs,), (rewards, discounts) = _pad_batch(
        [target_probs], [rewards, discounts]
    )
    r2 = rewards[:, None].astype(jnp.float32)
    d2 = discounts[:, None].astype(jnp.float32)
    kernel = functools.partial(
        _projection_kernel, A, support.v_min, support.v_max
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((padded, A), jnp.float32),
        grid=(padded // _TILE_B,),
        in_specs=[
            pl.BlockSpec((_TILE_B, A), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_TILE_B, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_TILE_B, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (_TILE_B, A), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(target_probs.astype(jnp.float32), r2, d2)
    return out[:B]


# --------------------------------------------------------------------------
# Fused projection + loss


def _log_softmax_tile(logits):
    """Numerically stable log-softmax over the atom (lane) axis of a tile."""
    mx = jnp.max(logits, axis=-1, keepdims=True)
    shifted = logits - mx
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
    return shifted - lse


def loss_tile(num_atoms, v_min, v_max, q, p, r, d):
    """Φ + log-softmax CE + overlap surrogate for one [TB, A] tile, m never
    leaving VMEM — the loss body shared VERBATIM by the fused-loss kernel
    and the fused loss+descent kernel (``ops/pallas_fused_step.py``), the
    same no-drift discipline as ``_project_tile``.

    Returns per-sample columns:
      ce[b]  = −Σ_i m[b,i]·log_softmax(q)[b,i]   (loss term AND "ce" priority)
      ov[b]  = |−Σ_i m[b,i]·softmax(q)[b,i]|     ("overlap" priority surrogate,
                reference ddpg.py:220-222)
    """
    m = _project_tile(num_atoms, v_min, v_max, p, r, d)
    logp = _log_softmax_tile(q)
    ce = -jnp.sum(m * logp, axis=-1, keepdims=True)
    ov = jnp.abs(-jnp.sum(m * jnp.exp(logp), axis=-1, keepdims=True))
    return ce, ov


def _fused_loss_kernel(
    num_atoms, v_min, v_max, q_ref, p_ref, r_ref, d_ref, ce_ref, ov_ref
):
    """Forward: see :func:`loss_tile`."""
    ce_ref[:], ov_ref[:] = loss_tile(
        num_atoms, v_min, v_max, q_ref[:], p_ref[:], r_ref[:], d_ref[:]
    )


def _fused_loss_grad_kernel(
    num_atoms, v_min, v_max, q_ref, p_ref, r_ref, d_ref, gce_ref, gov_ref,
    dq_ref,
):
    """Backward for BOTH outputs, with Φ REcomputed in VMEM:

        dce/dq = softmax(q)·Σ_i m_i − m
        dov/dq = sign(Σ_i m_i·softmax(q)_i) · softmax(q)·(m − Σ_i m_i·softmax(q)_i)

    (ov = |−Σ m·softmax(q)|; with the projection's nonnegative m the sign
    factor is 1, but it is computed so the VJP stays exact for arbitrary
    test inputs.) Recomputation (A VPU passes) trades a [B, A] HBM
    round-trip of saved residuals for arithmetic the memory-bound step has
    headroom for; the only reads are the same inputs the forward read.
    Σ_i m_i is 1 for a normalized target, but is computed rather than
    assumed so the gradient matches the XLA oracle even for unnormalized
    test inputs.
    """
    m = _project_tile(num_atoms, v_min, v_max, p_ref[:], r_ref[:], d_ref[:])
    sm = jnp.exp(_log_softmax_tile(q_ref[:]))
    msum = jnp.sum(m, axis=-1, keepdims=True)
    dot = jnp.sum(m * sm, axis=-1, keepdims=True)
    dq_ref[:] = gce_ref[:] * (sm * msum - m) + gov_ref[:] * jnp.sign(dot) * sm * (
        m - dot
    )


def _fused_call(support, interpret, kernel_fn, n_out, pred_logits,
                target_probs, rewards, discounts, extra_cols=()):
    """Shared pallas_call plumbing for the fused forward/backward kernels.

    ``extra_cols`` are additional [B] per-sample inputs fed as [TB, 1]
    columns (the backward pass's incoming cotangent). Returns ``n_out``
    arrays sliced back to the true batch.
    """
    B, A = target_probs.shape
    padded, (pred_logits, target_probs), ones = _pad_batch(
        [pred_logits, target_probs], [rewards, discounts, *extra_cols]
    )
    cols = [a[:, None].astype(jnp.float32) for a in ones]
    kernel = functools.partial(kernel_fn, A, support.v_min, support.v_max)
    row_spec = pl.BlockSpec((_TILE_B, A), lambda i: (i, 0), memory_space=pltpu.VMEM)
    col_spec = pl.BlockSpec((_TILE_B, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)
    out_shapes = [
        jax.ShapeDtypeStruct((padded, A if n == A else 1), jnp.float32)
        for n in n_out
    ]
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        grid=(padded // _TILE_B,),
        in_specs=[row_spec, row_spec] + [col_spec] * len(cols),
        out_specs=[row_spec if n == A else col_spec for n in n_out],
        interpret=interpret,
    )(pred_logits.astype(jnp.float32), target_probs.astype(jnp.float32), *cols)
    return [
        (o[:B, 0] if o.shape[-1] == 1 else o[:B]) for o in outs
    ]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_loss(support, interpret, pred_logits, target_probs, rewards, discounts):
    ce, ov = _fused_call(
        support, interpret, _fused_loss_kernel, (1, 1),
        pred_logits, target_probs, rewards, discounts,
    )
    return ce, ov


def _fused_loss_fwd(support, interpret, pred_logits, target_probs, rewards, discounts):
    out = _fused_loss(support, interpret, pred_logits, target_probs, rewards, discounts)
    # Residuals are all pre-existing arrays — nothing projection-sized is
    # saved; the backward kernel recomputes Φ in VMEM.
    return out, (pred_logits, target_probs, rewards, discounts)


def _fused_loss_bwd(support, interpret, residuals, cotangents):
    pred_logits, target_probs, rewards, discounts = residuals
    g_ce, g_ov = cotangents
    # Both outputs carry a real VJP (in the train step ov is a
    # value_and_grad aux, so g_ov is structurally zero there — but a
    # caller differentiating an overlap-based term gets the exact
    # gradient, not a silent zero). The target side (probs/rewards/
    # discounts) is stop-gradient by construction, matching the XLA path.
    _, A = target_probs.shape
    (dq,) = _fused_call(
        support, interpret, _fused_loss_grad_kernel, (A,),
        pred_logits, target_probs, rewards, discounts,
        extra_cols=(g_ce, g_ov),
    )
    return dq, None, None, None


_fused_loss.defvjp(_fused_loss_fwd, _fused_loss_bwd)


def fused_categorical_loss(
    support: CategoricalSupport,
    pred_logits: jax.Array,
    target_probs: jax.Array,
    rewards: jax.Array,
    discounts: jax.Array,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused Φ-projection + categorical cross-entropy, per sample.

    Equivalent to::

        m  = stop_gradient(categorical_projection(support, target_probs,
                                                  rewards, discounts))
        ce = -sum(m * log_softmax(pred_logits), -1)        # per-sample CE
        ov = abs(-sum(m * softmax(pred_logits), -1))       # overlap surrogate

    but the projected distribution ``m`` never touches HBM (see module
    docstring). Both outputs are differentiable w.r.t. ``pred_logits``
    (the target side is stop-gradient by construction). IS-weighted
    reduction stays outside — a [B] dot is byte-trivial and the unweighted
    per-sample CE doubles as the PER priority.

    Returns:
      (ce [B], overlap [B]) — both float32.
    """
    return _fused_loss(
        support, bool(interpret), pred_logits, target_probs, rewards, discounts
    )
