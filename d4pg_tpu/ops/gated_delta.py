"""The gated delta rule over a window: a state along the time axis.

One head holds a state ``S ∈ R^{dk×dv}``, ``S₀ = 0``, and a token does

    S ← e^{g_t} S                                  decay (g_t ≤ 0)
    S ← S + k_t ⊗ β_t (v_t − Sᵀ k_t)               the delta-rule write
    o_t = Sᵀ q_t                                   the read

(Gated DeltaNet, arXiv 2412.06464; ``q`` and ``k`` arrive normalised and
``q`` scaled.) Two forms of the same function on ``q, k [B, T, H, dk]``,
``v [B, T, H, dv]``, ``g, beta [B, T, H]`` → ``o [B, T, H, dv]``:

``gated_delta_recurrent``  the three lines above, token by token — the
    oracle: T sequential steps of rank-one work.
``gated_delta_chunked``    the window in chunks of ``chunk`` tokens. Inside
    a chunk the writes are untangled at once: with ``γ`` the chunk's
    cumulative log-decays and ``L = strict_tril(β k kᵀ ⊙ e^{γ_i − γ_j})``,
    ``(I + L)⁻¹`` turns values and keys into what each token writes given
    the state the chunk began with; a ``lax.scan`` over the chunks carries
    ``S`` and does matrix products only. XLA tier: no kernel (PERF.md
    section 7 has what a fused one would save).

``unit_lower_inverse``  ``(I + L)⁻¹`` in blocks, by the system's size alone
    (``inverse_plan``): where ``chunk = 16 · 2^m`` the 16 × 16 diagonal
    blocks are inverted by forward substitution — 16 sequential row steps
    over the blocks alone, every system and block at once — and joined two
    and two, ``[[A, 0], [M, B]]⁻¹ = [[A⁻¹, 0], [−B⁻¹ M A⁻¹, B⁻¹]]``, 16 → 32
    → 64: two products a level; any other size runs the row form over the
    whole system (``inverse_by_rows``: ``chunk`` steps over all of it, 17.9
    ms against 2.1 at the cell's 4,096 systems of 64 × 64, PERF.md section
    6, PR 36). Not the product form ``(I − L)(I + L²)(I + L⁴)…``: the powers
    of a non-normal nilpotent ``L`` grow before they cancel, and on
    strongly correlated keys with β ≈ 0.95 — adjacent rows of one stream —
    it reads an error of 3e+10 of the inverse's scale where both forms
    here read 3e-7 (``tests/test_torso_linear.py`` holds the case).

``matmul_precision``: the Gram product ``β k kᵀ``, the inversion (its row
steps elementwise, its joins' products) and the inverse's backward pass
are float32 at ``highest`` whatever the program's default — a ``chunk ×
chunk`` system a head whose error every later token of the chunk inherits,
under 1% of the layer's FLOPs. The decays are elementwise float32. Every
other product (the inverse applied, the scan's) runs at the caller's
precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from d4pg_tpu.utils.profiling import phase

HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_recurrent(q, k, v, g, beta):
    """Token by token; ``(o [B, T, H, dv], S [B, H, dk, dv])``."""
    b, _, h, dk = q.shape

    def step(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        held = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - held))
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    time_major = [jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)]
    state, out = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), q.dtype), time_major)
    return jnp.moveaxis(out, 0, 1), state


BLOCK = 16      # rows of a diagonal block: the only sequential steps of a blocked inverse


def inverse_plan(c: int) -> tuple[int, int]:
    """``(block, join_levels)`` for a ``c × c`` system, from its size alone:
    ``(16, m)`` where ``c = 16 · 2^m`` with ``m ≥ 1`` (64 → ``(16, 2)``), and
    ``(c, 0)`` — one block, the row form — for every other ``c``."""
    blocks = c // BLOCK
    if blocks < 2 or c % BLOCK or blocks & (blocks - 1):
        return c, 0
    return BLOCK, blocks.bit_length() - 1


def inverse_by_rows(lower):
    """``(I + L)⁻¹`` a row at a time: ``C`` sequential steps, each a
    multiply-reduce over every system's whole inverse-so-far."""
    c = lower.shape[-1]
    eye = jnp.eye(c, dtype=lower.dtype)

    def row(i, inverse):
        l_i = jax.lax.dynamic_index_in_dim(lower, i, axis=-2, keepdims=False)
        x_i = eye[i] - jnp.sum(l_i[..., :, None] * inverse, axis=-2)   # rows ≥ i are still 0
        return jax.lax.dynamic_update_index_in_dim(inverse, x_i, i, axis=-2)

    return jax.lax.fori_loop(0, c, row, jnp.zeros_like(lower))


def diagonal_blocks(lower, block: int):
    """``[..., C, C]`` → its ``C / block`` diagonal blocks ``[..., n, b, b]``,
    by plain slices (a reshape to ``[n, b, n, b]`` makes XLA lay the whole of
    ``L`` out a second time, PERF.md section 6, PR 36)."""
    return jnp.stack([lower[..., j:j + block, j:j + block]
                      for j in range(0, lower.shape[-1], block)], axis=-3)


def join_inverses(inverses, lower):
    """One level of ``[[A, 0], [M, B]]⁻¹ = [[A⁻¹, 0], [−B⁻¹ M A⁻¹, B⁻¹]]``:
    ``inverses [..., 2n, s, s]`` of the diagonal blocks of ``lower
    [..., 2ns, 2ns]`` → ``[..., n, 2s, 2s]``, two products at ``highest``."""
    n, s = inverses.shape[-3] // 2, inverses.shape[-1]
    pairs = inverses.reshape(inverses.shape[:-3] + (n, 2, s, s))
    a, b = pairs[..., 0, :, :], pairs[..., 1, :, :]
    m = jnp.stack([lower[..., (2 * p + 1) * s:(2 * p + 2) * s, 2 * p * s:(2 * p + 1) * s]
                   for p in range(n)], axis=-3)
    corner = -jnp.matmul(jnp.matmul(b, m, precision=HIGHEST), a, precision=HIGHEST)
    top = jnp.concatenate([a, jnp.zeros_like(a)], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([corner, b], axis=-1)], axis=-2)


@jax.custom_vjp
def unit_lower_inverse(lower):
    """``(I + L)⁻¹`` for strictly lower-triangular ``L [..., C, C]`` in exact
    float32 multiply-adds, every system at once, by ``inverse_plan(C)``: the
    diagonal blocks by forward substitution, then joined two and two. The
    steps are not differentiated: the backward pass is two products with
    the inverse."""
    block, levels = inverse_plan(lower.shape[-1])
    with phase("agent.linear_attention.solve"):
        if not levels:
            return inverse_by_rows(lower)
        inverses = inverse_by_rows(diagonal_blocks(lower, block))
        for _ in range(levels):
            inverses = join_inverses(inverses, lower)
        return inverses[..., 0, :, :]


def _inverse_fwd(lower):
    inverse = unit_lower_inverse(lower)
    return inverse, inverse


def _inverse_bwd(inverse, d_inverse):
    # X = (I + L)⁻¹: dX = −X dL X, so L̄ = −Xᵀ X̄ Xᵀ on the strict lower triangle
    t = jnp.swapaxes(inverse, -1, -2)
    d = -jnp.matmul(jnp.matmul(t, d_inverse, precision=HIGHEST), t, precision=HIGHEST)
    return (jnp.tril(d, -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def gated_delta_chunked(q, k, v, g, beta, chunk: int = 64):
    """The same ``(o, S)`` in chunks of ``chunk`` tokens (``T`` a multiple
    of it); the scan over chunks is the only sequential part."""
    b, t, h, dk = q.shape
    if t % chunk:
        raise ValueError(f"window {t} is not whole chunks of {chunk}")
    n = t // chunk
    # [n, B, H, C, ·]: the scan runs over the leading axis
    cut = lambda x: jnp.moveaxis(  # noqa: E731
        x.reshape((b, n, chunk, h) + x.shape[3:]), (1, 3), (0, 2))
    q, k, v, g, beta = (cut(x) for x in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=-1)                                # [n, B, H, C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # e^{γ_i − γ_j} for i ≥ j, 0 above: every exponent is ≤ 0
    decay = jnp.exp(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    k_beta = k * beta[..., None]
    gram = jnp.einsum("...ik,...jk->...ij", k_beta, k, precision=HIGHEST)
    solve = unit_lower_inverse(jnp.tril(gram * decay, -1))         # (I + L)⁻¹
    # what each token writes if the chunk began at S = 0, what the state it
    # did begin with takes off that, and q_i·k_j e^{γ_i − γ_j} for j ≤ i
    writes = solve @ (v * beta[..., None])
    reads = solve @ (k_beta * jnp.exp(gamma)[..., None])
    within = jnp.einsum("...ik,...jk->...ij", q, k) * decay
    q_in = q * jnp.exp(gamma)[..., None]
    k_out = k * jnp.exp(gamma[..., -1:] - gamma)[..., None]
    last = jnp.exp(gamma[..., -1])[..., None, None]

    def step(state, x):
        writes_i, reads_i, within_i, q_i, k_i, last_i = x
        new = writes_i - reads_i @ state                            # [B, H, C, dv]
        out = q_i @ state + within_i @ new
        state = state * last_i + jnp.swapaxes(k_i, -1, -2) @ new
        return state, out

    with phase("agent.linear_attention.scan"):
        state, out = jax.lax.scan(
            step, jnp.zeros((b, h, dk, v.shape[-1]), q.dtype),
            (writes, reads, within, q_in, k_out, last))
    return jnp.moveaxis(out, (0, 2), (1, 3)).reshape(b, t, h, -1), state
