"""Seeded inputs, made on the device: the same ``--seed`` gives the same ring,
tree, batch and weights on any layout.

The generator is a counter hash (the murmur3 finaliser over the element's
index, keyed by seed and stream): a handful of elementwise integer
operations on an iota, so XLA fuses a whole field into one pass that writes
its output and nothing else — no temporaries beside a multi-gigabyte ring,
and under a sharded ``out_shardings`` every chip makes its own rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_GOLDEN = 0x9E3779B9


def _mix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def uniform(seed: int, stream: int, shape) -> jax.Array:
    """f32 in [0, 1) with 24 random bits, a pure function of (seed, stream,
    position). Traced: call inside a jit, and pass the seed as an argument
    (a uint32 scalar) so that the program is the same for every seed and
    the persistent compile cache serves every run."""
    shape = tuple(shape)
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for axis in reversed(range(len(shape))):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, axis) * jnp.uint32(
            stride % (1 << 32)
        )
        stride *= shape[axis]
    key = _mix(jnp.asarray(seed, jnp.uint32) * jnp.uint32(_GOLDEN)
               + jnp.uint32((stream * 0x7F4A7C15 + 1) % (1 << 32)))
    bits = _mix(idx ^ key)
    return (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)


def ring_fields(seed, capacity, obs_dim, act_dim, gamma_n, reward_max):
    """The five row fields of a full ring: observations and actions uniform
    in [-1, 1), n-step rewards in [0, reward_max), discount γ^n with one row
    in a hundred terminal."""
    sym = lambda stream, shape: 2.0 * uniform(seed, stream, shape) - 1.0  # noqa: E731
    terminal = uniform(seed, 5, (capacity,)) < 0.01
    return dict(
        obs=sym(1, (capacity, obs_dim)),
        action=sym(2, (capacity, act_dim)),
        reward=reward_max * uniform(seed, 3, (capacity,)),
        next_obs=sym(4, (capacity, obs_dim)),
        discount=jnp.where(terminal, 0.0, jnp.float32(gamma_n)),
    )


def next_pow2(n: int) -> int:
    """Leaves of the tree over an ``n``-row lane."""
    return 1 << max(0, (n - 1).bit_length())


def tree_levels(leaves):
    """``[S, L]`` leaves → the flat ``[S, 2L]`` segment tree the program keeps
    (root at 1, leaves at [L, 2L)): parents are f32 pairwise sums, level by
    level, as ``replay/device_per.py`` repairs them."""
    levels = [leaves]
    while levels[-1].shape[1] > 1:
        child = levels[-1]
        levels.append(child[:, 0::2] + child[:, 1::2])
    pad = jnp.zeros_like(levels[-1])
    return jnp.concatenate([pad] + levels[::-1], axis=1)


def priority_leaves(seed, lanes, lane_capacity, lane_leaves, alpha, eps, p_max):
    """Random α-exponentiated priorities over each lane's filled rows, 2% of
    them zero-mass holes, zeros over the power-of-two padding."""
    shape = (lanes, lane_leaves)
    p = p_max * uniform(seed, 6, shape)
    leaves = (p + eps) ** jnp.float32(alpha)
    hole = uniform(seed, 7, shape) < 0.02
    pad = jax.lax.broadcasted_iota(jnp.int32, shape, 1) >= lane_capacity
    return jnp.where(hole | pad, 0.0, leaves)


def exact_leaves(seed, n_leaves):
    """Leaves whose every partial sum is an integer below 2^24, so an f32
    tree and an f64 cumulative sum hold the same values: small integers,
    thinned so that the expected total is 2^22, with a zero tail. (The
    ``chip_smoke._exact_leaves`` construction, sized to any tree.)"""
    keep = min(0.9, (1 << 22) / (2.0 * n_leaves))
    value = 1.0 + jnp.floor(3.0 * uniform(seed, 8, (n_leaves,)))
    alive = uniform(seed, 9, (n_leaves,)) < keep
    tail = jnp.arange(n_leaves) >= n_leaves - n_leaves // 8
    return jnp.where(alive & ~tail, value, 0.0)


def stratified_prefixes(seed, n, total):
    """``n`` prefix masses, one per equal-mass segment of [0, total)."""
    pre = (jnp.arange(n, dtype=jnp.float32) + uniform(seed, 10, (n,))) * (
        total / jnp.float32(n)
    )
    return jnp.minimum(pre, jnp.nextafter(total, jnp.float32(0.0)))


def batch(seed, n, obs_dim, act_dim, gamma_n, reward_max):
    """One seeded ``[n]`` batch with importance weights in (0.2, 1]."""
    out = ring_fields(seed + 101, n, obs_dim, act_dim, gamma_n, reward_max)
    out["weights"] = 1.0 - 0.8 * uniform(seed, 11, (n,))
    return out


def like(seed, stream, tree, scale_fn):
    """A pytree shaped like ``tree`` whose float leaves are seeded noise:
    leaf ``i`` is ``scale_fn(leaf) * (2u - 1)``. Used for the weights and
    Adam moments of the reference comparison."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for i, leaf in enumerate(leaves):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            out.append(leaf)
            continue
        u = uniform(seed, stream * 1000 + i, leaf.shape)
        out.append((scale_fn(leaf) * (2.0 * u - 1.0)).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def fan_in_scale(leaf):
    """Keeps activations of unit scale through a ReLU stack: ±sqrt(3/fan_in)
    for a kernel ``[in, out]``, ±0.1 for a bias."""
    if leaf.ndim >= 2:
        return float(np.sqrt(3.0 / leaf.shape[-2]))
    return 0.1
