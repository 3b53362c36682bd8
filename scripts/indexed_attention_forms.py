"""Time, on the device, the forms the indexed torso could take at the shape
it meets them — one query chunk ``[rows]`` against ``[keys]`` keys, k =
``index_topk``: four exact forms of "the k-th largest of each row" of the
float32 index scores, and two forms of the attention over the chosen keys
(masked: dense scores with the keys outside the choice at MASKED, no row
moved; gathered: the k chosen key and value rows read per query). PERF.md
section 6 (PR 31) has what each cost on the v5e; ``models/torso.py`` keeps
the forms that won.

    chiprun -- python scripts/indexed_attention_forms.py [rows] [keys] [k]
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp

from d4pg_tpu.models.torso import kth_largest


def by_sort(scores, k):
    return jnp.sort(scores, axis=-1)[..., scores.shape[-1] - k]


def by_top_k(scores, k):
    return jax.lax.top_k(scores, k)[0][..., k - 1]


def by_radix16(scores, k):
    """Four bits a pass: 8 passes, 15 thresholds each."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    sign = jnp.uint32(0x80000000)
    order = jnp.where(bits >= sign, ~bits, bits | sign)
    digits = jnp.arange(1, 16, dtype=jnp.uint32)

    def body(i, prefix):
        shift = (28 - 4 * i).astype(jnp.uint32)
        trials = prefix[..., None] | (digits << shift)                       # [..., 15]
        counts = jnp.sum(order[..., None, :] >= trials[..., None], axis=-1, dtype=jnp.int32)
        digit = jnp.sum(counts >= k, axis=-1).astype(jnp.uint32)             # monotone in the digit
        return prefix | (digit << shift)

    kth = jax.lax.fori_loop(0, 8, body, jnp.zeros(scores.shape[:-1], jnp.uint32))
    return jax.lax.bitcast_convert_type(jnp.where(kth >= sign, kth & ~sign, ~kth), jnp.float32)


FORMS = {"radix_select_32_passes": kth_largest, "radix_select_8_passes_of_4_bits": by_radix16,
         "row_sort": by_sort, "lax_top_k": by_top_k}


def masked_attention(q, k, v, member):
    """``q [T, G, R, d]``, ``k``/``v [S, G, d]``, ``member [T, S]`` bool."""
    logits = jnp.einsum("tgrd,sgd->grts", q, k) / q.shape[-1] ** 0.5
    probs = jax.nn.softmax(jnp.where(member[None, None], logits, -1e30), axis=-1)
    return jnp.einsum("grts,sgd->tgrd", probs, v)


def gathered_attention(q, k, v, chosen):
    """The same over ``chosen [T, k]`` int32: the chosen rows are read."""
    k_rows, v_rows = k[chosen], v[chosen]                                    # [T, k, G, d]
    logits = jnp.einsum("tgrd,tsgd->tgrs", q, k_rows) / q.shape[-1] ** 0.5
    return jnp.einsum("tgrs,tsgd->tgrd", jax.nn.softmax(logits, axis=-1), v_rows)


def _time(fn, *args, reps=10):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return {"ms": (time.perf_counter() - t0) / reps * 1e3, "compile_s": compile_s}, out


def attention_forms(rows, keys, k, groups=4, repeat=8, dim=128):
    """Forward, and forward + backward, of one chunk in both forms; the
    gathered form on ``rows / 4`` queries (its rows are 4 x its share)."""
    key = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(key[0], (rows, groups, repeat, dim), jnp.float32)
    kk = jax.random.normal(key[1], (keys, groups, dim), jnp.float32)
    v = jax.random.normal(key[2], (keys, groups, dim), jnp.float32)
    scores = jax.random.normal(key[3], (rows, keys), jnp.float32)
    _, chosen = jax.lax.top_k(scores, k)
    member = jnp.zeros((rows, keys), bool).at[jnp.arange(rows)[:, None], chosen].set(True)
    loss = lambda form: lambda q, k, v, c: jnp.sum(jnp.sin(form(q, k, v, c)))  # noqa: E731
    out = {}
    few = rows // 4
    for name, form, args in (("masked", masked_attention, (q, kk, v, member)),
                             ("gathered", gathered_attention, (q[:few], kk, v, chosen[:few]))):
        fwd, y = _time(jax.jit(form), *args)
        both, _ = _time(jax.jit(jax.grad(loss(form), argnums=(0, 1, 2))), *args)
        out[name] = {"queries": int(args[0].shape[0]), "forward": fwd, "forward_backward": both}
        out[name + "_first"] = y[:few]
    err = float(jnp.max(jnp.abs(out.pop("masked_first") - out.pop("gathered_first"))))
    out["max_difference_of_the_two_forms"] = err
    return out


def main():
    rows, keys, k = (int(a) for a in (sys.argv[1:4] + ["512", "8192", "2048"][len(sys.argv) - 1:]))
    scores = jax.random.normal(jax.random.PRNGKey(0), (rows, keys), jnp.float32)
    want = None
    out = {"device": jax.devices()[0].device_kind, "rows": rows, "keys": keys, "k": k}
    for name, form in FORMS.items():
        timed, got = _time(jax.jit(lambda s, form=form: form(s, k)), scores, reps=20)
        want = got if want is None else want
        out[name] = {**timed, "equal_to_the_first": bool(jnp.array_equal(got, want))}
    out["attention"] = attention_forms(rows, keys, k)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
