"""Time, on the device, one step of the PER tree's descent read two ways —
the left child by ``f32[n]`` gather (``sums[2 idx]``) and by compare-and-
select over the level's static slice (``device_per.left_by_select``; and
the same with the candidates on the minor axis) — for
candidate levels of 2^1 ... 2^15 words, ``n`` draws a dispatch in {256, 512,
1024, 2048, 8192}, in a 2^22- and a 2^26-word tree; then the whole walk with its top
``d`` steps dense. The two constants of ``device_per.draw_plan``
(``DENSE_DRAW_MAX_WORDS``, ``DENSE_DRAW_MIN_DRAWS``) were set from its table:
PERF.md section 6 (PR 33) has what each cost on the v5e.

Times are device durations of each program's executions, read from the
profiler's ``XLA Modules`` line (the programs are too short for a host
clock) and matched to the programs by their order — equal programs, such as
one gather step at two levels, share one executable and one name; each step
runs on the node indices the real walk reaches at that level from stratified
prefixes, and every form's leaves are checked against the all-gather walk
before anything is timed.

    chiprun -- python scripts/tree_descent_levels.py [out.json]
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cellbench import trace  # noqa: E402
from d4pg_tpu.replay import device_per as dper  # noqa: E402

TREES = (2 ** 22, 2 ** 26)
DRAWS = (256, 512, 1024, 2048, 8192)
LEVELS = range(1, 16)            # candidates: 2^level words
WALKS = (0, 4, 8, 10, 11, 12, 13, 14, 15, 16)
REPS = 12


def seeded_lane(width: int) -> jax.Array:
    """A full tree with 2% zero-mass holes, built densely on the device."""
    half = width // 2
    k1, k2 = jax.random.split(jax.random.PRNGKey(width % 1000003))
    leaves = jax.random.uniform(k1, (half,), jnp.float32, 0.05, 1.0)
    leaves = jnp.where(jax.random.uniform(k2, (half,)) < 0.02, 0.0, leaves)
    lane = jnp.zeros((width,), jnp.float32).at[half:].set(leaves)
    return dper.rebuild_ancestors(lane, half.bit_length() - 1)


def left_by_select_minor(sums, idx, level: int):
    """``left_by_select`` with the candidates on the minor axis (the
    reduce crosses lanes): timed beside it to say why it is not the form."""
    lo = 2 << level
    lefts = jax.lax.slice(sums, (lo,), (2 * lo,), (2,))
    which = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], lo // 2), 1)
    hit = which == (idx - lo // 2)[:, None]
    return jnp.sum(jnp.where(hit, lefts[None, :], jnp.float32(0.0)), axis=1)


FORMS = ("gather", "select", "select_minor")


def step(form: str, level: int):
    """One step of the walk at ``level``: the read under test, then the
    compare, the subtraction and the new index, so nothing is dead."""

    def fn(sums, flat, idx):
        if form == "select":
            left = dper.left_by_select(sums, idx, level)
        elif form == "select_minor":
            left = left_by_select_minor(sums, idx, level)
        else:
            left = sums[2 * idx]
        go_right = flat >= left
        flat = flat - jnp.where(go_right, left, jnp.float32(0.0))
        return flat, 2 * idx + go_right.astype(jnp.int32)

    return fn


def prefix_walk(sums, flat, levels: int):
    """``(flat, idx)`` after ``levels`` steps of the all-gather walk."""
    idx = jnp.ones(flat.shape, jnp.int32)
    for level in range(levels):
        flat, idx = step("gather", level)(sums, flat, idx)
    return flat, idx


def module_us(trace_dir: str, programs: int) -> list:
    """Median device microseconds of each of ``programs`` programs, run
    round-robin in that order for the whole trace."""
    modules = trace.from_xplane(trace.newest_xplane(trace_dir)).devices[0].modules
    if not modules or len(modules) % programs:
        raise RuntimeError(f"{len(modules)} executions traced for {programs} programs")
    durations = [dur / 1e3 for _, _, dur in sorted(modules, key=lambda m: m[1])]
    return [statistics.median(durations[i::programs]) for i in range(programs)]


def main(out_path: str | None) -> None:
    device = jax.devices()[0]
    report = {"platform": device.platform, "device_kind": device.device_kind,
              "levels": [], "walks": []}
    runs = []                     # (kind, key fields, jitted, args)
    for width in TREES:
        sums = jax.jit(seeded_lane, static_argnums=0)(width)
        for n in DRAWS:
            pre = dper.stratified_prefixes(jax.random.PRNGKey(n), 32, n // 32, sums[1])
            flat0 = pre.reshape(-1)
            want = np.asarray(jax.jit(dper.descend_prefix_gather)(sums, pre))
            for level in LEVELS:
                flat, idx = jax.jit(prefix_walk, static_argnums=2)(sums, flat0, level)
                outs = {}
                for form in FORMS:
                    fn = jax.jit(step(form, level))
                    outs[form] = jax.block_until_ready(fn(sums, flat, idx))
                    runs.append(("levels", dict(tree_words=width, draws=n, level=level,
                                                words=2 ** level, form=form),
                                 fn, (sums, flat, idx)))
                for form in FORMS[1:]:
                    for a, b in zip(outs["gather"], outs[form]):
                        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            for dense in WALKS:
                fn = jax.jit(lambda s, p, d=dense: dper.descend_prefix(s, p, dense_levels=d))
                np.testing.assert_array_equal(np.asarray(fn(sums, pre)), want)
                runs.append(("walks", dict(tree_words=width, draws=n, dense_levels=dense),
                             fn, (sums, pre)))
    us = [None] * len(runs)       # off the TPU every form is checked, none timed
    if device.platform == "tpu":
        with tempfile.TemporaryDirectory() as trace_dir:
            jax.profiler.start_trace(trace_dir)
            for _ in range(REPS):
                for _, _, fn, args in runs:
                    out = fn(*args)
                jax.block_until_ready(out)
            jax.profiler.stop_trace()
            us = module_us(trace_dir, len(runs))
    for (kind, fields, _, _), t in zip(runs, us):
        report[kind].append({**fields, "us": t})
    text = json.dumps(report)
    if out_path:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            f.write(text)
    for row in report["levels"] + report["walks"]:
        print(json.dumps(row))
    print(json.dumps({k: report[k] for k in ("platform", "device_kind")}))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else None)
