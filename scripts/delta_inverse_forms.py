"""Time, on the device, ``ops/gated_delta.py::unit_lower_inverse`` at the
shape the linear-attention cell meets it — 4,096 systems ``[128, 1, 32, 64,
64]`` float32, ``(I + L)⁻¹`` of a strictly lower ``L``:

``rows_64``   the row form over the whole system (64 steps: PR 34's);
``blocked``   ``inverse_plan``'s: the four 16 × 16 diagonal blocks by 16 row
              steps, then two join levels (the module's own);
beside them the blocked form's pieces alone (the cut of the diagonal blocks,
the base case, each join level), and the whole ``gated_delta_chunked``
(forward; forward + backward; the solve's device time under the profiler)
under each form, which is what the cell pays: alone, XLA lays a form's
argument out as it likes. Each form's error is taken against a float64
inverse. PERF.md section 6 (PR 36) has what each cost on the v5e, and
what the forms that lost cost (an unrolled base case, the systems on the
lane axis, joins on whole 64 × 64 arrays, the product form). Host clock,
median of 12. Off the TPU it checks both forms against a float64 inverse at
a small size and prints no time.

    chiprun -- python scripts/delta_inverse_forms.py [out.json]

(In a traced run of the cell the solve is the sub-phase
``agent.linear_attention.solve``: ``python -m cellbench.reducers.span_ms
<xplane.pb>`` reads it.)
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from cellbench import scopes, trace  # noqa: E402
from d4pg_tpu.ops import gated_delta as gd  # noqa: E402

REPS = 12
CHUNK = 64
SOLVE = "ph:agent.linear_attention.solve"


def joined(inverses, lower):
    while inverses.shape[-3] > 1:
        inverses = gd.join_inverses(inverses, lower)
    return inverses[..., 0, :, :]


def systems(shape, seed=0):
    """``L`` as the program builds it: ``strict_tril(β k kᵀ ⊙ decay)`` of
    unit keys, ``β`` in (0, 1), a chunk's cumulative decays."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k = jax.random.normal(ks[0], shape[:-1] + (128,), jnp.float32)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jax.nn.sigmoid(jax.random.normal(ks[1], shape[:-1]))
    gamma = jnp.cumsum(-0.05 * jax.nn.softplus(jax.random.normal(ks[2], shape[:-1])), axis=-1)
    keep = jnp.tril(jnp.ones(shape[-2:], bool))
    decay = jnp.exp(jnp.where(keep, gamma[..., :, None] - gamma[..., None, :], -jnp.inf))
    gram = jnp.einsum("...ik,...jk->...ij", k * beta[..., None], k, precision=gd.HIGHEST)
    return jnp.tril(gram * decay, -1)


def rule_inputs(t, h, d, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (1, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (1, t, h, d)))
    v = jax.random.normal(ks[2], (1, t, h, d))
    g = -0.05 * jax.nn.softplus(jax.random.normal(ks[3], (1, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, h)))
    return q, k, v, g, beta


def clock(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return {"ms": statistics.median(times), "min_ms": min(times), "compile_s": compile_s}, out


def under(patch, fn):
    """``fn`` traced with the module's functions of ``patch`` replaced."""
    kept = {name: getattr(gd, name) for name in patch}
    for name, value in patch.items():
        setattr(gd, name, value)
    try:
        return fn()
    finally:
        for name, value in kept.items():
            setattr(gd, name, value)


def solve_ops(xplane_path):
    """Device self time of the ops under the sub-phase around the inversion
    (``SOLVE`` in their ``op_name``), first device: ``(total ms, [[op, ms,
    executions]])``."""
    plane = next((p for p in scopes._planes(xplane_path)
                  if trace.DEVICE_PLANE.match(p.name)), None)
    if plane is None:
        return 0.0, []
    names = plane.event_names()
    events = [e for span in plane.lines for e in plane.line(span, only=trace.OP_LINE)]
    by_op: dict = {}
    for (key, _, _), (_, self_ns, _) in zip(events, trace.self_times(events)):
        name, op_name = names.get(key, ("", ""))
        if SOLVE in op_name:
            short = trace.hlo_category(name)[0]
            ms, n = by_op.get(short, (0.0, 0))
            by_op[short] = (ms + self_ns / 1e6, n + 1)
    ops = sorted(([k, ms, n] for k, (ms, n) in by_op.items()), key=lambda r: -r[1])
    return sum(r[1] for r in ops), ops


def traced_solve(fn, args, runs=3):
    """``fn(*args)`` under the profiler: the solve's ms a call, its ops."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as d:
        trace.start(d)
        for _ in range(runs):
            jax.block_until_ready(fn(*args))
        trace.stop()
        total, ops = solve_ops(trace.newest_xplane(d))
    return {"ms_a_call": total / runs,
            "ops": [[name, ms / runs, n // runs] for name, ms, n in ops[:12]]}


def forms_alone(lower, timed):
    """Both forms on ``lower`` against a float64 inverse."""
    c = lower.shape[-1]
    want = np.linalg.inv(np.eye(c) + np.asarray(lower, np.float64))
    out = {}
    for name, form in {"rows_64": gd.inverse_by_rows, "blocked": gd.unit_lower_inverse}.items():
        clocked, got = clock(jax.jit(form), lower)
        err = float(np.abs(np.asarray(got, np.float64) - want).max() / np.abs(want).max())
        assert err < 2e-6, (name, err)
        out[name] = {**(clocked if timed else {}), "error_of_scale": err}
    return out


def pieces_alone(lower):
    """The blocked form's parts, each a program of its own."""
    block, levels = gd.inverse_plan(lower.shape[-1])
    blocks = gd.diagonal_blocks(lower, block)
    by_blocks = gd.inverse_by_rows(blocks)
    pieces = {"diagonal_blocks": (lambda x: gd.diagonal_blocks(x, block), (lower,)),
              "base_case": (gd.inverse_by_rows, (blocks,)),
              f"joins.{levels}_levels": (joined, (by_blocks, lower))}
    for i in range(levels):
        pieces[f"joins.level_{i + 1}"] = (gd.join_inverses, (by_blocks, lower))
        by_blocks = gd.join_inverses(by_blocks, lower)
    return {name: clock(jax.jit(fn), *args)[0] for name, (fn, args) in pieces.items()}


def forms_in_the_rule(args, timed):
    """What the cell pays: one layer's whole chunked rule under each form
    (a plan of one block makes ``unit_lower_inverse`` the row form)."""
    patches = {"rows_64": {"inverse_plan": lambda c: (c, 0)}, "blocked": {}}
    out, first = {}, None
    for name, patch in patches.items():
        # functions of its own a form: jit's cache is keyed by the function
        rule = jax.jit(lambda *a: gd.gated_delta_chunked(*a, chunk=CHUNK))
        both = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
            gd.gated_delta_chunked(*a, chunk=CHUNK)[0])), argnums=(0, 1, 2, 3, 4)))
        forward, (got, _) = under(patch, lambda: clock(rule, *args))
        backward, _ = under(patch, lambda: clock(both, *args))
        first = got if first is None else first
        out[name] = {"max_difference_from_rows_64": float(jnp.max(jnp.abs(got - first)))}
        if timed:
            out[name].update(forward=forward, forward_backward=backward,
                             solve=traced_solve(rule, args))
    return out


def main():
    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    shape = (128, 1, 32, CHUNK, CHUNK) if on_tpu else (4, 1, 2, CHUNK, CHUNK)
    lower = systems(shape)
    out = {"device": device.device_kind, "platform": device.platform,
           "systems": list(shape), "reps": REPS, "timed": on_tpu,
           **forms_alone(lower, on_tpu)}
    if on_tpu:
        out["pieces"] = pieces_alone(lower)
    out["rule"] = forms_in_the_rule(
        rule_inputs(8192, 32, 128) if on_tpu else rule_inputs(256, 2, 16), on_tpu)
    text = json.dumps(out, indent=1)
    print(text)
    if len(sys.argv) > 1:
        os.makedirs(os.path.dirname(os.path.abspath(sys.argv[1])), exist_ok=True)
        with open(sys.argv[1], "w") as f:
            f.write(text)


if __name__ == "__main__":
    main()
