"""Does the flagship learner still start on the chip?

Drives the trainer's device-resident path once, through the normal entry
point (``train.main(argv)``), at the full width of the flagship model —
``--env halfcheetah`` (pure-JAX planar physics): obs 17, act 6, 3x256 actor
and critic, C51 with 51 atoms, B=256, n-step 3, PER, the preset's
1,000,000-row ring (2^20 tree leaves), K=32 grad steps per dispatch — for a
handful of dispatches, two evals and one checkpoint, with ``--debug-guards``
armed. Weights are random (seeded); depth of training is cut, width is not.

Legs (each passes or fails on its own; any failure fails the run):

- ``guards``       negative control: the transfer guards the training legs
                   rely on really do raise on this backend;
- ``kernels``      each of the four Pallas entry points, compiled
                   (``interpret=False``) at flagship shapes and compared on
                   the chip with its XLA oracle;
- ``train_xla``    the trainer with ``--projection xla
                   --device-tree-backend xla``;
- ``train_pallas`` the trainer with ``--projection pallas_fused
                   --device-tree-backend pallas --fused-descent``;
- ``train_dp``     only with ``--dp N``: ``train_xla`` sharded over N chips
                   from this one process, asserting ring, PER tree and batch
                   are split over N distinct devices.

The last line of stdout is the verdict, one JSON object with exactly two
keys: ``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count":
1}}``, the device as JAX reports it. The line before it is the report, one
JSON object too: the verdict's keys plus per-leg pass/fail with compile
seconds apart from run seconds, and the compile-cache directory with its
hits, the backend-compile seconds of the run (what a warm cache shortens;
JAX only stores programs that took a second or more to compile) and
cold-vs-warm seconds for the programs two legs share. No rate printed here
is a metric; speeds are "not measured". Without a TPU the default
invocation exits non-zero and prints no result. ``--cpu-rehearsal`` runs
the same legs at tiny sizes on the CPU backend (Pallas in interpret mode)
so the command can be debugged before it is sent to the chip; its output
says ``"platform": "cpu"``.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --dp 4          # four chips, one process
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal [--dp 2]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

ALL_LEGS = ("guards", "kernels", "train_xla", "train_pallas", "train_dp")

# jax.monitoring event names (jax/_src/dispatch.py, compiler.py).
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_BACKEND_COMPILE = _COMPILE_EVENTS[2]
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class Sizes:
    """The shapes a run uses. ``flagship`` is the halfcheetah preset's own
    sizes (nothing overridden but run length); ``rehearsal`` is small enough
    for the CPU interpreter."""

    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        self.atoms = 51
        if rehearsal:
            self.batch = 64
            self.k = 4
            self.leaves = 1 << 12
            self.dispatches = 4
            self.train_overrides = [
                "--hidden-sizes", "32,32", "--rmsize", "4096",
                "--bsize", "64", "--warmup", "256", "--num-envs", "4",
            ]
        else:
            self.batch = 256
            self.k = 32
            self.leaves = 1 << 20
            self.dispatches = 6
            self.train_overrides = []
        self.total_steps = self.k * self.dispatches
        self.eval_interval = self.total_steps // 2


class CompileMeter:
    """Reads JAX's own compile-time events per leg and per program, and
    pairs persistent-cache hits with the program they served."""

    def __init__(self):
        import jax.monitoring

        self.leg = None
        self.by_leg: dict[str, dict] = {}
        self._pending_hit = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def start(self, leg: str) -> None:
        self.leg = leg
        self.by_leg[leg] = {
            "spans": [], "backend_s": 0.0, "cache_hits": 0,
            "cache_misses": 0, "programs": {},
        }

    def compile_seconds(self, leg: str) -> float:
        """Wall time this leg spent tracing, lowering or compiling: the
        measure of the UNION of the event spans — a jitted function traced
        inside another's trace reports both spans, and summing durations
        would count the inner one twice."""
        total, end = 0.0, float("-inf")
        for a, b in sorted(self.by_leg[leg]["spans"]):
            if b > end:
                total += b - max(a, end)
                end = b
        return total

    def _span(self, name: str, start: float, end: float, **_kw) -> None:
        if self.leg is not None and name in _COMPILE_EVENTS:
            self.by_leg[self.leg]["spans"].append((start, end))

    def _event(self, name: str, **_kw) -> None:
        if self.leg is None:
            return
        rec = self.by_leg[self.leg]
        if name == _CACHE_HIT:
            rec["cache_hits"] += 1
            self._pending_hit = True
        elif name == _CACHE_MISS:
            rec["cache_misses"] += 1

    def _duration(self, name: str, secs: float, **kw) -> None:
        if self.leg is None or name not in _COMPILE_EVENTS:
            return
        rec = self.by_leg[self.leg]
        if name == _BACKEND_COMPILE:
            rec["backend_s"] += secs
            # compile_or_get_cached records the hit right before this event
            # closes, on the same thread: the flag belongs to this program.
            prog = rec["programs"].setdefault(
                str(kw.get("fun_name", "?")), {"backend_s": 0.0, "hits": 0, "n": 0}
            )
            prog["backend_s"] += secs
            prog["n"] += 1
            if self._pending_hit:
                prog["hits"] += 1
            self._pending_hit = False

    def cold_vs_warm(self) -> dict:
        """Programs some leg compiled cold and a LATER leg got from the
        persistent cache: seconds the compiler took vs seconds the cache
        took. Same process, fresh jit wrappers, so every hit is the on-disk
        cache, not JAX's in-memory one."""
        cold = warm = 0.0
        names = []
        legs = list(self.by_leg)
        for i, later in enumerate(legs):
            for name, prog in self.by_leg[later]["programs"].items():
                if not prog["hits"] or prog["hits"] != prog["n"]:
                    continue
                for earlier in legs[:i]:
                    first = self.by_leg[earlier]["programs"].get(name)
                    if first and not first["hits"]:
                        cold += first["backend_s"]
                        warm += prog["backend_s"]
                        names.append(name)
                        break
        return {
            "cold_compile_s": round(cold, 3),
            "warm_compile_s": round(warm, 3),
            "shared_programs": sorted(set(names)),
        }


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ------------------------------------------------------------------- guards
def leg_guards(platform: str) -> dict:
    """The training legs pass ``--debug-guards`` and count on a guarded
    transfer RAISING. On the CPU backend host and device memory are one, so
    the device-to-host guard has never been seen to fire; prove here that on
    this backend each guard has teeth, and that a device-only dispatch
    passes."""
    import jax

    from d4pg_tpu.analysis import no_implicit_transfers, no_transfers

    f = jax.jit(lambda a: a * 2.0)
    host = np.ones(8, np.float32)
    x = jax.device_put(host)
    jax.block_until_ready(f(x))

    def raised(fn) -> bool:
        try:
            fn()
        except Exception as e:  # noqa: BLE001 - the guard's error type is
            # a backend runtime error; anything else is re-raised below
            if "isallowed" in str(e):
                return True
            raise
        return False

    out = {}
    with no_transfers():
        out["device_only_dispatch_passes"] = not raised(
            lambda: jax.block_until_ready(f(x))
        )
        out["d2h_fetch_raises"] = raised(lambda: np.asarray(f(x)))
        out["explicit_h2d_raises"] = raised(lambda: jax.device_put(host))
        out["implicit_h2d_raises"] = raised(lambda: f(host))
    with no_implicit_transfers():
        out["implicit_h2d_raises_loose"] = raised(lambda: f(host))
        out["explicit_h2d_passes_loose"] = not raised(
            lambda: jax.device_put(host)
        )
    expected = dict.fromkeys(out, True)
    if platform == "cpu":
        # Known: no copy happens, so there is nothing for the guard to see.
        expected["d2h_fetch_raises"] = False
    _check(out == expected, f"transfer guards: got {out}, expected {expected}")
    return out


# ------------------------------------------------------------------ kernels
def _exact_leaves(n_leaves: int):
    """Leaf priorities whose every partial sum is an integer below 2^24:
    the f32 device tree and the f64 host tree then hold the same values, so
    the host tree is a third opinion on every index. Zero-mass holes
    (skipped by the descent) and an unfilled tail included."""
    r = np.random.default_rng(3)
    leaves = r.integers(1, 8, n_leaves).astype(np.float32)
    leaves[r.random(n_leaves) < 0.05] = 0.0
    leaves[n_leaves - n_leaves // 8:] = 0.0
    assert float(leaves.sum(dtype=np.float64)) < 2 ** 24
    return leaves


def leg_kernels(sizes: Sizes, platform: str) -> dict:
    import jax
    import jax.numpy as jnp

    from d4pg_tpu.ops import categorical_projection, make_support
    from d4pg_tpu.ops.pallas_fused_step import fused_categorical_loss_descent
    from d4pg_tpu.ops.pallas_mode import pallas_interpret
    from d4pg_tpu.ops.pallas_projection import (
        categorical_projection_pallas,
        fused_categorical_loss,
    )
    from d4pg_tpu.ops.pallas_tree import find_prefix_pallas
    from d4pg_tpu.replay import device_per as dper
    from d4pg_tpu.replay.native import NativeSumTree

    interpret = pallas_interpret()
    _check(interpret == (platform == "cpu"), "interpret mode on a TPU")
    B, A, L, K = sizes.batch, sizes.atoms, sizes.leaves, sizes.k
    out: dict = {"interpret": interpret, "B": B, "A": A, "leaves": L,
                 "draws": K * B}
    failures = []

    def sub(name, fn):
        try:
            out[name] = fn()
        except Exception as e:  # noqa: BLE001 - report every kernel, then fail
            traceback.print_exc()
            out[name] = {"ok": False, "error": f"{type(e).__name__}: {e}"[:600]}
            failures.append(name)

    # ---- loss-side inputs: the halfcheetah preset's support, n-step 3
    support = make_support(0.0, 1000.0, A)
    r = np.random.default_rng(0)
    q = jnp.asarray(r.normal(size=(B, A)), jnp.float32)
    p = jnp.asarray(r.dirichlet(np.ones(A), size=B), jnp.float32)
    rew = jnp.asarray(r.uniform(-1.0, 12.0, B), jnp.float32)
    disc = jnp.asarray(
        np.where(r.random(B) < 0.1, 0.0, 0.99 ** 3), jnp.float32
    )
    w = jnp.asarray(r.uniform(0.2, 1.0, B), jnp.float32)

    # The oracle is the XLA path at f32 matmul precision. TPU's DEFAULT
    # precision runs the projection's one-hot einsum in one bf16 pass; how
    # far that moves the projected probabilities is reported, not asserted
    # (it is what --projection xla trains with).
    def oracle_proj(p_, r_, d_):
        with jax.default_matmul_precision("highest"):
            return categorical_projection(support, p_, r_, d_)

    def oracle_loss(q_, p_, r_, d_):
        m = jax.lax.stop_gradient(oracle_proj(p_, r_, d_))
        logp = jax.nn.log_softmax(q_, axis=-1)
        ce = -jnp.sum(m * logp, axis=-1)
        ov = jnp.abs(-jnp.sum(m * jnp.exp(logp), axis=-1))
        return ce, ov

    # Tolerances. Every quantity is an f32 sum of <= A = 51 terms, added in
    # another association than the oracle's and fed by exp/log
    # implementations that may differ in the last ulps: the bound is about
    # A * 2^-23 * max|term| ~ 6e-6 * max|term|. Probabilities and dq have
    # terms <= 1 -> 1e-4 absolute leaves 16x headroom; CE has terms
    # m*|log p| <= ~10 and magnitude ~log(51) -> 1e-3 absolute.
    TOL_P, TOL_CE = 1e-4, 1e-3

    def maxabs(a, b):
        return float(jnp.max(jnp.abs(a - b)))

    def projection():
        got = categorical_projection_pallas(support, p, rew, disc, interpret)
        want = jax.jit(oracle_proj)(p, rew, disc)
        dflt = jax.jit(
            lambda *a: categorical_projection(support, *a)
        )(p, rew, disc)
        err = maxabs(got, want)
        _check(bool(jnp.all(jnp.isfinite(got))), "projection not finite")
        _check(err <= TOL_P, f"projection: max|kernel-oracle|={err} > {TOL_P}")
        return {"ok": True, "max_abs_err": err, "tol": TOL_P,
                "xla_default_precision_max_abs_dev": maxabs(dflt, want)}

    def fused_loss():
        def k_obj(q_):
            ce, ov = fused_categorical_loss(support, q_, p, rew, disc, interpret)
            return jnp.sum(w * ce) + jnp.sum(w * ov), (ce, ov)

        def o_obj(q_):
            ce, ov = oracle_loss(q_, p, rew, disc)
            return jnp.sum(w * ce) + jnp.sum(w * ov), (ce, ov)

        (_, (ce, ov)), dq = jax.jit(jax.value_and_grad(k_obj, has_aux=True))(q)
        (_, (ce_o, ov_o)), dq_o = jax.jit(
            jax.value_and_grad(o_obj, has_aux=True)
        )(q)
        errs = {"ce": maxabs(ce, ce_o), "overlap": maxabs(ov, ov_o),
                "dq": maxabs(dq, dq_o)}
        _check(bool(jnp.all(jnp.isfinite(dq))), "fused loss grad not finite")
        _check(errs["ce"] <= TOL_CE, f"fused loss CE err {errs['ce']}")
        _check(errs["overlap"] <= TOL_P, f"fused overlap err {errs['overlap']}")
        _check(errs["dq"] <= TOL_P, f"fused loss grad err {errs['dq']}")
        return {"ok": True, "max_abs_err": errs,
                "tol": {"ce": TOL_CE, "overlap": TOL_P, "dq": TOL_P}}

    # ---- tree-side inputs: a FULL ring of arbitrary f32 priorities, the
    # shape training gives them ((|td| + eps)^0.6), zero-mass holes included
    rr = np.random.default_rng(7)
    leaves = ((rr.exponential(1.0, L) + 1e-6) ** 0.6).astype(np.float32)
    leaves[rr.random(L) < 0.02] = 0.0
    lane = dper.tree_from_priorities(leaves, L).sums[0]
    pre = dper.stratified_prefixes(jax.random.PRNGKey(5), K, B, lane[1])
    # Boundary prefixes: a prefix EQUAL to a left child's sum must go right
    # (>=). The left spine's node sums, one per level, overwrite the head of
    # the last row.
    spine = lane[2 ** np.arange(1, int(math.log2(L)) + 1)]
    pre = pre.at[K - 1, : spine.shape[0]].set(spine)

    def tree_descent():
        # The kernel runs the tree walk itself (ops/pallas_tree.py), so the
        # contract is equality with the XLA descent on every draw — no
        # tolerance.
        got = np.asarray(find_prefix_pallas(lane, pre, interpret))
        want = np.asarray(jax.jit(dper.descend_prefix)(lane, pre))
        bad = int(np.sum(got != want))
        _check(bad == 0, f"pallas descent: {bad}/{got.size} indices differ "
               "from the XLA descent")
        return {"ok": True, "indices": int(got.size), "mismatches": 0,
                "distinct_leaves": int(np.unique(got).size)}

    def native_tree():
        # Third opinion, and the proof that the committed native source
        # compiles here: the host f64 tree (--tree-backend native), on
        # leaves whose sums f32 and f64 both hold exactly.
        exact = _exact_leaves(L)
        lane_x = dper.tree_from_priorities(exact, L).sums[0]
        pr = dper.stratified_prefixes(jax.random.PRNGKey(9), K, B, lane_x[1])
        host = NativeSumTree(L)
        host.set(np.arange(L), exact.astype(np.float64))
        want = host.find_prefixsum_idx(
            np.asarray(pr, np.float64).reshape(-1)
        ).reshape(pr.shape)
        _check(np.array_equal(
            np.asarray(jax.jit(dper.descend_prefix)(lane_x, pr)), want),
            "XLA descent != host native tree on exact sums")
        _check(np.array_equal(
            np.asarray(find_prefix_pallas(lane_x, pr, interpret)), want),
            "pallas descent != host native tree on exact sums")
        return {"ok": True, "indices": int(want.size)}

    def fused_step():
        nxt = pre[K - 1]  # the row with the boundary prefixes

        def k_obj(q_):
            ce, ov, idx = fused_categorical_loss_descent(
                support, q_, p, rew, disc, nxt, lane, interpret
            )
            return jnp.sum(w * ce), (ce, ov, idx)

        (_, (ce, ov, idx)), dq = jax.jit(
            jax.value_and_grad(k_obj, has_aux=True)
        )(q)
        # Same tiles as the separate kernels -> same bits.
        ce_s, ov_s = fused_categorical_loss(support, q, p, rew, disc, interpret)
        _check(bool(jnp.all(ce == ce_s)) and bool(jnp.all(ov == ov_s)),
               "fused step loss bits != fused loss kernel")
        idx_o = jax.jit(dper.descend_prefix)(lane, nxt)
        _check(bool(jnp.all(idx == idx_o)), "fused step descent != XLA descent")
        dq_o = jax.jit(jax.grad(
            lambda q_: jnp.sum(w * oracle_loss(q_, p, rew, disc)[0])
        ))(q)
        err = maxabs(dq, dq_o)
        _check(err <= TOL_P, f"fused step grad err {err}")
        return {"ok": True, "dq_max_abs_err": err, "tol": TOL_P,
                "loss_bitwise_equal_to_fused_loss_kernel": True,
                "descent_mismatches": 0}

    sub("categorical_projection_pallas", projection)
    sub("fused_categorical_loss", fused_loss)
    sub("find_prefix_pallas", tree_descent)
    sub("native_sum_tree", native_tree)
    sub("fused_categorical_loss_descent", fused_step)
    if failures:
        raise AssertionError(f"kernels failed: {failures}")
    return out


# ----------------------------------------------------------------- training
def _train_argv(sizes: Sizes, log_dir: str, extra: list[str]) -> list[str]:
    return [
        "--env", "halfcheetah",
        "--replay-placement", "device", "--p-replay",
        "--steps-per-dispatch", str(sizes.k),
        "--debug-guards",
        # Inert while the priority tree lives on the device, but a run
        # moved to host/hybrid placement then fails on a missing compiler
        # instead of degrading to NumPy ("auto").
        "--tree-backend", "native",
        # One collection segment + ring/tree ingest per dispatch, so the
        # ingest programs meet their one-compile budget in steady state.
        "--env-steps-per-train-step", "16",
        "--total-steps", str(sizes.total_steps),
        "--eval-interval", str(sizes.eval_interval),
        "--eval-episodes", "1",
        "--checkpoint-interval", "1000000",  # -> the one save at the end
        "--snapshot-replay",
        "--seed", "0",
        "--log-dir", log_dir,
        *sizes.train_overrides,
        *extra,
    ]


def leg_train(sizes: Sizes, platform: str, log_dir: str, extra: list[str],
              dp: int = 0) -> dict:
    import jax

    import train

    _check(jax.default_backend() == platform,
           f"backend is {jax.default_backend()!r}, expected {platform!r}")
    argv = _train_argv(sizes, log_dir, extra)
    trainer = train.main(argv)
    out: dict = {"argv": " ".join(argv)}

    # -- it trained: every dispatch happened, the losses are numbers
    _check(trainer.grad_steps == sizes.total_steps,
           f"grad_steps {trainer.grad_steps} != {sizes.total_steps}")
    with open(os.path.join(log_dir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    _check(len(rows) >= 2, f"metrics.jsonl has {len(rows)} rows, want >= 2")
    for row in rows:
        for key in ("critic_loss", "actor_loss", "priority_mean", "q_mean",
                    "eval_return_mean"):
            _check(math.isfinite(row[key]), f"{key}={row[key]} in {row}")
    out["rows"] = len(rows)
    out["critic_loss"] = [row["critic_loss"] for row in rows]
    out["priority_mean"] = [row["priority_mean"] for row in rows]
    out["replay_size"] = rows[-1]["replay_size"]

    # -- the guards held: --debug-guards raises on a guarded transfer or an
    # over-budget compile, so getting here means none; the counts say what
    # the budgets were measured against.
    counts = trainer.sentinel.counts()
    out["compiles"] = {k: counts[k] for k in
                       ("megastep", "ring_ingest", "tree_ingest")}
    _check(out["compiles"] == {"megastep": 1, "ring_ingest": 1,
                               "tree_ingest": 1},
           f"recompiled after the first dispatch: {out['compiles']}")

    # -- priorities moved off their seed: new rows enter the device tree at
    # max_priority^alpha = 1.0; the megastep's write-back must have
    # replaced sampled leaves with (|td|+eps)^alpha and raised the max.
    ckpt = os.path.join(log_dir, "checkpoints")
    with np.load(os.path.join(ckpt, "device_per.npz")) as z:
        pa = z["priorities_alpha"]
        max_priority = float(z["max_priority"])
    filled = pa[pa > 0]
    moved = int(np.sum(filled != 1.0))
    _check(np.all(np.isfinite(pa)) and filled.size > 0, "empty/NaN tree")
    _check(moved > 0 and max_priority != 1.0,
           f"priorities still at seed: moved={moved} max={max_priority}")
    out["tree"] = {"leaves_filled": int(filled.size), "leaves_moved": moved,
                   "max_priority": max_priority}
    # -- the checkpoint committed: the manifest digests the Orbax step dir
    # and the side files (trainer meta, replay snapshot, priority sidecar).
    from d4pg_tpu.runtime import manifest

    ok, why, _ = manifest.verify_step_dir(
        ckpt, sizes.total_steps,
        manifest.default_step_dir(ckpt, sizes.total_steps),
    )
    _check(ok, f"checkpoint step {sizes.total_steps} unattested: {why}")
    _check(os.path.exists(os.path.join(ckpt, "replay.npz")), "no replay.npz")

    if dp:
        out["sharding"] = _check_sharding(trainer, sizes, dp)
    return out


def _check_sharding(trainer, sizes: Sizes, dp: int) -> dict:
    """Ring rows, PER-tree lanes and the batch axis each split over ``dp``
    distinct devices. Ring and tree are arrays — read their shards. The
    batch never exists as a global array (each shard draws B/dp rows from
    its own ring slice inside shard_map), so read it off the program: the
    lowered megastep must gather per-shard ``[K, B/dp, obs]`` blocks."""
    ring, tree = trainer._ring, trainer._dev_per.tree

    def spread(arr, rows):
        shards = arr.addressable_shards
        devices = {s.device for s in shards}
        _check(len(devices) == dp and len(shards) == dp,
               f"{len(shards)} shards on {len(devices)} devices, want {dp}")
        _check(all(s.data.shape[0] == rows for s in shards),
               f"shard rows {[s.data.shape for s in shards]}, want {rows}")
        return sorted(d.id for d in devices)

    cap = trainer.config.replay_capacity
    out = {
        "ring_devices": spread(ring.reward, cap // dp),
        "tree_devices": spread(tree.sums, 1),
        "mesh_devices": sorted(
            d.id for d in trainer._mega_mesh.devices.flatten()
        ),
    }
    _check(len(set(out["mesh_devices"])) == dp, "mesh devices not distinct")
    text = trainer._megastep.lower(
        trainer.state, ring, tree, trainer._megastep_key
    ).as_text()
    b_local = trainer.config.batch_size // dp
    obs = trainer.config.agent.obs_dim
    per_shard = f"tensor<{sizes.k}x{b_local}x{obs}xf32>"
    whole = f"tensor<{sizes.k}x{trainer.config.batch_size}x{obs}xf32>"
    _check(per_shard in text and whole not in text,
           f"megastep program: want per-shard batch {per_shard}, and no "
           f"{whole}")
    out["per_shard_batch"] = per_shard
    return out


# --------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter
    )
    ap.add_argument("--dp", type=int, default=0, metavar="N",
                    help="also run train_dp: the XLA trainer sharded over N "
                         "devices driven by this one process")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend (needs "
                         "JAX_PLATFORMS=cpu); output is labelled cpu")
    args = ap.parse_args(argv)

    # Everything of the repo this script needs, before anything is printed:
    # run from a directory that holds only this file, it fails right here.
    import train  # noqa: F401
    from d4pg_tpu.utils.backend import cpu_requested
    from d4pg_tpu.utils.compile_cache import ENV_VAR, configure_compile_cache

    want = "cpu" if args.cpu_rehearsal else "tpu"
    if args.cpu_rehearsal:
        if not cpu_requested():
            raise SystemExit(
                "chip_smoke: --cpu-rehearsal needs JAX_PLATFORMS=cpu exported"
            )
        if args.dp:
            import jax

            jax.config.update("jax_num_cpu_devices", args.dp)
    cache_dir = configure_compile_cache()
    import jax

    device = jax.devices()[0]
    if device.platform != want:
        raise SystemExit(
            f"chip_smoke: JAX platform is {device.platform!r}, this run needs "
            f"{want!r}. Without a TPU there is no result; --cpu-rehearsal "
            "(with JAX_PLATFORMS=cpu) debugs the command at tiny sizes."
        )
    if args.dp and len(jax.devices()) < args.dp:
        raise SystemExit(
            f"chip_smoke: --dp {args.dp} needs {args.dp} devices, JAX has "
            f"{len(jax.devices())}"
        )

    # The leg list is fixed by --dp: a passing run ran every one of them.
    legs = [leg for leg in ALL_LEGS if leg != "train_dp" or args.dp]

    sizes = Sizes(args.cpu_rehearsal)
    meter = CompileMeter()
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    runners = {
        "guards": lambda: leg_guards(want),
        "kernels": lambda: leg_kernels(sizes, want),
        "train_xla": lambda: leg_train(
            sizes, want, os.path.join(work, "train_xla"),
            ["--projection", "xla", "--device-tree-backend", "xla"]),
        "train_pallas": lambda: leg_train(
            sizes, want, os.path.join(work, "train_pallas"),
            ["--projection", "pallas_fused", "--device-tree-backend",
             "pallas", "--fused-descent"]),
        "train_dp": lambda: leg_train(
            sizes, want, os.path.join(work, "train_dp"),
            ["--projection", "xla", "--device-tree-backend", "xla",
             "--dp", str(args.dp)], dp=args.dp),
    }
    results: dict = {}
    try:
        for leg in legs:
            print(f"[chip_smoke] ---- leg {leg}", flush=True)
            meter.start(leg)
            t0 = time.monotonic()
            try:
                detail = runners[leg]()
                ok, err = True, None
            except (Exception, SystemExit) as e:  # noqa: BLE001 - a failed
                # leg is a result; the remaining legs still run
                traceback.print_exc()
                ok, detail = False, None
                err = f"{type(e).__name__}: {e}"[:800]
            wall = time.monotonic() - t0
            rec = meter.by_leg[leg]
            compile_s = meter.compile_seconds(leg)
            results[leg] = {
                "ok": ok,
                "wall_s": round(wall, 2),
                # tracing + lowering + backend compile, as wall time
                "compile_s": round(compile_s, 2),
                # the backend share alone — XLA/Mosaic compiling, or the
                # persistent cache answering: the only part a cache can
                # shorten (tracing and lowering are Python, every run)
                "backend_compile_s": round(rec["backend_s"], 2),
                "run_s": round(wall - compile_s, 2),
                "cache_hits": rec["cache_hits"],
                "cache_misses": rec["cache_misses"],
            }
            if err:
                results[leg]["error"] = err
            if detail:
                results[leg]["detail"] = detail
            print(f"[chip_smoke] leg {leg}: {'ok' if ok else 'FAILED'} "
                  f"{json.dumps(results[leg])}", flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)

    ok = all(r["ok"] for r in results.values())
    verdict = {
        "ok": ok,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
        },
    }
    report = {
        **verdict,
        "sizes": "rehearsal" if sizes.rehearsal else "flagship",
        "legs": results,
        "compile_cache": {
            "dir": cache_dir,
            "from_env": bool(os.environ.get(ENV_VAR)),
            "hits": sum(r["cache_hits"] for r in results.values()),
            "misses": sum(r["cache_misses"] for r in results.values()),
            "backend_compile_s": round(
                sum(r["backend_compile_s"] for r in results.values()), 2
            ),
            **meter.cold_vs_warm(),
        },
        "speeds": "not measured",
        "claim": None,
    }
    if not args.cpu_rehearsal:
        # A copy the chip tool brings back (its view of stdout is capped).
        out_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "chiprun_out"
        )
        os.makedirs(out_dir, exist_ok=True)
        name = f"chip_smoke_dp{args.dp}.json" if args.dp else "chip_smoke.json"
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(report, f, indent=1)
    # Two JSON lines: the report, then — LAST, with exactly these keys and
    # nothing more, because that is what the driver parses — the verdict.
    sys.stdout.flush()
    print(json.dumps(report))
    print(json.dumps(verdict), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
