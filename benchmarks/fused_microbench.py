"""Chip-independent fused-vs-unfused microbench smoke (tier-1-safe).

The flagship bench (``bench.py``) needs the TPU; between chip runs a
regression in the train step's program would otherwise be invisible. This
smoke runs ONE fused
(``projection_backend="pallas_fused"``, Pallas interpreter on CPU) and one
unfused ("xla" oracle) train step on whatever backend is available, and
records into a JSON artifact:

- relative step time (interpret-mode Pallas is EXPECTED to be slower on
  CPU — the interpreter executes the kernel op-by-op; the number exists so
  a 10× jump in either absolute time rings a bell, not as a TPU proxy);
- a bytes proxy: XLA cost-analysis "bytes accessed" of the compiled
  single-step program for each backend. On CPU this counts the interpreted
  kernel's inner ops rather than one opaque TPU kernel, so the USEFUL
  regression signal is the unfused program's bytes (the one-hot-matmul
  materialization the fused kernel exists to delete) and both programs'
  drift over rounds, not the cross-backend ratio.

Run as a script to (re)generate ``benchmarks/cpu_microbench.json``:

    JAX_PLATFORMS=cpu python benchmarks/fused_microbench.py

``tests/test_fused_microbench.py`` runs the same function at smaller
shapes every tier-1 pass.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_microbench(
    out_path: str | None = None,
    *,
    batch: int = 128,
    hidden: int = 64,
    atoms: int = 51,
    timed_steps: int = 3,
) -> dict:
    """Time fused vs unfused train steps + collect the bytes proxy.

    Returns the artifact dict; writes it to ``out_path`` when given.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from d4pg_tpu.agent import D4PGConfig, create_train_state, jit_train_step
    from d4pg_tpu.models.critic import DistConfig

    rng = np.random.default_rng(0)
    obs_dim, act_dim = 17, 6
    batch_data = {
        "obs": jnp.asarray(rng.normal(size=(batch, obs_dim)), jnp.float32),
        "action": jnp.asarray(rng.uniform(-1, 1, size=(batch, act_dim)), jnp.float32),
        "reward": jnp.asarray(rng.uniform(-1, 0, size=batch), jnp.float32),
        "next_obs": jnp.asarray(rng.normal(size=(batch, obs_dim)), jnp.float32),
        "discount": jnp.full((batch,), 0.99, jnp.float32),
        "weights": jnp.ones((batch,), jnp.float32),
    }

    out = {
        "metric": "fused_vs_unfused_cpu_microbench",
        "backend": jax.default_backend(),
        "batch": batch,
        "hidden": hidden,
        "atoms": atoms,
        "timed_steps": timed_steps,
    }
    for name, backend in (("unfused", "xla"), ("fused", "pallas_fused")):
        config = D4PGConfig(
            obs_dim=obs_dim,
            action_dim=act_dim,
            hidden_sizes=(hidden, hidden, hidden),
            dist=DistConfig(
                kind="categorical", num_atoms=atoms, v_min=-150.0, v_max=150.0
            ),
            projection_backend=backend,
        )
        state = create_train_state(config, jax.random.PRNGKey(0))
        step = jit_train_step(config, donate=False)
        try:
            cost = step.lower(state, batch_data).compile().cost_analysis()
            out[f"{name}_bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
            out[f"{name}_flops"] = float(cost.get("flops", 0.0))
        except Exception:  # d4pglint: disable=broad-except  -- optional XLA
            # cost-analysis probe: shape of the failure varies by backend/
            # jax version and the benchmark's timings land either way
            pass
        state, _, priorities = step(state, batch_data)  # compile + warmup
        jax.block_until_ready(priorities)
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            state, _, priorities = step(state, batch_data)
        jax.block_until_ready(priorities)
        out[f"{name}_step_ms"] = (time.perf_counter() - t0) / timed_steps * 1e3
    if "unfused_step_ms" in out and "fused_step_ms" in out:
        out["fused_over_unfused_time"] = out["fused_step_ms"] / out["unfused_step_ms"]
    if out.get("unfused_bytes_accessed") and out.get("fused_bytes_accessed"):
        out["fused_over_unfused_bytes"] = (
            out["fused_bytes_accessed"] / out["unfused_bytes_accessed"]
        )
    if out_path:
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, out_path)
    return out


if __name__ == "__main__":
    artifact = os.path.join(os.path.dirname(__file__), "cpu_microbench.json")
    print(json.dumps(run_microbench(artifact)))
