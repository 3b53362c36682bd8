"""Two readings the hybrid torso's preset and its cell's check 1 are set
from, taken on the device at the published widths (PERF.md section 6, PR 34
has what each read on the v5e):

``blocks``   the expert layer of ``--torso qwen3_next`` as the cell cuts it
             (8,192 tokens, top-10 of 512, experts 0-15 held: about 160 live
             rows an expert), forward and backward, at ``expert_block_rows``
             128, 256 and 512: milliseconds a call, median of REPEATS.
``check``    check 1 of ``cellbench/drivers/learner_lin.py`` twice on one
             seed: the program's step wrapped in ``highest`` (what decides
             ``correct``) and at the program's default precision, which has
             to fail. Every tolerance of the driver stands between the two.

    chiprun --timeout 3000 -- python scripts/hybrid_torso_readings.py blocks check [seed]

Off the TPU it runs the tiny preset and prints no time under a device
metric's name (``platform`` says where it ran).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from d4pg_tpu.models import torso as T

REPEATS = 12
CELL = "humanoid_qwen3next_ep32.learn_per_lin8k"


def cell_agent(tiny: bool):
    from cellbench import manifest as mf
    from train import build_parser, config_from_args

    cell = mf.cell(*mf.load(), CELL)
    argv = list(cell.config["argv"]) + list(cell.traffic["argv"])
    if tiny:
        argv = [a if a != "Humanoid-v4" else "pendulum" for a in argv]
        argv += cell.config["rehearsal_argv"]
    return cell, config_from_args(build_parser().parse_args(argv))


def blocks(cfg, tokens: int) -> dict:
    out = {}
    for rows in (128, 256, 512):
        c = dataclasses.replace(cfg, expert_block_rows=rows)
        p = T._block_init(c, jax.random.PRNGKey(0), moe=True, layer=0)["ffn"]
        x = jax.random.normal(jax.random.PRNGKey(1), (tokens, c.hidden_size))

        @jax.jit
        def both(p, x):
            def loss(p, x):
                y, (load, dropped) = T.expert_layer(c, p, x)
                return jnp.sum(jnp.sin(y)), load
            (_, load), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, x)
            return load, grads

        load, _ = jax.block_until_ready(both(p, x))
        times = []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            jax.block_until_ready(both(p, x))
            times.append(1e3 * (time.perf_counter() - t0))
        times.sort()
        out[str(rows)] = {
            "live_rows_an_expert": [int(v) for v in load], "buffer_rows": c.padded_pairs(tokens)}
        if jax.devices()[0].platform == "tpu":      # a CPU's clock is no device time
            out[str(rows)].update(ms_forward_and_backward=times[len(times) // 2], ms_min=times[0])
    return out


def check(cell, cfg, seed: int) -> dict:
    from cellbench.drivers import learner_lin as ll

    out = {}
    for name, precision in (("highest", "highest"), ("program_default", None)):
        r = ll.reference_check(cfg.agent, cfg.batch_size, seed, cell.config["reference"],
                               say=lambda *a: print("[readings]", *a, flush=True),
                               precision=precision)
        out[name] = r
        print(json.dumps({name: r}, default=str), flush=True)
    return out


def main(argv) -> int:
    platform = jax.devices()[0].platform
    cell, cfg = cell_agent(tiny=platform != "tpu")
    seed = next((int(a) for a in argv if a.isdigit()), 2700000001)
    out = {"platform": platform, "device": jax.devices()[0].device_kind, "seed": seed}
    if "blocks" in argv:
        out["blocks"] = blocks(cfg.agent.torso, cfg.batch_size * cfg.agent.torso.window)
        print(json.dumps({"blocks": out["blocks"]}), flush=True)
    if "check" in argv:
        out["check"] = check(cell, cfg, seed)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "hybrid_torso_readings.json"), "w") as f:
        json.dump(out, f, indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
