"""The routed-expert layer alone, forward and backward, at each torso cell's
widths and tokens: what one tree's layer reads and how long it takes, so
that two trees — the parent's gather-first layer and the in-loop gathers of
PR 38 — are held to each other bit for bit and clock for clock on one chip.

    run DIR       in the root of a checkout: each case's outputs (y, and the
                  gradients of x and of every parameter of the layer under
                  ``sum(sin(y))``) and its ``load`` into ``DIR/<case>.npz``
                  (an array over 256 MiB as its sha256); milliseconds a
                  call (median and least of REPEATS) on the TPU only
    compare A B   each case's arrays of A against B: equal to the bit, or the
                  largest difference and where; with ``torso.gathered_rows``
                  of the load against ``padded_pairs``. Prints one JSON line
                  and writes ``chiprun_out/expert_layer_forms.json``

The cases: the three torso cells' expert layers as their configurations cut
them (the router's full width, the held experts, B·T tokens), and one whose
chip holds every expert (GLM-4.7-Flash's 64, top-4): the live share near 1,
where P / R gathers of R rows replace one of P. A CPU runs the tiny presets
and prints no time. Inputs are seeded: x ~ N(0, 1) and the layer's own
initialisation, so the routing is that of a fresh layer, not of a cell's.

    chiprun -- bash -c 'cd _scratch/parent && python ../../scripts/expert_layer_forms.py run /tmp/p;
                        python scripts/expert_layer_forms.py run /tmp/c;
                        python scripts/expert_layer_forms.py compare /tmp/p /tmp/c'
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())        # the checkout this is run from, not this file's

import jax
import jax.numpy as jnp
import numpy as np

from d4pg_tpu.models import torso as T

REPEATS = 12
DIGEST_ABOVE = 2 ** 28      # bytes: a larger array is kept as its sha256
CELLS = {"glm": "humanoid_glm47flash_ep8.learn_per_ctx32",
         "keye": "humanoid_keyevl2_ep8.learn_per_ctx8k",
         "qwen": "humanoid_qwen3next_ep32.learn_per_lin8k"}


def cases(tiny: bool) -> dict:
    """``{case: (torso config, tokens)}`` from the cells' own argv."""
    from cellbench import manifest as mf
    from train import build_parser, config_from_args

    out = {}
    for name, cell_name in CELLS.items():
        cell = mf.cell(*mf.load(), cell_name)
        argv = list(cell.config["argv"]) + list(cell.traffic["argv"])
        if tiny:
            argv = [a if a != "Humanoid-v4" else "pendulum" for a in argv]
            argv += cell.config["rehearsal_argv"]
        cfg = config_from_args(build_parser().parse_args(argv))
        out[name] = cfg.agent.torso, cfg.batch_size * cfg.agent.torso.window
    glm, tokens = out["glm"]
    out["glm_every_expert"] = dataclasses.replace(
        glm, experts_first=0, experts_held=glm.n_routed_experts), tokens
    return out


def run(out_dir: str) -> dict:
    on_tpu = jax.devices()[0].platform == "tpu"
    os.makedirs(out_dir, exist_ok=True)
    said = {}
    for name, (cfg, tokens) in cases(tiny=not on_tpu).items():
        p = T._block_init(cfg, jax.random.PRNGKey(0), moe=True)["ffn"]
        x = jax.random.normal(jax.random.PRNGKey(1), (tokens, cfg.hidden_size))

        @jax.jit
        def both(p, x, cfg=cfg):
            def loss(p, x):
                y, (load, _) = T.expert_layer(cfg, p, x)
                return jnp.sum(jnp.sin(y)), (y, load)
            (_, (y, load)), grads = jax.value_and_grad(
                loss, argnums=(0, 1), has_aux=True)(p, x)
            return y, load, grads

        y, load, grads = jax.block_until_ready(both(p, x))
        times = []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            jax.block_until_ready(both(p, x))
            times.append(1e3 * (time.perf_counter() - t0))
        times.sort()
        arrays = {"y": y, "load": load, "d_x": grads[1]}
        for path, leaf in jax.tree_util.tree_flatten_with_path(grads[0])[0]:
            arrays["d_" + "/".join(str(getattr(k, "key", k)) for k in path)] = leaf
        np.savez(os.path.join(out_dir, name + ".npz"), **{
            k: v if v.nbytes <= DIGEST_ABOVE else np.array(hashlib.sha256(v.tobytes()).hexdigest())
            for k, v in jax.device_get(arrays).items()})
        said[name] = {"tokens": tokens, "padded_pairs": cfg.padded_pairs(tokens),
                      "block_rows": cfg.expert_block_rows}
        if on_tpu:              # a CPU's clock is no device time
            said[name].update(ms_forward_and_backward=times[len(times) // 2], ms_min=times[0])
        print(json.dumps({name: said[name]}), flush=True)
    return said


def compare(a_dir: str, b_dir: str) -> dict:
    on_tpu = jax.devices()[0].platform == "tpu"
    out = {"platform": jax.devices()[0].platform, "device": jax.devices()[0].device_kind}
    for name, (cfg, tokens) in cases(tiny=not on_tpu).items():
        a, b = np.load(os.path.join(a_dir, name + ".npz")), np.load(os.path.join(b_dir, name + ".npz"))
        differ = {}
        for key in a.files:
            if not np.array_equal(a[key], b[key]):
                if a[key].dtype.kind == "U":            # digests: no place to point at
                    differ[key] = "sha256 differs"
                    continue
                gap = np.abs(a[key].astype(np.float64) - b[key])
                at = np.unravel_index(np.argmax(gap), gap.shape)
                differ[key] = {"max_abs": float(gap[at]), "at": [int(i) for i in at],
                               "of_largest": float(np.abs(a[key]).max())}
        load = a["load"]
        rows = T.gathered_rows(cfg, load)
        out[name] = {"bit_equal": not differ, "differ": differ,
                     "padded_pairs": cfg.padded_pairs(tokens), "pairs": int(load.sum()),
                     "gathered_rows": int(rows), "live_share": float(rows / cfg.padded_pairs(tokens)),
                     "describe": T.describe_experts(cfg, tokens)}
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "expert_layer_forms.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return out


def main(argv) -> int:
    if argv[:1] == ["run"] and len(argv) == 2:
        run(argv[1])
    elif argv[:1] == ["compare"] and len(argv) == 3:
        compare(argv[1], argv[2])
    else:
        print(__doc__, file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
