"""Training CLI.

Flag surface covers the reference's 19 argparse flags (``main.py:31-56``)
with TPU-native equivalents: ``--n-workers`` becomes ``--num-envs`` (on-device
vectorized actors) and ``--dp`` (synchronous data-parallel devices, replacing
Hogwild workers); ``--multithread`` is gone (the single-process design is
always "multithreaded" via async dispatch).

SIGTERM/SIGINT trigger a graceful preemption: the current dispatch
finishes, a full checkpoint (+ replay snapshot if ``--snapshot-replay``)
lands, and the process exits 75 — the same "restart me with --resume"
contract as the RSS watchdog, so a TPU-VM preemption notice loses nothing
since the last periodic save. A second signal hard-kills.

Examples:
    python train.py --env pendulum --total-steps 50000
    python train.py --env pointmass_goal --her --n-step 1
    python train.py --env pendulum --dp 8 --batch-size 512   # 8-chip DP
    python train.py --env Pendulum-v1 --log-dir runs/p1 \
        --export-bundle runs/p1/bundle     # package for d4pg_tpu.serve
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import threading

from d4pg_tpu.agent.state import D4PGConfig
from d4pg_tpu.config import TrainConfig
from d4pg_tpu.models.critic import DistConfig
from d4pg_tpu.models.torso import TORSO_PRESETS


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="TPU-native D4PG")
    # reference-parity flags (main.py:31-56)
    p.add_argument("--env", default="pendulum",
                   help="pendulum | pointmass_goal | any gymnasium id")
    p.add_argument("--rmsize", "--replay-capacity", dest="replay_capacity",
                   type=int, default=None,
                   help="replay ring capacity (default: env preset's cap, "
                        "else 1M); an explicit value always wins")
    p.add_argument("--tau", type=float, default=0.001)
    p.add_argument("--bsize", "--batch-size", dest="batch_size", type=int, default=256)
    p.add_argument("--gamma", type=float, default=0.99)
    p.add_argument("--max-steps", dest="max_episode_steps", type=int, default=None)
    p.add_argument("--action-repeat", type=int, default=1,
                   help="dm_control only: apply each action for N control "
                        "steps, summing rewards (DrQ convention; 4 for "
                        "pixel swingup)")
    p.add_argument("--warmup", dest="warmup_steps", type=int, default=1_000)
    p.add_argument("--p-replay", "--prioritized", dest="prioritized",
                   action=argparse.BooleanOptionalAction, default=True)
    p.add_argument("--v-min", type=float, default=None)
    p.add_argument("--v-max", type=float, default=None)
    p.add_argument("--n-atoms", type=int, default=51)
    p.add_argument("--n-step", "--n-steps", dest="n_step", type=int, default=3)
    p.add_argument("--her", action="store_true")
    p.add_argument("--her-k", type=int, default=4)
    p.add_argument("--log-dir", default=None)
    p.add_argument("--ou-theta", type=float, default=0.15)
    p.add_argument("--ou-sigma", type=float, default=0.2)
    p.add_argument("--ou-mu", type=float, default=0.0)
    p.add_argument("--noise", choices=["gaussian", "ou"], default="gaussian")
    p.add_argument("--noise-epsilon", type=float, default=0.3)
    p.add_argument("--noise-decay-steps", type=int, default=0,
                   help="env steps to linearly anneal exploration scale to "
                        "--noise-scale-final (0 = constant, the reference's "
                        "effective behavior, SURVEY.md quirk #10)")
    p.add_argument("--noise-scale-final", type=float, default=0.1)
    p.add_argument("--random-eps", type=float, default=0.0,
                   help="HER-DDPG exploration mixture: probability of "
                        "replacing a collection action with a uniform draw "
                        "from the box (Andrychowicz et al. 2017 §4.4; "
                        "breaks the tanh-corner collapse on sparse goal "
                        "tasks). 0 = off")
    p.add_argument("--action-l2", type=float, default=0.0,
                   help="actor-loss coefficient on mean(a^2) (HER-DDPG "
                        "action regularizer, same paper). 0 = off")
    p.add_argument("--obs-norm", action="store_true",
                   help="running observation normalization at the data "
                        "boundary: clip((x-mean)/std, +-5), Welford stats "
                        "per sampled batch (HER-DDPG convention; host "
                        "state-feature envs only)")
    # TPU-native flags
    p.add_argument("--num-envs", type=int, default=16,
                   help="vectorized on-device exploration envs, or host actor "
                        "pool size for gymnasium envs (was --n_workers)")
    p.add_argument("--async-collect", action="store_true",
                   help="decouple actors from the learner: collection runs in "
                        "a background thread against published actor params")
    p.add_argument("--publish-interval", type=int, default=10,
                   help="grad steps between actor-param publications (async)")
    p.add_argument("--on-device", action="store_true",
                   help="fully on-device training (pure-JAX envs): rollout + "
                        "n-step collapse + device replay + K train steps as "
                        "one XLA program per iteration (BASELINE config 5)")
    p.add_argument("--async-writeback", action="store_true",
                   help="flush PER priorities from a background thread with "
                        "one batched device fetch per wake (the sync path "
                        "fetches once per dispatch)")
    p.add_argument("--dp", type=int, default=None,
                   help="data-parallel device count (None = single device)")
    p.add_argument("--dp-hogwild", action="store_true",
                   help="async-DP staleness emulation: each replica runs "
                        "the K-step dispatch window on its own diverging "
                        "param copy, then one param pmean resyncs (the "
                        "reference's Hogwild trade, staleness bounded by "
                        "K = --steps-per-dispatch)")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--hidden-sizes", default=None,
                   help="comma-separated MLP trunk widths (default "
                        "256,256,256); must match the checkpoint when "
                        "resuming or exporting a bundle")
    p.add_argument("--torso", choices=sorted(TORSO_PRESETS), default=None,
                   help="sequence torso under the critic (models/torso.py): "
                        "actor and critic MLPs become heads on its output over "
                        "a window of the last --torso-window ring rows; needs "
                        "--replay-placement device on one device")
    p.add_argument("--torso-layers", type=int, default=None, metavar="N",
                   help="blocks of the torso kept, the leading dense ones "
                        "included (default: the preset's depth)")
    p.add_argument("--torso-experts-held", default=None, metavar="FIRST:COUNT",
                   help="the routed experts this learner holds of each expert "
                        "layer, one share of an expert-parallel layer (default: "
                        "all of them); the router keeps its full width")
    p.add_argument("--torso-window", type=int, default=None, metavar="T",
                   help="history window in ring rows (default: the preset's)")
    p.add_argument("--torso-span", choices=("episode", "stream"), default=None,
                   help="what a history window may span: the drawn row's "
                        "episode (the default), or its whole stream — the "
                        "context is then the stream's last T rows across "
                        "episode ends (in-context RL); a torso whose attention "
                        "runs under an indexer, or the hybrid stack")
    p.add_argument("--twin-critic", action="store_true",
                   help="clipped double-Q (TD3-style) distributional twin "
                        "critics; fixes the single-critic plateau on "
                        "Hopper/Walker2d-class tasks")
    p.add_argument("--critic-head", choices=["categorical", "scalar", "mixture_gaussian"],
                   default="categorical",
                   help="critic value-distribution head: categorical (C51, "
                        "the default and the oracle), scalar (plain DDPG), "
                        "or mixture_gaussian (MoG with Gauss-Hermite CE "
                        "Bellman backup, ops/mog.py — the head the paper "
                        "names and the reference leaves TODO-empty)")
    p.add_argument("--num-mixtures", type=int, default=5,
                   help="mixture components M for --critic-head "
                        "mixture_gaussian")
    p.add_argument("--critic-ensemble", type=int, default=0,
                   help="REDQ-style critic ensemble width E (0 = off): E "
                        "independent critics stacked on a mesh-shardable "
                        "axis, Bellman targets min over a random subset, "
                        "actor ascends the ensemble mean; mutually "
                        "exclusive with --twin-critic")
    p.add_argument("--ensemble-min-targets", type=int, default=2,
                   help="size M of the random target subset the ensemble "
                        "backup minimizes over (REDQ in-target "
                        "minimization; M=E recovers min-over-all)")
    p.add_argument("--compute-dtype", choices=["float32", "bfloat16"], default="float32")
    p.add_argument("--projection", choices=["xla", "pallas", "pallas_fused"],
                   default="xla",
                   help="categorical projection backend: pallas = custom TPU "
                        "projection kernel; pallas_fused = projection + "
                        "log-softmax CE + priorities in ONE kernel (the "
                        "projected distribution never touches HBM)")
    p.add_argument("--total-steps", type=int, default=100_000,
                   help="learner grad steps to run")
    p.add_argument("--env-steps-per-train-step", type=float, default=1.0,
                   help="collect:train ratio (env steps per grad step); "
                        "enforced from both sides in --async-collect mode")
    p.add_argument("--pool-start-method", choices=["spawn", "fork", "forkserver"],
                   default="spawn",
                   help="actor-pool worker start method; spawn keeps children "
                        "JAX-free, fork starts faster on few-core hosts")
    p.add_argument("--actor-device", choices=["auto", "cpu", "default"],
                   default="auto",
                   help="backend for host-env collection/eval forwards; auto "
                        "= CPU whenever the learner is on an accelerator "
                        "(a host env acts one observation at a time; the "
                        "actor MLP is microseconds on CPU)")
    p.add_argument("--steps-per-dispatch", type=int, default=1,
                   help="grad steps fused into one device dispatch (K>1 "
                        "amortizes dispatch latency; PER priorities update "
                        "once per dispatch)")
    p.add_argument("--replay-placement", choices=["host", "device", "hybrid"],
                   default="host",
                   help="where sampled batches live: host = per-dispatch "
                        "H2D batch upload (the seeded oracle); device = "
                        "HBM-resident ring + fused megastep with in-kernel "
                        "draws — uniform AND prioritized (the PER segment "
                        "tree is device-resident too) — and ZERO "
                        "per-grad-step transfers; hybrid = LEGACY PER: "
                        "indices/IS-weights from the host sum-tree ([K,B] "
                        "int32 up, [K,B] priorities back), kept as the "
                        "host-tree oracle (docs/data_plane.md)")
    p.add_argument("--device-tree-backend", choices=["xla", "pallas"],
                   default="xla",
                   help="device-PER descent implementation: xla = jnp "
                        "log-depth gather descent (reference + oracle); "
                        "pallas = the same walk without gathers "
                        "(ops/pallas_tree.py), same leaves; compiled on tpu, "
                        "interpreted under JAX_PLATFORMS=cpu, an error "
                        "elsewhere")
    p.add_argument("--prefetch", action="store_true",
                   help="double-buffered replay->device pipeline: batch N+1 "
                        "is host-sampled and its device_put started while "
                        "the device runs step N, so sampling + H2D transfer "
                        "leave the critical path (one dispatch of priority/"
                        "freshness staleness, same class as "
                        "--steps-per-dispatch)")
    p.add_argument("--batch-scale", type=int, default=1, metavar="S",
                   help="the large-batch recipe in one knob: batch x S, "
                        "lr x S (linear scaling), PER-beta anneal / S "
                        "(tracks data seen), warmup x S, "
                        "steps-per-dispatch / S — derived from the B=256 "
                        "baseline after env presets (docs/data_plane.md "
                        "'Large-batch recipe')")
    p.add_argument("--fused-descent", action="store_true",
                   help="fuse the device-PER tree descent INTO the scan "
                        "body's loss kernel: one Pallas program per grad "
                        "step computes loss(t) + the step-(t+1) descent "
                        "(software pipelining; byte-identical to the "
                        "separate-programs tier). Requires "
                        "--replay-placement device --per --projection "
                        "pallas_fused, single device")
    p.add_argument("--ingest-prefetch", action="store_true",
                   help="double-buffer the ring ingest: gather + H2D the "
                        "next flush's first chunk right after each "
                        "megastep dispatch, overlapping the transfer with "
                        "the in-flight compute (device placement; ignored "
                        "— declared — elsewhere)")
    p.add_argument("--eval-interval", type=int, default=2_000)
    p.add_argument("--eval-episodes", type=int, default=10)
    p.add_argument("--concurrent-eval", dest="concurrent_eval",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="host-env eval runs in a dedicated thread on a "
                        "published param copy (reference evaluator process) "
                        "so eval crossings cost zero grad steps")
    p.add_argument("--checkpoint-interval", type=int, default=10_000)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--snapshot-replay", action="store_true",
                   help="save/restore the replay buffer with checkpoints so "
                        "--resume keeps its experience")
    p.add_argument("--lr-actor", type=float, default=1e-4)
    p.add_argument("--lr-critic", type=float, default=1e-4)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tree-backend", choices=["auto", "numpy", "native"], default="auto")
    p.add_argument("--ring-dtype", choices=["auto", "float32", "bfloat16"],
                   default="auto",
                   help="--on-device HBM ring row dtype for flat obs; "
                        "bfloat16 halves the per-sample gather bytes "
                        "(pixel rings always store uint8)")
    p.add_argument("--transfer-dtype", choices=["float32", "bfloat16", "uint8"],
                   default="float32",
                   help="host->device batch wire format for observations; "
                        "bfloat16 halves transfer bytes on wide-obs "
                        "configs, uint8 (pixel envs) ships the replay's "
                        "stored bytes raw at 1/4 the f32 traffic")
    p.add_argument("--export-bundle", default=None, metavar="DIR",
                   help="instead of training: package this run's champion "
                        "actor (checkpoints/best_actor.npz, else the "
                        "latest Orbax step) + config + action bounds + "
                        "obs-norm stats into a serving bundle at DIR for "
                        "python -m d4pg_tpu.serve, then exit")
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of grad steps 10-60 here")
    # networked collection fleet (d4pg_tpu/fleet, docs/fleet.md)
    p.add_argument("--fleet-listen", type=int, default=None, metavar="PORT",
                   help="run the experience-ingest server on PORT (0 = "
                        "ephemeral, printed at startup): remote actor hosts "
                        "(python -m d4pg_tpu.fleet.actor) stream n-step "
                        "windows into replay — alongside local collection, "
                        "or instead of it with --num-envs 0")
    p.add_argument("--fleet-host", default="0.0.0.0", metavar="ADDR",
                   help="ingest bind address (default 0.0.0.0 so remote "
                        "actor hosts can reach it; 127.0.0.1 = loopback-"
                        "only fleet)")
    p.add_argument("--fleet-bundle", default=None, metavar="DIR",
                   help="publish the acting bundle here for fleet actors "
                        "(atomic re-export every --fleet-publish-interval "
                        "grad steps, bumping the bundle generation; actors "
                        "hot-swap on the bundle.json mtime)")
    p.add_argument("--fleet-publish-interval", type=int, default=200,
                   help="grad steps between fleet bundle publications")
    p.add_argument("--fleet-max-gen-lag", type=int, default=1,
                   help="ingest drops windows produced under a bundle (or "
                        "obs-norm stats) generation older than current "
                        "minus this lag")
    p.add_argument("--fleet-wire-dtype", choices=["auto", "float32", "bfloat16"],
                   default="auto",
                   help="fleet ingest wire encoding for flat observation "
                        "rows: auto/float32 = byte-identical f32; bfloat16 "
                        "halves wire bytes with a declared bf16 round "
                        "(pixel envs always negotiate u8-quantized rows)")
    # league membership (d4pg_tpu/league, docs/league.md): set by the
    # controller when it spawns/forks this learner — never by hand
    p.add_argument("--variant-id", type=int, default=None,
                   help="league variant id this learner IS: stamped onto "
                        "every metrics.jsonl row + trainer_meta.json (the "
                        "league controller's fork-resume attestation) and "
                        "negotiated in the fleet HELLO (actors assigned "
                        "elsewhere are refused)")
    p.add_argument("--league-generation", type=int, default=0,
                   help="league generation that spawned/forked this "
                        "learner (rides the metrics rows next to "
                        "--variant-id)")
    p.add_argument("--chaos", default=None, metavar="PLAN",
                   help="deterministic fault injection (d4pg_tpu/chaos.py): "
                        "';'-separated site@count[:arg][#actor] entries, "
                        "e.g. 'seed=7;env_raise@40;worker_kill@12#1;"
                        "ckpt_truncate@1;wb_stall@3:0.5' — proves the "
                        "supervisor/restart/fallback paths on demand")
    p.add_argument("--pool-step-timeout", dest="pool_step_timeout_s",
                   type=float, default=60.0,
                   help="supervised actor pool: seconds a worker may take "
                        "to answer one step before it is declared hung and "
                        "restarted (monotonic deadline)")
    p.add_argument("--debug-guards", action="store_true",
                   help="runtime invariant guards (d4pg_tpu/analysis): "
                        "recompile sentinel on every jitted entry point, "
                        "transfer guard around the steady-state dispatch, "
                        "staging ledger on replay/pool staging slots — "
                        "guard trips raise immediately instead of "
                        "silently corrupting or taxing the run")
    p.add_argument("--max-rss-gb", type=float, default=0.0,
                   help="RSS watchdog: past this limit the trainer "
                        "checkpoints and exits cleanly so a supervisor can "
                        "--resume (0 = off); guards against host OOM kills "
                        "and leaky device-client libraries")
    # multi-host bring-up (jax.distributed): every host runs the same
    # command; after initialize, jax.devices() spans the whole cluster and
    # make_mesh builds one global mesh (docs/multihost.md has the recipe).
    # Env-var fallbacks let pod launchers template one command line.
    p.add_argument("--distributed", action="store_true",
                   help="initialize jax.distributed with Cloud-TPU-pod "
                        "autodetection (metadata server supplies "
                        "coordinator/process ids)")
    p.add_argument("--coordinator",
                   default=os.environ.get("D4PG_COORDINATOR"),
                   help="coordinator address host:port for explicit "
                        "clusters (env D4PG_COORDINATOR)")
    p.add_argument("--num-processes", type=int,
                   default=int(os.environ.get("D4PG_NUM_PROCESSES", "0")) or None,
                   help="total process count (env D4PG_NUM_PROCESSES)")
    p.add_argument("--process-id", type=int,
                   default=int(os.environ.get("D4PG_PROCESS_ID", "-1"))
                   if os.environ.get("D4PG_PROCESS_ID") is not None else None,
                   help="this process's rank (env D4PG_PROCESS_ID)")
    return p


def config_from_args(args: argparse.Namespace) -> TrainConfig:
    dist = DistConfig(
        kind=args.critic_head,
        num_atoms=args.n_atoms,
        num_mixtures=args.num_mixtures,
        v_min=args.v_min if args.v_min is not None else -10.0,
        v_max=args.v_max if args.v_max is not None else 10.0,
    )
    agent = D4PGConfig(
        dist=dist,
        gamma=args.gamma,
        n_step=args.n_step,
        tau=args.tau,
        lr_actor=args.lr_actor,
        lr_critic=args.lr_critic,
        noise_kind=args.noise,
        noise_epsilon=args.noise_epsilon,
        noise_decay_steps=args.noise_decay_steps,
        noise_scale_final=args.noise_scale_final,
        random_eps=args.random_eps,
        action_l2=args.action_l2,
        ou_theta=args.ou_theta,
        ou_sigma=args.ou_sigma,
        ou_mu=args.ou_mu,
        prioritized=args.prioritized,
        compute_dtype=args.compute_dtype,
        projection_backend=args.projection,
        twin_critic=args.twin_critic,
        critic_ensemble=args.critic_ensemble,
        ensemble_min_targets=args.ensemble_min_targets,
    )
    if args.hidden_sizes:
        agent = dataclasses.replace(
            agent,
            hidden_sizes=tuple(
                int(h) for h in str(args.hidden_sizes).split(",") if h.strip()
            ),
        )
    if args.torso:
        changes = {"row_stride": max(1, args.num_envs)}   # the writer's env interleave
        if args.torso_layers is not None:
            changes["num_hidden_layers"] = args.torso_layers
        if args.torso_experts_held is not None:
            first, count = (int(v) for v in args.torso_experts_held.split(":"))
            changes.update(experts_first=first, experts_held=count)
        if args.torso_window is not None:
            changes["window"] = args.torso_window
        preset = TORSO_PRESETS[args.torso]
        if args.torso_span is not None and args.torso_span != preset.span:
            if "span" not in {f.name for f in dataclasses.fields(preset)}:
                raise SystemExit(
                    f"--torso-span {args.torso_span}: --torso {args.torso} states no "
                    "span (its windows end where the episode began)")
            changes["span"] = args.torso_span
        agent = dataclasses.replace(agent, torso=dataclasses.replace(preset, **changes))
    # run-identity log dir (reference main.py:59-66)
    log_dir = args.log_dir or (
        f"runs/{args.env}_{'PER' if args.prioritized else 'UNI'}"
        f"{'_HER' if args.her else ''}_n{args.n_step}_{args.num_envs}env"
    )
    cfg = TrainConfig(
        env=args.env,
        max_episode_steps=args.max_episode_steps,
        action_repeat=args.action_repeat,
        num_envs=args.num_envs,
        her=args.her,
        her_k=args.her_k,
        obs_norm=args.obs_norm,
        async_collect=args.async_collect,
        publish_interval=args.publish_interval,
        total_steps=args.total_steps,
        warmup_steps=args.warmup_steps,
        batch_size=args.batch_size,
        steps_per_dispatch=args.steps_per_dispatch,
        prefetch=args.prefetch,
        batch_scale=args.batch_scale,
        fused_descent=args.fused_descent,
        ingest_prefetch=args.ingest_prefetch,
        replay_placement=args.replay_placement,
        env_steps_per_train_step=args.env_steps_per_train_step,
        pool_start_method=args.pool_start_method,
        actor_device=args.actor_device,
        async_priority_writeback=args.async_writeback,
        replay_capacity=args.replay_capacity,
        prioritized=args.prioritized,
        n_step=args.n_step,
        tree_backend=args.tree_backend,
        device_tree_backend=args.device_tree_backend,
        transfer_dtype=args.transfer_dtype,
        ring_dtype=args.ring_dtype,
        eval_interval=args.eval_interval,
        eval_episodes=args.eval_episodes,
        concurrent_eval=args.concurrent_eval,
        log_dir=log_dir,
        checkpoint_interval=args.checkpoint_interval,
        resume=args.resume,
        snapshot_replay=args.snapshot_replay,
        profile_dir=args.profile_dir,
        fleet_listen=args.fleet_listen,
        fleet_host=args.fleet_host,
        fleet_bundle=args.fleet_bundle,
        fleet_publish_interval=args.fleet_publish_interval,
        fleet_max_gen_lag=args.fleet_max_gen_lag,
        fleet_wire_dtype=args.fleet_wire_dtype,
        variant_id=args.variant_id,
        league_generation=args.league_generation,
        debug_guards=args.debug_guards,
        chaos=args.chaos,
        pool_step_timeout_s=args.pool_step_timeout_s,
        max_rss_gb=args.max_rss_gb,
        dp=args.dp,
        dp_hogwild=args.dp_hogwild,
        tp=args.tp,
        agent=agent,
        seed=args.seed,
    )
    # Env preset always applies (dims, v-range, pixel wiring, pixel-sized
    # replay cap); explicit --v-min/--v-max then beat it. Explicit --rmsize
    # beats the preset cap inside apply_env_preset (non-default wins).
    # Batch-scale then derives the large-batch recipe from the preset-
    # resolved baseline (preset first so the rules scale FINAL values).
    from d4pg_tpu.config import apply_batch_scale, apply_env_preset

    cfg = apply_batch_scale(apply_env_preset(cfg))
    if args.v_min is not None or args.v_max is not None:
        dist = dataclasses.replace(
            cfg.agent.dist,
            v_min=args.v_min if args.v_min is not None else cfg.agent.dist.v_min,
            v_max=args.v_max if args.v_max is not None else cfg.agent.dist.v_max,
        )
        cfg = dataclasses.replace(
            cfg, agent=dataclasses.replace(cfg.agent, dist=dist)
        )
    return cfg


def export_bundle_from_run(cfg: TrainConfig, bundle_dir: str) -> str:
    """Package a trained run into a serving bundle (``--export-bundle``).

    Prefers the keep-best champion (``checkpoints/best_actor.npz`` — the
    policy ``best_eval.json`` attests); falls back to the actor slice of
    the latest Orbax full-state step. Action bounds come from the live
    env's ``NormalizeAction`` when the env can be constructed here (host
    adapters expose their Box); pure-JAX envs and unconstructible envs get
    the canonical (−1, 1) box the policy acts in natively.
    """
    import json

    import jax

    from d4pg_tpu.runtime.checkpoint import load_trainer_meta
    from d4pg_tpu.serve.bundle import actor_template, export_bundle

    env = None
    try:
        from d4pg_tpu.envs import make_env

        env = make_env(cfg.env, cfg.max_episode_steps, cfg.action_repeat)
    except Exception as e:
        print(
            f"[export-bundle] could not construct env {cfg.env!r} ({e}); "
            "using preset dims and canonical (-1,1) action bounds"
        )
    low = high = None
    if env is not None:
        from d4pg_tpu.runtime.trainer import _reconcile_config

        cfg = _reconcile_config(cfg, env)
        norm = getattr(env, "_normalize", None)
        if norm is not None:
            low, high = norm.low, norm.high
    agent_cfg = cfg.agent
    ckpt_dir = os.path.join(cfg.log_dir, "checkpoints")
    best_npz = os.path.join(ckpt_dir, "best_actor.npz")
    meta = load_trainer_meta(cfg.log_dir)
    provenance = {
        "env": cfg.env,
        "log_dir": os.path.abspath(cfg.log_dir),
        "env_steps": meta.get("env_steps"),
    }
    obs_norm_state = meta.get("obs_norm")
    if os.path.exists(best_npz):
        from d4pg_tpu.runtime.trainer import load_best_actor

        params = load_best_actor(cfg.log_dir, actor_template(agent_cfg))
        provenance["source"] = "best_actor.npz"
        best_json = os.path.join(cfg.log_dir, "best_eval.json")
        if os.path.exists(best_json):
            try:
                with open(best_json) as f:
                    provenance["best_eval"] = json.load(f)
            except (OSError, ValueError):
                pass
        # Pair the champion with the normalizer statistics captured WHEN it
        # was scored (best_obs_norm.json, written beside best_actor.npz) —
        # trainer_meta.json keeps drifting with later collection, which is
        # the wrong μ/σ for these params.
        best_norm = os.path.join(ckpt_dir, "best_obs_norm.json")
        if os.path.exists(best_norm):
            with open(best_norm) as f:
                obs_norm_state = json.load(f)
        elif cfg.obs_norm:
            print(
                "[export-bundle] warning: no best_obs_norm.json next to "
                "best_actor.npz (run predates the paired snapshot); using "
                "trainer_meta.json statistics, which may postdate the "
                "champion params"
            )
    else:
        from d4pg_tpu.agent import create_train_state
        from d4pg_tpu.runtime.checkpoint import CheckpointManager

        ckpt = CheckpointManager(ckpt_dir)
        step = ckpt.latest_step()
        if step is None:
            ckpt.close()
            raise SystemExit(
                f"--export-bundle: no best_actor.npz and no Orbax "
                f"checkpoint under {ckpt_dir} — train (and checkpoint) first"
            )
        state = ckpt.restore(
            create_train_state(agent_cfg, jax.random.PRNGKey(cfg.seed)), step
        )
        ckpt.close()
        params = jax.device_get(state.actor_params)
        provenance["source"] = f"orbax:{step}"
        provenance["grad_steps"] = step
    if cfg.obs_norm and obs_norm_state is None:
        raise SystemExit(
            "--export-bundle: run is flagged --obs-norm but neither "
            "best_obs_norm.json nor trainer_meta.json carries normalizer "
            "statistics; export would serve the net un-normalized inputs"
        )
    out = export_bundle(
        bundle_dir,
        agent_cfg,
        params,
        action_low=low,
        action_high=high,
        obs_norm_state=obs_norm_state,
        meta=provenance,
    )
    if env is not None and hasattr(env, "close"):
        env.close()
    print(
        f"[export-bundle] wrote {out} "
        f"(source={provenance['source']}, obs_dim={agent_cfg.obs_dim}, "
        f"action_dim={agent_cfg.action_dim}, "
        f"obs_norm={'yes' if obs_norm_state else 'no'})"
    )
    return out


def install_preemption_handlers(stop_callback) -> None:
    """SIGTERM/SIGINT → graceful preemption via ``stop_callback`` (which
    must be signal-safe: it only sets an event). First signal arms the
    checkpoint-and-exit-75 path, second hard-kills — the arm-first /
    restore-disposition / guarded-print ordering lives in
    :func:`d4pg_tpu.utils.signals.install_graceful_signals`."""
    from d4pg_tpu.utils.signals import install_graceful_signals

    install_graceful_signals(
        stop_callback,
        "[signal] {sig}: checkpointing and exiting 75 "
        "(--resume restarts; second signal hard-kills)",
    )


def main(argv=None):
    """Run the CLI. Returns the (closed) :class:`Trainer` of a host-loop
    training run so a caller that drives the normal entry point — tests,
    ``chip_smoke.py`` — can inspect what it built (step counters, recompile
    sentinel, where ring and tree were placed); ``None`` for
    ``--export-bundle`` and ``--on-device``."""
    args = build_parser().parse_args(argv)
    from d4pg_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    if args.debug_guards:
        # Arm the lock-order witness BEFORE any guarded component builds
        # its locks (named_lock/named_condition wrap only when enabled);
        # Trainer.close checks the recorded nesting against the committed
        # benchmarks/lock_order_graph.json.
        from d4pg_tpu.analysis import flowledger, lockwitness

        lockwitness.enable()
        # The conservation ledger rides the same flag: drain/close paths
        # (fleet ingest, mirror tap) check their accounting identities.
        flowledger.enable()
    if args.distributed or args.coordinator or (args.num_processes or 0) > 1:
        # Before config_from_args/Trainer import anything that touches
        # devices: the backend binds to the local slice at first use.
        from d4pg_tpu.parallel import initialize_distributed

        info = initialize_distributed(
            args.coordinator, args.num_processes, args.process_id,
            autodetect=args.distributed,
        )
        print(f"[distributed] {info}")
    else:
        info = None
    from d4pg_tpu.runtime import Trainer

    cfg = config_from_args(args)
    if args.export_bundle:
        if cfg.agent.torso is not None:
            raise SystemExit(
                "--export-bundle: a torso actor acts on each session's last "
                f"{cfg.agent.torso.window} observations through the critic's "
                "torso; d4pg_tpu.serve holds no per-session state and a "
                "bundle of the actor head alone would serve garbage — "
                "refused (stateful serving sessions: ROADMAP B-m3)")
        export_bundle_from_run(cfg, args.export_bundle)
        return None
    if info is not None:
        # Surface the actual bring-up topology to the config: negotiation
        # validates the multi-host combination (device placement + dp
        # divisibility), and the Trainer sizes per-host buffers from it.
        cfg = dataclasses.replace(
            cfg, num_processes=int(info["process_count"])
        )
    if info is not None and info["process_index"] != 0:
        # Every process runs the same command line; secondary hosts write
        # metrics to their own subdir so a shared filesystem sees no
        # clobbering, but SHARED artifacts (checkpoints, trainer meta,
        # replay snapshot) resolve through run_root — the canonical run
        # dir process 0 owns and is the only writer of.
        cfg = dataclasses.replace(
            cfg,
            run_root=cfg.log_dir,
            log_dir=os.path.join(
                cfg.log_dir, f"worker{info['process_index']}"
            ),
        )
    print(f"config: {cfg}")
    # THE CLI validation call site (replay/source.py): one negotiation
    # pass over the capability table replaces the old per-flag refusal
    # ladder — the Trainer re-validates post-env with the env kind
    # resolved, against the SAME table, so the two can never drift.
    from d4pg_tpu.replay.source import validate_train_config

    try:
        validate_train_config(cfg, on_device=args.on_device)
    except ValueError as e:
        raise SystemExit(str(e))
    if args.on_device:
        from d4pg_tpu.runtime.on_device import run_on_device

        preempt_event = threading.Event()
        install_preemption_handlers(preempt_event.set)
        final = run_on_device(cfg, preempt_event=preempt_event)
        preempted = final.pop("_preempted", False)
        print(f"done: {final}")
        if preempted:
            sys.exit(75)  # rss-watchdog: checkpointed, restart with --resume
        return None
    trainer = Trainer(cfg)
    install_preemption_handlers(trainer.request_preemption)
    try:
        final = trainer.train()
        print(f"done: {final}")
    finally:
        trainer.close()
    if trainer.preempted:
        # EX_TEMPFAIL: "checkpointed, restart me with --resume" — a
        # supervisor loop keys on this to distinguish preemption (75) from
        # completion (0).
        sys.exit(75)
    return trainer


if __name__ == "__main__":
    main()
