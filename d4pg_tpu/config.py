"""One typed config covering every knob of the system.

Replaces the reference's argparse namespace + runtime mutation + hidden
in-code defaults (SURVEY.md §5 'config / flag system'): all 19 reference
flags (``main.py:31-56``) have an equivalent here, plus the defaults the
reference buries in code (lrs ``ddpg.py:19``, PER α/β/ε ``ddpg.py:81-87``,
warmup ``main.py:204``, cycle structure ``main.py:300-303``, Adam betas
``shared_adam.py:4``). Env presets replace ``configure_env_params``
(``main.py:84-99``).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional

from d4pg_tpu.agent.state import D4PGConfig


@dataclass(frozen=True)
class TrainConfig:
    """Full experiment configuration."""

    # environment
    env: str = "pendulum"
    max_episode_steps: Optional[int] = None  # None → env default
    # dm_control only (DrQ convention): each agent step applies the action
    # for N control steps, summing rewards; pixel obs render once per agent
    # step. Divides frames-to-solve by ~N for pixel tasks (repeat 4 is the
    # published setting for cartpole swingup).
    action_repeat: int = 1
    num_envs: int = 16                 # vectorized on-device actors
    her: bool = False                  # hindsight relabeling (goal envs)
    her_k: int = 4
    # Running observation normalization at the data boundary (HER-DDPG,
    # ops/obs_norm.py): clip((x−μ)/σ, ±5) applied to training batches and
    # acting/eval forwards; Welford stats folded once per OBSERVED env step
    # at collection time (updating per sampled batch would double-count
    # PER-favored transitions — see Trainer._ingest_obs).
    # Host (gymnasium/dm_control state) envs only; default off.
    obs_norm: bool = False

    # run shape (reference: epochs × 50 cycles × (16 episodes + 40 steps))
    total_steps: int = 100_000         # learner grad steps
    warmup_steps: int = 1_000          # env steps before learning (main.py:204)
    env_steps_per_train_step: float = 1.0  # collect:train ratio
    batch_size: int = 256
    # Grad steps fused into one device dispatch (lax.scan over K host-sampled
    # batches). K>1 amortizes per-dispatch overhead (between K=1 calls the
    # chip idles for the host launch). PER priorities go
    # stale within the K-step window (written back after the dispatch), the
    # same staleness class the reference accepts from Hogwild asynchrony.
    steps_per_dispatch: int = 1
    # Double-buffered replay→device input pipeline: dispatch N is fed from a
    # batch that was host-sampled — and whose device_put was started — while
    # dispatch N−1 ran on the device, so host sampling and the H2D transfer
    # disappear from the critical path (the input-side symmetric of the
    # async priority write-back). Cost: the staged batch reflects priorities
    # and replay contents as of one dispatch earlier — the same staleness
    # class as steps_per_dispatch>1, and strictly less than async_collect's.
    # Default off so existing runs are batch-for-batch identical.
    prefetch: bool = False
    # The large-batch flagship recipe (ISSUE 16): one knob S deriving the
    # whole wide-shape configuration from the B=256 baseline via
    # apply_batch_scale — batch ×S, linear-LR ×S (Goyal et al. 2017: S×
    # the data per gradient supports S× the step), PER-β anneal ÷S in
    # grad steps (each grad step now consumes S× the samples, so the
    # anneal tracks DATA seen, not steps taken), warmup ×S (the first
    # wide batch needs as many decorrelated rows as S baseline batches),
    # steps-per-dispatch ÷S (a wide batch already amortizes dispatch
    # latency — keep work per dispatch roughly constant). 1 = off,
    # byte-identical to before.
    batch_scale: int = 1
    # Fused descent-in-scan Pallas tier (ISSUE 16): the device-PER
    # megastep's scan body runs categorical loss + the NEXT step's tree
    # descent as ONE Pallas program (ops/pallas_fused_step.py) instead of
    # a separate whole-[K,B] descent up front. Byte-identical to the
    # separate-programs tier by construction; requires device placement +
    # PER + projection_backend=pallas_fused + categorical head, no dp
    # (negotiation declares the gaps).
    fused_descent: bool = False
    # Double-buffered ingest (ISSUE 16): right after each megastep
    # dispatch, pre-gather + device_put the next flush's first chunk
    # (DeviceRingSync.stage) so the H2D transfer overlaps the in-flight
    # compute instead of serializing before the next dispatch. Device
    # placement only; ignored (declared) elsewhere.
    ingest_prefetch: bool = False
    # Runtime invariant guards (d4pg_tpu/analysis): recompile sentinel on
    # every jitted entry point, transfer guard around steady-state
    # dispatch, staging ledger on every rotated host staging slot. Debug
    # mode — guard trips raise instead of silently corrupting/taxing the
    # run. Off by default (the ledger adds a lock per staged slot).
    debug_guards: bool = False

    # async actor/learner decoupling (host actor pool only): collection runs
    # in a background thread against periodically published actor params
    # while the learner trains — the BASELINE north-star "streaming batches
    # asynchronously" decomposition. The env:train ratio is enforced from
    # both sides (collector throttles ahead, learner waits when starved).
    async_collect: bool = False
    publish_interval: int = 10         # grad steps between param publications
    # Flush PER priorities from a background thread instead of blocking the
    # learner loop on the device→host fetch. The thread drains everything
    # queued since its last wake, concatenates on device, and pays ONE
    # fetch for the whole group — so it keeps up at any dispatch rate
    # (synchronous write-back caps the learner at one fetch per dispatch).
    # Priorities go a
    # few hundred grad steps stale at high rates — the same staleness class
    # as K-step dispatch and the reference's Hogwild asynchrony.
    async_priority_writeback: bool = False
    # Actor-pool worker start method. "spawn" keeps children JAX-free (safe
    # with an initialized TPU client); "fork" starts much faster on few-core
    # hosts since children inherit the parent's imports.
    pool_start_method: str = "spawn"
    # Supervised-pool failure handling (runtime/actor_pool.py): a worker
    # that misses this monotonic per-step reply deadline is treated as hung
    # — killed and restarted under jittered exponential backoff. Generous
    # by default: a false positive costs a worker restart plus a dropped
    # n-step window.
    pool_step_timeout_s: float = 60.0
    # Consecutive failures (crash/hang/failed restart) before a worker is
    # QUARANTINED: permanently masked out of the batch (the compiled batch
    # shape never changes; the effective batch shrinks). A completed step
    # resets the count.
    pool_max_worker_failures: int = 3
    # Chaos harness (d4pg_tpu/chaos.py): seeded deterministic fault-plan
    # spec, e.g. "seed=7;env_raise@40;worker_kill@12#1;ckpt_truncate@1".
    # None = no injection (production). The plan is deterministic in
    # per-site event counts, so a chaos run replays exactly.
    chaos: Optional[str] = None
    # Networked collection fleet (d4pg_tpu/fleet, docs/fleet.md): when
    # fleet_listen is set, the trainer runs an experience-ingest server on
    # that port (0 = ephemeral, printed at startup) and remote actor hosts
    # (python -m d4pg_tpu.fleet.actor) stream complete n-step windows into
    # the replay buffer — alongside local collection, or INSTEAD of it when
    # num_envs == 0 (the learner then paces against ingested windows the
    # way async_collect paces against the pool).
    fleet_listen: Optional[int] = None
    # Ingest bind address: 0.0.0.0 so remote actor hosts can actually
    # reach it (the point of a NETWORKED fleet); set 127.0.0.1 for a
    # loopback-only fleet (the smoke/soak scripts' localhost topology
    # works either way).
    fleet_host: str = "0.0.0.0"
    # Weight distribution for fleet actors: the trainer re-exports the
    # serving bundle into this directory (atomic params-first/json-second —
    # the same attestation serve hot-reload keys on) every
    # fleet_publish_interval grad steps, bumping the bundle GENERATION;
    # ingest drops windows older than generation − fleet_max_gen_lag.
    fleet_bundle: Optional[str] = None
    fleet_publish_interval: int = 200
    fleet_max_gen_lag: int = 1
    # Fleet wire encoding for FLAT observation rows (ISSUE 13): "auto" =
    # float32 (byte-identical to local collection; pixel envs always
    # negotiate u8-quantized rows, which ARE byte-identical through the
    # shared quantization point), "bfloat16" halves flat-row wire bytes
    # with a declared bf16 round (the one lossy mode — see
    # docs/data_plane.md wire-encoding tradeoffs). Negotiated with each
    # actor at HELLO (replay/source.py:negotiate_fleet).
    fleet_wire_dtype: str = "auto"
    # Bounded ingest admission queue (frames): past it the ingest answers
    # OVERLOADED(queue_full) — the serve batcher's explicit-shed contract.
    fleet_queue_limit: int = 64
    # League identity (ISSUE 15, d4pg_tpu/league): which population member
    # this learner IS and which league generation spawned/forked it. None
    # = not a league run (no columns added). When set: stamped onto every
    # metrics.jsonl row (numeric — the MetricsLogger contract), into
    # trainer_meta.json (the controller's fork-resume ATTESTATION: a clone
    # that checkpoints under its own variant_id proves it resumed and
    # progressed, not restarted from scratch), and into the fleet HELLO
    # capability vector (actors assigned to another variant are refused).
    variant_id: Optional[int] = None
    league_generation: int = 0
    # Where host-env collection/eval forwards run: "cpu" jits the actor on
    # the host CPU backend against published numpy params, "default" uses
    # the accelerator, "auto" picks cpu whenever the default backend is an
    # accelerator. The 3×256 actor forward is microseconds on CPU; on the
    # accelerator each single-observation act is a dispatch plus a
    # device→host fetch in the collection loop's critical path. The BASELINE
    # north-star layout — actors on TPU-VM host CPU, learner on chip — is
    # exactly this. Pure-JAX envs ignore it (their rollout IS the device).
    actor_device: str = "auto"

    # Where sampled batches live (ROADMAP items 1/2 — the megastep data
    # plane):
    #   "host"   — the existing path: host PER/uniform sampling, per-dispatch
    #              H2D batch upload + D2H priority fetch (the seeded oracle);
    #   "device" — replay mirrored into a device-resident HBM ring
    #              (replay/device_ring.py); the fused megastep draws indices
    #              in-kernel and trains with ZERO per-grad-step transfers
    #              (runtime/megastep.py). PER composes: the priority
    #              structure itself is a device-resident segment tree
    #              (replay/device_per.py) — stratified descent, IS weights,
    #              and priority write-back all inside the megastep, sharded
    #              over dp with the striped ring;
    #   "hybrid" — LEGACY PER: the host sum-tree computes indices + IS
    #              weights and ships only the tiny [K, B] int32/f32 blocks;
    #              rows are gathered on-device, priorities come back as one
    #              [K, B] block per dispatch (same seeded index stream as
    #              the host path — frozen-literal-tested). Kept as the
    #              host-data-plane byte-parity oracle.
    # Host experience ingest streams into the ring in large infrequent
    # chunks (the ingest_chunk stage), never per step.
    replay_placement: str = "host"
    # Device-PER descent implementation (the ops/pallas_projection.py
    # backend-ladder convention): "xla" is the jnp log-depth gather
    # descent (the reference program and the oracle), "pallas" the
    # kernel that runs the same walk without gathers (ops/pallas_tree.py)
    # and returns the same leaves; compiled on platform tpu, interpreted
    # under JAX_PLATFORMS=cpu (tests).
    device_tree_backend: str = "xla"
    # replay. Capacity None = "unset": resolved to the env preset's cap if
    # any, else 1M (reference --rmsize default) — a sentinel, so an explicit
    # --rmsize 1000000 is distinguishable from the default and never
    # silently downgraded by a preset.
    replay_capacity: Optional[int] = None
    # On-device HBM ring row dtype for FLAT observations: "bfloat16" halves
    # the per-sample gather bytes (the bandwidth-bound part of the fused
    # step per the bench roofline). Pixel envs always store uint8 rows
    # regardless. "auto" == float32 today.
    ring_dtype: str = "auto"
    prioritized: bool = True           # reference --p_replay
    n_step: int = 3                    # reference --n_steps
    tree_backend: str = "auto"
    # Host→device batch staging dtype for observations. "bfloat16" halves
    # the host→device bytes per dispatch (what wide-obs host envs such as
    # Humanoid's 348-dim obs pay most of). Obs are cast back to
    # f32 INSIDE the jitted step, so only the wire format changes; bf16's
    # 8-bit mantissa is ~3 decimal digits of obs precision, far above
    # exploration-noise scale. "uint8" (pixel envs only) goes further:
    # sampled rows leave the quantized replay as raw bytes and dequantize
    # ÷255 in-jit — 4× fewer transfer bytes than f32 (a K=32 batch-256
    # 48×48×2 dispatch is 302 MB in f32). Host-path only (pure-JAX envs
    # never transfer batches).
    transfer_dtype: str = "float32"

    # evaluation / logging / checkpoint
    eval_interval: int = 2_000         # grad steps between evals
    eval_episodes: int = 10            # reference main.py:309
    # Host-env eval runs in a dedicated thread on a published param copy —
    # the reference's separate evaluator process (main.py:103-134) — so an
    # eval crossing costs the learner ZERO grad steps (a 10×1000-step
    # HalfCheetah eval otherwise stalls it for seconds). If an eval is
    # still in flight at the next crossing, the newer request replaces the
    # waiting one (that crossing logs no row — same as the reference's
    # time-based evaluator missing steps). Pure-JAX envs ignore this: their
    # jitted on-device eval is already sub-dispatch-cost.
    concurrent_eval: bool = True
    ewma_alpha: float = 0.05           # reference main.py:131
    log_dir: str = "runs/default"
    checkpoint_interval: int = 10_000
    resume: bool = False
    # Also snapshot the replay buffer alongside each checkpoint (latest
    # only) and restore it on --resume, so resumed runs don't restart from
    # an empty buffer + fresh warmup. Costs disk + a few seconds per save.
    snapshot_replay: bool = False
    # capture a jax.profiler trace of grad steps [10, 60) into this dir
    profile_dir: Optional[str] = None
    # Failure detection / elastic restart: when > 0, the trainer watches its
    # own RSS at every eval crossing and, past the limit, checkpoints
    # (state + replay snapshot if enabled), sets Trainer.preempted, and
    # returns; train.py then exits 75 (vs 0 on completion) so a supervisor
    # reruns with --resume and the remaining --total-steps budget
    # (runs/mujoco_supervisor.sh is such a loop). Exists because long runs
    # can be killed by the host (OOM killers, leaky device-client
    # libraries); a clean self-preemption beats a SIGKILL that loses
    # everything since the last checkpoint.
    max_rss_gb: float = 0.0

    # distribution
    dp: Optional[int] = None           # None → single device
    # Multi-host (ISSUE 17, docs/multihost.md): how many jax.distributed
    # processes share the mesh. 1 = single-controller (every existing
    # path, unchanged). Set by train.py from the bring-up result — the
    # capability negotiation (replay/source.py) uses it to declare the
    # multihost composition rules, and the trainer uses it to size the
    # process-LOCAL replay shard (replay_capacity / num_processes) and
    # select the per-host flusher.
    num_processes: int = 1
    # Canonical run directory for SHARED artifacts (checkpoints, replay
    # snapshot, trainer_meta) on a multi-host run: secondary processes log
    # under log_dir/workerN but must checkpoint-restore from the SAME
    # directory process 0 saves into. None = log_dir (single-host, and
    # process 0 of a multi-host run).
    run_root: Optional[str] = None
    # Hogwild-staleness DP (SURVEY §2.2): each replica runs the K
    # steps_per_dispatch window on its own diverging param copy (no
    # per-step gradient sync), then one param/optimizer pmean resyncs —
    # 1 AllReduce per K steps instead of K, the reference's async-worker
    # trade with the staleness bounded by K.
    dp_hogwild: bool = False
    tp: int = 1

    # algorithm
    agent: D4PGConfig = field(default_factory=D4PGConfig)

    seed: int = 0


DEFAULT_REPLAY_CAPACITY = 1_000_000  # reference --rmsize default


# Per-env presets: categorical support + episode limits (replaces
# configure_env_params, main.py:84-99, which hardcodes Pendulum and comments
# out the rest).
ENV_PRESETS = {
    "pendulum": dict(v_min=-300.0, v_max=0.0, obs_dim=3, action_dim=1, max_episode_steps=200),
    "pointmass_goal": dict(v_min=-50.0, v_max=0.0, obs_dim=6, action_dim=2, max_episode_steps=50),
    # Pixel env: obs is a flattened 48×48×2 render. replay_capacity caps the
    # default 1M ring — at 4608 bytes/obs (uint8-quantized storage) 100k
    # transitions ≈ 0.9 GB host RAM; 1M would be ~9 GB.
    "pixel_pendulum": dict(
        v_min=-300.0, v_max=0.0, obs_dim=48 * 48 * 2, action_dim=1,
        max_episode_steps=200, pixel_shape=(48, 48, 2), replay_capacity=100_000,
    ),
    # Pure-JAX on-device locomotion (envs/locomotion.py) — the flagship
    # tasks with rollout + replay + learn in one XLA program (--on-device).
    "halfcheetah": dict(v_min=0.0, v_max=1000.0, obs_dim=17, action_dim=6, max_episode_steps=1000),
    "hopper": dict(v_min=0.0, v_max=500.0, obs_dim=11, action_dim=3, max_episode_steps=1000),
    "walker2d": dict(v_min=0.0, v_max=500.0, obs_dim=17, action_dim=6, max_episode_steps=1000),
    # On-device 3D Humanoid (envs/spatial.py engine) — 45-dim proprioceptive
    # obs (see envs/locomotion.py:Humanoid docstring for the layout rationale).
    # v_max 1500 (not 1000): the round-4 v1500 study measured q_mean
    # saturating against v_max=1000 and +15% final return from widening
    # (runs/humanoid_ondevice_v1500/NOTES.md) — applied to the gym Humanoid
    # ids below for the same reason (VERDICT round-4 weak #1).
    "humanoid": dict(v_min=0.0, v_max=1500.0, obs_dim=45, action_dim=17, max_episode_steps=1000),
    "ant": dict(v_min=0.0, v_max=1000.0, obs_dim=27, action_dim=8, max_episode_steps=1000),
    "Pendulum-v1": dict(v_min=-300.0, v_max=0.0, obs_dim=3, action_dim=1, max_episode_steps=200),
    "HalfCheetah-v4": dict(v_min=0.0, v_max=1000.0, obs_dim=17, action_dim=6, max_episode_steps=1000),
    "HalfCheetah-v5": dict(v_min=0.0, v_max=1000.0, obs_dim=17, action_dim=6, max_episode_steps=1000),
    "Humanoid-v4": dict(v_min=0.0, v_max=1500.0, obs_dim=376, action_dim=17, max_episode_steps=1000),
    "Humanoid-v5": dict(v_min=0.0, v_max=1500.0, obs_dim=348, action_dim=17, max_episode_steps=1000),
}


def apply_env_preset(config: TrainConfig) -> TrainConfig:
    """Fill obs/action dims and categorical support from the env preset."""
    preset = ENV_PRESETS.get(config.env)
    if preset is None:
        return config
    dist = dataclasses.replace(
        config.agent.dist, v_min=preset["v_min"], v_max=preset["v_max"]
    )
    agent = dataclasses.replace(
        config.agent,
        obs_dim=preset["obs_dim"],
        action_dim=preset["action_dim"],
        dist=dist,
        n_step=config.n_step,
        prioritized=config.prioritized,
        pixel_shape=preset.get("pixel_shape", config.agent.pixel_shape),
    )
    max_steps = (
        config.max_episode_steps
        if config.max_episode_steps is not None
        else preset["max_episode_steps"]
    )
    replay_capacity = config.replay_capacity
    if replay_capacity is None:
        replay_capacity = preset.get("replay_capacity", DEFAULT_REPLAY_CAPACITY)
    return dataclasses.replace(
        config, agent=agent, max_episode_steps=max_steps,
        replay_capacity=replay_capacity,
    )


def apply_batch_scale(config: TrainConfig) -> TrainConfig:
    """Derive the large-batch recipe from the baseline config (ISSUE 16).

    One multiplier ``S = config.batch_scale`` rewrites every knob the wide
    shape moves, so a recipe is ``--batch-scale 8``, not five hand-tuned
    flags that can drift apart:

    ==================  =========================  ==========================
    knob                rule                       why
    ==================  =========================  ==========================
    batch_size          × S                        the point
    lr_actor/lr_critic  × S                        linear scaling: S× the
                                                   data per gradient supports
                                                   S× the step (Goyal 2017)
    per_beta_steps      ÷ S (floor 1)              β anneal tracks DATA seen;
                                                   each grad step now eats S×
                                                   the samples
    warmup_steps        × S                        the first wide batch needs
                                                   as many decorrelated rows
                                                   as S baseline batches
    steps_per_dispatch  ÷ S (floor 1)              a wide batch already
                                                   amortizes dispatch latency
    ==================  =========================  ==========================

    Applied AFTER :func:`apply_env_preset` (presets set baseline values;
    the scale derives from them). ``S <= 1`` returns the config unchanged
    — byte-for-byte, so every existing run is unaffected.
    """
    s = int(config.batch_scale)
    if s <= 1:
        return config
    agent = dataclasses.replace(
        config.agent,
        lr_actor=config.agent.lr_actor * s,
        lr_critic=config.agent.lr_critic * s,
        per_beta_steps=max(1, config.agent.per_beta_steps // s),
    )
    return dataclasses.replace(
        config,
        agent=agent,
        batch_size=config.batch_size * s,
        warmup_steps=config.warmup_steps * s,
        steps_per_dispatch=max(1, config.steps_per_dispatch // s),
    )
