"""One data plane: the capability seam every replay ingestion path answers to.

The repo has three ways experience reaches the learner's replay —

- **local collection** (host pool / sync env loops → n-step writers →
  the host sum-tree/ring),
- **fleet ingest** (remote actor hosts → ``WINDOWS``/``WINDOWS2`` frames
  → ``ReplayBuffer.add_batch``),
- **device/hybrid placement** (the host buffer mirrored into an
  HBM-resident ring, sampled in-kernel) —

and, until ISSUE 13, a matrix of hard refusals glued them together:
``--fleet-listen`` refused ``--her``/``--obs-norm``/pixels, device
placement refused pixels/obs-norm/dp_hogwild, hybrid refused dp, and the
same checks lived twice (train.py AND the Trainer constructor), drifting
a little more each PR. This module replaces that with ONE rule table:

- :func:`negotiate` maps a :class:`RequestedCaps` (what a config asks
  for) to a :class:`Negotiation` — verdict ``pass``, ``negotiated``
  (the request is honored with a declared action, e.g. hybrid placement
  keeping the legacy host-tree PER round-trip), or ``gap`` (a declared
  capability gap with a machine-readable reason code). Every refusal the
  system can utter lives HERE, once; the messages below are the exact
  strings the CLI and the Trainer raise, so they can never drift again.
  Since ISSUE 14, ``device`` placement composes with PER outright (the
  priority structure is device-resident, ``replay/device_per.py``) —
  the old ``per_downgraded_uniform`` action is gone.
- :func:`validate_train_config` is the single call site both entry
  points use (``train.py`` pre-env, ``Trainer.__init__`` post-env).
- :func:`learner_fleet_caps` / :func:`negotiate_fleet` are the fleet
  HELLO handshake's capability vector: the learner states what its
  replay requires (obs wire mode f32/u8/bf16, actor-side HER on/off,
  generation-tagged obs-norm stats on/off), the actor declares what it
  supports, and a mismatch is refused with a STRUCTURED reason the actor
  can print/alert on — never a silent wrong-distribution stream.
- :func:`composition_matrix` enumerates scenario × placement over the
  same table; the committed ``benchmarks/composition_matrix.json`` is
  its output, schema-gated (tools/d4pglint/schema_check.py) so every
  cell is pass/negotiated or a DECLARED gap — zero undeclared refusals.

Deliberately JAX-free (stdlib only): imported by train.py before any
backend decision and by the fleet ingest server.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional, Tuple

# Fleet wire observation encodings (d4pg_tpu/fleet/wire.py implements the
# codecs; the names here are the negotiation vocabulary):
#   f32  — 4 bytes/elem, byte-identical to the in-process writer path;
#   u8   — 1 byte/elem, pixel rows quantized at the SAME point
#          ReplayBuffer._encode_obs quantizes (rint(obs*255)), so the
#          stored buffer bytes stay fleet-vs-local identical;
#   bf16 — 2 bytes/elem, flat rows truncated to bfloat16 on the wire
#          (deterministic round-to-nearest-even; content is bf16-rounded
#          f32 by declaration — the one mode that is NOT byte-identical
#          to local collection, and says so in the matrix).
OBS_MODES = ("f32", "u8", "bf16")

# Tree leaves per dp shard the Pallas descent tiers accept: the kernel
# (ops/pallas_tree.py) keeps 4 bytes per leaf resident in VMEM, and 2^24
# leaves (64 MiB of a v5e's 128 MiB) is the largest size it was compiled
# and checked against the XLA descent at (chip run, PR 21).
PALLAS_TREE_MAX_LEAVES = 1 << 24


@dataclass(frozen=True)
class CapabilityGap:
    """One declared gap: ``code`` is the machine-readable reason (stable,
    matrix/artifact vocabulary), ``message`` the human refusal text."""

    code: str
    message: str


@dataclass(frozen=True)
class Negotiation:
    """Outcome of negotiating one requested composition."""

    verdict: str                              # "pass" | "negotiated" | "gap"
    actions: Tuple[str, ...] = ()             # declared downgrades applied
    gaps: Tuple[CapabilityGap, ...] = ()      # non-empty iff verdict=="gap"

    @property
    def ok(self) -> bool:
        return self.verdict != "gap"

    def message(self) -> str:
        return "; ".join(g.message for g in self.gaps)


@dataclass(frozen=True)
class RequestedCaps:
    """What one training configuration asks of the data plane. Built from
    a TrainConfig (:func:`from_train_config`); plain flags so scenario
    rows in the composition matrix can state them directly."""

    placement: str = "host"
    prioritized: bool = True
    pixel: bool = False
    obs_norm: bool = False
    her: bool = False
    fleet: bool = False
    fleet_only: bool = False
    fleet_bundle: bool = False
    fleet_wire: str = "auto"        # auto | float32 | bfloat16
    on_device: bool = False
    async_collect: bool = False
    num_envs: int = 1
    dp: int = 0                     # 0 = no data parallelism
    tp: int = 1
    dp_hogwild: bool = False
    steps_per_dispatch: int = 1
    transfer_dtype: str = "float32"
    prefetch: bool = False
    # ISSUE 16 — the large-batch/fused-kernel tier's capability flags:
    # fused_descent asks for the descent-in-scan Pallas program (device-
    # PER only, single device, pallas_fused projection, categorical
    # head); ingest_prefetch asks for the double-buffered ring staging
    # (device placement; a declared no-op elsewhere). projection /
    # dist_kind ride along so the fused-descent preconditions are
    # negotiable facts, not trainer-side asserts.
    fused_descent: bool = False
    ingest_prefetch: bool = False
    device_tree: str = "xla"        # xla | pallas (device-PER descent)
    projection: str = "xla"         # xla | pallas | pallas_fused
    dist_kind: str = "categorical"  # categorical | quantile | iqn
    chaos: bool = False
    # A sequence torso under the critic (models/torso.py): batches are
    # windows over the device ring, the policy acts on a window.
    torso: bool = False
    batch_size: int = 256
    replay_capacity: Optional[int] = None
    # Multi-host (ISSUE 17): how many jax.distributed processes share the
    # mesh. 1 = single-controller. >1 requires the dp-sharded device data
    # plane (the striped layout is what makes per-host replay shards
    # exact), with dp and capacity dealt evenly across processes.
    processes: int = 1
    # League variant id (ISSUE 15): which population member this learner
    # IS. 0 = the default/pre-league variant; the fleet HELLO negotiates
    # it so an actor host assigned to variant A can never stream into
    # variant B's replay (silent cross-variant contamination).
    variant: int = 0
    # None = not yet known (train.py validates before the env exists;
    # the Trainer re-validates after, with the env kind resolved).
    is_jax_env: Optional[bool] = None


def from_train_config(config, *, on_device: bool = False,
                      is_jax_env: Optional[bool] = None) -> RequestedCaps:
    """Project a ``TrainConfig`` onto the capability vocabulary."""
    return RequestedCaps(
        placement=config.replay_placement,
        prioritized=bool(config.prioritized),
        pixel=bool(config.agent.pixel_shape),
        obs_norm=bool(config.obs_norm),
        her=bool(config.her),
        fleet=config.fleet_listen is not None,
        fleet_only=config.fleet_listen is not None and config.num_envs == 0,
        fleet_bundle=bool(config.fleet_bundle),
        fleet_wire=getattr(config, "fleet_wire_dtype", "auto"),
        on_device=on_device,
        async_collect=bool(config.async_collect),
        num_envs=int(config.num_envs),
        dp=int(config.dp or 0),
        tp=int(config.tp),
        dp_hogwild=bool(config.dp_hogwild),
        steps_per_dispatch=int(config.steps_per_dispatch),
        transfer_dtype=config.transfer_dtype,
        prefetch=bool(config.prefetch),
        fused_descent=bool(getattr(config, "fused_descent", False)),
        ingest_prefetch=bool(getattr(config, "ingest_prefetch", False)),
        device_tree=getattr(config, "device_tree_backend", "xla"),
        projection=config.agent.projection_backend,
        dist_kind=config.agent.dist.kind,
        chaos=bool(config.chaos),
        torso=config.agent.torso is not None,
        batch_size=int(config.batch_size),
        replay_capacity=config.replay_capacity,
        processes=int(getattr(config, "num_processes", 1) or 1),
        variant=int(getattr(config, "variant_id", None) or 0),
        is_jax_env=is_jax_env,
    )


def negotiate(caps: RequestedCaps) -> Negotiation:
    """THE rule table: every composition verdict the system can reach.

    The message strings are the exact refusal texts both entry points
    raise — single-sourced so CLI and constructor can never drift.
    """
    gaps: List[CapabilityGap] = []
    actions: List[str] = []

    def gap(code: str, message: str) -> None:
        gaps.append(CapabilityGap(code, message))

    if caps.placement not in ("host", "device", "hybrid"):
        gap(
            "unknown_placement",
            f"replay_placement must be host|device|hybrid, got "
            f"{caps.placement!r}",
        )
        return Negotiation("gap", (), tuple(gaps))

    # Device placement composes with PER outright since ISSUE 14: the
    # priority structure itself is device-resident (replay/device_per.py
    # — stratified descent, IS weights, and write-back inside the fused
    # megastep), so device×PER is a PASS, not the old uniform downgrade.
    if caps.placement == "hybrid" and caps.prioritized:
        # Hybrid is now the LEGACY placement: the host sum-tree still
        # owns the descent and ships [K, B] index/weight blocks every
        # dispatch. It stays supported as the byte-parity oracle of the
        # host data plane — a declared action, so the matrix says which
        # cells still pay the host round-trip.
        actions.append("hybrid_legacy_host_tree")
    if caps.placement == "hybrid" and not caps.prioritized:
        gap(
            "hybrid_requires_per",
            "replay_placement=hybrid is the PER mode (host sum-tree "
            "indices + on-device gather); use replay_placement=device "
            "for uniform replay",
        )

    if caps.placement != "host":
        if caps.pixel:
            gap(
                "device_ring_f32_only",
                "replay_placement=device/hybrid mirrors f32 rows into "
                "HBM; pixel (uint8-quantized) buffers are host-path only "
                "for now",
            )
        if caps.obs_norm:
            gap(
                "obs_norm_host_sampling",
                "--obs-norm normalizes sampled batches on the host; "
                "it is incompatible with a device-resident ring "
                "(rows are gathered in-kernel)",
            )
        if caps.transfer_dtype != "float32":
            gap(
                "transfer_dtype_host_only",
                "--transfer-dtype compresses the per-dispatch batch "
                "upload, which replay_placement=device/hybrid removes "
                "entirely; use float32",
            )
        if caps.dp:
            if caps.placement == "hybrid":
                gap(
                    "hybrid_single_device",
                    "replay_placement=hybrid is single-device: the "
                    "host sum-tree's [K, B] index blocks are global, "
                    "so shard-local gathers can't serve them; use "
                    "--replay-placement device for the sharded "
                    "(uniform) megastep",
                )
            if caps.tp != 1:
                gap(
                    "sharded_megastep_dp_only",
                    "the sharded megastep mesh is dp-only (tp=1); "
                    "tensor parallelism composes via the host-path "
                    "GSPMD step (--replay-placement host --tp N)",
                )
            if caps.dp_hogwild:
                gap(
                    "dp_hogwild_host_only",
                    "--dp-hogwild is a host-path DP mode; the sharded "
                    "megastep syncs gradients every step",
                )
            if caps.batch_size % caps.dp:
                gap(
                    "batch_not_divisible",
                    f"--batch-size {caps.batch_size} must be "
                    f"divisible by --dp {caps.dp} (each shard draws "
                    "batch/dp rows)",
                )
            if caps.replay_capacity and caps.replay_capacity % caps.dp:
                gap(
                    "capacity_not_divisible",
                    f"replay capacity {caps.replay_capacity} must "
                    f"be divisible by --dp {caps.dp} (each shard "
                    "owns capacity/dp ring rows)",
                )
        if caps.prefetch:
            actions.append("prefetch_ignored")
        if caps.fleet:
            # Opened by ISSUE 13 at the HOST placement; the device ring
            # composes with ingest through the same host-buffer mirror
            # local collection uses, so nothing refuses here.
            pass

    # A torso's batches are windows of consecutive ring rows of one env's
    # stream, gathered inside the single-device megastep, and its policy
    # carries each env's last observations: the paths that have neither
    # are refused by name.
    if caps.torso:
        if caps.placement != "device":
            gap(
                "torso_device_placement_only",
                "--torso gathers its history windows from the device ring "
                "inside the megastep; it requires --replay-placement device",
            )
        if caps.dp or caps.processes > 1:
            gap(
                "torso_single_device",
                "--torso is single-device: a striped ring splits a stream's "
                "consecutive rows over the shards and the torso's weights "
                "are not sharded yet (drop --dp)",
            )
        if caps.fused_descent:
            gap(
                "torso_no_fused_descent",
                "--torso trains through the separate-programs PER tier; "
                "--fused-descent is not wired through it",
            )
        if (caps.her or caps.fleet or caps.async_collect or caps.on_device
                or caps.is_jax_env is False):
            gap(
                "torso_sync_jax_collection_only",
                "--torso acts on each env's last observations, which only "
                "the synchronous pure-JAX collector keeps (no --her, "
                "--fleet-listen, --async-collect, --on-device or host env)",
            )

    # ISSUE 17 — the process-spanning mesh. Every structural requirement
    # is a declared gap: multihost exists only where the dp-sharded device
    # data plane's striped layout makes per-host replay shards exact.
    if caps.processes > 1:
        if caps.placement != "device":
            gap(
                "multihost_device_placement_only",
                "--num-processes > 1 requires --replay-placement device: "
                "per-host replay shards ride the sharded ring's striped "
                "layout (host/hybrid keep a single global host buffer "
                "no process owns)",
            )
        if not caps.dp:
            gap(
                "multihost_requires_dp",
                "--num-processes > 1 requires --dp: the multi-host mesh "
                "IS the dp-sharded megastep mesh spanning processes",
            )
        elif caps.dp % caps.processes:
            gap(
                "multihost_dp_not_divisible",
                f"--dp {caps.dp} must be divisible by --num-processes "
                f"{caps.processes} (each process owns dp/num_processes "
                "contiguous mesh shards)",
            )
        if caps.replay_capacity and caps.replay_capacity % caps.processes:
            gap(
                "multihost_capacity_not_divisible",
                f"replay capacity {caps.replay_capacity} must be "
                f"divisible by --num-processes {caps.processes} (each "
                "process owns a capacity/num_processes local shard)",
            )

    # ISSUE 16 — fused descent-in-scan tier. Every precondition is a
    # declared gap, not a trainer assert: the fused kernel pipelines the
    # NEXT step's tree descent into the loss program, which only exists
    # where loss and descent are both Pallas programs over a device-
    # resident tree.
    if caps.fused_descent:
        if caps.placement != "device":
            gap(
                "fused_descent_device_only",
                "--fused-descent fuses the device-PER tree descent into "
                "the megastep's loss kernel; it requires "
                "--replay-placement device",
            )
        elif not caps.prioritized:
            gap(
                "fused_descent_requires_per",
                "--fused-descent pipelines the PRIORITY-tree descent; "
                "uniform replay has no descent to fuse (drop the flag)",
            )
        if caps.dp:
            gap(
                "fused_descent_single_device",
                "--fused-descent is single-device: the sharded megastep "
                "keeps separate per-shard descent programs (drop the "
                "flag or --dp)",
            )
        if caps.projection != "pallas_fused":
            gap(
                "fused_descent_requires_pallas_fused",
                "--fused-descent extends the pallas_fused loss kernel "
                "with the descent tile; use --projection pallas_fused",
            )
        if caps.dist_kind != "categorical":
            gap(
                "fused_descent_categorical_only",
                "--fused-descent fuses into the CATEGORICAL projection "
                "kernel; quantile/IQN heads keep the separate-programs "
                "tier",
            )

    # Both Pallas descent tiers (the standalone kernel and the fused
    # descent-in-scan) keep one lane's left-child sums — 4 bytes per leaf —
    # resident in VMEM. A ring too large for that is refused here, by name
    # — never swapped for the XLA descent behind the user's back. THE one
    # check: the kernel itself has none (past it, Mosaic fails the compile).
    if (
        caps.placement == "device" and caps.prioritized
        and (caps.device_tree == "pallas" or caps.fused_descent)
        and caps.replay_capacity
    ):
        per_shard = -(-caps.replay_capacity // max(caps.dp, 1))
        leaves = 1 << (per_shard - 1).bit_length()  # next pow2
        if leaves > PALLAS_TREE_MAX_LEAVES:
            gap(
                "pallas_tree_too_many_leaves",
                f"--device-tree-backend pallas / --fused-descent hold each "
                f"shard's tree in VMEM, 4 bytes per leaf: {leaves} leaves "
                f"per shard is over the limit of {PALLAS_TREE_MAX_LEAVES} "
                "(2^24, 64 MiB — the largest that compiled and matched the "
                "XLA descent on a TPU v5e, PR 21). Use "
                "--device-tree-backend xla, a smaller --rmsize, or more "
                "--dp shards",
            )

    # Double-buffered ingest staging: meaningful only where a DeviceRing
    # flush exists on the dispatch path AND is unsharded (the sharded
    # sync stages per-shard inside its own flush rounds).
    if caps.ingest_prefetch and (caps.placement != "device" or caps.dp):
        actions.append("ingest_prefetch_ignored")

    if caps.dp_hogwild:
        if not caps.dp:
            gap(
                "dp_hogwild_requires_dp",
                "--dp-hogwild is a DP mode: it requires --dp",
            )
        elif caps.placement == "host" and caps.steps_per_dispatch <= 1:
            gap(
                "dp_hogwild_needs_fused_window",
                "--dp-hogwild needs --steps-per-dispatch > 1: the "
                "dispatch window IS the staleness bound (K local "
                "steps between param resyncs)",
            )

    if caps.transfer_dtype == "uint8" and not caps.pixel:
        gap(
            "uint8_wire_requires_pixel",
            "--transfer-dtype uint8 requires a pixel env (uint8-"
            "quantized replay); use bfloat16 for flat observations",
        )
    elif caps.transfer_dtype not in ("float32", "bfloat16", "uint8"):
        gap(
            "unknown_transfer_dtype",
            "transfer_dtype must be float32|bfloat16|uint8, "
            f"got {caps.transfer_dtype!r}",
        )

    if caps.obs_norm and (caps.pixel or caps.is_jax_env):
        # is_jax_env may be None (unknown pre-env at the CLI): the
        # Trainer re-validates with it resolved. Pure-JAX envs act AND
        # evaluate inside jit, so the host-boundary normalizer never
        # sees their forwards — fleet-only mode included (eval would
        # silently run un-normalized).
        gap(
            "obs_norm_flat_envs_only",
            "--obs-norm supports host state-feature envs only "
            "(pure-JAX envs act inside jit; pixel obs are uint8 "
            "frames the conv encoder already scales)",
        )

    if caps.fleet_bundle and not caps.fleet:
        gap(
            "fleet_bundle_requires_listen",
            "--fleet-bundle does nothing without --fleet-listen: the "
            "bundle is published at ingest generation bumps (use "
            "--export-bundle for a one-shot export)",
        )

    if caps.fleet:
        if caps.obs_norm and not caps.fleet_only:
            # ISSUE 13 opens fleet+obs-norm, but with exactly ONE
            # statistics writer: the ingest writer thread folds stats per
            # ingested window. Local collection folds per acted step —
            # two unsynchronized Welford writers would tear the merge.
            gap(
                "obs_norm_fleet_single_writer",
                "--fleet-listen with --obs-norm requires --num-envs 0 "
                "(fleet-only): normalizer statistics fold at exactly one "
                "boundary — the ingest writer — and concurrent local "
                "collection would race the Welford merge",
            )
        if caps.fleet_only and caps.async_collect:
            gap(
                "fleet_only_async_collect",
                "--async-collect needs local envs; with --num-envs 0 "
                "the fleet is the only collector (drop --async-collect)",
            )
        if caps.fleet_wire == "bfloat16" and caps.pixel:
            gap(
                "fleet_wire_bf16_flat_only",
                "--fleet-wire-dtype bfloat16 compresses FLAT rows; pixel "
                "rows already stream u8-quantized at 1/4 the f32 bytes",
            )
    elif caps.fleet_wire not in ("auto", "float32"):
        gap(
            "fleet_wire_requires_listen",
            "--fleet-wire-dtype shapes the fleet ingest wire; it does "
            "nothing without --fleet-listen",
        )
    if caps.fleet_wire not in ("auto", "float32", "bfloat16"):
        gap(
            "unknown_fleet_wire",
            "fleet_wire_dtype must be auto|float32|bfloat16, got "
            f"{caps.fleet_wire!r}",
        )

    if caps.num_envs == 0 and not caps.fleet:
        gap(
            "no_collection_source",
            "--num-envs 0 means no local collection at all; it requires "
            "--fleet-listen so remote actor hosts supply the experience",
        )

    if caps.on_device:
        if caps.fleet:
            gap(
                "on_device_fleet",
                "--fleet-listen feeds the HOST replay buffer; --on-device "
                "keeps replay inside one XLA program (the flag would be "
                "silently ignored)",
            )
        if caps.transfer_dtype != "float32":
            gap(
                "on_device_transfer_dtype",
                "--transfer-dtype is a HOST-path link optimization; "
                "--on-device envs never transfer batches (the flag would "
                "be silently ignored)",
            )
        if caps.obs_norm:
            gap(
                "on_device_obs_norm",
                "--obs-norm is a host data-boundary feature; the on-device "
                "path keeps observations inside jit (the flag would be "
                "silently ignored)",
            )
        if caps.chaos:
            gap(
                "on_device_chaos",
                "--chaos targets the host runtime's fault surfaces (pool "
                "workers, flusher, checkpoint commit); the on-device path "
                "has none of them (the flag would be silently ignored)",
            )
        if caps.placement != "host":
            gap(
                "on_device_placement",
                "--replay-placement configures the HOST trainer's data "
                "plane; --on-device already keeps rollout+replay+learn in "
                "one XLA program (the flag would be silently ignored)",
            )

    if gaps:
        return Negotiation("gap", tuple(actions), tuple(gaps))
    if actions:
        return Negotiation("negotiated", tuple(actions), ())
    return Negotiation("pass", (), ())


def validate_train_config(config, *, on_device: bool = False,
                          is_jax_env: Optional[bool] = None,
                          raise_on_gap: bool = True) -> Negotiation:
    """THE validation call site (train.py and Trainer.__init__ both land
    here). Raises ``ValueError`` carrying every gap message when the
    composition has a declared gap; returns the :class:`Negotiation` so
    callers apply/announce the declared actions (prefetch ignored,
    hybrid's legacy note) — mutation stays with the owner of the config
    object."""
    n = negotiate(
        from_train_config(config, on_device=on_device, is_jax_env=is_jax_env)
    )
    if raise_on_gap and not n.ok:
        raise ValueError(n.message())
    return n


# ------------------------------------------------------------ fleet HELLO
# What a pre-ISSUE-13 actor implicitly declares: v1 wire, plain f32
# windows, no actor-side HER, no stats tagging — and (ISSUE 15) variant 0,
# the default/pre-league variant, so a pre-variant actor negotiates
# byte-compatibly against a default learner and is REFUSED by any league
# variant learner (it cannot know which population member it feeds). A
# HELLO without a "caps" key negotiates as this.
LEGACY_ACTOR_CAPS = {
    "wire": 1,
    "obs_modes": ["f32"],
    "her": False,
    "obs_norm": False,
    "variant": 0,
    # ISSUE 18: which experience stream this connection feeds ("actor" =
    # collection fleet, "mirror" = flywheel serving tap). Informational —
    # it selects the ingest server's per-source counter, never a refusal.
    "source": "actor",
}


def learner_fleet_caps(caps: RequestedCaps) -> dict:
    """What the learner's replay config REQUIRES of fleet actors: the
    server half of the HELLO capability vector."""
    if caps.pixel:
        obs_mode = "u8"      # the 17.4 MB/s ingest wall rules out f32 pixels
    elif caps.fleet_wire == "bfloat16":
        obs_mode = "bf16"
    else:
        obs_mode = "f32"
    return {
        "obs_mode": obs_mode,
        "her": caps.her,
        "obs_norm": caps.obs_norm,
        "variant": int(caps.variant),
    }


def negotiate_fleet(learner: dict, actor: dict
                    ) -> Tuple[Optional[dict], Tuple[CapabilityGap, ...]]:
    """Negotiate one actor connection against the learner's requirements.

    Returns ``(chosen, gaps)``: ``chosen`` is the capability set the
    connection will speak (None when refused), ``gaps`` the structured
    refusal reasons (the ingest server ships them back as JSON so a
    mis-deployed actor host fails with an actionable, machine-readable
    reason instead of streaming a silently-wrong distribution)."""
    gaps: List[CapabilityGap] = []
    modes = tuple(actor.get("obs_modes") or ("f32",))
    want_mode = learner["obs_mode"]
    if want_mode not in modes:
        gaps.append(CapabilityGap(
            "obs_mode_unsupported",
            f"learner streams obs as {want_mode!r}, actor supports "
            f"{list(modes)} (upgrade the actor host: WINDOWS2 frames)",
        ))
    actor_her = bool(actor.get("her", False))
    if learner["her"] and not actor_her:
        gaps.append(CapabilityGap(
            "her_required",
            "learner trains on hindsight-relabeled windows; this actor "
            "does not relabel (run it with --her)",
        ))
    elif actor_her and not learner["her"]:
        gaps.append(CapabilityGap(
            "her_unexpected",
            "actor ships hindsight-relabeled windows but the learner "
            "did not ask for HER (drop the actor's --her)",
        ))
    learner_variant = int(learner.get("variant", 0))
    actor_variant = int(actor.get("variant", 0))
    if learner_variant != actor_variant:
        # League assignment is an exact-match capability: windows from a
        # host assigned to another variant (or to none — pre-variant
        # actors declare 0) would silently train the wrong population
        # member on the wrong policy's experience.
        gaps.append(CapabilityGap(
            "variant_mismatch",
            f"learner is league variant {learner_variant}, actor is "
            f"assigned variant {actor_variant} (re-point the actor host "
            "at its assigned variant's ingest port)",
        ))
    actor_norm = bool(actor.get("obs_norm", False))
    if learner["obs_norm"] and not actor_norm:
        gaps.append(CapabilityGap(
            "obs_norm_required",
            "learner normalizes observations; this actor does not apply "
            "the bundle's generation-tagged stats (upgrade the actor "
            "host / re-point it at the published bundle)",
        ))
    elif actor_norm and not learner["obs_norm"]:
        gaps.append(CapabilityGap(
            "obs_norm_unexpected",
            "actor acts on normalized observations but the learner "
            "publishes no statistics (bundle/learner config skew)",
        ))
    if gaps:
        return None, tuple(gaps)
    return (
        {
            "obs_mode": want_mode,
            "her": learner["her"],
            "obs_norm": learner["obs_norm"],
            "variant": learner_variant,
            # pure passthrough: a mirror tap's windows count under their
            # own ingest counter but are otherwise ordinary experience
            "source": str(actor.get("source", "actor")),
        },
        (),
    )


# ------------------------------------------------------ composition matrix
# Scenario rows: named config fragments over the capability vocabulary.
# Placements are the columns. The committed artifact
# benchmarks/composition_matrix.json is negotiate() evaluated over this
# grid — regenerate with `python benchmarks/composition_matrix.py`.
SCENARIOS: Tuple[Tuple[str, dict], ...] = (
    ("flat", dict()),
    ("flat_uniform", dict(prioritized=False)),
    ("pixel", dict(pixel=True, transfer_dtype="uint8")),
    ("obs_norm", dict(obs_norm=True, is_jax_env=False)),
    ("her", dict(her=True, is_jax_env=False)),
    ("her_obs_norm", dict(her=True, obs_norm=True, is_jax_env=False)),
    ("dp2", dict(dp=2)),
    ("dp2_hogwild", dict(dp=2, dp_hogwild=True, steps_per_dispatch=8)),
    ("fleet_flat", dict(fleet=True, fleet_only=True, fleet_bundle=True,
                        num_envs=0)),
    ("fleet_pixel", dict(fleet=True, fleet_only=True, fleet_bundle=True,
                         num_envs=0, pixel=True)),
    ("fleet_obs_norm", dict(fleet=True, fleet_only=True, fleet_bundle=True,
                            num_envs=0, obs_norm=True, is_jax_env=False)),
    ("fleet_her", dict(fleet=True, fleet_only=True, fleet_bundle=True,
                       num_envs=0, her=True, is_jax_env=False)),
    ("fleet_her_obs_norm", dict(fleet=True, fleet_only=True,
                                fleet_bundle=True, num_envs=0, her=True,
                                obs_norm=True, is_jax_env=False)),
    ("fleet_bf16_wire", dict(fleet=True, fleet_only=True, fleet_bundle=True,
                             num_envs=0, fleet_wire="bfloat16")),
    ("fleet_mixed_obs_norm", dict(fleet=True, num_envs=2, obs_norm=True,
                                  is_jax_env=False)),
    # ISSUE 16: the large-batch flagship recipe's full capability ask —
    # fused descent-in-scan + double-buffered ingest at a wide batch.
    # device = pass; host/hybrid = declared gaps (the fused tier only
    # exists where the tree is device-resident).
    ("large_batch_fused", dict(fused_descent=True, ingest_prefetch=True,
                               projection="pallas_fused",
                               batch_size=2048)),
)

PLACEMENTS = ("host", "device", "hybrid")


def composition_matrix() -> List[dict]:
    """Every scenario × placement cell, negotiated. The artifact rows."""
    cells: List[dict] = []
    for name, fragment in SCENARIOS:
        for placement in PLACEMENTS:
            caps = RequestedCaps(placement=placement, **fragment)
            n = negotiate(caps)
            cell = {
                "scenario": name,
                "placement": placement,
                "verdict": n.verdict,
            }
            if n.actions:
                cell["actions"] = list(n.actions)
            if n.gaps:
                cell["gaps"] = [
                    {"code": g.code, "message": g.message} for g in n.gaps
                ]
            cells.append(cell)
    return cells
