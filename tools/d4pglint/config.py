"""d4pglint manifests: which files answer to which invariant.

These lists ARE the policy — adding a module to a manifest turns the
corresponding checks on for it, and a module's absence is an explicit
decision, not an oversight (reviewed like code, because it is code).
Paths are repo-root-relative with forward slashes.
"""

from __future__ import annotations

# Every check id, as referenced by `# d4pglint: disable=<id>` comments.
ALL_CHECKS = (
    "host-jax-import",       # host-only modules must not import jax at top level
    "lock-blocking-call",    # no blocking call while holding a lock
    "shared-mutable-state",  # cross-thread attribute writes: lock or declare
    "wall-clock-deadline",   # time.time() is not a deadline/interval clock
    "broad-except",          # broad handlers must re-raise or log
    "jit-purity",            # no numpy/float64 host ops inside jit-traced fns
    "hot-path-alloc",        # no per-step allocation in hot-path functions
    "thread-discipline",     # threads are named daemons
    "global-rng",            # seeded Generators only, no np.random module state
    "unbounded-retry",       # retry loops use the bounded Backoff util
    "device-loop-transfer",  # no host numpy / .item() in megastep bodies
    "counter-discipline",    # FLOW-manifest counters: +=/-= under lock only
    "loop-blocking-call",    # no blocking call inside event-loop callbacks
    # -- whole-program checks (tools/d4pglint/wholeprog/): the full parsed
    #    file map at once, not one AST at a time --
    "lock-order",            # global lock-acquisition-order graph is acyclic
    "protocol-conformance",  # wire-id space: codecs, endpoints, MAX_PAYLOAD
    "thread-lifecycle",      # bounded joins, shed answers, timed waits
    "flowcheck",             # conservation identities: sites, paths, asserts
    "unused-suppression",    # disable= comments must still silence something
)

# What `python -m tools.d4pglint` lints when given no paths: the product
# code. Tests are exempt on purpose (they monkeypatch, sleep under locks
# in stress harnesses, and seed deliberate violations).
DEFAULT_PATHS = (
    "d4pg_tpu",
    "tools",
    "benchmarks",
    "train.py",
    "__graft_entry__.py",
)

# The `_lazy.py` contract: these modules are imported by processes that
# must never pull the JAX runtime (spawned actor-pool workers, thin
# clients) or before backend configuration (__graft_entry__ dryrun), so
# `import jax`/`flax`/... at module top level is a bug even though it
# "works" on the dev box.
HOST_ONLY_MODULES = (
    "d4pg_tpu/__init__.py",
    "d4pg_tpu/_lazy.py",
    "d4pg_tpu/config.py",
    "d4pg_tpu/envs/__init__.py",
    "d4pg_tpu/envs/gym_adapter.py",
    "d4pg_tpu/runtime/__init__.py",
    "d4pg_tpu/runtime/actor_pool.py",
    "d4pg_tpu/runtime/metrics.py",
    "d4pg_tpu/serve/__init__.py",
    "d4pg_tpu/serve/protocol.py",
    # The event-loop I/O core (ISSUE 20): one selectors thread owns every
    # serving/router connection — it moves frame bytes for host-only
    # front-ends (router included), so a JAX import here would leak into
    # all of them AND stall the restart-in-milliseconds contract.
    "d4pg_tpu/netio/__init__.py",
    "d4pg_tpu/netio/loop.py",
    "d4pg_tpu/netio/attack.py",
    "d4pg_tpu/serve/client.py",
    "d4pg_tpu/serve/stats.py",
    # The replica front-end moves bytes and stat files, never tensors: M
    # replicas own the devices, the router must restart in milliseconds —
    # a JAX import here would also break the soak's kill/restart timing.
    "d4pg_tpu/serve/router.py",
    # The autoscaler runs beside (or inside) the router process under the
    # same restart-in-milliseconds contract: it moves signals and spawns/
    # drains processes, never tensors.
    "d4pg_tpu/serve/autoscaler.py",
    # The collection fleet: actor hosts run env + a NumPy policy and must
    # never pull the JAX runtime (the whole point of the numpy-policy
    # contract); the ingest server is constructed by the trainer before
    # any backend decision and imported by device-free tests.
    "d4pg_tpu/fleet/__init__.py",
    "d4pg_tpu/fleet/wire.py",
    "d4pg_tpu/fleet/policy.py",
    "d4pg_tpu/fleet/ingest.py",
    "d4pg_tpu/fleet/actor.py",
    # The fleet actor's n-step collapse reuses the replay writers, so the
    # whole (numpy-only) replay package must stay JAX-free at import.
    "d4pg_tpu/replay/__init__.py",
    "d4pg_tpu/replay/uniform.py",
    "d4pg_tpu/replay/nstep_writer.py",
    # Actor-side HER (ISSUE 13): remote hosts run the repo's OWN
    # HindsightWriter, so the relabeler must stay provably JAX-free.
    "d4pg_tpu/replay/her.py",
    # The capability seam: imported by train.py before any backend
    # decision AND by the (host-only) fleet ingest handshake.
    "d4pg_tpu/replay/source.py",
    # The JAX-free twin of the pure-JAX pixel env — what a fleet actor
    # host runs for the pixel cell (parity-tested against the jnp one).
    "d4pg_tpu/envs/pixel_pendulum_host.py",
    # The flywheel (ISSUE 18): the mirror tap rides inside router AND
    # replica processes, the IS gate inside the (host-only) router, and
    # the sim client is a thin env+socket loop — none may pull JAX.
    "d4pg_tpu/flywheel/__init__.py",
    "d4pg_tpu/flywheel/spool.py",
    "d4pg_tpu/flywheel/tap.py",
    "d4pg_tpu/flywheel/gate.py",
    "d4pg_tpu/flywheel/sim_client.py",
    # utils/__init__ must stay lazy: an eager profiling import there would
    # drag JAX into every utils.retry / utils.signals importer (fleet hosts).
    "d4pg_tpu/utils/__init__.py",
    "d4pg_tpu/utils/signals.py",
    "d4pg_tpu/utils/retry.py",
    # Process-group lifecycle (ISSUE 15): imported by the league
    # controller, the autoscaler, and scripts/spawnlib.py — all processes
    # that move PIDs and JSON, never tensors.
    "d4pg_tpu/utils/procs.py",
    # The checkpoint commit-record primitives, split JAX-free out of
    # runtime/checkpoint.py so the league controller (and the stub
    # learners) can verify/fork checkpoints without Orbax.
    "d4pg_tpu/runtime/manifest.py",
    # The league controller (ISSUE 15): supervises N learner processes —
    # a JAX import here would pay seconds per restart-after-kill-9 and
    # break the restart-in-milliseconds supervision contract.
    "d4pg_tpu/league/__init__.py",
    "d4pg_tpu/league/controller.py",
    "d4pg_tpu/league/__main__.py",
    "d4pg_tpu/chaos.py",
    "d4pg_tpu/analysis/__init__.py",
    "d4pg_tpu/analysis/ledger.py",
    # The lock-order witness wraps locks in host-only modules (router,
    # fleet hosts, the replay data plane) — a JAX import here would leak
    # into every one of them.
    "d4pg_tpu/analysis/lockwitness.py",
    # The conservation ledger checks counter dicts at drain in the same
    # host-only processes (router, tap, fleet hosts) — JAX-free for the
    # same reason as the lock witness.
    "d4pg_tpu/analysis/flowledger.py",
)

# JAX-runtime packages whose top-level import violates host-only-ness.
JAX_FAMILY = ("jax", "jaxlib", "flax", "optax", "orbax", "chex")

# Preallocated-staging rule: these functions are the per-step hot path of
# the data plane — a fresh numpy allocation per call here is the exact
# regression PR 2 existed to remove. `module suffix::qualname` keys;
# nested function defs inside them are exempt (lazy one-time init
# closures like the staging `mk()` allocators).
HOT_PATH_FUNCTIONS = (
    "d4pg_tpu/replay/per.py::PrioritizedReplayBuffer.sample_block",
    "d4pg_tpu/replay/per.py::PrioritizedReplayBuffer._draw",
    "d4pg_tpu/runtime/actor_pool.py::HostActorPool._step_cmd",
    "d4pg_tpu/runtime/trainer.py::Trainer._sample_staged",
    "d4pg_tpu/serve/batcher.py::DynamicBatcher._device_loop",
    "d4pg_tpu/serve/batcher.py::DynamicBatcher._reply_loop",
    "d4pg_tpu/serve/batcher.py::DynamicBatcher.submit",
    "d4pg_tpu/serve/router.py::Router._pick",
    # the multi-tenant admission check runs once per request BEFORE
    # dispatch: one lock hop, token-bucket float math, zero numpy
    # allocation (ISSUE-12 satellite)
    "d4pg_tpu/serve/router.py::Router._admit_tenant",
    # the ingest double buffer's staging step (ISSUE 16): runs once per
    # dispatch overlapped with device compute — index buffers are
    # preallocated in __init__, only the locked gather + the explicit
    # device_put staging copies remain
    "d4pg_tpu/replay/device_ring.py::DeviceRingSync.stage",
)

# The jit-traced bodies of the device-resident data plane (the megastep
# and the ring ingest — `module suffix::qualname` keys, same convention
# as HOT_PATH_FUNCTIONS, nested defs INCLUDED since loss closures trace
# too). Inside them, `np.*` calls bake trace-time constants or force an
# implicit H2D upload per dispatch, and `.item()`/`__array__` coercions
# force a blocking D2H sync — each one silently breaks the megastep's
# zero-transfer contract that `--debug-guards` enforces at runtime
# (analysis/transfer.py:no_transfers). The lint catches it at review
# time, on every code path, not just the ones a guarded run executes.
MEGASTEP_FUNCTIONS = (
    "d4pg_tpu/runtime/megastep.py::megastep_uniform_body",
    "d4pg_tpu/runtime/megastep.py::megastep_hybrid_body",
    "d4pg_tpu/runtime/megastep.py::draw_uniform_indices",
    "d4pg_tpu/runtime/megastep.py::sharded_megastep_uniform_body",
    # Device-resident PER (ISSUE 14): the body runs descent + IS weights
    # + write-back inside the fused dispatch — a host coercion anywhere
    # in it or in the tree primitives below re-tethers PER to the host.
    "d4pg_tpu/runtime/megastep.py::megastep_device_per_body",
    # The fused descent-in-scan tier (ISSUE 16): descent + loss as ONE
    # Pallas program per scan step — the body and the fused kernel's
    # wrapper both trace into the large-batch megastep dispatch.
    "d4pg_tpu/runtime/megastep.py::megastep_device_per_fused_body",
    "d4pg_tpu/ops/pallas_fused_step.py::fused_categorical_loss_descent",
    "d4pg_tpu/replay/device_ring.py::ingest_body",
    "d4pg_tpu/replay/device_ring.py::sharded_ingest_body",
    # the ring's row accessors: what the ingest bodies and gather_batches
    # trace (lane-dense storage of wide rows lives behind them)
    "d4pg_tpu/replay/device_ring.py::DeviceRing.rows",
    "d4pg_tpu/replay/device_ring.py::DeviceRing.set_rows",
    # A sequence torso (ISSUE 27): the window gather and the torso's
    # traced functions run inside the same dispatch.
    "d4pg_tpu/agent/d4pg.py::gather_windows",
    "d4pg_tpu/models/torso.py::torso_apply",
    "d4pg_tpu/models/torso.py::mla",
    "d4pg_tpu/models/torso.py::expert_layer",
    "d4pg_tpu/models/torso.py::dispatch_plan",
    "d4pg_tpu/models/torso.py::_routed_fwd",
    "d4pg_tpu/models/torso.py::_routed_bwd",
    # the hybrid stack's mixers and the gated delta rule (ISSUE 34)
    "d4pg_tpu/models/torso.py::gated_delta_net",
    "d4pg_tpu/models/torso.py::gated_attention",
    "d4pg_tpu/models/torso.py::causal_conv",
    "d4pg_tpu/ops/gated_delta.py::gated_delta_chunked",
    "d4pg_tpu/ops/gated_delta.py::unit_lower_inverse",
    "d4pg_tpu/ops/gated_delta.py::inverse_by_rows",
    "d4pg_tpu/ops/gated_delta.py::diagonal_blocks",
    "d4pg_tpu/ops/gated_delta.py::join_inverses",
    # The device priority tree's traced primitives (replay/device_per.py):
    # every one is traced into the megastep or the per-flush tree seed.
    "d4pg_tpu/replay/device_per.py::repair_ancestors",
    "d4pg_tpu/replay/device_per.py::rebuild_ancestors",
    "d4pg_tpu/replay/device_per.py::set_leaves",
    "d4pg_tpu/replay/device_per.py::update_leaves_last_wins",
    "d4pg_tpu/replay/device_per.py::stratified_prefixes",
    "d4pg_tpu/replay/device_per.py::descend_prefix_gather",
    "d4pg_tpu/replay/device_per.py::left_by_select",
    "d4pg_tpu/replay/device_per.py::descend_prefix",
    "d4pg_tpu/replay/device_per.py::lane_draw",
    "d4pg_tpu/replay/device_per.py::lane_min_leaf",
    "d4pg_tpu/replay/device_per.py::beta_at",
    "d4pg_tpu/replay/device_per.py::importance_weights",
    "d4pg_tpu/replay/device_per.py::write_back_lane",
    "d4pg_tpu/replay/device_per.py::tree_ingest_lane_body",
    # The Pallas descent kernel and its wrapper trace into the megastep
    # when device_tree_backend="pallas".
    "d4pg_tpu/ops/pallas_tree.py::_descend_kernel",
    "d4pg_tpu/ops/pallas_tree.py::descend_tile",
    "d4pg_tpu/ops/pallas_tree.py::_pick_rows",
    "d4pg_tpu/ops/pallas_tree.py::left_rows",
    "d4pg_tpu/ops/pallas_tree.py::find_prefix_pallas",
    # The sharded megastep's deterministic cross-shard combine: traced
    # into every sharded dispatch, so a host coercion here would smuggle
    # a sync into the zero-transfer loop exactly like the bodies above.
    "d4pg_tpu/parallel/dp.py::det_pmean",
    "d4pg_tpu/parallel/dp.py::_det_mean_buffer",
)

# numpy allocators flagged inside hot-path functions (np.asarray is
# exempt: it is a no-op on an existing same-dtype array, which is how
# the hot paths use it).
ALLOC_CALLS = (
    "stack", "concatenate", "vstack", "hstack", "empty", "zeros",
    "ones", "full", "array", "copy", "tile", "repeat",
)

# np.random attributes that are fine (explicit seeded generator API —
# RandomState included: a seeded instance is an explicit generator, and
# dm_control's task seeding requires one); everything else on np.random
# is hidden global state.
RNG_OK = (
    "default_rng", "Generator", "SeedSequence", "PCG64", "Philox",
    "RandomState",
)

# Local wrapper callables that jit their argument — functions passed to
# these are treated as jit-traced for the jit-purity check, in addition
# to @jax.jit/@jit/@partial(jax.jit, ...) decorators and
# `x = jax.jit(f)` assignments.
JIT_WRAPPER_CALLS = ("jit", "_act_jit")

# Blocking calls under a lock: method names that block on I/O, timers, or
# other threads. `.wait` on the lock object being held is exempt (that is
# the condition-variable pattern). `.join` is only flagged for no-arg /
# timeout-only calls (so `", ".join(parts)` never matches).
BLOCKING_SIMPLE_CALLS = ("sleep",)                     # time.sleep
BLOCKING_MODULE_CALLS = {
    "subprocess": ("run", "call", "check_call", "check_output", "Popen"),
    "os": ("system", "waitpid", "read", "write"),
}
BLOCKING_METHOD_CALLS = (
    "recv", "send", "sendall", "accept", "connect", "listen", "result",
)
BLOCKING_QUEUE_METHODS = ("get", "put")  # on names containing queue/_q

# Event-loop callback manifest (ISSUE 20): these functions run ON the
# netio FrameLoop thread — ONE thread serves every connection, so a
# single blocking call here stalls the whole fleet's I/O (the exact
# failure the event-loop port exists to remove). `module suffix::qual`
# keys like HOT_PATH_FUNCTIONS, except NESTED defs are NOT implicitly
# checked and must be listed explicitly (`Outer._tick` style): most
# closures in these files are done-callbacks that run on OTHER threads
# (batcher reply threads, replica-link readers), while loop-timer
# closures scheduled via call_soon/call_later DO run on the loop.
# `conn.send(...)` is exempt by name: that is the Connection frame-queue
# API (append + wake, non-blocking by contract), not a socket send —
# raw `sock.send/recv/accept` sites must carry a suppression proving
# the fd is non-blocking.
LOOP_CALLBACK_FUNCTIONS = (
    # the loop itself: everything dispatched from FrameLoop._run
    "d4pg_tpu/netio/loop.py::FrameLoop._run",
    "d4pg_tpu/netio/loop.py::FrameLoop._select_timeout",
    "d4pg_tpu/netio/loop.py::FrameLoop._drain_waker",
    "d4pg_tpu/netio/loop.py::FrameLoop._run_callbacks",
    "d4pg_tpu/netio/loop.py::FrameLoop._call_at",
    "d4pg_tpu/netio/loop.py::FrameLoop._run_timers",
    "d4pg_tpu/netio/loop.py::FrameLoop._do_accept",
    "d4pg_tpu/netio/loop.py::FrameLoop._shed_accept",
    "d4pg_tpu/netio/loop.py::FrameLoop._resume_accept",
    "d4pg_tpu/netio/loop.py::FrameLoop._close_listener",
    "d4pg_tpu/netio/loop.py::FrameLoop._on_readable",
    "d4pg_tpu/netio/loop.py::FrameLoop._check_read_deadline",
    "d4pg_tpu/netio/loop.py::FrameLoop._flush",
    "d4pg_tpu/netio/loop.py::FrameLoop._check_write_deadline",
    "d4pg_tpu/netio/loop.py::FrameLoop._set_mask",
    "d4pg_tpu/netio/loop.py::FrameLoop._protocol_error",
    "d4pg_tpu/netio/loop.py::FrameLoop._evict",
    "d4pg_tpu/netio/loop.py::FrameLoop._teardown",
    "d4pg_tpu/netio/loop.py::FrameLoop._begin_shutdown",
    "d4pg_tpu/netio/loop.py::FrameLoop._final_cleanup",
    # the chaos attackers ride the victim's own loop as timer callbacks
    "d4pg_tpu/netio/attack.py::tick_attacks",
    "d4pg_tpu/netio/attack.py::_quiet_close",
    "d4pg_tpu/netio/attack.py::_attack_socket",
    "d4pg_tpu/netio/attack.py::_start_slowloris",
    "d4pg_tpu/netio/attack.py::_start_slowloris._tick",
    "d4pg_tpu/netio/attack.py::_start_zero_window",
    "d4pg_tpu/netio/attack.py::_start_zero_window._tick",
    "d4pg_tpu/netio/attack.py::_start_fd_exhaust",
    "d4pg_tpu/netio/attack.py::_start_fd_exhaust._release",
    # front-end frame handlers: per-frame work on the loop thread — the
    # only slow work (inference / replica dispatch) must leave via a
    # batcher submit or an async client future, never block in place
    "d4pg_tpu/serve/server.py::PolicyServer._serve_conn",
    "d4pg_tpu/serve/server.py::PolicyServer._on_conn_open",
    "d4pg_tpu/serve/server.py::PolicyServer._on_conn_close",
    "d4pg_tpu/serve/server.py::PolicyServer._on_protocol_error",
    "d4pg_tpu/serve/server.py::PolicyServer._reply",
    "d4pg_tpu/serve/router.py::Router._serve_conn",
    "d4pg_tpu/serve/router.py::Router._admit_and_route",
    "d4pg_tpu/serve/router.py::Router._on_conn_open",
    "d4pg_tpu/serve/router.py::Router._on_conn_close",
    "d4pg_tpu/serve/router.py::Router._on_protocol_error",
    "d4pg_tpu/serve/router.py::Router._reply",
    "d4pg_tpu/serve/router.py::Router._route",
)
