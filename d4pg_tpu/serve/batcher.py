"""Dynamic micro-batching around the jitted deterministic actor.

The SEED-RL-shaped core of the serving subsystem: requests from any number
of connections funnel into ONE bounded queue consumed by ONE device thread,
which assembles batches under a ``(max_batch, max_wait_us)`` window — a
batch dispatches when it reaches ``max_batch`` rows or when ``max_wait_us``
has elapsed since its first request, whichever comes first. Batching turns
N tiny actor forwards into one device call, which is the entire throughput
story: per-call dispatch overhead dominates a 3×256 MLP forward (a
dispatch plus the reply fetch against a ~µs forward).

Shape discipline: batches are padded up to a small fixed ladder of bucket
sizes (powers of two up to ``max_batch``), so ``act_deterministic``
compiles ONCE per bucket at warmup and never again — in particular a
checkpoint hot-reload swaps ``params`` as a traced argument (same pytree
structure/shapes/dtypes ⇒ jit cache hit). :attr:`DynamicBatcher.compile_count`
counts actual traces via a trace-time side effect, so tests assert the
no-recompile property directly.

The staged observation batch is donated to the device computation
(``donate_argnums``): the input buffer's device memory is reused for the
output instead of holding both live — the same donation discipline as the
train step.

Load shedding is explicit and immediate: a full queue rejects the request
with ``queue_full`` (the caller replies ``OVERLOADED`` — clients see a
fast, honest no instead of a diverging latency tail), and requests whose
deadline expired while queued are dropped at assembly time with
``deadline`` (running them would waste a batch slot on an answer the
client already gave up on).
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Optional, Sequence

import numpy as np

from d4pg_tpu.agent.state import D4PGConfig
from d4pg_tpu.analysis.ledger import NULL_LEDGER
from d4pg_tpu.serve.stats import ServeStats
from d4pg_tpu.utils.profiling import StageTimers
from d4pg_tpu.analysis import lockwitness


class ShedError(Exception):
    """The request was load-shed, not failed. ``reason`` is the wire reason
    (``queue_full`` | ``deadline`` | ``draining``)."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


class _Request:
    __slots__ = ("obs", "deadline", "future", "t_submit")

    def __init__(self, obs, deadline, future, t_submit):
        self.obs = obs
        self.deadline = deadline    # absolute perf_counter seconds, or None
        self.future = future
        self.t_submit = t_submit


def default_buckets(max_batch: int) -> tuple:
    """Powers of two up to ``max_batch``, always ending exactly at it."""
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(int(max_batch))
    return tuple(sorted(set(out)))


class DynamicBatcher:
    """Single-device-thread dynamic batcher over ``act_deterministic``.

    ``submit(obs, deadline_s)`` → Future resolving to the env-scale action
    (normalize → actor → clip(−1,1) → affine to [low, high]); raises
    :class:`ShedError` through the future (or synchronously on queue-full)
    when shed.
    """

    # Unguarded cross-thread writes, each safe by argument (d4pglint
    # shared-mutable-state contract):
    _THREAD_SAFE = (
        # single transition None→exception; readers check-then-raise
        "_thread_error",
        # device thread is the ONLY writer (single-device-thread design);
        # the reply thread never touches the rotation
        "_staging_flip",
    )

    def __init__(
        self,
        config: D4PGConfig,
        params,
        *,
        max_batch: int = 64,
        max_wait_us: int = 2000,
        queue_limit: int = 256,
        buckets: Optional[Sequence[int]] = None,
        action_low=None,
        action_high=None,
        obs_norm_stats: Optional[dict] = None,
        obs_norm_clip: float = 5.0,
        obs_norm_eps: float = 1e-2,
        stats: Optional[ServeStats] = None,
        timers: Optional[StageTimers] = None,
        ledger=None,
        sentinel=None,
        guard_transfers: bool = False,
        name: str = "serve",
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if queue_limit < max_batch:
            raise ValueError(
                f"queue_limit ({queue_limit}) must be >= max_batch "
                f"({max_batch}): a full window must fit in the queue"
            )
        self.config = config
        self.max_batch = int(max_batch)
        self.max_wait_s = max_wait_us / 1e6
        self.queue_limit = int(queue_limit)
        self.buckets = (
            tuple(sorted(set(int(b) for b in buckets)))
            if buckets
            else default_buckets(max_batch)
        )
        if self.buckets[-1] != self.max_batch:
            raise ValueError(
                f"largest bucket ({self.buckets[-1]}) must equal max_batch "
                f"({self.max_batch})"
            )
        self.stats = stats or ServeStats(
            batch_edges=self.buckets,
            queue_edges=default_buckets(max(queue_limit, 1)),
        )
        self.timers = timers or StageTimers(annotate_prefix="serve/")

        self._obs_clip = float(obs_norm_clip)
        self._obs_norm_eps = float(obs_norm_eps)
        # Published as ONE (mean, std) tuple read exactly once per
        # normalize — hot reload (set_obs_norm) swaps it atomically from
        # the watcher thread while submit() reads it (the obs_norm.py
        # single-tuple-publication discipline).
        self._obs_pub = self._derive_obs_pub(obs_norm_stats)

        low = (
            np.full(config.action_dim, -1.0, np.float32)
            if action_low is None
            else np.asarray(action_low, np.float32)
        )
        high = (
            np.full(config.action_dim, 1.0, np.float32)
            if action_high is None
            else np.asarray(action_high, np.float32)
        )

        import jax
        import jax.numpy as jnp

        from d4pg_tpu.agent import act_deterministic

        self._trace_count = 0
        identity_bounds = bool(np.all(low == -1.0) and np.all(high == 1.0))
        low_j, high_j = jnp.asarray(low), jnp.asarray(high)

        def infer(params, obs):
            # Trace-time side effect: this line executes only when jit
            # actually (re)traces — the compile counter hot-reload tests
            # assert on.
            self._trace_count += 1
            a = jnp.clip(act_deterministic(config, params, obs), -1.0, 1.0)
            if not identity_bounds:
                a = low_j + (a + 1.0) * 0.5 * (high_j - low_j)
            return a

        # The obs batch is DONATED: its device buffer is dead after the
        # forward and XLA may write the actions into it.
        self._infer = jax.jit(infer, donate_argnums=(1,))
        # Recompile sentinel (--debug-guards): the jit cache must hold
        # exactly one program per bucket after warmup; a hot reload or a
        # stray dtype drift that retraces trips check(). The trace-count
        # side effect above stays as the wire-visible compile_count.
        # ``name`` scopes the sentinel entry and the ledger staging groups:
        # a multi-policy server runs one batcher PER resident policy, and
        # two batchers sharing the literal "serve.infer" key would pool
        # their compile budgets (hiding a per-policy retrace) and alias
        # each other's staging-slot generations. Default stays "serve" so
        # single-policy traces/budgets are unchanged.
        self.name = name
        self._sentinel = sentinel
        if sentinel is not None:
            sentinel.track(
                f"{name}.infer", self._infer, budget=len(self.buckets)
            )
        # Transfer guard (--debug-guards): steady-state dispatch must see
        # only device-resident operands; the staging device_put below is
        # the one explicit, exempt copy. Resolved once here — the device
        # loop must not pay import machinery per batch.
        self._dispatch_guard = contextlib.nullcontext
        if guard_transfers:
            from d4pg_tpu.analysis.transfer import no_implicit_transfers

            self._dispatch_guard = no_implicit_transfers
        self._jnp = jnp
        # Params live on device once; set_params swaps this reference
        # atomically (device thread reads it once per batch, so an in-flight
        # batch finishes on the params it started with).
        self._params = jax.device_put(params)
        self._device_put = jax.device_put

        # Preallocated per-bucket host staging, TWO rotating slots per
        # bucket: device_put may copy from host memory asynchronously, so
        # the buffer a dispatch was staged from must not be overwritten
        # while its H2D can still be in flight. Two slots are sufficient
        # ONLY because ``_inflight`` below bounds the device thread to two
        # outstanding batches: the reply thread's ``np.asarray`` on batch N
        # synchronizes on N's compute — which device-order implies N's H2D
        # finished — before releasing the permit that lets the device
        # thread stage batch N+2 into N's slot. Without that bound an
        # async backend (TPU dispatch returns immediately) would let the
        # host run arbitrarily far ahead, overwriting live staging and
        # growing the reply queue without limit.
        self._staging = {
            b: [np.zeros((b, config.obs_dim), np.float32) for _ in range(2)]
            for b in self.buckets
        }
        self._staging_flip = {b: 0 for b in self.buckets}
        self._inflight = threading.Semaphore(2)
        # Staging ledger (--debug-guards): generation-tags the 2-slot
        # rotation above; a write into a slot whose dispatch the reply
        # thread hasn't fetched yet raises at the overwrite site. Group
        # names precomputed — no per-batch f-string on the device loop.
        self._ledger = ledger if ledger is not None else NULL_LEDGER
        self._staging_group = {b: f"{name}.staging[{b}]" for b in self.buckets}
        # Test hook (staging-ledger stress test): pin the rotation to one
        # slot to seed the PR-2/PR-3 early-reuse bug class deliberately.
        self._test_force_flip: Optional[int] = None

        self._queue: deque[_Request] = deque()
        # Witnessed under --debug-guards: the name is the lock's static
        # node id in benchmarks/lock_order_graph.json (lockwitness docs).
        self._cond = lockwitness.named_condition("DynamicBatcher._cond")
        self._draining = False
        self._stopped = False
        self._thread: Optional[threading.Thread] = None
        self._thread_error: Optional[BaseException] = None
        # Reply distribution runs on its OWN thread: resolving futures fires
        # the callers' callbacks (the server writes a socket frame per
        # reply), and doing that inline would stall the device thread for
        # the whole fan-out — the next batch's assembly+dispatch should
        # overlap it instead. The device thread hands over the DEVICE
        # result array; the reply thread pays the D2H fetch too.
        self._reply_q: deque = deque()
        self._reply_cond = lockwitness.named_condition(
            "DynamicBatcher._reply_cond"
        )
        self._reply_thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------ lifecycle
    def start(self, warmup: bool = True) -> None:
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("batcher device thread already running")
        if warmup:
            self.warmup()
        with self._cond:  # same guard as every other _draining/_stopped write
            self._draining = False
            self._stopped = False
        self._thread = threading.Thread(
            target=self._device_loop, name="serve-batcher", daemon=True
        )
        self._thread.start()
        self._reply_thread = threading.Thread(
            target=self._reply_loop, name="serve-reply", daemon=True
        )
        self._reply_thread.start()

    def warmup(self) -> None:
        """Compile every bucket up front so no live request ever pays a
        compile (first-request latency would otherwise be seconds)."""
        import warnings

        with warnings.catch_warnings():
            # The CPU backend cannot honor donation and says so once per
            # bucket compile; on accelerators the donation is real. The
            # condition is expected, not actionable — keep serve logs clean.
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            for b in self.buckets:
                a = self._infer(
                    self._params, self._jnp.zeros((b, self.config.obs_dim))
                )
            np.asarray(a)

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop the device thread. ``drain=True``: new submissions shed
        ``draining`` but everything already queued is answered first."""
        with self._cond:
            self._draining = True
            if not drain:
                while self._queue:
                    req = self._queue.popleft()
                    self._shed(req, "draining")
            self._stopped = not drain or not self._queue
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise RuntimeError("batcher device thread failed to drain")
            self._thread = None
        if self._reply_thread is not None:
            with self._reply_cond:
                self._reply_q.append(None)  # sentinel AFTER the last batch
                self._reply_cond.notify()
            self._reply_thread.join(timeout)
            if self._reply_thread.is_alive():
                raise RuntimeError("batcher reply thread failed to drain")
            self._reply_thread = None

    @property
    def compile_count(self) -> int:
        """Number of times the inference function was traced (== compiled
        programs). Stable across hot reloads by construction."""
        return self._trace_count

    @property
    def queue_depth(self) -> int:
        with self._cond:
            return len(self._queue)

    def check_alive(self) -> None:
        if self._thread_error is not None:
            raise RuntimeError("batcher device thread died") from self._thread_error

    # ------------------------------------------------------------ hot reload
    def set_params(self, params, version: Optional[int] = None) -> None:
        """Swap serving params. The new pytree must match the compiled
        structure/shapes (same actor architecture) — then the swap is a jit
        cache hit and costs zero recompiles; a mismatch raises here, before
        the live reference moves."""
        import jax

        new = jax.device_put(params)
        old_td = jax.tree_util.tree_structure(self._params)
        new_td = jax.tree_util.tree_structure(new)
        if old_td != new_td:
            raise ValueError("new params tree structure differs from serving tree")
        for a, b in zip(
            jax.tree_util.tree_leaves(self._params), jax.tree_util.tree_leaves(new)
        ):
            if np.shape(a) != np.shape(b):
                raise ValueError(
                    f"new params leaf shape {np.shape(b)} differs from "
                    f"serving shape {np.shape(a)}"
                )
        self._params = new  # atomic reference swap
        self.stats.inc("params_reloads")
        if version is not None:
            with self.stats._lock:
                self.stats.params_version = version
        else:
            self.stats.inc("params_version")

    def _derive_obs_pub(self, stats: Optional[dict]):
        """(mean_f32, std_f32_floored) from persisted Welford stats, or
        None when normalization is off — the same derivation the trainer's
        RunningObsNorm.load_state_dict applies."""
        if stats is None:
            return None
        count = float(stats["count"])
        mean = np.asarray(stats["mean"], np.float64)
        if mean.shape != (self.config.obs_dim,):
            raise ValueError(
                f"obs_norm stats are {mean.shape}-shaped, obs_dim is "
                f"{self.config.obs_dim}"
            )
        m2 = np.asarray(stats["m2"], np.float64)
        std = (
            np.sqrt(np.maximum(m2 / count, 0.0))
            if count > 0
            else np.ones_like(mean)
        )
        return (
            mean.astype(np.float32),
            np.maximum(std, self._obs_norm_eps).astype(np.float32),
        )

    def set_obs_norm(self, stats: Optional[dict]) -> None:
        """Hot-swap the normalizer statistics (bundle re-export flow):
        params trained under fresher running statistics must be served
        with them — swapping one without the other silently scales the
        net's inputs off its trained distribution."""
        self._obs_pub = self._derive_obs_pub(stats)  # atomic publication

    # ------------------------------------------------------------ submission
    def _normalize(self, obs: np.ndarray) -> np.ndarray:
        obs = np.asarray(obs, np.float32).reshape(self.config.obs_dim)
        pub = self._obs_pub  # one read: matched (mean, std), never torn
        if pub is None:
            return obs
        mean, std = pub
        return np.clip((obs - mean) / std, -self._obs_clip, self._obs_clip)

    def submit(self, obs: np.ndarray, deadline_s: Optional[float] = None) -> Future:
        """Enqueue one observation. ``deadline_s`` is relative seconds the
        client is willing to wait; past it the request is shed rather than
        computed. Raises :class:`ShedError` synchronously on queue-full /
        draining (the fast path for the overload reply)."""
        self.check_alive()
        self.stats.inc("requests_total")
        t = time.perf_counter()
        req = _Request(
            self._normalize(obs),
            None if deadline_s is None else t + deadline_s,
            Future(),
            t,
        )
        with self._cond:
            if self._draining:
                self.stats.inc("shed_draining")
                raise ShedError("draining")
            if len(self._queue) >= self.queue_limit:
                self.stats.inc("shed_queue_full")
                raise ShedError("queue_full")
            self._queue.append(req)
            self.stats.inc("inflight")
            self.stats.queue_hist.add(len(self._queue))
            self._cond.notify()
        # Outside the lock: the callback may fire inline if the device
        # thread already resolved the future, and it takes the stats lock.
        # add_done_callback fires exactly once on EVERY resolution path
        # (reply, shed, device/reply-thread death sweep, cancel), which is
        # what makes the gauge trustworthy as a dispatch-weight signal.
        req.future.add_done_callback(self._dec_inflight)
        return req.future

    def _dec_inflight(self, _fut) -> None:
        self.stats.inc("inflight", -1)

    def _shed(self, req: _Request, reason: str) -> None:
        if reason == "deadline":
            self.stats.inc("shed_deadline")
        elif reason == "draining":
            self.stats.inc("shed_draining")
        if not req.future.set_running_or_notify_cancel():
            return
        req.future.set_exception(ShedError(reason))

    # ------------------------------------------------------------ device loop
    def _take_batch(self) -> Optional[list]:
        """Block for the first request, then fill the window: up to
        ``max_batch`` rows or ``max_wait_s`` after the first row, whichever
        first. Returns None when stopped and drained."""
        with self._cond:
            while not self._queue:
                if self._stopped or (self._draining and not self._queue):
                    return None
                self._cond.wait(0.05)
            batch = [self._queue.popleft()]
            window_end = time.perf_counter() + self.max_wait_s
            while len(batch) < self.max_batch:
                while self._queue and len(batch) < self.max_batch:
                    batch.append(self._queue.popleft())
                if len(batch) >= self.max_batch or self._draining:
                    break
                remaining = window_end - time.perf_counter()
                if remaining <= 0:
                    break
                self._cond.wait(remaining)
                if not self._queue and time.perf_counter() >= window_end:
                    break
            return batch

    def _device_loop(self) -> None:
        live: list = []  # the in-hand batch; ownership moves to the reply
        # queue on append, so the except sweep below never double-resolves
        try:
            while True:
                batch = self._take_batch()
                if batch is None:
                    return
                now = time.perf_counter()
                live = []
                for req in batch:
                    if req.deadline is not None and now > req.deadline:
                        self._shed(req, "deadline")
                    elif req.future.set_running_or_notify_cancel():
                        live.append(req)
                if not live:
                    continue
                n = len(live)
                bucket = next(b for b in self.buckets if b >= n)
                # Backpressure: at most 2 batches between here and the
                # reply thread's fetch (staging-slot safety + bounded
                # reply queue). The timeout loop keeps a dead reply
                # thread from wedging this one forever.
                while not self._inflight.acquire(timeout=0.5):
                    if self._thread_error is not None:
                        raise RuntimeError(
                            "reply thread died; device thread stopping"
                        ) from self._thread_error
                with self.timers.stage("assemble"):
                    flip = self._staging_flip[bucket]
                    if self._test_force_flip is not None:
                        flip = self._test_force_flip
                    self._staging_flip[bucket] = 1 - flip
                    self._ledger.write(self._staging_group[bucket], flip)
                    staging = self._staging[bucket][flip]
                    for i, req in enumerate(live):
                        staging[i] = req.obs
                with self.timers.stage("device_infer"):
                    # device_put copies the staging slot to a fresh device
                    # buffer (which infer then donates) — the one explicit,
                    # guard-exempt transfer. The dispatch is async — the
                    # reply thread pays the D2H fetch, so this thread moves
                    # straight on to the next batch.
                    dev_obs = self._device_put(staging)
                    with self._dispatch_guard():
                        dev_actions = self._infer(self._params, dev_obs)
                # The hold pins the staging slot until the reply thread's
                # D2H fetch proves the dispatch (and its H2D) finished.
                # holder formatted only for a real ledger — guards-off
                # batches must not pay a per-batch f-string.
                hold = self._ledger.hold(
                    self._staging_group[bucket], flip,
                    holder=(
                        f"dispatch(n={n})"
                        if self._ledger is not NULL_LEDGER
                        else None
                    ),
                )
                with self._reply_cond:
                    self._reply_q.append((live, dev_actions, hold))
                    self._reply_cond.notify()
                live = []  # resolved (or failed) by the reply thread now
                self.stats.observe_batch(n, bucket)
                with self._cond:
                    if self._draining and not self._queue:
                        self._stopped = True
                        self._cond.notify_all()
        except BaseException as e:
            self._thread_error = e
            # Fail everything this thread still owns — the queue AND the
            # in-hand `live` batch (whose futures are already RUNNING but
            # were never handed to the reply queue): a dead device thread
            # must not leave any client waiting out its full timeout.
            for req in live:
                if not req.future.done():
                    req.future.set_exception(e)
            with self._cond:
                pending, self._queue = list(self._queue), deque()
                self._stopped = True
                self._cond.notify_all()
            for req in pending:
                if req.future.set_running_or_notify_cancel():
                    req.future.set_exception(e)
            raise

    def _reply_loop(self) -> None:
        try:
            while True:
                with self._reply_cond:
                    # Bounded wait: the notifier (device thread) can die
                    # without stop() ever pushing the sentinel — this
                    # thread must wake on its own clock and EXIT once the
                    # device thread is gone and the reply queue is drained
                    # (its death sweep already failed everything queued
                    # behind us).
                    while not self._reply_q:
                        if self._thread_error is not None:
                            return
                        self._reply_cond.wait(0.5)
                    item = self._reply_q.popleft()
                if item is None:
                    return
                live, dev_actions, hold = item
                with self.timers.stage("reply"):
                    # D2H fetch synchronizes on this batch's compute (and
                    # transitively its H2D) — its staging slot is free the
                    # moment this returns, so the permit (and the ledger
                    # hold) is released here.
                    actions = np.asarray(dev_actions)
                    hold.release()
                    self._inflight.release()
                    t_done = time.perf_counter()
                    for i, req in enumerate(live):
                        # per-row copy: the futures outlive this loop and
                        # must not alias one shared buffer — aliasing IS
                        # the bug class the ledger polices
                        req.future.set_result(actions[i].copy())  # d4pglint: disable=hot-path-alloc
                        self.stats.latency.add(t_done - req.t_submit)
                    self.stats.inc("replies_ok", len(live))
        except BaseException as e:
            self._thread_error = e
            # fail the batches still queued for reply, then everything in
            # the submit queue via the device-thread contract; the device
            # thread notices _thread_error in its bounded acquire loop
            with self._reply_cond:
                items, self._reply_q = list(self._reply_q), deque()
            for item in items:
                if item is None:
                    continue
                for req in item[0]:
                    if not req.future.done():
                        req.future.set_exception(e)
            raise
