"""d4pglint self-tests: per check, a bad fixture that MUST fire, a good
fixture that must NOT, and proof the ``# d4pglint: disable=`` suppression
silences exactly that finding. Plus: the repo itself lints clean (the
tier-1 contract scripts/lint.sh enforces), and the benchmark/metrics
schema checker's own good/bad fixtures.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap

import pytest

from tools.d4pglint import ALL_CHECKS, lint_paths, lint_source
from tools.d4pglint.schema_check import (
    check_benchmark_json,
    check_metrics_jsonl,
)

# A minimal conforming model of serve/protocol.py: every wire id, the
# protocol-module codecs, MAX_PAYLOAD-bounded framing, and the prober
# endpoint. Shared with tests/test_wholeprog.py (its multi-file endpoint
# fixtures need a clean protocol module in the map) so the two files can
# never drift on what "conforming" means.
PROTOCOL_GOOD_SRC = """
import struct

MAX_PAYLOAD = 1 << 20
PROTOCOL_VERSION = 1
HEADER = struct.Struct("<2sBBII")

ACT = 1
ACT_OK = 2
OVERLOADED = 3
ERROR = 4
HEALTHZ = 5
HEALTHZ_OK = 6
HELLO = 7
HELLO_OK = 8
WINDOWS = 9
WINDOWS_OK = 10
ACT2 = 11
WINDOWS2 = 12
FEEDBACK = 13
FEEDBACK_OK = 14


class ProtocolError(Exception):
    pass


def read_frame(stream):
    length = 0
    if length > MAX_PAYLOAD:
        raise ProtocolError("oversized")
    return HEALTHZ_OK, 0, b""


def write_frame(sock, msg_type, req_id, payload=b""):
    if len(payload) > MAX_PAYLOAD:
        raise ProtocolError("oversized")


def encode_act(obs, deadline_us=0):
    return b""


def decode_act(payload, obs_dim):
    return payload, 0


def encode_act2(obs, deadline_us=0, policy_id="default", qos=0, tenant=""):
    return b""


def decode_act2(payload):
    return payload, 0, "default", 0, ""


def encode_action(action):
    return b""


def decode_action(payload):
    return payload


def encode_feedback(reward, action, next_obs, log_prob=0.0,
                    terminated=False, truncated=False, policy_id=None):
    return b""


def decode_feedback(payload):
    return {}


def probe_healthz(host, port):
    msg_type, _req_id, payload = read_frame(None)
    if msg_type != HEALTHZ_OK:
        raise ProtocolError("unexpected healthz reply")
    return {}
"""

# (check_id, relpath, bad_src, good_src) — relpath matters: several checks
# key on the manifests in tools/d4pglint/config.py.
FIXTURES = [
    (
        "host-jax-import",
        "d4pg_tpu/runtime/actor_pool.py",
        """
        import numpy as np
        import jax
        """,
        """
        import numpy as np

        def act():
            import jax  # lazy: only the paths that need it pay it
            return jax
        """,
    ),
    (
        "lock-blocking-call",
        "d4pg_tpu/runtime/x.py",
        """
        import time

        def flush(self):
            with self._lock:
                time.sleep(0.1)
        """,
        """
        import time

        def flush(self):
            with self._lock:
                n = self._n
            time.sleep(0.1)

        def wait_pattern(self):
            with self._cond:
                self._cond.wait(1.0)  # cv pattern: waiting the held lock

        def join_strings(self):
            with self._lock:
                return ", ".join(self.parts)  # str.join is not a thread join
        """,
    ),
    (
        "shared-mutable-state",
        "d4pg_tpu/runtime/x.py",
        """
        import threading

        class Pump:
            def start(self):
                threading.Thread(target=self._loop, name="p", daemon=True).start()

            def _loop(self):
                self.count = 1
        """,
        """
        import threading

        class Pump:
            _THREAD_SAFE = ("count",)  # single-writer, readers tolerate staleness

            def start(self):
                threading.Thread(target=self._loop, name="p", daemon=True).start()

            def _loop(self):
                self.count = 1
                with self._lock:
                    self.guarded = 2
        """,
    ),
    (
        "wall-clock-deadline",
        "d4pg_tpu/runtime/x.py",
        """
        import time

        def deadline():
            return time.time() + 5.0
        """,
        """
        import time

        def deadline():
            return time.monotonic() + 5.0
        """,
    ),
    (
        "broad-except",
        "d4pg_tpu/runtime/x.py",
        """
        def f():
            try:
                g()
            except Exception:
                pass
        """,
        """
        def f():
            try:
                g()
            except ValueError:
                pass
            try:
                g()
            except Exception as e:
                print(f"context: {e}")
            try:
                g()
            except BaseException:
                raise
        """,
    ),
    (
        "jit-purity",
        "d4pg_tpu/agent/x.py",
        """
        import jax
        import numpy as np

        def step(x):
            return np.asarray(x) + 1

        jit_step = jax.jit(step)
        """,
        """
        import jax
        import jax.numpy as jnp
        import numpy as np

        def step(x):
            return jnp.asarray(x) + 1

        jit_step = jax.jit(step)

        def host_helper(x):
            return np.asarray(x)  # not jit-traced: fine
        """,
    ),
    (
        "hot-path-alloc",
        "d4pg_tpu/replay/per.py",
        """
        import numpy as np

        class PrioritizedReplayBuffer:
            def sample_block(self, b, k):
                return np.stack([self.rows[i] for i in range(k)])
        """,
        """
        import numpy as np

        class PrioritizedReplayBuffer:
            def sample_block(self, b, k):
                def mk():  # nested lazy init closure: exempt
                    return np.zeros((b, k))

                st = self._staging or mk()
                st[:] = 0
                return st
        """,
    ),
    (
        "thread-discipline",
        "d4pg_tpu/runtime/x.py",
        """
        import threading

        def start(fn):
            threading.Thread(target=fn).start()
        """,
        """
        import threading

        def start(fn):
            threading.Thread(target=fn, name="worker", daemon=True).start()
        """,
    ),
    (
        "global-rng",
        "d4pg_tpu/replay/x.py",
        """
        import numpy as np

        def draw(n):
            return np.random.uniform(size=n)
        """,
        """
        import numpy as np

        def draw(n, rng=None):
            rng = rng or np.random.default_rng(0)
            return rng.uniform(size=n)
        """,
    ),
    (
        "unbounded-retry",
        "d4pg_tpu/runtime/x.py",
        """
        import time

        def connect_forever(mk):
            while True:
                try:
                    return mk()
                except OSError:
                    time.sleep(1.0)

        def connect_forever_while1(mk):
            while 1:
                try:
                    return mk()
                except OSError:
                    time.sleep(1.0)
        """,
        """
        import time

        from d4pg_tpu.utils.retry import Backoff

        def connect_bounded(mk):
            for attempt in Backoff(max_attempts=5):
                try:
                    return mk()
                except OSError:
                    continue  # Backoff sleeps between bounded attempts
            raise TimeoutError("gave up")

        def connect_range(mk):
            for attempt in range(5):
                try:
                    return mk()
                except OSError:
                    time.sleep(0.1)  # bounded by the range
            raise TimeoutError("gave up")

        def loop_that_escapes(mk):
            while True:
                try:
                    return mk()
                except OSError:
                    raise  # no silent retry: escapes the loop

        def condition_bounded(mk, stop):
            while not stop.is_set():
                try:
                    return mk()
                except OSError:
                    time.sleep(0.1)  # terminates via the loop condition

        def event_loop_with_inner_bounded_retry(q, send):
            while True:  # long-lived event loop, not itself a retry
                msg = q.get()
                for attempt in range(3):
                    try:
                        send(msg)
                        break
                    except OSError:
                        time.sleep(0.1)  # bounded by the INNER range
        """,
    ),
    (
        "device-loop-transfer",
        "d4pg_tpu/runtime/megastep.py",
        """
        import numpy as np

        def megastep_uniform_body(config, k, batch, state, ring, key):
            idx = np.arange(4)
            return ring.size.item()
        """,
        """
        import jax.numpy as jnp
        import numpy as np

        def megastep_uniform_body(config, k, batch, state, ring, key):
            idx = jnp.arange(4)

            def loss(p):  # nested closures trace too — but this is clean
                return jnp.sum(p[idx])

            return loss

        def host_helper(x):
            return np.asarray(x).item()  # not in the manifest: fine
        """,
    ),
    (
        "counter-discipline",
        "d4pg_tpu/serve/stats.py",
        """
        import threading

        class ServeStats:
            def __init__(self):
                self._lock = threading.Lock()
                self.requests_total = 0

            def admit(self):
                self.requests_total += 1
        """,
        """
        import threading

        class ServeStats:
            def __init__(self):
                self._lock = threading.Lock()
                self.requests_total = 0

            def admit(self):
                with self._lock:
                    self.requests_total += 1
        """,
    ),
    (
        "loop-blocking-call",
        "d4pg_tpu/serve/router.py",
        """
        import time

        class Router:
            def _serve_conn(self, conn, msg_type, req_id, payload):
                time.sleep(0.1)
                conn.sock.recv(4096)
        """,
        """
        class Router:
            def _serve_conn(self, conn, msg_type, req_id, payload):
                # conn.send is the frame-queue API (append + wake): exempt
                conn.send(2, req_id, payload)
                # a stall becomes a loop TIMER, never a sleep on the loop
                self._loop.call_later(
                    0.1, self._admit_and_route, conn, req_id
                )

            def _admit_and_route(self, conn, req_id):
                def done(f):
                    # nested def, not in the manifest: runs on the
                    # replica link's reader thread, so result() is fine
                    conn.send(2, req_id, f.result())

                self._dispatch().add_done_callback(done)
        """,
    ),
    (
        "lock-order",
        "d4pg_tpu/runtime/x.py",
        """
        import threading

        class Pump:
            def __init__(self):
                self._alock = threading.Lock()
                self._block = threading.Lock()

            def forward(self):
                with self._alock:
                    with self._block:
                        pass

            def backward(self):
                with self._block:
                    with self._alock:
                        pass
        """,
        """
        import threading

        class Pump:
            def __init__(self):
                self._alock = threading.Lock()
                self._block = threading.Lock()

            def forward(self):
                with self._alock:
                    with self._block:  # consistent global order
                        pass

            def backward(self):
                with self._alock:
                    pass
                with self._block:  # sequential, never nested inverted
                    pass
        """,
    ),
    (
        "protocol-conformance",
        "d4pg_tpu/serve/protocol.py",
        """
        ACT = 1
        ACT_OK = 1
        """,
        PROTOCOL_GOOD_SRC,
    ),
    (
        "thread-lifecycle",
        "d4pg_tpu/runtime/x.py",
        """
        import threading

        class Pump:
            def start(self):
                self._t = threading.Thread(
                    target=self._loop, name="pump", daemon=True
                )
                self._t.start()

            def _loop(self):
                self._cond.wait()

            def close(self):
                pass
        """,
        """
        import threading

        class Pump:
            _DETACHED_THREADS = ("pump-conn",)  # unblocked by close()'s socket close

            def start(self):
                self._t = threading.Thread(
                    target=self._loop, name="pump", daemon=True
                )
                self._t.start()
                threading.Thread(
                    target=self._loop, name="pump-conn", daemon=True
                ).start()

            def _loop(self):
                with self._cond:
                    self._cond.wait(0.5)

            def close(self):
                self._t.join(timeout=5)
        """,
    ),
    (
        "flowcheck",
        "d4pg_tpu/fleet/actor.py",
        # A consumed-but-unbooked exit: the else arm pops the pending
        # entry, then raises without booking a terminal disposition —
        # the exact FleetLink bug class the pass exists to catch. The
        # good twin books "dropped" before raising.
        """
        import threading

        class FleetLink:
            def __init__(self, on_ack):
                self._pending = {}
                self._pending_lock = threading.Lock()
                self._on_ack = on_ack

            def _read_loop(self):
                while True:
                    msg_type, req_id = self._recv()
                    with self._pending_lock:
                        n = self._pending.pop(req_id, None)
                    if n is None:
                        continue
                    if msg_type == 1:
                        self._on_ack("accepted", n)
                    elif msg_type == 2:
                        self._on_ack("stale", n)
                    elif msg_type == 3:
                        self._on_ack("shed", n)
                    else:
                        raise RuntimeError("unexpected reply type")

            def _fail_send(self, req_id):
                with self._pending_lock:
                    n = self._pending.pop(req_id, None)
                if n is not None:
                    self._on_ack("dropped", n)

        class FleetActor:
            def __init__(self):
                self._stats_lock = threading.Lock()
                self._stats = {}

            def _inc(self, key, n=1):
                with self._stats_lock:
                    self._stats[key] += n

            def run(self):
                self._inc("windows_emitted", 1)

            def _on_ack(self, kind, n):
                self._inc(
                    {
                        "accepted": "windows_acked",
                        "stale": "windows_stale",
                        "shed": "windows_shed",
                        "dropped": "windows_dropped_reconnect",
                    }[kind],
                    n,
                )
        """,
        """
        import threading

        class FleetLink:
            def __init__(self, on_ack):
                self._pending = {}
                self._pending_lock = threading.Lock()
                self._on_ack = on_ack

            def _read_loop(self):
                while True:
                    msg_type, req_id = self._recv()
                    with self._pending_lock:
                        n = self._pending.pop(req_id, None)
                    if n is None:
                        continue
                    if msg_type == 1:
                        self._on_ack("accepted", n)
                    elif msg_type == 2:
                        self._on_ack("stale", n)
                    elif msg_type == 3:
                        self._on_ack("shed", n)
                    else:
                        self._on_ack("dropped", n)
                        raise RuntimeError("unexpected reply type")

            def _fail_send(self, req_id):
                with self._pending_lock:
                    n = self._pending.pop(req_id, None)
                if n is not None:
                    self._on_ack("dropped", n)

        class FleetActor:
            def __init__(self):
                self._stats_lock = threading.Lock()
                self._stats = {}

            def _inc(self, key, n=1):
                with self._stats_lock:
                    self._stats[key] += n

            def run(self):
                self._inc("windows_emitted", 1)

            def _on_ack(self, kind, n):
                self._inc(
                    {
                        "accepted": "windows_acked",
                        "stale": "windows_stale",
                        "shed": "windows_shed",
                        "dropped": "windows_dropped_reconnect",
                    }[kind],
                    n,
                )
        """,
    ),
    (
        "unused-suppression",
        "d4pg_tpu/runtime/x.py",
        """
        import time

        def f():
            return time.monotonic()  # d4pglint: disable=wall-clock-deadline  -- stale: the fix landed
        """,
        """
        import time

        def g():
            return time.time()  # d4pglint: disable=wall-clock-deadline  -- human-facing timestamp
        """,
    ),
]

assert {f[0] for f in FIXTURES} == set(ALL_CHECKS), "fixture per check"


def _lint(src: str, relpath: str, check: str):
    return lint_source(textwrap.dedent(src), relpath, checks=[check])


@pytest.mark.parametrize(
    "check,relpath,bad,good", FIXTURES, ids=[f[0] for f in FIXTURES]
)
def test_bad_fixture_fires_good_fixture_clean(check, relpath, bad, good):
    findings, _ = _lint(bad, relpath, check)
    assert findings, f"{check}: bad fixture produced no finding"
    assert all(f.check == check for f in findings)
    findings, _ = _lint(good, relpath, check)
    assert findings == [], f"{check}: good fixture fired: {findings}"


@pytest.mark.parametrize(
    "check,relpath,bad,good", FIXTURES, ids=[f[0] for f in FIXTURES]
)
def test_suppression_silences_exactly_the_finding(check, relpath, bad, good):
    findings, _ = _lint(bad, relpath, check)
    lines = textwrap.dedent(bad).splitlines()
    for f in findings:
        lines[f.line - 1] += f"  # d4pglint: disable={check}  -- test fixture"
    suppressed_src = "\n".join(lines)
    findings2, suppressed = lint_source(
        suppressed_src, relpath, checks=[check]
    )
    assert findings2 == []
    assert len(suppressed) == len(findings)  # audited, not vanished
    # an unrelated id must NOT suppress it
    other = next(c for c in ALL_CHECKS if c != check)
    lines = textwrap.dedent(bad).splitlines()
    for f in findings:
        lines[f.line - 1] += f"  # d4pglint: disable={other}"
    findings3, _ = lint_source("\n".join(lines), relpath, checks=[check])
    assert len(findings3) == len(findings)


def test_repo_lints_clean():
    """The tier-1 contract: zero findings over the product-code manifest
    (suppressions are allowed — they carry justifications)."""
    findings, _suppressed = lint_paths()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\n\ndef f():\n    return time.time()\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.d4pglint", str(bad)],
        capture_output=True, text=True, cwd="/root/repo",
    )
    assert proc.returncode == 1
    assert "wall-clock-deadline" in proc.stdout
    ok = tmp_path / "ok.py"
    ok.write_text("import time\n\ndef f():\n    return time.monotonic()\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.d4pglint", str(ok)],
        capture_output=True, text=True, cwd="/root/repo",
    )
    assert proc.returncode == 0, proc.stdout


def test_lint_counts_at_least_eight_checks():
    assert len(ALL_CHECKS) >= 8  # ISSUE-4 acceptance floor


# ------------------------------------------------------------- schema checks
def test_benchmark_schema_good_and_bad(tmp_path):
    good_obj = tmp_path / "a.json"
    good_obj.write_text(json.dumps({"backend": "cpu", "x": 1.0}))
    assert check_benchmark_json(str(good_obj)) == []
    for bad_doc in ["{", json.dumps({"x": 1}),
                    json.dumps([{"backend": "cpu"}]),
                    json.dumps(3), json.dumps({})]:
        p = tmp_path / "bad.json"
        p.write_text(bad_doc)
        assert check_benchmark_json(str(p)), f"accepted: {bad_doc!r}"


def test_metrics_jsonl_schema_good_and_bad(tmp_path):
    good = tmp_path / "metrics.jsonl"
    good.write_text(
        json.dumps({"step": 1, "t": 0.5, "loss": 1.25}) + "\n"
        + json.dumps({"step": 2, "t": 1.0, "loss": 1.0}) + "\n"
    )
    assert check_metrics_jsonl(str(good)) == []
    for bad_row in [
        "not json",
        json.dumps({"t": 0.5}),                       # no step
        json.dumps({"step": "three", "t": 0.5}),      # non-int step
        json.dumps({"step": 1}),                      # no t
        json.dumps({"step": 1, "t": 0.1, "env": "pendulum"}),  # non-numeric
    ]:
        p = tmp_path / "bad.jsonl"
        p.write_text(bad_row + "\n")
        assert check_metrics_jsonl(str(p)), f"accepted: {bad_row!r}"


def test_schema_check_passes_on_committed_artifacts():
    from tools.d4pglint.schema_check import check_tree

    assert check_tree("/root/repo") == []
