"""Replica front-end (`serve/router.py`) over real sockets: dispatch and
spread, health-driven ejection / re-admission, bounded failover (the
accounting identity: every request answered ok / OVERLOADED / error —
never silently lost), canary rollout promote + rollback, and the
PolicyClient bounded-retry satellite.

The subprocess/CLI half of this surface lives in scripts/router_smoke.sh
(tests/test_router_smoke.py) and the chaos-soak router leg; everything
here is in-process so kill instants and reload instants are deterministic.
"""

import socket
import threading
import time

import jax
import numpy as np
import pytest

from d4pg_tpu.agent import act_deterministic
from d4pg_tpu.agent.state import D4PGConfig
from d4pg_tpu.analysis import flowledger
from d4pg_tpu.serve import (
    PolicyBundle,
    PolicyClient,
    PolicyServer,
    Router,
    protocol,
)
from d4pg_tpu.serve.batcher import ShedError
from d4pg_tpu.serve.bundle import actor_template, export_bundle, load_bundle
from d4pg_tpu.serve.client import ConnectionClosed, Overloaded

CFG = D4PGConfig(obs_dim=4, action_dim=2, hidden_sizes=(8, 8))
OBS = np.array([0.1, -0.2, 0.05, 0.3], np.float32)
PARAMS = actor_template(CFG)


def _bundle(params=None, path=None):
    return PolicyBundle(
        config=CFG,
        actor_params=params if params is not None else PARAMS,
        action_low=np.full(2, -1.0, np.float32),
        action_high=np.full(2, 1.0, np.float32),
        obs_norm=None,
        meta={"source": "test"},
        path=path,
    )


def _ref(params, obs=OBS):
    return np.clip(
        np.asarray(act_deterministic(CFG, params, obs[None])[0]), -1.0, 1.0
    )


def _wait(cond, timeout=20.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def _server(bundle=None, port=0, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_wait_us", 200)
    kw.setdefault("watch_bundle", False)
    srv = PolicyServer(
        bundle if bundle is not None else _bundle(), port=port, **kw
    )
    srv.start()
    return srv


def _router(servers, **kw):
    kw.setdefault("probe_interval_s", 0.05)
    kw.setdefault("probe_timeout_s", 1.0)
    kw.setdefault("readmit_after", 2)
    r = Router([("127.0.0.1", s.port) for s in servers], port=0, **kw)
    r.start()
    r.wait_for_replicas(len(servers), timeout_s=60)
    return r


def kill_policy_server_abruptly(server) -> None:
    """Simulate SIGKILL on an in-process :class:`PolicyServer`: abortive-
    close the listener and every live connection (peers see an RST —
    exactly a killed process's teardown as observed from the wire), no
    drain, nothing answered. The REAL ``kill -9`` path runs through
    subprocess replicas in scripts/router_smoke.sh and chaos_soak.sh."""
    server._shutdown.set()
    server._loop.stop_accepting()
    for c in server._loop.connections():
        c.abort()  # RST, queued replies dropped — wire-identical to kill -9
    server._loop.close(flush_timeout_s=0.5)
    server.batcher.stop(drain=False, timeout=5)


def _drain_all(router, servers, killed=()):
    router.drain()
    for s in servers:
        if s not in killed:
            s.drain()


# --------------------------------------------------------------- dispatch
def test_roundtrip_spread_and_healthz():
    """Requests through the router match the direct forward; sequential
    traffic round-robins across both replicas (least-loaded ties rotate);
    router healthz carries the fleet view + the accounting surface."""
    servers = [_server() for _ in range(2)]
    router = _router(servers)
    try:
        ref = _ref(PARAMS)
        with PolicyClient("127.0.0.1", router.port) as c:
            for _ in range(10):
                np.testing.assert_allclose(c.act(OBS), ref, rtol=1e-5, atol=1e-6)
            h = c.healthz()
        assert h["router"] is True and h["status"] == "ok"
        assert h["admitted"] == 2 and len(h["replicas"]) == 2
        assert h["requests_total"] == 10
        assert h["answered_total"] == h["replies_ok"] == 10
        assert h["replies_overloaded"] == 0 and h["replies_error"] == 0
        # both replicas actually served (tie rotation, not lowest-index pin)
        assert all(r["ok"] >= 3 for r in h["replicas"]), h["replicas"]
        # the prober's replica view carries the healthz satellite fields
        for r in h["replicas"]:
            assert r["admitted"] is True and r["status"] == "ok"
            assert r["compile_count"] == 2  # buckets (1, 2), compiled once
            assert r["pid"] is not None
    finally:
        _drain_all(router, servers)


def test_replica_kill_mid_stream_fails_over_with_identity():
    """An abrupt replica death with requests in flight: every submitted
    request is still answered (bounded failover on the survivor), the dead
    replica is ejected, and nothing is silently lost."""
    servers = [_server() for _ in range(2)]
    # slow both device threads so the kill lands with requests IN FLIGHT
    for s in servers:
        real = s.batcher._infer

        def slow(p, o, _real=real):
            time.sleep(0.05)
            return _real(p, o)

        s.batcher._infer = slow
    router = _router(servers)
    try:
        with PolicyClient("127.0.0.1", router.port) as c:
            futs = [c.act_async(OBS) for _ in range(40)]
            time.sleep(0.1)  # several dispatched to each replica
            kill_policy_server_abruptly(servers[0])
            outcomes = {"ok": 0, "overloaded": 0}
            for f in futs:
                try:
                    f.result(60)
                    outcomes["ok"] += 1
                except Overloaded:
                    outcomes["overloaded"] += 1
            # the survivor absorbs everything the dead replica dropped
            assert outcomes["ok"] + outcomes["overloaded"] == 40
            assert outcomes["ok"] >= 30, outcomes
            # post-kill traffic flows on the survivor
            assert c.act(OBS, timeout=30).shape == (2,)
            h = c.healthz()
        assert h["requests_total"] == h["answered_total"] == 41
        assert h["retries"] >= 1  # in-flight work was actively rescued
        assert h["ejections"] >= 1
        dead = next(r for r in h["replicas"] if not r["admitted"])
        assert dead["ejected_reason"]
    finally:
        _drain_all(router, servers, killed=(servers[0],))


def test_all_replicas_ejected_router_answers_overloaded():
    servers = [_server()]
    router = _router(servers)
    try:
        with PolicyClient("127.0.0.1", router.port) as c:
            assert c.act(OBS).shape == (2,)
            kill_policy_server_abruptly(servers[0])
            _wait(
                lambda: router.healthz()["admitted"] == 0,
                msg="sole replica ejected",
            )
            with pytest.raises(Overloaded) as ei:
                c.act(OBS)
            assert "no_replicas" in str(ei.value)
            h = c.healthz()
        # the shed is ANSWERED — the identity holds through total outage
        assert h["requests_total"] == h["answered_total"] == 2
        assert h["replies_overloaded"] == 1
        assert h["status"] == "degraded"  # router alive, fleet gone
    finally:
        _drain_all(router, servers, killed=(servers[0],))


def test_restarted_replica_is_readmitted_after_k_probes():
    servers = [_server()]
    port = servers[0].port
    router = _router(servers, readmit_after=3)
    try:
        kill_policy_server_abruptly(servers[0])
        _wait(lambda: router.healthz()["admitted"] == 0, msg="ejection")
        restarted = _server(port=port)  # same address, fresh process state
        servers.append(restarted)
        _wait(lambda: router.healthz()["admitted"] == 1, msg="re-admission")
        h = router.healthz()
        assert h["replicas"][0]["healthy_streak"] >= 3
        kinds = [e["event"] for e in h["events_tail"]]
        assert "eject" in kinds and "admit" in kinds
        with PolicyClient("127.0.0.1", router.port) as c:
            np.testing.assert_allclose(
                c.act(OBS), _ref(PARAMS), rtol=1e-5, atol=1e-6
            )
    finally:
        _drain_all(router, servers, killed=(servers[0],))


def test_overloaded_replica_triggers_bounded_redispatch():
    """A replica that sheds (OVERLOADED) is retried on a different replica
    under the bounded budget — the client sees success, the router counts
    the retry."""
    servers = [_server() for _ in range(2)]

    def always_shed(obs, deadline_s=None):
        servers[0].stats.inc("shed_queue_full")
        raise ShedError("queue_full")

    servers[0].batcher.submit = always_shed
    router = _router(servers)
    try:
        with PolicyClient("127.0.0.1", router.port) as c:
            for _ in range(8):
                np.testing.assert_allclose(
                    c.act(OBS), _ref(PARAMS), rtol=1e-5, atol=1e-6
                )
            h = c.healthz()
        assert h["replies_ok"] == 8 and h["replies_overloaded"] == 0
        assert h["retries"] >= 1  # ~half the picks landed on the shedder
    finally:
        _drain_all(router, servers)


# ---------------------------------------------------------------- canary
def _canary_fleet(tmp_path, chaos=None, params_new=None, break_canary=False,
                  **router_kw):
    """Two replicas serving on-disk bundles (watching them), a canary
    source dir with new params, and a router wired for rollout.
    ``break_canary`` deletes the source's params file BEFORE the router
    starts (bundle.json still present, so the rollout triggers) — the
    deploy-I/O-failure path."""
    import os

    dirs = [str(tmp_path / f"replica{i}") for i in range(2)]
    for d in dirs:
        export_bundle(d, CFG, PARAMS)
    canary_dir = str(tmp_path / "canary")
    export_bundle(
        canary_dir,
        CFG,
        params_new
        if params_new is not None
        else jax.tree_util.tree_map(lambda x: x + 0.5, PARAMS),
    )
    if break_canary:
        os.remove(os.path.join(canary_dir, "actor_params.npz"))
    servers = [
        _server(load_bundle(d), watch_bundle=True, poll_interval_s=0.05)
        for d in dirs
    ]
    router = Router(
        [("127.0.0.1", s.port) for s in servers],
        port=0,
        bundle_dirs=dirs,
        probe_interval_s=0.05,
        probe_timeout_s=1.0,
        readmit_after=2,
        canary_bundle=canary_dir,
        canary_fraction=0.5,
        canary_min_samples=5,
        canary_window=64,
        canary_attest_timeout_s=20.0,
        chaos=chaos,
        **router_kw,
    )
    router.start()
    router.wait_for_replicas(2, timeout_s=60)
    return servers, router, dirs


def test_canary_rollout_auto_promotes(tmp_path):
    """Healthy canary: deploy → observe (split traffic) → promote rolls
    every baseline forward, each attested — and the whole rollout swaps
    params on live replicas with zero recompiles."""
    servers, router, dirs = _canary_fleet(tmp_path)
    params_new = jax.tree_util.tree_map(lambda x: x + 0.5, PARAMS)
    try:
        state = lambda: router.healthz()["canary"]["state"]  # noqa: E731
        _wait(lambda: state() != "idle", msg="rollout start")
        ref_old, ref_new = _ref(PARAMS), _ref(params_new)
        with PolicyClient("127.0.0.1", router.port) as c:
            # drive traffic until the verdict: every reply is one of the
            # two param sets, never garbage
            for _ in range(400):
                a = c.act(OBS, timeout=30)
                assert np.allclose(a, ref_old, atol=1e-5) or np.allclose(
                    a, ref_new, atol=1e-5
                ), a
                if state() == "idle":
                    break
                time.sleep(0.01)
            _wait(lambda: state() == "idle", msg="rollout settle")
            h = c.healthz()
            assert h["canary_promotions"] == 1 and h["canary_rollbacks"] == 0
            # every replica attests the version its OWN dir now carries
            # (the version vector is per-replica: each roll-forward is its
            # own attested write into that replica's bundle dir)...
            import os

            for r, d in zip(h["replicas"], dirs):
                assert r["bundle_mtime"] == os.stat(
                    os.path.join(d, "bundle.json")
                ).st_mtime
            # ...and serves the new params, with the bucket programs intact
            for _ in range(4):
                np.testing.assert_allclose(
                    c.act(OBS), ref_new, rtol=1e-5, atol=1e-6
                )
        for s in servers:
            assert s.batcher.compile_count == 2  # zero recompiles
            assert s.stats.params_reloads >= 1
        kinds = [e["event"] for e in router.healthz()["events_tail"]]
        assert "canary_start" in kinds and "canary_promoted" in kinds
    finally:
        _drain_all(router, servers)


def test_corrupt_canary_rolls_back_baselines_never_reload(tmp_path):
    """The canary_corrupt chaos fault: the deployed params are truncated,
    the canary replica's reload fails (degraded → ejected), the router
    auto-rolls-back, the canary re-admits on the RESTORED bundle, and the
    baseline replica never reloads at all."""
    from d4pg_tpu.chaos import ChaosInjector, ChaosPlan

    inj = ChaosInjector(ChaosPlan.parse("canary_corrupt@1"))
    servers, router, dirs = _canary_fleet(tmp_path, chaos=inj)
    try:
        _wait(
            lambda: router.stats.canary_rollbacks >= 1,
            msg="auto-rollback on corrupt canary",
        )
        assert inj.injections_total == 1
        _wait(
            lambda: router.healthz()["canary"]["state"] == "idle"
            and router.healthz()["admitted"] == 2,
            msg="rollback settle + re-admission",
        )
        h = router.healthz()
        assert h["canary_promotions"] == 0
        kinds = [e["event"] for e in h["events_tail"]]
        assert "canary_rollback" in kinds and "canary_rolled_back" in kinds
        # the canary is back on the old version; the baseline NEVER reloaded
        assert servers[0].stats.params_reloads == 0
        assert servers[0].healthz()["status"] == "ok"
        with PolicyClient("127.0.0.1", router.port) as c:
            for _ in range(6):
                np.testing.assert_allclose(
                    c.act(OBS), _ref(PARAMS), rtol=1e-5, atol=1e-6
                )
        for s in servers:
            assert s.batcher.compile_count == 2  # zero recompiles throughout
    finally:
        _drain_all(router, servers)


def test_deploy_io_error_rolls_back_instead_of_split_brain(tmp_path):
    """A mid-deploy I/O failure (here: the canary source's params file
    vanishes between the mtime check and the copy) must route through the
    normal rollback — the touched replica is restored and re-ejected until
    it attests the old version — instead of stranding it on a half-deployed
    rollout with the state machine stuck in idle."""
    servers, router, dirs = _canary_fleet(tmp_path, break_canary=True)
    try:
        _wait(
            lambda: router.stats.canary_rollbacks >= 1,
            msg="rollback on deploy I/O error",
        )
        _wait(
            lambda: router.healthz()["canary"]["state"] == "idle"
            and router.healthz()["admitted"] == 2,
            msg="rollback settle + re-admission",
        )
        h = router.healthz()
        assert h["canary_promotions"] == 0
        events = h["events_tail"]
        rb = next(e for e in events if e["event"] == "canary_rollback")
        assert "deploy I/O error" in rb["reason"], rb
        assert any(e["event"] == "canary_rolled_back" for e in events)
        # baseline untouched; the restored canary serves the OLD params
        assert servers[0].stats.params_reloads == 0
        with PolicyClient("127.0.0.1", router.port) as c:
            for _ in range(6):
                np.testing.assert_allclose(
                    c.act(OBS), _ref(PARAMS), rtol=1e-5, atol=1e-6
                )
    finally:
        _drain_all(router, servers)


def test_promote_io_error_rolls_back_whole_rollout(tmp_path):
    """The canary source vanishing DURING a rollout (after the canary
    deployed, before the promote step copies it to the baselines): the
    promote deploy raises, and the rollout must roll back — canary
    restored to the old bundle, baseline never touched — instead of
    spinning in 'promoting' forever."""
    import os

    servers, router, dirs = _canary_fleet(tmp_path)
    try:
        state = lambda: router.healthz()["canary"]["state"]  # noqa: E731
        _wait(lambda: state() != "idle", msg="rollout start")
        # canary (replica 1) is deployed by the tick that left idle; the
        # promote deploy to the baseline runs several ticks later (attest
        # + observe with min_samples of traffic) — break the source now
        os.remove(os.path.join(str(tmp_path / "canary"), "actor_params.npz"))
        with PolicyClient("127.0.0.1", router.port) as c:
            for _ in range(400):
                try:
                    c.act(OBS, timeout=30)
                except Overloaded:
                    # the rollback re-ejects EVERY touched replica, and
                    # this rollout touched both (canary + the backed-up
                    # promote target): a transient all-ejected window
                    # answering OVERLOADED(no_replicas) is the documented
                    # honest behavior, not a failure of this test
                    pass
                if router.stats.canary_rollbacks >= 1:
                    break
                time.sleep(0.01)
        _wait(
            lambda: router.stats.canary_rollbacks >= 1,
            msg="rollback on promote I/O error",
        )
        _wait(
            lambda: state() == "idle"
            and router.healthz()["admitted"] == 2,
            msg="rollback settle + re-admission",
        )
        h = router.healthz()
        # one rollout, one outcome: the promote VERDICT fired but the
        # rollout ended rolled back — it must never book a promotion too
        assert h["canary_promotions"] == 0 and h["canary_rollbacks"] == 1
        events = h["events_tail"]
        rb = next(e for e in events if e["event"] == "canary_rollback")
        assert "deploy I/O error during promote" in rb["reason"], rb
        # the promote target was backed up before its deploy failed, so
        # the rollback conservatively restores it (one reload of identical
        # old params — at most); the whole fleet ends on the OLD params
        assert servers[0].stats.params_reloads <= 1
        with PolicyClient("127.0.0.1", router.port) as c:
            for _ in range(6):
                np.testing.assert_allclose(
                    c.act(OBS), _ref(PARAMS), rtol=1e-5, atol=1e-6
                )
        for s in servers:
            assert s.batcher.compile_count == 2  # zero recompiles throughout
    finally:
        _drain_all(router, servers)


def test_canary_fraction_must_leave_both_groups_traffic():
    """fraction 0 routes nothing to the canary and fraction 1 starves the
    baseline — either way the comparison windows can never BOTH fill and
    the rollout would observe forever. Refused at construction."""
    for bad in (0.0, 1.0):
        with pytest.raises(ValueError, match="canary-fraction"):
            Router(
                [("127.0.0.1", 1)],
                bundle_dirs=["/tmp/x"],
                canary_bundle="/tmp/y",
                canary_fraction=bad,
            )


def test_observation_starved_rollout_rolls_back(tmp_path):
    """A rollout whose comparison windows never fill (no traffic) must
    not wedge in 'observing' forever: the observe deadline rolls it back
    so canary traffic unfreezes and newer versions can roll out later."""
    servers, router, dirs = _canary_fleet(
        tmp_path, canary_observe_timeout_s=0.6
    )
    try:
        # no ACT traffic at all: min_samples can never be reached
        _wait(
            lambda: router.stats.canary_rollbacks >= 1,
            msg="starvation rollback",
        )
        _wait(
            lambda: router.healthz()["canary"]["state"] == "idle"
            and router.healthz()["admitted"] == 2,
            msg="rollback settle",
        )
        events = router.healthz()["events_tail"]
        rb = next(e for e in events if e["event"] == "canary_rollback")
        assert "observation starved" in rb["reason"], rb
        with PolicyClient("127.0.0.1", router.port) as c:
            np.testing.assert_allclose(
                c.act(OBS), _ref(PARAMS), rtol=1e-5, atol=1e-6
            )
    finally:
        _drain_all(router, servers)


def test_stuck_replica_is_ejected_and_requests_rescued():
    """A replica whose device thread wedges still answers healthz ok — the
    prober alone would never eject it and its dispatched requests would
    hang forever, breaking the accounting identity. The stuck watchdog
    (--stuck-after) ejects it; closing the dispatch link fails the hung
    futures over onto the survivor."""
    release = threading.Event()
    servers = [_server() for _ in range(2)]
    real = servers[0].batcher._infer

    def wedged(p, o, _real=real):
        release.wait(120)  # healthz stays "ok" the whole time
        return _real(p, o)

    servers[0].batcher._infer = wedged
    router = _router(servers, stuck_after_s=0.4)
    try:
        assert servers[0].healthz()["status"] == "ok"
        with PolicyClient("127.0.0.1", router.port) as c:
            futs = [c.act_async(OBS) for _ in range(8)]
            ref = _ref(PARAMS)
            for f in futs:  # every request rescued, none abandoned
                np.testing.assert_allclose(f.result(30), ref, rtol=1e-5,
                                           atol=1e-6)
            h = c.healthz()
        assert h["requests_total"] == h["answered_total"] == 8
        assert h["replies_ok"] == 8
        assert h["retries"] >= 1
        events = router.healthz()["events_tail"]
        assert any(
            e["event"] == "eject" and e["reason"] == "stuck" for e in events
        ), [e["event"] for e in events]
    finally:
        release.set()
        _drain_all(router, servers)


# --------------------------------------------- PolicyClient retry satellite
class _ScriptedBackend:
    """Minimal protocol speaker for client-retry tests: each accepted
    connection runs one scripted behavior ('reset' = abortive close on
    accept; else a list of per-ACT replies: 'overloaded' | 'ok')."""

    def __init__(self, scripts):
        import socket

        self._sock = socket.create_server(("127.0.0.1", 0))
        self.port = self._sock.getsockname()[1]
        self._scripts = list(scripts)
        self._thread = threading.Thread(
            target=self._run, name="scripted-backend", daemon=True
        )
        self._thread.start()

    def _run(self):
        for script in self._scripts:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            if script == "reset":
                # accept the link, take ONE request, then RST — the death
                # lands mid-request, after connect succeeded (closing at
                # accept races the client's connect itself)
                try:
                    protocol.read_frame(conn.makefile("rb"))
                except OSError:
                    pass
                protocol.abortive_close(conn)
                continue
            replies = list(script)
            try:
                rfile = conn.makefile("rb")
                while True:
                    frame = protocol.read_frame(rfile)
                    if frame is None:
                        break
                    _t, req_id, _p = frame
                    kind = replies.pop(0) if replies else "ok"
                    if kind == "overloaded":
                        protocol.write_frame(
                            conn, protocol.OVERLOADED, req_id, b"queue_full"
                        )
                    else:
                        protocol.write_frame(
                            conn,
                            protocol.ACT_OK,
                            req_id,
                            protocol.encode_action(
                                np.zeros(2, np.float32)
                            ),
                        )
            except OSError:
                pass
            finally:
                try:
                    conn.close()
                except OSError:
                    pass

    def close(self):
        try:
            self._sock.close()
        except OSError:
            pass


def test_client_retry_off_by_default_fast_fails():
    backend = _ScriptedBackend([["overloaded", "ok"]])
    try:
        with PolicyClient("127.0.0.1", backend.port) as c:
            with pytest.raises(Overloaded):
                c.act(OBS)  # historical semantics: the shed is surfaced
            assert c.act(OBS).shape == (2,)  # next call is not poisoned
    finally:
        backend.close()


def test_client_bounded_retry_rides_through_shed():
    backend = _ScriptedBackend([["overloaded", "overloaded", "ok"]])
    try:
        with PolicyClient(
            "127.0.0.1", backend.port, retries=2, retry_seed=0
        ) as c:
            assert c.act(OBS).shape == (2,)
    finally:
        backend.close()


def test_client_retry_budget_is_bounded():
    backend = _ScriptedBackend([["overloaded"] * 8])
    try:
        with PolicyClient(
            "127.0.0.1", backend.port, retries=1, retry_seed=0
        ) as c:
            with pytest.raises(Overloaded):
                c.act(OBS)  # 1 retry = 2 attempts, both shed → surfaced
    finally:
        backend.close()


def test_client_retry_redials_a_dead_link():
    """ConnectionClosed mid-request: the retry path tears down the dead
    link and redials — the SECOND connection serves the request."""
    backend = _ScriptedBackend(["reset", ["ok"]])
    try:
        c = PolicyClient("127.0.0.1", backend.port, retries=3, retry_seed=0)
        try:
            assert c.act(OBS, timeout=10).shape == (2,)
        finally:
            c.close()
    finally:
        backend.close()


def test_client_retry_zero_keeps_connectionclosed_fatal():
    backend = _ScriptedBackend(["reset"])
    try:
        c = PolicyClient("127.0.0.1", backend.port)
        try:
            with pytest.raises((ConnectionClosed, OSError)):
                c.act(OBS, timeout=10)
        finally:
            c.close()
    finally:
        backend.close()


def test_client_close_is_final_even_with_retries():
    """close() must stay final for a retry-enabled client: a later act()
    fails fast with ConnectionClosed instead of the retry path re-dialing
    a fresh socket + reader thread nobody will ever tear down."""
    backend = _ScriptedBackend([["ok"]])
    try:
        c = PolicyClient("127.0.0.1", backend.port, retries=2)
        np.testing.assert_allclose(
            c.act(np.zeros(4, np.float32), timeout=10), np.zeros(2)
        )
        reader = c._reader
        c.close()
        reader.join(timeout=10)
        with pytest.raises(ConnectionClosed):
            c.act(OBS, timeout=10)
        assert c._reader is reader  # no resurrected link
    finally:
        backend.close()


# ------------------------------------------------- healthz prober surface
def test_healthz_prober_fields_and_replica_id(tmp_path):
    """The satellite fields the router's prober needs: bundle_mtime (the
    serving version vector), inflight, uptime_s, compile_count, pid — plus
    --replica-id stamped into healthz AND the metrics row."""
    import os

    d = str(tmp_path / "b")
    export_bundle(d, CFG, PARAMS)
    srv = _server(load_bundle(d), replica_id=3, watch_bundle=True,
                  poll_interval_s=3600.0)
    try:
        with PolicyClient("127.0.0.1", srv.port) as c:
            c.act(OBS)
        h = protocol.probe_healthz("127.0.0.1", srv.port)
        assert h["status"] == "ok"
        assert h["bundle_mtime"] == os.stat(
            os.path.join(d, "bundle.json")
        ).st_mtime
        assert h["inflight"] == 0  # gauge returns to rest after completion
        assert h["uptime_s"] > 0
        assert h["compile_count"] == 2
        assert h["replica_id"] == 3
        assert h["pid"] == os.getpid()  # in-process server
        assert srv._metrics_row()["replica_id"] == 3.0
    finally:
        srv.drain()


def test_bundle_mtime_attests_only_successful_reloads(tmp_path):
    """Satellite regression: a FAILED bundle reload must not advance the
    healthz version vector (the canary controller would promote a rollout
    nobody loaded), and the degraded status must clear on the next
    successful reload — not stick."""
    import os

    d = str(tmp_path / "b")
    export_bundle(d, CFG, PARAMS)
    srv = _server(load_bundle(d), watch_bundle=True, poll_interval_s=3600.0)
    try:
        m0 = srv.healthz()["bundle_mtime"]
        # corrupt re-export: truncated params + advanced json mtime
        pfile = os.path.join(d, "actor_params.npz")
        with open(pfile, "rb+") as f:
            f.truncate(os.path.getsize(pfile) // 2)
        os.utime(
            os.path.join(d, "bundle.json"), (time.time() + 2, time.time() + 2)
        )
        assert srv.check_reload() is False
        h = srv.healthz()
        assert h["status"] == "degraded"
        assert h["bundle_mtime"] == m0  # version vector did NOT move
        # a subsequent good export clears degraded and attests the new one
        params_new = jax.tree_util.tree_map(lambda x: x + 0.25, PARAMS)
        export_bundle(d, CFG, params_new)
        os.utime(
            os.path.join(d, "bundle.json"), (time.time() + 4, time.time() + 4)
        )
        assert srv.check_reload() is True
        h = srv.healthz()
        assert h["status"] == "ok"
        assert h["bundle_mtime"] == os.stat(
            os.path.join(d, "bundle.json")
        ).st_mtime
        assert h["bundle_mtime"] != m0
        with PolicyClient("127.0.0.1", srv.port) as c:
            np.testing.assert_allclose(
                c.act(OBS), _ref(params_new), rtol=1e-5, atol=1e-6
            )
    finally:
        srv.drain()


# ------------------------------------------------ idle connections are free
@pytest.fixture
def armed_ledger():
    """``--debug-guards``' conservation ledger: an imbalance raises out of
    the drain."""
    flowledger.enable()
    yield
    flowledger.reset()


@pytest.mark.parametrize("conns", [50, 300])
@pytest.mark.parametrize("front", ["server", "router"])
def test_idle_connections_cost_no_threads(front, conns, armed_ledger):
    """``netio.FrameLoop``'s reason to exist: a front end holds its client
    connections on ONE event-loop thread, so the thread count does not grow
    with the connection count (a thread-per-connection front end grows by
    ``conns`` here). Beside the idle population an interactive client keeps
    getting right answers, and the front end's flow identity is exact after
    the drain. No clock is asserted."""
    server = _server()
    router = _router([server]) if front == "router" else None
    target = router if router is not None else server
    idle = []
    try:
        with PolicyClient("127.0.0.1", target.port) as c:
            ref = _ref(PARAMS)
            # every constant-count thread (replica link reader, prober,
            # batcher) exists before the empty front end is counted
            for _ in range(8):
                c.act(OBS)
            held_empty = target.healthz()["netio"]["conns_open"]
            threads_empty = threading.active_count()
            while len(idle) < conns:
                # backlog-sized bursts: the accept loop is bounded a tick
                idle += [
                    socket.create_connection(
                        ("127.0.0.1", target.port), timeout=15
                    )
                    for _ in range(min(64, conns - len(idle)))
                ]
                _wait(
                    lambda: target.healthz()["netio"]["conns_open"]
                    >= held_empty + len(idle),
                    msg=f"{len(idle)} idle connections accepted",
                )
            growth = threading.active_count() - threads_empty
            assert growth <= 4, (
                f"{growth} threads for {conns} idle connections: the loop "
                "must hold them on O(1) threads"
            )
            for _ in range(20):
                np.testing.assert_allclose(
                    c.act(OBS), ref, rtol=1e-5, atol=1e-6
                )
            held = c.healthz()["netio"]["conns_open"]
        assert held >= held_empty + conns
    finally:
        for s in idle:
            s.close()
        if router is not None:
            router.drain()
        server.drain()
    # after the drain (a replica books a reply once it is written): every
    # request answered ok, none shed or failed, and the identity exact
    snap = target.stats.snapshot()
    assert snap["requests_total"] == snap["replies_ok"] == 28
    family = "router" if router is not None else "serve-stats"
    assert flowledger.check(family, snap) is True
