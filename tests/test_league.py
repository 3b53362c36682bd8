"""League controller (ISSUE 15): PBT lifecycle, crash consistency, the
variant capability in the fleet HELLO, and the manifest-verified fork.

Fast by design: the controller is JAX-free and the learners here are
``scripts/league_stub_learner.py`` — a deterministic stand-in that
speaks exactly train.py's league surface (manifest-attested checkpoints,
exit-75 drain, trainer_meta attestation, genome-determined fitness) in
milliseconds. The REAL-learner league runs in ``scripts/league_smoke.sh``
(tier-1) and chaos_soak leg 9.
"""

import json
import os
import random
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STUB = os.path.join(REPO, "scripts", "league_stub_learner.py")

from d4pg_tpu.league.controller import (  # noqa: E402
    LeagueConfig,
    LeagueController,
    genome_argv,
    perturb_genome,
)
from d4pg_tpu.runtime import manifest as ckpt_manifest  # noqa: E402
from d4pg_tpu.utils import procs  # noqa: E402


# ----------------------------------------------------------------- helpers
def _stub_config(tmp_path, genomes, **kw):
    base = dict(
        league_dir=str(tmp_path / "league"),
        learner_argv=[sys.executable, STUB, "--checkpoint-interval", "4",
                      "--eval-interval", "2", "--tick-seconds", "0.03"],
        genomes=genomes,
        seed=7,
        generations=1,
        poll_interval_s=0.1,
        gen_timeout_s=60.0,
        drain_timeout_s=20.0,
        attest_timeout_s=20.0,
        observe_timeout_s=20.0,
    )
    base.update(kw)
    return LeagueConfig(**base)


GOOD = {"lr_actor": 1e-4, "max_episode_steps": 50}
MID = {"lr_actor": 1e-4, "max_episode_steps": 200}
BAD = {"lr_actor": 1e-3, "max_episode_steps": 250}


def _league_pids(league_dir):
    """Every live process whose cmdline names the league dir — the
    zero-orphans scan."""
    out = []
    for name in os.listdir("/proc"):
        if name.isdigit():
            cmd = procs.pid_cmdline(int(name))
            if str(league_dir) in cmd and "league_stub" in cmd:
                out.append(int(name))
    return out


# --------------------------------------------------------------- jax-free
def test_league_controller_is_jax_free():
    """The supervision contract: a controller restart after kill -9 must
    cost milliseconds, so importing the whole league package (plus the
    manifest/procs machinery it forks and kills through) must never load
    the JAX runtime — manifest-enforced (HOST_ONLY_MODULES) and proven
    here in a clean subprocess."""
    code = (
        "import sys\n"
        "import d4pg_tpu.league.controller, d4pg_tpu.league.__main__\n"
        "import d4pg_tpu.runtime.manifest, d4pg_tpu.utils.procs\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n"
        "assert not bad, bad\n"
        "print('JAXFREE_OK')\n"
    )
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120, cwd=REPO,
    )
    assert p.returncode == 0 and "JAXFREE_OK" in p.stdout, (
        p.stdout + p.stderr
    )


# ------------------------------------------------------------------ genome
def test_perturb_genome_seeded_and_bounded():
    g = {"lr_actor": 1e-4, "tau": 0.001, "max_episode_steps": 200}
    a = perturb_genome(g, random.Random(3))
    b = perturb_genome(g, random.Random(3))
    assert a == b  # seeded: the league's decision stream replays
    assert a["max_episode_steps"] == 200  # structural genes untouched
    for k in ("lr_actor", "tau"):
        assert a[k] in (g[k] * 0.8, g[k] * 1.25)


def test_genome_argv_refuses_unknown_keys():
    with pytest.raises(ValueError, match="unknown genome key"):
        genome_argv({"learning_rate": 1e-4})
    argv = genome_argv({"lr_actor": 1e-4, "batch_size": 16})
    assert "--lr-actor" in argv and "--bsize" in argv


# ------------------------------------------------- league metrics columns
def test_metrics_logger_static_league_columns(tmp_path):
    """MetricsLogger(static=...) stamps the league identity columns onto
    EVERY row, numeric (the schema_check contract: integer-valued pair,
    both or neither)."""
    from d4pg_tpu.runtime.metrics import MetricsLogger
    from tools.d4pglint.schema_check import check_metrics_jsonl

    log = MetricsLogger(
        str(tmp_path), use_tensorboard=False,
        static={"variant_id": 3, "league_generation": 1},
    )
    log.log(1, {"critic_loss": 0.5})
    log.log(2, {"critic_loss": 0.4, "eval_return_mean": -100.0})
    log.close()
    path = os.path.join(str(tmp_path), "metrics.jsonl")
    rows = [json.loads(l) for l in open(path)]
    assert all(
        r["variant_id"] == 3.0 and r["league_generation"] == 1.0
        for r in rows
    )
    assert check_metrics_jsonl(path) == []
    # the pair rule: a row carrying one column without the other is a
    # schema violation (hand-rolled writers can't half-adopt the contract)
    with open(path, "a") as f:
        f.write(json.dumps({"step": 3, "t": 1.0, "variant_id": 3.0}) + "\n")
    errs = check_metrics_jsonl(path)
    assert errs and "pair" in errs[0]


# ------------------------------------------------- fleet HELLO variant cap
def test_negotiate_fleet_variant_exact_match():
    from d4pg_tpu.replay.source import LEGACY_ACTOR_CAPS, negotiate_fleet

    learner = {"obs_mode": "f32", "her": False, "obs_norm": False,
               "variant": 4}
    # pre-variant actor (and pre-ISSUE-13 legacy) declare variant 0
    chosen, gaps = negotiate_fleet(learner, LEGACY_ACTOR_CAPS)
    assert chosen is None
    assert [g.code for g in gaps] == ["variant_mismatch"]
    chosen, gaps = negotiate_fleet(
        learner,
        {"obs_modes": ["f32"], "her": False, "obs_norm": False,
         "variant": 4},
    )
    assert gaps == () and chosen["variant"] == 4
    # default learner x default actor: byte-compat cell stays open
    learner["variant"] = 0
    chosen, gaps = negotiate_fleet(learner, LEGACY_ACTOR_CAPS)
    assert gaps == () and chosen["variant"] == 0


def test_ingest_refuses_wrong_variant_with_structured_reason():
    import socket

    import numpy as np  # noqa: F401  (buffer stub needs nothing)

    from d4pg_tpu.fleet import wire
    from d4pg_tpu.fleet.ingest import IngestServer
    from d4pg_tpu.serve import protocol

    class _Buf:
        def add_batch(self, t):
            pass

    srv = IngestServer(
        _Buf(), obs_dim=3, action_dim=1, n_step=3, gamma=0.99,
        caps={"obs_mode": "f32", "her": False, "obs_norm": False,
              "variant": 9},
    ).start()
    try:
        def hello(caps):
            s = socket.create_connection(("127.0.0.1", srv.port), timeout=5)
            protocol.write_frame(
                s, protocol.HELLO, 1,
                wire.encode_hello(
                    actor_id="a", env="e", obs_dim=3, action_dim=1,
                    n_step=3, gamma=0.99, generation=0, caps=caps,
                ),
            )
            frame = protocol.read_frame(s.makefile("rb"))
            return s, frame

        # assigned elsewhere: refused with the machine-readable code
        s, (t, _r, payload) = hello(
            {"obs_modes": ["f32"], "her": False, "obs_norm": False,
             "variant": 2}
        )
        assert t == protocol.ERROR
        doc = wire.decode_refusal(payload)
        assert [g["code"] for g in doc["gaps"]] == ["variant_mismatch"]
        s.close()
        # correctly assigned: accepted, variant echoed for the actor's
        # wrong-port check
        s, (t, _r, payload) = hello(
            {"obs_modes": ["f32"], "her": False, "obs_norm": False,
             "variant": 9}
        )
        assert t == protocol.HELLO_OK
        assert wire.decode_hello_ok(payload)["caps"]["variant"] == 9
        s.close()
    finally:
        srv.close()


# --------------------------------------------------------- checkpoint fork
def _fake_run(run_dir, steps, content=b"x" * 512):
    ckpt = os.path.join(run_dir, "checkpoints")
    os.makedirs(ckpt, exist_ok=True)
    meta = os.path.join(ckpt, "trainer_meta.json")
    for step in steps:
        sd = os.path.join(ckpt, str(step))
        os.makedirs(sd, exist_ok=True)
        with open(os.path.join(sd, "params.bin"), "wb") as f:
            f.write(content + str(step).encode())
        with open(meta, "w") as f:
            json.dump({"env_steps": step}, f)
        ckpt_manifest.write_manifest_file(
            ckpt_manifest.manifest_path(ckpt, step),
            ckpt_manifest.build_manifest(step, sd, [meta]),
        )
    return ckpt


def test_fork_copies_newest_intact_steps_and_side_files(tmp_path):
    src = _fake_run(str(tmp_path / "src"), [4, 8, 12])
    dst = str(tmp_path / "dst" / "checkpoints")
    copied = ckpt_manifest.fork_checkpoint(src, dst, depth=2)
    assert copied == [8, 12]
    assert ckpt_manifest.intact_steps(dst) == [8, 12]
    assert os.path.exists(os.path.join(dst, "trainer_meta.json"))
    # fork refuses to clobber an existing run's checkpoints
    with pytest.raises(FileExistsError):
        ckpt_manifest.fork_checkpoint(src, dst, depth=2)


def test_fork_skips_torn_source_step(tmp_path):
    """A truncated source step is skipped at fork exactly as restore
    would skip it — the clone only ever receives attested bytes."""
    src = _fake_run(str(tmp_path / "src"), [4, 8, 12])
    victim = os.path.join(src, "12", "params.bin")
    with open(victim, "rb+") as f:
        f.truncate(100)
    dst = str(tmp_path / "dst" / "checkpoints")
    assert ckpt_manifest.fork_checkpoint(src, dst, depth=2) == [4, 8]


def test_fork_retries_when_live_source_gc_wins_the_race(tmp_path, monkeypatch):
    """The source learner is ALIVE while it is forked, so Orbax GC can
    delete a just-verified step mid-copy — the fork must re-verify and
    retry (bounded), never crash the controller (review finding)."""
    src = _fake_run(str(tmp_path / "src"), [4, 8, 12])
    dst = str(tmp_path / "dst" / "checkpoints")
    real = ckpt_manifest._copy_fork
    calls = []

    def racy(src_dir, dst_dir, good):
        if not calls:
            calls.append(1)
            raise FileNotFoundError("step 12 directory is gone (GC)")
        return real(src_dir, dst_dir, good)

    monkeypatch.setattr(ckpt_manifest, "_copy_fork", racy)
    assert ckpt_manifest.fork_checkpoint(src, dst, depth=2) == [8, 12]
    assert ckpt_manifest.intact_steps(dst) == [8, 12]


def test_clone_corrupt_falls_back_to_older_forked_step(tmp_path):
    """The clone_corrupt chaos shape: the newest FORKED step torn after
    the copy — verify-on-restore (stub learner == restore_verified
    semantics) must fall back to the older copied step."""
    src = _fake_run(str(tmp_path / "src"), [4, 8])
    dst = str(tmp_path / "dst" / "checkpoints")
    assert ckpt_manifest.fork_checkpoint(src, dst, depth=2) == [4, 8]
    from d4pg_tpu.chaos import truncate_checkpoint_step

    truncate_checkpoint_step(os.path.join(dst, "8"))
    assert ckpt_manifest.intact_steps(dst) == [4]


# --------------------------------------------------------- controller runs
def test_league_promotes_planted_better_variant(tmp_path):
    """The acceptance shape, in-process: 3 variants with fitness
    separation baked into the genomes — the worst is culled, the clone
    forks from the planted winner, attests, and promotes."""
    ctl = LeagueController(_stub_config(tmp_path, [GOOD, MID, BAD]))
    rc = ctl.run()
    assert rc == 0
    s = ctl.state
    assert s["generation"] == 1 and s["promotions"] == 1
    assert s["rollbacks"] == 0
    [edge] = s["lineage"]
    assert edge["parent"] == 1 and edge["reason"] == "clone"  # GOOD is uid 1
    # the worst (BAD, uid 3) was culled and its slot re-seeded
    assert ctl._variant(3)["status"] == "retired"
    assert ctl._variant(3)["killed"] == 1
    assert ctl._members()[2] == edge["child"]
    # clone's genome is a perturbation of the winner's
    child = ctl._variant(edge["child"])
    assert child["genome"]["lr_actor"] in (1e-4 * 0.8, 1e-4 * 1.25)
    # summary passes its own schema gate + zero orphans
    from tools.d4pglint.schema_check import check_league_soak

    assert check_league_soak(
        os.path.join(ctl.dir, "league_summary.json")
    ) == []
    assert _league_pids(ctl.dir) == []


def test_rollback_on_fitness_below_bar_reforks_unperturbed(tmp_path):
    """The canary-rollback shape: genomes planted so ANY perturbation of
    the winner scores below the culled victim's bar — the clone must
    roll back and the slot re-seed with the parent's exact recipe."""
    g1 = {"lr_actor": 1e-4, "max_episode_steps": 50}
    g2 = {"lr_actor": 1e-4, "max_episode_steps": 51}
    g3 = {"lr_actor": 1e-4, "max_episode_steps": 52}
    ctl = LeagueController(_stub_config(tmp_path, [g1, g2, g3]))
    rc = ctl.run()
    assert rc == 0
    s = ctl.state
    assert s["rollbacks"] == 1 and s["promotions"] == 1
    reasons = [e["reason"] for e in s["lineage"]]
    assert reasons == ["clone", "rollback_refork"]
    refork = s["lineage"][-1]
    # the re-fork carries the parent's UNPERTURBED genome
    assert ctl._variant(refork["child"])["genome"] == g1
    from tools.d4pglint.schema_check import check_league_soak

    assert check_league_soak(
        os.path.join(ctl.dir, "league_summary.json")
    ) == []


def test_crash_looping_variant_quarantined(tmp_path):
    """The actor-pool discipline at league scale: a variant whose genome
    'diverges' (stub crash-loop) burns its seeded Backoff budget and is
    quarantined; the league completes on the survivors."""
    diverged = {"lr_actor": 1.0, "max_episode_steps": 50}
    ctl = LeagueController(_stub_config(
        tmp_path, [GOOD, MID, diverged], restart_max_attempts=2,
    ))
    rc = ctl.run()
    assert rc == 0
    v3 = ctl._variant(3)
    assert v3["status"] == "quarantined"
    assert v3["restarts"] == 2  # the full bounded budget, then no more
    assert v3["exited_err"] == 3  # initial + 2 restarts, all crashed
    assert ctl.state["generation"] == 1  # survivors carried the league
    from tools.d4pglint.schema_check import check_league_soak

    assert check_league_soak(
        os.path.join(ctl.dir, "league_summary.json")
    ) == []


def test_all_terminal_league_stops_loudly(tmp_path):
    """Every member quarantined ⇒ the league must STOP with rc 1 (the
    all-quarantined actor-pool rule), never spin silently forever."""
    diverged = {"lr_actor": 1.0, "max_episode_steps": 50}
    ctl = LeagueController(_stub_config(
        tmp_path, [diverged, dict(diverged), dict(diverged)],
        restart_max_attempts=1,
    ))
    rc = ctl.run()
    assert rc == 1
    # the stop fires as soon as fewer than two members can ever rank
    # again — at least two are quarantined by then, none keeps running
    statuses = [
        ctl._variant(u)["status"] for u in ctl._members().values()
    ]
    assert statuses.count("quarantined") >= 2
    assert _league_pids(ctl.dir) == []


def test_lone_survivor_league_stops_loudly(tmp_path):
    """One live member left (the rest quarantined) ⇒ exploit/explore can
    never rank again — the league must stop loudly, not poll forever
    (review finding: the all-terminal check alone missed this)."""
    diverged = {"lr_actor": 1.0, "max_episode_steps": 50}
    ctl = LeagueController(_stub_config(
        tmp_path, [GOOD, dict(diverged), dict(diverged)],
        restart_max_attempts=1,
    ))
    rc = ctl.run()
    assert rc == 1
    statuses = sorted(
        ctl._variant(u)["status"] for u in ctl._members().values()
    )
    assert statuses.count("quarantined") == 2
    assert _league_pids(ctl.dir) == []


def test_crash_looping_refork_gives_up_slot_bounded(tmp_path):
    """A rollback re-fork that itself crash-loops must GIVE THE SLOT UP
    (one bounded outcome), never re-fork forever (review finding: the
    quarantine branch used to re-enter _rollback for reforks too)."""
    ctl = LeagueController(_stub_config(tmp_path, [GOOD, MID, BAD]))
    pending = {"gen": 0, "actions": []}
    action = {
        "phase": "observing", "kill_uid": 3, "src_uid": 1,
        "child_uid": 4, "genome": dict(GOOD),
        "reason": "rollback_refork", "bar_fitness": None,
        "fork_steps": [],
    }
    pending["actions"].append(action)
    ctl.state["variants"]["4"] = ctl._new_variant(
        4, 2, dict(GOOD), parent=1, born_gen=0
    )
    ctl.state["variants"]["4"]["status"] = "quarantined"
    ctl.state["pending"] = pending
    before = ctl.state["next_uid"]
    ctl._observe(pending, action)
    assert action["phase"] == "done"                  # resolved, not re-forked
    assert ctl.state["next_uid"] == before            # no new clone minted
    assert ctl.state["rollbacks"] == 1
    ctl.shutdown()


def test_journal_refuses_mismatched_resume_args(tmp_path):
    ctl = LeagueController(_stub_config(tmp_path, [GOOD, MID, BAD]))
    ctl.shutdown()
    with pytest.raises(RuntimeError, match="journal disagrees"):
        LeagueController(_stub_config(tmp_path, [GOOD, MID, BAD], seed=8))
    with pytest.raises(RuntimeError, match="journal disagrees"):
        LeagueController(_stub_config(tmp_path, [GOOD, MID]))


# ------------------------------------------- controller crash consistency
def _controller_argv(league_dir, *, chaos=None, generations=1):
    argv = [
        sys.executable, "-m", "d4pg_tpu.league",
        "--dir", str(league_dir), "--seed", "7",
        "--generations", str(generations),
        "--poll-interval", "0.1", "--gen-timeout", "60",
        "--drain-timeout", "20", "--attest-timeout", "20",
        "--observe-timeout", "20",
        "--genome", "lr_actor=1e-4,max_episode_steps=50",
        "--genome", "lr_actor=1e-4,max_episode_steps=200",
        "--genome", "lr_actor=1e-3,max_episode_steps=250",
    ]
    if chaos:
        argv += ["--chaos", chaos]
    argv += ["--", sys.executable, STUB, "--checkpoint-interval", "4",
             "--eval-interval", "2", "--tick-seconds", "0.03"]
    return argv


def test_controller_kill9_resumes_same_generation(tmp_path):
    """THE crash-consistency contract (ISSUE 15 satellite): kill -9 the
    controller at a seeded-random instant mid-generation; the restarted
    controller must resume the SAME generation (never double-book),
    re-adopt or restart the learners, finish the league, and leave zero
    orphaned learner processes with the lineage DAG intact."""
    league = tmp_path / "league"
    proc = subprocess.Popen(
        _controller_argv(league), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    # wait until a generation is IN FLIGHT (journal holds pending work)
    journal = league / "league.json"
    deadline = time.monotonic() + 60
    pending_seen = False
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            pytest.fail(
                "controller finished before the kill window: "
                + proc.stdout.read()[-2000:]
            )
        try:
            doc = json.loads(journal.read_text())
            if doc.get("pending"):
                pending_seen = True
                break
        except (OSError, ValueError):
            pass
        time.sleep(0.02)
    assert pending_seen, "no pending generation within the deadline"
    gen_before = doc["generation"]
    # the seeded-random instant: anywhere inside the generation's apply
    time.sleep(random.Random(71).uniform(0.0, 0.4))
    proc.kill()  # SIGKILL: no cleanup, no journal flush
    proc.wait()
    # learners were spawned as their own sessions: some may still be
    # alive (that is the point — the restart must re-adopt them)
    rerun = subprocess.run(
        _controller_argv(league), cwd=REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert rerun.returncode == 0, rerun.stdout[-3000:]
    assert "journal_resumed" in rerun.stdout
    final = json.loads(journal.read_text())
    # the SAME generation resumed and committed exactly once
    assert final["generation"] == gen_before + 1
    assert final["pending"] is None
    assert final["promotions"] + final["rollbacks"] >= 1
    # lineage DAG intact + accounting identity exact (schema-gated)
    from tools.d4pglint.schema_check import check_league_soak

    assert check_league_soak(str(league / "league_summary.json")) == []
    # zero orphaned learner processes
    assert _league_pids(league) == []


def test_controller_kill_chaos_site_roundtrip(tmp_path):
    """The chaos-site version of the same story: controller_kill@N
    SIGKILLs the controller from the inside; variant_kill@N SIGKILLs a
    learner group (restarted under Backoff); clone_corrupt@N tears the
    fork (the clone falls back to the older copied step)."""
    league = tmp_path / "league"
    first = subprocess.run(
        _controller_argv(
            league, chaos="seed=5;variant_kill@2;clone_corrupt@1;"
                          "controller_kill@3",
        ),
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert first.returncode == -signal.SIGKILL, first.stdout[-2000:]
    assert "controller_kill: SIGKILL self" in first.stdout
    assert "variant_kill: SIGKILL" in first.stdout
    rerun = subprocess.run(
        _controller_argv(league), cwd=REPO, capture_output=True,
        text=True, timeout=120,
    )
    assert rerun.returncode == 0, rerun.stdout[-3000:]
    final = json.loads((league / "league.json").read_text())
    assert final["generation"] == 1 and final["pending"] is None
    assert _league_pids(league) == []


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
