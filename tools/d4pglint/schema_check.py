"""Schema checks for committed artifacts and metrics logs.

Two machine-readable surfaces downstream tooling (plots, the soak and
static-analysis freshness tests) parses:

- ``benchmarks/*.json`` — one JSON object per artifact (the composition
  matrix, the lock-order graph, the flow identities, the league and
  flywheel soak records; none is a timing). Every object but the two
  static-analysis graphs carries a ``backend`` key, and each has its own
  shape check below. A truncated or hand-mangled artifact should fail
  lint, not a plot script three PRs later.
- ``metrics.jsonl`` — append-only rows from
  :class:`d4pg_tpu.runtime.MetricsLogger`: every line a JSON object with
  an int ``step``, a numeric ``t``, and numeric values throughout
  (schema: docs/data_plane.md).

CLI: ``python -m tools.d4pglint.schema_check [root]`` checks every
``benchmarks/*.json`` plus every ``runs/**/metrics.jsonl``; exits 1 on
any violation.
"""

from __future__ import annotations

import glob
import json
import os
import sys


def check_benchmark_json(path: str) -> list[str]:
    """Problems with one benchmarks/*.json artifact ([] = clean)."""
    errs = []
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable/invalid JSON ({e})"]
    if not isinstance(doc, dict):
        errs.append(f"{path}: top level must be an object")
    elif not doc:
        errs.append(f"{path}: empty object")
    elif "backend" not in doc:
        errs.append(
            f"{path}: object missing 'backend' (which "
            "hardware produced this record?)"
        )
    return errs


def check_composition_matrix(path: str) -> list[str]:
    """Shape + invariants for ``benchmarks/composition_matrix.json`` —
    the ISSUE-13 acceptance artifact:

    - every scenario × placement cell is present, with verdict
      ``pass`` / ``negotiated`` / ``gap``;
    - every ``gap`` cell carries machine-readable reasons (code +
      message) and every ``negotiated`` cell its declared actions —
      zero undeclared refusals;
    - the cells match a FRESH evaluation of the rule table
      (``d4pg_tpu.replay.source.composition_matrix()``, JAX-free):
      drift means someone changed a capability rule without
      regenerating — ``python benchmarks/composition_matrix.py``.
    """
    from d4pg_tpu.replay import source

    errs = []
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable/invalid JSON ({e})"]
    for key in ("backend", "schema", "cells", "counts", "wire_encodings"):
        if key not in doc:
            errs.append(f"{path}: missing top-level key {key!r}")
    if doc.get("schema") != "composition-matrix/v1":
        errs.append(
            f"{path}: unknown schema {doc.get('schema')!r} "
            "(expected 'composition-matrix/v1')"
        )
    cells = doc.get("cells")
    if not isinstance(cells, list) or not cells:
        return errs + [f"{path}: 'cells' must be a non-empty list"]
    for i, c in enumerate(cells):
        v = c.get("verdict")
        if v not in ("pass", "negotiated", "gap"):
            errs.append(f"{path}: cells[{i}] verdict {v!r} unknown")
            continue
        if v == "gap":
            gaps = c.get("gaps")
            if not gaps or not all(
                isinstance(g, dict) and g.get("code") and g.get("message")
                for g in gaps
            ):
                errs.append(
                    f"{path}: cells[{i}] "
                    f"({c.get('scenario')}×{c.get('placement')}) is a gap "
                    "without machine-readable code+message reasons — "
                    "undeclared refusals are not committable"
                )
        if v == "negotiated" and not c.get("actions"):
            errs.append(
                f"{path}: cells[{i}] negotiated without declared actions"
            )
    fresh = source.composition_matrix()
    if cells != fresh:
        fresh_by = {(c["scenario"], c["placement"]): c for c in fresh}
        old_by = {(c["scenario"], c["placement"]): c for c in cells}
        changed = sorted(
            f"{s}×{p}"
            for key in set(fresh_by) | set(old_by)
            for s, p in [key]
            if fresh_by.get(key) != old_by.get(key)
        )
        errs.append(
            f"{path}: stale vs the current capability rule table "
            f"(changed cells: {', '.join(changed) or 'ordering'}) — "
            "regenerate with `python benchmarks/composition_matrix.py`"
        )
    return errs


def check_lock_order_graph(path: str, root: str | None = None) -> list[str]:
    """Shape + invariants for ``benchmarks/lock_order_graph.json``:

    - the committed artifact parses and carries the v1 schema fields;
    - every edge endpoint is a declared node;
    - the graph is ACYCLIC (Kahn) — the committed artifact is the repo's
      standing claim that no lock-order deadlock exists, so a cyclic one
      must never be committable;
    - with ``root`` given, the artifact matches a fresh analysis of the
      lint manifest (drift = someone changed lock nesting without
      regenerating: ``python -m tools.d4pglint.wholeprog.lockgraph
      --write``).
    """
    from tools.d4pglint.wholeprog.lockgraph import (
        GRAPH_SCHEMA,
        build_lock_graph,
        is_acyclic,
    )

    errs = []
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable/invalid JSON ({e})"]
    if not isinstance(doc, dict) or doc.get("schema") != GRAPH_SCHEMA:
        return [f"{path}: missing/unknown schema (expected {GRAPH_SCHEMA!r})"]
    nodes = doc.get("nodes")
    edges = doc.get("edges")
    if not (isinstance(nodes, list) and all(isinstance(n, str) for n in nodes)):
        return [f"{path}: 'nodes' must be a list of lock ids"]
    if not isinstance(edges, list):
        return [f"{path}: 'edges' must be a list"]
    pairs = []
    for i, e in enumerate(edges):
        if not (isinstance(e, dict) and "from" in e and "to" in e):
            errs.append(f"{path}: edges[{i}] missing from/to")
            continue
        for end in (e["from"], e["to"]):
            if end not in nodes:
                errs.append(
                    f"{path}: edges[{i}] endpoint {end!r} not in 'nodes'"
                )
        if not (isinstance(e.get("sites"), list) and e["sites"]):
            errs.append(f"{path}: edges[{i}] needs non-empty 'sites'")
        pairs.append((e["from"], e["to"]))
    if not is_acyclic(nodes, pairs):
        errs.append(
            f"{path}: lock-order graph is CYCLIC — a committed artifact "
            "must never attest a deadlock; fix the inversion, then "
            "regenerate"
        )
    if root is not None:
        from tools.d4pglint.core import parse_default_files

        fresh = build_lock_graph(parse_default_files(root))
        fresh_pairs = {(e["from"], e["to"]) for e in fresh["edges"]}
        if set(nodes) != set(fresh["nodes"]) or set(pairs) != fresh_pairs:
            gone_n = sorted(set(nodes) - set(fresh["nodes"]))
            new_n = sorted(set(fresh["nodes"]) - set(nodes))
            gone_e = sorted(set(pairs) - fresh_pairs)
            new_e = sorted(fresh_pairs - set(pairs))
            detail = "; ".join(
                f"{k}: {v}" for k, v in (
                    ("stale nodes", gone_n), ("new nodes", new_n),
                    ("stale edges", gone_e), ("new edges", new_e),
                ) if v
            )
            errs.append(
                f"{path}: stale vs the current code ({detail}) — "
                "regenerate with `python -m "
                "tools.d4pglint.wholeprog.lockgraph --write`"
            )
    return errs


def check_flow_identities(path: str, root: str | None = None) -> list[str]:
    """Shape + invariants for ``benchmarks/flow_identities.json``:

    - the committed artifact parses and carries the v1 schema fields;
    - every family names its identity, counters, and (for class-owned
      families) at least one increment site per non-derived counter;
    - every family has at least one ASSERTION site — an identity nobody
      checks is a claim, not a contract;
    - with ``root`` given, the artifact byte-matches a fresh analysis
      (drift = counters/dispositions changed without regenerating:
      ``python -m tools.d4pglint.wholeprog.flowcheck --write``).
    """
    from tools.d4pglint.wholeprog.flowcheck import GRAPH_SCHEMA

    errs = []
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable/invalid JSON ({e})"]
    if not isinstance(doc, dict) or doc.get("schema") != GRAPH_SCHEMA:
        return [f"{path}: missing/unknown schema (expected {GRAPH_SCHEMA!r})"]
    fams = doc.get("families")
    if not (isinstance(fams, dict) and fams):
        return [f"{path}: 'families' must be a non-empty object"]
    for name, fam in sorted(fams.items()):
        if not isinstance(fam, dict):
            errs.append(f"{path}: families[{name!r}] must be an object")
            continue
        if "==" not in str(fam.get("identity", "")):
            errs.append(f"{path}: families[{name!r}] identity needs `==`")
        if not fam.get("assertion_sites"):
            errs.append(
                f"{path}: families[{name!r}] has no assertion site — an "
                "identity no test/soak/healthz checks is uncommittable"
            )
        derived = set(fam.get("derived", ()))
        sites = fam.get("increment_sites", {})
        if fam.get("class"):
            for counter in fam.get("counters", ()):
                if counter not in derived and not sites.get(counter):
                    errs.append(
                        f"{path}: families[{name!r}] counter {counter!r} "
                        "has no increment site"
                    )
    if root is not None:
        from tools.d4pglint.core import parse_default_files
        from tools.d4pglint.wholeprog.flowcheck import build_flow_graph

        fresh = build_flow_graph(parse_default_files(root), root)
        if doc != fresh:
            stale = sorted(
                k for k in set(doc.get("families", {})) | set(fresh["families"])
                if doc.get("families", {}).get(k) != fresh["families"].get(k)
            )
            errs.append(
                f"{path}: stale vs the current code (families drifted: "
                f"{', '.join(stale) or 'top-level fields'}) — regenerate "
                "with `python -m tools.d4pglint.wholeprog.flowcheck "
                "--write`"
            )
    return errs


def check_league_soak(path: str) -> list[str]:
    """Shape + invariants for ``benchmarks/league_soak.json`` — the
    ISSUE-15 acceptance artifact (the league controller's end-of-run
    summary from a real soak run):

    - per-variant process ACCOUNTING IDENTITY, recomputed here, not
      trusted: every process the controller ever started or adopted for a
      variant is accounted as a graceful exit (0), a preemption drain
      (75), a crash, a controller kill, or still-live — a committed
      artifact can never attest a silently lost learner process;
    - the promotion LINEAGE is a well-formed DAG: every clone edge names
      existing variants, a child is born in the generation its edge
      records, and no variant is its own ancestor;
    - every fork has exactly one recorded outcome — a clone edge promotes
      or rolls back, a rollback-refork edge promotes or gives the slot up
      (``promotions + rollbacks == lineage edges``) — ``identity_ok`` is
      attested true, and ``orphans_swept`` is 0 (the zero-orphaned-
      learners contract).
    """
    errs = []
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable/invalid JSON ({e})"]
    for key in ("backend", "schema", "seed", "slots",
                "generations_completed", "promotions", "rollbacks",
                "variants", "lineage", "identity_ok", "orphans_swept"):
        if key not in doc:
            errs.append(f"{path}: missing top-level key {key!r}")
    if doc.get("schema") != "league-soak/v1":
        errs.append(
            f"{path}: unknown schema {doc.get('schema')!r} "
            "(expected 'league-soak/v1')"
        )
    variants = doc.get("variants")
    if not isinstance(variants, dict) or not variants:
        return errs + [f"{path}: 'variants' must be a non-empty object"]
    for uid, row in variants.items():
        for key in ("slot", "parent", "born_gen", "genome", "spawned",
                    "adopted", "exited_0", "exited_75", "exited_err",
                    "killed", "live", "restarts", "quarantined"):
            if key not in row:
                errs.append(f"{path}: variants[{uid}] missing {key!r}")
        started = row.get("spawned", 0) + row.get("adopted", 0)
        accounted = (
            row.get("exited_0", 0) + row.get("exited_75", 0)
            + row.get("exited_err", 0) + row.get("killed", 0)
            + row.get("live", 0)
        )
        if started != accounted:
            errs.append(
                f"{path}: variants[{uid}] process identity broken: "
                f"spawned+adopted ({started}) != exits+kills+live "
                f"({accounted}) — a learner process went unaccounted"
            )
    lineage = doc.get("lineage")
    if not isinstance(lineage, list):
        errs.append(f"{path}: 'lineage' must be a list")
    else:
        for i, e in enumerate(lineage):
            child, parent = str(e.get("child")), str(e.get("parent"))
            if child not in variants or parent not in variants:
                errs.append(
                    f"{path}: lineage[{i}] names unknown variant(s) "
                    f"{e.get('child')}->{e.get('parent')}"
                )
                continue
            if variants[child].get("born_gen") != e.get("gen"):
                errs.append(
                    f"{path}: lineage[{i}] child {child} born_gen "
                    f"{variants[child].get('born_gen')} != edge gen "
                    f"{e.get('gen')}"
                )
        # ancestry must terminate at a seed variant (parent null): a cycle
        # in the committed lineage means the DAG claim is false
        for uid in variants:
            seen, cur = set(), uid
            while variants.get(cur, {}).get("parent") is not None:
                if cur in seen:
                    errs.append(f"{path}: lineage cycle through {uid}")
                    break
                seen.add(cur)
                cur = str(variants[cur]["parent"])
        resolved = doc.get("promotions", 0) + doc.get("rollbacks", 0)
        if resolved != len(lineage):
            errs.append(
                f"{path}: promotions+rollbacks ({resolved}) != lineage "
                f"edges ({len(lineage)}) — every fork needs exactly one "
                "recorded outcome"
            )
    if doc.get("identity_ok") is not True:
        errs.append(
            f"{path}: identity_ok is {doc.get('identity_ok')!r} — the "
            "committed artifact must attest the accounting identity"
        )
    if doc.get("orphans_swept") != 0:
        errs.append(
            f"{path}: orphans_swept is {doc.get('orphans_swept')!r} — "
            "zero orphaned learner processes is the contract"
        )
    if doc.get("promotions", 0) < 1:
        errs.append(
            f"{path}: no promotion recorded — the soak exists to prove "
            "the planted better variant promotes"
        )
    return errs


def check_flywheel_soak(path: str) -> list[str]:
    """Shape + invariants for ``benchmarks/flywheel_soak.json`` — the
    ISSUE-18 acceptance artifact (the closed-loop chaos soak's summary,
    chaos_soak.sh leg 10):

    - the EVAL CLAIM recomputed, not trusted: the fixed-seed return
      after training on the bundle's own served traffic must be STRICTLY
      above the degraded starting point;
    - the GATE story complete: the stalled evaluation rolled back (never
      wedged), the planted bad bundle was BLOCKED by the off-policy gate
      (a refusing verdict with the full decision-table fields), the good
      bundle PASSED and promoted — and the router's gate counters add up
      (evaluations == pass + block + stalls);
    - both planes' ACCOUNTING IDENTITIES recomputed from the committed
      counters: the tap's window ledger (built == acked + stale + shed
      + dropped_chaos + dropped_link + dropped_full + pending) and the
      ingest's per-source split (from_mirror + from_actors == ingested,
      every window mirror-sourced);
    - the chaos sites demonstrably FIRED: ``mirror_drop`` losses appear
      in the tap's explicit dropped counter, ``gate_stall`` in the
      router's gate_stalls.
    """
    errs = []
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: unreadable/invalid JSON ({e})"]
    for key in ("backend", "schema", "env", "eval", "gate", "counters",
                "identity_ok"):
        if key not in doc:
            errs.append(f"{path}: missing top-level key {key!r}")
    if doc.get("schema") != "flywheel-soak/v1":
        errs.append(
            f"{path}: unknown schema {doc.get('schema')!r} "
            "(expected 'flywheel-soak/v1')"
        )
    ev = doc.get("eval")
    if not isinstance(ev, dict):
        errs.append(f"{path}: 'eval' must be an object")
    else:
        for key in ("before", "after", "episodes", "seed"):
            if key not in ev:
                errs.append(f"{path}: eval missing {key!r}")
        before, after = ev.get("before"), ev.get("after")
        if not (isinstance(before, (int, float))
                and isinstance(after, (int, float)) and after > before):
            errs.append(
                f"{path}: eval return must STRICTLY rise across the soak "
                f"(before={before!r}, after={after!r}) — the closed loop "
                "exists to improve the bundle on its own served traffic"
            )
    gate = doc.get("gate")
    if not isinstance(gate, dict):
        errs.append(f"{path}: 'gate' must be an object")
        gate = {}
    verdict_keys = ("samples", "ess", "v_behavior", "v_candidate",
                    "passed", "reason")
    for leg, want_passed in (("bad", False), ("good", True)):
        row = gate.get(leg)
        if not isinstance(row, dict) or not isinstance(
            row.get("verdict"), dict
        ):
            errs.append(f"{path}: gate.{leg}.verdict must be an object")
            continue
        v = row["verdict"]
        for key in verdict_keys:
            if key not in v:
                errs.append(f"{path}: gate.{leg}.verdict missing {key!r}")
        if v.get("passed") is not want_passed:
            errs.append(
                f"{path}: gate.{leg}.verdict.passed is "
                f"{v.get('passed')!r} (the planted {leg} bundle must be "
                f"{'allowed' if want_passed else 'blocked'})"
            )
    if gate.get("bad", {}).get("blocked") is not True:
        errs.append(
            f"{path}: gate.bad.blocked must attest True — the bad bundle "
            "must be stopped BEFORE live error rate ever sees it"
        )
    if gate.get("good", {}).get("promoted") is not True:
        errs.append(f"{path}: gate.good.promoted must attest True")
    counters = doc.get("counters")
    if not isinstance(counters, dict):
        return errs + [f"{path}: 'counters' must be an object"]
    router = counters.get("router")
    if not isinstance(router, dict):
        errs.append(f"{path}: counters.router must be an object")
    else:
        for key, floor in (("gate_evaluations", 3), ("gate_pass", 1),
                           ("gate_block", 1), ("gate_stalls", 1),
                           ("canary_promotions", 1),
                           ("canary_rollbacks", 2)):
            if not isinstance(router.get(key), int) or router[key] < floor:
                errs.append(
                    f"{path}: counters.router.{key} must be an int >= "
                    f"{floor}, got {router.get(key)!r}"
                )
        if isinstance(router.get("gate_evaluations"), int) and (
            router["gate_evaluations"]
            != router.get("gate_pass", 0) + router.get("gate_block", 0)
            + router.get("gate_stalls", 0)
        ):
            errs.append(
                f"{path}: gate accounting broken: evaluations "
                f"({router.get('gate_evaluations')}) != pass + block + "
                f"stalls — a gate verdict went unaccounted"
            )
    tap = counters.get("tap")
    if not isinstance(tap, dict):
        errs.append(f"{path}: counters.tap must be an object")
    else:
        sides = ("windows_acked", "windows_stale", "windows_shed",
                 "windows_dropped_chaos", "windows_dropped_link",
                 "windows_dropped_full", "pending")
        missing = [k for k in ("windows_built",) + sides if k not in tap]
        if missing:
            errs.append(f"{path}: counters.tap missing {missing}")
        elif tap["windows_built"] != sum(tap[k] for k in sides):
            errs.append(
                f"{path}: tap window identity broken: windows_built "
                f"({tap['windows_built']}) != acked+stale+shed+dropped+"
                f"pending ({sum(tap[k] for k in sides)}) — a mirrored "
                "window went unaccounted"
            )
        if tap.get("windows_dropped_chaos", 0) < 1:
            errs.append(
                f"{path}: counters.tap.windows_dropped_chaos is "
                f"{tap.get('windows_dropped_chaos')!r} — the mirror_drop "
                "chaos site must demonstrably fire (and balance)"
            )
    ingest = counters.get("ingest")
    if not isinstance(ingest, dict):
        errs.append(f"{path}: counters.ingest must be an object")
    else:
        mir = ingest.get("windows_from_mirror")
        act = ingest.get("windows_from_actors")
        tot = ingest.get("windows_ingested")
        if not all(isinstance(v, (int, float)) for v in (mir, act, tot)):
            errs.append(
                f"{path}: counters.ingest needs numeric windows_ingested "
                "/ windows_from_mirror / windows_from_actors"
            )
        else:
            if mir + act != tot:
                errs.append(
                    f"{path}: ingest source identity broken: from_mirror "
                    f"({mir}) + from_actors ({act}) != ingested ({tot})"
                )
            if not mir > 0 or act != 0:
                errs.append(
                    f"{path}: the soak's learner is mirror-fed ONLY "
                    f"(from_mirror={mir!r}, from_actors={act!r})"
                )
    if doc.get("identity_ok") is not True:
        errs.append(
            f"{path}: identity_ok is {doc.get('identity_ok')!r} — the "
            "committed artifact must attest the accounting identities"
        )
    return errs


# League identity columns (ISSUE 15): when a row carries one it must
# carry both, integer-valued and non-negative — the league controller
# groups rows by (variant_id, league_generation).
_LEAGUE_COLUMNS = ("variant_id", "league_generation")


def check_metrics_jsonl(path: str, max_rows: int | None = None) -> list[str]:
    """Problems with one metrics.jsonl ([] = clean)."""
    errs = []
    try:
        f = open(path, encoding="utf-8")
    except OSError as e:
        return [f"{path}: unreadable ({e})"]
    with f:
        for lineno, line in enumerate(f, start=1):
            if max_rows is not None and lineno > max_rows:
                break
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except ValueError:
                errs.append(f"{path}:{lineno}: invalid JSON row")
                continue
            if not isinstance(row, dict):
                errs.append(f"{path}:{lineno}: row is not an object")
                continue
            step = row.get("step")
            if not isinstance(step, int) or isinstance(step, bool):
                errs.append(f"{path}:{lineno}: missing/non-int 'step'")
            if not isinstance(row.get("t"), (int, float)):
                errs.append(f"{path}:{lineno}: missing/non-numeric 't'")
            for k, v in row.items():
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    errs.append(
                        f"{path}:{lineno}: non-numeric value for {k!r} "
                        f"({type(v).__name__}) — MetricsLogger rows are "
                        "numeric-only by contract"
                    )
                    break
            present = [k for k in _LEAGUE_COLUMNS if k in row]
            if present and len(present) != len(_LEAGUE_COLUMNS):
                errs.append(
                    f"{path}:{lineno}: league columns are a pair — "
                    f"row has {present} but not "
                    f"{[k for k in _LEAGUE_COLUMNS if k not in row]}"
                )
            for k in present:
                v = row[k]
                if isinstance(v, bool) or not isinstance(v, (int, float)) \
                        or v != int(v) or v < 0:
                    errs.append(
                        f"{path}:{lineno}: {k!r} must be a non-negative "
                        f"integer value, got {v!r}"
                    )
    return errs


def check_tree(root: str) -> list[str]:
    errs = []
    for path in sorted(glob.glob(os.path.join(root, "benchmarks", "*.json"))):
        if os.path.basename(path) == "lock_order_graph.json":
            # its own schema (and acyclicity pin + freshness vs the
            # current code) replaces the generic backend-key rule
            errs.extend(check_lock_order_graph(path, root))
            continue
        if os.path.basename(path) == "flow_identities.json":
            # same contract as the lock graph: its own schema + a
            # freshness pin vs the current code
            errs.extend(check_flow_identities(path, root))
            continue
        errs.extend(check_benchmark_json(path))
        if os.path.basename(path) == "composition_matrix.json":
            errs.extend(check_composition_matrix(path))
        if os.path.basename(path) == "league_soak.json":
            errs.extend(check_league_soak(path))
        if os.path.basename(path) == "flywheel_soak.json":
            errs.extend(check_flywheel_soak(path))
    for path in sorted(
        glob.glob(os.path.join(root, "runs", "**", "metrics.jsonl"),
                  recursive=True)
    ):
        # Bounded: the lint gate must stay O(1) in the operator's local
        # run history (a long run logs hundreds of thousands of rows).
        errs.extend(check_metrics_jsonl(path, max_rows=2000))
    return errs


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    root = args[0] if args else os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    errs = check_tree(root)
    for e in errs:
        print(e)
    n = len(errs)
    print(f"schema-check: {n} problem{'s' if n != 1 else ''}")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
