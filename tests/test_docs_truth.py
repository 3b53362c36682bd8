"""The user-facing documents name only what the tree holds.

For each document a reader is sent to — ``README.md``, ``docs/*.md``,
``cellbench/README.md`` and the verify skill — two checks:

- ``paths``: every token in a code span or code block that is a repo path
  (under ``d4pg_tpu/``, ``tests/``, ``tools/``, ``scripts/``,
  ``benchmarks/``, ``cellbench/``, ``native/``, or a bare ``name.py``)
  exists, once a ``:line`` / ``::name`` suffix is stripped. A glob must
  match something; a bare ``name.py`` must be some file's name.
- ``flags``: every ``--flag`` is an option string that some
  ``add_argument`` call in the repo's Python declares (an AST scan, nothing
  imported), or belongs to another tool (``FOREIGN_FLAGS``).

``PERF.md``, ``ROADMAP.md``, ``CHANGES.md`` and ``SURVEY.md`` are histories
and name what is gone on purpose: not held. A stale path or flag is fixed in
the document, not listed here.
"""

from __future__ import annotations

import ast
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    ["README.md", "cellbench/README.md", ".claude/skills/verify/SKILL.md"]
    + sorted(
        os.path.relpath(p, REPO)
        for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
    )
)

# Options of other tools that the documents show in commands. Nothing of
# this repo's own belongs here.
FOREIGN_FLAGS = {
    "--no-deps", "--no-build-isolation",          # pip
    "--xla_force_host_platform_device_count",     # XLA_FLAGS
    "--chips",                                    # the chip tool
    "--logdir",                                   # tensorboard
    "--worker", "--command",                      # gcloud
}

PATH_ROOTS = ("d4pg_tpu", "tests", "tools", "scripts", "benchmarks",
              "cellbench", "native")
_PATH = re.compile(
    r"(?<![\w./-])((?:%s)/[\w./*{},<>\[\]-]*|[A-Za-z_]\w*\.py)(?![\w/])"
    % "|".join(PATH_ROOTS)
)
_FLAG = re.compile(r"(?<![\w-])--[a-z][\w-]*")
# a code block, or a code span (which may wrap over one line break)
_CODE = re.compile(r"```.*?```|`[^`\n]+(?:\n[^`\n]+)?`", re.S)


@functools.cache
def _python_files() -> tuple:
    found = []
    for base, dirs, files in os.walk(REPO):
        # scratch copies of other commits, run outputs and caches are not
        # the tree (all are in .gitignore)
        dirs[:] = [
            d for d in dirs
            if not d.startswith((".", "_")) and not d.endswith("_out")
        ]
        for f in files:
            if f.endswith(".py"):
                found.append(os.path.join(base, f))
    return tuple(found)


@functools.cache
def _declared_flags() -> frozenset:
    flags = set()
    for path in _python_files():
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "add_argument"):
                continue
            names = [
                a.value for a in node.args
                if isinstance(a, ast.Constant) and isinstance(a.value, str)
                and a.value.startswith("--")
            ]
            flags.update(names)
            if any(k.arg == "action" and "BooleanOptionalAction"
                   in ast.unparse(k.value) for k in node.keywords):
                flags.update("--no-" + n[2:] for n in names)
    return frozenset(flags)


def _expand(token: str) -> list:
    """``a/{b,c}.py`` -> ``a/b.py``, ``a/c.py`` (one level is all the
    documents use)."""
    m = re.search(r"\{([^{}]*)\}", token)
    if not m:
        return [token]
    return [token[:m.start()] + alt + token[m.end():]
            for alt in m.group(1).split(",")]


def _stale_paths(text: str) -> list:
    names = {os.path.basename(p) for p in _python_files()}
    stale = []
    for span in _CODE.findall(text):
        for token in _PATH.findall(span):
            token = re.sub(r"(::?[\w.\[\]-]*)+$", "", token).rstrip(".,")
            for path in _expand(token):
                if "<" in path or "…" in path:
                    continue  # a placeholder: `cellbench/configs/<name>.json`
                if "/" not in path:
                    ok = path in names
                elif "*" in path:
                    ok = bool(glob.glob(os.path.join(REPO, path),
                                        recursive=True))
                else:
                    ok = os.path.exists(os.path.join(REPO, path))
                if not ok:
                    stale.append(path)
    return sorted(set(stale))


def _stale_flags(text: str) -> list:
    known = _declared_flags() | FOREIGN_FLAGS
    return sorted(set(_FLAG.findall(text)) - known)


@pytest.mark.parametrize("check", ["paths", "flags"])
@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_the_tree_holds(document, check):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        text = f.read()
    stale = (_stale_paths if check == "paths" else _stale_flags)(text)
    assert not stale, (
        f"{document} names {check} the tree does not hold: {stale} — fix "
        "the document"
    )

