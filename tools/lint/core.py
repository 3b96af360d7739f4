"""lhtpu-lint core: file loading, waivers, scoping, orchestration.

The linter is pure stdlib-``ast`` — it never imports the code under
analysis (so it runs in milliseconds, needs no JAX, and cannot be
confused by import-time side effects). The one exception is
``lighthouse_tpu/common/knobs.py``, which the knob checks execute in
isolation via importlib (it depends on nothing but the stdlib) so the
knob registry and the generated README table have a single source.

Waiver syntax::

    risky_line()  # lhtpu: ignore[LH502] -- why this swallow is safe

The justification after ``--`` is REQUIRED: a waiver without one is
itself a finding (LH002). Multiple codes: ``ignore[LH201,LH502]``.
"""

from __future__ import annotations

import ast
import os
import re
import subprocess
from dataclasses import dataclass

#: bumped whenever a check family changes behavior; embedded in bench
#: JSON lines (lint provenance) and the --json output.
LINT_VERSION = "1.0.0"

#: directories never walked in full-tree mode
SKIP_DIRS = {
    ".git", "__pycache__", ".pytest_cache", ".jax_cache", "chiprun_out",
    "chip_checkout",
    ".claude", "build", "dist", "node_modules",
}

#: fixture files deliberately violate the invariants; they are linted
#: only when named explicitly (the golden tests do exactly that).
FIXTURE_DIR = os.path.join("tests", "fixtures", "lint")

_WAIVER_RE = re.compile(
    r"#\s*lhtpu:\s*ignore\[([A-Z0-9_,\s]+)\](\s*--\s*(\S.*))?"
)


@dataclass(frozen=True)
class Finding:
    file: str      # repo-relative path
    line: int      # 1-indexed
    code: str      # e.g. "LH201"
    message: str

    def as_dict(self) -> dict:
        return {
            "file": self.file, "line": self.line,
            "code": self.code, "message": self.message,
        }

    def render(self) -> str:
        return f"{self.file}:{self.line}: {self.code} {self.message}"


class FileCtx:
    """One parsed source file plus its waiver table."""

    def __init__(self, root: str, rel: str, source: str):
        self.root = root
        self.rel = rel.replace(os.sep, "/")
        self.source = source
        self.tree = ast.parse(source, filename=rel)
        # line -> set of waived codes; lines with a waiver but no
        # justification recorded separately (LH002).
        self.waivers: dict[int, set[str]] = {}
        self.unjustified: list[int] = []
        for lineno, text in enumerate(source.splitlines(), start=1):
            m = _WAIVER_RE.search(text)
            if not m:
                continue
            codes = {c.strip() for c in m.group(1).split(",") if c.strip()}
            self.waivers[lineno] = codes
            if not m.group(3):
                self.unjustified.append(lineno)

    @property
    def in_fixture_dir(self) -> bool:
        return self.rel.startswith(FIXTURE_DIR.replace(os.sep, "/"))

    @property
    def fixture_family(self) -> str | None:
        """Golden fixtures opt into exactly ONE family via filename
        (``lh101_pos.py`` -> family ``lh1``) so each file triggers
        exactly one code without tripping sibling families."""
        if not self.in_fixture_dir:
            return None
        m = re.match(r"(lh\d)", os.path.basename(self.rel))
        return m.group(1) if m else None

    def waived(self, line: int, code: str) -> bool:
        codes = self.waivers.get(line)
        return bool(codes) and (code in codes or "ALL" in codes)


class Ctx:
    """Whole-run context handed to every check family."""

    def __init__(self, root: str, files: list[FileCtx],
                 full_tree: bool):
        self.root = root
        self.files = files
        #: True when the whole repo was walked — repo-level checks
        #: (README table staleness, dead knobs, missing grouped twins)
        #: only make sense then, not on a --changed-only subset.
        self.full_tree = full_tree
        self.findings: list[Finding] = []

    def add(self, f: FileCtx, line: int, code: str, message: str) -> None:
        if f.waived(line, code):
            return
        self.findings.append(Finding(f.rel, line, code, message))

    def by_rel(self, rel: str) -> FileCtx | None:
        for f in self.files:
            if f.rel == rel:
                return f
        return None


def iter_python_files(root: str):
    """Repo-relative paths of every lintable .py file (skips fixture
    and vendored/cache dirs)."""
    fixture_prefix = FIXTURE_DIR + os.sep
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(
            d for d in dirnames
            if d not in SKIP_DIRS and not d.startswith(".")
        )
        for name in sorted(filenames):
            if not name.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, name), root)
            if rel.startswith(fixture_prefix):
                continue
            yield rel


def changed_files(root: str) -> list[str]:
    """Repo-relative .py paths from ``git diff --name-only HEAD`` plus
    untracked files — the quick pre-commit scope."""
    out: list[str] = []
    for args in (
        ["git", "diff", "--name-only", "HEAD"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        proc = subprocess.run(
            args, cwd=root, capture_output=True, text=True, check=False,
        )
        out.extend(
            line.strip() for line in proc.stdout.splitlines()
            if line.strip().endswith(".py")
        )
    seen: set[str] = set()
    return [p for p in out if not (p in seen or seen.add(p))]


def _load(root: str, rel: str) -> FileCtx | None:
    path = os.path.join(root, rel)
    try:
        with open(path, "r", encoding="utf-8") as fh:
            source = fh.read()
        return FileCtx(root, rel, source)
    except (OSError, SyntaxError, UnicodeDecodeError):
        return None


def run_lint(root: str, files: list[str] | None = None) -> list[Finding]:
    """Lint the tree (or an explicit repo-relative file list) and
    return all findings, sorted by (file, line, code).

    Full-tree mode additionally runs the repo-level checks (README
    knob-table staleness, dead knobs, grouped-twin completeness).
    Explicit fixture files under ``tests/fixtures/lint/`` are placed in
    every family's scope so one tiny file can exercise one code.
    """
    from . import (builder_checks, determinism_checks, hygiene_checks,
                   knobs_checks, purity_checks, stage_checks)

    root = os.path.abspath(root)
    full_tree = files is None
    rels = list(iter_python_files(root)) if full_tree else [
        f.replace(os.sep, "/") for f in files
    ]
    ctxs = [c for c in (_load(root, rel) for rel in rels) if c is not None]
    ctx = Ctx(root, ctxs, full_tree)

    for f in ctxs:
        for line in f.unjustified:
            # not waivable: a waiver of the waiver-hygiene check would
            # defeat the justification requirement
            ctx.findings.append(Finding(
                f.rel, line, "LH002",
                "waiver missing justification (want "
                "'# lhtpu: ignore[CODE] -- why')",
            ))

    purity_checks.run(ctx)        # LH1xx
    knobs_checks.run(ctx)         # LH2xx
    stage_checks.run(ctx)         # LH3xx
    builder_checks.run(ctx)       # LH4xx
    hygiene_checks.run(ctx)       # LH5xx
    determinism_checks.run(ctx)   # LH6xx

    # identical findings can be emitted twice (e.g. a nested traced fn
    # reachable through two paths) — Finding is frozen, so dedupe by id
    return sorted(
        set(ctx.findings), key=lambda fi: (fi.file, fi.line, fi.code)
    )
