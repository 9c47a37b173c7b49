"""Rule engine: file contexts, suppressions, runner, output.

Stdlib-only by contract (``ast``, ``re``, ``json``) — the tier-1 test
imports this package with jax/numpy purged from ``sys.modules`` and a
blocking meta-path hook installed, so a stray ``import numpy`` here is
a test failure, not a style nit.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import json
import os
import re
import subprocess
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: directories never walked for source files
_SKIP_DIRS = {"__pycache__", ".git", ".jax_cache", "chiprun_out",
              "_checkout", "_parent", ".pytest_cache", "node_modules"}

#: the suppression comment:  "apex: noqa[<rule>]: justification"
#: after a hash (spelled without one here or it would register itself)
_NOQA_RE = re.compile(
    r"#\s*apex:\s*noqa\[([A-Za-z0-9_-]+)\]\s*(?::\s*(.*?))?\s*$")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation, anchored where the suppression comment goes.

    ``extra_suppress_lines`` lists additional lines whose suppression
    comment also covers this finding (e.g. TIER1-COST anchors at the
    ``.warmup()`` call but accepts a suppression on the enclosing
    ``def`` line, so one comment covers a helper used by many tests).
    """

    rule: str
    path: str  # repo-relative, posix separators
    line: int
    message: str
    col: int = 0
    extra_suppress_lines: Tuple[int, ...] = ()

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


@dataclasses.dataclass
class Suppression:
    path: str
    line: int
    rule: str
    justification: str
    used: bool = False


class FileCtx:
    """One parsed source file: text, lines, AST, suppressions."""

    def __init__(self, abspath: str, rel: str, source: str):
        self.abspath = abspath
        self.rel = rel
        self.source = source
        self.lines = source.splitlines()
        self.tree: Optional[ast.AST] = None
        self.parse_error: Optional[str] = None
        try:
            self.tree = ast.parse(source, filename=rel)
        except SyntaxError as e:  # surfaced as a finding by the runner
            self.parse_error = f"syntax error: {e.msg} (line {e.lineno})"
        self.suppressions: List[Suppression] = []
        # tokenize so only REAL comments count — a docstring that
        # *documents* the noqa syntax must not register as one
        try:
            tokens = list(tokenize.generate_tokens(
                io.StringIO(source).readline))
        except (tokenize.TokenError, SyntaxError, IndentationError):
            tokens = []
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _NOQA_RE.search(tok.string)
            if m:
                self.suppressions.append(Suppression(
                    path=rel, line=tok.start[0], rule=m.group(1),
                    justification=(m.group(2) or "").strip()))

    @property
    def module_name(self) -> str:
        rel = self.rel[:-3] if self.rel.endswith(".py") else self.rel
        parts = [p for p in rel.split("/") if p]
        if parts and parts[-1] == "__init__":
            parts = parts[:-1]
        return ".".join(parts)


def find_repo_root(start: str) -> str:
    """Walk up from ``start`` to the checkout root (pyproject.toml or
    .git); falls back to ``start`` itself (synthetic test trees)."""
    d = os.path.abspath(start)
    if os.path.isfile(d):
        d = os.path.dirname(d)
    while True:
        if os.path.exists(os.path.join(d, "pyproject.toml")) or \
                os.path.exists(os.path.join(d, ".git")):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            return os.path.abspath(start if os.path.isdir(start)
                                   else os.path.dirname(start))
        d = parent


def _iter_py_files(path: str) -> Iterable[str]:
    if os.path.isfile(path):
        yield path
        return
    for dirpath, dirnames, filenames in os.walk(path):
        dirnames[:] = sorted(d for d in dirnames if d not in _SKIP_DIRS)
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


class Project:
    """The analyzed world: ``targets`` are the files findings may be
    reported in; ``index`` additionally parses the whole ``apex_tpu``
    package under the repo root so cross-module rules (the tracer-leak
    call walk) resolve callees that are not themselves lint targets
    (``--changed`` mode)."""

    def __init__(self, root: str, target_files: Sequence[str]):
        self.root = os.path.abspath(root)
        self.targets: List[FileCtx] = []
        self.index: Dict[str, FileCtx] = {}  # module name -> ctx
        self.by_rel: Dict[str, FileCtx] = {}
        self._package_indexed = False
        # overlapping targets (`apex_tpu apex_tpu/serving`) resolve to
        # one ctx — appending it twice would double every per-target
        # finding and the pinned suppressions.active count
        self.target_rels: set = set()
        for path in target_files:
            ctx = self._load(path)
            if ctx is not None and ctx.rel not in self.target_rels:
                self.target_rels.add(ctx.rel)
                self.targets.append(ctx)

    def ensure_package_index(self) -> None:
        """Parse the whole ``apex_tpu`` package into the index (lazy —
        only cross-module rules pay for it; a tests-only TIER1-COST
        run never does). ``bench.py`` and ``examples`` ride along:
        they are first-class lint targets whose justified suppressions
        must stay visible to a partial ``--changed`` run that anchors
        a global-rule finding there."""
        if self._package_indexed:
            return
        self._package_indexed = True
        for name in ("apex_tpu", "bench.py", "examples"):
            p = os.path.join(self.root, name)
            if os.path.exists(p):
                for path in _iter_py_files(p):
                    self._load(path)

    def _load(self, path: str) -> Optional[FileCtx]:
        abspath = os.path.abspath(path)
        rel = os.path.relpath(abspath, self.root).replace(os.sep, "/")
        if rel in self.by_rel:
            return self.by_rel[rel]
        try:
            with open(abspath, "r", encoding="utf-8") as f:
                source = f.read()
        except OSError:
            return None
        ctx = FileCtx(abspath, rel, source)
        self.by_rel[rel] = ctx
        self.index[ctx.module_name] = ctx
        return ctx

    def read_text(self, rel: str) -> Optional[str]:
        """A repo file outside the python index (docs, csrc)."""
        try:
            with open(os.path.join(self.root, rel), "r",
                      encoding="utf-8") as f:
                return f.read()
        except OSError:
            return None


def changed_files(root: str) -> List[str]:
    """Repo-relative paths touched vs HEAD (worktree + staged +
    untracked) — the pre-commit surface. A failing git query is a
    usage error, not an empty change set: silently analyzing 0 files
    would let the gate pass without linting anything."""
    out: List[str] = []
    for args in (["diff", "--name-only", "HEAD"],
                 ["ls-files", "--others", "--exclude-standard"]):
        try:
            r = subprocess.run(["git", "-C", root] + args,
                               capture_output=True, text=True, timeout=30)
        except (OSError, subprocess.TimeoutExpired) as e:
            raise ValueError(f"--changed requires a working git: {e}")
        if r.returncode != 0:
            raise ValueError(
                f"--changed: `git {' '.join(args)}` failed in {root}: "
                f"{r.stderr.strip() or r.stdout.strip()}")
        out.extend(l.strip() for l in r.stdout.splitlines() if l.strip())
    seen = set()
    return [p for p in out if not (p in seen or seen.add(p))]


@dataclasses.dataclass
class Result:
    findings: List[Finding]
    suppressions_used: List[Suppression]
    rules: List[str]
    files: int

    @property
    def exit_code(self) -> int:
        return 1 if self.findings else 0


def run_analysis(target_paths: Sequence[str], *,
                 rules: Optional[Sequence[str]] = None,
                 root: Optional[str] = None,
                 changed_only: bool = False) -> Result:
    """Run the battery over ``target_paths`` (files or directories).

    ``rules`` restricts the battery by id (NOQA hygiene always runs,
    scoped to the enabled ids). ``changed_only`` intersects the targets
    with the git-changed set. Findings suppressed by a justified
    ``# apex: noqa[RULE]: why`` comment are dropped; bare or unused
    suppressions come back as NOQA-BARE / NOQA-UNUSED findings.
    """
    from apex_tpu.analysis.rules import ALL_RULES

    first = target_paths[0] if target_paths else os.getcwd()
    root = os.path.abspath(root) if root else find_repo_root(first)

    files: List[str] = []
    for t in target_paths:
        # an explicit target that does not exist must be a usage error,
        # not a silent 0-files "clean" pass from the merge gate itself
        # (e.g. the CLI's relative defaults run from the wrong cwd)
        if not os.path.exists(t):
            raise ValueError(f"target does not exist: {t}")
        files.extend(_iter_py_files(t))
    changed: Optional[set] = None
    if changed_only:
        changed = set(changed_files(root))
        files = [f for f in files
                 if os.path.relpath(os.path.abspath(f), root)
                 .replace(os.sep, "/") in changed]

    project = Project(root, files)

    enabled = [r for r in ALL_RULES
               if rules is None or r.id in set(rules)]
    if rules is not None:
        known = {r.id for r in ALL_RULES}
        bad = set(rules) - known
        if bad:
            raise ValueError(
                f"unknown rule ids {sorted(bad)}; known: {sorted(known)}")

    findings: List[Finding] = []
    for ctx in project.targets:
        if ctx.parse_error:
            findings.append(Finding(
                "PARSE", ctx.rel, 1, ctx.parse_error))
    for rule in enabled:
        if changed is not None and rule.triggers:
            # global rule in --changed mode: run only when one of its
            # inputs changed (its findings are not per-target anyway);
            # a trigger ending in "/" matches the whole subtree
            if not any(c == t or (t.endswith("/") and c.startswith(t))
                       for c in changed for t in rule.triggers):
                continue
        findings.extend(rule.run(project))

    # -- suppression pass --------------------------------------------------
    # matching draws on EVERY indexed file, not just targets: global
    # rules (METRIC-DRIFT) anchor findings at package files a partial
    # --changed run never targeted, and a justified suppression there
    # must still silence them. Hygiene (bare/unused) below stays
    # targets-only — a partial run cannot judge a non-target noqa.
    sup_at: Dict[Tuple[str, int], List[Suppression]] = {}
    enabled_ids = {r.id for r in enabled}
    for ctx in project.by_rel.values():
        for s in ctx.suppressions:
            sup_at.setdefault((s.path, s.line), []).append(s)

    visible: List[Finding] = []
    for f in findings:
        matched = None
        for line in (f.line,) + f.extra_suppress_lines:
            for s in sup_at.get((f.path, line), []):
                if s.rule == f.rule:
                    matched = s
                    break
            if matched:
                break
        if matched is None:
            visible.append(f)
        else:
            matched.used = True

    # ids a suppression may legitimately name beyond the enabled battery
    # (runner-emitted findings are suppressible like any other)
    known_ids = {r.id for r in ALL_RULES} | \
        {"PARSE", "NOQA-BARE", "NOQA-UNUSED", "NOQA-UNKNOWN"}
    used: List[Suppression] = []
    for ctx in project.targets:
        for s in ctx.suppressions:
            if s.rule not in enabled_ids:
                # a typo'd / renamed rule id would otherwise be a
                # permanently dead annotation no run ever flags; only
                # the full battery can judge it (a --rules run cannot
                # tell "another battery's id" from "no such id")
                if rules is None and s.rule not in known_ids:
                    visible.append(Finding(
                        "NOQA-UNKNOWN", s.path, s.line,
                        f"suppression names unknown rule {s.rule!r} — "
                        f"known ids: {', '.join(sorted(known_ids))}"))
                continue  # another run's battery owns this one
            if not s.justification:
                visible.append(Finding(
                    "NOQA-BARE", s.path, s.line,
                    f"suppression of {s.rule} carries no justification "
                    f"— write `# apex: noqa[{s.rule}]: <why>`"))
            if s.used:
                used.append(s)
            else:
                visible.append(Finding(
                    "NOQA-UNUSED", s.path, s.line,
                    f"suppression of {s.rule} matches no finding — the "
                    f"rule no longer fires here; delete the comment"))

    visible.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return Result(findings=visible, suppressions_used=used,
                  rules=sorted(enabled_ids), files=len(project.targets))


def summary_dict(result: Result) -> dict:
    """The machine-readable (``--json``) shape. ``suppressions.active``
    is the pinned can-only-go-down count from the satellite contract."""
    counts: Dict[str, int] = {}
    for f in result.findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    sup_by_rule: Dict[str, int] = {}
    for s in result.suppressions_used:
        sup_by_rule[s.rule] = sup_by_rule.get(s.rule, 0) + 1
    return {
        "version": 1,
        "files": result.files,
        "rules": result.rules,
        "findings": [dataclasses.asdict(f) for f in result.findings],
        "counts": counts,
        "suppressions": {
            "active": len(result.suppressions_used),
            "by_rule": sup_by_rule,
        },
        "exit_code": result.exit_code,
    }


def render_text(result: Result) -> str:
    out = [f.render() for f in result.findings]
    out.append(
        f"{len(result.findings)} finding(s), "
        f"{len(result.suppressions_used)} active suppression(s), "
        f"{result.files} file(s) analyzed")
    return "\n".join(out)


def render_json(result: Result) -> str:
    return json.dumps(summary_dict(result), indent=2, sort_keys=True)
