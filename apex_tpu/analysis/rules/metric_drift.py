"""METRIC-DRIFT: doc-mentioned vs registered metric and span names.

Dashboards and runbooks are written against ``docs/API.md``; scrapes
are written against what the registry actually exports. A renamed
counter that only updates one side is a silent observability outage —
the scrape returns 0-series, the dashboard goes flat, nobody alarms.
Both directions are checked:

- a metric name mentioned in ``docs/API.md`` / ``README.md`` /
  ``bench.py`` that no ``registry.counter/gauge/histogram`` call in
  ``apex_tpu/telemetry`` or ``apex_tpu/serving`` registers is drift
  (anchored at the doc mention);
- a registered ``serving_*``/``api_*`` metric — or ``engine.*`` /
  ``sched.*`` span section — that ``docs/API.md`` never mentions is an
  undocumented export (anchored at the registration site, suppressible
  there).

Doc tokens support the label and brace-alternation shorthand the docs
already use: ``serving_requests_shed_total{reason="..."}`` is the bare
name, ``serving_spec_{drafted,accepted}_total`` expands to both. To
keep bench.py's non-metric JSON keys out of scope, an *unregistered*
mention only counts when it carries a canonical metric suffix
(``_total``/``_seconds``/``_bytes``/``_state``).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Set, Tuple

from apex_tpu.analysis._astutil import const_str
from apex_tpu.analysis.core import Finding, Project

_REGISTER_METHODS = {"counter", "gauge", "histogram"}
_METRIC_PREFIX = re.compile(r"^(serving|api)_[a-z0-9_]+$")
#: a call whose first argument is such a constant records that section
#: (``spans.section``, ``section_at``, or a wrapper around them)
_SPAN_PREFIX = re.compile(r"^(engine|sched)\.[a-z_]+$")

_DOC_METRIC_TOKEN = re.compile(
    r"\b((?:serving|api)_[a-z0-9_]+(?:\{[^}\n]*\}[a-z0-9_]*)?)")
_DOC_SPAN_TOKEN = re.compile(r"\bengine\.([a-z_]+)\b")
#: ``sched`` is also what code samples and bench.py call their
#: Scheduler, so a ``sched.<x>`` token is a span claim only where it
#: stands alone in backticks or quotes
_DOC_SCHED_TOKEN = re.compile(r"[`\"'](sched\.[a-z_]+)[`\"']")
#: an unregistered doc mention is only drift when it looks like a
#: metric, not a JSON key that happens to share the prefix
_CANONICAL_SUFFIX = ("_total", "_seconds", "_bytes", "_state")

#: where registrations are collected from
_REGISTRY_SUBTREES = ("apex_tpu/telemetry/", "apex_tpu/serving/")
#: mention-side files
_DOC_FILES = ("docs/API.md", "README.md", "bench.py")


def _expand_doc_token(token: str) -> List[str]:
    m = re.match(r"([a-z0-9_]+)\{([^}]*)\}([a-z0-9_]*)", token)
    if not m:
        return [token]
    pre, content, post = m.groups()
    if "=" in content or '"' in content:
        return [pre] if not post else [pre + post]
    # alternation is INFIX (`serving_spec_{drafted,accepted}_total`);
    # a brace after a complete name (`api_responses_total{route,code}`)
    # is a label set
    if not post and not pre.endswith("_"):
        return [pre]
    if "," in content:
        return [pre + part.strip() + post
                for part in content.split(",") if part.strip()]
    return [pre + content + post]


class MetricDriftRule:
    id = "METRIC-DRIFT"
    summary = ("metric/span names in docs/API.md, README.md, bench.py "
               "must be registered in telemetry/serving, and every "
               "registered name must be documented in docs/API.md")
    triggers: Tuple[str, ...] = ("docs/API.md", "README.md", "bench.py",
                                 "apex_tpu/telemetry/",
                                 "apex_tpu/serving/")

    def run(self, project: Project) -> Iterable[Finding]:
        api_text = project.read_text("docs/API.md")
        if api_text is None:
            return []  # not this repo shape (synthetic tree)
        project.ensure_package_index()  # registrations may not be targets

        registered: Dict[str, Tuple[str, int]] = {}
        spans: Dict[str, Tuple[str, int]] = {}
        for ctx in project.by_rel.values():
            if ctx.tree is None or not any(
                    ctx.rel.startswith(p) for p in _REGISTRY_SUBTREES):
                continue
            for node in ast.walk(ctx.tree):
                if not (isinstance(node, ast.Call) and node.args):
                    continue
                name = const_str(node.args[0])
                if name is None:
                    continue
                if _SPAN_PREFIX.match(name):
                    spans.setdefault(name, (ctx.rel, node.lineno))
                elif isinstance(node.func, ast.Attribute) and \
                        node.func.attr in _REGISTER_METHODS and \
                        _METRIC_PREFIX.match(name):
                    registered.setdefault(name, (ctx.rel, node.lineno))

        if not registered and not spans:
            return []  # nothing to drift against (synthetic tree)

        # names an `engine.<x>` doc token may legitimately mean besides
        # a span: Engine methods/attributes (engine.warmup() etc.)
        engine_api = self._engine_api_names(project)

        findings: List[Finding] = []
        mentioned_api: Set[str] = set()
        for rel in _DOC_FILES:
            text = project.read_text(rel)
            if text is None:
                continue
            for lineno, line in enumerate(text.splitlines(), start=1):
                for m in _DOC_METRIC_TOKEN.finditer(line):
                    for name in _expand_doc_token(m.group(1)):
                        if rel == "docs/API.md":
                            mentioned_api.add(name)
                        if name in registered:
                            continue
                        if name.endswith(_CANONICAL_SUFFIX):
                            findings.append(Finding(
                                self.id, rel, lineno,
                                f"metric {name!r} is mentioned here but "
                                f"never registered in apex_tpu/telemetry"
                                f" or apex_tpu/serving — renamed or "
                                f"removed without updating the doc"))
                claims = [m.group(1)
                          for m in _DOC_SCHED_TOKEN.finditer(line)]
                if rel == "docs/API.md":
                    mentioned_api.update(claims)
                for m in _DOC_SPAN_TOKEN.finditer(line):
                    name = f"engine.{m.group(1)}"
                    if rel == "docs/API.md":
                        mentioned_api.add(name)
                    # the Engine-API excuse applies only to call-spelled
                    # mentions (`engine.warmup()`); a BARE mention of a
                    # name that happens to collide with an Engine method
                    # (engine.admit, engine.fetch) is still a span claim
                    # and must be backed by a registration
                    is_call = line[m.end():m.end() + 1] == "("
                    if not (is_call and m.group(1) in engine_api):
                        claims.append(name)
                for name in claims:
                    if name not in spans:
                        findings.append(Finding(
                            self.id, rel, lineno,
                            f"span section {name!r} is mentioned here "
                            f"but never emitted by any call that names "
                            f"it — renamed or removed without updating "
                            f"the doc"))
        for name, (rel, lineno) in sorted(registered.items()):
            if name not in mentioned_api:
                findings.append(Finding(
                    self.id, rel, lineno,
                    f"metric {name!r} is registered here but docs/"
                    f"API.md never mentions it — document the export "
                    f"(scrapes and dashboards are written against the "
                    f"doc)"))
        for name, (rel, lineno) in sorted(spans.items()):
            if name not in mentioned_api:
                findings.append(Finding(
                    self.id, rel, lineno,
                    f"span section {name!r} is emitted here but docs/"
                    f"API.md never mentions it — document the export"))
        return findings

    @staticmethod
    def _engine_api_names(project: Project) -> Set[str]:
        ctx = project.by_rel.get("apex_tpu/serving/engine.py")
        names: Set[str] = set()
        if ctx is None or ctx.tree is None:
            return names
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                names.add(node.name)
            elif isinstance(node, ast.Attribute) and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id == "self":
                names.add(node.attr)
        return names
