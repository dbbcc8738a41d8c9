"""Hygiene checkers: the three legacy lints absorbed, plus metric-kind rules.

``HygieneChecker`` carries the no-print and socket-discipline rules exactly as
``tools/lint_no_print.py``/``tools/lint_sockets.py`` enforced them (those CLIs
are now thin shims over this module — one parse pass instead of three).

``MetricChecker`` carries the metric-name/documentation rules from
``tools/lint_metric_names.py`` and adds the v2 hygiene rules:

* ``metric-kind-misuse`` — ``.set()`` on a counter (counters are monotonic),
  a gauge/histogram named ``*_total`` (the suffix is the counter contract
  scrapers aggregate with ``rate()``), or a gauge that is only ever
  ``inc()``ed anywhere in the tree (it is a counter wearing the wrong type);
* ``metric-label-cardinality`` — a label value fed straight from request
  data (a subscript/``.get()``/f-string expression): labels are for BOUNDED
  dimensions; per-request values explode the series space until the
  registry/TSDB cap starves real series (docs/observability.md).
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .core import Checker, Finding, ParsedModule, call_name, dotted_name, is_library_path

METRIC_NAME_RE = re.compile(r"^distar_[a-z][a-z0-9_]*$")
REGISTER_METHODS = ("counter", "gauge", "histogram")

#: files allowed to register dynamically-built metric names, with every name
#: their dynamic path can produce (which must itself be documented). Keys are
#: posix paths relative to the distar_tpu package root (the shape the legacy
#: lint used).
DYNAMIC_ALLOW: Dict[str, List[str]] = {}

TIMEOUT_REQUIRED = ("urlopen", "create_connection")


def _pkg_relpath(relpath: str) -> Optional[str]:
    """Path relative to the distar_tpu package root, None when outside it."""
    parts = relpath.replace(os.sep, "/").split("/")
    if "distar_tpu" in parts:
        return "/".join(parts[parts.index("distar_tpu") + 1:])
    return None


class HygieneChecker(Checker):
    """no-print (library code only) + socket discipline (whole tree)."""

    name = "hygiene"
    rules = {
        "no-print": "error",
        "socket-bare-except": "error",
        "socket-no-timeout": "error",
    }

    def check_module(self, mod: ParsedModule) -> Iterable[Finding]:
        # relpath when scanning the repo; abspath covers package-rooted
        # scans (the legacy lint CLIs pass the distar_tpu dir itself)
        check_print = is_library_path(mod.relpath) or is_library_path(mod.abspath)
        for node in ast.walk(mod.tree):
            if (check_print and isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "print"):
                yield self.finding(
                    "no-print", mod, node.lineno,
                    "bare print() in library code — route output through "
                    "TextLogger or the metrics registry "
                    "(docs/observability.md)",
                    ident="bare print",
                )
            elif isinstance(node, ast.ExceptHandler) and node.type is None:
                yield self.finding(
                    "socket-bare-except", mod, node.lineno,
                    "bare 'except:' — catch a typed error (resilience "
                    "taxonomy) or 'Exception'; bare swallows "
                    "KeyboardInterrupt/SystemExit",
                    ident="bare except",
                )
            elif isinstance(node, ast.Call) and call_name(node) in TIMEOUT_REQUIRED:
                if not any(kw.arg == "timeout" for kw in node.keywords):
                    yield self.finding(
                        "socket-no-timeout", mod, node.lineno,
                        f"{call_name(node)}() without an explicit timeout= — "
                        f"unbounded network wait (the week-long-run lesson "
                        f"behind the shuttle deadline fix)",
                        ident=f"{call_name(node)} no timeout",
                    )


class MetricChecker(Checker):
    """Metric naming/documentation + counter-vs-gauge + label cardinality."""

    name = "metrics"
    rules = {
        "metric-name": "error",
        "metric-undocumented": "error",
        "metric-dynamic-name": "error",
        "metric-kind-misuse": "error",
        "metric-label-cardinality": "warning",
    }

    def __init__(self, repo_root: str, docs_path: Optional[str] = None):
        self.repo_root = repo_root
        self.docs_path = docs_path or os.path.join(
            repo_root, "docs", "observability.md")
        self._documented: Optional[Set[str]] = None
        #: metric name -> set of ops observed anywhere in the tree, and one
        #: registration site per name (for the finalize-stage inc-only rule)
        self._gauge_ops: Dict[str, Set[str]] = {}
        self._gauge_sites: Dict[str, Tuple[ParsedModule, int]] = {}

    @property
    def documented(self) -> Set[str]:
        """Backticked ``distar_*`` names in docs/observability.md (table +
        prose both count — operators read the whole page)."""
        if self._documented is None:
            names: Set[str] = set()
            if os.path.exists(self.docs_path):
                with open(self.docs_path) as f:
                    text = f.read()
                for token in re.findall(r"`([^`\n]+)`", text):
                    m = re.match(r"(distar_[a-z0-9_]+)", token)
                    if m:
                        names.add(m.group(1))
            self._documented = names
        return self._documented

    # -------------------------------------------------------------- per-module
    def check_module(self, mod: ParsedModule) -> Iterable[Finding]:
        pkg_rel = _pkg_relpath(mod.relpath)
        if pkg_rel is None:
            pkg_rel = _pkg_relpath(mod.abspath)
        if pkg_rel is None:
            return  # metric registration rules cover the package only
        # var (dotted) -> (kind, name) for instrument-variable tracking
        bound: Dict[str, Tuple[str, str]] = {}
        registrations: List[Tuple[ast.Call, str, Optional[str]]] = []  # (call, kind, name)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in REGISTER_METHODS and node.args:
                kind = node.func.attr
                first = node.args[0]
                name = first.value if (isinstance(first, ast.Constant)
                                       and isinstance(first.value, str)) else None
                registrations.append((node, kind, name))

        for call, kind, name in registrations:
            if name is None:
                allowed = DYNAMIC_ALLOW.get(pkg_rel)
                if allowed is None:
                    yield self.finding(
                        "metric-dynamic-name", mod, call.lineno,
                        "dynamically-named metric registration — declare its "
                        "names in distar_tpu/analysis/hygiene.py DYNAMIC_ALLOW",
                        ident="dynamic metric name",
                    )
                else:
                    for dyn in allowed:
                        if dyn not in self.documented:
                            yield self.finding(
                                "metric-undocumented", mod, call.lineno,
                                f"dynamic metric {dyn!r} missing from the "
                                f"docs/observability.md metric table",
                                ident=f"undocumented {dyn}",
                            )
                continue
            if not METRIC_NAME_RE.match(name):
                yield self.finding(
                    "metric-name", mod, call.lineno,
                    f"metric {name!r} violates the distar_<subsystem>_<name> "
                    f"convention",
                    ident=f"bad name {name}",
                )
            elif name not in self.documented:
                yield self.finding(
                    "metric-undocumented", mod, call.lineno,
                    f"metric {name!r} missing from the docs/observability.md "
                    f"metric table",
                    ident=f"undocumented {name}",
                )
            if kind in ("gauge", "histogram") and name.endswith("_total"):
                yield self.finding(
                    "metric-kind-misuse", mod, call.lineno,
                    f"{kind} named {name!r} — the _total suffix is the counter "
                    f"contract (scrapers rate() it); rename or make it a "
                    f"counter",
                    ident=f"_total {kind} {name}",
                )
            yield from self._check_labels(mod, call, name)
            if kind == "gauge":
                self._gauge_sites.setdefault(name, (mod, call.lineno))

        # instrument-variable op tracking (set on counter, inc-only gauges)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Assign) or not isinstance(node.value, ast.Call):
                continue
            v = node.value
            if isinstance(v.func, ast.Attribute) and v.func.attr in REGISTER_METHODS \
                    and v.args and isinstance(v.args[0], ast.Constant) \
                    and isinstance(v.args[0].value, str):
                for tgt in node.targets:
                    d = dotted_name(tgt)
                    if d:
                        bound[d] = (v.func.attr, v.args[0].value)
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
                continue
            op = node.func.attr
            if op not in ("set", "inc", "dec", "observe"):
                continue
            target = node.func.value
            kind = name = None
            if isinstance(target, ast.Call) and isinstance(target.func, ast.Attribute) \
                    and target.func.attr in REGISTER_METHODS and target.args \
                    and isinstance(target.args[0], ast.Constant) \
                    and isinstance(target.args[0].value, str):
                kind, name = target.func.attr, target.args[0].value
            else:
                d = dotted_name(target)
                if d in bound:
                    kind, name = bound[d]
            if kind is None:
                continue
            if kind == "counter" and op in ("set", "dec"):
                yield self.finding(
                    "metric-kind-misuse", mod, node.lineno,
                    f".{op}() on counter {name!r} — counters are monotonic; "
                    f"use a gauge for values that move both ways",
                    ident=f"{op} on counter {name}",
                )
            if kind == "gauge":
                self._gauge_ops.setdefault(name, set()).add(op)
                self._gauge_sites.setdefault(name, (mod, node.lineno))

    def finalize(self) -> Iterable[Finding]:
        for name, ops in sorted(self._gauge_ops.items()):
            if ops == {"inc"}:
                mod, line = self._gauge_sites[name]
                yield self.finding(
                    "metric-kind-misuse", mod, line,
                    f"gauge {name!r} is only ever inc()ed across the tree — "
                    f"it is a counter wearing the wrong type (rate() queries "
                    f"and staleness handling differ); register it as a "
                    f"counter",
                    ident=f"inc-only gauge {name}",
                )
        self._gauge_ops = {}
        self._gauge_sites = {}

    # ----------------------------------------------------------------- labels
    def _check_labels(self, mod: ParsedModule, call: ast.Call, name: str
                      ) -> Iterable[Finding]:
        for kw in call.keywords:
            if kw.arg in (None, "help", "reservoir"):
                continue
            v = kw.value
            unbounded = (
                isinstance(v, ast.Subscript)
                or isinstance(v, ast.JoinedStr)
                or (isinstance(v, ast.Call) and call_name(v) in ("get", "format"))
            )
            if unbounded:
                yield self.finding(
                    "metric-label-cardinality", mod, v.lineno,
                    f"label {kw.arg}={ast.unparse(v)!r} on {name!r} is fed "
                    f"from request/payload data — label values must be "
                    f"BOUNDED (token, role, shard), or the series space "
                    f"grows until the registry/TSDB cap starves real series",
                    ident=f"label {kw.arg} on {name}",
                )
