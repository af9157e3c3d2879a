"""Checker: metrics discipline (naming, registration, label hygiene).

Three rules over every metric the package declares — AST-level, so the
sweep needs no imports and covers modules the runtime naming lint in
``tests/test_observability.py`` used to reach only via a hand-grown
module list:

- ``metric-naming`` — any ``.counter(...)`` / ``.gauge(...)`` /
  ``.histogram(...)`` call with a literal name: snake_case
  everywhere, counters end ``_total``, histograms carry a unit suffix
  (``_seconds`` / ``_bytes`` / ``_size``), gauges are bare nouns (no
  ``_total``), label names snake_case.
- ``metric-registry`` — package modules outside ``observability/``
  must not construct ``Counter``/``Gauge``/``Histogram`` directly:
  registration goes through ``observability.REGISTRY`` (or an
  explicit per-node ``Registry()``, which stays allowed — federation
  depends on it).
- ``metric-labels`` — a ``.labels(...)`` value built from an f-string,
  ``%``-formatting, ``str.format`` or ``str(...)`` conversion, or a
  bare name that smells like a peer identity (``peer``/``addr``/
  ``host``), risks unbounded cardinality: peer-shaped values must go
  through ``peer_bucket`` / ``peer_bucket_label``
  (docs/observability.md).
"""

from __future__ import annotations

import ast
import re

from ..core import FileCtx, Finding, call_name, str_const

_SNAKE = re.compile(r"^[a-z][a-z0-9_]*$")
_HISTOGRAM_UNITS = ("_seconds", "_size", "_bytes", "_steps")
_FACTORIES = ("counter", "gauge", "histogram")
_CONSTRUCTORS = ("Counter", "Gauge", "Histogram")
_PEERISH = frozenset({"peer", "peers", "addr", "address", "host",
                      "hostport", "remote", "ip"})
_BUCKET_FNS = ("peer_bucket", "peer_bucket_label")


class MetricsChecker:
    name = "metrics"
    rules = ("metric-naming", "metric-registry", "metric-labels")

    def check_file(self, ctx: FileCtx):
        out: list[Finding] = []
        in_obs = ctx.top_dir == "observability"
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            last = name.rsplit(".", 1)[-1]
            if isinstance(node.func, ast.Attribute) and \
                    last in _FACTORIES:
                self._check_naming(ctx, node, last, out)
            elif isinstance(node.func, ast.Name) and \
                    last in _CONSTRUCTORS and not in_obs and \
                    ctx.relpath.startswith("pybitmessage_tpu/"):
                if str_const(node.args[0] if node.args else None) \
                        is not None:
                    out.append(ctx.finding(
                        "metric-registry", node,
                        "%s constructed directly — register through "
                        "observability.REGISTRY so /metrics and the "
                        "naming gate see it" % last))
            elif isinstance(node.func, ast.Attribute) and \
                    last == "labels":
                self._check_labels(ctx, node, out)
        return out

    def finish(self):
        return ()

    # -- naming --------------------------------------------------------------

    def _check_naming(self, ctx: FileCtx, node: ast.Call, kind: str,
                      out: list[Finding]) -> None:
        mname = str_const(node.args[0] if node.args else None)
        if mname is None:
            return      # dynamic name: not statically checkable
        problems: list[str] = []
        if not _SNAKE.match(mname):
            problems.append("not snake_case")
        if kind == "counter" and not mname.endswith("_total"):
            problems.append("counter must end _total")
        if kind == "histogram" and \
                not mname.endswith(_HISTOGRAM_UNITS):
            problems.append("histogram needs a unit suffix "
                            "(_seconds/_bytes/_size/_steps)")
        if kind == "gauge" and mname.endswith("_total"):
            problems.append("gauge must not end _total")
        for ln in self._label_names(node):
            if not _SNAKE.match(ln):
                problems.append("label %r not snake_case" % ln)
        if problems:
            out.append(ctx.finding(
                "metric-naming", node,
                "metric %r: %s (docs/observability.md conventions)"
                % (mname, "; ".join(problems))))

    def _label_names(self, node: ast.Call) -> list[str]:
        cand = None
        if len(node.args) >= 3:
            cand = node.args[2]
        for kw in node.keywords:
            if kw.arg == "labelnames":
                cand = kw.value
        if isinstance(cand, (ast.Tuple, ast.List)):
            return [v for v in (str_const(e) for e in cand.elts)
                    if v is not None]
        return []

    # -- label-value cardinality ---------------------------------------------

    def _check_labels(self, ctx: FileCtx, node: ast.Call,
                      out: list[Finding]) -> None:
        for kw in node.keywords:
            if kw.arg is None:
                continue
            bad = self._risky_value(kw.value)
            if bad:
                out.append(ctx.finding(
                    "metric-labels", node,
                    "label %r value is %s — unbounded label "
                    "cardinality; peer-shaped values go through "
                    "peer_bucket (docs/observability.md)"
                    % (kw.arg, bad)))

    def _risky_value(self, value: ast.AST) -> str | None:
        for sub in ast.walk(value):
            if isinstance(sub, ast.Call):
                sname = call_name(sub).rsplit(".", 1)[-1]
                if sname in _BUCKET_FNS:
                    return None     # explicitly bucketed: fine
        if isinstance(value, ast.JoinedStr):
            return "an f-string"
        if isinstance(value, ast.BinOp) and \
                isinstance(value.op, ast.Mod) and \
                (isinstance(value.left, ast.Constant) and
                 isinstance(value.left.value, str)):
            return "%-formatted"
        if isinstance(value, ast.Call):
            sname = call_name(value).rsplit(".", 1)[-1]
            if sname == "format":
                return "str.format-built"
            if sname == "str":
                return "a str(...) conversion"
        if isinstance(value, ast.Name) and \
                value.id.lower() in _PEERISH:
            return "a raw peer identity"
        if isinstance(value, ast.Attribute) and \
                value.attr.lower() in _PEERISH:
            return "a raw peer identity"
        return None
