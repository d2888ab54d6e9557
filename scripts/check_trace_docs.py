#!/usr/bin/env python
"""CI gate: the span map and step-event schema tables in
docs/observability.md must match what the code actually emits.

    python scripts/check_trace_docs.py        # exit 1 on drift

Two contracts, both diffed in BOTH directions:

- **Span names** — every literal first argument of ``span(...)`` /
  ``export_span(...)`` in the package vs the "## Span map" table.  The
  one non-literal site, ``span(f"http.{kind}", ...)``, serves the two
  OpenAI endpoints; it is expanded to ``http.chat`` / ``http.completion``
  and the doc's ``http.{chat,completion}`` brace form is expanded the
  same way.
- **Step-event kinds** — every literal first argument of
  ``<...>events.record("kind", ...)`` and of ``host_event("kind", ...)``
  (the host's rare, long events: runtime/events.py) vs the "## Engine
  step-event schema" table.  (Other ``.record(...)`` receivers — SLO windows,
  latency histograms — take numbers, not kinds, and are skipped by the
  receiver-name filter.)

New spans/kinds cannot land undocumented, and the doc cannot advertise
ones the code no longer emits.

Import-safe: ``from check_trace_docs import check`` — the tier-1 test
tests/test_trace_docs.py runs exactly this.  Pure AST walk: nothing in
the package is imported or executed.
"""

import ast
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DOC = os.path.join(ROOT, "docs", "observability.md")
PKG = os.path.join(ROOT, "dynamo_tpu")

_SPAN_FNS = {"span", "export_span"}

# the single parameterized span site: span(f"http.{kind}") in the
# frontend's _serve, fanned out over its two endpoints
_HTTP_KINDS = ("chat", "completion")


def _call_name(call: ast.Call) -> str:
    fn = call.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return ""


def _receiver_chain(call: ast.Call) -> str:
    """Dotted receiver of an attribute call: self.events.record ->
    "self.events"."""
    fn = call.func
    if not isinstance(fn, ast.Attribute):
        return ""
    parts = []
    node = fn.value
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _python_files(root: str = PKG):
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def emitted_span_names(root: str = PKG) -> set:
    """Every span name the package can emit."""
    names = set()
    for path in _python_files(root):
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            # modules that import lazily alias as _span / _export_span
            if _call_name(node).lstrip("_") not in _SPAN_FNS:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                names.add(arg.value)
            elif isinstance(arg, ast.JoinedStr):
                # f"http.{kind}" — the literal prefix identifies it
                head = arg.values[0] if arg.values else None
                if (isinstance(head, ast.Constant)
                        and head.value == "http."):
                    names.update(f"http.{k}" for k in _HTTP_KINDS)
                else:
                    names.add(f"<dynamic span in {path}:{arg.lineno}>")
    return names


def emitted_event_kinds(root: str = PKG) -> set:
    """Every step-event kind the package can record: literal first args
    of ``record()`` calls whose receiver chain ends in ``events``, and of
    ``host_event()`` calls."""
    kinds = set()
    for path in _python_files(root):
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            name = _call_name(node)
            if name == "record":
                recv = _receiver_chain(node)
                if not recv.split(".")[-1].endswith("events"):
                    continue
            elif name != "host_event":  # runtime/events.py: the host's
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                kinds.add(arg.value)
            else:
                kinds.add(f"<dynamic kind in {path}:{node.lineno}>")
    return kinds


def _table_names(text: str, marker: str) -> set:
    """Backticked first-column names of the table under `marker`,
    stopping at the next section."""
    if marker not in text:
        return set()
    section = text.split(marker, 1)[1]
    nxt = re.search(r"^## ", section, re.M)
    if nxt:
        section = section[: nxt.start()]
    names = set()
    for m in re.finditer(r"^\|\s*`([^`]+)`", section, re.M):
        name = m.group(1)
        brace = re.fullmatch(r"([\w.]*)\{([\w,]+)\}([\w.]*)", name)
        if brace:  # http.{chat,completion} -> http.chat, http.completion
            for alt in brace.group(2).split(","):
                names.add(brace.group(1) + alt + brace.group(3))
        else:
            names.add(name)
    return names


def documented_span_names(doc_path: str = DOC) -> set:
    try:
        with open(doc_path) as f:
            return _table_names(f.read(), "## Span map")
    except OSError:
        return set()


def documented_event_kinds(doc_path: str = DOC) -> set:
    try:
        with open(doc_path) as f:
            return _table_names(f.read(), "## Engine step-event schema")
    except OSError:
        return set()


def check(doc_path: str = DOC, root: str = PKG) -> list:
    """Returns a list of drift errors (empty = contract holds)."""
    errors = []
    doc_spans = documented_span_names(doc_path)
    doc_kinds = documented_event_kinds(doc_path)
    if not doc_spans:
        return [f"no span map table found in {doc_path}"]
    if not doc_kinds:
        return [f"no step-event schema table found in {doc_path}"]
    code_spans = emitted_span_names(root)
    code_kinds = emitted_event_kinds(root)
    for n in sorted(code_spans - doc_spans):
        errors.append(f"span emitted but undocumented: {n}")
    for n in sorted(doc_spans - code_spans):
        errors.append(f"span documented but never emitted: {n}")
    for n in sorted(code_kinds - doc_kinds):
        errors.append(f"event kind recorded but undocumented: {n}")
    for n in sorted(doc_kinds - code_kinds):
        errors.append(f"event kind documented but never recorded: {n}")
    return errors


def main() -> int:
    errors = check()
    if errors:
        for e in errors:
            print(e, file=sys.stderr)
        print(f"TRACE DOC DRIFT ({len(errors)} issue(s))", file=sys.stderr)
        return 1
    print(
        f"TRACE DOC OK ({len(documented_span_names())} spans, "
        f"{len(documented_event_kinds())} event kinds)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
