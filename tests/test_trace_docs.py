"""Tracing contract: every span name and step-event kind the code emits
must match the Span map / Engine step-event schema tables in
docs/observability.md (scripts/check_trace_docs.py — wired here as a
tier-1 gate so new spans and event kinds can't land undocumented)."""

import os
import sys

SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"
)
if SCRIPTS not in sys.path:
    sys.path.insert(0, SCRIPTS)

from check_trace_docs import (  # noqa: E402
    DOC,
    check,
    documented_event_kinds,
    documented_span_names,
    emitted_event_kinds,
    emitted_span_names,
)


def test_no_drift():
    assert check() == []


def test_emitters_enumerate_known_names():
    spans = emitted_span_names()
    # the f-string site expands to the two OpenAI endpoints
    assert {"http.chat", "http.completion"} <= spans
    assert {"engine.prefill", "engine.decode", "kvbm.offload",
            "kvbm.onboard", "service.call", "service.handle",
            "router.schedule", "migration.reissue"} <= spans
    assert not any(n.startswith("<dynamic") for n in spans)
    kinds = emitted_event_kinds()
    assert {"admit", "plan", "loop_yield", "first_token", "decode_block",
            "decode_chain",
            "spec_round", "kvbm_offload", "kvbm_onboard"} <= kinds
    assert not any(k.startswith("<dynamic") for k in kinds)


def test_doc_tables_parse_and_expand_braces():
    spans = documented_span_names()
    assert "http.chat" in spans and "http.completion" in spans
    assert "http.{chat,completion}" not in spans
    kinds = documented_event_kinds()
    assert "decode_block" in kinds
    # the two tables must not bleed into each other or into metrics
    assert not any(k.startswith("dynamo_") for k in spans | kinds)


def test_drift_detected_both_directions(tmp_path):
    """Removing a documented span/kind OR documenting a ghost one
    fails."""
    with open(DOC) as f:
        text = f.read()
    assert "| `engine.decode` |" in text
    assert "| `spec_round` |" in text
    mutated = (
        text
        .replace("| `spec_round` | slice | `k`, `batch`, `drafted`, "
                 "`accepted`, step attributes without `attn` |\n", "")
        .replace("## Span map\n",
                 "## Span map\n\n| Span | Emitted by | Attributes |\n"
                 "|---|---|---|\n| `ghost.span` | nobody | |\n")
    )
    doc = tmp_path / "observability.md"
    doc.write_text(mutated)
    errors = check(str(doc))
    assert any("undocumented: spec_round" in e for e in errors)
    assert any("never emitted: ghost.span" in e for e in errors)
