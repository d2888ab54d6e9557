#!/usr/bin/env python
"""CI gate: the metrics reference table in docs/observability.md must
match the metric families the code actually registers.

    python scripts/check_metrics_docs.py        # exit 1 on drift

Instantiates the REAL registries — ``FrontendMetrics`` (every
``dynamo_frontend_*`` family plus the tracing span counters) and the
worker's ``EngineStatsCollector`` naming over a representative
``ForwardPassMetrics`` stats dict (including the dynamic families:
per-rung dispatch counters, KVBM tier stats, disagg transfer counters)
— and diffs the exposed names against the documented table.  New metrics
cannot land undocumented, and the doc cannot advertise series that no
longer exist.

Dynamic per-rung counters are documented with a ``{N}`` placeholder;
the checker canonicalizes live rung digits to ``{N}`` before comparing.

Import-safe: ``from check_metrics_docs import check`` — the tier-1 test
tests/test_metrics_docs.py runs exactly this.
"""

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DOC = os.path.join(ROOT, "docs", "observability.md")

# counter families whose exposed series append _total
_COUNTER_SUFFIX = {"counter"}


class _FakeExporter:
    """Stands in for a live span exporter so TracingSpanCollector yields
    its families during the check (they're absent when export is off)."""

    sent = 0
    dropped = 0

    def close(self):
        pass


def frontend_metric_names() -> set:
    """Exposed family names of a fresh FrontendMetrics registry."""
    import dynamo_tpu.runtime.tracing as tracing
    from dynamo_tpu.frontend.metrics import FrontendMetrics

    saved = tracing._EXPORTER  # noqa: SLF001
    tracing._EXPORTER = _FakeExporter()  # noqa: SLF001
    try:
        reg = FrontendMetrics().registry
        names = set()
        for fam in reg.collect():
            name = fam.name
            if fam.type in _COUNTER_SUFFIX:
                name += "_total"
            names.add(name)
        return names
    finally:
        tracing._EXPORTER = saved  # noqa: SLF001


def representative_engine_stats() -> dict:
    """A stats dict exercising every family the worker can expose:
    static ForwardPassMetrics fields, the block-ladder per-rung dynamic
    counters, sharded-pool aggregate usage, expert-model counters, KVBM tier stats, and the
    disagg decode handler's transfer counters."""
    from dynamo_tpu.engine import ForwardPassMetrics

    stats = dict(vars(ForwardPassMetrics()))
    stats["decode_rung8_dispatches_total"] = 0  # block ladder (any rung)
    # continuous-chain fall-out reasons export as ONE labeled family
    stats["decode_cc_fallout_total"] = {"admission": 0}
    stats["kv_usage_aggregate"] = 0.0           # ShardedPagePool
    # expert models (engine.metrics() when the steps carry moe stats)
    stats["moe_assignments_total"] = 0
    stats["moe_local_assignments_total"] = 0  # a share of the experts
    stats["moe_experts_hit_total"] = 0
    stats["moe_steps_total"] = 0
    for form in ("all_experts", "dispatched"):  # the steps by `moe_form`
        stats[f"moe_{form}_steps_total"] = 0
        stats[f"moe_{form}_tokens_total"] = 0
    stats["hc_res_err_ppm_max"] = 0  # a residual of several streams
    # state slots beside the pages (a model with state-space layers)
    stats["state_slots_total"] = 0
    stats["state_slots_running"] = 0
    stats["state_snapshots"] = 0
    stats["state_snapshot_stored_total"] = 0
    stats["state_snapshot_hits_total"] = 0
    stats["state_snapshot_evictions_total"] = 0
    stats["state_hit_tokens_shortened_total"] = 0
    stats["cross_rows_total"] = 0  # a decoder-hybrid-decoder's cross half
    # KVBM tiers (engine.metrics() with a connector attached)
    stats["kvbm_host_blocks"] = 0
    stats["kvbm_pending_offloads"] = 0
    stats["kvbm_inflight_offloads"] = 0
    stats["kvbm_disk_blocks"] = 0
    stats["kvbm_offload_total"] = 0
    stats["kvbm_onboard_total"] = 0
    stats["kvbm_evict_total"] = 0
    stats["kvbm_host_hits_total"] = 0
    stats["kvbm_host_misses_total"] = 0
    stats["kvbm_disk_hits_total"] = 0
    stats["kvbm_disk_misses_total"] = 0
    stats["kvbm_host_bytes"] = 0
    stats["kvbm_host_capacity_bytes"] = 0
    stats["kvbm_disk_bytes"] = 0
    # DisaggDecodeHandler.metrics() riders
    stats["kv_transfer_count"] = 0
    stats["kv_transfer_ms_total"] = 0.0
    stats["kv_transfer_bytes_total"] = 0
    stats["kv_transfer_device_count"] = 0
    stats["prefill_fallback_total"] = 0
    return stats


def worker_metric_names() -> set:
    """Exposed family names of the worker status-server registry
    (EngineStatsCollector over the representative stats + the tracing
    span counters)."""
    import dynamo_tpu.runtime.tracing as tracing
    from dynamo_tpu.analysis import leak_ledger
    from dynamo_tpu.runtime.metrics import (
        EngineStatsCollector,
        LeakLedgerCollector,
        TracingSpanCollector,
        XlaLedgerCollector,
    )

    stats = representative_engine_stats()
    names = set()
    for fam in EngineStatsCollector(lambda: stats).collect():
        name = fam.name
        if fam.type in _COUNTER_SUFFIX:
            name += "_total"
        names.add(name)
    for fam in XlaLedgerCollector().collect():
        name = fam.name
        if fam.type in _COUNTER_SUFFIX:
            name += "_total"
        names.add(name)
    # leakcheck is off by default; flip the module flag so the
    # collector's families surface for the diff (same trick as the
    # fake tracing exporter below)
    saved_on = leak_ledger._ON  # noqa: SLF001
    leak_ledger._ON = True  # noqa: SLF001
    try:
        for fam in LeakLedgerCollector().collect():
            name = fam.name
            if fam.type in _COUNTER_SUFFIX:
                name += "_total"
            names.add(name)
    finally:
        leak_ledger._ON = saved_on  # noqa: SLF001
    saved = tracing._EXPORTER  # noqa: SLF001
    tracing._EXPORTER = _FakeExporter()  # noqa: SLF001
    try:
        for fam in TracingSpanCollector().collect():
            name = fam.name
            if fam.type in _COUNTER_SUFFIX:
                name += "_total"
            names.add(name)
    finally:
        tracing._EXPORTER = saved  # noqa: SLF001
    return names


def _canonical(name: str) -> str:
    """decode_rung8_... -> decode_rung{N}_... (doc placeholder form)."""
    return re.sub(r"decode_rung\d+", "decode_rung{N}", name)


def documented_names(doc_path: str = DOC) -> set:
    """Backticked metric names from the doc's "Metrics reference"
    section (the span/event tables above it are not metric families)."""
    try:
        with open(doc_path) as f:
            text = f.read()
    except OSError:
        return set()
    marker = "## Metrics reference"
    if marker in text:
        text = text.split(marker, 1)[1]
    return {
        m.group(1)
        for m in re.finditer(r"^\|\s*`([a-zA-Z0-9_{}]+)`", text, re.M)
    }


def check(doc_path: str = DOC) -> list:
    """Returns a list of drift errors (empty = contract holds)."""
    registered = {
        _canonical(n)
        for n in (frontend_metric_names() | worker_metric_names())
    }
    documented = documented_names(doc_path)
    errors = []
    if not documented:
        return [f"no metrics table found in {doc_path}"]
    for name in sorted(registered - documented):
        errors.append(f"registered but undocumented: {name}")
    for name in sorted(documented - registered):
        errors.append(f"documented but not registered: {name}")
    return errors


def main() -> int:
    errors = check()
    if errors:
        for e in errors:
            print(e, file=sys.stderr)
        print(f"METRICS DOC DRIFT ({len(errors)} issue(s))", file=sys.stderr)
        return 1
    n = len(documented_names())
    print(f"METRICS DOC OK ({n} documented families)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
