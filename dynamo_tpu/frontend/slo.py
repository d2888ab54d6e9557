"""Live per-request SLO accounting — the frontend half of the fleet
telemetry plane.

slo_met and goodput are defined HERE, from per-request TTFT and mean
ITL, and computed live, per model, over a sliding window, for the serving
fleet's `/metrics` + `/fleet.json` surfaces (`frontend/overload.py` scores
its offline arms with the same predicate):

- a request MEETS its SLO iff ``ttft_ms <= slo.ttft_ms`` and its mean
  inter-token latency ``itl_ms <= slo.itl_ms``;
- ``goodput`` counts only tokens from SLO-met requests; ``attained``
  counts all tokens; both divide by the covered window duration.

Accounting must ride the streaming hot path, so the aggregator is
lock-light and allocation-free per request: fixed log-bucket histograms
(one int-list increment per observation) inside a ring of N-second
sub-windows that rotate in place.  The acceptance micro-bench pins
``observe()`` under 20 µs/request (tests/test_slo_window.py).

SLO targets ride the ModelDeploymentCard (``slo_ttft_ms``/``slo_itl_ms``,
set by the worker CLI) and can be overridden fleet-wide at the frontend
via ``DYN_TPU_SLO_TTFT_MS`` / ``DYN_TPU_SLO_ITL_MS``.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

from ..analysis import affine

__all__ = [
    "LogBucketHistogram",
    "SLOAccountant",
    "SLOTargets",
    "SLOWindowCollector",
    "SlidingWindow",
]

# default SLO class when neither the model card nor the environment says
# otherwise (interactive chat on an 8B-class model)
DEFAULT_TTFT_MS = 2000.0
DEFAULT_ITL_MS = 100.0


@dataclass(frozen=True)
class SLOTargets:
    """Per-model latency targets the live window scores against."""

    ttft_ms: float = DEFAULT_TTFT_MS
    itl_ms: float = DEFAULT_ITL_MS

    @staticmethod
    def from_env(base: "SLOTargets" = None) -> "SLOTargets":
        """Environment overrides win over `base` (card / defaults); a
        typo'd knob is logged and ignored WITHOUT dropping the other
        (each parses independently, lenient so the frontend boots)."""
        from ..runtime.config import env_float_lenient

        base = base or SLOTargets()
        return SLOTargets(
            ttft_ms=env_float_lenient("DYN_TPU_SLO_TTFT_MS", base.ttft_ms),
            itl_ms=env_float_lenient("DYN_TPU_SLO_ITL_MS", base.itl_ms),
        )

    @staticmethod
    def from_card(mdc) -> "SLOTargets":
        """Card-carried targets, then env overrides on top."""
        return SLOTargets.from_env(SLOTargets(
            ttft_ms=float(getattr(mdc, "slo_ttft_ms", 0) or DEFAULT_TTFT_MS),
            itl_ms=float(getattr(mdc, "slo_itl_ms", 0) or DEFAULT_ITL_MS),
        ))

    def met(self, ttft_ms: float, itl_ms: float) -> bool:
        return ttft_ms <= self.ttft_ms and itl_ms <= self.itl_ms


# log-bucket geometry: quarter-powers of two from 1 µs to ~4.7 hours (ms
# domain), 136 buckets — the same fixed-cost layout for TTFT and ITL so
# sub-window merges are a single elementwise add
_LO_MS = 1e-3
_RATIO_LOG = math.log(2.0) / 4.0
_NBUCKETS = 136
_LOG_LO = math.log(_LO_MS)


class LogBucketHistogram:
    """Fixed log-spaced latency histogram (milliseconds).

    O(1) record (one `math.log` + one list increment), mergeable by
    elementwise count addition, percentile answered at the bucket's
    geometric midpoint — so any quantile is exact to within half a bucket
    ratio (~±9%), which the oracle test pins."""

    __slots__ = ("counts", "n", "n_finite", "total_ms", "exemplars")

    def __init__(self, exemplars: bool = False):
        self.counts: List[int] = [0] * _NBUCKETS
        self.n = 0
        self.n_finite = 0
        self.total_ms = 0.0
        # forensics: one exemplar slot per occupied bucket — the WORST
        # sample's (value, summary) so tail quantiles keep an identity
        # to pivot on (trace id + waterfall).  None when unarmed: the
        # bare record() path stays allocation-free.
        self.exemplars: Optional[Dict[int, tuple]] = (
            {} if exemplars else None)

    def record(self, v_ms: float, exemplar: Optional[dict] = None) -> None:
        if not v_ms > 0.0:  # 0, negative, NaN → first bucket
            idx = 0
        elif v_ms == float("inf"):
            idx = _NBUCKETS - 1
        else:
            idx = int((math.log(v_ms) - _LOG_LO) / _RATIO_LOG)
            if idx < 0:
                idx = 0
            elif idx >= _NBUCKETS:
                idx = _NBUCKETS - 1
        self.counts[idx] += 1
        self.n += 1
        if v_ms == v_ms and v_ms != float("inf") and v_ms > 0:
            self.n_finite += 1
            self.total_ms += v_ms
        if exemplar is not None and self.exemplars is not None:
            cur = self.exemplars.get(idx)
            if cur is None or v_ms > cur[0]:
                self.exemplars[idx] = (v_ms, exemplar)

    def merge(self, other: "LogBucketHistogram") -> None:
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.n += other.n
        self.n_finite += other.n_finite
        self.total_ms += other.total_ms
        if other.exemplars:
            if self.exemplars is None:
                self.exemplars = {}
            for idx, pair in other.exemplars.items():
                cur = self.exemplars.get(idx)
                if cur is None or pair[0] > cur[0]:
                    self.exemplars[idx] = pair

    def worst_exemplars(self, n: int) -> List[tuple]:
        """Up to `n` (value_ms, summary) pairs, worst value first."""
        if not self.exemplars:
            return []
        pairs = sorted(self.exemplars.values(), key=lambda p: -p[0])
        return pairs[:n]

    @staticmethod
    def bucket_mid_ms(idx: int) -> float:
        return math.exp(_LOG_LO + (idx + 0.5) * _RATIO_LOG)

    def percentile(self, p: float) -> Optional[float]:
        """p in [0, 1] → bucket geometric midpoint (None when empty)."""
        if self.n == 0:
            return None
        rank = max(1, math.ceil(p * self.n))
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= rank:
                return self.bucket_mid_ms(i)
        return self.bucket_mid_ms(_NBUCKETS - 1)

    def mean(self) -> Optional[float]:
        """Mean over FINITE observations only — errored requests record
        at inf and must not drag the mean toward zero."""
        return self.total_ms / self.n_finite if self.n_finite else None


class _Slot:
    """One sub-window of the ring."""

    __slots__ = ("epoch", "started", "completed", "slo_ok", "tokens",
                 "tokens_ok", "prompt_tokens", "t_first", "ttft", "itl",
                 "armed")

    def __init__(self, armed: bool = False):
        self.armed = armed
        self.reset(-1)

    def reset(self, epoch: int) -> None:
        self.epoch = epoch
        self.started = 0
        self.completed = 0
        self.slo_ok = 0
        self.tokens = 0
        self.tokens_ok = 0
        self.prompt_tokens = 0
        self.t_first: Optional[float] = None
        self.ttft = LogBucketHistogram(exemplars=self.armed)
        self.itl = LogBucketHistogram(exemplars=self.armed)


class SlidingWindow:
    """Ring of ``slots`` sub-windows each covering ``window_s/slots``
    seconds; rotation is an in-place slot reset, so recording never
    allocates and never scans.  Single-writer (the event loop thread) —
    no lock on the hot path."""

    def __init__(self, window_s: float = 60.0, slots: int = 12,
                 exemplars: bool = False):
        if slots < 2:
            raise ValueError("SlidingWindow needs at least 2 slots")
        self.window_s = float(window_s)
        self.sub_s = self.window_s / slots
        self.exemplars = exemplars
        self._ring = [_Slot(armed=exemplars) for _ in range(slots)]

    def _slot(self, now: float) -> _Slot:
        epoch = int(now / self.sub_s)
        slot = self._ring[epoch % len(self._ring)]
        if slot.epoch != epoch:
            slot.reset(epoch)
        return slot

    @affine("loop")
    def mark(self, now: Optional[float] = None) -> None:
        """Anchor the covered-duration start without recording anything
        — bench pins the live window to its phase t0 so the two goodput
        denominators are the same interval, not offset by the first
        Poisson arrival wait."""
        now = time.monotonic() if now is None else now
        slot = self._slot(now)
        if slot.t_first is None:
            slot.t_first = now

    @affine("loop")
    def record_start(self, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        slot = self._slot(now)
        slot.started += 1
        if slot.t_first is None:
            slot.t_first = now

    @affine("loop")
    def record(self, ttft_ms: float, itl_ms: float, output_tokens: int,
               slo_ok: bool, prompt_tokens: int = 0,
               now: Optional[float] = None,
               exemplar: Optional[dict] = None) -> None:
        now = time.monotonic() if now is None else now
        slot = self._slot(now)
        if slot.t_first is None:
            slot.t_first = now
        slot.completed += 1
        slot.tokens += output_tokens
        slot.prompt_tokens += prompt_tokens
        if slo_ok:
            slot.slo_ok += 1
            slot.tokens_ok += output_tokens
        slot.ttft.record(ttft_ms, exemplar)
        slot.itl.record(itl_ms, exemplar)

    def snapshot(self, now: Optional[float] = None) -> dict:
        """Merge the still-valid slots into one window summary.  Rates
        divide by the COVERED duration (first record in the window →
        now), so a short burst doesn't get diluted by empty slots."""
        now = time.monotonic() if now is None else now
        cur = int(now / self.sub_s)
        lo = cur - len(self._ring) + 1
        ttft, itl = LogBucketHistogram(), LogBucketHistogram()
        started = completed = ok = tokens = tokens_ok = ptokens = 0
        t_first = None
        for slot in self._ring:
            if not (lo <= slot.epoch <= cur):
                continue
            started += slot.started
            completed += slot.completed
            ok += slot.slo_ok
            tokens += slot.tokens
            tokens_ok += slot.tokens_ok
            ptokens += slot.prompt_tokens
            ttft.merge(slot.ttft)
            itl.merge(slot.itl)
            if slot.t_first is not None:
                t_first = (slot.t_first if t_first is None
                           else min(t_first, slot.t_first))
        duration = max(now - t_first, 1e-6) if t_first is not None else 0.0

        def dist(h: LogBucketHistogram) -> dict:
            return {
                "p50_ms": h.percentile(0.50),
                "p95_ms": h.percentile(0.95),
                "p99_ms": h.percentile(0.99),
                "mean_ms": h.mean(),
            }

        out = {
            "window_s": round(duration, 3),
            "requests_started": started,
            "requests_completed": completed,
            "slo_met": (ok / completed) if completed else None,
            "goodput_tok_s": (tokens_ok / duration) if duration else 0.0,
            "attained_tok_s": (tokens / duration) if duration else 0.0,
            "prompt_tok_s": (ptokens / duration) if duration else 0.0,
            "offered_rps": (started / duration) if duration else 0.0,
            "completed_rps": (completed / duration) if duration else 0.0,
            "ttft": dist(ttft),
            "itl": dist(itl),
        }
        if self.exemplars:
            # tail forensics: the worst windowed requests WITH identity
            # (trace id + waterfall summary), so a p99 number pivots to
            # a concrete request instead of staying anonymous
            out["tail"] = self._tail_from(ttft, itl, 3)
        return out

    @staticmethod
    def _tail_from(ttft: LogBucketHistogram, itl: LogBucketHistogram,
                   n: int) -> List[dict]:
        """N worst exemplar summaries across the merged ttft+itl bucket
        slots, deduped by trace id, ranked by end-to-end duration (falls
        back to the observed value for summaries without one)."""
        best: Dict[str, tuple] = {}
        for v, ex in (ttft.worst_exemplars(4 * n)
                      + itl.worst_exemplars(4 * n)):
            key = str(ex.get("trace_id", id(ex)))
            rank = float(ex.get("total_ms") or v)
            cur = best.get(key)
            if cur is None or rank > cur[0]:
                best[key] = (rank, ex)
        ranked = sorted(best.values(), key=lambda p: -p[0])
        return [ex for _, ex in ranked[:n]]

    def tail(self, n: int = 10, now: Optional[float] = None) -> List[dict]:
        """The window's N worst requests (exemplar summaries)."""
        if not self.exemplars:
            return []
        now = time.monotonic() if now is None else now
        cur = int(now / self.sub_s)
        lo = cur - len(self._ring) + 1
        ttft, itl = LogBucketHistogram(True), LogBucketHistogram(True)
        for slot in self._ring:
            if lo <= slot.epoch <= cur:
                ttft.merge(slot.ttft)
                itl.merge(slot.itl)
        return self._tail_from(ttft, itl, n)


class SLOAccountant:
    """Per-model SLO targets + sliding windows; the one object the
    frontend streams account into and every telemetry surface reads
    (`/metrics` via SLOWindowCollector, `/fleet.json`, the telemetry
    publisher)."""

    def __init__(self, window_s: float = 60.0, slots: int = 12,
                 default: Optional[SLOTargets] = None,
                 exemplars: bool = False):
        self.window_s = window_s
        self.slots = slots
        self.default = SLOTargets.from_env(default)
        # arm per-model windows with exemplar slots (tail forensics);
        # class windows stay bare — the tail surface is per-model
        self.exemplars = exemplars
        self.targets: Dict[str, SLOTargets] = {}
        self.windows: Dict[str, SlidingWindow] = {}
        # per-(model, priority-class) windows (overload control): same
        # definitions as the model window, split so the interactive
        # class's slo_met is visible while batch absorbs overload loss
        self.class_windows: Dict[tuple, SlidingWindow] = {}

    def set_targets(self, model: str, targets: SLOTargets) -> None:
        self.targets[model] = targets

    def targets_for(self, model: str) -> SLOTargets:
        return self.targets.get(model, self.default)

    def window(self, model: str) -> SlidingWindow:
        win = self.windows.get(model)
        if win is None:
            win = self.windows[model] = SlidingWindow(
                self.window_s, self.slots, exemplars=self.exemplars)
        return win

    def class_window(self, model: str, priority: str) -> SlidingWindow:
        key = (model, priority)
        win = self.class_windows.get(key)
        if win is None:
            win = self.class_windows[key] = SlidingWindow(self.window_s,
                                                          self.slots)
        return win

    def observe_start(self, model: str, now: Optional[float] = None,
                      priority: Optional[str] = None) -> None:
        self.window(model).record_start(now)
        if priority:
            self.class_window(model, priority).record_start(now)

    def observe(self, model: str, ttft_ms: float, itl_ms: float,
                output_tokens: int, prompt_tokens: int = 0,
                now: Optional[float] = None,
                priority: Optional[str] = None,
                exemplar: Optional[dict] = None) -> bool:
        """Account one COMPLETED request; returns whether it met its SLO
        (TTFT and mean ITL both under the target).  When a
        `priority` class is given the request ALSO lands in that class's
        window — the model window keeps scoring every request, so the
        existing surfaces don't change.  `exemplar` (a waterfall summary
        with a trace id) lands in the model window's bucket slots for
        the tail-forensics surfaces."""
        ok = self.targets_for(model).met(ttft_ms, itl_ms)
        self.window(model).record(ttft_ms, itl_ms, output_tokens, ok,
                                  prompt_tokens, now, exemplar=exemplar)
        if priority:
            self.class_window(model, priority).record(
                ttft_ms, itl_ms, output_tokens, ok, prompt_tokens, now)
        return ok

    def tail(self, n: int = 10,
             now: Optional[float] = None) -> Dict[str, List[dict]]:
        """Per-model N worst windowed requests (exemplar summaries) —
        the `/debug/tail.json` payload."""
        return {model: win.tail(n, now)
                for model, win in self.windows.items()}

    def observe_stream(self, model: str, *, t0: float,
                       t_first: Optional[float],
                       t_last_tok: Optional[float], ntokens: int,
                       n_choices: int, errored: bool,
                       prompt_tokens: int = 0,
                       priority: Optional[str] = None,
                       exemplar: Optional[dict] = None) -> bool:
        """Score one streamed HTTP request from its raw timestamps —
        the post-hoc half of the delivery loop's accounting (the loop
        only collects monotonic stamps; the TTFT/ITL math happens here,
        off the write path).

        A stream the client saw fail (or that never produced a token)
        scores at infinite latency: incidents must drag slo_met down
        while delivered tokens still count as attained.  n>1 choices
        stream concurrently, so per-STREAM ITL is the span over ONE
        choice's share of the tokens — dividing by the total would
        dilute a breach by ~n."""
        inf = float("inf")
        bad = errored or t_first is None
        return self.observe(
            model,
            ttft_ms=inf if bad else (t_first - t0) * 1e3,
            itl_ms=(inf if bad
                    else (t_last_tok - t_first)
                    / max(ntokens / max(n_choices, 1) - 1, 1) * 1e3),
            output_tokens=ntokens,
            prompt_tokens=prompt_tokens,
            priority=priority,
            exemplar=exemplar,
        )

    def snapshot(self, now: Optional[float] = None) -> Dict[str, dict]:
        out = {}
        for model, win in self.windows.items():
            slo = self.targets_for(model)
            out[model] = {
                **win.snapshot(now),
                "slo": {"ttft_ms": slo.ttft_ms, "itl_ms": slo.itl_ms},
            }
        for (model, priority), win in self.class_windows.items():
            if model in out:
                out[model].setdefault("classes", {})[priority] = \
                    win.snapshot(now)
        return out


class SLOWindowCollector:
    """Prometheus custom collector over a live SLOAccountant: the window
    summaries become gauges at scrape time (no double bookkeeping with
    the request-path accounting).  Families are always yielded (with no
    samples before traffic) so the docs contract sees them."""

    _QUANTS = (("p50_ms", "0.5"), ("p95_ms", "0.95"), ("p99_ms", "0.99"))

    def __init__(self, accountant: SLOAccountant):
        self.accountant = accountant

    def collect(self):
        from prometheus_client.core import GaugeMetricFamily

        slo_met = GaugeMetricFamily(
            "dynamo_frontend_slo_met_ratio",
            "Fraction of windowed requests meeting their TTFT+ITL SLO",
            labels=["model"])
        goodput = GaugeMetricFamily(
            "dynamo_frontend_goodput_tokens_per_second",
            "Windowed output tok/s from SLO-met requests",
            labels=["model"])
        attained = GaugeMetricFamily(
            "dynamo_frontend_attained_tokens_per_second",
            "Windowed output tok/s from all requests",
            labels=["model"])
        offered = GaugeMetricFamily(
            "dynamo_frontend_offered_requests_per_second",
            "Windowed request arrival rate",
            labels=["model"])
        ttft = GaugeMetricFamily(
            "dynamo_frontend_window_ttft_seconds",
            "Windowed TTFT quantiles (live log-bucket window)",
            labels=["model", "quantile"])
        itl = GaugeMetricFamily(
            "dynamo_frontend_window_itl_seconds",
            "Windowed mean-ITL quantiles (live log-bucket window)",
            labels=["model", "quantile"])
        # per-priority-class split of the same window definitions
        # (overload control) — NEW families, so the unlabeled per-model
        # ones above never change shape
        c_slo = GaugeMetricFamily(
            "dynamo_frontend_class_slo_met_ratio",
            "Per-priority-class fraction of windowed requests meeting SLO",
            labels=["model", "priority"])
        c_goodput = GaugeMetricFamily(
            "dynamo_frontend_class_goodput_tokens_per_second",
            "Per-priority-class windowed output tok/s from SLO-met requests",
            labels=["model", "priority"])
        c_attained = GaugeMetricFamily(
            "dynamo_frontend_class_attained_tokens_per_second",
            "Per-priority-class windowed output tok/s from all requests",
            labels=["model", "priority"])
        c_offered = GaugeMetricFamily(
            "dynamo_frontend_class_offered_requests_per_second",
            "Per-priority-class windowed request arrival rate",
            labels=["model", "priority"])
        try:
            snap = self.accountant.snapshot()
        except Exception:  # noqa: BLE001 — a scrape must not break /metrics
            snap = {}
        for model, s in snap.items():
            if s["slo_met"] is not None:
                slo_met.add_metric([model], s["slo_met"])
            goodput.add_metric([model], s["goodput_tok_s"])
            attained.add_metric([model], s["attained_tok_s"])
            offered.add_metric([model], s["offered_rps"])
            for key, q in self._QUANTS:
                if s["ttft"][key] is not None:
                    ttft.add_metric([model, q], s["ttft"][key] / 1e3)
                if s["itl"][key] is not None:
                    itl.add_metric([model, q], s["itl"][key] / 1e3)
            for priority, cs in (s.get("classes") or {}).items():
                if cs["slo_met"] is not None:
                    c_slo.add_metric([model, priority], cs["slo_met"])
                c_goodput.add_metric([model, priority], cs["goodput_tok_s"])
                c_attained.add_metric([model, priority], cs["attained_tok_s"])
                c_offered.add_metric([model, priority], cs["offered_rps"])
        return [slo_met, goodput, attained, offered, ttft, itl,
                c_slo, c_goodput, c_attained, c_offered]
