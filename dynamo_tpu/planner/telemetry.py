"""Fleet telemetry aggregator: the planner's live sensors.

A HealthWatcher-style watcher over the control-plane ``/telemetry/{ns}/``
prefix (written by each process's
:class:`~dynamo_tpu.runtime.metrics.TelemetryPublisher`, lease-scoped)
that joins the two telemetry families into one :class:`FleetSnapshot`:

- **frontend windows** (``component == "frontend"``): per-model live
  slo_met / goodput / offered rate / TTFT+ITL quantiles, merged across
  frontends (rates sum; ratios and quantiles weight by completed
  requests);
- **worker capacity snapshots**: queue depth, batch occupancy, page-pool
  utilization + watermark headroom, per-rung dispatch rates, decode-cc
  host gap, spec acceptance.

Staleness is surfaced, never hidden: an entry whose publisher missed
``stale_factor × interval_s`` — or whose key was deleted/forgotten (lease
expiry, partition reconcile) — stays in the snapshot **marked stale**
with its age, so consumers can distinguish "worker gone/unreachable"
from "worker idle" (the chaos kill/partition scenario asserts exactly
this).

On top of the join, :meth:`FleetTelemetryWatcher.sample` runs the online
estimators the SLA planner consumes:

- **knee estimation**: a rolling fit of offered rate vs slo_met per
  model → ``knee_rate_rps`` (the top of the contiguous prefix of rate bins that
  pass, computed from live windows);
- **observed PerfProfile**: (per-worker prefill load, TTFT p95) and
  (per-worker decode concurrency, ITL p95) observations accumulated into
  the monotone curves :class:`~dynamo_tpu.planner.perf_model.PerfProfile`
  interpolates — so ``Planner.plan_once()`` sizes replicas from measured
  live data, no ``synthetic_profile()`` anywhere in the loop;
- **LoadSample adaptation**: the current joined state as a
  :class:`~dynamo_tpu.planner.core.LoadSample` for ``Planner.observe()``
  (via :class:`TelemetryConnector.collect_load`).
"""

from __future__ import annotations

import asyncio
import logging
import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ..runtime.metrics import TELEMETRY_ROOT
from ..runtime.transport.wire import unpack
from .core import LoadSample
from .perf_model import PerfProfile

logger = logging.getLogger(__name__)

# quantile the observed profiles score latency at (tail-sensitive but
# stable at tier-1 sample counts)
_PROFILE_Q = "p95_ms"


@dataclass
class FleetSnapshot:
    """One joined view of the fleet at a point in time."""

    ts: float
    models: Dict[str, dict] = field(default_factory=dict)
    workers: Dict[str, dict] = field(default_factory=dict)
    knees: Dict[str, Optional[float]] = field(default_factory=dict)

    def fresh_workers(self, model: Optional[str] = None) -> Dict[str, dict]:
        return {
            k: w for k, w in self.workers.items()
            if not w.get("stale")
            and (model is None or w.get("model") in (None, model))
        }

    def to_dict(self) -> dict:
        return {
            "ts": self.ts,
            "models": self.models,
            "workers": self.workers,
            "knees": self.knees,
        }


class KneeEstimator:
    """Online knee fit over (offered rate, slo_met) observations.

    Samples bin into geometric rate buckets; the knee is the top of the
    CONTIGUOUS prefix of bins whose weighted slo_met clears the
    threshold: a bin passes only if every lower rate passed too, so one
    lucky window above the knee does not move it."""

    def __init__(self, threshold: float = 0.9, maxlen: int = 512,
                 bin_ratio: float = 1.25):
        self.threshold = threshold
        self._log_ratio = math.log(bin_ratio)
        self.samples: deque = deque(maxlen=maxlen)

    def add(self, rate_rps: float, slo_met: float,
            weight: float = 1.0) -> None:
        if rate_rps > 0 and weight > 0 and slo_met == slo_met:
            self.samples.append((float(rate_rps), float(slo_met),
                                 float(weight)))

    def estimate(self) -> Optional[float]:
        if not self.samples:
            return None
        bins: Dict[int, List[float]] = {}  # idx -> [w_sum, met_w, rate_w]
        for rate, met, w in self.samples:
            idx = int(round(math.log(rate) / self._log_ratio))
            b = bins.setdefault(idx, [0.0, 0.0, 0.0])
            b[0] += w
            b[1] += met * w
            b[2] += rate * w
        knee = None
        for idx in sorted(bins):
            w_sum, met_w, rate_w = bins[idx]
            if met_w / w_sum >= self.threshold:
                knee = rate_w / w_sum  # weighted mean rate in the bin
            else:
                break  # contiguous prefix only
        return knee


class _ProfileBuilder:
    """Accumulates (load, latency[, throughput]) observations and emits
    the monotone arrays PerfProfile interpolates (sort by load, running
    max on latency so queueing noise can't make the curve non-causal)."""

    def __init__(self, maxlen: int = 256, min_points: int = 3):
        self.min_points = min_points
        self.obs: deque = deque(maxlen=maxlen)

    def add(self, load: float, latency_s: float,
            throughput: float = 0.0) -> None:
        if load > 0 and latency_s > 0:
            self.obs.append((float(load), float(latency_s),
                             float(throughput)))

    def curves(self) -> Optional[Tuple[List[float], List[float], List[float]]]:
        if not self.obs:
            return None
        by_load: Dict[float, List[float]] = {}
        for load, lat, thpt in self.obs:
            key = round(load, 6)
            cur = by_load.setdefault(key, [0.0, 0.0])
            cur[0] = max(cur[0], lat)
            cur[1] = max(cur[1], thpt)
        if len(by_load) < self.min_points:
            return None
        xs = sorted(by_load)
        ys, ts = [], []
        run = 0.0
        for x in xs:
            run = max(run, by_load[x][0])
            ys.append(run)
            ts.append(by_load[x][1])
        return xs, ys, ts


class FleetTelemetryWatcher:
    """Joins ``/telemetry`` KV entries into FleetSnapshots and runs the
    online estimators.  ``start()`` begins the watch; ``sample()`` (or
    the optional ``start_sampling`` loop) takes a snapshot AND feeds the
    knee/profile estimators + the counter-track history."""

    def __init__(self, runtime, namespace: str = "dynamo",
                 stale_factor: float = 2.5, default_interval: float = 2.0,
                 knee_threshold: float = 0.9, history: int = 1024,
                 retention_s: float = 120.0):
        self.runtime = runtime
        self.namespace = namespace
        self.stale_factor = stale_factor
        self.default_interval = default_interval
        self.knee_threshold = knee_threshold
        # stale entries are RETAINED (marked) so consumers can see the
        # last-known state of a dead worker — but not forever: past this
        # horizon they prune, or a long-lived frontend would accumulate
        # one corpse per worker respawn (each lease is a new key)
        self.retention_s = retention_s
        # key -> {"payload": dict, "received": mono_s, "deleted": bool}
        self.entries: Dict[str, dict] = {}
        # last seq seen for keys we PRUNED whose KV key may still exist:
        # a later watch-reconnect replay of that unchanged seq must not
        # resurrect the payload as fresh (bounded — oldest forgotten)
        from collections import OrderedDict

        self._pruned_seqs: "OrderedDict[str, object]" = OrderedDict()
        self.knee_estimators: Dict[str, KneeEstimator] = {}
        self._prefill_obs: Dict[str, _ProfileBuilder] = {}
        self._decode_obs: Dict[str, _ProfileBuilder] = {}
        self.history: deque = deque(maxlen=history)
        self._task: Optional[asyncio.Task] = None
        self._sample_task: Optional[asyncio.Task] = None
        self._synced = asyncio.Event()

    # -- watch --------------------------------------------------------------- #

    async def start(self) -> "FleetTelemetryWatcher":
        self._task = asyncio.get_running_loop().create_task(self._watch())
        return self

    def start_sampling(self, period_s: float = 2.0) -> "FleetTelemetryWatcher":
        async def loop():
            while True:
                try:
                    self.sample()
                except Exception:  # noqa: BLE001
                    logger.exception("fleet sample failed")
                await asyncio.sleep(period_s)

        self._sample_task = asyncio.get_running_loop().create_task(loop())
        return self

    async def stop(self) -> None:
        for task in (self._task, self._sample_task):
            if task:
                task.cancel()
                await asyncio.gather(task, return_exceptions=True)

    async def wait_synced(self, timeout: float = 10.0) -> None:
        await asyncio.wait_for(self._synced.wait(), timeout)

    async def _watch(self) -> None:
        from ..runtime.transport.control_plane import watch_resilient

        prefix = f"{TELEMETRY_ROOT}/{self.namespace}/"
        async for ev in watch_resilient(self.runtime.control, prefix,
                                        "telemetry"):
            if ev.type == "sync":
                self._synced.set()
            elif ev.type == "put":
                try:
                    payload = unpack(ev.value)
                except Exception:  # noqa: BLE001 — skip torn payloads
                    continue
                if not isinstance(payload, dict):
                    continue
                self._on_put(ev.key, payload)
            elif ev.type in ("delete", "forget"):
                # mark stale, NEVER drop: the last-known capacity of a
                # dead/partitioned worker stays visible with its
                # staleness surfaced (chaos asserts this)
                entry = self.entries.get(ev.key)
                if entry is not None:
                    entry["deleted"] = True

    # -- join ---------------------------------------------------------------- #

    def _on_put(self, key: str, payload: dict) -> None:
        """Record a put; a watch reconnect replays every surviving key,
        which must NOT refresh a long-dead publisher's payload — an
        unchanged seq keeps the ORIGINAL receipt time so its age keeps
        growing.  (Comparing the payload's wall-clock ts to ours would
        also catch this, but cross-host clock skew would then mark
        healthy workers permanently stale; seq comparison is skew-free.)"""
        prev = self.entries.get(key)
        received = time.monotonic()
        seq = payload.get("seq")
        if (prev is not None and seq is not None
                and seq == prev["payload"].get("seq")):
            received = prev["received"]
        elif seq is not None and seq == self._pruned_seqs.get(key):
            # replay of a payload we already aged out: immediately stale
            received -= self.retention_s
        self.entries[key] = {
            "payload": payload,
            "received": received,
            "deleted": False,
        }

    def _is_stale(self, entry: dict, now_mono: float) -> Tuple[bool, float]:
        age = now_mono - entry["received"]
        interval = float(entry["payload"].get("interval_s")
                         or self.default_interval)
        return (entry["deleted"]
                or age > self.stale_factor * interval), age

    @staticmethod
    def _merge_windows(windows: List[dict]) -> dict:
        """Merge one model's windows across frontends: rates/counts sum,
        ratios and quantiles weight by completed requests."""
        if len(windows) == 1:
            return dict(windows[0])
        out: dict = {}
        for key in ("goodput_tok_s", "attained_tok_s", "prompt_tok_s",
                    "offered_rps", "completed_rps"):
            out[key] = sum(w.get(key) or 0.0 for w in windows)
        for key in ("requests_started", "requests_completed"):
            out[key] = sum(w.get(key) or 0 for w in windows)
        out["window_s"] = max(w.get("window_s") or 0.0 for w in windows)
        weights = [w.get("requests_completed") or 0 for w in windows]
        total_w = sum(weights)

        def wavg(values: List[Optional[float]]) -> Optional[float]:
            pairs = [(v, wt) for v, wt in zip(values, weights)
                     if v is not None and wt > 0]
            den = sum(wt for _, wt in pairs)
            return sum(v * wt for v, wt in pairs) / den if den else None

        out["slo_met"] = (
            wavg([w.get("slo_met") for w in windows]) if total_w else None
        )
        for dist in ("ttft", "itl"):
            out[dist] = {
                q: wavg([(w.get(dist) or {}).get(q) for w in windows])
                for q in ("p50_ms", "p95_ms", "p99_ms", "mean_ms")
            }
        slos = [w.get("slo") for w in windows if w.get("slo")]
        if slos:
            out["slo"] = slos[0]
        return out

    def snapshot(self, now_mono: Optional[float] = None,
                 with_knees: bool = True) -> FleetSnapshot:
        """Join the current entries (no estimator side effects).
        `with_knees=False` skips the knee fits — sample() recomputes
        them after feeding the estimators anyway."""
        now_mono = time.monotonic() if now_mono is None else now_mono
        per_model: Dict[str, List[dict]] = {}
        workers: Dict[str, dict] = {}
        for key, entry in list(self.entries.items()):
            stale, age = self._is_stale(entry, now_mono)
            if stale and age > self.retention_s:
                # past the retention horizon: drop it, but remember its
                # seq so a watch-reconnect replay can't resurrect it
                seq = entry["payload"].get("seq")
                if seq is not None:
                    self._pruned_seqs[key] = seq
                    self._pruned_seqs.move_to_end(key)
                    while len(self._pruned_seqs) > 1024:
                        self._pruned_seqs.popitem(last=False)
                del self.entries[key]
                continue
            payload = entry["payload"]
            # key = /telemetry/{ns}/{component}/{id}
            parts = key.strip("/").split("/")
            comp = parts[2] if len(parts) >= 4 else "?"
            ident = parts[3] if len(parts) >= 4 else "?"
            if payload.get("kind") == "frontend" or comp == "frontend":
                if stale:
                    continue  # a frontend's own windows age out with it
                for model, win in (payload.get("models") or {}).items():
                    per_model.setdefault(model, []).append(win)
            else:
                workers[f"{comp}/{ident}"] = {
                    **payload,
                    "stale": stale,
                    "age_s": round(age, 3),
                }
        models = {m: self._merge_windows(ws) for m, ws in per_model.items()}
        return FleetSnapshot(
            ts=time.time(),
            models=models,
            workers=workers,
            knees=({m: est.estimate()
                    for m, est in self.knee_estimators.items()}
                   if with_knees else {}),
        )

    # -- online estimation ---------------------------------------------------- #

    def sample(self, now_mono: Optional[float] = None) -> FleetSnapshot:
        """snapshot() + feed the knee/profile estimators and the
        counter-track history from it."""
        snap = self.snapshot(now_mono, with_knees=False)
        counters: Dict[str, float] = {}
        for model, win in snap.models.items():
            completed = win.get("requests_completed") or 0
            met = win.get("slo_met")
            offered = win.get("offered_rps") or 0.0
            if completed and met is not None and offered > 0:
                self.knee_estimators.setdefault(
                    model, KneeEstimator(self.knee_threshold)
                ).add(offered, met, weight=completed)
            fresh = snap.fresh_workers(model)
            # disagg fleets: prefill load lands only on prefill-capable
            # workers and decode concurrency only on decode-capable ones
            # — dividing across the whole fleet would halve the observed
            # per-role load and mis-size both pools
            pre = {k: w for k, w in fresh.items()
                   if w.get("disagg_role", "both") in ("both", "prefill")}
            dec = {k: w for k, w in fresh.items()
                   if w.get("disagg_role", "both") in ("both", "decode")}
            n_pre = len(pre) or len(fresh)
            n_dec = len(dec) or len(fresh)
            n = len(fresh)
            if n and completed:
                ttft = (win.get("ttft") or {}).get(_PROFILE_Q)
                itl = (win.get("itl") or {}).get(_PROFILE_Q)
                if ttft:
                    self._prefill_obs.setdefault(
                        model, _ProfileBuilder()
                    ).add((win.get("prompt_tok_s") or 0.0) / n_pre,
                          ttft / 1e3)
                if itl:
                    conc = sum(
                        (w.get("active_seqs") or 0)
                        + (w.get("waiting_seqs") or 0)
                        for w in (dec or fresh).values()
                    ) / n_dec
                    # snapshots can miss short-lived decodes entirely
                    # (sampled gauge vs sub-interval requests): Little's
                    # law over the window — attained tok/s × mean ITL —
                    # is the load actually sustained, so take the max
                    itl_mean = (win.get("itl") or {}).get("mean_ms")
                    per_worker_attained = (win.get("attained_tok_s")
                                           or 0.0) / n_dec
                    if itl_mean:
                        conc = max(conc,
                                   per_worker_attained * itl_mean / 1e3)
                    self._decode_obs.setdefault(
                        model, _ProfileBuilder()
                    ).add(conc, itl / 1e3, per_worker_attained)
            for key in ("goodput_tok_s", "attained_tok_s", "offered_rps"):
                counters[f"{model}.{key}"] = win.get(key) or 0.0
            if met is not None:
                counters[f"{model}.slo_met"] = met
        for wkey, w in snap.workers.items():
            if w.get("stale"):
                continue
            for key, src in (("queue_depth", "waiting_seqs"),
                             ("kv_usage", "kv_usage"),
                             ("batch_occupancy", "batch_occupancy")):
                v = w.get(src)
                if isinstance(v, (int, float)):
                    counters[f"{wkey}.{key}"] = float(v)
        snap.knees = {m: est.estimate()
                      for m, est in self.knee_estimators.items()}
        if counters:
            self.history.append({"ts": snap.ts, "values": counters})
        return snap

    def knee_rate_rps(self, model: str) -> Optional[float]:
        est = self.knee_estimators.get(model)
        return est.estimate() if est else None

    def load_sample(self,
                    snap: Optional[FleetSnapshot] = None
                    ) -> Optional[LoadSample]:
        """Adapt the joined state into the planner's observation unit.
        None until at least one fresh window or worker exists."""
        snap = snap or self.snapshot()
        fresh = snap.fresh_workers()
        if not snap.models and not fresh:
            return None
        return LoadSample(
            requests_per_s=sum(
                w.get("offered_rps") or 0.0 for w in snap.models.values()
            ),
            prefill_tokens_per_s=sum(
                w.get("prompt_tok_s") or 0.0 for w in snap.models.values()
            ),
            # decode-capable workers only (same role filter sample()
            # applies): prefill-role workers' in-flight seqs are not
            # decode load, and counting them over-sizes the decode pool
            concurrent_decodes=float(sum(
                (w.get("active_seqs") or 0) + (w.get("waiting_seqs") or 0)
                for w in fresh.values()
                if w.get("disagg_role", "both") in ("both", "decode")
            )),
        )

    def observed_profile(self, model: str,
                         kind: str = "decode") -> Optional[PerfProfile]:
        """A PerfProfile whose `kind` axis is MEASURED from live
        telemetry (the other axis carries the same observations so the
        profile stands alone); None until ≥3 distinct load points."""
        pre = (self._prefill_obs.get(model) or _ProfileBuilder()).curves()
        dec = (self._decode_obs.get(model) or _ProfileBuilder()).curves()
        need = pre if kind == "prefill" else dec
        if need is None:
            return None
        pre = pre or need
        dec = dec or need
        return PerfProfile(
            prefill_load=pre[0], ttft_s=pre[1],
            decode_concurrency=dec[0], itl_s=dec[1],
            decode_throughput=dec[2],
        )

    def counter_samples(self) -> List[dict]:
        """History for runtime.timeline counter tracks
        (`counters_to_chrome`): [{"ts": wall_s, "values": {...}}]."""
        return list(self.history)


class TelemetryConnector:
    """Planner connector whose observations come from the fleet watcher
    (scaling actions delegate to any underlying connector — Virtual,
    LocalProcess, or a test fake), closing observe→predict→scale on live
    data."""

    def __init__(self, watcher: FleetTelemetryWatcher, inner):
        self.watcher = watcher
        self.inner = inner

    async def scale(self, kind: str, replicas: int) -> None:
        await self.inner.scale(kind, replicas)

    async def collect_load(self) -> Optional[LoadSample]:
        # side-effect-free read: the estimators tick via the watcher's
        # start_sampling() loop — feeding them here too would double-
        # count windows whenever both run (planner cadence vs sampler
        # cadence would bias the knee/profile fits)
        return self.watcher.load_sample()
