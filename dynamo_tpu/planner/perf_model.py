"""Per-worker performance interpolation (reference
/root/reference/components/src/dynamo/planner/utils/perf_interpolation.py +
the pre_swept_results npz grids): given profiling sweeps of TTFT vs
prefill load and ITL vs decode load, answer "how much load can one worker
take while meeting the SLO?"."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np


@dataclass
class PerfProfile:
    """Monotone samples from a profiling sweep of ONE worker."""

    # prefill: tokens/s offered → TTFT seconds
    prefill_load: Sequence[float]
    ttft_s: Sequence[float]
    # decode: concurrent sequences → ITL seconds
    decode_concurrency: Sequence[float]
    itl_s: Sequence[float]
    # decode throughput at each concurrency (output tok/s)
    decode_throughput: Sequence[float]

    @staticmethod
    def load_npz(path: str) -> "PerfProfile":
        with np.load(path) as z:
            return PerfProfile(
                z["prefill_load"], z["ttft_s"],
                z["decode_concurrency"], z["itl_s"], z["decode_throughput"],
            )

    def save_npz(self, path: str) -> None:
        np.savez(
            path,
            prefill_load=np.asarray(self.prefill_load),
            ttft_s=np.asarray(self.ttft_s),
            decode_concurrency=np.asarray(self.decode_concurrency),
            itl_s=np.asarray(self.itl_s),
            decode_throughput=np.asarray(self.decode_throughput),
        )

    # -- interpolators ------------------------------------------------------- #

    def ttft_at(self, prefill_tokens_per_s: float) -> float:
        return float(np.interp(
            prefill_tokens_per_s, self.prefill_load, self.ttft_s
        ))

    def itl_at(self, concurrency: float) -> float:
        return float(np.interp(
            concurrency, self.decode_concurrency, self.itl_s
        ))

    def max_prefill_load_under(self, ttft_slo_s: float) -> float:
        """Largest offered prefill tok/s with interpolated TTFT <= SLO."""
        loads = np.asarray(self.prefill_load, np.float64)
        ttfts = np.asarray(self.ttft_s, np.float64)
        ok = ttfts <= ttft_slo_s
        if not ok.any():
            return 0.0
        if ok.all():
            return float(loads[-1])
        # last ok sample, then interpolate to the SLO crossing
        i = int(np.where(ok)[0][-1])
        if i + 1 >= len(loads):
            return float(loads[-1])
        x0, x1 = loads[i], loads[i + 1]
        y0, y1 = ttfts[i], ttfts[i + 1]
        if y1 == y0:
            return float(x0)
        return float(x0 + (ttft_slo_s - y0) * (x1 - x0) / (y1 - y0))

    def max_decode_concurrency_under(self, itl_slo_s: float) -> float:
        conc = np.asarray(self.decode_concurrency, np.float64)
        itls = np.asarray(self.itl_s, np.float64)
        ok = itls <= itl_slo_s
        if not ok.any():
            return 0.0
        if ok.all():
            return float(conc[-1])
        i = int(np.where(ok)[0][-1])
        if i + 1 >= len(conc):  # a measured grid need not be monotone
            return float(conc[-1])
        x0, x1 = conc[i], conc[i + 1]
        y0, y1 = itls[i], itls[i + 1]
        if y1 == y0:
            return float(x0)
        return float(x0 + (itl_slo_s - y0) * (x1 - x0) / (y1 - y0))


def synthetic_profile(
    prefill_capacity_tok_s: float = 20_000.0,
    base_ttft_s: float = 0.08,
    base_itl_s: float = 0.01,
    max_concurrency: float = 64.0,
) -> PerfProfile:
    """Queueing-shaped default profile for tests / first boot (latency grows
    ~1/(1-utilization))."""
    util = np.linspace(0.05, 0.98, 24)
    prefill_load = util * prefill_capacity_tok_s
    ttft = base_ttft_s / (1.0 - util)
    conc = np.linspace(1, max_concurrency, 24)
    itl = base_itl_s * (1.0 + (conc / max_concurrency) ** 2 * 3.0)
    thpt = conc / itl
    return PerfProfile(prefill_load, ttft, conc, itl, thpt)
