"""Overload-control bench phase: mixed-class Poisson load past the knee.

`overload_phase` answers: past the saturation knee, do
priority classes + admission shedding + decode preemption
(docs/overload_control.md) actually protect interactive latency, and
what does that cost batch?  Two arms run the SAME arrival schedule and
token demands at the same offered rate (default 2x the knee) against a
MockEngine — which reuses the real Scheduler, so the class-aware
admission, queue-deadline shedding, and park/resume preemption under
test are the production code paths:

- ``control=False``: one undifferentiated class, no shedding, no
  preemption — every request fights through the same FIFO (the
  pre-overload-control behavior).  Past the knee the queue grows
  without bound, TTFTs blow through the SLO for everyone, and goodput
  collapses while attained throughput stays high: the
  attained-vs-goodput gap.
- ``control=True``: the declared interactive share rides the priority
  class; batch absorbs the overload (queued behind interactive with a
  deadline, shed with a structured ``overloaded`` error at the knee,
  parked mid-decode when an interactive head needs the slot).

Accounting: a request is SLO-met
when TTFT and mean ITL both land under the target; goodput counts
tokens from SLO-met requests only.  Shed requests count in the offered
rate but are excluded from SLO scoring — a clean 429 is load control
working, not a latency breach (the same convention as the frontend's
live windows, frontend/slo.py).

The tier-1 gate (tests/test_overload_phase.py) runs both arms at
reduced duration and holds the two acceptance bars from the overload
work: interactive slo_met >= 0.9 at 2x knee with control on, and the
attained-vs-goodput gap cut at least in half vs control off.
"""

from __future__ import annotations

import asyncio
import random
import time
from typing import Any, Dict, List, Optional

from ..mocker.engine import MockEngine, MockEngineArgs

# SLO targets for the phase: ITL sized so decode speed at full batch is
# not the failure mode — requests miss by QUEUEING (TTFT) or by being
# starved mid-decode, which is exactly what overload control manages
DEFAULT_SLO = {"ttft_ms": 600.0, "itl_ms": 60.0}


def default_overload_args(control: bool) -> MockEngineArgs:
    """Mock capacity/timing tuned so the knee sits near 8 req/s at the
    default shape (prompt 64 / gen 32): 8 decode slots at ~26 ms/step
    full-batch serve ~9.5 req/s flat out.  The control arm adds the
    overload knobs; the baseline arm runs the same capacity with
    overload control disabled (depth 0)."""
    kw: Dict[str, Any] = dict(
        num_pages=256, page_size=16, max_num_seqs=8,
        max_prefill_tokens=512, max_model_len=1024,
        speedup_ratio=1.0,
        decode_base=0.010, decode_per_seq=0.002,
    )
    if control:
        kw.update(
            # knee signal: queue at least one full batch deep (the
            # headroom floor is set above the whole pool — this shape
            # is slot-bound, not page-bound)
            overload_queue_depth=8,
            overload_headroom_pages=10**6,
            batch_deadline_s=1.0,
        )
    return MockEngineArgs(**kw)


def _class_stats(rows: List[dict], dt: float, slo: Dict[str, float]
                 ) -> Dict[str, Any]:
    served = [r for r in rows if not r["shed"]]
    ok = [r for r in served
          if r["ttft_ms"] <= slo["ttft_ms"] and r["itl_ms"] <= slo["itl_ms"]]
    ttfts = sorted(r["ttft_ms"] for r in served)
    return {
        "n": len(rows),
        "shed": sum(1 for r in rows if r["shed"]),
        "offered_rps": round(len(rows) / dt, 3),
        "slo_met": round(len(ok) / len(served), 4) if served else None,
        "goodput_tok_s": round(sum(r["tokens"] for r in ok) / dt, 2),
        "attained_tok_s": round(sum(r["tokens"] for r in served) / dt, 2),
        "ttft_p50_ms": round(ttfts[len(ttfts) // 2], 1) if ttfts else None,
        "ttft_p99_ms": round(
            ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))], 1
        ) if ttfts else None,
    }


async def run_overload_arm(*, rate_rps: float, n_req: int,
                           prompt_len: int = 64, gen: int = 32,
                           slo: Optional[Dict[str, float]] = None,
                           interactive_frac: float = 0.35, seed: int = 23,
                           control: bool = True,
                           args: Optional[MockEngineArgs] = None
                           ) -> Dict[str, Any]:
    """One arm: Poisson arrivals at `rate_rps`, each request drawn
    interactive with probability `interactive_frac` (same RNG seed both
    arms → identical schedules and class assignments; the baseline arm
    simply doesn't DECLARE the class to the engine)."""
    slo = slo or dict(DEFAULT_SLO)
    engine = MockEngine(args or default_overload_args(control))
    rng = random.Random(seed)
    waits: List[float] = []
    classes: List[str] = []
    acc = 0.0
    for _ in range(n_req):
        acc += rng.expovariate(rate_rps)
        waits.append(acc)
        classes.append("interactive" if rng.random() < interactive_frac
                       else "batch")

    async def one(i: int) -> dict:
        await asyncio.sleep(waits[i])
        req: Dict[str, Any] = {
            "token_ids": [((i * 13 + j) % 997) + 1
                          for j in range(prompt_len)],
            "sampling_options": {"temperature": 0.0},
            "stop_conditions": {"max_tokens": gen, "ignore_eos": True},
        }
        if control:
            req["priority"] = classes[i]
        t_submit = time.perf_counter()
        n = 0
        t_first = t_last = None
        shed = False
        async for out in engine.generate(req):
            if out.get("finish_reason") == "error":
                err = out.get("error")
                shed = isinstance(err, dict) and err.get("code") == "overloaded"
            if out.get("token_ids"):
                t_last = time.perf_counter()
                if t_first is None:
                    t_first = t_last
                n += len(out["token_ids"])
        return {
            "cls": classes[i],
            "tokens": n,
            "shed": shed,
            "ttft_ms": ((t_first - t_submit) * 1e3 if t_first
                        else float("inf")),
            "itl_ms": ((t_last - t_first) / max(n - 1, 1) * 1e3
                       if t_first else float("inf")),
        }

    t0 = time.perf_counter()
    rows = await asyncio.gather(*[one(i) for i in range(n_req)])
    dt = time.perf_counter() - t0
    m = engine.metrics()
    await engine.shutdown()
    overall = _class_stats(list(rows), dt, slo)
    gap = overall["attained_tok_s"] - overall["goodput_tok_s"]
    return {
        "control": control,
        "rate_rps": rate_rps,
        "n_req": n_req,
        "duration_s": round(dt, 2),
        "slo": slo,
        **overall,
        "gap_tok_s": round(gap, 2),
        "classes": {
            cls: _class_stats([r for r in rows if r["cls"] == cls], dt, slo)
            for cls in ("interactive", "batch")
        },
        "engine": {
            "shed_total": m.shed_total,
            "queued_total": m.queued_total,
            "preempted_total": m.preempted_total,
            "resumed_total": m.resumed_total,
            "parked_seqs": m.parked_seqs,
            "parked_pages": m.parked_pages,
        },
    }


async def overload_phase(*, knee_rps: float = 8.0, factor: float = 2.0,
                         n_req: int = 240, prompt_len: int = 64,
                         gen: int = 32,
                         slo: Optional[Dict[str, float]] = None,
                         interactive_frac: float = 0.35, seed: int = 23,
                         log=None) -> Dict[str, Any]:
    """Both arms at `factor` x the knee rate; reports the per-class
    split and how much of the attained-vs-goodput gap overload control
    recovers (`gap_cut` = off-arm gap / on-arm gap)."""
    rate = knee_rps * factor
    kw = dict(rate_rps=rate, n_req=n_req, prompt_len=prompt_len, gen=gen,
              slo=slo, interactive_frac=interactive_frac, seed=seed)
    off = await run_overload_arm(control=False, **kw)
    on = await run_overload_arm(control=True, **kw)
    gap_cut = (off["gap_tok_s"] / on["gap_tok_s"]
               if on["gap_tok_s"] > 0 else float("inf"))
    if log:
        ion = on["classes"]["interactive"]
        bon = on["classes"]["batch"]
        log(f"[overload_phase] {rate:g} rps ({factor:g}x knee): "
            f"off slo_met {off['slo_met']} gap {off['gap_tok_s']} tok/s | "
            f"on interactive slo_met {ion['slo_met']} "
            f"batch slo_met {bon['slo_met']} shed {bon['shed']}/{bon['n']} "
            f"gap {on['gap_tok_s']} tok/s (cut {gap_cut:.1f}x, "
            f"preempted {on['engine']['preempted_total']} "
            f"resumed {on['engine']['resumed_total']})")
    return {
        "knee_rps": knee_rps,
        "rate_rps": rate,
        "off": off,
        "on": on,
        "interactive_slo_met": on["classes"]["interactive"]["slo_met"],
        "batch_slo_met": on["classes"]["batch"]["slo_met"],
        "gap_cut": (round(gap_cut, 2)
                    if gap_cut != float("inf") else None),
    }
