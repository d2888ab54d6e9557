#!/usr/bin/env python3
"""`ops/ssm.selective_scan` at a serving cell's shapes against the float32
loop, single process, for the chip (no children):

    python scripts/check_selective_scan.py [--small] [--impl auto[,xla,pallas]]

Two 512-token chunks of one row over 5,120 channels and 16 states (`--small`:
two of 32 over 1,024 and 4, on whatever backend is here; tier-1 runs that;
"pallas" off a TPU is the kernel INTERPRETED, which this script asks for and
the served entry never does): the
second chunk starts from the state the first handed over, and each hands out
its state after 128, 256 and 384 tokens.  `A` and the step sizes are drawn as
the family initialises them (A = -(1..N) by state index, steps in 0.001-0.1),
so that a state REMEMBERS across the boundary and a decay by state index
differs from one by channel: the benchmark's checkpoint draws both near a
constant, where `correct` cannot see either (benchmark/configs/
phi4-mini-flash-3.8b.json, `assumed.weights`).

Held against the loop, token by token in numpy float32: the outputs of both
chunks, the final state and the six states handed out.  And two faults
planted in the LOOP, which must read far from the committed scan:
`scalar_decay` (A[n, c] = A[0, c] for every n) and `state_not_carried` (the
second chunk from zeros).  One JSON line; exit 1 where the scan is further
from the loop than `TOL`, or a fault nearer than 100 x `TOL`.  It times
nothing: what the scan costs is read from a traced cell (`step.
selective_scan_device_pct`, the kernel's own line in the breakdown)."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# |scan - loop| over the largest |loop| value: float32 on both sides, the
# device's exponential and sums in another order (a v5e reads 1.6e-5 on the
# outputs and 2.2e-5 on the states, PR 48)
TOL = 5e-5


def loop(np, x, dt, A, Bm, Cm, h):
    """The recurrence, one token after the other: x, dt [S, C]; A [N, C];
    Bm, Cm [S, N]; h [N, C] -> (y [S, C], every state [S, N, C])."""
    ys, hs = [], []
    for t in range(x.shape[0]):
        h = np.exp(dt[t][None, :] * A) * h + Bm[t][:, None] * (dt[t] * x[t])
        ys.append((Cm[t][:, None] * h).sum(0))
        hs.append(h)
    return np.stack(ys), np.stack(hs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--impl", default="auto",
                    help="forms of `ops.ssm.selective_scan` to hold against "
                    "the loop: auto (the served path's choice), xla, pallas")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops import ssm

    S, C, N, every = (32, 1024, 4, 8) if args.small else (512, 5120, 16, 128)
    at = tuple(range(every, S, every))[:3]
    rng = np.random.default_rng(48)
    f32 = np.float32
    x = rng.standard_normal((2 * S, C)).astype(f32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (2 * S, C))).astype(f32)
    A = -np.broadcast_to(np.arange(1, N + 1, dtype=f32)[:, None], (N, C))
    Bm = rng.standard_normal((2 * S, N)).astype(f32)
    Cm = rng.standard_normal((2 * S, N)).astype(f32)
    zero = np.zeros((N, C), f32)
    want_y, want_h = loop(np, x, dt, A, Bm, Cm, zero)
    scale = float(np.abs(want_y).max())

    # the chunks' inputs on the device once: a call is the scan alone
    dev = [tuple(jnp.asarray(a[None, n * S:(n + 1) * S])
                 for a in (x, dt, Bm, Cm)) for n in (0, 1)]
    A_d = jnp.asarray(A)

    def chunk(n, h0, impl):
        xs, dts, bs, cs = dev[n]
        return scan[impl](xs, dts, A_d, bs, cs, h0)

    on_tpu = jax.default_backend() == "tpu"
    forms = {"auto": {}, "xla": {"kernel": False},
             "pallas": {"kernel": True, "interpret": not on_tpu}}
    scan = {i: jax.jit(lambda *a, i=i: ssm.selective_scan(*a, at, **forms[i]))
            for i in args.impl.split(",")}
    out = {"device": jax.devices()[0].device_kind, "tokens": S,
           "channels": C, "states": N, "handed_out_at": list(at), "tol": TOL}
    worst = 0.0
    for u in scan:
        h, err_y, err_h = jnp.asarray(zero[None]), 0.0, 0.0
        for n in (0, 1):
            y, h, hs = chunk(n, h, u)
            err_y = max(err_y, float(np.abs(
                np.asarray(y[0]) - want_y[n * S:(n + 1) * S]).max()))
            for t, got in zip((*at, S), (*hs, h)):
                err_h = max(err_h, float(np.abs(
                    np.asarray(got[0]) - want_h[n * S + t - 1]).max()))
        out[u] = {
            "y_err_over_max": err_y / scale,
            "state_err_over_max": err_h / float(np.abs(want_h).max())}
        worst = max(worst, err_y / scale,
                    err_h / float(np.abs(want_h).max()))
    # the faults, planted in the loop: how far each reads from the loop
    first = next(iter(scan))
    got2 = np.asarray(chunk(1, jnp.asarray(want_h[S - 1][None]), first)[0][0])
    faults = {
        "scalar_decay": loop(np, x, dt, np.repeat(A[:1], N, 0), Bm, Cm,
                             zero)[0][S:],
        "state_not_carried": loop(np, x[S:], dt[S:], A, Bm[S:], Cm[S:],
                                  zero)[0]}
    out["faults_over_max"] = {k: float(np.abs(v - got2).max()) / scale
                              for k, v in faults.items()}
    out["ok"] = bool(worst <= TOL and all(
        v >= 100 * TOL for v in out["faults_over_max"].values()))
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
