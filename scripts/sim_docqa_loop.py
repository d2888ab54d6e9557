"""A discrete-event model of the benchmark's closed loop (`benchmark/loops/
closed.py` over `benchmark/traffic/docqa-1tok.json`) against this engine's
prefill scheduling, to read how `ttft_p50_ms` / `ttft_p95_ms` spread over
seeds WITHOUT the chip (PERF.md, finding 25, third prediction: it reproduced
six measured seeds of the nemotron cell within 3% each, and told a design
that spreads 12% from one that spreads 1%).  CPU only; no JAX.

What it models: four clients, each taking the next session of the seeded
order; first in first out over chunks; the next step planned when the
current one starts (one step in flight); a short last chunk (at most
`short` tokens) shares a step with up to three more.  What it is given:
step times by bucket (`STEP`, `SHARED`: read them off a traced run's
`prefill_chunk` slices) and a policy: where a follow-up's prefix hit ends
(`cached`) and how a prompt is cut into chunks (`chunk`).  What it leaves
out: eviction, decode, the frontend's own time beyond two constants.

    python scripts/sim_docqa_loop.py            # the policies of finding 25
"""
import heapq
import math
import os
import random
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, 'benchmark'))
from lib import traffic  # noqa: E402

MIX = traffic.load_mix(
    os.path.join(ROOT, 'benchmark', 'traffic', 'docqa-1tok.json'))
SIZES = traffic.session_sizes(MIX)
# ms a step by bucket, one row; and of a shared short step by its rows (the
# nemotron cell's traced window, PERF.md section 5, before the tail row)
STEP = {16: 14.4, 32: 14.7, 64: 15.3, 128: 17.5, 256: 20.4, 512: 40.2}
SHARED = {1: 15.3, 2: 23.1, 3: 23.2, 4: 24.0}
PAGE = 16

def bucket(n):
    b = 16
    while b < n: b *= 2
    return b

class Seq:
    def __init__(s, client, L, cached, t_arr, doc):
        s.client, s.L, s.n, s.t_arr, s.doc = client, L, cached, t_arr, doc
        s.cached = cached

def order(seed, cycle):
    o = list(range(len(SIZES)))
    random.Random(f"order:{seed}:{cycle}").shuffle(o)
    return o

def run(seed, policy, Tw=12.9, T=40.0, noise=0.0, c1=4.0, c2=4.0, budget=512,
        rng=None, short=64):
    """One run of `seed`: (requests, p50, p95, records) of the window [Tw,
    Tw + T) seconds.  `policy`: {"cached": (doc, L, turn) -> tokens a
    request resumes at, "chunk": (seq, chunk, budget) -> the chunk's length,
    "lone": seq -> its short row shares no step (optional)}.  `noise`: the
    relative sigma of a step's time; c1, c2: ms from the client to the
    engine and back."""
    rng = rng or random.Random(0)
    nxt = [0]
    def take():
        cyc, pos = divmod(nxt[0], len(SIZES)); nxt[0] += 1
        return SIZES[order(seed, cyc)[pos]]
    # client state
    sess = [None]*4; turn = [0]*4
    waiting = []  # arrivals heap (t, idx, seq)
    cnt = 0
    def send(c, t):
        nonlocal cnt
        if sess[c] is None or turn[c] >= len(sess[c]['turns']):
            sess[c] = take(); turn[c] = 0
        s = sess[c]; doc = s['prefix_len']; L = doc + s['turns'][turn[c]][0]
        cached = policy['cached'](doc, L, turn[c])
        q = Seq(c, L, cached, t + c1, doc); q.turn = turn[c]
        turn[c] += 1
        cnt += 1
        heapq.heappush(waiting, (q.t_arr, cnt, q))
    for c in range(4): send(c, 0.0)
    running = []
    recs = []
    t = 0.0  # time when the device becomes free of step k-1 == plan point of k+1
    # pipeline: planned step (list of (seq, chunk, samples)), executing step end
    def plan(now):
        while waiting and waiting[0][0] <= now:
            running.append(heapq.heappop(waiting)[2])
        items = []
        for q in running:
            if q.n >= q.L: continue
            chunk = min(q.L - q.n, budget)
            chunk = policy['chunk'](q, chunk, budget)
            is_short = chunk <= short and chunk == q.L - q.n and not policy.get('lone', lambda q: False)(q)
            if items and not is_short: continue
            items.append((q, chunk))
            if not is_short or len(items) >= 4: break
        for q, ch in items: q.n += ch
        return items
    def dur(items):
        if len(items) == 1:
            d = STEP[bucket(items[0][1])]
        else:
            d = SHARED[len(items)] * (STEP[bucket(max(c for _, c in items))] / STEP[64])
        return d * (1 + (rng.gauss(0, noise) if noise else 0))
    end_time = (Tw + T) * 1000 + 2000
    dev_free = 0.0
    nextp = plan(0.0)
    while dev_free < end_time:
        if not nextp:
            # idle: jump to next arrival
            if not waiting: break
            now = max(dev_free, waiting[0][0]) + 0.5
            dev_free = now
            nextp = plan(now)
            continue
        cur = nextp
        start = dev_free
        end = start + dur(cur)
        # plan the following step at the start of this one
        nextp = plan(start + 1.0)
        # a request finishes when its last chunk has RUN (`n` counts what is
        # planned: the next plan may have advanced it already)
        for q, ch in cur:
            q.exec = getattr(q, 'exec', q.cached) + ch
            if q.exec >= q.L:
                q.done = True
                t_first = end + c2
                recs.append((q.t_arr - c1, t_first - (q.t_arr - c1), q.turn, q.L, q.cached))
                running.remove(q)
                send(q.client, t_first)
        dev_free = end
    w0, w1 = Tw*1000, (Tw+T)*1000
    tt = sorted(r[1] for r in recs if w0 <= r[0] < w1)
    n = len(tt)
    p50 = statistics.median(tt); p95 = tt[max(0, math.ceil(0.95*n) - 1)]
    return n, p50, p95, recs

def pol_every(every=128):
    def cached(doc, L, turn):
        if turn == 0: return 0
        return (doc // PAGE * PAGE) // every * every
    return {'cached': cached, 'chunk': lambda q, ch, b: ch}

def spread(v):
    q = statistics.quantiles(v, n=4); return (q[2]-q[0]) / statistics.median(v)

def pol_tail(pages=3):
    """A prompt ends in a tail row of `pages` whole pages and what is left;
    a follow-up resumes at its last shared page."""
    def cached(doc, L, turn):
        return 0 if turn == 0 else doc // PAGE * PAGE

    def chunk(q, ch, b):
        tail = (q.L - 1) // PAGE * PAGE - pages * PAGE
        return min(ch, tail - q.n) if q.turn == 0 and q.n < tail else ch
    return {'cached': cached, 'chunk': chunk}


if __name__ == '__main__':
    seeds = [2000000000 + 7919 * i for i in range(24)]
    for name, pol in (("a state every 128 tokens", pol_every(128)),
                      ("every 512 (chunk ends)", pol_every(512)),
                      ("at every page (page-only models)", pol_every(16)),
                      ("every 128 + the tail row", pol_tail())):
        out = [run(s, pol) for s in seeds]
        p95 = [o[2] for o in out]
        sets = [round(100 * spread(p95[i:i + 6]), 1) for i in range(0, 24, 6)]
        print(f"{name:34s} requests {statistics.median(o[0] for o in out):4.0f}"
              f"  p50 {statistics.median(o[1] for o in out):6.1f}"
              f"  p95 {statistics.median(p95):6.1f}"
              f"  spread of p95 a set of six, % {sets}")
