"""The closed loop: `clients` sessions in flight; each client takes the next
session of the seeded order when its last one completes, cycle after cycle
through the mix's set (lib/traffic.py).

Warm-up is the same loop before the window opens: the clients run until
`warmup.cycles` whole cycles of the set have completed (so every shape the
window can meet has been met: the window sends the same set), and until the
worker has compiled or loaded no program for `warmup.quiet_seconds`; at most
`warmup.max_seconds`.  The window then opens on clients in steady state.

`run(ctx)` returns the window's data: t0, t1, records (every request of the
loop, warm-up included; a metric takes those due inside the window),
metrics0/metrics1 (the worker's /metrics.json at the window's edges) and
the warm-up's account.  ctx: url, model, mix, seed, seconds, vocab,
metrics_url."""

import asyncio
import time

from lib import collect, loadgen, traffic


class ClosedLoop:
    def __init__(self, url, model, mix, seed, vocab):
        self.url, self.model, self.mix = url, model, mix
        self.seed, self.vocab = seed, vocab
        self.records = []
        self.sessions_done = 0
        self._next = 0
        self._n = int(mix["set_size"])
        self._plan, self._cycle = [], -1
        self._stopping = False
        self._tasks = []
        self._http = None

    def _take(self):
        cycle, pos = divmod(self._next, self._n)
        self._next += 1
        if cycle != self._cycle:
            self._cycle = cycle
            self._plan = traffic.sessions(self.mix, self.seed, self.vocab,
                                          cycle)
        return self._plan[pos]

    async def _client(self):
        while not self._stopping:
            whole = await loadgen.run_session(
                self._http, self.url, self.model, self._take(),
                loadgen.now(), "closed", self.records,
                stopping=lambda: self._stopping)
            if whole:
                self.sessions_done += 1
            elif not self._stopping:
                await asyncio.sleep(0.2)  # do not spin on a dead server

    async def start(self):
        self._http = loadgen.session_of()
        self._tasks = [asyncio.ensure_future(self._client())
                       for _ in range(int(self.mix["clients"]))]

    async def stop(self):
        self._stopping = True
        await asyncio.gather(*self._tasks)
        await self._http.close()


async def warm_up(loop, metrics_url, spec, set_size):
    """(seconds, quiet, sessions completed)."""
    t0 = last_change = time.monotonic()
    last = None
    need = int(spec["cycles"]) * set_size
    while True:
        await asyncio.sleep(0.5)
        now = time.monotonic()
        count = collect.compile_count(await collect.get_json(metrics_url))
        if count != last:
            last, last_change = count, now
        if now - t0 >= spec["max_seconds"]:
            return now - t0, False, loop.sessions_done
        if (loop.sessions_done >= need
                and now - last_change >= spec["quiet_seconds"]):
            return now - t0, True, loop.sessions_done


async def run(ctx):
    mix = ctx["mix"]
    loop = ClosedLoop(ctx["url"], ctx["model"], mix, ctx["seed"],
                      ctx["vocab"])
    await loop.start()
    took, quiet, done = await warm_up(loop, ctx["metrics_url"], mix["warmup"],
                                      int(mix["set_size"]))
    metrics0 = await collect.get_json(ctx["metrics_url"])
    t0 = time.monotonic()
    await asyncio.sleep(ctx["seconds"])
    metrics1 = await collect.get_json(ctx["metrics_url"])
    await loop.stop()
    return {"t0": t0, "t1": t0 + ctx["seconds"], "records": loop.records,
            "metrics0": metrics0, "metrics1": metrics1,
            "warmup": {"kind": "closed", "seconds": took, "quiet": quiet,
                       "sessions": done}}
