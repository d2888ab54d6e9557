"""What the run collects from the worker's status server: `/metrics.json`
snapshots (counters and host-clock totals made where the work happens, read
as deltas over the window) and the step-event ring of `/events.json`, polled
with its cursor so that nothing is dropped."""

import asyncio

import aiohttp


async def get_json(url, timeout=30):
    async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=timeout)) as http:
        async with http.get(url) as resp:
            if resp.status != 200:
                raise RuntimeError(f"{url} answered {resp.status}")
            return await resp.json(content_type=None)


def compile_count(metrics):
    """Programs compiled + programs read from the persistent cache so far."""
    xla = metrics["runtime"]["xla"]
    return xla["cache_hits"] + xla["cache_misses"], xla["backend_compiles"]


class EventPoller:
    """Polls /events.json?since_ns=<cursor> every `period` seconds.  Events
    of every ring (one per dp rank) are kept with the ring's name; the
    cursor is per response (the rings share one clock)."""

    def __init__(self, status_url, period=1.0):
        self.url = status_url + "/events.json"
        self.period = period
        self.events = []       # dicts with "ring" added
        self.dropped = 0       # events lost to ring wrap between polls
        self.anchor = None     # (wall_ns, mono_ns) of the worker's clocks
        self._cursor = {}
        self._seen_total = {}
        self._task = None
        self._stop = False

    async def _poll_once(self):
        cur = min(self._cursor.values(), default=None)
        url = self.url if cur is None else f"{self.url}?since_ns={cur}"
        dump = await get_json(url)
        for ring, d in dump.items():
            self.anchor = (d["wall_ns"], d["mono_ns"])
            evs = d["events"]
            seen = self._cursor.get(ring, 0)
            new = [e for e in evs if e["t_ns"] + e["dur_ns"] > seen]
            total = d["recorded_total"]
            if ring in self._seen_total:
                self.dropped += max(
                    0, total - self._seen_total[ring] - len(new))
            self._seen_total[ring] = total
            for e in new:
                e["ring"] = ring
            self.events.extend(new)
            self._cursor[ring] = d["watermark_ns"]

    async def _run(self):
        while not self._stop:
            try:
                await self._poll_once()
            except Exception:  # noqa: BLE001 — a missed poll is caught up
                pass
            await asyncio.sleep(self.period)

    def start(self):
        self._task = asyncio.ensure_future(self._run())

    async def stop(self):
        self._stop = True
        await self._task
        await self._poll_once()
