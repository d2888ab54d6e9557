"""Requests from one process, one thread: real HTTP requests to the frontend
from an asyncio loop, stamped on the parent's monotonic clock when each was
due, sent, got its first token and its last.  The loops that schedule them
are files of their own, benchmark/loops/<loop>.py, found by the mix's `loop`.

Each request opens its own connection (so only the router, not connection
affinity, can bring a repeat to the replica that holds its prefix)."""

import asyncio
import time

import aiohttp

from . import stats

now = time.monotonic


def completion_body(model, req, logprobs=None, stream=True):
    """`logprobs` (legacy completions): None for none, 0 for the sampled
    token's own logprob from the plain step programs, k > 0 for the top-k
    variant of the step programs as well."""
    body = {"model": model, "prompt": req["prompt"],
            "max_tokens": req["max_tokens"], "temperature": 0,
            "stream": stream, "nvext": {"ignore_eos": True}}
    if logprobs is not None:
        body["logprobs"] = logprobs
    return body


async def stream_request(http, url, model, req, t_due, phase):
    """Send one streamed completion; return its record."""
    rec = {"phase": phase, "t_due": t_due, "t_sent": now(),
           "prompt_len": len(req["prompt"]), "max_tokens": req["max_tokens"],
           "status": None, "finish": None, "error": None,
           "t_first": None, "t_last": None, "frames": 0}
    try:
        async with http.post(url, json=completion_body(model, req)) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = (await resp.text())[:300]
                return rec
            async for raw in resp.content:
                frame = stats.sse_frame(raw)
                if frame is None:
                    continue
                if "error" in frame:
                    rec["error"] = frame["error"]
                    continue
                t = now()
                rec["frames"] += 1
                if rec["t_first"] is None:
                    rec["t_first"] = t
                rec["t_last"] = t
                rec["finish"] = frame["finish"] or rec["finish"]
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    return rec


def session_of():
    # a new connection per request; no total timeout (a request may queue)
    return aiohttp.ClientSession(
        connector=aiohttp.TCPConnector(force_close=True, limit=0),
        timeout=aiohttp.ClientTimeout(total=None, sock_read=600))


async def run_session(http, url, model, turns, t_due, phase, records,
                      stopping=lambda: False):
    """The turns of one session, one after the other.  The first is due at
    `t_due`; a later turn is due (and sent) when the one before completed.
    No further turn is sent once `stopping()`.  True iff every turn was
    sent and finished with `length`."""
    for k, req in enumerate(turns):
        if stopping():
            return False
        rec = await stream_request(http, url, model, req, t_due, phase)
        rec["turn"] = k
        records.append(rec)
        if rec["finish"] != "length":
            return False  # a failed turn ends the session
        t_due = now()
    return True
