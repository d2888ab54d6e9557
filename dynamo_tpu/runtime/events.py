"""Engine step event recorder — a lock-light fixed-size ring buffer.

The spans in `runtime.tracing` answer "where did THIS request's time go";
this recorder answers "what was the ENGINE doing, step by step" — admit,
plan, step, rung selection, spec accept, pool alloc/free, disagg handoff —
at monotonic-ns resolution with near-zero overhead, so a TTFT outlier or
a chaos-scenario failure can be replayed as a timeline instead of
inferred from aggregate counters (reference analog: the KV-event
recorder + mocker step logs, here generalized to every engine decision).

Design constraints:
- the pump's executor thread records on the device-step hot path, so one
  `record()` must stay well under 5 µs (tier-1 micro-benchmark in
  tests/test_step_events.py) — a preallocated list slot write under a
  plain lock, no dict churn beyond the caller's attr kwargs;
- `dump()` is wait-free for the writer: it snapshots under the same lock
  and carries BOTH a wall-clock and a monotonic anchor so offline tools
  (runtime/timeline.py) can place monotonic event times on the spans'
  wall-clock axis.

The recorder is always attached to the engine; `DYN_TPU_STEP_EVENTS`
overrides the ring capacity (0 disables recording entirely — `record`
short-circuits on one attribute load).

Crash-surviving flight recorder: with `DYN_TPU_FLIGHT_DIR` set, every
recorded event is also mirrored into fixed-size mmap-backed binary
segments in that directory. The mmap pages are shared with the page
cache, so a SIGKILL leaves whatever was already written readable — the
black box that the in-memory ring (gone with the process) cannot
provide. Each 512-byte record slot carries a trailing commit marker
written LAST, so a reader treats a torn final record as a clean prefix
end, never as garbage (`load_flight_dir` / `scripts/postmortem.py`).

Host events: the rare, long things a process does off the step path
(the worker's start-up phases, a program's trace / lowering / compile /
cache load, a collector pause, a late lease renewal) enter through the
module-level `host_event()`, on the same monotonic clock.  Until an
engine's ring attaches (`attach_host_events`) they wait in a small
bounded buffer; the ring adopts them once, each with its OWN `t_ns`
(earlier than the ring's birth), and later ones are written straight to
it.  A process with several rings (`--dp-ranks`) has ONE that takes
them: the first engine built (rank 0), for as long as it lives.
`DYN_TPU_STEP_EVENTS=0` turns them off with the rest.  The collector's
callback is the one caller that may run INSIDE this module's locks (a
collection starts between any two bytecodes): it takes none and parks its
pause for `_flush_gc`."""

from __future__ import annotations

import json
import mmap
import os
import re
import struct
import collections
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

from ..analysis import make_lock

DEFAULT_CAPACITY = 4096

# -- flight-recorder binary format ------------------------------------------ #
# Header page (4096 B): magic, version, record size, slot count, pid, and
# the wall/mono clock anchors that let offline tools place monotonic event
# times on the OTLP spans' wall-clock axis (same contract as ring dumps).
FLIGHT_MAGIC = b"DYNFLTR1"
FLIGHT_VERSION = 1
FLIGHT_HEADER_SIZE = 4096
# a step slice fits with its phases, ordinal, hand-offs and request id: an
# expert model's shared `prefill_chunk` is 430 bytes of kind and attributes
# (256 held no `prefill_chunk` slice at all since the slices carry phases)
FLIGHT_RECORD_SIZE = 512
_FLIGHT_COMMIT = 0xA5  # written to the slot's LAST byte after the payload
_HDR = struct.Struct("<8sIIIIqqH")  # magic ver rec_size n_slots pid wall mono service_len
_REC = struct.Struct("<qqHH")  # t_ns dur_ns kind_len attr_len
_REC_PAYLOAD_MAX = FLIGHT_RECORD_SIZE - _REC.size - 1  # minus commit byte
_SEG_RE = re.compile(r"^flight-(\d+)-(\d+)\.seg$")

DEFAULT_FLIGHT_SLOTS = 4096  # ~2 MiB/segment
DEFAULT_FLIGHT_KEEP = 4

# one shared encoder: json.dumps with non-default kwargs constructs a
# fresh JSONEncoder per call — ~2.4µs of the 5µs/event budget
_ATTR_ENCODE = json.JSONEncoder(separators=(",", ":"), default=str).encode


def _encode_attrs(attrs: Dict[str, Any]) -> bytes:
    """Compact-JSON attr bytes, with a manual fast path for the dicts the
    step hot path records: integers (rung/batch/chain, the `*_us` phases)
    and plain identifiers such as `attn` or a request id — ~0.8µs
    cheaper per event than even a cached JSONEncoder.  Keys come from
    `record(**attrs)` kwargs, so they are identifiers needing no
    escaping; any other value falls back to the real encoder (which
    `default=str`s anything unserializable)."""
    parts = []
    for k, v in attrs.items():
        if type(v) is int:  # exact: bool is a subclass, floats can be NaN
            parts.append('"%s":%d' % (k, v))
        elif type(v) is str and v.isascii() \
                and v.replace("-", "").replace("_", "").isalnum():
            parts.append('"%s":"%s"' % (k, v))  # nothing to escape
        else:
            try:
                return _ATTR_ENCODE(attrs).encode("utf-8")
            except (TypeError, ValueError):
                return b"{}"
    return ("{" + ",".join(parts) + "}").encode("ascii")


class FlightRecorder:
    """Mmap-backed spill of step events into fixed-size binary segments.

    Caller-serialized: `append` runs under the StepEventRecorder's ring
    lock, so the recorder keeps no lock of its own. The hot path is one
    struct pack + one compact json.dumps + two mmap slice writes — well
    inside the ring's 5 µs/event budget (micro-benched with the spill
    armed in tests/test_step_events.py). Any I/O error permanently
    disables the spill rather than breaking serving."""

    def __init__(self, directory: str, service: str = "",
                 segment_slots: int = DEFAULT_FLIGHT_SLOTS,
                 keep: int = DEFAULT_FLIGHT_KEEP):
        self.directory = directory
        self.service = service
        self.segment_slots = max(16, int(segment_slots))
        self.keep = max(1, int(keep))
        self.pid = os.getpid()
        self.segments_written = 0
        self.records_written = 0
        self._seq = 0
        self._slot = 0
        self._mm: Optional[mmap.mmap] = None
        self._kind_cache: Dict[str, bytes] = {}  # kinds are a small set
        self.ok = True
        try:
            # lint: allow(blocking-in-async): one-time setup at recorder creation
            os.makedirs(directory, exist_ok=True)
            self._open_segment()
        except OSError:
            self.ok = False

    def _open_segment(self) -> None:
        path = os.path.join(
            self.directory, f"flight-{self.pid}-{self._seq:08d}.seg")
        size = FLIGHT_HEADER_SIZE + self.segment_slots * FLIGHT_RECORD_SIZE
        fd = os.open(path, os.O_CREAT | os.O_TRUNC | os.O_RDWR, 0o644)
        try:
            os.ftruncate(fd, size)  # zero-filled: commit markers start 0
            self._mm = mmap.mmap(fd, size)
        finally:
            os.close(fd)
        svc = self.service.encode("utf-8", "replace")[:256]
        hdr = _HDR.pack(FLIGHT_MAGIC, FLIGHT_VERSION, FLIGHT_RECORD_SIZE,
                        self.segment_slots, self.pid, time.time_ns(),
                        time.monotonic_ns(), len(svc))
        self._mm[0:len(hdr)] = hdr
        self._mm[_HDR.size:_HDR.size + len(svc)] = svc
        self._slot = 0
        self.segments_written += 1
        self._prune()

    def _prune(self) -> None:
        """Keep at most `keep` segments for THIS pid (other processes
        sharing the directory prune their own)."""
        mine = []
        for name in os.listdir(self.directory):
            m = _SEG_RE.match(name)
            if m and int(m.group(1)) == self.pid:
                mine.append((int(m.group(2)), name))
        mine.sort()
        for _, name in mine[:-self.keep]:
            try:
                os.unlink(os.path.join(self.directory, name))
            except OSError:
                pass

    def append(self, t_ns: int, dur_ns: int, kind: str,
               attrs: Dict[str, Any]) -> None:
        """Spill one event (caller holds the ring lock)."""
        if not self.ok:
            return
        try:
            kb = self._kind_cache.get(kind)
            if kb is None:
                kb = kind.encode("ascii", "replace")[:64]
                self._kind_cache[kind] = kb
            ab = _encode_attrs(attrs)
            if len(kb) + len(ab) > _REC_PAYLOAD_MAX:
                ab = b'{"truncated":true}'
            if self._slot >= self.segment_slots:
                self._seq += 1
                self._mm.close()
                self._open_segment()
            off = FLIGHT_HEADER_SIZE + self._slot * FLIGHT_RECORD_SIZE
            body = _REC.pack(t_ns, dur_ns, len(kb), len(ab)) + kb + ab
            self._mm[off:off + len(body)] = body
            # commit marker LAST: a reader never sees a half-written
            # record as committed (SIGKILL-consistent via the page cache)
            self._mm[off + FLIGHT_RECORD_SIZE - 1] = _FLIGHT_COMMIT
            self._slot += 1
            self.records_written += 1
        except (OSError, ValueError):
            self.ok = False

    def close(self) -> None:
        if self._mm is not None:
            try:
                self._mm.flush()
                self._mm.close()
            except (OSError, ValueError):
                pass
            self._mm = None
        self.ok = False

    @classmethod
    def from_env(cls) -> Optional["FlightRecorder"]:
        from .config import env_int, env_str

        directory = env_str("DYN_TPU_FLIGHT_DIR")
        if not directory:
            return None
        from .tracing import default_service_name

        return cls(
            directory,
            service=default_service_name(),
            segment_slots=env_int("DYN_TPU_FLIGHT_SEGMENT_SLOTS",
                                  DEFAULT_FLIGHT_SLOTS),
            keep=env_int("DYN_TPU_FLIGHT_KEEP", DEFAULT_FLIGHT_KEEP),
        )


def load_flight_segment(path: str) -> Dict[str, Any]:
    """Parse one flight segment into a ring-dump-shaped dict.

    Torn tails are expected (the writer died mid-record): parsing stops
    at the first slot whose commit marker is absent or whose payload
    fails to decode — the committed prefix is returned, never an error.
    Raises ValueError only when the HEADER is invalid (not a segment)."""
    with open(path, "rb") as f:
        raw = f.read()
    if len(raw) < _HDR.size:
        raise ValueError(f"{path}: too short for a flight segment header")
    magic, version, rec_size, n_slots, pid, wall_ns, mono_ns, svc_len = (
        _HDR.unpack_from(raw, 0))
    if magic != FLIGHT_MAGIC:
        raise ValueError(f"{path}: bad magic {magic!r}")
    if version != FLIGHT_VERSION or rec_size != FLIGHT_RECORD_SIZE:
        raise ValueError(
            f"{path}: unsupported version/record size {version}/{rec_size}")
    service = raw[_HDR.size:_HDR.size + svc_len].decode("utf-8", "replace")
    events: List[Dict[str, Any]] = []
    for slot in range(n_slots):
        off = FLIGHT_HEADER_SIZE + slot * rec_size
        if off + rec_size > len(raw):
            break  # truncated file: clean-prefix end
        if raw[off + rec_size - 1] != _FLIGHT_COMMIT:
            break  # first uncommitted slot: end of the committed prefix
        try:
            t_ns, dur_ns, kind_len, attr_len = _REC.unpack_from(raw, off)
            p = off + _REC.size
            kind = raw[p:p + kind_len].decode("ascii")
            attrs = json.loads(raw[p + kind_len:p + kind_len + attr_len])
            if not isinstance(attrs, dict):
                attrs = {"value": attrs}
        except (struct.error, UnicodeDecodeError, ValueError):
            break  # torn payload despite marker: stop at the clean prefix
        events.append({"t_ns": t_ns, "dur_ns": dur_ns, "kind": kind,
                       **attrs})
    return {
        "wall_ns": wall_ns,
        "mono_ns": mono_ns,
        "pid": pid,
        "service": service or f"pid{pid}",
        "capacity": n_slots,
        "recorded_total": len(events),
        "dropped_total": 0,
        "events": events,
    }


def load_flight_dir(directory: str,
                    pid: Optional[int] = None) -> List[Dict[str, Any]]:
    """Load every flight segment in `directory` (optionally one pid's),
    merged per-pid in segment order, as ring-dump-shaped dicts — the
    `ring_dumps` input `runtime.timeline.merge_timeline` already takes.
    Unreadable or non-segment files are skipped, not fatal: a postmortem
    works with whatever the dead process tree left behind."""
    by_pid: Dict[int, List[tuple]] = {}
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return []
    for name in names:
        m = _SEG_RE.match(name)
        if not m:
            continue
        seg_pid, seq = int(m.group(1)), int(m.group(2))
        if pid is not None and seg_pid != pid:
            continue
        try:
            dump = load_flight_segment(os.path.join(directory, name))
        except (OSError, ValueError):
            continue
        by_pid.setdefault(seg_pid, []).append((seq, dump))
    out: List[Dict[str, Any]] = []
    for seg_pid in sorted(by_pid):
        segs = sorted(by_pid[seg_pid])
        merged = dict(segs[0][1])
        merged["events"] = [e for _, d in segs for e in d["events"]]
        merged["recorded_total"] = len(merged["events"])
        merged["segments"] = len(segs)
        out.append(merged)
    return out


# -- host events -------------------------------------------------------------- #

# every kind `host_event` is called with in the package; the merged
# timeline draws them on a track of their own (runtime/timeline.py)
HOST_EVENT_KINDS = frozenset((
    "startup.imports", "startup.backend", "startup.weights",
    "startup.engine", "startup.serve", "ready", "program", "gc_pause",
    "lease_renew"))
_HOST_BUFFER_MAX = 1024  # a start-up's phases, programs and pauses
_host_lock = threading.Lock()
_host_buffer: List[tuple] = []  # guarded-by: _host_lock
_host_ring: Optional["weakref.ref[StepEventRecorder]"] = None
_gc_t0 = 0
# pauses the collector's callback saw, waiting for a point where a lock may
# be taken: a collection starts between any two bytecodes, inside this
# module's locks too, so the callback itself takes none (a deque's append
# is atomic) and `_flush_gc` moves them on
_gc_pending: "collections.deque[tuple]" = collections.deque(maxlen=256)


def _attached_ring() -> Optional["StepEventRecorder"]:
    """The live ring that takes this process's host events, if any."""
    return _host_ring() if _host_ring is not None else None


def _host_events_on() -> bool:
    from .config import env_int

    return env_int("DYN_TPU_STEP_EVENTS", DEFAULT_CAPACITY) > 0


def host_event(kind: str, t0_ns: Optional[int] = None,
               t1_ns: Optional[int] = None, **attrs: Any) -> None:
    """Record a host event where it happens, whether or not a ring exists
    yet: `StepEventRecorder.record`'s arguments (a slice [t0_ns, t1_ns or
    now], or an instant), written to the attached ring or, before one
    attaches, kept in a bounded buffer (the newest are dropped past
    `_HOST_BUFFER_MAX`)."""
    if _gc_pending:
        _flush_gc()
    t1 = time.monotonic_ns() if t1_ns is None else t1_ns
    t0 = t1 if t0_ns is None else t0_ns
    with _host_lock:
        ring = _attached_ring()
        if ring is None:
            if len(_host_buffer) < _HOST_BUFFER_MAX and _host_events_on():
                _host_buffer.append((t0, t1 - t0, kind, attrs))
            return
    ring.record(kind, t0_ns=t0, t1_ns=t1, **attrs)


def attach_host_events(ring: "StepEventRecorder") -> bool:
    """An engine's ring takes the process's host events: it adopts the
    buffer and is written to from now on.  The first ring to ask wins
    while it lives (a `--dp-ranks` worker: rank 0's), so one process's
    host events are on one ring; False for the others."""
    global _host_ring
    install_host_probes()
    with _host_lock:
        if _attached_ring() is not None:
            return False
        _host_ring = weakref.ref(ring)
        buffered, _host_buffer[:] = list(_host_buffer), []
    for t, dur, kind, attrs in buffered:  # each with its OWN times
        ring.record(kind, t0_ns=t, t1_ns=t + dur, **attrs)
    return True


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    """`gc.callbacks`: a `gc_pause` slice for a generation-2 collection
    or any pause of 1 ms or more; returns at once otherwise."""
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.monotonic_ns()
        return
    t1 = time.monotonic_ns()
    if info["generation"] == 2 or t1 - _gc_t0 >= 1_000_000:
        _gc_pending.append(
            (_gc_t0, t1, info["generation"], info["collected"]))


def _flush_gc() -> None:
    """The pauses `_on_gc` left, as `gc_pause` host events.  Called where
    no lock of this module is held: by the next `record()` of any ring, by
    `dump()` / `snapshot()` before they read (so a poll's cursor never
    passes a pause that ended before the poll) and by `host_event`."""
    pauses = []
    while _gc_pending:  # emptied first: recording looks here again
        try:
            pauses.append(_gc_pending.popleft())
        except IndexError:  # another thread took the last one
            break
    for t0, t1, gen, collected in pauses:
        host_event("gc_pause", t0, t1, gen=gen, collected=collected)


def _on_program(t0_ns: int, t1_ns: int, attrs: Dict[str, Any]) -> None:
    """The compile ledger's sink, on the thread that compiled."""
    host_event("program", t0_ns, t1_ns, **attrs)


def install_host_probes() -> None:
    """The collector's callback and the compile ledger's sink, once a
    process (the worker calls this first thing, an engine's ring when it
    attaches).  Nothing where `DYN_TPU_STEP_EVENTS=0`."""
    import gc

    if _on_gc in gc.callbacks or not _host_events_on():
        return
    gc.callbacks.append(_on_gc)
    from ..analysis import xla_ledger

    xla_ledger.set_program_sink(_on_program)


class StepEventRecorder:
    """Fixed-capacity ring of (t_ns, dur_ns, kind, attrs) tuples."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 flight: Optional[FlightRecorder] = None):
        self.capacity = max(0, int(capacity))
        self.enabled = self.capacity > 0
        self._ring: List[Optional[tuple]] = [None] * self.capacity  # guarded-by: _lock
        self._n = 0  # total events ever recorded  # guarded-by: _lock
        # per-kind lifetime counts (survive ring wrap + clear, like _n):
        # lets periodic consumers (telemetry's host-gap stat) skip the
        # full ring dump unless the kind they care about actually moved
        self.kind_totals: Dict[str, int] = {}
        self.flight = flight if self.enabled else None  # guarded-by: _lock
        self._lock = make_lock("events._lock")

    @classmethod
    def from_env(cls) -> "StepEventRecorder":
        from .config import env_int

        return cls(env_int("DYN_TPU_STEP_EVENTS", DEFAULT_CAPACITY),
                   flight=FlightRecorder.from_env())

    @staticmethod
    def now() -> int:
        """Monotonic ns — the `t0_ns` anchor for duration events."""
        return time.monotonic_ns()

    def record(self, kind: str, t0_ns: Optional[int] = None,
               t1_ns: Optional[int] = None, **attrs: Any) -> None:
        """Record one event.  With `t0_ns` (a prior `now()`), the event is
        a duration slice [t0_ns, now]; without, an instant.  `t1_ns` (the
        caller's own last `now()`) ends the slice there instead: parts
        the caller measured up to that reading then add up to the slice,
        whatever happens to the thread before it gets here."""
        if not self.enabled:
            return
        if _gc_pending:
            _flush_gc()
        t = time.monotonic_ns() if t1_ns is None else t1_ns
        if t0_ns is not None:
            ev = (t0_ns, t - t0_ns, kind, attrs)
        else:
            ev = (t, 0, kind, attrs)
        with self._lock:
            self._ring[self._n % self.capacity] = ev
            self._n += 1
            self.kind_totals[kind] = self.kind_totals.get(kind, 0) + 1
            if self.flight is not None:
                self.flight.append(ev[0], ev[1], kind, attrs)

    def __len__(self) -> int:
        with self._lock:
            return min(self._n, self.capacity)

    @property
    def total(self) -> int:
        with self._lock:
            return self._n

    def totals(self) -> Dict[str, int]:
        """Per-kind lifetime counts (survive ring wrap), copied under
        the lock — the cheap periodic-consumer surface (telemetry
        publishers) that skips the full ring dump."""
        with self._lock:
            return dict(self.kind_totals)

    def _snap(self) -> tuple:
        """(recorded_total, events in record order) in ONE lock
        acquisition, so dump()'s counters agree with its event list."""
        with self._lock:
            n, ring = self._n, list(self._ring)
        if n <= self.capacity:
            return n, [e for e in ring[:n]]
        head = n % self.capacity
        return n, ring[head:] + ring[:head]

    def snapshot(self) -> List[tuple]:
        """Events in record order (oldest surviving first)."""
        if not self.enabled:
            return []
        _flush_gc()
        return self._snap()[1]

    def dump(self, since_ns: Optional[int] = None) -> Dict[str, Any]:
        """JSON-able ring dump with time anchors (the worker debug
        endpoint's payload, and timeline.py's merge input).

        `wall_ns - mono_ns` converts any event's monotonic time to the
        wall clock the OTLP spans use.

        With `since_ns` (the `watermark_ns` of a previous dump), only
        events COMMITTED after that instant are returned — a cursor so
        pollers fetch deltas instead of the whole ring each scrape. An
        event commits at `t_ns + dur_ns` (record time), which is
        monotone in record order; filtering on start time would lose
        long slices that began before the watermark."""
        _flush_gc()
        mono = time.monotonic_ns()
        wall = time.time_ns()
        n, events = self._snap()
        watermark = since_ns or 0
        for (t, d, _k, _a) in events:
            if t + d > watermark:
                watermark = t + d
        if since_ns is not None:
            events = [e for e in events if e[0] + e[1] > since_ns]
        return {
            "wall_ns": wall,
            "mono_ns": mono,
            "capacity": self.capacity,
            "recorded_total": n,
            "dropped_total": max(0, n - self.capacity),
            "watermark_ns": watermark,
            "events": [
                {"t_ns": t, "dur_ns": d, "kind": k, **a}
                for (t, d, k, a) in events
            ],
        }

    def clear(self) -> None:
        with self._lock:
            self._ring = [None] * self.capacity
            self._n = 0
