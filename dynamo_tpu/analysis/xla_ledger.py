"""Runtime JAX contracts: compile ledger + thread-role transfer guard.

The static half of the JAX contract checker lives in ``jitcheck.py``;
this module is the runtime half (docs/jax_contracts.md):

Compile ledger
--------------
``ledgered_jit`` is a drop-in ``jax.jit`` replacement the engine's
step builders use.  It wraps the function in a trace probe BEFORE
handing it to ``jax.jit``: the probe body executes exactly when jax
traces (= jit cache miss) and never on a cache hit, so every XLA
compilation is attributed to a ``(function, arg-signature, tags)``
tuple with zero hot-path cost — the compiled callable jax caches is
keyed on the wrapper, and cache hits never re-enter Python.

``steady_scope`` marks a region where ZERO new compilations are
allowed (the steady-state tripwire): traces recorded inside an active
scope become ``trips()``, which the pytest session gate
(tests/conftest.py, next to the lockcheck gate) requires empty.
``note_decode_block()`` counts decode blocks; with
``DYN_TPU_XLALEDGER_STEADY=N`` set, the ledger self-arms a persistent
steady scope after N blocks (after warmup, N decode blocks ⇒ 0 new
compiles).  ``DYN_TPU_XLALEDGER=0`` disables the probe entirely
(``ledgered_jit`` degrades to ``jax.jit``).

A ``jax.monitoring`` listener backstops the probe and gives every
program's birth a clock.  jax (0.9) reports, on the thread that
compiles and in this order: ``/jax/core/compile/jaxpr_trace_duration``
(the probe fires inside it), ``.../jaxpr_to_mlir_module_duration``
(lowering), then ``.../backend_compile_duration`` around
``compile_or_get_cached`` — so ``backend_compile_seconds`` HOLDS the
persistent cache's loads, and a retrieval reports itself just before
as ``/jax/compilation_cache/cache_retrieval_time_sec``.  The probe
stamps its entry with ``t_ns`` (monotonic: the step ring's clock) and
makes it the thread's open birth; the listener adds each stage to it
(``trace_us``, ``lower_us``, ``compile_us``, ``load_us``, ``hit``) and,
when the backend stage ends, hands the program to the sink
(``set_program_sink``: `runtime/events.py` puts it on the ring as a
``program`` slice) and remembers its ``t_ns`` for the thread
(``births_between``: the engine's step slice says ``compiled``).  A
compile OUTSIDE a ledgered function (library warmup,
``SamplingParams.make``'s ``jit_convert_element_type``) carries no
function identity: its birth opens at its lowering (its trace is not
looked for), it reaches the sink with an empty ``fn`` if lowering and
backend took 1 ms or more, and is counted (``programs_sub_ms``)
otherwise.  An entry that never reaches the backend (traced inline
under another jit, ``eval_shape``, ``.lower()``) keeps what it saw and
reaches no sink.

Program store
-------------
The probe is also the one door every engine program is born through, so
it is where a program's lowered module is kept across processes
(``compile_cache.ProgramStore``, installed by ``set_program_store``).  A
program that comes with a description of what its body closes over
(``ledgered_jit(..., closes_over=...)``: ``Layout.wrap`` gives one) is
looked up BEFORE its body is traced, by that description and the call's
abstract signature.  A miss exports the body (``jax.export``: the one
trace and lowering, where they happened anyway), writes the module with
the ``note_path_choice`` notes its trace made, and calls it; a hit replays
the notes and calls the stored module, so a cold and a warm start run the
same StableHLO under the same outer ``jit``.  The birth says ``stored`` 1
or 0.  A program whose export or call raises is traced as before, says so
once in a ``program_store`` path choice, and its birth carries no
``stored``.

Transfer guard (``DYN_TPU_XFERCHECK=1``)
----------------------------------------
Role threads (``step``/``drain`` per ``contracts.THREAD_NAME_ROLES``)
must never perform an IMPLICIT device→host sync — ``.item()``,
``float()``/``int()``/``bool()`` coercion — mid-step; explicit
``jax.device_get`` is the one sanctioned sync and is wrapped in an
allow scope.  Unknown threads (pytest main, user code) are exempt.

Coverage is three-layered because the native guard is backend-shaped:
``jax.transfer_guard_device_to_host("disallow")`` is entered
persistently on role threads (it is thread-local), which catches
implicit D2H on real TPU — but is inert on the CPU backend where
tier-1 runs (arrays are already host-resident).  So the installer also
patches ``ArrayImpl.item/__float__/__int__/__bool__/__index__`` with a
role check that raises ``HostSyncError`` on step/drain threads, which
fires on every backend.  ``np.asarray`` on a device array cannot be
intercepted from Python (numpy uses the C buffer protocol), so that
case is covered statically by jitcheck's ``host-sync`` rule plus the
native guard on TPU.
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from . import contracts

__all__ = [
    "CompileEntry",
    "HostSyncError",
    "allow_host_sync",
    "births_between",
    "compiles_by_fn",
    "entries",
    "guard_state",
    "install_transfer_guard",
    "last_entry",
    "ledger_enabled",
    "ledgered_jit",
    "note_decode_block",
    "note_path_choice",
    "note_transfer_violation",
    "reset",
    "set_program_sink",
    "set_program_store",
    "steady_scope",
    "summary",
    "thread_role_init",
    "transfer_violations",
    "transfer_violations_total",
    "trips",
    "xfercheck_enabled",
]

# Flags read once at import (same convention as contracts._MODE); tests
# flip the module globals via monkeypatch, not the env.
_LEDGER_ON = os.environ.get("DYN_TPU_XLALEDGER", "1") not in ("", "0")
_XFERCHECK = os.environ.get("DYN_TPU_XFERCHECK", "") not in ("", "0")
# after N decode blocks, self-arm the steady tripwire (0 = never)
_AUTO_STEADY_BLOCKS = int(os.environ.get("DYN_TPU_XLALEDGER_STEADY", "0") or 0)

# roles whose threads must not implicitly sync (docs/jax_contracts.md)
_GUARDED_ROLES = ("step", "drain")

_SIG_MAX_CHARS = 200


def ledger_enabled() -> bool:
    return _LEDGER_ON


def xfercheck_enabled() -> bool:
    return _XFERCHECK


class HostSyncError(RuntimeError):
    """An implicit device→host sync ran on a step/drain-role thread."""


@dataclasses.dataclass
class CompileEntry:
    """One attributed XLA compilation (jit cache miss)."""

    fn: str               # qualname of the traced function
    signature: str        # aval signature, e.g. "f32[4,64], i32[4]"
    tags: Dict[str, Any]  # e.g. {"rung": 4}
    thread: str
    in_steady: bool       # a steady scope was active → this is a trip
    scope: str            # the steady scope's label ("" outside)
    program: str = ""     # the jitted program's name (`Layout.wrap`'s)
    role: str = ""        # contracts role of the tracing thread
    sig: str = ""         # 8 hex digits over the WHOLE signature
    t_ns: int = 0         # monotonic ns at the probe: the ring's clock
    # stages the listener saw on this thread after the probe, in
    # microseconds; None = the birth never got there
    trace_us: Optional[int] = None
    lower_us: Optional[int] = None
    compile_us: Optional[int] = None  # backend stage less the cache load
    load_us: Optional[int] = None     # persistent-cache retrieval
    hit: Optional[int] = None         # 1: the cache answered, 0: compiled
    # 1: the lowered module came from the program store, 0: it was derived
    # and written there; None: no store, or the program was declined
    stored: Optional[int] = None

    def format(self) -> str:
        tag = f" {self.tags}" if self.tags else ""
        stages = "".join(
            f" {k}={v}" if k in ("hit", "stored")
            else f" {k[:-3]}={v / 1000:.1f}ms"
            for k, v in self.stages().items())
        return (f"{self.fn}({self.signature}){tag} [thread={self.thread}]"
                f"{stages}")

    def stages(self) -> Dict[str, int]:
        """The stages seen, by their event-attribute names."""
        return {k: getattr(self, k) for k in (
            "trace_us", "lower_us", "compile_us", "load_us", "hit", "stored")
            if getattr(self, k) is not None}


_LOCK = threading.Lock()
# all guarded-by: _LOCK
_entries: List[CompileEntry] = []
_trips: List[CompileEntry] = []
_compiles_by_fn: Dict[str, int] = {}
_decode_blocks = 0
_auto_steady_armed = False
_steady_labels: List[str] = []
_backend_compiles = 0
_backend_compile_secs = 0.0
_program_events = 0   # births handed to the sinks
_programs_sub_ms = 0  # unledgered births under 1 ms: counted, not handed
_program_sink: Optional[Callable[[int, int, Dict[str, Any]], None]] = None
# `compile_cache.ProgramStore` (`set_program_store`): None = every program
# is traced, as before there was one
_program_store: Any = None
_programs_stored = 0        # births whose module came from the store
_programs_store_writes = 0  # births whose module was derived and written
_BORN_MAX = 64        # births a thread remembers for `births_between`
# persistent-cache outcomes (jax/_src/compiler.py events): requests that
# consulted the cache, and those it answered with a stored executable —
# the difference really compiled
_cache_requests = 0
_cache_hits = 0
# trace-time dispatch decisions: (site, choice, reason, dims) → traces
_path_choices: Dict[Tuple[str, str, str, str], int] = {}
_choice_by_dims: Dict[Tuple[str, str], str] = {}  # (site, dims) → choice
_violations: List[dict] = []
_violations_by_kind: Dict[str, int] = {}
_MAX_RECORDS = 4096

_tls = threading.local()

# threads that ran thread_role_init: name → guard description
_guard_threads: Dict[str, str] = {}


# -- signature formatting ------------------------------------------------------ #

_DTYPE_SHORT = {
    "float32": "f32", "float16": "f16", "bfloat16": "bf16",
    "float64": "f64", "int32": "i32", "int64": "i64", "int16": "i16",
    "int8": "i8", "uint32": "u32", "uint8": "u8", "bool": "b1",
}


def _fmt_leaf(x: Any) -> str:
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        d = _DTYPE_SHORT.get(str(dtype), str(dtype))
        return f"{d}[{','.join(str(s) for s in shape)}]"
    r = repr(x)
    return r if len(r) <= 24 else r[:21] + "..."


def _fmt_signature(args: tuple, kwargs: dict) -> Tuple[str, str]:
    """(the signature for reading, cut at `_SIG_MAX_CHARS`; 8 hex digits of
    a hash over EVERY leaf: a step program's first leaves are the model's
    parameters, so two programs of one function agree in the first and
    differ only in the second)."""
    try:
        leaves = [_fmt_leaf(x)
                  for x in jax.tree_util.tree_leaves((args, kwargs))]
    except Exception:  # noqa: BLE001 — attribution must never break tracing
        return "<unformattable>", ""
    parts: List[str] = []
    for leaf in leaves:
        parts.append(leaf)
        if sum(len(p) + 2 for p in parts) > _SIG_MAX_CHARS:
            parts.append(f"...+{len(leaves) - len(parts)} more")
            break
    digest = hashlib.blake2s(", ".join(leaves).encode(), digest_size=4)
    return ", ".join(parts), digest.hexdigest()


# -- ledger recording ---------------------------------------------------------- #

def _record_trace(fn_name: str, signature: str,
                  tags: Optional[Dict[str, Any]],
                  program: str = "", sig: str = "") -> CompileEntry:
    t_ns = time.monotonic_ns()
    role = contracts.current_role() or ""
    with _LOCK:
        in_steady = bool(_steady_labels) or _auto_steady_armed
        scope = (_steady_labels[-1] if _steady_labels
                 else ("auto-steady" if _auto_steady_armed else ""))
        e = CompileEntry(
            fn=fn_name, signature=signature, tags=dict(tags or {}),
            thread=threading.current_thread().name,
            in_steady=in_steady, scope=scope, program=program, role=role,
            sig=sig, t_ns=t_ns,
        )
        if len(_entries) < _MAX_RECORDS:
            _entries.append(e)
        _compiles_by_fn[fn_name] = _compiles_by_fn.get(fn_name, 0) + 1
        if in_steady and len(_trips) < _MAX_RECORDS:
            _trips.append(e)
    return e


def ledgered_jit(fn: Callable, *, tags: Optional[Dict[str, Any]] = None,
                 name: Optional[str] = None, closes_over: Any = None,
                 **jit_kwargs) -> Callable:
    """``jax.jit`` with compile attribution.

    ``name`` names the compiled program (``jit_<name>`` on a profiler
    trace's "XLA Modules" line); the ledger keeps attributing compiles
    to the function's qualified name.  ``closes_over`` describes, by its
    ``repr``, everything ``fn`` closes over that its lowered module depends
    on: with it (and a store installed) the module is kept in and taken
    from the program store; without it the program is traced every time.

    Drop-in at the call sites the engine uses
    (``partial(ledgered_jit, donate_argnums=...)`` mirrors
    ``partial(jax.jit, ...)``).  The probe wrapper's body runs only
    when jax traces ``fn`` — i.e. on a jit cache miss — so recording
    costs nothing on the steady-state hit path.  Returns plain
    ``jax.jit(fn)`` when the ledger is disabled, for exact parity.
    """
    qual = getattr(fn, "__qualname__", getattr(fn, "__name__", repr(fn)))
    if name is not None:
        fn.__name__ = name
    if not _LEDGER_ON:
        return jax.jit(fn, **jit_kwargs)
    import functools

    donate = jit_kwargs.get("donate_argnums", ())

    @functools.wraps(fn)
    def probe(*args, **kwargs):
        signature, sig = _fmt_signature(args, kwargs)
        e = _record_trace(qual, signature, tags, fn.__name__, sig)
        # the outermost trace on this thread opens the program's birth;
        # a ledgered function traced INSIDE another is inlined there
        depth = getattr(_tls, "trace_depth", 0)
        if depth == 0:
            _tls.birth = e
        _tls.trace_depth = depth + 1
        try:
            if depth == 0 and closes_over is not None:
                module = _from_store(e, fn, donate, closes_over, args, kwargs)
                if module is not None:
                    return module
            return fn(*args, **kwargs)
        finally:
            _tls.trace_depth = depth

    return jax.jit(probe, **jit_kwargs)


def set_program_store(store: Any) -> None:
    """`compile_cache.configure` installs the process's `ProgramStore`;
    None takes it away (every program is traced)."""
    global _program_store
    _program_store = store


def _abstract_signature(args: tuple, kwargs: dict) -> str:
    """The WHOLE signature of a call under trace: the tree and every
    leaf's abstract value (shape, dtype, weak type, and its sharding where
    the type carries one)."""
    leaves, tree = jax.tree_util.tree_flatten((args, kwargs))
    return f"{tree}: " + ", ".join(
        repr(getattr(x, "aval", x)) for x in leaves)


_stored_module_p: Any = None


def _stored_module_primitive() -> Any:
    """`jax.export`'s `call_exported` under a name of our own: its abstract
    evaluation and its lowering rule as they are.  jax (0.9,
    `pxla.jaxpr_transfer_mem_kinds`) finds `call_exported` by NAME in a
    jitted function and COMMITS that function's results to their device
    (for modules whose results live in host memory).  A flat engine's
    arrays are uncommitted, so a step fed its own committed pool back would
    be lowered and compiled a second time under its second signature.  A
    jax without the two rules raises here, and the program is traced."""
    global _stored_module_p
    with _LOCK:
        if _stored_module_p is None:
            from jax._src.export import _export
            from jax.extend.core import Primitive
            from jax.interpreters import mlir

            p = Primitive("stored_module")
            p.multiple_results = True
            p.def_effectful_abstract_eval(
                _export._call_exported_abstract_eval)
            mlir.register_lowering(p, _export._call_exported_lowering)
            _stored_module_p = p
        return _stored_module_p


def _call_module(exported: Any, args: tuple, kwargs: dict) -> Any:
    """The stored module called on the probe's tracers.  An operand the
    module does not read (a greedy step's `top_k`) gets a constant in its
    place, so the outer ``jit`` drops it from the executable's parameters
    as it drops an operand its own trace never touched: the call by itself
    would use them all."""
    import jax.numpy as jnp

    leaves, tree = jax.tree_util.tree_flatten((args, kwargs))
    if tree != exported.in_tree:
        raise ValueError(f"called with {tree}, stored for {exported.in_tree}")
    if any(a.memory_space != jax.memory.Space.Device
           for a in exported.out_avals):
        raise ValueError("a result outside device memory")
    kept = set(exported.module_kept_var_idx)
    out = _stored_module_primitive().bind(
        *[x if i in kept else jnp.zeros(x.shape, x.dtype)
          for i, x in enumerate(leaves)], exported=exported)
    return exported.out_tree.unflatten(out)


def _from_store(e: CompileEntry, fn: Callable, donate: Any, closes_over: Any,
                args: tuple, kwargs: dict) -> Any:
    """The traced result of the program's stored module, called on the
    probe's tracers, or None where the caller has to trace ``fn``: no
    store, or a program that cannot be carried (its export, its
    serialisation or the call of its module raised)."""
    global _programs_stored, _programs_store_writes
    store = _program_store
    if store is None:
        return None
    from jax import export

    try:
        key = store.key(
            name=e.program, tags=e.tags, donate=donate,
            closes_over=closes_over,
            signature=_abstract_signature(args, kwargs))
        got = store.load(key)
        if got is None:
            avals = jax.tree.map(lambda x: x.aval, (args, kwargs))
            # the trace's `note_path_choice` notes go with the module; jax
            # reports the inner trace and lowering like any other, and the
            # listener leaves them in this birth's own trace
            _tls.notes = []
            try:
                exported = export.export(
                    jax.jit(fn, donate_argnums=donate))(*avals[0], **avals[1])
                notes = _tls.notes
            finally:
                _tls.notes = None
            wrote = store.save(key, exported, notes)
        else:
            exported, notes = got
            for note in notes:
                _note_path_choice(tuple(note))
        # a cold start runs what a warm one will: the module as the file
        # holds it
        out = _call_module(exported, args, kwargs)
        e.stored = int(got is not None)
        with _LOCK:
            if got is None:
                _programs_store_writes += wrote
            else:
                _programs_stored += 1
        return out
    except Exception as why:  # noqa: BLE001 — whatever refuses, trace it
        note_path_choice(
            "program_store", "traced",
            f"{type(why).__name__}: {str(why).splitlines()[0][:160]}"
            if str(why) else type(why).__name__, program=e.program)
        return None


@contextlib.contextmanager
def steady_scope(label: str = "steady"):
    """Mark a region where any new compilation is a tripwire hit."""
    with _LOCK:
        _steady_labels.append(label)
    try:
        yield
    finally:
        with _LOCK:
            _steady_labels.remove(label)


def note_decode_block(n: int = 1) -> None:
    """Engine hook: called once per dispatched decode block.  Feeds the
    DYN_TPU_XLALEDGER_STEADY=N self-arming warmup counter."""
    global _decode_blocks, _auto_steady_armed
    if _AUTO_STEADY_BLOCKS <= 0:
        with _LOCK:
            _decode_blocks += n
        return
    with _LOCK:
        _decode_blocks += n
        if not _auto_steady_armed and _decode_blocks >= _AUTO_STEADY_BLOCKS:
            _auto_steady_armed = True


def entries() -> List[CompileEntry]:
    with _LOCK:
        return list(_entries)


def trips() -> List[CompileEntry]:
    """Compilations that happened inside a steady scope — the session
    gate (tests/conftest.py) requires this empty."""
    with _LOCK:
        return list(_trips)


def last_entry() -> Optional[CompileEntry]:
    """Most recent attributed compile — the wedge watchdog prints this
    so a compile storm mid-test is diagnosable post-mortem."""
    with _LOCK:
        return _entries[-1] if _entries else None


def compiles_by_fn() -> Dict[str, int]:
    with _LOCK:
        return dict(_compiles_by_fn)


def note_path_choice(site: str, choice: str, reason: str, **dims) -> None:
    """Record which device program a step was traced into, and why.

    Called from the trace-time forks (`ops.paged_attention._adapt`, the
    engine's block/per-step decode choice), so it runs once per compiled
    variant and never on the hot path.  Each distinct decision is logged
    once; `summary()["path_choices"]` carries all of them for the status
    server.  A trace made for the program store keeps its notes with the
    module, and a start that takes the module from the store notes them
    again."""
    _note_path_choice((site, choice, reason,
                       ",".join(f"{k}={v}" for k, v in sorted(dims.items()))))


def _note_path_choice(key: Tuple[str, str, str, str]) -> None:
    notes = getattr(_tls, "notes", None)
    if notes is not None:
        notes.append(list(key))
    with _LOCK:
        first = key not in _path_choices
        _path_choices[key] = _path_choices.get(key, 0) + 1
        _choice_by_dims[(key[0], key[3])] = key[1]
    if first:
        import logging

        logging.getLogger(__name__).info(
            "path choice: %s -> %s (%s) [%s]", *key)


def path_choice(site: str, **dims) -> Optional[str]:
    """The choice last noted at `site` for exactly these dims, or None."""
    with _LOCK:
        return _choice_by_dims.get(
            (site, ",".join(f"{k}={v}" for k, v in sorted(dims.items()))))


def summary() -> dict:
    with _LOCK:
        return {
            "compiles_total": sum(_compiles_by_fn.values()),
            "by_fn": dict(_compiles_by_fn),
            "backend_compiles": _backend_compiles,
            "backend_compile_seconds": round(_backend_compile_secs, 3),
            "cache_hits": _cache_hits,
            "cache_misses": _cache_requests - _cache_hits,
            "program_events": _program_events,
            "programs_sub_ms": _programs_sub_ms,
            "programs_stored": _programs_stored,
            "programs_store_writes": _programs_store_writes,
            "path_choices": [
                {"site": s, "choice": c, "reason": r, "dims": d, "traces": n}
                for (s, c, r, d), n in _path_choices.items()
            ],
            "decode_blocks": _decode_blocks,
            "trips": [t.format() for t in _trips],
            "transfer_violations": dict(_violations_by_kind),
        }


def reset() -> None:
    """Test isolation: drop all recorded state (steady scopes stay)."""
    global _decode_blocks, _auto_steady_armed, _backend_compiles
    global _backend_compile_secs, _cache_hits, _cache_requests
    global _program_events, _programs_sub_ms
    global _programs_stored, _programs_store_writes
    with _LOCK:
        _program_events = _programs_sub_ms = 0
        _programs_stored = _programs_store_writes = 0
        _path_choices.clear()
        _choice_by_dims.clear()
        _backend_compile_secs = 0.0
        _cache_hits = _cache_requests = 0
        _entries.clear()
        _trips.clear()
        _compiles_by_fn.clear()
        _violations.clear()
        _violations_by_kind.clear()
        _decode_blocks = 0
        _auto_steady_armed = False
        _backend_compiles = 0


# -- monitoring backstop ------------------------------------------------------- #

_listener_installed = False


def set_program_sink(
        sink: Optional[Callable[[int, int, Dict[str, Any]], None]]) -> None:
    """`sink(t0_ns, t1_ns, attrs)` is called once for every program whose
    backend stage ends, on the thread that compiled it: `fn` (the
    program's name; "" outside a ledgered function), `sig` (8 hex digits
    over the whole signature: its readable head stays in `entries()`),
    `tags`, `role` and the stages seen (`CompileEntry.stages`).  One slot
    (`runtime/events.py` fills it); None empties it."""
    global _program_sink
    _program_sink = sink


def births_between(t0_ns: int, t1_ns: int) -> int:
    """How many programs handed to the sink began their birth on the
    CALLING thread in [t0_ns, t1_ns): a step's slice asks with the span
    of its build and dispatch and says `compiled`.  Thread-local, so no
    lock; forgets what it counted and anything older (a step in flight
    is recorded after the next was dispatched, whose programs stay)."""
    born = getattr(_tls, "born", None)
    if not born:
        return 0
    _tls.born = [t for t in born if t >= t1_ns]
    return sum(1 for t in born if t0_ns <= t < t1_ns)


def _open_birth(dur_s: float = 0.0) -> CompileEntry:
    """A birth outside any ledgered function (no `fn`, in no list), begun
    `dur_s` ago by the stage jax just reported: its lowering as a rule,
    so such a program's trace is not in its slice."""
    e = _tls.birth = CompileEntry(
        fn="", signature="", tags={}, in_steady=False, scope="",
        thread=threading.current_thread().name,
        role=contracts.current_role() or "",
        t_ns=time.monotonic_ns() - int(dur_s * 1e9))
    return e


def _us(seconds: float) -> int:
    return int(seconds * 1e6)


def _born(e: CompileEntry, backend_s: float) -> None:
    """The backend stage ended: the birth is whole."""
    global _program_events, _programs_sub_ms
    _tls.birth = None
    t1 = time.monotonic_ns()
    e.compile_us = max(0, _us(backend_s) - (e.load_us or 0))
    if not e.fn and (e.lower_us or 0) + _us(backend_s) < 1000:
        with _LOCK:
            _programs_sub_ms += 1
        return
    with _LOCK:
        _program_events += 1
    _tls.born = getattr(_tls, "born", [])[-(_BORN_MAX - 1):] + [e.t_ns]
    sink = _program_sink
    if sink is None:
        return
    attrs = {"fn": e.program[:48], "role": e.role, **e.stages()}
    if e.fn:
        attrs["sig"] = e.sig
    if e.tags:
        attrs["tags"] = ",".join(f"{k}={v}" for k, v in e.tags.items())[:40]
    try:
        sink(e.t_ns, t1, attrs)
    # lint: allow(swallowed-exception): a sink must never break a compile
    except Exception:  # noqa: BLE001
        pass


def _on_event_duration(event: str, duration: float, fun_name: str = "",
                       **kwargs) -> None:
    global _backend_compiles, _backend_compile_secs
    if getattr(_tls, "notes", None) is not None:
        return  # an export inside the open birth's trace: `_from_store`
    b = getattr(_tls, "birth", None)
    if "backend_compile" in event:
        with _LOCK:
            _backend_compiles += 1
            _backend_compile_secs += duration
        if b is None or (b.fn and b.lower_us is None):  # none, or a stale one
            b = _open_birth(duration)
        _born(b, duration)
    elif event.endswith("/jaxpr_trace_duration"):
        # the probe's own trace just ended: depth 0 again, and jax names
        # the program.  Inside it (depth > 0) these are the jitted library
        # functions it calls; any other is an unledgered function's, whose
        # birth opens at its lowering
        if (b is not None and b.fn and b.trace_us is None
                and not getattr(_tls, "trace_depth", 0)
                and fun_name in ("", b.program)):
            b.trace_us = _us(duration)
    elif event.endswith("/jaxpr_to_mlir_module_duration"):
        # the open ledgered birth's, if jax names its module (`jit_<fn>`):
        # one that was traced and never compiled must not take the next
        # program's stages
        if (b is None or not b.fn or b.lower_us is not None
                or b.program not in (fun_name or b.program)):
            b = _open_birth(duration)
        b.lower_us = _us(duration)
    elif event.endswith("/compilation_cache/cache_retrieval_time_sec"):
        (b or _open_birth(duration)).load_us = _us(duration)


def _on_event(event: str, **kwargs) -> None:
    global _cache_hits, _cache_requests
    if event.endswith("/compilation_cache/cache_hits"):
        with _LOCK:
            _cache_hits += 1
        (getattr(_tls, "birth", None) or _open_birth()).hit = 1
    elif event.endswith("/compilation_cache/compile_requests_use_cache"):
        with _LOCK:
            _cache_requests += 1
        (getattr(_tls, "birth", None) or _open_birth()).hit = 0


def _install_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    _listener_installed = True
    try:
        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration
        )
        jax.monitoring.register_event_listener(_on_event)
    # lint: allow(swallowed-exception): monitoring is a best-effort backstop; the attributed ledger works without it
    except Exception:  # noqa: BLE001
        pass


if _LEDGER_ON:
    _install_listener()


# -- transfer guard ------------------------------------------------------------ #

def _sync_allowed() -> bool:
    return getattr(_tls, "allow_depth", 0) > 0


@contextlib.contextmanager
def allow_host_sync(reason: str = ""):
    """Sanction an explicit device→host sync on a role thread (the
    drain thread's ``device_get``; any fetch a human signed off on)."""
    _tls.allow_depth = getattr(_tls, "allow_depth", 0) + 1
    try:
        yield
    finally:
        _tls.allow_depth -= 1


def note_transfer_violation(kind: str, role: str) -> None:
    with _LOCK:
        _violations_by_kind[kind] = _violations_by_kind.get(kind, 0) + 1
        if len(_violations) < _MAX_RECORDS:
            _violations.append({
                "kind": kind,
                "role": role,
                "thread": threading.current_thread().name,
            })


def transfer_violations() -> List[dict]:
    with _LOCK:
        return [dict(v) for v in _violations]


def transfer_violations_total() -> Dict[str, int]:
    with _LOCK:
        return dict(_violations_by_kind)


def _guard_check(kind: str) -> None:
    """Raise iff the current thread's role forbids implicit D2H."""
    if not _XFERCHECK:
        return  # patches may outlive a test's enable; stay inert
    if _sync_allowed():
        return
    role = contracts.current_role()
    if role not in _GUARDED_ROLES:
        return
    note_transfer_violation(kind, role)
    raise HostSyncError(
        f"implicit device->host sync ({kind}) on a {role!r}-role thread "
        f"({threading.current_thread().name}); fetch via jax.device_get "
        f"on the drain side, or wrap in xla_ledger.allow_host_sync()"
    )


_patched = False


def _array_impl_class():
    try:
        from jaxlib import xla_extension

        return xla_extension.ArrayImpl
    except Exception:  # noqa: BLE001 — jaxlib layout varies across versions
        return None


def install_transfer_guard() -> bool:
    """Idempotently patch ``ArrayImpl``'s implicit-sync dunders with the
    role check, and wrap ``jax.device_get`` in an allow scope.  Returns
    True when the patch is in place.  Process-global, but the check
    itself is role-gated per call, so unknown threads are unaffected.

    ``__array__``/``np.asarray`` is NOT covered here: numpy reads the
    buffer protocol straight from C.  The static ``host-sync`` lint and
    the native per-thread transfer guard (TPU) own that case.
    """
    global _patched
    if _patched:
        return True
    cls = _array_impl_class()
    if cls is None:
        return False

    def guarded(kind: str, orig):
        def method(self, *a, **kw):
            _guard_check(kind)
            return orig(self, *a, **kw)
        method.__name__ = getattr(orig, "__name__", kind)
        return method

    for kind, dunder in (
        ("item", "item"),
        ("float", "__float__"),
        ("int", "__int__"),
        ("bool", "__bool__"),
        ("index", "__index__"),
    ):
        orig = getattr(cls, dunder, None)
        if orig is not None and not getattr(orig, "_dyn_tpu_guard", False):
            m = guarded(kind, orig)
            m._dyn_tpu_guard = True
            try:
                setattr(cls, dunder, m)
            except TypeError:
                # immutable extension type on this jaxlib — the native
                # guard + static lint still cover role threads
                _patched = False
                return False

    if not getattr(jax.device_get, "_dyn_tpu_guard", False):
        import functools

        _orig_device_get = jax.device_get

        @functools.wraps(_orig_device_get)
        def device_get(x):
            with allow_host_sync("jax.device_get is the sanctioned sync"):
                return _orig_device_get(x)

        device_get._dyn_tpu_guard = True
        jax.device_get = device_get

    _patched = True
    return True


def thread_role_init() -> None:
    """Executor ``initializer=``: on step/drain threads (resolved from
    the thread name via ``contracts``), enter a PERSISTENT native
    ``jax.transfer_guard_device_to_host("disallow")`` — thread-local in
    jax, effective on real TPU — and ensure the Python-level patches
    (effective on CPU) are installed.  No-op on unknown threads and
    when DYN_TPU_XFERCHECK is off, so production pays nothing."""
    if not _XFERCHECK:
        return
    role = contracts.current_role()
    name = threading.current_thread().name
    if role not in _GUARDED_ROLES:
        _guard_threads[name] = f"role={role or 'none'} (exempt)"
        return
    installed = install_transfer_guard()
    ctx = jax.transfer_guard_device_to_host("disallow")
    ctx.__enter__()  # deliberately never exited: guard for the
    _tls.native_guard = ctx  # thread's whole life
    _guard_threads[name] = (
        f"role={role} d2h=disallow "
        f"(native=on, patch={'on' if installed else 'off'})"
    )


def guard_state() -> Dict[str, str]:
    """Per-thread guard status for the wedge watchdog's forensics dump."""
    return dict(_guard_threads)
