"""Test fixtures.

JAX tests run on a virtual 8-device CPU mesh (no TPU pod needed), mirroring
the reference's strategy of testing distributed behavior with local
subprocesses + simulators (reference tests/conftest.py:195
EtcdServer/NatsServer fixtures and the mocker engine).

pytest-asyncio is not available in this image, so `async def` tests are run
via a pytest_pyfunc_call hook in a fresh event loop.  Use the async context
managers in dynamo_tpu.testing instead of async fixtures.
"""

import asyncio
import inspect
import os
import threading

# Tests are pinned to the CPU backend (8 virtual devices): both must be
# set before jax initializes anywhere in the test process.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import pytest


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    assert jax.default_backend() == "cpu" and len(jax.devices()) == 8


# Files whose tests build engine after engine of one tiny model and lost a
# sixth of their seconds or more to this (PERF.md, "Tier-1"; the files
# whose engines differ test by test gained nothing and are not named).  Never
# one that counts BACKEND compiles (test_xla_ledger, test_program_store,
# test_startup_events).
_SHARES_EXECUTABLES = {
    "test_block_ladder", "test_deepseek_v3", "test_engine", "test_kvbm",
    "test_model", "test_multimodal", "test_nemotron_h", "test_phi4flash",
    "test_prefill_batching", "test_prefill_overlap", "test_quantization",
    "test_smallthinker", "test_wide_ep", "test_xing4_0",
}


@pytest.fixture(scope="module", autouse=True)
def shared_executables(request, tmp_path_factory):
    """Engines built alike compile alike, ONCE: XLA's persistent cache, on
    for the modules named above, in a directory of this worker's own that
    dies with the session.  An `async` test has a loop of its own and an
    engine belongs to one loop, so such a file builds an engine a test, and
    each engine's `Layout` jits its own closures: XLA compiled the same tiny
    program anew every test, a third of such a test's time.  What is
    traced, lowered and counted by the compile ledger is as before."""
    if request.module.__name__ not in _SHARES_EXECUTABLES:
        yield
        return
    from jax.experimental.compilation_cache import compilation_cache as cc

    # one directory a worker: `numbered=False` finds it again
    directory = tmp_path_factory.getbasetemp() / "xla_executables"
    on = {"jax_compilation_cache_dir": str(directory),
          "jax_persistent_cache_min_compile_time_secs": 0.0,
          "jax_persistent_cache_min_entry_size_bytes": -1}
    was = {name: getattr(jax.config, name) for name in on}
    for name, value in on.items():
        jax.config.update(name, value)
    cc.reset_cache()
    yield
    for name, value in was.items():
        jax.config.update(name, value)
    cc.reset_cache()


# -- wedge forensics ----------------------------------------------------------- #
#
# A wedged test (thread stuck in a C call, ABBA deadlock, drain thread
# waiting on a dead loop) used to surface only as the driver's opaque
# suite-level kill.  The watchdog arms a per-test soft deadline: on
# overrun it dumps every thread's stack — and, when DYN_TPU_LOCKCHECK=1,
# which tracked locks each thread was holding — to the REAL stderr
# (pytest's capture would eat it), then lets the test keep running so
# the hard timeout still owns the kill.

_WEDGE_SOFT_DEADLINE = float(os.environ.get("DYN_TPU_WEDGE_TIMEOUT", "570"))

# Dup'd REAL stderr, captured in pytest_configure while capture is
# suspended: pytest's fd-level capture redirects fd 2 to a temp file
# during tests, and a wedge dump into a temp file that dies with the
# killed process is no dump at all.
_WEDGE_STDERR = None


def _wedge_stderr():
    import sys

    return _WEDGE_STDERR if _WEDGE_STDERR is not None else sys.__stderr__


def _dump_wedge_forensics(nodeid: str) -> None:
    import faulthandler

    err = _wedge_stderr()
    try:
        err.write(
            f"\n=== WEDGE WATCHDOG: {nodeid} still running after "
            f"{_WEDGE_SOFT_DEADLINE:.0f}s — thread dump follows ===\n"
        )
        try:
            from dynamo_tpu.analysis import contracts, lockcheck

            if contracts.checks_mode() == "record":
                held = lockcheck.held_locks_by_thread()
                err.write(f"held tracked locks: {held or '{}'}\n")
        except Exception:  # noqa: BLE001 — forensics must not mask the dump
            pass
        try:
            # a compile storm mid-test shows up as the last ledger entry;
            # a wedged role thread shows its transfer-guard state
            from dynamo_tpu.analysis import xla_ledger

            guards = xla_ledger.guard_state()
            if guards:
                err.write(f"transfer-guard state: {guards}\n")
            last = xla_ledger.last_entry()
            if last is not None:
                err.write(
                    f"last xla compile ({len(xla_ledger.entries())} "
                    f"total): {last.format()}\n"
                )
        except Exception:  # noqa: BLE001 — forensics must not mask the dump
            pass
        try:
            # what the wedged test was waiting on: every attributed task
            # still pending, plus the resource-account balances
            from dynamo_tpu.analysis import leak_ledger

            if leak_ledger.leakcheck_enabled():
                pending = leak_ledger.pending_task_table()
                if pending:
                    err.write(f"pending tasks ({len(pending)}):\n")
                    for line in pending:
                        err.write(f"  {line}\n")
                imb = leak_ledger.imbalances()
                if imb:
                    err.write(f"leak-ledger imbalances: {imb}\n")
        except Exception:  # noqa: BLE001 — forensics must not mask the dump
            pass
        faulthandler.dump_traceback(file=err)
        err.write("=== end wedge dump ===\n")
        err.flush()
    except Exception:  # noqa: BLE001 — a dead stderr must not crash the timer
        pass


@pytest.fixture(autouse=True)
def _wedge_watchdog(request):
    if os.environ.get("DYN_TPU_WEDGE_WATCHDOG", "1") in ("", "0"):
        yield
        return
    import faulthandler
    import threading

    # Python-level timer first: it can resolve held-lock names.  The
    # faulthandler C watchdog backstops it 30s later — it fires even
    # when every Python thread is wedged behind the GIL.
    timer = threading.Timer(
        _WEDGE_SOFT_DEADLINE, _dump_wedge_forensics, args=(request.node.nodeid,)
    )
    timer.name = "wedge-watchdog"
    timer.daemon = True
    timer.start()
    faulthandler.dump_traceback_later(
        _WEDGE_SOFT_DEADLINE + 30, exit=False, file=_wedge_stderr()
    )
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        timer.cancel()


# -- lockcheck session gate ----------------------------------------------------- #

def pytest_sessionstart(session):
    """Under DYN_TPU_LOCKCHECK=1, give subprocesses (chaos workers) a
    directory to drop nonclean lockcheck reports into."""
    try:
        from dynamo_tpu.analysis import contracts
    except Exception:  # noqa: BLE001 — collection must survive a broken package
        return
    if contracts.checks_mode() != "record":
        return
    if not os.environ.get("DYN_TPU_LOCKCHECK_DIR"):
        import tempfile

        os.environ["DYN_TPU_LOCKCHECK_DIR"] = tempfile.mkdtemp(
            prefix="dyn-tpu-lockcheck-"
        )


def _ledger_gate(session) -> None:
    """The compile-ledger acceptance gate (always on next to lockcheck):
    the session must end with zero steady-state recompile trips and
    zero transfer-guard violations.  Tests that deliberately provoke
    either must ``xla_ledger.reset()`` before returning."""
    import sys

    try:
        from dynamo_tpu.analysis import xla_ledger
    except Exception:  # noqa: BLE001 — no gate without the package
        return
    if not xla_ledger.ledger_enabled():
        return
    s = xla_ledger.summary()
    print(
        f"\nxla ledger: {s['compiles_total']} attributed compiles "
        f"({s['backend_compiles']} backend), {s['decode_blocks']} decode "
        f"blocks, {len(s['trips'])} steady-state trips, "
        f"{sum(s['transfer_violations'].values())} transfer violations"
    )
    problems = [f"steady-state recompile: {t}" for t in s["trips"]]
    problems += [
        f"transfer-guard violation: {kind} ×{n}"
        for kind, n in s["transfer_violations"].items()
    ]
    if problems:
        print("XLA LEDGER GATE FAILED:", file=sys.stderr)
        for p in problems:
            print("  " + p, file=sys.stderr)
        session.exitstatus = 1
        raise pytest.UsageError(
            f"xla ledger gate: {len(problems)} problem(s) — see above"
        )


# nodeids of tests that failed — a failed test abandons its resources
# mid-body (shutdown never runs), and that failure is already reported;
# the leak gate excuses debris attributed to them instead of
# double-reporting it
_failed_nodeids: set = set()


def pytest_runtest_logreport(report):
    if report.failed:
        _failed_nodeids.add(report.nodeid)


def _leak_gate(session) -> None:
    """The DYN_TPU_LEAKCHECK=1 acceptance gate: the session must end
    with zero orphaned tasks, zero swallowed task exceptions, zero
    unjoined repo threads, and balanced page/lease accounts.  Tests
    that deliberately provoke a leak must ``leak_ledger.reset()``
    before returning.  Records owned by a FAILED test are excused —
    the failure itself is the report."""
    import sys

    try:
        from dynamo_tpu.analysis import leak_ledger
    except Exception:  # noqa: BLE001 — no gate without the package
        return
    if not leak_ledger.leakcheck_enabled():
        return
    s = leak_ledger.summary()
    imb = s["imbalances"]
    orphans = [o for o in s["orphans"]
               if o.get("owner") not in _failed_nodeids]
    swallowed = [w for w in s["swallowed"]
                 if w.get("owner") not in _failed_nodeids]
    excused = ((len(s["orphans"]) - len(orphans))
               + (len(s["swallowed"]) - len(swallowed)))
    print(
        f"\nleak ledger: {s['tasks_tracked']} tasks tracked "
        f"({s['tasks_active']} active), {len(orphans)} orphaned, "
        f"{len(swallowed)} swallowed exceptions, "
        f"{len(s['leaked_threads'])} leaked threads, "
        f"pages imbalance {imb.get('pages', 0)}, "
        f"leases outstanding {imb.get('leases', 0)}"
    )
    if excused:
        print(f"leak ledger: {excused} record(s) excused "
              f"(owned by {len(_failed_nodeids)} failed test(s))")
    problems = [f"orphaned task: {o}" for o in orphans]
    problems += [f"swallowed task exception: {w}" for w in swallowed]
    problems += [f"unjoined thread: {t}" for t in s["leaked_threads"]]
    problems += [f"account imbalance: {k} = {v}" for k, v in imb.items()]
    if problems:
        print("LEAK LEDGER GATE FAILED:", file=sys.stderr)
        for p in problems:
            print("  " + p, file=sys.stderr)
        session.exitstatus = 1
        raise pytest.UsageError(
            f"leak ledger gate: {len(problems)} problem(s) — see above"
        )


def pytest_sessionfinish(session, exitstatus):
    """The DYN_TPU_LOCKCHECK=1 acceptance gate: the whole session (chaos
    subprocesses included) must record zero lock-order cycles, zero
    certain self-deadlocks, and zero thread-affinity violations.
    The compile-ledger gate (zero steady-state recompiles, zero
    transfer-guard violations) runs unconditionally alongside it; the
    leak-ledger gate joins them under DYN_TPU_LEAKCHECK=1."""
    _leak_gate(session)
    _ledger_gate(session)
    try:
        from dynamo_tpu.analysis import contracts, lockcheck
    except Exception:  # noqa: BLE001 — no gate without the package
        return
    if contracts.checks_mode() != "record":
        return
    import sys

    rep = lockcheck.report()
    problems = []
    try:
        lockcheck.assert_clean(rep)
    except AssertionError as e:
        problems.append(str(e))
    sub_dir = os.environ.get("DYN_TPU_LOCKCHECK_DIR", "")
    if sub_dir and os.path.isdir(sub_dir):
        for name in sorted(os.listdir(sub_dir)):
            if name.startswith("lockcheck-") and name.endswith(".json"):
                problems.append(
                    "nonclean subprocess lockcheck report: "
                    + os.path.join(sub_dir, name)
                )
    print(
        f"\nlockcheck: {rep['acquired_total']} acquisitions, "
        f"{len(rep['edges'])} order edges, {len(rep['cycles'])} cycles, "
        f"{len(rep['self_deadlocks'])} self-deadlocks, "
        f"{len(rep['affinity_violations'])} affinity violations"
    )
    if problems:
        print("LOCKCHECK GATE FAILED:", file=sys.stderr)
        for p in problems:
            print("  " + p, file=sys.stderr)
        session.exitstatus = 1
        raise pytest.UsageError(
            f"lockcheck gate: {len(problems)} problem(s) — see above"
        )


def pytest_configure(config):
    """Build the native C++ libs when a toolchain is present so the
    native-twin tests actually run instead of rotting as skips."""
    global _WEDGE_STDERR
    import sys

    try:
        # capture is suspended during configure, so fd 2 is the real
        # terminal here — dup it for the wedge watchdog's dumps
        _WEDGE_STDERR = os.fdopen(os.dup(sys.__stderr__.fileno()), "w")
    except OSError:
        _WEDGE_STDERR = None
    config.addinivalue_line(
        "markers",
        "async_timeout(seconds): per-test cap for async tests (default 600)",
    )
    config.addinivalue_line(
        "markers",
        "timeout(seconds): documented cap for subprocess-heavy tests "
        "(inert without pytest-timeout; the harness async cap governs)",
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (`-m 'not slow'`)",
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection scenario over the operator-managed stack "
        "(tests/test_chaos.py; deliberately NOT slow — the 5 core "
        "kill/partition scenarios are tier-1 gates, select with -m chaos)",
    )
    import shutil
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    native = os.path.join(root, "native")
    if shutil.which("make") and shutil.which(os.environ.get("CXX", "g++")):
        try:
            subprocess.run(
                ["make", "-C", native, "all"], check=True,
                capture_output=True, timeout=120,
            )
        except (subprocess.CalledProcessError, subprocess.TimeoutExpired) as e:
            # warn, don't abort: pure-Python suites must stay runnable on a
            # half-broken toolchain; the native tests themselves then skip
            out = getattr(e, "stderr", b"") or b""
            import warnings

            warnings.warn(
                f"native build failed (native tests will skip): "
                f"{out.decode(errors='replace')[-500:]}",
                stacklevel=1,
            )


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames  # noqa: SLF001
        }
        # 120s proved flaky under the full suite: the pooled-mixed e2e runs
        # ~110s alone (XLA:CPU compiles), so any suite-wide slowdown tipped
        # it over and the resulting teardown-mid-step cascade poisoned the
        # run (VERDICT r4 weak #1).  Generous per-test cap; the real guard
        # against hangs is the driver's suite-level timeout.
        timeout = 600
        marker = pyfuncitem.get_closest_marker("async_timeout")
        if marker and marker.args:
            timeout = marker.args[0]
        loop = asyncio.new_event_loop()
        try:
            from dynamo_tpu.analysis import leak_ledger
        except Exception:  # noqa: BLE001 — tests must run without the package
            leak_ledger = None
        if leak_ledger is not None:
            # attribute every task the test spawns to its nodeid
            leak_ledger.install_loop(loop, owner=pyfuncitem.nodeid)
        threads_before = {t.ident for t in threading.enumerate()}
        snap = (leak_ledger.snapshot()
                if leak_ledger is not None and leak_ledger.leakcheck_enabled()
                else None)
        ok = False
        try:
            loop.run_until_complete(
                asyncio.wait_for(fn(**kwargs), timeout=timeout)
            )
            ok = True
        finally:
            # Cancel stragglers (watch loops etc.) so loop.close() is
            # quiet — on FAILURE too, or the abandoned tasks are GC'd
            # later as destroyed-pending noise blamed on this test.
            try:
                pending = [t for t in asyncio.all_tasks(loop)
                           if not t.done()]
                for t in pending:
                    t.cancel()
                if pending:
                    loop.run_until_complete(
                        asyncio.gather(*pending, return_exceptions=True)
                    )
            except Exception:  # noqa: BLE001 — best-effort after a failure
                pass
            if leak_ledger is not None:
                if ok:
                    # anything still pending survived the owner's shutdown
                    # AND the straggler sweep — a real orphan
                    leak_ledger.note_loop_closing(loop)
                else:
                    # a failed test legitimately abandons its engines
                    # (pytest skips the rest of the body, shutdown
                    # included); the failure is the report — roll the
                    # ledger back to its pre-test state and excuse the
                    # thread debris instead of double-reporting it at
                    # the session gate
                    if snap is not None:
                        leak_ledger.restore(snap)
                    leak_ledger.excuse_new_threads(
                        threads_before, owner=pyfuncitem.nodeid)
            # Join default-executor threads before closing: loop.close()
            # does NOT wait for them, and a leaked worker that later posts
            # call_soon_threadsafe hits "Event loop is closed" and competes
            # with the next tests for CPU.  Bounded so one genuinely wedged
            # thread can't hang the whole suite.
            try:
                loop.run_until_complete(
                    loop.shutdown_default_executor(timeout=10)
                )
            except Exception:  # noqa: BLE001
                pass
            loop.close()
        return True
    return None
