"""Concurrency contract checker tests: lint rule fixtures (positive +
negative per rule), lock-order/ABBA detection, thread-affinity units,
and the zero-cost disabled path.  The final test is the tier-1 gate:
the whole dynamo_tpu package must lint clean."""

import textwrap
import threading

import pytest

from dynamo_tpu.analysis import contracts, lockcheck
from dynamo_tpu.analysis.lint import RULES, lint_source


def findings_for(src, rule=None):
    findings, _ = lint_source(textwrap.dedent(src), path="fixture.py")
    if rule is None:
        return findings
    return [f for f in findings if f.rule == rule]


# -- lint: guarded-by --------------------------------------------------------- #

def test_guarded_by_flags_unlocked_access():
    fs = findings_for(
        """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._blocks = {}  # guarded-by: _lock

            def size(self):
                return len(self._blocks)
        """,
        "guarded-by",
    )
    assert len(fs) == 1
    assert "_blocks" in fs[0].message and fs[0].line


def test_guarded_by_accepts_locked_access_and_init():
    fs = findings_for(
        """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._blocks = {}  # guarded-by: _lock

            def size(self):
                with self._lock:
                    return len(self._blocks)
        """,
        "guarded-by",
    )
    assert fs == []


def test_guarded_by_comment_on_line_above():
    fs = findings_for(
        """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                # guarded-by: _lock
                self._blocks = {}

            def size(self):
                return len(self._blocks)
        """,
        "guarded-by",
    )
    assert len(fs) == 1


def test_guarded_by_exempts_locked_suffix_methods():
    """``*_locked`` names declare "caller holds the lock"."""
    fs = findings_for(
        """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._blocks = {}  # guarded-by: _lock

            def _evict_locked(self):
                self._blocks.clear()
        """,
        "guarded-by",
    )
    assert fs == []


# -- lint: blocking-under-lock ------------------------------------------------ #

def test_blocking_under_lock_flags_sleep_in_with():
    fs = findings_for(
        """
        import threading
        import time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def slow(self):
                with self._lock:
                    time.sleep(1)
        """,
        "blocking-under-lock",
    )
    assert len(fs) == 1
    assert "time.sleep" in fs[0].message


def test_blocking_outside_lock_is_clean():
    fs = findings_for(
        """
        import threading
        import time

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def slow(self):
                with self._lock:
                    n = 1
                time.sleep(n)
        """,
        "blocking-under-lock",
    )
    assert fs == []


def test_blocking_under_lock_through_call_graph():
    """One level of intra-module resolution: a method that blocks,
    called under the lock, is flagged at the call site."""
    fs = findings_for(
        """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def _write(self):
                open("/tmp/x", "w").write("hi")

            def save(self):
                with self._lock:
                    self._write()
        """,
        "blocking-under-lock",
    )
    assert len(fs) == 1
    assert "_write" in fs[0].message


# -- lint: blocking-in-async -------------------------------------------------- #

def test_blocking_in_async_flags_bare_open():
    fs = findings_for(
        """
        async def handler():
            with open("/etc/hosts") as f:
                return f.read()
        """,
        "blocking-in-async",
    )
    assert len(fs) == 1


def test_blocking_in_async_accepts_to_thread_and_sync_def():
    fs = findings_for(
        """
        import asyncio

        async def handler():
            return await asyncio.to_thread(read_it)

        def read_it():
            with open("/etc/hosts") as f:
                return f.read()
        """,
        "blocking-in-async",
    )
    assert fs == []


# -- lint: thread-hygiene ----------------------------------------------------- #

def test_thread_hygiene_requires_name_and_daemon():
    fs = findings_for(
        """
        import threading

        def go():
            t = threading.Thread(target=print)
            t.start()
        """,
        "thread-hygiene",
    )
    assert len(fs) == 1


def test_thread_hygiene_accepts_named_daemon():
    fs = findings_for(
        """
        import threading

        def go():
            t = threading.Thread(target=print, name="worker", daemon=True)
            t.start()
        """,
        "thread-hygiene",
    )
    assert fs == []


# -- lint: bare-except / swallowed-exception ---------------------------------- #

def test_bare_except_flagged():
    fs = findings_for(
        """
        def f():
            try:
                g()
            except:
                pass
        """,
    )
    assert [f.rule for f in fs] == ["bare-except"]


def test_swallowed_exception_flagged_and_narrow_ok():
    src = """
    def f():
        try:
            g()
        except Exception:
            pass

    def h():
        try:
            g()
        except OSError:
            pass
    """
    fs = findings_for(src, "swallowed-exception")
    assert len(fs) == 1


def test_swallowed_exception_ok_when_handled_or_logged():
    fs = findings_for(
        """
        import logging

        def f():
            try:
                g()
            except Exception:
                logging.exception("g failed")
        """,
        "swallowed-exception",
    )
    assert fs == []


# -- lint: allowlist ---------------------------------------------------------- #

def test_allow_comment_suppresses_and_is_reported():
    src = """
    import threading
    import time

    class C:
        def __init__(self):
            self._lock = threading.Lock()

        def slow(self):
            with self._lock:
                # lint: allow(blocking-under-lock): fixture needs it
                time.sleep(1)
    """
    findings, allows = lint_source(textwrap.dedent(src), path="fixture.py")
    assert findings == []
    assert len(allows) == 1
    assert allows[0].rule == "blocking-under-lock"
    assert allows[0].reason == "fixture needs it"


def test_allow_comment_wrong_rule_does_not_suppress():
    src = """
    import threading
    import time

    class C:
        def __init__(self):
            self._lock = threading.Lock()

        def slow(self):
            with self._lock:
                # lint: allow(guarded-by): wrong rule
                time.sleep(1)
    """
    findings, _ = lint_source(textwrap.dedent(src), path="fixture.py")
    assert [f.rule for f in findings] == ["blocking-under-lock"]


def test_rules_registry_is_stable():
    assert set(RULES) == {
        "guarded-by", "blocking-under-lock", "blocking-in-async",
        "thread-hygiene", "bare-except", "swallowed-exception",
    }


# -- lockcheck: lock-order graph ---------------------------------------------- #

@pytest.fixture
def clean_lockcheck():
    lockcheck.reset()
    yield
    lockcheck.reset()


def test_abba_cycle_detected(clean_lockcheck):
    """The classic ABBA inversion is flagged from the order graph alone —
    no run has to actually deadlock."""
    a = lockcheck.TrackedLock("fixture.A")
    b = lockcheck.TrackedLock("fixture.B")

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    t1 = threading.Thread(target=ab, name="fixture-ab", daemon=True)
    t1.start(); t1.join(5)
    t2 = threading.Thread(target=ba, name="fixture-ba", daemon=True)
    t2.start(); t2.join(5)

    cycles = lockcheck.cycles()
    assert cycles == [["fixture.A", "fixture.B"]]
    with pytest.raises(AssertionError, match="lock-order cycle"):
        lockcheck.assert_clean()


def test_consistent_order_is_clean(clean_lockcheck):
    a = lockcheck.TrackedLock("fixture.A")
    b = lockcheck.TrackedLock("fixture.B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert lockcheck.cycles() == []
    lockcheck.assert_clean()


def test_name_level_classes_catch_cross_instance_inversion(clean_lockcheck):
    """Two distinct instance PAIRS, one inversion between the two lock
    NAMES — lockdep-style classing reports it even though no single pair
    was ever taken both ways."""
    a1 = lockcheck.TrackedLock("fixture.A")
    b1 = lockcheck.TrackedLock("fixture.B")
    a2 = lockcheck.TrackedLock("fixture.A")
    b2 = lockcheck.TrackedLock("fixture.B")
    with a1:
        with b1:
            pass
    with b2:
        with a2:
            pass
    assert lockcheck.cycles() == [["fixture.A", "fixture.B"]]


def test_self_deadlock_recorded_not_wedged(clean_lockcheck):
    """Re-acquiring a non-reentrant TrackedLock is recorded as a certain
    deadlock BEFORE the thread wedges (the fixture uses non-blocking
    acquire so the test itself cannot hang)."""
    a = lockcheck.TrackedLock("fixture.self")
    with a:
        # blocking re-acquire would wedge this thread for real; the
        # recorder keys on (same instance, non-reentrant, blocking)
        a._note_order(lockcheck._held_stack(), blocking=True)
    rep = lockcheck.report()
    assert len(rep["self_deadlocks"]) == 1
    assert rep["self_deadlocks"][0]["lock"] == "fixture.self"
    with pytest.raises(AssertionError, match="self-deadlock"):
        lockcheck.assert_clean()


def test_hold_time_stats_and_held_by_thread(clean_lockcheck):
    a = lockcheck.TrackedLock("fixture.hold")
    with a:
        held = lockcheck.held_locks_by_thread()
        me = threading.current_thread().name
        assert held.get(me) == ["fixture.hold"]
    stats = lockcheck.hold_time_stats()
    assert stats["fixture.hold"]["acquisitions"] == 1
    assert stats["fixture.hold"]["p99_us"] >= 0
    assert lockcheck.held_locks_by_thread() == {}


def test_blocking_probe_records_under_lock(clean_lockcheck):
    a = lockcheck.TrackedLock("fixture.probe")
    # a private stand-in, NOT time.sleep: under DYN_TPU_LOCKCHECK=1 the
    # global probes have already wrapped the real primitives
    probed = lockcheck.wrap_blocking(lambda: None, "fixture.block")
    with a:
        probed()
    evs = lockcheck.blocking_events()
    assert len(evs) == 1
    assert evs[0]["call"] == "fixture.block"
    assert evs[0]["locks"] == ["fixture.probe"]
    # informational: blocking events alone never fail assert_clean
    lockcheck.assert_clean()


def test_reentrant_tracked_lock_reenters(clean_lockcheck):
    r = lockcheck.TrackedLock("fixture.r", reentrant=True)
    with r:
        with r:
            pass
    assert lockcheck.report()["self_deadlocks"] == []


# -- contracts: thread affinity ----------------------------------------------- #

@pytest.fixture
def raise_mode(monkeypatch):
    monkeypatch.setattr(contracts, "_MODE", "raise")
    yield
    contracts.clear_affinity_violations()


@pytest.fixture
def record_mode(monkeypatch):
    monkeypatch.setattr(contracts, "_MODE", "record")
    yield
    contracts.clear_affinity_violations()


def run_on_thread(name, fn):
    """Run fn on a fresh thread with the given name; re-raise its
    exception here."""
    box = {}

    def tgt():
        try:
            box["r"] = fn()
        except BaseException as e:  # noqa: BLE001 — relayed to the test thread
            box["e"] = e

    t = threading.Thread(target=tgt, name=name, daemon=True)
    t.start(); t.join(5)
    if "e" in box:
        raise box["e"]
    return box.get("r")


def test_affine_raises_on_wrong_role(raise_mode):
    @contracts.affine("step")
    def step_only():
        return "ok"

    with pytest.raises(contracts.AffinityError, match="step_only"):
        run_on_thread("kvbm-offload", step_only)


def test_affine_passes_on_declared_role(raise_mode):
    @contracts.affine("step")
    def step_only():
        return "ok"

    assert run_on_thread("jax-engine-step_0", step_only) == "ok"


def test_affine_unmanaged_thread_exempt(raise_mode):
    """Threads the role map doesn't know (unit tests driving components
    synchronously) have no role and never trip contracts."""
    @contracts.affine("step")
    def step_only():
        return "ok"

    assert run_on_thread("pytest-driver", step_only) == "ok"


def test_affine_loop_role_from_running_loop(raise_mode):
    import asyncio

    @contracts.affine("drain")
    def drain_only():
        return "ok"

    async def drive():
        drain_only()

    with pytest.raises(contracts.AffinityError, match="'loop'"):
        asyncio.new_event_loop().run_until_complete(drive())


def test_register_thread_role_overrides_name(raise_mode):
    @contracts.affine("drain")
    def drain_only():
        return "ok"

    def tagged():
        contracts.register_thread_role("drain")
        return drain_only()

    assert run_on_thread("custom-g4-loop", tagged) == "ok"


def test_affine_records_instead_of_raising(record_mode):
    @contracts.affine("step")
    def step_only():
        return "ok"

    # record mode completes the call AND logs the violation (deduped)
    assert run_on_thread("kvbm-offload", step_only) == "ok"
    assert run_on_thread("kvbm-offload", step_only) == "ok"
    vs = contracts.affinity_violations()
    assert len(vs) == 1
    assert vs[0]["count"] == 2
    assert vs[0]["actual"] == "drain"
    with pytest.raises(AssertionError, match="affinity"):
        lockcheck.assert_clean()
    contracts.clear_affinity_violations()
    lockcheck.assert_clean()


def test_affine_async_checked_in_coroutine(raise_mode):
    import asyncio

    @contracts.affine("step")
    async def step_coro():
        return "ok"

    async def drive():
        await step_coro()

    with pytest.raises(contracts.AffinityError, match="step_coro"):
        asyncio.new_event_loop().run_until_complete(drive())


# -- disabled path is zero-cost ----------------------------------------------- #

def test_affine_is_identity_when_off():
    """Production builds must pay NOTHING: the decorator hands back the
    original function object — no wrapper frame on the decode hot path."""
    if contracts.checks_mode() != "off":
        pytest.skip("checks enabled in this session")

    def f():
        return 1

    assert contracts.affine("step")(f) is f


def test_make_lock_is_plain_lock_when_off():
    if contracts.checks_mode() != "off":
        pytest.skip("checks enabled in this session")
    lk = contracts.make_lock("fixture.plain")
    assert isinstance(lk, type(threading.Lock()))
    cond = contracts.make_condition("fixture.cond")
    assert isinstance(cond, threading.Condition)


def test_disabled_overhead_micro_bench():
    """Calling through an off-mode @affine function must cost the same
    as calling the function directly (identity ⇒ literally the same
    callable): one Python-level call a call, counted.  A tripwire against
    someone reintroducing a wrapper on the off path."""
    if contracts.checks_mode() != "off":
        pytest.skip("checks enabled in this session")
    from dynamo_tpu.testing import counted_calls

    def f(x):
        return x + 1

    g = contracts.affine("step")(f)
    assert g is f
    n = 1_000
    with counted_calls() as c:
        for i in range(n):
            g(i)
    assert c.total == n, dict(c.names)


# -- the tier-1 gate: the package lints clean --------------------------------- #

def test_dynamo_tpu_package_lints_clean():
    import scripts.lint_concurrency as lc

    findings, allows = lc.run()
    assert findings == [], "\n" + "\n".join(f.format() for f in findings)
    # every allowlist entry carries a justification by construction of
    # the regex; keep the count visible so growth is a conscious choice
    assert len(allows) < 60
