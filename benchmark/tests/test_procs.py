"""No process outlives the owner of a run (lib/procs.py `ProcSet`): on
`python -c` sleepers, by every way a run can end.  The sleepers carry a tag
of the TEST's own in their environment (`BENCH_TEST_TAG`), and "gone" means
no live process with it in /proc/*/environ 5 s later: the tests do not lean
on the tag the owner itself sweeps by."""

import functools
import os
import signal
import subprocess
import sys
import time

import pytest

from lib import procs
from proctags import alive, gone_within, new_tag

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PY = sys.executable

SLEEPER = "import time; time.sleep(300)"
STUBBORN = ("import signal, time; signal.signal(signal.SIGTERM, "
            "signal.SIG_IGN); print('deaf', flush=True); time.sleep(300)")
# dies of SIGTERM and leaves a grandchild that ignores it, in a session of
# its own: neither a signal to the child nor one to its group reaches it
PARENT_OF_ONE = (
    "import subprocess, sys, time; subprocess.Popen([sys.executable, '-c', "
    f"{STUBBORN!r}], start_new_session=True); print('forked', flush=True); "
    "time.sleep(300)")


def wait_for(path, needle, timeout=30):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if needle in procs.log_tail(path):
            return
        time.sleep(0.05)
    raise AssertionError(f"{needle!r} never came in {path}")


def owner(tag):
    return procs.ProcSet(dict(os.environ, BENCH_TEST_TAG=tag))


def between_two_spawns(ps, tmp):
    ps.spawn([PY, "-c", SLEEPER], "first", str(tmp / "first.log"))
    raise procs.RunFailure("the second child never got READY")


def deaf_child(ps, tmp):
    ps.spawn([PY, "-c", STUBBORN], "deaf", str(tmp / "deaf.log"))
    wait_for(str(tmp / "deaf.log"), "deaf")


def grandchild(ps, tmp):
    ps.spawn([PY, "-c", PARENT_OF_ONE], "parent", str(tmp / "parent.log"))
    wait_for(str(tmp / "parent.log"), "deaf")


def waited_for_child(ps, tmp):
    rc, out, _ = ps.run([PY, "-c", "print('answer')"], "short")
    assert (rc, out.strip()) == (0, "answer") and not ps.procs
    ps.spawn([PY, "-c", SLEEPER], "beside", str(tmp / "beside.log"))
    with pytest.raises(procs.RunFailure, match="did not finish"):
        ps.run([PY, "-c", SLEEPER], "too-long", timeout=0.5)
    raise KeyError("a reader's, after the window")


@pytest.mark.parametrize("scene,raised", [
    (between_two_spawns, procs.RunFailure), (deaf_child, None),
    (grandchild, None), (waited_for_child, KeyError)])
def test_leaving_the_guard_leaves_no_process(tmp_path, scene, raised):
    tag = new_tag()
    ps = owner(tag)
    ps.stop = functools.partial(ps.stop, timeout=1.0)  # the deaf one's wait
    before, t0 = signal.getsignal(signal.SIGTERM), time.monotonic()
    try:
        with ps.guard():
            assert signal.getsignal(signal.SIGTERM) is not before
            scene(ps, tmp_path)
            assert alive(tag)
    except (procs.RunFailure, KeyError) as e:
        assert raised is not None and isinstance(e, raised)
    else:
        assert raised is None
    assert not alive(tag), "stop() returns when no process is left"
    assert time.monotonic() - t0 < 30
    assert not ps.procs
    assert signal.getsignal(signal.SIGTERM) is before  # handlers restored


OWNER = """
import functools, os, sys, time
sys.path.insert(0, {bench!r})
from lib import procs
ps = procs.ProcSet(dict(os.environ))
ps.stop = functools.partial(ps.stop, timeout=1.0)
with ps.guard():
    ps.spawn([sys.executable, "-c", {parent!r}], "parent", {log!r})
    while "deaf" not in procs.log_tail({log!r}):
        time.sleep(0.05)
    print("READY", flush=True)
    time.sleep(300)
print("RESULT", flush=True)
"""


@pytest.mark.parametrize("sig", [signal.SIGTERM, signal.SIGINT,
                                 signal.SIGHUP, signal.SIGKILL])
def test_a_signalled_owner_takes_child_and_grandchild_with_it(tmp_path, sig):
    tag = new_tag()
    code = OWNER.format(bench=BENCH, parent=PARENT_OF_ONE,
                        log=str(tmp_path / "parent.log"))
    p = subprocess.Popen([PY, "-c", code], stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True,
                         env=dict(os.environ, BENCH_TEST_TAG=tag))
    try:
        assert p.stdout.readline().strip() == "READY"
        assert len(alive(tag)) >= 3  # owner, child, grandchild (+ watchdog)
        p.send_signal(sig)
        out, err = p.communicate(timeout=30)
    finally:
        p.kill()
    assert p.returncode != 0 and "RESULT" not in out
    if sig != signal.SIGKILL:
        assert p.returncode == 128 + sig
        assert err.count("BENCHMARK RUN FAILED:") == 1 and sig.name in err
    assert gone_within(tag), f"left running: {alive(tag)}"


def test_a_stack_that_fails_at_its_second_child_leaves_no_first(tmp_path):
    """`Stack.__init__` starts the control plane, then the frontend; the
    frontend refuses its router mode, and the control plane must not stay."""
    root = os.path.dirname(BENCH)
    tag = new_tag()
    ps = procs.ProcSet(dict(
        os.environ, BENCH_TEST_TAG=tag, JAX_PLATFORMS="cpu",
        PYTHONPATH=root + os.pathsep + os.environ.get("PYTHONPATH", "")))
    with pytest.raises(procs.RunFailure, match="process died"):
        with ps.guard():
            procs.Stack(ps, str(tmp_path), "no-such-router-mode")
    assert "READY" in procs.log_tail(str(tmp_path / "control.log"))
    assert gone_within(tag, 1.0), f"left running: {alive(tag)}"
