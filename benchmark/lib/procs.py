"""Child processes of one benchmark run.  ONE object, `ProcSet`, starts every
child `run.py` has (device probe, checkpoint writer, reference child,
control plane, frontend, worker, trace loader) and none of them, nor a
descendant of one, outlives the run, however the run ends:

- a normal end, a `RunFailure` or any other exception: `run.py` leaves
  `ProcSet.guard()`, which calls `stop()`: SIGTERM newest first, wait until
  each is GONE, SIGKILL past the deadline, then the watchdog (below) is let
  go and SIGKILLs whatever still carries the run's tag on its way out (a
  descendant that its child left behind);
- SIGTERM, SIGINT, SIGHUP to `run.py`: the handler `guard()` installs makes
  that same stop and leaves with 128 + the signal's number and no result;
- SIGKILL of `run.py`, which no handler sees: a watchdog child holds the
  read end of a pipe whose only write end is `run.py`'s.  The kernel closes
  it when `run.py` is gone by whatever cause; the watchdog then SIGKILLs
  every tagged process until none is left, and exits.

The tag is `BENCHMARK_RUN_TAG=<random>` in every child's environment, which
descendants inherit; "the run's processes" are those whose
`/proc/<pid>/environ` carries it.  Why a watchdog and a tag, not
`prctl(PR_SET_PDEATHSIG)` or a session whose group is signalled: the death
signal reaches direct children only, is tied to the spawning thread and
needs a `preexec_fn`; a group signal needs the signaller alive; a tag sweep
by a process that outlives the owner takes descendants in any session and
a child started a moment before the owner died.

Spawning is copied from scripts/_verify_harness.py and chip_smoke.py's
`Stack` (proven on the chip in PR 22) so that the yardstick imports nothing
a later PR may change.  One process per chip: the parent never imports jax;
control plane and frontend run with JAX_PLATFORMS=cpu; the worker alone
holds the chip."""

import contextlib
import json
import os
import signal
import socket
import subprocess
import sys
import time
import uuid

TAG_VAR = "BENCHMARK_RUN_TAG"
STOP_SIGNALS = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP)


class RunFailure(Exception):
    """The run cannot give a result: no result line, exit code 1."""


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def log_tail(path, n=3000):
    try:
        with open(path, errors="replace") as f:
            return f.read()[-n:]
    except OSError:
        return ""


def wait_for_line(proc, logpath, needle, timeout):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RunFailure(f"process died rc={proc.returncode}:\n"
                             f"{log_tail(logpath)}")
        with open(logpath, errors="replace") as f:
            if needle in f.read():
                return
        time.sleep(0.25)
    raise RunFailure(f"timeout waiting for {needle!r}:\n{log_tail(logpath)}")


def tagged_pids(tag, var=TAG_VAR):
    """Live processes (zombies left out) whose environment carries the
    run's tag, this process excluded."""
    needle = f"{var}={tag}".encode()
    found = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            with open(f"/proc/{entry}/environ", "rb") as f:
                if needle not in f.read().split(b"\0"):
                    continue
            with open(f"/proc/{entry}/stat", "rb") as f:
                if f.read().rsplit(b")", 1)[1].split()[0] == b"Z":
                    continue
        except (OSError, IndexError):
            continue  # gone meanwhile, or another user's
        found.append(int(entry))
    return found


def kill_tagged(tag, timeout=10.0):
    """SIGKILL every tagged process until none is left (a process may fork
    between two sweeps).  Returns the pids still there at the deadline."""
    deadline = time.monotonic() + timeout
    while True:
        pids = tagged_pids(tag)
        if not pids or time.monotonic() > deadline:
            return pids
        for pid in pids:
            with contextlib.suppress(ProcessLookupError, PermissionError):
                os.kill(pid, signal.SIGKILL)
        time.sleep(0.05)


def watchdog(tag):
    """The watchdog child's whole life: standard input is the pipe from the
    owner.  End of file means the owner is gone (or has stopped everything
    and closed it): kill what carries the tag, exit."""
    for sig in STOP_SIGNALS:  # a signal to the owner's group is the owner's
        signal.signal(sig, signal.SIG_IGN)
    while os.read(0, 4096):
        pass
    kill_tagged(tag)


class ProcSet:
    """The owner of every child of one run."""

    def __init__(self, env):
        self.tag = uuid.uuid4().hex
        self.env = {**env, TAG_VAR: self.tag}
        self.procs = []       # (Popen, name), oldest first
        self._watchdog = None

    def _popen(self, argv, name, env_extra, **kw):
        if self._watchdog is None:
            self._watchdog = subprocess.Popen(
                [sys.executable, "-S", os.path.abspath(__file__), self.tag],
                env=self.env, stdin=subprocess.PIPE,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                start_new_session=True)
        p = subprocess.Popen(argv, env={**self.env, **(env_extra or {})},
                             stdin=subprocess.DEVNULL, **kw)
        self.procs.append((p, name))
        return p

    def spawn(self, argv, name, log, env_extra=None):
        """A child that runs beside the parent; its output goes to `log`."""
        os.makedirs(os.path.dirname(log), exist_ok=True)
        with open(log, "w") as f:
            return self._popen(argv, name, env_extra, stdout=f,
                               stderr=subprocess.STDOUT)

    def run(self, argv, name, env_extra=None, timeout=900):
        """A child the parent waits for.  Returns (return code, standard
        output, standard error); past `timeout` it is killed and the run
        fails."""
        p = self._popen(argv, name, env_extra, stdout=subprocess.PIPE,
                        stderr=subprocess.PIPE, text=True)
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            p.communicate()
            raise RunFailure(f"{name} did not finish in {timeout} s") from None
        finally:
            self.forget(p)
        return p.returncode, out, err

    def forget(self, p):
        """A child that has ended and been waited for leaves the set."""
        if p.poll() is not None:
            self.procs = [e for e in self.procs if e[0] is not p]

    def dead(self):
        """Names of children that have exited on their own."""
        return [name for p, name in self.procs if p.poll() is not None]

    def stop(self, timeout=60.0):
        """SIGTERM newest first, wait until each is GONE, kill past the
        deadline, then kill what they left behind.  Returns when no process
        of the run is left.  The stop signals are held back meanwhile: a
        second stop inside this one would wait on the same children."""
        held = signal.pthread_sigmask(signal.SIG_BLOCK, STOP_SIGNALS)
        try:
            for p, _ in self.procs[::-1]:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            deadline = time.monotonic() + timeout
            for p, _ in self.procs:
                try:
                    p.wait(max(0.1, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait(30)
            self.procs = []
            if self._watchdog is not None:
                # end of file: the watchdog sweeps by tag, as it would had
                # this process died, and goes
                self._watchdog.stdin.close()
                try:
                    self._watchdog.wait(30)
                except subprocess.TimeoutExpired:
                    self._watchdog.kill()
                    self._watchdog.wait(30)
                self._watchdog = None
        finally:
            signal.pthread_sigmask(signal.SIG_SETMASK, held)

    @contextlib.contextmanager
    def guard(self):
        """Inside: a stop signal stops every child and leaves the process
        with 128 + its number, and leaving the block by any way stops them
        too."""
        def stopped(signum, _frame):
            for sig in STOP_SIGNALS:
                signal.signal(sig, signal.SIG_IGN)
            try:
                self.stop()
            finally:
                print("BENCHMARK RUN FAILED: stopped by "
                      f"{signal.Signals(signum).name}; every child was "
                      "stopped first", file=sys.stderr, flush=True)
                os._exit(128 + signum)  # no result line: nothing unwinds

        before = {sig: signal.signal(sig, stopped) for sig in STOP_SIGNALS}
        try:
            yield self
        finally:
            try:
                self.stop()
            finally:
                for sig, handler in before.items():
                    signal.signal(sig, handler)


class Stack:
    """Control plane + frontend (off the chip) + one worker (on it), started
    through their CLIs as a user following the README starts them."""

    MODEL_NAME = "bench"

    def __init__(self, ps, log_dir, router_mode):
        self.ps = ps
        self.log_dir = log_dir
        off_chip = {"JAX_PLATFORMS": "cpu"}
        port = free_port()
        self.control = f"127.0.0.1:{port}"
        cp, log = self._spawn(
            [sys.executable, "-m", "dynamo_tpu.runtime", "--host",
             "127.0.0.1", "--port", str(port)], "control", off_chip)
        wait_for_line(cp, log, "READY", 120)
        self.http_port = free_port()
        fe, log = self._spawn(
            [sys.executable, "-m", "dynamo_tpu.frontend", "--control",
             self.control, "--host", "127.0.0.1", "--port",
             str(self.http_port), "--router-mode", router_mode], "frontend",
            off_chip)
        wait_for_line(fe, log, "READY", 120)
        self.base = f"http://127.0.0.1:{self.http_port}"
        self.status_port = None
        self.worker = None

    def _spawn(self, argv, name, env_extra):
        log = os.path.join(self.log_dir, f"{name}.log")
        return self.ps.spawn(argv, name, log, env_extra), log

    def start_worker(self, model_dir, flags, env_extra, timeout):
        """Start the worker, wait for READY.  Returns (device identity from
        its DEVICE line, seconds from spawn to READY)."""
        self.status_port = free_port()
        t0 = time.monotonic()
        proc, log = self._spawn(
            [sys.executable, "-m", "dynamo_tpu.worker", "--control",
             self.control, "--model", model_dir, "--model-name",
             self.MODEL_NAME, "--status-port", str(self.status_port),
             *flags], "worker", env_extra)
        wait_for_line(proc, log, "READY worker", timeout)
        self.worker = proc
        device = None
        with open(log, errors="replace") as f:
            for line in f:
                if line.startswith("DEVICE "):
                    device = json.loads(line[len("DEVICE "):])
        if device is None:
            raise RunFailure("the worker printed no DEVICE line")
        return device, time.monotonic() - t0

    @property
    def status(self):
        return f"http://127.0.0.1:{self.status_port}"

    def stop_worker(self, timeout=120):
        """SIGTERM and wait until the process is gone: the profiler trace,
        when armed, is written during its shutdown."""
        if self.worker is None or self.worker.poll() is not None:
            return
        self.worker.send_signal(signal.SIGTERM)
        try:
            self.worker.wait(timeout)
        except subprocess.TimeoutExpired:
            self.worker.kill()
            self.worker.wait(30)


if __name__ == "__main__":
    watchdog(sys.argv[1])
