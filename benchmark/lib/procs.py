"""Child processes of one benchmark run: spawn with a log file, wait for a
line in the log, stop everything.  Copied from scripts/_verify_harness.py and
chip_smoke.py's `Stack` (proven on the chip in PR 22) so that the yardstick
imports nothing a later PR may change.

One process per chip: the parent never imports jax; control plane and
frontend run with JAX_PLATFORMS=cpu; the worker alone holds the chip."""

import json
import os
import signal
import socket
import subprocess
import sys
import time


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


class ProcSet:
    def __init__(self, log_dir, env):
        self.log_dir = log_dir
        self.env = env
        self.procs = []
        os.makedirs(log_dir, exist_ok=True)

    def spawn(self, argv, name, env_extra=None):
        log = os.path.join(self.log_dir, f"{name}.log")
        env = {**self.env, **(env_extra or {})}
        with open(log, "w") as f:
            p = subprocess.Popen(argv, env=env, stdout=f,
                                 stderr=subprocess.STDOUT)
        self.procs.append((p, log, name))
        return p, log

    def dead(self):
        """Names of children that have exited on their own."""
        return [name for p, _, name in self.procs if p.poll() is not None]

    def stop(self, timeout=60.0):
        """SIGTERM newest first, wait until each is GONE, kill past the
        deadline.  Returns when no child is left."""
        for p, _, _ in self.procs[::-1]:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + timeout
        for p, _, _ in self.procs:
            try:
                p.wait(max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(30)
        self.procs = []


class Stack:
    """Control plane + frontend (off the chip) + one worker (on it), started
    through their CLIs as a user following the README starts them."""

    MODEL_NAME = "bench"

    def __init__(self, log_dir, env, router_mode):
        self.ps = ProcSet(log_dir, env)
        off_chip = {"JAX_PLATFORMS": "cpu"}
        port = free_port()
        self.control = f"127.0.0.1:{port}"
        cp, log = self.ps.spawn(
            [sys.executable, "-m", "dynamo_tpu.runtime", "--host",
             "127.0.0.1", "--port", str(port)], "control", off_chip)
        wait_for_line(cp, log, "READY", 120)
        self.http_port = free_port()
        fe, log = self.ps.spawn(
            [sys.executable, "-m", "dynamo_tpu.frontend", "--control",
             self.control, "--host", "127.0.0.1", "--port",
             str(self.http_port), "--router-mode", router_mode], "frontend",
            off_chip)
        wait_for_line(fe, log, "READY", 120)
        self.base = f"http://127.0.0.1:{self.http_port}"
        self.status_port = None
        self.worker = None

    def start_worker(self, model_dir, flags, env_extra, timeout):
        """Start the worker, wait for READY.  Returns (device identity from
        its DEVICE line, seconds from spawn to READY)."""
        self.status_port = free_port()
        t0 = time.monotonic()
        proc, log = self.ps.spawn(
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

    def close(self):
        self.ps.stop()
