"""End-to-end driver: pipeline parallelism ACROSS hosts through the real
CLI surface.

    python scripts/verify_pp_multihost.py

Spawns: control plane, a 2-process multihost worker GROUP running the
tiny model with `--pp 2` — ONE pipeline stage per host (rank 0 serves,
rank 1 replays lockstep plans; each process provides 1 virtual CPU
device via `--local-devices`), and the frontend.  Greedy chat output
through HTTP must equal a single-process single-device worker serving
the same model.  Prints VERIFY PASS.  (pp×tp in one group needs the
model's vocab/heads divisible by tp — the tiny tokenizer's vocab of
261 is not, so the CLI driver stays tp=1; the pp×tp×multihost mesh is
covered by tests/test_multihost.py with a 256-vocab config.)
"""

import json
import os
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# CPU-only driver (every worker below is also started --platform cpu)
ENV = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
ENV.pop("XLA_FLAGS", None)  # workers set their own device counts


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wait_ready(proc, logpath, needle="READY", timeout=240):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if proc.poll() is not None:
            with open(logpath) as f:
                sys.exit(f"process died rc={proc.returncode}:\n{f.read()[-3000:]}")
        with open(logpath) as f:
            if needle in f.read():
                return
        time.sleep(0.5)
    with open(logpath) as f:
        sys.exit(f"timeout waiting for {needle!r}:\n{f.read()[-3000:]}")


def chat(port, prompt, max_tokens=8):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions",
        data=json.dumps({
            "model": "tiny-chat",
            "messages": [{"role": "user", "content": prompt}],
            "max_tokens": max_tokens, "temperature": 0,
            "nvext": {"ignore_eos": True},
        }).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=180) as r:
        out = json.loads(r.read().decode())
    return out["choices"][0]["message"]["content"]


def run_deployment(tmp, tag, worker_argv_extra, nprocs=1, coordinator=None):
    """control plane + worker proc(s) + frontend; returns (procs, port)."""
    procs = []
    control_port = free_port()
    control = f"127.0.0.1:{control_port}"

    def spawn(argv, name):
        log = os.path.join(tmp, f"{tag}-{name}.log")
        p = subprocess.Popen(argv, env=ENV, stdout=open(log, "w"),
                             stderr=subprocess.STDOUT)
        procs.append((p, log))
        return p, log

    cp, cplog = spawn([sys.executable, "-m", "dynamo_tpu.runtime",
                       "--host", "127.0.0.1", "--port", str(control_port)],
                      "control")
    wait_ready(cp, cplog)
    base = [sys.executable, "-m", "dynamo_tpu.worker", "--control", control,
            "--model", "tiny", "--dtype", "float32", "--platform", "cpu",
            *worker_argv_extra]
    if nprocs > 1:
        for rank in range(nprocs):
            spawn(base + ["--coordinator", coordinator,
                          "--num-hosts", str(nprocs),
                          "--host-id", str(rank)], f"worker{rank}")
        # rank 0 serves; follower prints its own READY
        wait_ready(procs[1][0], procs[1][1], needle="READY worker")
        wait_ready(procs[2][0], procs[2][1], needle="READY follower")
    else:
        w, wlog = spawn(base, "worker0")
        wait_ready(w, wlog, needle="READY worker")
    http_port = free_port()
    fe, felog = spawn([sys.executable, "-m", "dynamo_tpu.frontend",
                       "--control", control, "--host", "127.0.0.1",
                       "--port", str(http_port)], "frontend")
    wait_ready(fe, felog)
    # model discovery propagation
    deadline = time.time() + 120
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{http_port}/v1/models", timeout=5
            ) as r:
                if any(m["id"] == "tiny-chat"
                       for m in json.loads(r.read())["data"]):
                    break
        except Exception:
            pass
        time.sleep(0.5)
    else:
        sys.exit(f"{tag}: model never appeared")
    return procs, http_port


def stop(procs):
    for p, _ in procs[::-1]:
        if p.poll() is None:
            p.terminate()
    deadline = time.time() + 10
    for p, _ in procs:
        while p.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        if p.poll() is None:
            p.kill()


def main():
    tmp = tempfile.mkdtemp(prefix="vfy_ppmh_")
    prompts = ["hello world", "pipeline stages span hosts", "third prompt"]

    print("[1/2] single-process reference worker")
    ref_procs, ref_port = run_deployment(tmp, "ref", [])
    try:
        want = [chat(ref_port, p) for p in prompts]
        print(f"  reference outputs: {[w[:16] for w in want]!r}")
    finally:
        stop(ref_procs)

    print("[2/2] 2-process multihost worker group: --pp 2 "
          "(one stage per host)")
    coord = f"127.0.0.1:{free_port()}"
    pp_procs, pp_port = run_deployment(
        tmp, "ppmh",
        ["--pp", "2", "--local-devices", "1"],
        nprocs=2, coordinator=coord,
    )
    try:
        got = [chat(pp_port, p) for p in prompts]
    finally:
        stop(pp_procs)

    if got != want:
        sys.exit(f"MISMATCH:\n  want {want!r}\n  got  {got!r}\nlogs: {tmp}")
    print("[ok] pp=2 across 2 processes greedy-equals single-process")
    print("VERIFY PASS")


if __name__ == "__main__":
    main()
