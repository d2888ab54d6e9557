"""Unified launcher (dynamo_tpu.run), deployment graphs, and the
standalone router service."""

import asyncio
import json
import os
import signal
import subprocess
import sys
import time

import pytest
import yaml

from dynamo_tpu.deploy import GraphSpec, format_commands, render_manifests

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT}

GRAPH = """
namespace: testns
control_plane: {}
components:
  frontend:
    kind: frontend
    args: {port: 8123, router-mode: kv}
  decode:
    kind: worker
    replicas: 2
    args: {model: tiny, disagg-role: decode}
  prefill-router:
    kind: router
    args: {target-component: prefill, no-kv-events: true}
"""


def test_graph_parse_and_render():
    spec = GraphSpec.parse(GRAPH)
    assert spec.namespace == "testns"
    assert [c.name for c in spec.components] == [
        "frontend", "decode", "prefill-router"
    ]
    cmds = spec.render_local("127.0.0.1:1234")
    assert len(cmds) == 4  # decode has 2 replicas
    assert all("--control" in c and "127.0.0.1:1234" in c for c in cmds)
    assert all("--namespace" in c for c in cmds)
    text = format_commands(spec, "127.0.0.1:1234")
    assert "dynamo_tpu.router" in text and "--no-kv-events" in text


def test_graph_rejects_unknown_kind():
    with pytest.raises(ValueError, match="unknown kind"):
        GraphSpec.parse(
            "components:\n  x:\n    kind: nonsense\n"
        ).render_local("a:1")
    with pytest.raises(ValueError, match="no components"):
        GraphSpec.parse("namespace: x\n")


def test_k8s_render_shapes():
    spec = GraphSpec.parse(GRAPH)
    docs = list(yaml.safe_load_all(render_manifests(spec)))
    kinds = [(d["kind"], d["metadata"]["name"]) for d in docs]
    assert ("Namespace", "testns") in kinds
    assert ("Deployment", "control-plane") in kinds
    assert ("Service", "control-plane") in kinds
    # component objects carry the dynamo- prefix K8sActuator patches
    assert ("Deployment", "dynamo-frontend") in kinds
    assert ("Service", "dynamo-frontend") in kinds  # frontend exposes its port
    decode = next(d for d in docs if d["kind"] == "Deployment"
                  and d["metadata"]["name"] == "dynamo-decode")
    assert decode["spec"]["replicas"] == 2
    container = decode["spec"]["template"]["spec"]["containers"][0]
    assert container["resources"]["limits"]["google.com/tpu"] == "1"
    assert "--control" in container["command"]
    assert "control-plane.testns.svc:7801" in container["command"]


def test_run_batch_echo(tmp_path):
    """`dynamo_tpu.run --in batch --out echo` end-to-end as a subprocess:
    embedded control plane, echo engine, JSONL in/out."""
    inp = tmp_path / "in.jsonl"
    outp = tmp_path / "out.jsonl"
    rows = [{"prompt": "hello roundtrip"}, {"prompt": "second line"}]
    inp.write_text("".join(json.dumps(r) + "\n" for r in rows))
    r = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.run",
         "--in", "batch", "--out", "echo",
         "--input-file", str(inp), "--output-file", str(outp),
         "--max-tokens", "64"],
        env=ENV, cwd=ROOT, capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    got = [json.loads(line) for line in outp.read_text().splitlines()]
    assert len(got) == 2
    # echo engine: the templated prompt (which embeds the user text) comes back
    assert "hello roundtrip" in got[0]["response"]
    assert "second line" in got[1]["response"]


async def test_standalone_router_service():
    """Mock workers registered at ns.prefill + `python -m dynamo_tpu.router`
    subprocess routing over them; RemoteRouterClient round-trips."""
    from dynamo_tpu.disagg.handler import RemoteRouterClient
    from dynamo_tpu.llm import ModelDeploymentCard
    from dynamo_tpu.mocker import MockEngine, MockEngineArgs
    from dynamo_tpu.runtime import ControlPlaneServer, DistributedRuntime
    from dynamo_tpu.testing import tiny_tokenizer
    from dynamo_tpu.worker import serve_engine

    control = await ControlPlaneServer().start()
    rts, wids = [], []
    tok = tiny_tokenizer()
    for _ in range(2):
        rt = await DistributedRuntime.connect(control.address)
        served = await serve_engine(
            rt, MockEngine(MockEngineArgs()), ModelDeploymentCard(
                name="mock", tokenizer_json=tok.to_json_str(),
            ),
            component="prefill", publish_kv_events=False,
        )
        rts.append(rt)
        wids.append(served.instance.instance_id)
    proc = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.router",
         "--control", control.address, "--no-kv-events"],
        env=ENV, cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    try:
        # wait for READY
        loop = asyncio.get_running_loop()
        line = await asyncio.wait_for(
            loop.run_in_executor(None, proc.stdout.readline), 60
        )
        while "READY" not in line:
            line = await asyncio.wait_for(
                loop.run_in_executor(None, proc.stdout.readline), 60
            )
        client_rt = await DistributedRuntime.connect(control.address)
        rrc = RemoteRouterClient(client_rt)
        picks = set()
        for i in range(6):
            wid = await rrc.choose(
                {"token_ids": list(range(16 * (i + 1))),
                 "request_id": f"r{i}"}
            )
            from dynamo_tpu.router.worker_key import unpack_worker

            assert unpack_worker(wid)[0] in wids
            picks.add(wid)
            rrc.mark_finished(f"r{i}")
        assert picks  # routed to real instances
        await client_rt.shutdown(graceful=False)
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(10)
        except subprocess.TimeoutExpired:
            proc.kill()
        for rt in rts:
            await rt.shutdown(graceful=False)
        await control.stop()


@pytest.mark.timeout(300)
def test_worker_cli_engine_tuning_flags():
    """The engine-tuning CLI surface (--quantization int8,
    --attention-impl, --decode-steps/-chain, --speculative-ngram-k,
    --no-prefix-caching) must build a serving worker that answers
    requests — the int8 and speculative paths are otherwise
    unreachable from the CLIs."""
    import socket as _socket
    import threading
    import urllib.request

    def free_port():
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    cp_port = free_port()
    http_port = free_port()
    procs = []
    logs = {}

    def spawn(args):
        p = subprocess.Popen(
            [sys.executable, "-u", *args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=ENV, cwd=ROOT,
        )
        procs.append(p)
        buf = logs.setdefault(args[1], [])
        for line in p.stdout:
            buf.append(line)
            if "READY" in line:
                break
        else:
            raise AssertionError(f"{args} exited without READY:\n{''.join(buf)}")
        # keep draining so a chatty child can't fill the pipe and wedge
        threading.Thread(
            target=lambda: [buf.append(l) for l in p.stdout], daemon=True
        ).start()
        return p

    try:
        spawn(["-m", "dynamo_tpu.runtime", "--port", str(cp_port),
               "--host", "127.0.0.1"])
        control = f"127.0.0.1:{cp_port}"
        spawn(["-m", "dynamo_tpu.worker", "--control", control,
               "--model", "tiny", "--dtype", "float32", "--platform", "cpu",
               "--page-size", "8", "--num-pages", "96",
               "--max-prefill-tokens", "64", "--max-model-len", "128",
               "--quantization", "int8", "--attention-impl", "xla",
               "--decode-steps", "4", "--decode-chain", "2",
               "--speculative-ngram-k", "2", "--no-prefix-caching"])
        spawn(["-m", "dynamo_tpu.frontend", "--control", control,
               "--host", "127.0.0.1", "--port", str(http_port)])
        body = json.dumps({
            "model": "tiny-chat",
            "messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 6, "temperature": 0, "nvext": {"ignore_eos": True},
        }).encode()
        deadline = time.time() + 60
        last_err = None
        while True:
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{http_port}/v1/chat/completions",
                    body, {"Content-Type": "application/json"},
                )
                with urllib.request.urlopen(req, timeout=60) as r:
                    out = json.load(r)
                break
            except Exception as e:  # noqa: BLE001 — may still be registering
                last_err = e
                assert time.time() < deadline, (
                    f"no successful response before deadline; last error: "
                    f"{last_err!r}\nworker log tail:\n"
                    + "".join(logs.get("dynamo_tpu.worker", [])[-30:])
                )
                time.sleep(0.5)
        assert out["usage"]["completion_tokens"] == 6
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()


# -- one process per chip -------------------------------------------------------- #

_TWO_CHIP_WORKERS = """
namespace: t
control_plane: {}
components:
  frontend: {kind: frontend}
  decode: {kind: worker, replicas: 2, args: {model: tiny}}
"""


def test_local_launcher_refuses_two_chip_holding_workers():
    """A chip belongs to one process: a graph that would start two workers
    on the accelerator is refused before anything is spawned; mock workers
    and workers pinned to the CPU do not count."""
    from dynamo_tpu.deploy.graph import LocalLauncher

    spec = GraphSpec.parse(_TWO_CHIP_WORKERS)
    launcher = LocalLauncher(spec)
    with pytest.raises(ValueError, match="one|ONE"):
        launcher.start()
    assert launcher.procs == [] and launcher._control_proc is None
    for pin in ({"platform": "cpu"}, {"mock": True}):
        doc = yaml.safe_load(_TWO_CHIP_WORKERS)
        doc["components"]["decode"]["args"].update(pin)
        GraphSpec.parse(yaml.safe_dump(doc)).check_one_process_per_chip()


def test_only_workers_may_see_the_accelerator(monkeypatch):
    from dynamo_tpu.deploy.graph import process_env

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    spec = GraphSpec.parse(_TWO_CHIP_WORKERS)
    envs = {argv[2]: process_env(argv) for argv in spec.render_local("c:1")}
    assert envs["dynamo_tpu.frontend"]["JAX_PLATFORMS"] == "cpu"
    assert "JAX_PLATFORMS" not in envs["dynamo_tpu.worker"]


def test_local_actuator_clamps_chip_holding_replicas(monkeypatch):
    from dynamo_tpu.deploy import controller
    from dynamo_tpu.deploy.controller import LocalActuator

    spawned = []

    class FakeProc:
        pid = 1

        def poll(self):
            return None

        def send_signal(self, sig):
            pass

    monkeypatch.setattr(controller.subprocess, "Popen",
                        lambda argv, **kw: spawned.append(argv) or FakeProc())
    spec = GraphSpec.parse(_TWO_CHIP_WORKERS)
    decode = next(c for c in spec.components if c.name == "decode")
    act = LocalActuator("c:1")
    act.scale_to(decode, 2)
    assert len(spawned) == 1 and act.observed(decode) == 1
    decode.args["platform"] = "cpu"
    act.scale_to(decode, 3)
    assert len(spawned) == 3
