"""Declarative deployment graphs: a YAML spec naming the components of a
deployment (frontend, workers, routers, planner), rendered either to local
subprocess commands or to Kubernetes manifests.

The spec mirrors the reference's `DynamoGraphDeployment` CRD
(/root/reference/deploy/cloud/operator/api/v1alpha1/
dynamographdeployment_types.go:31 — a graph of services with per-service
replicas/resources), flattened to what the TPU stack needs:

```yaml
namespace: dynamo
control_plane: {}            # omit to join an existing one via --control
components:
  frontend:
    kind: frontend           # frontend | worker | router | planner
    replicas: 1
    args: {port: 8000, router-mode: kv}
  decode:
    kind: worker
    replicas: 2
    args: {model: tiny, disagg-role: decode, page-size: 16}
  prefill:
    kind: worker
    args: {model: tiny, disagg-role: prefill}
  prefill-router:
    kind: router
    args: {target-component: prefill}
```
"""

from __future__ import annotations

import os
import shlex
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import yaml

_KIND_MODULE = {
    "frontend": "dynamo_tpu.frontend",
    "worker": "dynamo_tpu.worker",
    "router": "dynamo_tpu.router",
    "planner": "dynamo_tpu.planner",
}


def process_env(argv: List[str]) -> Dict[str, str]:
    """Environment for one launched process.  One process per chip: only a
    worker may take the accelerator, so everything else (frontend, router,
    planner, control plane — their packages import jax) is held to the CPU
    backend."""
    env = dict(os.environ)
    if _KIND_MODULE["worker"] not in argv:
        env["JAX_PLATFORMS"] = "cpu"
    return env


@dataclass
class MultinodeSpec:
    """A worker group spanning hosts: ONE graph entry fans out to
    `num_hosts` lockstep ranks (reference: `MultinodeSpec` nodeCount on
    DynamoComponentDeployment,
    dynamocomponentdeployment_types.go:105-108).  Rank 0 serves; other
    ranks replay its dispatches (JaxEngine.follower_loop).  A group
    lives and dies together — losing any rank tears down and respawns
    the whole group (lockstep state cannot survive a lost rank)."""

    num_hosts: int
    coordinator_port: int = 9999

    @classmethod
    def parse(cls, d: Optional[Dict[str, Any]]) -> Optional["MultinodeSpec"]:
        if not d:
            return None
        n = int(d.get("num_hosts", d.get("num-hosts", 0)))
        if n < 2:
            raise ValueError("multinode.num_hosts must be >= 2")
        return cls(
            num_hosts=n,
            coordinator_port=int(
                d.get("coordinator_port", d.get("coordinator-port", 9999))
            ),
        )


@dataclass
class ComponentSpec:
    name: str
    kind: str
    replicas: int = 1
    args: Dict[str, Any] = field(default_factory=dict)
    multinode: Optional[MultinodeSpec] = None

    def group_commands(self, control: str, coordinator: str,
                       namespace: str = "") -> List[List[str]]:
        """Per-host argvs for ONE multinode group: the same command on
        every host plus `--coordinator/--num-hosts/--host-id`."""
        if self.multinode is None:
            raise ValueError(f"component {self.name!r} is not multinode")
        if self.kind != "worker":
            raise ValueError("multinode groups are worker components")
        base = self.command(control, namespace=namespace)
        return [
            base + ["--coordinator", coordinator,
                    "--num-hosts", str(self.multinode.num_hosts),
                    "--host-id", str(i)]
            for i in range(self.multinode.num_hosts)
        ]

    @property
    def takes_chip(self) -> bool:
        """Whether a replica's process takes the accelerator: a worker
        that is neither `mock` nor pinned to `platform: cpu`."""
        return (self.kind == "worker" and not self.args.get("mock")
                and self.args.get("platform") != "cpu")

    def command(self, control: str, namespace: str = "") -> List[str]:
        """The process argv for one replica (reference: per-service pod
        command in DynamoComponentDeployment)."""
        if self.kind not in _KIND_MODULE:
            raise ValueError(
                f"component {self.name!r}: unknown kind {self.kind!r} "
                f"(known: {sorted(_KIND_MODULE)})"
            )
        argv = [sys.executable, "-m", _KIND_MODULE[self.kind],
                "--control", control]
        for key, value in self.args.items():
            flag = "--" + str(key).replace("_", "-")
            if value is True:
                argv.append(flag)
            elif value is False or value is None:
                continue
            else:
                argv += [flag, str(value)]
        if namespace and "--namespace" not in argv:
            argv += ["--namespace", namespace]
        return argv


@dataclass
class GraphSpec:
    namespace: str = "dynamo"
    control_plane: Optional[Dict[str, Any]] = None  # {} = launch one
    components: List[ComponentSpec] = field(default_factory=list)

    @classmethod
    def parse(cls, text: str) -> "GraphSpec":
        d = yaml.safe_load(text) or {}
        comps = []
        raw = d.get("components") or {}
        if isinstance(raw, list):  # list form: entries carry their name
            raw = {c.pop("name"): c for c in raw}
        for name, c in raw.items():
            comp = ComponentSpec(
                name=name,
                kind=c.get("kind", "worker"),
                replicas=int(c.get("replicas", 1)),
                args=dict(c.get("args") or {}),
                multinode=MultinodeSpec.parse(c.get("multinode")),
            )
            if comp.multinode is not None and comp.kind != "worker":
                # reject at PARSE time: an actuation-time failure inside
                # the reconcile loop would abort every pass and starve
                # the remaining components
                raise ValueError(
                    f"component {name!r}: multinode groups are worker "
                    f"components (got kind {comp.kind!r})"
                )
            comps.append(comp)
        if not comps:
            raise ValueError("deployment graph has no components")
        return cls(
            namespace=d.get("namespace", "dynamo"),
            control_plane=d.get("control_plane"),
            components=comps,
        )

    @classmethod
    def load(cls, path: str) -> "GraphSpec":
        with open(path) as f:
            return cls.parse(f.read())

    def check_one_process_per_chip(self) -> None:
        """Refuse a graph whose local realization would start more than
        one process that takes the accelerator: a chip belongs to one
        process, and the second worker would fail or hang on a device the
        first one holds."""
        holders = [
            comp.name for comp in self.components if comp.takes_chip
            for _ in range(comp.replicas * (
                comp.multinode.num_hosts if comp.multinode else 1))
        ]
        if len(holders) > 1:
            raise ValueError(
                f"{len(holders)} worker processes on this host would each "
                f"take the accelerator ({', '.join(holders)}): run ONE "
                "chip-holding worker per host (--tp / --dp-ranks spread it "
                "over the host's chips) or pin the others with "
                "`platform: cpu`"
            )

    def render_local(self, control: str) -> List[List[str]]:
        """Flat list of argvs, replicas expanded, namespace injected.
        Multinode groups expand to num_hosts ranks each, with a fresh
        local coordinator port per group."""
        out = []
        for comp in self.components:
            if comp.multinode is not None:
                for _ in range(comp.replicas):
                    out.extend(comp.group_commands(
                        control, f"127.0.0.1:{_free_port()}",
                        namespace=self.namespace,
                    ))
                continue
            argv = comp.command(control, namespace=self.namespace)
            for _ in range(comp.replicas):
                out.append(list(argv))
        return out


class LocalLauncher:
    """Realize a graph as local OS processes (the non-k8s deploy path —
    the reference's launch scripts / LocalProcessConnector role)."""

    def __init__(self, spec: GraphSpec, control: str = ""):
        self.spec = spec
        self.control = control
        self.procs: List[subprocess.Popen] = []
        self._control_proc: Optional[subprocess.Popen] = None

    def start(self, stdout=None) -> str:
        """Launch everything; returns the control-plane address."""
        self.spec.check_one_process_per_chip()
        if not self.control:
            if self.spec.control_plane is None:
                raise ValueError(
                    "graph has no control_plane section and no --control "
                    "address was given"
                )
            import socket

            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            argv = [sys.executable, "-m", "dynamo_tpu.runtime",
                    "--host", "127.0.0.1", "--port", str(port)]
            self._control_proc = subprocess.Popen(
                argv, stdout=stdout, stderr=subprocess.STDOUT,
                env=process_env(argv),
            )
            self.control = f"127.0.0.1:{port}"
            time.sleep(0.5)  # the control plane binds quickly
        for argv in self.spec.render_local(self.control):
            self.procs.append(subprocess.Popen(
                argv, stdout=stdout, stderr=subprocess.STDOUT,
                env=process_env(argv),
            ))
        return self.control

    def poll(self) -> Dict[str, int]:
        """pid → returncode for exited processes."""
        return {
            p.pid: p.returncode
            for p in self.procs
            if p.poll() is not None
        }

    def stop(self, timeout: float = 10.0) -> None:
        stop_processes(
            self.procs + ([self._control_proc] if self._control_proc else []),
            timeout,
        )


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def stop_processes(procs: List[subprocess.Popen], timeout: float = 10.0) -> None:
    """SIGTERM every live process, then kill whatever outlives the
    deadline (shared by the launcher and the controller's actuator)."""
    import signal as _signal

    for p in procs:
        if p.poll() is None:
            p.send_signal(_signal.SIGTERM)
    deadline = time.time() + timeout
    for p in procs:
        while p.poll() is None and time.time() < deadline:
            time.sleep(0.1)
        if p.poll() is None:
            p.kill()


def format_commands(spec: GraphSpec, control: str) -> str:
    return "\n".join(
        shlex.join(argv) for argv in spec.render_local(control or "<control>")
    )
