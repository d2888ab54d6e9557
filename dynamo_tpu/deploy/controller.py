"""Operator-lite controller: a level-triggered reconcile loop that keeps
a deployment graph's ACTUAL state (live replicas) converged on its
DESIRED state (the spec, plus runtime scale overrides from the planner).

The reference ships a Kubernetes operator whose controller watches
`DynamoGraphDeployment` resources and reconciles per-service replica
counts (/root/reference/deploy/cloud/operator/api/v1alpha1/
dynamographdeployment_types.go:31, controller_common.go).  Here the same
reconcile semantics run as a first-party loop over two actuators:

- `LocalActuator` — replicas are OS processes on this host (spawn /
  SIGTERM); crashed replicas are detected by `poll()` and respawned.
- `K8sActuator` — replicas are Deployment `spec.replicas` patched
  through `kubectl` against the manifests `deploy.k8s` rendered (the
  actuation path of the reference's KubernetesConnector,
  components/src/dynamo/planner/kubernetes_connector.py:48).

Desired-state inputs, merged every tick:
1. the graph spec's per-component `replicas`;
2. the planner's targets key `/planner/{namespace}/targets` in the
   control-plane KV (written by `planner.connectors.VirtualConnector`) —
   entries name a component directly, or a disagg role ("prefill" /
   "decode") that maps onto the component with that `disagg-role` arg.

This closes the planner's actuation loop without Kubernetes: planner →
control-plane KV → controller → processes.
"""

from __future__ import annotations

import asyncio
import logging
import signal
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

from ..runtime import DistributedRuntime
from ..runtime.transport.wire import unpack
from .graph import ComponentSpec, GraphSpec, process_env

logger = logging.getLogger(__name__)

PLANNER_ROOT = "/planner"


class LocalActuator:
    """Replicas as local OS processes.  A MULTINODE component's replica
    is a whole GROUP of `num_hosts` rank processes spawned around a
    fresh coordinator port — the fan-out the reference's operator gets
    from `MultinodeSpec` nodeCount + Grove/LWS grouping.  A group lives
    and dies together: any dead rank tears the group down (SIGTERM the
    survivors) and reconcile respawns it whole, because lockstep state
    cannot survive a lost rank (JaxEngine.follower_loop poisons)."""

    def __init__(self, control: str, stdout=None, namespace: str = ""):
        self.control = control
        self.stdout = stdout
        self.namespace = namespace
        self._procs: Dict[str, List[subprocess.Popen]] = {}
        # multinode components: name → list of rank-process groups
        self._groups: Dict[str, List[List[subprocess.Popen]]] = {}
        # replicas scaled down but possibly still draining: tracked so a
        # SIGTERM-ignoring worker is still reaped/killed at shutdown
        self._stopping: List[subprocess.Popen] = []
        # components whose replicas take the accelerator (_chip_room)
        self._chip_comps: set = set()

    def observed(self, comp: ComponentSpec) -> int:
        self._stopping = [p for p in self._stopping if p.poll() is None]
        if comp.multinode is not None:
            groups = self._groups.setdefault(comp.name, [])
            alive: List[List[subprocess.Popen]] = []
            for group in groups:
                dead = [p for p in group if p.poll() is not None]
                if dead:
                    logger.warning(
                        "%s group lost rank(s) %s — tearing down the "
                        "group", comp.name,
                        [(p.pid, p.returncode) for p in dead],
                    )
                    for p in group:
                        if p.poll() is None:
                            p.send_signal(signal.SIGTERM)
                            self._stopping.append(p)
                else:
                    alive.append(group)
            groups[:] = alive
            return len(groups)
        procs = self._procs.setdefault(comp.name, [])
        # reap exits (crash detection): a dead replica simply stops
        # counting toward observed state and reconcile replaces it
        dead = [p for p in procs if p.poll() is not None]
        for p in dead:
            logger.warning(
                "%s replica pid %d exited rc=%s", comp.name, p.pid,
                p.returncode,
            )
        procs[:] = [p for p in procs if p.poll() is None]
        return len(procs)

    def _chip_room(self, comp: ComponentSpec, replicas: int) -> int:
        """One process per chip: of the processes this actuator starts on
        this host, at most one may take the accelerator.  A request for
        more is refused loudly and clamped (raising here would abort every
        reconcile pass and starve the other components)."""
        if not comp.takes_chip:
            return replicas
        self._chip_comps.add(comp.name)
        others = sum(
            1 for name in self._chip_comps if name != comp.name
            for p in (self._procs.get(name, [])
                      + [r for g in self._groups.get(name, []) for r in g])
            if p.poll() is None
        )
        hosts = comp.multinode.num_hosts if comp.multinode else 1
        room = 0 if others or hosts > 1 else 1
        if replicas > room:
            logger.error(
                "%s: %d chip-holding replica(s) of %d host process(es) "
                "wanted, room for %d on this host — a chip belongs to one "
                "process (use --tp / --dp-ranks inside one worker, or "
                "`platform: cpu`)", comp.name, replicas, hosts, room,
            )
        return min(replicas, room)

    def scale_to(self, comp: ComponentSpec, replicas: int) -> None:
        replicas = self._chip_room(comp, replicas)
        if comp.multinode is not None:
            from .graph import _free_port

            groups = self._groups.setdefault(comp.name, [])
            while len(groups) < replicas:
                coord = f"127.0.0.1:{_free_port()}"
                group = []
                for argv in comp.group_commands(
                    self.control, coord, namespace=self.namespace
                ):
                    p = subprocess.Popen(
                        argv, stdout=self.stdout, stderr=subprocess.STDOUT,
                        env=process_env(argv),
                    )
                    group.append(p)
                groups.append(group)
                logger.info(
                    "%s: spawned %d-host group pids %s (coordinator %s)",
                    comp.name, comp.multinode.num_hosts,
                    [p.pid for p in group], coord,
                )
            while len(groups) > replicas:
                group = groups.pop()
                for p in group:
                    p.send_signal(signal.SIGTERM)
                    self._stopping.append(p)
                logger.info("%s: stopping group pids %s", comp.name,
                            [p.pid for p in group])
            return
        procs = self._procs.setdefault(comp.name, [])
        argv = comp.command(self.control, namespace=self.namespace)
        while len(procs) < replicas:
            p = subprocess.Popen(
                argv, stdout=self.stdout, stderr=subprocess.STDOUT,
                env=process_env(argv),
            )
            procs.append(p)
            logger.info("%s: spawned replica pid %d", comp.name, p.pid)
        while len(procs) > replicas:
            p = procs.pop()
            p.send_signal(signal.SIGTERM)  # workers drain gracefully
            self._stopping.append(p)
            logger.info("%s: stopping replica pid %d", comp.name, p.pid)

    def stop_all(self, timeout: float = 10.0) -> None:
        from .graph import stop_processes

        stop_processes(
            [p for procs in self._procs.values() for p in procs]
            + [p for groups in self._groups.values()
               for group in groups for p in group]
            + self._stopping,
            timeout,
        )


class K8sActuator:
    """Replicas as Deployment spec.replicas, patched via kubectl (the
    manifests themselves come from `deploy.k8s.render_manifests`).
    Multinode components render as StatefulSets whose pod count is
    groups × num_hosts (ordinal → host-id, deploy/k8s.py), so scaling
    a group count patches `replicas = groups * num_hosts`."""

    def __init__(self, namespace: str, kubectl: str = "kubectl"):
        self.namespace = namespace
        self.kubectl = kubectl

    @staticmethod
    def _kind_of(comp: ComponentSpec) -> str:
        return "statefulset" if comp.multinode is not None else "deployment"

    def patch_command(self, comp_name: str, replicas: int,
                      kind: str = "deployment") -> List[str]:
        return [
            self.kubectl, "-n", self.namespace, "patch", kind,
            f"dynamo-{comp_name}", "--type", "merge", "-p",
            '{"spec": {"replicas": %d}}' % replicas,
        ]

    def observed(self, comp: ComponentSpec) -> Optional[int]:
        # spec.replicas, NOT status.availableReplicas: the controller
        # converges the DESIRED count; pods that are pending/crashing
        # are the Deployment/StatefulSet controller's job, and
        # re-patching an already-correct spec every tick would spam the
        # API server
        out = subprocess.run(
            [self.kubectl, "-n", self.namespace, "get", self._kind_of(comp),
             f"dynamo-{comp.name}", "-o", "jsonpath={.spec.replicas}"],
            capture_output=True, text=True, timeout=15,
        )
        if out.returncode != 0:
            return None
        pods = int(out.stdout.strip() or 0)
        if comp.multinode is not None:
            n = comp.multinode.num_hosts
            if pods % n:
                # a hand-scaled / partially-applied StatefulSet with a
                # non-multiple pod count would floor-divide to the
                # desired group count and never heal (the stray pod
                # waits forever for group peers) — force a re-patch
                return -1
            return pods // n
        return pods

    def scale_to(self, comp: ComponentSpec, replicas: int) -> None:
        pods = replicas
        if comp.multinode is not None:
            pods = replicas * comp.multinode.num_hosts
        subprocess.run(
            self.patch_command(comp.name, pods, self._kind_of(comp)),
            check=True, timeout=15,
        )

    def stop_all(self) -> None:  # k8s resources outlive the controller
        pass


class GraphController:
    """The reconcile loop.  `await start()`, then it converges live state
    on (spec ∪ planner targets) every `interval` seconds."""

    def __init__(self, spec: GraphSpec, control: str,
                 runtime: Optional[DistributedRuntime] = None,
                 actuator=None, interval: float = 1.0, stdout=None,
                 status_cb=None):
        self.spec = spec
        self.control = control
        self.runtime = runtime
        self.actuator = actuator or LocalActuator(
            control, stdout=stdout, namespace=spec.namespace
        )
        self.interval = interval
        self.desired: Dict[str, int] = {
            c.name: c.replicas for c in spec.components
        }
        self._comp: Dict[str, ComponentSpec] = {
            c.name: c for c in spec.components
        }
        # components dropped from the spec but whose replicas are still
        # draining: reconciled to 0 until observed 0, then forgotten
        self._retired: Dict[str, ComponentSpec] = {}
        # components whose definition changed: bounce to 0 this pass so
        # the next pass brings them up with the new argv
        self._restart: set = set()
        self._task: Optional[asyncio.Task] = None
        self._wake = asyncio.Event()
        self.reconciles = 0
        # async callback invoked with the post-pass status dict — the
        # operator uses it to publish /deployments/{name}/status
        self.status_cb = status_cb

    def update_spec(self, spec: GraphSpec) -> None:
        """Adopt a new desired spec (the operator's CRD-update path,
        reference: DynamoGraphDeployment reconcile on resource change).
        Removed components drain to 0; changed components bounce so
        replicas restart with the new argv; spec replica counts reset
        any planner override (the planner re-merges on the next tick,
        exactly like a re-applied k8s resource).  The namespace is
        immutable (like most CRD identity fields): the actuator and the
        planner targets key are namespace-scoped at construction, so a
        rename would silently split state — delete and re-apply
        instead."""
        if spec.namespace != self.spec.namespace:
            raise ValueError(
                f"namespace is immutable ({self.spec.namespace!r} -> "
                f"{spec.namespace!r}); delete the deployment and apply "
                f"it under the new namespace"
            )
        new_names = {c.name for c in spec.components}
        for name, comp in list(self._comp.items()):
            if name not in new_names:
                self._retired[name] = comp
                self._comp.pop(name)
                self.desired.pop(name, None)
        for comp in spec.components:
            old = self._comp.get(comp.name)
            if (self._retired.pop(comp.name, None) is not None
                    and not isinstance(self.actuator, K8sActuator)):
                # re-added while its old replicas may still be draining:
                # bounce so survivors can't keep running the old argv
                # (on k8s the template never changed — no point killing
                # healthy pods; the replica patch alone converges)
                self._restart.add(comp.name)
            if old is not None and (
                old.kind != comp.kind or old.args != comp.args
                or old.multinode != comp.multinode
            ):
                if isinstance(self.actuator, K8sActuator):
                    # a replica bounce cannot deliver a new argv there:
                    # the pod template lives in the rendered manifests,
                    # and patching spec.replicas 0->N would disrupt for
                    # zero effect — the template must be re-applied
                    # (helm upgrade / kubectl apply of --render k8s)
                    logger.warning(
                        "%s: definition changed but the k8s actuator "
                        "only scales replicas — re-apply the rendered "
                        "manifests for the new args to take effect",
                        comp.name,
                    )
                else:
                    self._restart.add(comp.name)
            self._comp[comp.name] = comp
            self.desired[comp.name] = comp.replicas
        self.spec = spec
        self._wake.set()

    @property
    def targets_key(self) -> str:
        return f"{PLANNER_ROOT}/{self.spec.namespace}/targets"

    def _component_for_target(self, key: str) -> Optional[str]:
        """Planner targets name a component, or a disagg role that maps
        onto the component carrying that role."""
        if key in self._comp:
            return key
        for name, comp in self._comp.items():
            if comp.args.get("disagg-role") == key or comp.args.get(
                "disagg_role"
            ) == key:
                return name
        return None

    async def _merge_planner_targets(self) -> None:
        if self.runtime is None:
            return
        try:
            data = await self.runtime.control.get(self.targets_key)
        except (ConnectionError, RuntimeError):
            return
        if not data:
            return
        targets = unpack(data)
        for key, val in targets.items():
            if key == "updated_at":
                continue
            name = self._component_for_target(str(key))
            if name is None:
                logger.warning("planner target %r matches no component", key)
                continue
            val = max(0, int(val))
            if self.desired.get(name) != val:
                logger.info("planner target: %s -> %d replicas", name, val)
                self.desired[name] = val

    async def reconcile(self) -> Dict[str, Dict[str, int]]:
        """One level-triggered pass; returns the post-pass status.
        Actuator calls run on an executor thread — kubectl against a
        slow API server (or a SIGTERM drain wait) must not stall the
        event loop carrying the control-plane connection."""
        await self._merge_planner_targets()
        loop = asyncio.get_running_loop()
        status = {}
        for name, comp in list(self._comp.items()):
            want = self.desired.get(name)
            if want is None:
                continue  # removed by a concurrent update_spec mid-pass
            if name in self._restart:
                # definition changed: drain now, rebuild next pass
                await loop.run_in_executor(
                    None, self.actuator.scale_to, comp, 0
                )
                self._restart.discard(name)
                self._wake.set()  # converge back up promptly
                status[name] = {"desired": want, "observed": 0,
                                "restarting": True}
                continue
            have = await loop.run_in_executor(
                None, self.actuator.observed, comp
            )
            if have is not None and have != want:
                await loop.run_in_executor(
                    None, self.actuator.scale_to, comp, want
                )
            status[name] = {"desired": want, "observed": have}
        for name, comp in list(self._retired.items()):
            have = await loop.run_in_executor(
                None, self.actuator.observed, comp
            )
            if have is None:
                # actuator error (e.g. kubectl timeout) — NOT drained;
                # keep the component retired and retry next pass
                status[name] = {"desired": 0, "observed": None}
            elif have:
                await loop.run_in_executor(
                    None, self.actuator.scale_to, comp, 0
                )
                status[name] = {"desired": 0, "observed": have}
            else:
                self._retired.pop(name)
        self.reconciles += 1
        if self.status_cb is not None:
            try:
                await self.status_cb(status)
            except Exception:  # noqa: BLE001 — status is best-effort
                logger.exception("status callback failed")
        return status

    async def scale(self, name: str, replicas: int) -> None:
        if name not in self._comp:
            raise KeyError(f"unknown component {name!r}")
        self.desired[name] = max(0, int(replicas))
        self._wake.set()

    async def start(self) -> "GraphController":
        self._task = asyncio.get_running_loop().create_task(self._loop())
        return self

    async def _loop(self) -> None:
        while True:
            # clear BEFORE reconciling: a wake set during the pass
            # (update_spec/scale from another task, the restart bounce)
            # must shorten the next sleep, not be discarded
            self._wake.clear()
            try:
                await self.reconcile()
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — the loop must survive
                logger.exception("reconcile pass failed")
            try:
                await asyncio.wait_for(self._wake.wait(), self.interval)
            except asyncio.TimeoutError:
                pass

    async def stop(self, stop_replicas: bool = True) -> None:
        if self._task:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)
        if stop_replicas:
            loop = asyncio.get_running_loop()
            # scale everything to 0 THROUGH the actuator first: for k8s
            # this is the only teardown there is (stop_all is a no-op —
            # the objects outlive the controller), for local it starts
            # the graceful SIGTERM drain that stop_all then reaps
            for comp in list(self._comp.values()) + list(
                self._retired.values()
            ):
                try:
                    await loop.run_in_executor(
                        None, self.actuator.scale_to, comp, 0
                    )
                except Exception:  # noqa: BLE001 — teardown continues
                    logger.exception("scale-to-0 of %s failed during "
                                     "stop", comp.name)
            await loop.run_in_executor(None, self.actuator.stop_all)
