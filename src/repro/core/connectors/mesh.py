"""MeshConnector: one accelerator site (a pod slice) as a deployment unit.

The declarative config mirrors the paper's model-description files: a mesh
topology plus named services whose replicas are sub-slices.  R1 maps onto
TPU reality exactly — a pod slice is gang-allocated atomically.

On this CPU container the *declared* topology is validated and recorded
(it feeds the dry-run and scheduler), while the *runtime* mesh uses the
devices that actually exist — the same degradation a laptop run of a
production config would use.
"""
from __future__ import annotations

import math
import time
from typing import Any, Dict, List, Optional

import jax

from repro.core.connector import (Connector, ConnectorCopyKind, ObjectStore,
                                  ResourceInfo)


class MeshConnector(Connector):
    """config:
        topology: {data: 16, model: 16}        # declared production shape
        services: {trainer: {replicas: 1, cores: 8, memory_gb: 64}}
        deploy_delay_s: 0.0
        shared_store: true                     # pod-local shared filesystem
    """

    def __init__(self, name: str, config: Optional[dict] = None):
        super().__init__(name, config)
        self._resources: Dict[str, ResourceInfo] = {}
        self._stores: Dict[str, ObjectStore] = {}
        self._meshes: Dict[str, Any] = {}
        self._shared: Optional[ObjectStore] = None

    # -- declared (production) topology --------------------------------------
    def declared_topology(self) -> Dict[str, int]:
        return dict(self.config.get("topology", {"data": 1, "model": 1}))

    def declared_chips(self) -> int:
        return math.prod(self.declared_topology().values())

    # -- lifecycle -------------------------------------------------------------
    def deploy(self) -> None:
        delay = float(self.config.get("deploy_delay_s", 0.0))
        if delay:
            time.sleep(delay)
        if self.config.get("shared_store", True):
            self._shared = ObjectStore(f"{self.name}:shared")
        services = self.config.get("services", {"default": {"replicas": 1}})
        n_dev = jax.device_count()
        # one runtime mesh per site (a pod slice IS one physical mesh);
        # replicas share it — also keeps jit caches hot across replicas
        model_axis = min(int(self.config.get("model_axis", 1)), n_dev)
        site_mesh = jax.make_mesh(
            (max(n_dev // model_axis, 1), model_axis), ("data", "model"))
        for svc, scfg in services.items():
            for i in range(int(scfg.get("replicas", 1))):
                rname = f"{self.name}/{svc}/{i}"
                self._resources[rname] = ResourceInfo(
                    rname, svc, cores=int(scfg.get("cores", 8)),
                    memory_gb=float(scfg.get("memory_gb", 64.0)))
                self._stores[rname] = self._shared or ObjectStore(rname)
                self._meshes[rname] = site_mesh
        self.deployed = True

    def undeploy(self) -> None:
        # the stores go with the site, and their device arrays with them
        for store in set(self._stores.values()):
            store.clear()
        self._resources.clear()
        self._stores.clear()
        self._meshes.clear()
        self.deployed = False

    # -- discovery ---------------------------------------------------------------
    def get_available_resources(self, service: str) -> List[str]:
        return [r for r, info in self._resources.items()
                if info.service == service]

    def resource_info(self, resource: str) -> ResourceInfo:
        return self._resources[resource]

    def store(self, resource: str) -> ObjectStore:
        return self._stores[resource]

    def stores(self) -> List[ObjectStore]:
        return list({id(s): s for s in self._stores.values()}.values())

    def shared_data_space(self) -> bool:
        return self._shared is not None

    def mesh(self, resource: str):
        return self._meshes[resource]

    # -- execution ------------------------------------------------------------------
    def run(self, resource: str, command: Any,
            environment: Optional[Dict[str, str]] = None,
            workdir: Optional[str] = None,
            capture_output: bool = False) -> Any:
        if resource not in self._resources:
            raise KeyError(f"unknown resource {resource}")
        ctx = {"resource": resource, "connector": self,
               "environment": environment or {},
               "mesh": self._meshes[resource],
               "declared_topology": self.declared_topology()}
        with self._meshes[resource]:          # ambient mesh for pjit users
            out = command(ctx)
        return out if capture_output else None
