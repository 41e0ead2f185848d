"""Plain data model of a configuration, built from its JSON file alone.

The benchmark's yardstick (``reference``) reads these objects and nothing of
the program under test.  Task ids follow Storm's naming as the
payload API reports them: ``<topology>/<component>[<index>]``.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent


@dataclasses.dataclass
class Component:
    id: str
    parallelism: int
    memory_load: float

    def task_ids(self, topology_id: str) -> List[str]:
        return [f"{topology_id}/{self.id}[{i}]" for i in range(self.parallelism)]


@dataclasses.dataclass
class Topology:
    id: str
    components: Dict[str, Component]
    edges: List[Tuple[str, str]]

    def memory_of(self) -> Dict[str, float]:
        """task id -> on-heap MB."""
        return {
            t: c.memory_load for c in self.components.values() for t in c.task_ids(self.id)
        }


@dataclasses.dataclass
class Node:
    id: str
    rack_id: str
    memory_capacity_mb: float
    alive: bool = True


@dataclasses.dataclass
class Cluster:
    nodes: Dict[str, Node]

    def with_dead(self, dead) -> "Cluster":
        return Cluster(
            {
                nid: dataclasses.replace(n, alive=nid not in set(dead))
                for nid, n in self.nodes.items()
            }
        )


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def topology_from_spec(spec: dict) -> Topology:
    comps = {}
    for c in spec["components"]:
        comps[c["id"]] = Component(
            id=c["id"],
            parallelism=int(c.get("parallelism", 1)),
            memory_load=float(c.get("memory_load_mb", 128.0)),
        )
    edges = [(e["src"], e["dst"]) for e in spec.get("edges", [])]
    return Topology(id=spec["id"], components=comps, edges=edges)


def cluster_from_config(cfg: dict) -> Cluster:
    c = cfg["cluster"]
    nodes = {}
    for r in range(c["racks"]):
        for n in range(c["nodes_per_rack"]):
            nid = f"r{r}n{n}"
            nodes[nid] = Node(nid, f"rack{r}", float(c["memory_mb"]))
    return Cluster(nodes)


def payload_dict(cfg: dict, tenant: int, scheduler: dict, topology_id: Optional[str] = None) -> dict:
    """The ``SchedulingPayload`` dict a Storm user would submit."""
    topo = dict(cfg["tenants"][tenant])
    if topology_id is not None:
        topo["id"] = topology_id
    return {
        "topology": topo,
        "cluster": dict(cfg["cluster"]),
        "scheduler": scheduler,
    }
