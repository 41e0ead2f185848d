"""Plain reference for a placement: network cost and hard-capacity validity.

Written from the configuration's edges and cluster alone: every task of a
component edge talks to every task downstream of it (Storm's shuffle), and
R-Storm's netDist ladder prices each pair by where the two tasks sit.
Nothing here imports the program under test.
"""

from __future__ import annotations

from typing import Dict, Iterable, Mapping

import numpy as np

from .model import Cluster, Topology


def network_cost(
    topology: Topology, cluster: Cluster, placements: Mapping[str, str], distance: Mapping[str, float]
) -> float:
    """Sum of netDist over all communicating task pairs (placed tasks only).
    A node the cluster does not have is a rack of its own (``violations``
    counts it as broken)."""
    rack = {nid: n.rack_id for nid, n in cluster.nodes.items()}
    total = 0.0
    for src, dst in topology.edges:
        a = [placements[t] for t in topology.components[src].task_ids(topology.id) if t in placements]
        b = [placements[t] for t in topology.components[dst].task_ids(topology.id) if t in placements]
        if not a or not b:
            continue
        na, nb = np.array(a)[:, None], np.array(b)[None, :]
        ra = np.array([rack.get(x, f"?{x}") for x in a])[:, None]
        rb = np.array([rack.get(x, f"?{x}") for x in b])[None, :]
        d = np.where(
            na == nb,
            distance["same_node"],
            np.where(ra == rb, distance["same_rack"], distance["cross_rack"]),
        )
        total += float(d.sum())
    return total


def violations(
    tenants: Iterable[Topology], cluster: Cluster, placements: Mapping[str, str]
) -> Dict[str, int]:
    """Broken guarantees of one cluster-wide placement, as counts: tasks on a
    dead or unknown node, and nodes whose memory the placed tasks exceed."""
    memory: Dict[str, float] = {}
    for topo in tenants:
        memory.update(topo.memory_of())
    used: Dict[str, float] = {}
    dead = 0
    for tid, nid in placements.items():
        node = cluster.nodes.get(nid)
        if node is None or not node.alive:
            dead += 1
            continue
        used[nid] = used.get(nid, 0.0) + memory[tid]
    over = sum(
        1 for nid, mb in used.items() if mb > cluster.nodes[nid].memory_capacity_mb
    )
    return {"dead": dead, "over": over}
