"""Least work of the annealer, counted from the configuration's shapes.

A swap proposal on chain ``b`` exchanges the nodes of two tasks ``i`` and
``j``.  Whatever the implementation, to decide it the device has to read at
least: the two tasks' node ids; their adjacency rows (one neighbour id per
neighbour) and each neighbour's node id; the net-distance entries of every
neighbour to the old and to the new node (two per neighbour); and, per hard
dimension, the capacity and the used amount of the two nodes.  Each entry is
counted as one 4-byte word, below the program's 8-byte floats, so the count
is a lower bound that any implementation reads the same way, and a share of
the HBM roofline built on it cannot pass 100 %.  The annealer does no matrix
work and the v5e publishes no peak for 32- or 64-bit vector arithmetic, so
the bound is HBM bandwidth.
"""

from __future__ import annotations

WORD_BYTES = 4


def task_degrees(topology_spec: dict) -> list:
    """Number of communicating partner tasks of every task (shuffle edges:
    every task of one end talks to every task of the other)."""
    par = {c["id"]: int(c.get("parallelism", 1)) for c in topology_spec["components"]}
    deg = {cid: 0 for cid in par}
    for e in topology_spec.get("edges", []):
        deg[e["src"]] += par[e["dst"]]
        deg[e["dst"]] += par[e["src"]]
    return [deg[cid] for cid in par for _ in range(par[cid])]


def bytes_per_proposal(topology_spec: dict, hard_dims: int = 1) -> float:
    """Expected least bytes one swap proposal reads (two tasks drawn
    uniformly, so each contributes the mean degree)."""
    degs = task_degrees(topology_spec)
    mean_deg = sum(degs) / len(degs)
    words = (
        2  # the two tasks' node ids
        + 2 * mean_deg  # their neighbour ids
        + 2 * mean_deg  # the neighbours' node ids
        + 2 * 2 * mean_deg  # net distance of each neighbour to the old and new node
        + 2 * 2 * hard_dims  # capacity and use of the two nodes
    )
    return words * WORD_BYTES
