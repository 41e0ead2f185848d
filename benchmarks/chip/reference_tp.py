"""Plain re-score of the fluid throughput model: the steady-state sink
throughput of every tenant on the cluster, solved jointly as they share
nodes and links.

Written from the configuration alone; nothing here imports the program
under test.  The model is the one the program states for its fluid solver
(``repro/stream/simulator.py``), restated mechanism by mechanism:

* **source ceiling** — a task of a component with ``max_rate_per_task``
  never processes faster than that;
* **CPU** — processor sharing per node: Σ rate × cost over the node's tasks
  stays within its CPU points, and a task's service rate is what the node
  leaves it (at most one core) over its cost per tuple;
* **bandwidth** — per-NIC egress and ingress and per-rack uplink bytes grow
  linearly with the spout rate λ and stay within link capacity;
* **ack loop** — max-spout-pending keeps ``pending`` tuples in flight, so
  λ = pending / L(λ), L the flow-weighted critical-path latency (hop
  latency by where the two tasks sit, serialization inflated by the
  sender's NIC load, M/M/1 sojourn per task, the acker's round trip);
* **memory thrash** — a node whose placed tasks exceed its memory keeps
  ``THRASH_FACTOR`` of its CPU.

What a Storm user states in a topology the re-score takes from the
configuration and nothing else: each tenant's ``max_spout_pending`` and each
component's ``cpu_cost_per_tuple`` (CPU point-seconds per tuple),
``tuple_bytes``, ``emit_ratio`` and ``memory_load_mb``.  A configuration
that leaves one out is refused (:class:`Unstated`): Storm's defaults.yaml
leaves ``topology.max.spout.pending`` unset and knows no tuple size, emit
ratio or tuple rate, so no default stands for a deployment.  What the fluid
model itself fixes (the network, the acker's round trip, the thrash factor
and the caps of its latency terms) is restated below with its source.

Per unit λ every spout component emits one tuple per second, split over its
tasks; a shuffle edge splits a task's output evenly over the downstream
tasks that are placed (``local_or_shuffle`` over the colocated ones where
any exist).  A tenant's sink throughput is λ times the per-unit rates of its
sink tasks.  A task on a dead or unknown node is not placed.

The fixed point is found by bracketing, not by the program's damped
iteration: a tenant's λ is the root of λ · L(λ) = pending below its hard
bounds, which is unique because L never falls as λ rises; tenants that
share the cluster are solved in turn against the others' rates until no λ
moves by ``JOINT_RTOL`` of the largest.
"""

from __future__ import annotations

import math
from typing import Dict, Iterable, Mapping

import numpy as np

# The fluid model's own constants (``repro/stream/simulator.py``).
#: Constant acker round trip, spout -> acker -> spout, seconds.
ACK_OVERHEAD_S = 5e-3
#: Share of its CPU that a memory-thrashed node keeps.
THRASH_FACTOR = 0.002
#: Utilization caps of a task's queue and of a NIC in the latency terms.
RHO_CAP = 0.999
UTIL_CAP = 0.999
#: CPU points of one core: a task is one executor thread (Storm's RAS counts
#: 100 points per core).
ONE_CORE = 100.0
EPS = 1e-12

# The network the fluid model fixes (``repro/stream/network.py``), after
# R-Storm's Emulab testbed (Middleware '15, sec. 6.1): 100 Mbps NICs and a
# 4 ms round trip between racks.  The same-node and same-rack latencies and
# the 1 Gbps rack uplink are the model's own.
LAT_SAME_NODE_S = 25e-6  # loopback between two workers of one node
LAT_SAME_RACK_S = 250e-6  # through the top-of-rack switch
LAT_CROSS_RACK_S = 2e-3  # half the 4 ms round trip
NIC_BYTES_PER_S = 12.5e6
RACK_UPLINK_BYTES_PER_S = 125e6

#: What every tenant and every component of a re-scored configuration states.
TENANT_FIELDS = ("max_spout_pending",)
COMPONENT_FIELDS = ("cpu_cost_per_tuple", "tuple_bytes", "emit_ratio", "memory_load_mb")


#: The program's fluid solve stops once a damped step moves λ by less than
#: 1e-9 of λ.  Each step is at least half the distance to the fixed point
#: (the ack bound falls as λ rises), so a solve that stops so lies within
#: 1e-9 of the fixed point.  A comparison with the program's solve allows
#: ten times that; a float32 solve misses by more (5.6e-8 and up).
TOLERANCE = 1e-8
#: Tighter than any of the program's rules: the bracket of a tenant's λ, and
#: the joint solve's rounds.
SOLVE_RTOL = 1e-14
JOINT_RTOL = 1e-12
ROUNDS = 100


class Unstated(ValueError):
    """The configuration leaves out a number the fluid model needs."""


def unstated(spec: dict) -> list:
    """The fields of ``TENANT_FIELDS`` and ``COMPONENT_FIELDS`` that the
    tenant ``spec`` leaves out, as ``<tenant>.<field>`` or
    ``<tenant>.<component>.<field>``."""
    tid = spec.get("id", "?")
    out = [f"{tid}.{f}" for f in TENANT_FIELDS if spec.get(f) is None]
    for c in spec["components"]:
        out += [f"{tid}.{c['id']}.{f}" for f in COMPONENT_FIELDS if c.get(f) is None]
    return out


def _capacity_bound(use, cap):
    """min over entries with use > EPS of max(cap, 0) / use (inf if none)."""
    binds = use > EPS
    if not binds.any():
        return math.inf
    return float(np.min(np.maximum(cap[binds], 0.0) / use[binds]))


class _Tenant:
    """Per-unit-λ load of one placed tenant."""

    def __init__(self, spec: dict, placements: Mapping[str, str], node_index: Mapping[str, int],
                 n_nodes: int, rack_of: np.ndarray, dtype):
        tid = spec["id"]
        comps = spec["components"]
        self.acked = bool(spec.get("acked", True))
        cids = [c["id"] for c in comps]
        down = {cid: [] for cid in cids}
        indeg = {cid: 0 for cid in cids}
        grouping = {}
        for e in spec.get("edges", []):
            down[e["src"]].append(e["dst"])
            indeg[e["dst"]] += 1
            grouping[(e["src"], e["dst"])] = e.get("grouping", "shuffle")
        order, frontier = [], sorted(c for c in cids if indeg[c] == 0)
        while frontier:
            c = frontier.pop(0)
            order.append(c)
            for d in down[c]:
                indeg[d] -= 1
                if indeg[d] == 0:
                    frontier.append(d)
        if len(order) != len(cids):
            raise ValueError(f"tenant {tid!r} is not a DAG")
        self.order, self.down = order, down
        spec_of = {c["id"]: c for c in comps}
        self.spouts = [c for c in cids if spec_of[c].get("is_spout", False)]
        self.sinks = [c for c in cids if not down[c]]
        self.pending = dtype(
            int(spec["max_spout_pending"])
            * sum(int(spec_of[c].get("parallelism", 1)) for c in self.spouts)
        )

        # Tasks, component by component; node -1 where not placed.
        self.node, self.rate, self.cost, self.max_rate = {}, {}, {}, {}
        for cid in cids:
            c = spec_of[cid]
            p = int(c.get("parallelism", 1))
            self.node[cid] = np.array(
                [node_index.get(placements.get(f"{tid}/{cid}[{i}]"), -1) for i in range(p)],
                dtype=np.int64,
            )
            self.cost[cid] = dtype(c["cpu_cost_per_tuple"])
            self.max_rate[cid] = c.get("max_rate_per_task")
        placed = {cid: self.node[cid] >= 0 for cid in cids}

        # Lossless per-task rates and per-node flows of each edge.
        task_in = {cid: np.zeros(len(self.node[cid]), dtype=dtype) for cid in cids}
        self.edges, flows = [], []
        for cid in order:
            c = spec_of[cid]
            if c.get("is_spout", False):
                share = dtype(1.0) / dtype(len(self.node[cid]))
                r = np.where(placed[cid], share, dtype(0.0)).astype(dtype)
                out = r
            else:
                r = task_in[cid]
                out = r * dtype(c["emit_ratio"])
            self.rate[cid] = r
            m = placed[cid]
            src_out = np.bincount(
                self.node[cid][m], weights=out[m], minlength=n_nodes
            ).astype(dtype)
            for dst in down[cid]:
                dst_n = np.bincount(self.node[dst][placed[dst]], minlength=n_nodes).astype(dtype)
                k = dtype(dst_n.sum())
                if k == 0:
                    F = np.zeros((n_nodes, n_nodes), dtype=dtype)
                elif grouping[(cid, dst)] == "local_or_shuffle":
                    local = dst_n > 0
                    F = np.outer(np.where(local, dtype(0.0), src_out), dst_n) / k
                    F[np.diag_indices(n_nodes)] += np.where(local, src_out, dtype(0.0))
                    # A dst task on node b gets node b's output over its local
                    # peers, plus its share of the output of nodes with none.
                    per_task = np.where(local, src_out / np.maximum(dst_n, dtype(1.0)), dtype(0.0))
                    spill = np.where(local, dtype(0.0), src_out).sum() / k
                    got = per_task[self.node[dst]] + spill
                    task_in[dst] += np.where(placed[dst], got, dtype(0.0))
                else:
                    F = np.outer(src_out, dst_n) / k
                    task_in[dst] += np.where(placed[dst], src_out.sum() / k, dtype(0.0))
                self.edges.append((cid, dst, dtype(c["tuple_bytes"])))
                flows.append(F)

        # Per-node usage per unit λ.
        self.cpu = np.zeros(n_nodes, dtype=dtype)
        self.memory = np.zeros(n_nodes, dtype=np.float64)
        for cid in cids:
            nodes, m = self.node[cid], placed[cid]
            cpu = self.rate[cid][m] * self.cost[cid]
            self.cpu += np.bincount(nodes[m], weights=cpu, minlength=n_nodes).astype(dtype)
            mb = float(spec_of[cid]["memory_load_mb"])
            self.memory += np.bincount(nodes[m], minlength=n_nodes) * mb
        same_node = np.eye(n_nodes, dtype=bool)
        same_rack = rack_of[:, None] == rack_of[None, :]
        base = np.where(
            same_node, LAT_SAME_NODE_S, np.where(same_rack, LAT_SAME_RACK_S, LAT_CROSS_RACK_S)
        ).astype(dtype)
        self.egress = np.zeros(n_nodes, dtype=dtype)
        self.ingress = np.zeros(n_nodes, dtype=dtype)
        self.rack_up = np.zeros(int(rack_of.max()) + 1, dtype=dtype)
        #: per edge: the flow-weighted mean base latency, and each sender
        #: node's share of the edge's flow that leaves the node.
        self.hop_base = np.zeros(len(flows), dtype=dtype)
        self.hop_share = np.zeros((len(flows), n_nodes), dtype=dtype)
        self.ser = np.zeros(len(flows), dtype=dtype)
        for e, ((src, _, tb), F) in enumerate(zip(self.edges, flows)):
            off = np.where(same_node, dtype(0.0), F)
            self.egress += off.sum(axis=1) * tb
            self.ingress += off.sum(axis=0) * tb
            cross = np.where(same_rack, dtype(0.0), F).sum(axis=1) * tb
            self.rack_up += np.bincount(
                rack_of, weights=cross, minlength=len(self.rack_up)
            ).astype(dtype)
            total = F.sum()
            if total > EPS:
                self.hop_base[e] = (F * base).sum() / total
                self.hop_share[e] = off.sum(axis=1) / total
            self.ser[e] = tb / dtype(NIC_BYTES_PER_S)
        self.edge_index = {(s, d): e for e, (s, d, _) in enumerate(self.edges)}
        self.sink_rate = dtype(sum(self.rate[c].sum() for c in self.sinks))

    def source_bound(self) -> float:
        b = math.inf
        for cid, mr in self.max_rate.items():
            if mr is None:
                continue
            r = self.rate[cid]
            if (r > EPS).any():
                b = min(b, float(mr / r[r > EPS].max()))
        return b


class _Cluster:
    def __init__(self, cfg: dict, dead: Iterable[str], dtype):
        c = cfg["cluster"]
        racks, per_rack = int(c["racks"]), int(c["nodes_per_rack"])
        dead = set(dead)
        self.index = {
            f"r{r}n{n}": r * per_rack + n
            for r in range(racks)
            for n in range(per_rack)
            if f"r{r}n{n}" not in dead
        }
        self.n = racks * per_rack
        self.rack_of = np.repeat(np.arange(racks), per_rack)
        self.cpu = dtype(c["cpu"])
        self.memory_mb = float(c["memory_mb"])


class _Solver:
    def __init__(self, cluster: _Cluster, tenants, dtype):
        self.dtype = dtype
        self.tenants = tenants
        mem = sum(t.memory for t in tenants)
        thrashed = mem > cluster.memory_mb + 1e-9
        thrashed_cpu = cluster.cpu * dtype(THRASH_FACTOR)
        self.eff_cpu = np.where(thrashed, thrashed_cpu, cluster.cpu).astype(dtype)
        self.one_core = dtype(min(float(cluster.cpu), ONE_CORE))

    def _others(self, i, lam):
        """Per-node CPU and NIC use and per-rack uplink use of every tenant
        but ``i`` at its rate ``lam[j]``."""
        me = self.tenants[i]
        use = [np.zeros_like(a) for a in (me.cpu, me.egress, me.ingress, me.rack_up)]
        for j, t in enumerate(self.tenants):
            if j != i:
                for acc, a in zip(use, (t.cpu, t.egress, t.ingress, t.rack_up)):
                    acc += a * lam[j]
        return use

    def latency(self, t: _Tenant, lam, other_cpu, other_egress):
        d = self.dtype
        util = np.minimum((t.egress * lam + other_egress) / d(NIC_BYTES_PER_S), d(UTIL_CAP))
        slow = d(1.0) / np.maximum(d(1e-3), d(1.0) - util)
        hop = t.hop_base + t.ser * (t.hop_share @ slow)
        service = {}
        for cid in t.order:
            cost, mr = t.cost[cid], t.max_rate[cid]
            nodes, r = t.node[cid], t.rate[cid]
            m = nodes >= 0
            if (cost <= EPS and mr is None) or not m.any():
                service[cid] = d(0.0)
                continue
            n, r = nodes[m], r[m]
            residual = self.eff_cpu[n] - t.cpu[n] * lam - other_cpu[n] + r * lam * cost
            points = np.maximum(np.minimum(residual, self.one_core), d(0.0))
            mu = points / cost if cost > EPS else np.full(len(n), np.inf, dtype=d)
            if mr is not None:
                mu = np.minimum(mu, d(mr))
            mu = np.maximum(mu, d(EPS))
            rho = np.minimum(r * lam / mu, d(RHO_CAP))
            w = np.maximum(r, d(EPS))
            service[cid] = (w * (d(1.0) / mu) / (d(1.0) - rho)).sum() / w.sum()
        path = {}
        for cid in reversed(t.order):
            best = d(0.0)
            for dst in t.down[cid]:
                best = max(best, hop[t.edge_index[(cid, dst)]] + service[dst] + path[dst])
            path[cid] = best
        lat = d(0.0)
        for sp in t.spouts:
            lat = max(lat, service[sp] + path[sp])
        return lat + d(ACK_OVERHEAD_S)

    def solve(self, i: int, lam) -> float:
        """Tenant i's λ against the others' current rates."""
        d = self.dtype
        t = self.tenants[i]
        if not t.acked:
            raise ValueError("the fluid re-score models acked topologies only")
        o_cpu, o_eg, o_in, o_up = self._others(i, lam)
        hard = min(
            t.source_bound(),
            _capacity_bound(t.egress, d(NIC_BYTES_PER_S) - o_eg),
            _capacity_bound(t.ingress, d(NIC_BYTES_PER_S) - o_in),
            _capacity_bound(t.rack_up, d(RACK_UPLINK_BYTES_PER_S) - o_up),
            _capacity_bound(t.cpu, self.eff_cpu - o_cpu),
        )
        pending = t.pending

        def psi(x):
            return x * self.latency(t, d(x), o_cpu, o_eg) - pending

        hi = min(hard, float(pending) / ACK_OVERHEAD_S)
        f_hi = psi(hi)
        if f_hi <= 0.0:  # the ack loop does not bind below the hard bounds
            return d(hi)
        # Illinois: regula falsi on psi, which rises with x, bracket [lo, hi].
        lo, f_lo, side = 0.0, -float(pending), 0
        rtol = max(SOLVE_RTOL, 4.0 * float(np.finfo(d).eps))
        x = hi
        for _ in range(200):
            x = (lo * f_hi - hi * f_lo) / (f_hi - f_lo)
            if not lo < x < hi:
                x = 0.5 * (lo + hi)
            f = float(psi(x))
            if f == 0.0:
                break
            if f > 0.0:
                hi, f_hi = x, f
                if side == 1:
                    f_lo *= 0.5
                side = 1
            else:
                lo, f_lo = x, f
                if side == -1:
                    f_hi *= 0.5
                side = -1
            if hi - lo <= rtol * hi:
                x = 0.5 * (lo + hi)
                break
        return d(x)

    def joint(self) -> list:
        """Every tenant's λ at the joint fixed point λ_i = solve(i, λ), by
        rounds from rest in which each tenant is solved against the others'
        current rates.  Where two tenants bind each other on a shared node
        the fixed points can form a segment and the rounds drift along it;
        after ``ROUNDS`` the last round's rates stand (the program's own
        joint solve stops after 40)."""
        n = len(self.tenants)
        lam = [self.dtype(0.0)] * n
        for _ in range(ROUNDS if n > 1 else 1):
            delta = 0.0
            for i in range(n):
                new = self.solve(i, lam)
                delta = max(delta, abs(float(new) - float(lam[i])))
                lam[i] = new
            if delta <= JOINT_RTOL * max(1.0, max(float(x) for x in lam)):
                break
        return lam


def sink_throughputs(cfg: dict, placements: Mapping[str, Mapping[str, str]],
                     tenant_of: Mapping[str, int], dead: Iterable[str] = (),
                     dtype=np.float64) -> Dict[str, float]:
    """Steady-state sink throughput, tuples/s, of each topology id in
    ``placements`` (task id -> node id), solved jointly: every tenant given
    shares the cluster with the others.  ``tenant_of`` maps a topology id to
    its tenant in ``cfg``; task ids are ``<topology id>/<component>[<i>]``.
    ``dtype`` float32 makes the lower-precision solve of the control.
    Raises :class:`Unstated` where a tenant leaves out what the model needs."""
    cluster = _Cluster(cfg, dead, dtype)
    ids = sorted(placements)
    tenants = []
    for tid in ids:
        spec = dict(cfg["tenants"][tenant_of[tid]])
        missing = unstated(spec)
        if missing:
            raise Unstated("the fluid re-score needs " + ", ".join(missing))
        spec["id"] = tid
        tenants.append(
            _Tenant(spec, placements[tid], cluster.index, cluster.n, cluster.rack_of, dtype)
        )
    lam = _Solver(cluster, tenants, dtype).joint()
    return {tid: float(lam[i] * t.sink_rate) for i, (tid, t) in enumerate(zip(ids, tenants))}


def cluster_throughput(cfg: dict, placements, tenant_of, dead: Iterable[str] = ()) -> float:
    """Summed sink throughput of every tenant in ``placements``, jointly."""
    return sum(sink_throughputs(cfg, placements, tenant_of, dead).values())


def alone(cfg: dict, tid: str, placement: Mapping[str, str], tenant: int,
          dead: Iterable[str] = (), dtype=np.float64) -> float:
    """Sink throughput of topology ``tid`` alone on the cluster, as the
    program's never-worse check and its reported plan simulate it."""
    return sink_throughputs(cfg, {tid: placement}, {tid: tenant}, dead, dtype)[tid]
