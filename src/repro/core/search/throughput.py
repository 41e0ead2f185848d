"""Batched throughput proxy — search what the paper measures (§6).

The paper's headline claims are about *sink throughput*, but network cost is
only a proxy that diverges exactly in the CPU-bound and shedding regimes
(§6.3.2, §6.5).  This module distills the simulator's binding analysis
(:mod:`repro.stream.simulator`) into a per-candidate bound that is one
vmapped/jit jax reduction over a ``(B, T)`` placement batch:

    proxy(p) = min(source, cpu(p), bandwidth(p), ack(p)) × lossless sink rate

* **source** — the placement-independent λ ceiling from intrinsic per-task
  rates (``max_rate_per_task``);
* **cpu(p)** — segment-sum the per-task CPU cost rows onto nodes, divide
  into per-node *effective* capacity (memory over-subscription thrashes a
  node to ``thrash_factor`` of its CPU, the §6.5 collapse mechanism);
* **bandwidth(p)** — edge-gather per-link flow: per-NIC egress/ingress and
  per-rack uplink bytes per unit λ against link capacity;
* **ack(p)** — first-order credit loop for acked topologies:
  ``pending / L₀(p)`` with L₀ the *zero-load* critical-path latency
  (flow-weighted hop latencies by placement class + per-component service
  at free capacity + the constant acker round trip).  The queueing-aware
  refinement (utilization-inflated serialization, M/M/1 sojourn at the
  operating point) is a recorded ROADMAP follow-up.

The per-task rates are the simulator's *lossless* component rates under a
uniform shuffle split (placement-independent by construction — what makes
the whole bound a gather/segment-sum instead of a fixed-point solve).  The
evaluator models Storm's ``local_or_shuffle`` locality routing for the
bandwidth/ack terms: a src task with a colocated dst routes everything
locally (no NIC bytes, intra-node latency), computed per candidate via one
extra segment-sum of colocation counts.  The annealer's O(degree)
incremental hot loop keeps the uniform-split approximation (locality flips
have non-local state effects); the scheduler's final candidate selection
and the never-worse-than-greedy check use this faithful evaluator.

Exactness contract (the same golden-equality bar as ``evaluate_batch``):
every per-task rate/flow is quantized to a dyadic grid at compile time
(``GRID`` for resource rows, the finer ``ACK_GRID`` for latency×flow
summands), so all segment-sums are exact integer arithmetic in float64 —
the sum order (numpy ``add.at`` vs XLA scatter/segment_sum) cannot change a
bit, and the numpy fallback is bit-identical to the jax path.  The scalar
simulator reuses :func:`capacity_bound` for its own per-node bounds, so the
proxy and the simulator share one source of truth for "binding bound"
semantics.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import numpy as np

from .backend import chunk_ranges, fetch, jax_modules, resolve_backend, x64
from .batch import BatchArena

_EPS = 1e-12

#: Dyadic quantization grid for per-task rates/flows: values become exact
#: multiples of 2^-26, so segment-sums (of realistically bounded magnitude)
#: are exact in float64 regardless of accumulation order — the structural
#: guarantee behind numpy/jax bit-equality of the proxy.
GRID = 2.0 ** -26

#: Finer grid for latency×flow summands (magnitudes ~1e-8..1e-2); sums stay
#: exact while below 2^53 × ACK_GRID ≈ 32 seconds of aggregate latency.
ACK_GRID = 2.0 ** -48


def quantize(x: np.ndarray, grid: float = GRID) -> np.ndarray:
    """Round to a dyadic grid (float64, exact representation)."""
    return np.round(np.asarray(x, dtype=np.float64) / grid) * grid


def capacity_bound(use, cap, xp=np):
    """λ ceiling from ``use × λ ≤ cap`` per entry, reduced over the trailing
    axis: ``min over entries with use > eps of max(cap, 0) / use`` (``inf``
    when nothing binds).

    The one array-form "binding bound" both the scalar simulator
    (``Simulator._cpu_bound`` / ``_bandwidth_bound``) and the batched proxy
    compute — extracted so the two cannot drift.
    """
    use = xp.asarray(use)
    binds = use > _EPS
    ratio = xp.where(binds, xp.maximum(cap, 0.0) / xp.where(binds, use, 1.0), xp.inf)
    return xp.min(ratio, axis=-1, initial=xp.inf)


@dataclasses.dataclass(frozen=True)
class AckPlan:
    """Static (hashable) description of the zero-load ack-loop bound.

    ``dp`` drives the unrolled critical-path recursion: for each component
    (reverse topological order) the tuple of ``(comp_edge_index, downstream
    component index)`` pairs; ``svc`` is the per-component zero-load service
    delay; ``spouts`` the component indices the path maximum starts from.
    Hashable so the jit-compiled evaluator caches per topology structure.
    """

    acked: bool
    pending: float
    ack_overhead_s: float
    svc: Tuple[float, ...]
    spouts: Tuple[int, ...]
    dp: Tuple[Tuple[int, Tuple[Tuple[int, int], ...]], ...]
    n_comp_edges: int


def ack_lambda(num, den, plan: AckPlan, xp=np):
    """λ ceiling from the credit loop: pending / L₀, where the hop latency
    of component edge *k* is ``num[..., k] / den[k]`` (flow-weighted mean
    over its task pairs) and L₀ is the critical spout→sink path.

    ``num`` has trailing axis ``max(n_comp_edges, 1)`` (leading axes
    broadcast); returns that leading shape.  ``inf`` (a scalar — the
    minimum with it is an identity) for unanchored topologies.
    """
    if not plan.acked:
        return np.inf
    hop = xp.where(den > 0.0, num / xp.where(den > 0.0, den, 1.0), 0.0)
    zeros = hop[..., 0] * 0.0
    path = {}
    for ci, downs in plan.dp:
        best = zeros
        for ce, d in downs:
            best = xp.maximum(best, hop[..., ce] + plan.svc[d] + path[d])
        path[ci] = best
    L = zeros
    for sp in plan.spouts:
        L = xp.maximum(L, plan.svc[sp] + path[sp])
    return plan.pending / (L + plan.ack_overhead_s)


@dataclasses.dataclass(frozen=True)
class ThroughputModel:
    """Per-(topology, cluster) arrays the proxy reduces over.

    All per-task quantities are grid-quantized; all arrays are aligned with
    the owning ``BatchArena`` (``tids`` task order, ``node_ids`` node order,
    ``edges`` edge order, ``adj`` adjacency slots).
    """

    task_cpu: np.ndarray   # (T,) CPU points per unit λ (rate × cost)
    task_mem: np.ndarray   # (T,) memory MB (static)
    cpu_cap: np.ndarray    # (N,) CPU points
    mem_cap: np.ndarray    # (N,) memory MB
    rack_of: np.ndarray    # (N,) intp rack index
    n_racks: int
    edge_bytes: np.ndarray  # (E,) bytes/s per unit λ, aligned with ba.edges
    edge_comp: np.ndarray   # (E,) intp component-edge index per task edge
    edge_lat: np.ndarray    # (3, E) latency×flow summands per placement class
    den_flow: np.ndarray    # (n_comp_edges,) flow sums (hop-mean denominators)
    # Storm locality routing (local_or_shuffle): a src task with ≥1
    # colocated dst task routes *everything* locally — its pairs carry no
    # NIC bytes and intra-node latency.  ``pair_key`` maps each task edge
    # to its (src task, comp edge) combo; ``local_num`` is the combo's
    # quantized out-rate × intra-node latency (its ack contribution while
    # locally routed; zero for shuffle combos).
    edge_local: np.ndarray  # (E,) bool — src component edge is local_or_shuffle
    pair_key: np.ndarray    # (E,) intp combo index
    combo_ce: np.ndarray    # (K,) intp comp-edge per combo
    local_num: np.ndarray   # (K,) float64
    n_combos: int
    adj_bytes: np.ndarray   # (T, max_deg) per-slot edge bytes, aligned with ba.adj
    adj_src: np.ndarray     # (T, max_deg) True where the row task is the edge src
    adj_comp: np.ndarray    # (T, max_deg) intp component-edge index per slot
    adj_lat: np.ndarray     # (3, T, max_deg) latency×flow summands per slot
    ack: AckPlan
    nic_bw: float
    rack_bw: float
    thrash_factor: float
    source_bound: float    # scalar λ ceiling (inf when no component is rate-limited)
    sink_rate: float       # lossless per-unit-λ sink processing rate

    @property
    def nic_cap(self) -> np.ndarray:
        return np.full(self.cpu_cap.shape[0], self.nic_bw, dtype=np.float64)

    @property
    def rack_cap(self) -> np.ndarray:
        return np.full(max(self.n_racks, 1), self.rack_bw, dtype=np.float64)


def lossless_task_profile(topology):
    """(per-task rate, per-task-edge flow) under the lossless uniform split.

    Returns ``(rates, flows)`` where ``rates[tid]`` is the per-unit-λ
    processed rate of one task (spouts: emitted) and ``flows[(src_cid,
    dst_cid)]`` is the per-unit-λ tuple flow on one (src task, dst task)
    pair of that component edge.  Placement-independent: shuffle semantics
    split each task's output uniformly over all downstream tasks.
    """
    from ...stream.simulator import _component_rates  # stream imports core; lazy here

    rate_in, rate_out = _component_rates(topology)
    rates = {}
    for cid, comp in topology.components.items():
        r = rate_out[cid] if comp.is_spout else rate_in[cid]
        per_task = r / comp.parallelism
        for t in comp.tasks(topology.id):
            rates[t.id] = per_task
    flows = {}
    for src, dst in topology.edges:
        cs, cd = topology.components[src], topology.components[dst]
        flows[(src, dst)] = rate_out[src] / (cs.parallelism * cd.parallelism)
    return rates, flows


def _ack_plan(topology, cluster, ce_of, ack_overhead_s) -> AckPlan:
    """Compile the static critical-path recursion for the ack bound."""
    from ...stream.simulator import _cpu_cost, _topo_order

    order = _topo_order(topology)
    cindex = {cid: k for k, cid in enumerate(order)}
    live_caps = [n.spec.cpu_capacity for n in cluster.live_nodes()]
    one_core = min(min(live_caps) if live_caps else 100.0, 100.0)
    svc = []
    for cid in order:
        comp = topology.components[cid]
        cost = _cpu_cost(comp)
        mu = one_core / cost if cost > _EPS else np.inf
        if comp.max_rate_per_task is not None:
            mu = min(mu, comp.max_rate_per_task)
        svc.append(1.0 / mu if np.isfinite(mu) and mu > _EPS else 0.0)
    dp = tuple(
        (
            cindex[cid],
            tuple(
                (ce_of[(cid, d)], cindex[d]) for d in topology.downstream(cid)
            ),
        )
        for cid in reversed(order)
    )
    pending = sum(
        topology.max_spout_pending * c.parallelism for c in topology.spouts
    )
    return AckPlan(
        acked=bool(topology.acked),
        pending=float(pending),
        ack_overhead_s=float(ack_overhead_s),
        svc=tuple(svc),
        spouts=tuple(cindex[c.id] for c in topology.spouts),
        dp=dp,
        n_comp_edges=len(ce_of),
    )


def compile_throughput(
    ba: BatchArena,
    topology,
    cluster,
    network=None,
    thrash_factor: Optional[float] = None,
) -> ThroughputModel:
    """Compile the proxy arrays for one ``BatchArena``.

    ``network`` defaults to the paper's Emulab model; ``thrash_factor`` and
    the ack overhead to the simulator's constants (so proxy and simulator
    agree on the §6.5 collapse mechanism and the credit loop).
    """
    from ...stream.simulator import ACK_OVERHEAD_S, THRASH_FACTOR, _cpu_cost
    from ...stream.network import EMULAB_NETWORK

    if network is None:
        network = EMULAB_NETWORK
    if thrash_factor is None:
        thrash_factor = THRASH_FACTOR
    if ba.rack_of is None:
        raise ValueError("BatchArena was compiled without rack information")

    rates, flows = lossless_task_profile(topology)
    comps = topology.components
    tindex = {tid: i for i, tid in enumerate(ba.tids)}

    task_cpu = np.zeros(ba.n_tasks, dtype=np.float64)
    task_mem = np.zeros(ba.n_tasks, dtype=np.float64)
    for t in topology.all_tasks():
        i = tindex.get(t.id)
        if i is None:
            continue
        comp = comps[t.component_id]
        # Same units as _TopologyLoad._build: points per unit λ.
        task_cpu[i] = rates[t.id] * _cpu_cost(comp)
        task_mem[i] = comp.memory_load

    ce_of = {edge: k for k, edge in enumerate(topology.edges)}

    # Per-task-edge arrays, replaying BatchArena.from_arena's edge loop so
    # rows align with ba.edges and slots with ba.adj.  The three edge_lat
    # rows are the quantized latency×flow summands for the placement
    # classes (same node / same rack / inter-rack); crossing classes carry
    # the zero-load serialization delay.
    E = ba.edges.shape[0]
    edge_bytes = np.zeros(E, dtype=np.float64)
    edge_comp = np.zeros(E, dtype=np.intp)
    edge_lat = np.zeros((3, E), dtype=np.float64)
    edge_local = np.zeros(E, dtype=bool)
    pair_key = np.zeros(E, dtype=np.intp)
    combo_index: dict = {}
    combo_ce_list: List[int] = []
    local_num_list: List[float] = []
    adj_bytes = np.zeros(ba.adj.shape, dtype=np.float64)
    adj_src = np.zeros(ba.adj.shape, dtype=bool)
    adj_comp = np.zeros(ba.adj.shape, dtype=np.intp)
    adj_lat = np.zeros((3,) + ba.adj.shape, dtype=np.float64)
    slot = [0] * ba.n_tasks
    e = 0
    for src, dst in topology.task_edges():
        a, b = tindex.get(src.id), tindex.get(dst.id)
        if a is None or b is None:
            continue
        cs = comps[src.component_id]
        cedge = (src.component_id, dst.component_id)
        flow = flows[cedge]
        byt = float(quantize(flow * cs.tuple_bytes))
        ser = cs.tuple_bytes / network.nic_bw
        lat3 = quantize(
            np.array(
                [
                    network.lat_inter_process * flow,
                    (network.lat_inter_node + ser) * flow,
                    (network.lat_inter_rack + ser) * flow,
                ]
            ),
            ACK_GRID,
        )
        ce = ce_of[cedge]
        is_local = topology.groupings.get(cedge, "shuffle") == "local_or_shuffle"
        combo = (a, ce)
        if combo not in combo_index:
            combo_index[combo] = len(combo_ce_list)
            combo_ce_list.append(ce)
            # Per-src-task ack contribution while locally routed: the whole
            # out rate traverses intra-node hops (only local combos use it).
            n_dst = comps[dst.component_id].parallelism
            local_num_list.append(
                float(
                    quantize(flow * n_dst * network.lat_inter_process, ACK_GRID)
                )
                if is_local
                else 0.0
            )
        assert ba.adj[a, slot[a]] == b and ba.adj[b, slot[b]] == a
        edge_bytes[e] = byt
        edge_comp[e] = ce
        edge_lat[:, e] = lat3
        edge_local[e] = is_local
        pair_key[e] = combo_index[combo]
        for r, is_src in ((a, True), (b, False)):
            adj_bytes[r, slot[r]] = byt
            adj_src[r, slot[r]] = is_src
            adj_comp[r, slot[r]] = ce
            adj_lat[:, r, slot[r]] = lat3
            slot[r] += 1
        e += 1
    combo_ce = (
        np.array(combo_ce_list, dtype=np.intp)
        if combo_ce_list
        else np.zeros(1, dtype=np.intp)
    )
    local_num = (
        np.array(local_num_list, dtype=np.float64)
        if local_num_list
        else np.zeros(1, dtype=np.float64)
    )

    den_flow = np.zeros(max(len(ce_of), 1), dtype=np.float64)
    q_flows = {edge: float(quantize(f, ACK_GRID)) for edge, f in flows.items()}
    for src, dst in topology.task_edges():
        if src.id in tindex and dst.id in tindex:
            den_flow[ce_of[(src.component_id, dst.component_id)]] += q_flows[
                (src.component_id, dst.component_id)
            ]

    source = np.inf
    for comp in comps.values():
        if comp.max_rate_per_task is None:
            continue
        r = rates[comp.tasks(topology.id)[0].id]  # equal across the component
        if r > _EPS:
            source = min(source, comp.max_rate_per_task / r)
    sink_rate = sum(
        rates[t.id] for s in topology.sinks() for t in s.tasks(topology.id)
    )

    cpu_cap = np.array(
        [cluster.nodes[nid].spec.cpu_capacity for nid in ba.node_ids], dtype=np.float64
    )
    mem_cap = np.array(
        [cluster.nodes[nid].spec.memory_capacity_mb for nid in ba.node_ids],
        dtype=np.float64,
    )
    return ThroughputModel(
        task_cpu=quantize(task_cpu),
        task_mem=quantize(task_mem),
        cpu_cap=cpu_cap,
        mem_cap=mem_cap,
        rack_of=ba.rack_of.astype(np.intp),
        n_racks=int(ba.n_racks),
        edge_bytes=edge_bytes,
        edge_comp=edge_comp,
        edge_lat=edge_lat,
        den_flow=den_flow,
        edge_local=edge_local,
        pair_key=pair_key,
        combo_ce=combo_ce,
        local_num=local_num,
        n_combos=max(len(combo_ce_list), 1),
        adj_bytes=adj_bytes,
        adj_src=adj_src,
        adj_comp=adj_comp,
        adj_lat=adj_lat,
        ack=_ack_plan(topology, cluster, ce_of, ACK_OVERHEAD_S),
        nic_bw=float(network.nic_bw),
        rack_bw=float(network.rack_uplink_bw),
        thrash_factor=float(thrash_factor),
        source_bound=float(source),
        sink_rate=float(sink_rate),
    )


def hard_lambda(
    cpu_load, mem_used, egress, ingress, rack_up,
    cpu_cap, mem_cap, nic_cap, rack_cap,
    thrash_factor, source_bound, xp=np,
):
    """min(source, cpu, bandwidth) from per-node/per-rack aggregates
    (trailing axis = nodes/racks; leading axes broadcast — ``(B, N)``
    batches or ``(N,)`` singles).  Shared by the batched evaluator and the
    annealer's hot loop."""
    eff_cap = xp.where(mem_used > mem_cap + 1e-9, cpu_cap * thrash_factor, cpu_cap)
    b = capacity_bound(cpu_load, eff_cap, xp=xp)
    b = xp.minimum(b, capacity_bound(egress, nic_cap, xp=xp))
    b = xp.minimum(b, capacity_bound(ingress, nic_cap, xp=xp))
    b = xp.minimum(b, capacity_bound(rack_up, rack_cap, xp=xp))
    return xp.minimum(b, source_bound)


def edge_lat_class(src_n, dst_n, rack_of, edge_lat, xp=np):
    """Select the latency×flow summand per task edge from its placement
    class (gather rows of the precompiled (3, ...) quantized table)."""
    same_node = src_n == dst_n
    same_rack = rack_of[src_n] == rack_of[dst_n]
    return xp.where(
        same_node, edge_lat[0], xp.where(same_rack, edge_lat[1], edge_lat[2])
    )


def aggregates_numpy(ba: BatchArena, tm: ThroughputModel, P: np.ndarray):
    """(cpu_load, mem_used, egress, ingress, rack_up, ack_num) for a
    ``(B, T)`` batch — the carried state of the throughput objective."""
    B = P.shape[0]
    N, R = ba.n_nodes, max(tm.n_racks, 1)
    CE = max(tm.ack.n_comp_edges, 1)
    bidx = np.arange(B)[:, None]
    cpu_load = np.zeros((B, N))
    mem_used = np.zeros((B, N))
    np.add.at(cpu_load, (bidx, P), tm.task_cpu[None, :])
    np.add.at(mem_used, (bidx, P), tm.task_mem[None, :])
    egress = np.zeros((B, N))
    ingress = np.zeros((B, N))
    rack_up = np.zeros((B, R))
    ack_num = np.zeros((B, CE))
    if ba.edges.shape[0]:
        src_n = P[:, ba.edges[:, 0]]
        dst_n = P[:, ba.edges[:, 1]]
        cross = src_n != dst_n
        w = np.where(cross, tm.edge_bytes[None, :], 0.0)
        np.add.at(egress, (bidx, src_n), w)
        np.add.at(ingress, (bidx, dst_n), w)
        rs, rd = tm.rack_of[src_n], tm.rack_of[dst_n]
        wr = np.where(rs != rd, tm.edge_bytes[None, :], 0.0)
        np.add.at(rack_up, (bidx, rs), wr)
        lat = edge_lat_class(src_n, dst_n, tm.rack_of, tm.edge_lat[:, None, :])
        np.add.at(ack_num, (bidx, np.broadcast_to(tm.edge_comp, src_n.shape)), lat)
    return cpu_load, mem_used, egress, ingress, rack_up, ack_num


def proxy_from_state(
    cpu_load, mem_used, egress, ingress, rack_up, ack_num, tm: ThroughputModel, xp=np
):
    """The full proxy from carried aggregates (leading axes broadcast)."""
    lam = hard_lambda(
        cpu_load, mem_used, egress, ingress, rack_up,
        tm.cpu_cap, tm.mem_cap, tm.nic_cap, tm.rack_cap,
        tm.thrash_factor, tm.source_bound, xp=xp,
    )
    lam = xp.minimum(lam, ack_lambda(ack_num, tm.den_flow, tm.ack, xp=xp))
    return lam * tm.sink_rate


def swap_state_terms(
    P, bidx, i, j, na, nb, adj, adj_bytes, adj_src, adj_comp, adj_lat, rack_of,
    xp=np,
):
    """Scatter terms updating the carried throughput state for swapping the
    nodes of task rows ``i`` (na→nb) and ``j`` (nb→na), per chain.

    Returns ``(eg_idx, eg_val, in_idx, in_val, rk_idx, rk_val, ce_idx,
    ce_val)``, each ``(B, 4·max_deg)``: old contributions of the incident
    edges negated, new contributions positive.  Mutual i–j edges appear in
    both adjacency rows and are halved (0.5× a grid value is exact), so
    their total stays right; padded slots carry zero weights throughout.
    """
    col = bidx[:, None]
    parts = []
    for r, pos_old, pos_new, other, other_new in (
        (i, na, nb, j, na),
        (j, nb, na, i, nb),
    ):
        nbr = adj[r]
        w = adj_bytes[r]
        is_src = adj_src[r]
        ce = adj_comp[r]
        l0, l1, l2 = adj_lat[0][r], adj_lat[1][r], adj_lat[2][r]
        mutual = nbr == other[:, None]
        half = xp.where(mutual, 0.5, 1.0)
        nbr_old = P[col, xp.where(nbr >= 0, nbr, 0)]
        nbr_new = xp.where(mutual, other_new[:, None], nbr_old)
        for pos_r, nbr_pos, sign in (
            (pos_old, nbr_old, -1.0),
            (pos_new, nbr_new, 1.0),
        ):
            src = xp.where(is_src, pos_r[:, None], nbr_pos)
            dst = xp.where(is_src, nbr_pos, pos_r[:, None])
            same_node = src == dst
            v = sign * half * xp.where(same_node, 0.0, w)
            rs, rd = rack_of[src], rack_of[dst]
            same_rack = rs == rd
            vr = sign * half * xp.where(same_rack, 0.0, w)
            vl = sign * half * xp.where(
                same_node, l0, xp.where(same_rack, l1, l2)
            )
            parts.append((src, v, dst, v, rs, vr, ce, vl))
    return tuple(
        xp.concatenate([p[k] for p in parts], axis=1) for k in range(8)
    )


def _locality_chunk_numpy(ba: BatchArena, tm: ThroughputModel, P: np.ndarray):
    """Locality-aware proxy for one numpy chunk — the faithful evaluator
    (the annealer's carried state keeps the uniform-split approximation;
    see the module docstring)."""
    B = P.shape[0]
    N, R = ba.n_nodes, max(tm.n_racks, 1)
    CE, K = max(tm.ack.n_comp_edges, 1), tm.n_combos
    bidx = np.arange(B)[:, None]
    cpu_load = np.zeros((B, N))
    mem_used = np.zeros((B, N))
    np.add.at(cpu_load, (bidx, P), tm.task_cpu[None, :])
    np.add.at(mem_used, (bidx, P), tm.task_mem[None, :])
    egress = np.zeros((B, N))
    ingress = np.zeros((B, N))
    rack_up = np.zeros((B, R))
    ack_num = np.zeros((B, CE))
    if ba.edges.shape[0]:
        src_n = P[:, ba.edges[:, 0]]
        dst_n = P[:, ba.edges[:, 1]]
        colo = src_n == dst_n
        L = np.zeros((B, K))
        np.add.at(
            L,
            (bidx, np.broadcast_to(tm.pair_key, src_n.shape)),
            colo.astype(np.float64),
        )
        L_pair = L[:, tm.pair_key]  # (B, E) gather of each pair's combo count
        routed_local = tm.edge_local[None, :] & (L_pair > 0.0)
        w = np.where(~colo & ~routed_local, tm.edge_bytes[None, :], 0.0)
        np.add.at(egress, (bidx, src_n), w)
        np.add.at(ingress, (bidx, dst_n), w)
        rs, rd = tm.rack_of[src_n], tm.rack_of[dst_n]
        wr = np.where((rs != rd) & ~routed_local, tm.edge_bytes[None, :], 0.0)
        np.add.at(rack_up, (bidx, rs), wr)
        lat = np.where(
            routed_local,
            0.0,
            edge_lat_class(src_n, dst_n, tm.rack_of, tm.edge_lat[:, None, :]),
        )
        np.add.at(ack_num, (bidx, np.broadcast_to(tm.edge_comp, src_n.shape)), lat)
        ln = np.where(L > 0.0, tm.local_num[None, :], 0.0)
        np.add.at(ack_num, (bidx, np.broadcast_to(tm.combo_ce, ln.shape)), ln)
    return proxy_from_state(
        cpu_load, mem_used, egress, ingress, rack_up, ack_num, tm
    )


def _throughput_numpy(ba: BatchArena, tm: ThroughputModel, P: np.ndarray, chunk: int):
    B = P.shape[0]
    out = np.zeros(B, dtype=np.float64)
    for lo, hi in chunk_ranges(B, chunk):
        out[lo:hi] = _locality_chunk_numpy(ba, tm, P[lo:hi])
    return out


def _throughput_pallas(ba: BatchArena, tm: ThroughputModel, P: np.ndarray, chunk: int):
    """Proxy via the fused scoring kernel (netcost/capacity/dead ride along
    in the same pass — the point of the fusion; callers that want all four
    should go through ``evaluate_batch(backend="pallas")`` directly)."""
    from .kernels import fused_score  # jax-only import, deferred

    B = P.shape[0]
    out = np.zeros(B, dtype=np.float64)
    for lo, hi in chunk_ranges(B, chunk):
        out[lo:hi] = fused_score(ba, P[lo:hi], tm=tm)[3]
    return out


@functools.lru_cache(maxsize=None)
def _jax_tp_fn(n_nodes: int, n_racks: int, n_combos: int, ack: AckPlan):
    """jit-compiled vmapped proxy (cached per node/rack/combo count and
    topology structure; array shapes re-specialize via jit's own cache)."""
    jax, jnp = jax_modules()
    n_racks = max(n_racks, 1)
    n_ce = max(ack.n_comp_edges, 1)

    @jax.jit
    def evaluate(
        P, task_cpu, task_mem, cpu_cap, mem_cap, nic_cap, rack_cap,
        edges, edge_bytes, edge_comp, edge_lat, den_flow, rack_of,
        edge_local, pair_key, combo_ce, local_num,
        thrash_factor, source_bound, sink_rate,
    ):
        def one(p):
            cpu_load = jax.ops.segment_sum(task_cpu, p, num_segments=n_nodes)
            mem_used = jax.ops.segment_sum(task_mem, p, num_segments=n_nodes)
            src_n, dst_n = p[edges[:, 0]], p[edges[:, 1]]
            colo = src_n == dst_n
            L = jax.ops.segment_sum(
                colo.astype(jnp.float64), pair_key, num_segments=n_combos
            )
            routed_local = edge_local & (L[pair_key] > 0.0)
            w = jnp.where(~colo & ~routed_local, edge_bytes, 0.0)
            egress = jax.ops.segment_sum(w, src_n, num_segments=n_nodes)
            ingress = jax.ops.segment_sum(w, dst_n, num_segments=n_nodes)
            rs, rd = rack_of[src_n], rack_of[dst_n]
            wr = jnp.where((rs != rd) & ~routed_local, edge_bytes, 0.0)
            rack_up = jax.ops.segment_sum(wr, rs, num_segments=n_racks)
            lat = jnp.where(
                routed_local,
                0.0,
                edge_lat_class(src_n, dst_n, rack_of, edge_lat, xp=jnp),
            )
            ack_num = jax.ops.segment_sum(lat, edge_comp, num_segments=n_ce)
            ln = jnp.where(L > 0.0, local_num, 0.0)
            ack_num = ack_num + jax.ops.segment_sum(
                ln, combo_ce, num_segments=n_ce
            )
            lam = hard_lambda(
                cpu_load, mem_used, egress, ingress, rack_up,
                cpu_cap, mem_cap, nic_cap, rack_cap,
                thrash_factor, source_bound, xp=jnp,
            )
            lam = jnp.minimum(lam, ack_lambda(ack_num, den_flow, ack, xp=jnp))
            return lam * sink_rate

        return jax.vmap(one)(P)

    return evaluate


def _throughput_jax(ba: BatchArena, tm: ThroughputModel, P: np.ndarray, chunk: int):
    fn = _jax_tp_fn(ba.n_nodes, tm.n_racks, tm.n_combos, tm.ack)
    out = np.zeros(P.shape[0], dtype=np.float64)
    with x64():
        # Honor chunking on the jax path too: one (chunk, E) gather at a
        # time instead of a monolithic (B, E) one (same contract as
        # ``evaluate_batch``; at most two compiled shapes per batch size).
        for lo, hi in chunk_ranges(P.shape[0], chunk):
            out[lo:hi] = fetch(
                fn(
                    P[lo:hi], tm.task_cpu, tm.task_mem,
                    tm.cpu_cap, tm.mem_cap, tm.nic_cap, tm.rack_cap,
                    ba.edges, tm.edge_bytes, tm.edge_comp, tm.edge_lat,
                    tm.den_flow, tm.rack_of,
                    tm.edge_local, tm.pair_key, tm.combo_ce, tm.local_num,
                    tm.thrash_factor, tm.source_bound, tm.sink_rate,
                ),
                "score",
            )
    return out


def throughput_batch(
    ba: BatchArena,
    tm: ThroughputModel,
    placements: np.ndarray,
    backend: str = "auto",
    chunk: int = 256,
) -> np.ndarray:
    """(B,) throughput proxy (tuples/s) for a ``(B, T)`` candidate batch
    (or one ``(T,)`` row).  Backends are bit-identical (grid quantization
    makes every reduction exact)."""
    P = np.ascontiguousarray(np.atleast_2d(placements))
    if P.shape[1] != ba.n_tasks:
        raise ValueError(
            f"placement batch has {P.shape[1]} tasks, arena has {ba.n_tasks}"
        )
    resolved = resolve_backend(backend)
    if resolved == "pallas":
        return _throughput_pallas(ba, tm, P, chunk)
    if resolved == "jax":
        return _throughput_jax(ba, tm, P, chunk)
    return _throughput_numpy(ba, tm, P, chunk)
