"""Batched multi-start annealing over candidate placements.

B independent chains run pairwise-swap local search *simultaneously*: each
step proposes one swap per chain, scores it with the same O(degree)
incremental delta the sequential ``SwapAnnealer`` uses
(:func:`repro.core.engine.arena.swap_network_delta`; the jax netcost scan
sums it by node, :func:`histogram_network_delta`), and accepts it under a
threshold-accepting schedule (Dueck & Scheuer's deterministic cousin of
simulated annealing): a swap is accepted iff

    Δ(net + penalty × hard-violation)  ≤  threshold(step)

with the threshold annealing linearly to 0, where the loop becomes pure
hill-climbing.  Threshold accepting was chosen over Metropolis acceptance
deliberately — no ``exp``/``log`` in the hot loop means the accept decision
is a comparison of *exact* float64 quantities, so the jax scan and the
numpy fallback produce bit-identical chains.

All randomness (swap proposals) is pregenerated with numpy's Philox
generator from one seed and fed to both backends as data, so a fixed seed
gives a deterministic result regardless of backend or chain count ordering.

Because violations are penalized at ``OVERLOAD_PENALTY`` (≫ any threshold),
chains seeded with feasible placements stay feasible at every step, while
infeasible seeds (random init) are driven toward feasibility first.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from ...obs import get_hub
from ..engine.arena import swap_network_delta, swap_overload_delta
from .backend import fetch, jax_modules, resolve_backend, x64
from .batch import BatchArena
from .objective import OVERLOAD_PENALTY
from .throughput import (
    ThroughputModel,
    ack_lambda,
    aggregates_numpy,
    hard_lambda,
    proxy_from_state,
    swap_state_terms,
)

#: Registry-visible objective modes for the batched annealer / search.
OBJECTIVES = ("netcost", "throughput")

#: Initial accept threshold, in net-distance hops: early steps may accept
#: swaps that worsen the placement by up to this much, escaping the greedy
#: seed's local minimum; anneals linearly to 0.
DEFAULT_T0 = 2.0

def move_delta(move_cost, move_base, i, j, na, nb, xp=np):
    """Δ(migration term) for swapping tasks ``i``/``j`` between nodes
    ``na``/``nb``: each task's penalty toggles on whether its new node
    matches its pre-move node.  With all-zero costs the result is ±0.0,
    which is bitwise inert on the accept comparisons — zero-cost arenas
    walk chains identical to arenas without the term."""
    ci, cj = move_cost[i], move_cost[j]
    bi, bj = move_base[i], move_base[j]
    return ci * (
        (nb != bi).astype(xp.float64) - (na != bi).astype(xp.float64)
    ) + cj * ((na != bj).astype(xp.float64) - (nb != bj).astype(xp.float64))


def histogram_network_delta(net, na, nb, counts, m_ab, xp=np):
    """Δ(network cost) of swapping the nodes ``na``/``nb`` (B,) of two tasks
    i and j, from ``counts`` (B, N): i's neighbours on each node less j's.

    The same sum as :func:`swap_network_delta`, regrouped by node:
    Σₙ counts[b, n] · (net[nb, n] − net[na, n]) − m_ab · corr.  It reads
    two rows of ``net`` per chain where that reads two entries per
    neighbour, one at a time.  The result is the shared delta's to the bit
    only where every sum is exact in any order, which
    :func:`sums_exactly` checks."""
    rows = net[nb] - net[na]
    corr = net[na, na] + net[nb, nb] - 2.0 * net[na, nb]
    return (counts * rows).sum(axis=-1) - m_ab * corr


def sums_exactly(net, terms: int) -> bool:
    """Whether every sum of up to ``terms`` entries of ``net``, each with
    a sign, is exact in float64 whatever the order: every entry is a whole
    multiple of one power of two 2**-s, and ``terms`` times the largest
    entry stays below 2**53 of those.  The rack distances (0.5, 1, 2) are;
    a table holding 0.1 is not, past two terms."""
    top = float(np.abs(net).max())
    for s in range(1075):
        scaled = np.ldexp(net, s)
        if np.array_equal(scaled, np.round(scaled)):
            return top * terms * 2.0**s < 2.0**53
    return False


def swap_proposals(
    n_tasks: int, steps: int, n_chains: int, seed: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Pregenerated (i, j) task-index proposals, shape (steps, B) each.

    ``j = (i + offset) % T`` with offset ≥ 1 guarantees i ≠ j.  Philox is
    counter-based, so the stream is stable across numpy versions/platforms.
    """
    rng = np.random.Generator(np.random.Philox(seed))
    ii = rng.integers(0, n_tasks, size=(steps, n_chains), dtype=np.int64)
    off = rng.integers(1, max(n_tasks, 2), size=(steps, n_chains), dtype=np.int64)
    return ii, (ii + off) % n_tasks


class BatchAnnealer:
    """Run B swap-search chains in lockstep on one BatchArena."""

    def __init__(self, ba: BatchArena, backend: str = "auto"):
        self.ba = ba
        self.backend = resolve_backend(backend)

    def run(
        self,
        P0: np.ndarray,
        steps: int,
        seed: int,
        t0: float = DEFAULT_T0,
        objective: str = "netcost",
        tm: Optional[ThroughputModel] = None,
        multi_swap: int = 1,
    ) -> np.ndarray:
        """Anneal every chain of ``P0`` (B, T) for ``steps`` proposals each;
        returns the final (B, T) batch (numpy, regardless of backend).

        ``objective="netcost"`` (default) accepts on Δ(net + penalty ×
        violation) ≤ threshold.  ``objective="throughput"`` (requires a
        compiled ``ThroughputModel``) *maximizes* the throughput proxy with
        netcost as the annealed tie-break: a swap is accepted iff it reduces
        hard violation, or — violation unchanged — raises the proxy, or —
        proxy unchanged (the min-bound plateaus often) — passes the netcost
        threshold test.  All comparisons are of exact float64 quantities
        (grid-quantized state), so both backends walk identical chains.

        ``multi_swap=k`` fuses k pregenerated proposals into each
        ``lax.scan`` element on the jax path: the same per-swap math is
        applied sequentially inside one scan step (threshold-accept per
        swap, within the block), so the chain — and the final placements —
        are *bit-identical* to ``multi_swap=1`` while the scan runs k×
        fewer steps (k× less per-step launch/carry overhead).  The numpy
        fallback has no launch overhead and already walks the identical
        chain, so ``multi_swap`` is a no-op there by construction.
        """
        if objective not in OBJECTIVES:
            raise ValueError(
                f"unknown objective {objective!r}; choose from {OBJECTIVES}"
            )
        if objective == "throughput" and tm is None:
            raise ValueError("objective='throughput' requires a ThroughputModel")
        if multi_swap < 1:
            raise ValueError(f"multi_swap must be >= 1, got {multi_swap}")
        P0 = np.ascontiguousarray(np.atleast_2d(P0))
        n_chains, n_tasks = P0.shape
        if n_tasks != self.ba.n_tasks:
            raise ValueError(
                f"init batch has {n_tasks} tasks, arena has {self.ba.n_tasks}"
            )
        if n_tasks < 2 or (self.ba.edges.size == 0 and self.ba.avail.size == 0):
            self.accepted = np.zeros(n_chains, dtype=np.int64)
            return P0.copy()  # nothing a swap could improve
        hub = get_hub()
        # "pallas" selects the fused evaluator in evaluate_batch/
        # throughput_batch; the annealer's hot loop is the fused multi-swap
        # scan either way, so it shares the jax path (bit-identical chains).
        use_jax = self.backend in ("jax", "pallas")
        with hub.span("anneal.dispatch"):
            ii, jj = swap_proposals(n_tasks, steps, n_chains, seed)
            thresh = np.linspace(float(t0), 0.0, steps)
            used0 = self.ba.used(P0)
            if objective == "throughput":
                if use_jax:
                    P, acc = self._run_jax_tp(P0, used0, ii, jj, thresh, tm, multi_swap)
                else:
                    P, acc = self._run_numpy_tp(P0, used0, ii, jj, thresh, tm)
            elif use_jax:
                P, acc = self._run_jax(P0, used0, ii, jj, thresh, multi_swap)
            else:
                P, acc = self._run_numpy(P0, used0, ii, jj, thresh)
        if use_jax:
            P = fetch(P, "anneal")
        #: Per-chain accepted swaps of the last run, as the run left them
        #: (on the device for the jax path): read through accepted_total.
        self.accepted = acc
        # Ambient observability: a live MetricsHub counts proposals and
        # accepted swaps; the device program is the same with or without it.
        if hub.enabled:
            hub.counter("search.proposals").inc(steps * n_chains)
            hub.counter("search.accepted").inc(self.accepted_total())
        return P.astype(np.intp)

    def accepted_total(self) -> int:
        """Accepted swaps of the last :meth:`run`, over all its chains
        (copies the carried per-chain counts from the device)."""
        return int(np.asarray(self.accepted).sum(dtype=np.int64))

    # -- numpy fallback --------------------------------------------------------
    def _run_numpy(self, P0, used0, ii, jj, thresh):
        ba = self.ba
        P = P0.astype(np.intp, copy=True)
        used = used0.copy()
        bidx = np.arange(P.shape[0])
        acc = np.zeros(P.shape[0], dtype=np.int64)
        mb, mc = ba.move_base, ba.move_cost
        for s in range(ii.shape[0]):
            i, j = ii[s], jj[s]
            na, nb = P[bidx, i], P[bidx, j]
            ai, mi = ba.adj[i], ba.adj_mask[i]
            aj, mj = ba.adj[j], ba.adj_mask[j]
            pa = P[bidx[:, None], np.where(mi, ai, 0)]
            pb = P[bidx[:, None], np.where(mj, aj, 0)]
            m_ab = ((ai == j[:, None]) & mi).sum(axis=-1)
            delta = swap_network_delta(ba.net, na, nb, pa, pb, m_ab, mi, mj)
            di, dj = ba.hard_demand[i], ba.hard_demand[j]
            delta = delta + OVERLOAD_PENALTY * swap_overload_delta(
                ba.avail[na], ba.avail[nb], used[bidx, na], used[bidx, nb], di, dj
            )
            if mc is not None:
                delta = delta + move_delta(mc, mb, i, j, na, nb)
            accept = (na != nb) & (delta <= thresh[s])
            P[bidx, i] = np.where(accept, nb, na)
            P[bidx, j] = np.where(accept, na, nb)
            du = np.where(accept[:, None], dj - di, 0.0)
            np.add.at(used, (bidx, na), du)
            np.add.at(used, (bidx, nb), -du)
            acc += accept
        return P, acc

    # -- numpy fallback, throughput objective ----------------------------------
    def _run_numpy_tp(self, P0, used0, ii, jj, thresh, tm):
        ba = self.ba
        P = P0.astype(np.intp, copy=True)
        used = used0.copy()
        B = P.shape[0]
        bidx = np.arange(B)
        acc = np.zeros(B, dtype=np.int64)
        mb, mc = ba.move_base, ba.move_cost
        cpu_load, mem_used, egress, ingress, rack_up, ack_num = aggregates_numpy(
            ba, tm, P
        )
        nic_cap, rack_cap = tm.nic_cap, tm.rack_cap
        tp = proxy_from_state(
            cpu_load, mem_used, egress, ingress, rack_up, ack_num, tm
        )
        for s in range(ii.shape[0]):
            i, j = ii[s], jj[s]
            na, nb = P[bidx, i], P[bidx, j]
            ai, mi = ba.adj[i], ba.adj_mask[i]
            aj, mj = ba.adj[j], ba.adj_mask[j]
            pa = P[bidx[:, None], np.where(mi, ai, 0)]
            pb = P[bidx[:, None], np.where(mj, aj, 0)]
            m_ab = ((ai == j[:, None]) & mi).sum(axis=-1)
            dnet = swap_network_delta(ba.net, na, nb, pa, pb, m_ab, mi, mj)
            if mc is not None:
                dnet = dnet + move_delta(mc, mb, i, j, na, nb)
            di, dj = ba.hard_demand[i], ba.hard_demand[j]
            dov = swap_overload_delta(
                ba.avail[na], ba.avail[nb], used[bidx, na], used[bidx, nb], di, dj
            )
            # Candidate throughput state (functional copies; committed only
            # where accepted).
            dc = tm.task_cpu[j] - tm.task_cpu[i]
            dm = tm.task_mem[j] - tm.task_mem[i]
            cl, mu = cpu_load.copy(), mem_used.copy()
            cl[bidx, na] += dc
            cl[bidx, nb] -= dc
            mu[bidx, na] += dm
            mu[bidx, nb] -= dm
            eg, ing, rk, an = (
                egress.copy(), ingress.copy(), rack_up.copy(), ack_num.copy(),
            )
            (ei, ev, ii2, iv, ri, rv, ci, cv) = swap_state_terms(
                P, bidx, i, j, na, nb,
                ba.adj, tm.adj_bytes, tm.adj_src, tm.adj_comp, tm.adj_lat,
                tm.rack_of,
            )
            np.add.at(eg, (bidx[:, None], ei), ev)
            np.add.at(ing, (bidx[:, None], ii2), iv)
            np.add.at(rk, (bidx[:, None], ri), rv)
            np.add.at(an, (bidx[:, None], ci), cv)
            lam = hard_lambda(
                cl, mu, eg, ing, rk,
                tm.cpu_cap, tm.mem_cap, nic_cap, rack_cap,
                tm.thrash_factor, tm.source_bound,
            )
            tp_new = np.minimum(
                lam, ack_lambda(an, tm.den_flow, tm.ack)
            ) * tm.sink_rate
            # Compare tp_new/tp directly — forming tp_new - tp would invite
            # XLA to contract the final multiply and the subtract into one
            # FMA on the jax path, yielding sub-ulp nonzero "differences"
            # where the plateau is exact (backend golden equality hinges on
            # both paths asking the same question of the same bits).
            accept = (na != nb) & (
                (dov < 0.0)
                | (
                    (dov == 0.0)
                    & ((tp_new > tp) | ((tp_new == tp) & (dnet <= thresh[s])))
                )
            )
            P[bidx, i] = np.where(accept, nb, na)
            P[bidx, j] = np.where(accept, na, nb)
            du = np.where(accept[:, None], dj - di, 0.0)
            np.add.at(used, (bidx, na), du)
            np.add.at(used, (bidx, nb), -du)
            w = accept[:, None]
            cpu_load = np.where(w, cl, cpu_load)
            mem_used = np.where(w, mu, mem_used)
            egress = np.where(w, eg, egress)
            ingress = np.where(w, ing, ingress)
            rack_up = np.where(w, rk, rack_up)
            ack_num = np.where(w, an, ack_num)
            tp = np.where(accept, tp_new, tp)
            acc += accept
        return P, acc

    # -- jax scan, throughput objective ----------------------------------------
    def _run_jax_tp(self, P0, used0, ii, jj, thresh, tm, k):
        ba = self.ba
        state = aggregates_numpy(ba, tm, P0.astype(np.intp))
        mb, mc = ba.move_arrays()
        model_args = (
            ba.net, ba.avail, ba.hard_demand, ba.adj, ba.adj_mask,
            mb.astype(np.int32), mc,
            tm.task_cpu, tm.task_mem, tm.cpu_cap, tm.mem_cap,
            tm.nic_cap, tm.rack_cap, tm.adj_bytes, tm.adj_src,
            tm.adj_comp, tm.adj_lat, tm.rack_of, tm.den_flow,
            np.float64(tm.thrash_factor), np.float64(tm.source_bound),
            np.float64(tm.sink_rate),
        )
        P, used = P0.astype(np.int32), used0
        acc = np.zeros(P0.shape[0], dtype=np.int32)
        with x64():
            for lo, hi, kk in _swap_blocks(ii.shape[0], k):
                P, used, state, acc = _jax_anneal_tp_fn(tm.ack, kk)(
                    *model_args, P, used, state, acc,
                    _rows(ii, lo, hi, kk), _rows(jj, lo, hi, kk),
                    thresh[lo:hi].reshape(-1, kk),
                )
        return P, acc

    # -- jax scan --------------------------------------------------------------
    def _run_jax(self, P0, used0, ii, jj, thresh, k):
        P, used = P0.astype(np.int32), used0
        acc = np.zeros(P0.shape[0], dtype=np.int32)
        tables = scan_tables(self.ba)
        with x64():
            for lo, hi, kk in _swap_blocks(ii.shape[0], k):
                P, used, acc = _jax_anneal_fn(kk)(
                    *tables, P, used, acc,
                    _rows(ii, lo, hi, kk), _rows(jj, lo, hi, kk),
                    thresh[lo:hi].reshape(-1, kk),
                )
        return P, acc


def scan_tables(ba: BatchArena) -> tuple:
    """The arena's tables as the netcost scan reads them: ``(net, avail,
    hard_demand, adj, move_base, move_cost)``.

    Indices are int32, so no 64-bit integer is left in the scan (the chip
    gathers an int64 as two u32 halves); the padding mask is ``adj >= 0``
    inside it.  The scan sums the network distances by node
    (:func:`histogram_network_delta`), in another order than the numpy
    path, so it refuses a table whose sums that order could round: a
    delta sums at most 8 · (``max_deg`` + 1) distances."""
    if not sums_exactly(ba.net, 8 * (ba.adj.shape[1] + 1)):
        raise ValueError(
            "the jax netcost scan needs network distances whose sums are "
            "exact in float64 (multiples of one power of two); run this "
            "table with backend='numpy'"
        )
    mb, mc = ba.move_arrays()
    return (
        ba.net, ba.avail, ba.hard_demand, ba.adj.astype(np.int32),
        mb.astype(np.int32), mc,
    )


def _swap_blocks(steps: int, k: int):
    """Split ``steps`` proposals into a main run of k-fused scan elements
    plus a k=1 tail for the remainder — (lo, hi, k_eff) segments.  Only two
    compiled variants per k ever exist (k and 1), and a k > steps simply
    degrades to the tail."""
    k = max(1, min(k, steps))
    main = (steps // k) * k
    if main:
        yield 0, main, k
    if steps > main:
        yield main, steps, 1


def _rows(a: np.ndarray, lo: int, hi: int, k: int) -> np.ndarray:
    """(steps, B) int proposal rows → (outer, k, B) int32 scan elements."""
    return a[lo:hi].astype(np.int32).reshape(-1, k, a.shape[1])


@functools.lru_cache(maxsize=None)
def _jax_anneal_fn(k: int):
    """jit-compiled lax.scan over k-fused proposal blocks — the same
    per-swap math as ``BatchAnnealer._run_numpy``, with scatter updates.
    Each scan element carries k proposals, applied sequentially (unrolled
    at trace time), so the chain is bit-identical to k=1 while the scan —
    and its per-step dispatch/carry overhead — shrinks k×.  Returns the
    full carry so a tail call can chain.  One cached callable per k serves
    every arena/batch size (jit re-specializes on array shapes).

    Takes :func:`scan_tables`'s tables.  The net part of the delta is
    :func:`histogram_network_delta`: the chip gathers an array one element
    at a time but a table row at once, so it reads each neighbour's node
    and two rows of ``net``, not two ``net`` entries per neighbour."""
    jax, jnp = jax_modules()

    @jax.jit
    def anneal(
        net, avail, hard_demand, adj, move_base, move_cost,
        P0, used0, acc0, ii, jj, thresh,
    ):
        bidx = jnp.arange(P0.shape[0], dtype=jnp.int32)
        nodes = jnp.arange(net.shape[0], dtype=jnp.int32)

        def on_nodes(P, a):
            """Adjacency rows (B, max_deg) → each chain's count of those
            neighbours on each node, (B, N); padding (-1) counts nowhere."""
            p = jnp.where(a >= 0, P[bidx[:, None], jnp.maximum(a, 0)], -1)
            return (p[..., None] == nodes).sum(axis=1, dtype=jnp.int32)

        def swap(P, used, acc, i, j, th):
            na, nb = P[bidx, i], P[bidx, j]
            ai, aj = adj[i], adj[j]
            counts = on_nodes(P, ai) - on_nodes(P, aj)
            m_ab = (ai == j[:, None]).sum(axis=-1, dtype=jnp.int32)
            delta = histogram_network_delta(net, na, nb, counts, m_ab, xp=jnp)
            di, dj = hard_demand[i], hard_demand[j]
            delta = delta + OVERLOAD_PENALTY * swap_overload_delta(
                avail[na], avail[nb], used[bidx, na], used[bidx, nb], di, dj, xp=jnp
            )
            # ±0.0 with zero costs — accept comparisons are unchanged.
            delta = delta + move_delta(move_cost, move_base, i, j, na, nb, xp=jnp)
            accept = (na != nb) & (delta <= th)
            P = P.at[bidx, i].set(jnp.where(accept, nb, na))
            P = P.at[bidx, j].set(jnp.where(accept, na, nb))
            du = jnp.where(accept[:, None], dj - di, 0.0)
            used = used.at[bidx, na].add(du).at[bidx, nb].add(-du)
            # Pure integer side-channel for the accepted-swap counts — no
            # float path reads it, so chains are unchanged.
            return P, used, acc + accept.astype(jnp.int32)

        def step(carry, xs):
            P, used, acc = carry
            i, j, th = xs  # (k, B), (k, B), (k,)
            for r in range(k):
                P, used, acc = swap(P, used, acc, i[r], j[r], th[r])
            return (P, used, acc), None

        (P, used, acc), _ = jax.lax.scan(step, (P0, used0, acc0), (ii, jj, thresh))
        return P, used, acc

    return anneal


@functools.lru_cache(maxsize=None)
def _jax_anneal_tp_fn(ack, k: int):
    """jit-compiled lax.scan for the throughput objective — the same
    per-swap math as ``BatchAnnealer._run_numpy_tp`` (one cached callable
    per topology structure and fusion factor: the AckPlan and k are the
    static keys; every model array is a traced argument so no constants
    are baked in).  Like :func:`_jax_anneal_fn`, each scan element applies
    k proposals sequentially and the full aggregate state is returned so
    a tail call can chain: the proxy recomputed from the carried exact
    (grid-quantized) aggregates at a chain boundary is bit-identical to
    the carried value, so chains split across calls never diverge."""
    jax, jnp = jax_modules()

    @jax.jit
    def anneal(
        net, avail, hard_demand, adj, adj_mask, move_base, move_cost,
        task_cpu, task_mem, cpu_cap, mem_cap, nic_cap, rack_cap,
        adj_bytes, adj_src, adj_comp, adj_lat, rack_of, den_flow,
        thrash_factor, source_bound, sink_rate,
        P0, used0, state0, acc0, ii, jj, thresh,
    ):
        bidx = jnp.arange(P0.shape[0])
        cpu0, mem0, eg0, in0, rk0, an0 = state0
        tp0 = jnp.minimum(
            hard_lambda(
                cpu0, mem0, eg0, in0, rk0,
                cpu_cap, mem_cap, nic_cap, rack_cap,
                thrash_factor, source_bound, xp=jnp,
            ),
            ack_lambda(an0, den_flow, ack, xp=jnp),
        ) * sink_rate

        def swap(carry, i, j, th):
            (
                P, used, cpu_load, mem_used, egress, ingress,
                rack_up, ack_num, tp, acc,
            ) = carry
            na, nb = P[bidx, i], P[bidx, j]
            ai, mi = adj[i], adj_mask[i]
            aj, mj = adj[j], adj_mask[j]
            pa = P[bidx[:, None], jnp.where(mi, ai, 0)]
            pb = P[bidx[:, None], jnp.where(mj, aj, 0)]
            m_ab = ((ai == j[:, None]) & mi).sum(axis=-1)
            dnet = swap_network_delta(net, na, nb, pa, pb, m_ab, mi, mj, xp=jnp)
            # ±0.0 with zero costs — the tie-break compare is unchanged.
            dnet = dnet + move_delta(move_cost, move_base, i, j, na, nb, xp=jnp)
            di, dj = hard_demand[i], hard_demand[j]
            dov = swap_overload_delta(
                avail[na], avail[nb], used[bidx, na], used[bidx, nb], di, dj, xp=jnp
            )
            dc = task_cpu[j] - task_cpu[i]
            dm = task_mem[j] - task_mem[i]
            cl = cpu_load.at[bidx, na].add(dc).at[bidx, nb].add(-dc)
            mu = mem_used.at[bidx, na].add(dm).at[bidx, nb].add(-dm)
            (ei, ev, ij2, iv, ri, rv, ci, cv) = swap_state_terms(
                P, bidx, i, j, na, nb,
                adj, adj_bytes, adj_src, adj_comp, adj_lat, rack_of, xp=jnp,
            )
            col = bidx[:, None]
            eg = egress.at[col, ei].add(ev)
            ing = ingress.at[col, ij2].add(iv)
            rk = rack_up.at[col, ri].add(rv)
            an = ack_num.at[col, ci].add(cv)
            lam = hard_lambda(
                cl, mu, eg, ing, rk,
                cpu_cap, mem_cap, nic_cap, rack_cap,
                thrash_factor, source_bound, xp=jnp,
            )
            tp_new = jnp.minimum(lam, ack_lambda(an, den_flow, ack, xp=jnp)) * sink_rate
            # Direct comparisons, not tp_new - tp: a subtract after the
            # multiply is FMA-contractible under XLA (see the numpy twin).
            accept = (na != nb) & (
                (dov < 0.0)
                | ((dov == 0.0) & ((tp_new > tp) | ((tp_new == tp) & (dnet <= th))))
            )
            P = P.at[bidx, i].set(jnp.where(accept, nb, na))
            P = P.at[bidx, j].set(jnp.where(accept, na, nb))
            du = jnp.where(accept[:, None], dj - di, 0.0)
            used = used.at[bidx, na].add(du).at[bidx, nb].add(-du)
            w = accept[:, None]
            return (
                P,
                used,
                jnp.where(w, cl, cpu_load),
                jnp.where(w, mu, mem_used),
                jnp.where(w, eg, egress),
                jnp.where(w, ing, ingress),
                jnp.where(w, rk, rack_up),
                jnp.where(w, an, ack_num),
                jnp.where(accept, tp_new, tp),
                # Integer acceptance side-channel (telemetry only).
                acc + accept.astype(jnp.int32),
            )

        def step(carry, xs):
            i, j, th = xs  # (k, B), (k, B), (k,)
            for r in range(k):
                carry = swap(carry, i[r], j[r], th[r])
            return carry, None

        carry0 = (P0, used0, cpu0, mem0, eg0, in0, rk0, an0, tp0, acc0)
        carry, _ = jax.lax.scan(step, carry0, (ii, jj, thresh))
        return carry[0], carry[1], carry[2:8], carry[9]

    return anneal
