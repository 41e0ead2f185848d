"""Fused candidate-scoring Pallas kernel (``backend="pallas"``).

One ``pl.pallas_call`` scores a ``(B, T)`` candidate block for all four
objective terms in a single pass over the block:

* **net** — edge-gather netcost (``net[p[src], p[dst]]`` summed per row);
* **violation** — per-node/per-dim hard-capacity segment-sum overshoot;
* **dead** — dead-node hit count;
* **throughput** — the locality-aware proxy ``min(source, cpu, bandwidth,
  ack) × sink_rate`` (optional: only when a ``ThroughputModel`` is given).

The grid tiles the batch dimension only (``block_b`` candidates per
program; the batch is padded to a block multiple and the padded rows are
sliced off by the wrapper — the masking idiom from the Pallas guide, done
at the host boundary so no partial block ever reaches the kernel).  Each
program reads its own placement block plus the shared arena/model arrays
and writes its own output rows — no cross-program accumulation, so grid
execution order cannot affect a bit.

Exactness contract: every accumulated quantity is a dyadic-grid multiple
(``throughput.GRID`` / ``ACK_GRID``; net distances are 0.5-multiples), so
float64 segment-sums are exact regardless of accumulation order, and the
elementwise tail (divisions, min/max, the ack recursion) is identical
correctly-rounded IEEE arithmetic on identical bits.  The kernel is
therefore bit-identical to the numpy and jax-vmap oracles — pinned by
``tests/test_search_kernels.py`` over the §6 topology suite.

Deployment note: the kernel does not compile for a TPU.  Its accumulators
and outputs are float64, and its body uses jnp gather/scatter
(``x.at[].add``, advanced-index gathers); the Mosaic lowering refuses
both (``tests/test_chip_compile.py`` pins the refusal).  Interpret mode
executes it exactly off-TPU; on a TPU the compiled path raises
:data:`~repro.core.search.backend.PALLAS_ON_TPU_ERROR` before lowering.
The 32-bit one-hot/matmul rewrite is ROADMAP Speed item 3.  Committed
call sites must not hard-code ``interpret=True`` (the ``pallas-interpret``
lint rule): the default is computed from the runtime platform by
:func:`default_interpret`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

from ..backend import PALLAS_ON_TPU_ERROR, jax_modules, on_tpu, x64
from ..batch import BatchArena
from ..throughput import ThroughputModel, ack_lambda, edge_lat_class, hard_lambda

#: Candidates per grid program.  The per-program working set is the
#: (block_b, E) edge gather — small enough for VMEM on every §6 topology
#: while keeping ≥10k-candidate batches to ~1e3 programs.
DEFAULT_BLOCK_B = 8


def default_interpret() -> bool:
    """Interpret unless running on a real TPU — committed call sites plumb
    this instead of hard-coding ``interpret=True`` (lint: pallas-interpret).
    Interpret mode executes the kernel through XLA with float64 intact,
    which is exactly what the golden-equality contract needs on CPU."""
    return not on_tpu()


def _fused_kernel(
    # inputs (refs): candidate block + shared arena arrays
    P_ref, net_ref, avail_ref, demand_ref, deadw_ref, edges_ref, evalid_ref,
    mb_ref, mc_ref,
    *refs,
    blk_b: int,
    n_nodes: int,
    n_racks: int,
    n_ce: int,
    n_combos: int,
    ack,
    thrash_factor: float,
    source_bound: float,
    sink_rate: float,
    with_tp: bool,
):
    """Score one (blk_b, T) placement block; write (blk_b,) output rows.

    ``refs`` is the variadic tail: with ``with_tp`` the 11 ThroughputModel
    input refs precede the output refs (net, viol, dead[, tp]).
    """
    jax, jnp = jax_modules()

    if with_tp:
        (
            task_cpu_ref, task_mem_ref, cpu_cap_ref, mem_cap_ref,
            nic_cap_ref, rack_cap_ref, edge_bytes_ref, edge_comp_ref,
            edge_lat_ref, den_flow_ref, rack_of_ref, edge_local_ref,
            pair_key_ref, combo_ce_ref, local_num_ref,
            net_o, viol_o, dead_o, tp_o,
        ) = refs
    else:
        net_o, viol_o, dead_o = refs

    P = P_ref[...]  # (blk_b, T) int32 node indices
    # 2D iota (TPU requires ≥2D); broadcasts against every (blk_b, X) index.
    bidx = jax.lax.broadcasted_iota(jnp.int32, (blk_b, 1), 0)

    # -- hard capacity + dead count (the evaluate_batch terms) -------------
    demand = demand_ref[...]          # (T, Dh)
    avail = avail_ref[...]            # (N, Dh)
    used = jnp.zeros(
        (blk_b, n_nodes, demand.shape[1]), dtype=jnp.float64
    ).at[bidx, P].add(demand[None, :, :])
    viol_o[...] = jnp.maximum(used - avail[None, :, :], 0.0).sum(axis=(1, 2))
    dead_o[...] = deadw_ref[...][P].sum(axis=-1)

    # -- edge-gather netcost ----------------------------------------------
    edges = edges_ref[...]            # (E, 2) int32 (E padded to ≥1)
    src_t, dst_t = edges[:, 0], edges[:, 1]
    src_n = P[:, src_t]               # (blk_b, E)
    dst_n = P[:, dst_t]
    evalid = evalid_ref[...]          # (E,) 1.0 real edge / 0.0 padding
    # Migration soft cost: per-task penalty when placed off its pre-move
    # node (zero arrays on non-reconfig arenas → +0.0, bitwise inert).
    net_o[...] = (net_ref[...][src_n, dst_n] * evalid[None, :]).sum(
        axis=-1
    ) + jnp.where(P != mb_ref[...][None, :], mc_ref[...][None, :], 0.0).sum(
        axis=-1
    )

    if not with_tp:
        return

    # -- throughput proxy (the _jax_tp_fn math, batched over the block) ----
    task_cpu = task_cpu_ref[...]
    task_mem = task_mem_ref[...]
    cpu_load = jnp.zeros((blk_b, n_nodes), dtype=jnp.float64).at[bidx, P].add(
        task_cpu[None, :]
    )
    mem_used = jnp.zeros((blk_b, n_nodes), dtype=jnp.float64).at[bidx, P].add(
        task_mem[None, :]
    )
    edge_bytes = edge_bytes_ref[...]
    edge_comp = edge_comp_ref[...]
    rack_of = rack_of_ref[...]
    pair_key = pair_key_ref[...]
    colo = src_n == dst_n
    L = jnp.zeros((blk_b, n_combos), dtype=jnp.float64).at[
        bidx, pair_key[None, :]
    ].add(colo.astype(jnp.float64))
    routed_local = edge_local_ref[...][None, :] & (L[bidx, pair_key[None, :]] > 0.0)
    w = jnp.where(~colo & ~routed_local, edge_bytes[None, :], 0.0)
    egress = jnp.zeros((blk_b, n_nodes), dtype=jnp.float64).at[bidx, src_n].add(w)
    ingress = jnp.zeros((blk_b, n_nodes), dtype=jnp.float64).at[bidx, dst_n].add(w)
    rs, rd = rack_of[src_n], rack_of[dst_n]
    wr = jnp.where((rs != rd) & ~routed_local, edge_bytes[None, :], 0.0)
    rack_up = jnp.zeros((blk_b, n_racks), dtype=jnp.float64).at[bidx, rs].add(wr)
    lat = jnp.where(
        routed_local,
        0.0,
        edge_lat_class(src_n, dst_n, rack_of, edge_lat_ref[...][:, None, :], xp=jnp),
    )
    ack_num = jnp.zeros((blk_b, n_ce), dtype=jnp.float64).at[
        bidx, edge_comp[None, :]
    ].add(lat)
    ln = jnp.where(L > 0.0, local_num_ref[...][None, :], 0.0)
    ack_num = ack_num.at[bidx, combo_ce_ref[...][None, :]].add(ln)
    lam = hard_lambda(
        cpu_load, mem_used, egress, ingress, rack_up,
        cpu_cap_ref[...], mem_cap_ref[...], nic_cap_ref[...], rack_cap_ref[...],
        thrash_factor, source_bound, xp=jnp,
    )
    lam = jnp.minimum(lam, ack_lambda(ack_num, den_flow_ref[...], ack, xp=jnp))
    tp_o[...] = lam * sink_rate


@functools.lru_cache(maxsize=None)
def _fused_fn(
    n_nodes: int,
    n_racks: int,
    n_ce: int,
    n_combos: int,
    ack,
    thrash_factor: float,
    source_bound: float,
    sink_rate: float,
    block_b: int,
    with_tp: bool,
    interpret: bool,
):
    """jit-compiled fused scorer (one cached callable per arena/model
    structure; array shapes re-specialize via jit's own shape cache)."""
    jax, jnp = jax_modules()
    from jax.experimental import pallas as pl

    kernel = functools.partial(
        _fused_kernel,
        blk_b=block_b,
        n_nodes=n_nodes,
        n_racks=n_racks,
        n_ce=n_ce,
        n_combos=n_combos,
        ack=ack,
        thrash_factor=thrash_factor,
        source_bound=source_bound,
        sink_rate=sink_rate,
        with_tp=with_tp,
    )

    def _full(a):
        """BlockSpec for an un-tiled shared array (every program sees it)."""
        nd = a.ndim
        return pl.BlockSpec(a.shape, lambda i: (0,) * nd)

    @jax.jit
    def run(P, net, avail, demand, deadw, edges, evalid, mb, mc, *tp_arrays):
        Bp, T = P.shape
        inputs = (P, net, avail, demand, deadw, edges, evalid, mb, mc) + tp_arrays
        n_out = 4 if with_tp else 3
        out = pl.pallas_call(
            kernel,
            grid=(pl.cdiv(Bp, block_b),),  # Bp pre-padded to a block multiple
            in_specs=[pl.BlockSpec((block_b, T), lambda i: (i, 0))]
            + [_full(a) for a in inputs[1:]],
            out_specs=[pl.BlockSpec((block_b,), lambda i: (i,))] * n_out,
            out_shape=[jax.ShapeDtypeStruct((Bp,), jnp.float64)] * n_out,
            interpret=interpret,
        )(*inputs)
        return out

    return run


def _padded_inputs(ba: BatchArena, tm: Optional[ThroughputModel]):
    """Numpy input arrays with the empty-edge / empty-hard-dim cases padded
    to width ≥1 (a (0,0) dummy edge with zero weights/latency scores 0 in
    every term, and zero-width demand columns violate nothing)."""
    N = ba.n_nodes
    Dh = ba.avail.shape[1]
    if Dh:
        avail, demand = ba.avail, ba.hard_demand
    else:
        avail = np.zeros((N, 1), dtype=np.float64)
        demand = np.zeros((ba.n_tasks, 1), dtype=np.float64)
    deadw = (~ba.alive).astype(np.float64)
    E = ba.edges.shape[0]
    if E:
        edges = ba.edges.astype(np.int32)
        evalid = np.ones(E, dtype=np.float64)
    else:
        edges = np.zeros((1, 2), dtype=np.int32)
        evalid = np.zeros(1, dtype=np.float64)
    mb, mc = ba.move_arrays()
    base = (ba.net, avail, demand, deadw, edges, evalid, mb.astype(np.int32), mc)
    if tm is None:
        return base, ()
    if E:
        eb, ec, el3 = tm.edge_bytes, tm.edge_comp, tm.edge_lat
        elc, pk = tm.edge_local, tm.pair_key
    else:
        eb = np.zeros(1, dtype=np.float64)
        ec = np.zeros(1, dtype=np.int32)
        el3 = np.zeros((3, 1), dtype=np.float64)
        elc = np.zeros(1, dtype=bool)
        pk = np.zeros(1, dtype=np.int32)
    tp_arrays = (
        tm.task_cpu, tm.task_mem, tm.cpu_cap, tm.mem_cap,
        tm.nic_cap, tm.rack_cap, eb, ec.astype(np.int32), el3,
        tm.den_flow, tm.rack_of.astype(np.int32), elc,
        pk.astype(np.int32), tm.combo_ce.astype(np.int32), tm.local_num,
    )
    return base, tp_arrays


def fused_score(
    ba: BatchArena,
    placements: np.ndarray,
    tm: Optional[ThroughputModel] = None,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Score a ``(B, T)`` batch in **one** fused ``pallas_call``.

    Returns ``(net, violation, dead, throughput)`` — numpy float64/int64,
    bit-identical to the ``evaluate_batch``/``throughput_batch`` oracles;
    ``throughput`` is None unless ``tm`` is given.  ``interpret=None``
    resolves via :func:`default_interpret` (interpret off-TPU).
    """
    P = np.ascontiguousarray(np.atleast_2d(placements))
    B, T = P.shape
    if T != ba.n_tasks:
        raise ValueError(
            f"placement batch has {T} tasks, arena has {ba.n_tasks}"
        )
    if block_b < 1:
        raise ValueError(f"block_b must be >= 1, got {block_b}")
    interp = default_interpret() if interpret is None else bool(interpret)
    if not interp and on_tpu():
        raise RuntimeError(PALLAS_ON_TPU_ERROR)
    # Pad the batch to a block multiple with node-0 rows; the padded rows
    # score garbage that never leaves this function.
    n_blocks = -(-B // block_b)
    Bp = n_blocks * block_b
    P32 = np.zeros((Bp, T), dtype=np.int32)
    P32[:B] = P
    base, tp_arrays = _padded_inputs(ba, tm)
    fn = _fused_fn(
        ba.n_nodes,
        max(tm.n_racks, 1) if tm is not None else 1,
        max(tm.ack.n_comp_edges, 1) if tm is not None else 1,
        tm.n_combos if tm is not None else 1,
        tm.ack if tm is not None else None,
        tm.thrash_factor if tm is not None else 0.0,
        tm.source_bound if tm is not None else np.inf,
        tm.sink_rate if tm is not None else 0.0,
        block_b,
        tm is not None,
        interp,
    )
    with x64():
        out = fn(P32, *base, *tp_arrays)
    net = np.asarray(out[0], dtype=np.float64)[:B]
    viol = np.asarray(out[1], dtype=np.float64)[:B]
    dead = np.asarray(out[2], dtype=np.float64)[:B].astype(np.int64)
    tp = (
        np.asarray(out[3], dtype=np.float64)[:B] if tm is not None else None
    )
    return net, viol, dead, tp
