"""Pure-functional batched placement objective.

Given a ``BatchArena`` and a batch of candidate placements as an int array
``(B, T)`` of node indices, return per-candidate

* ``net``        — network cost: inter-node edge traffic × rack distance
  (the quadratic QM3DKP term R-Storm's greedy minimizes implicitly), plus
  — on arenas carrying ``move_base``/``move_cost`` (reconfiguration
  searches) — the per-task migration penalty for every task placed away
  from its pre-rebalance node;
* ``violation``  — total hard-capacity overshoot across nodes and hard
  columns (0.0 ⇔ the candidate respects every hard constraint);
* ``dead``       — count of tasks placed on dead nodes.

One vmapped/jit-compiled reduction on the jax backend (float64 via the
scoped x64 context), and the same math as a chunked numpy reduction when
jax is absent.  Both paths are exact for the repo's resource values (net
distances are 0.5-multiples; demands are dyadic), so outputs are golden-
equal across backends — the search subsystem's determinism rests on this.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np

# Penalty weight folding hard-capacity overshoot into one scalar cost — the
# same constant the sequential annealer uses (re-exported for the search),
# so accept thresholds mean the same thing in both engines.
from ..engine.annealing import OVERLOAD_PENALTY
from .backend import chunk_ranges, fetch, jax_modules, resolve_backend, x64
from .batch import BatchArena


@dataclasses.dataclass(frozen=True)
class BatchEval:
    """Per-candidate objective terms, always numpy float64/int64 on exit."""

    net: np.ndarray  # (B,) float64
    violation: np.ndarray  # (B,) float64
    dead: np.ndarray  # (B,) int64
    # (B,) float64 throughput proxy (tuples/s), populated only when a
    # ThroughputModel was passed to ``evaluate_batch`` — the quantity the
    # "throughput" search objective maximizes.
    throughput: Optional[np.ndarray] = None

    @property
    def feasible(self) -> np.ndarray:
        """(B,) bool: no hard-capacity overshoot and no dead-node hits."""
        return (self.violation <= 0.0) & (self.dead == 0)

    def penalized(self) -> np.ndarray:
        """(B,) combined scalar cost (net + penalty × violation)."""
        return self.net + OVERLOAD_PENALTY * self.violation


def _evaluate_numpy(ba: BatchArena, P: np.ndarray, chunk: int) -> BatchEval:
    B = P.shape[0]
    net = np.zeros(B, dtype=np.float64)
    viol = np.zeros(B, dtype=np.float64)
    dead = np.zeros(B, dtype=np.int64)
    e0, e1 = ba.edges[:, 0], ba.edges[:, 1]
    mb, mc = ba.move_base, ba.move_cost
    for lo, hi in chunk_ranges(B, chunk):
        p = P[lo:hi]
        if e0.size:
            net[lo:hi] = ba.net[p[:, e0], p[:, e1]].sum(axis=-1)
        if mc is not None:
            # Same edge-sum + move-sum decomposition as the jax/pallas
            # paths; dyadic costs make the sum order-independent.
            net[lo:hi] = net[lo:hi] + np.where(p != mb, mc, 0.0).sum(axis=-1)
        used = ba.used(p)
        viol[lo:hi] = np.maximum(used - ba.avail, 0.0).sum(axis=(1, 2))
        dead[lo:hi] = (~ba.alive[p]).sum(axis=-1)
    return BatchEval(net=net, violation=viol, dead=dead)


@functools.lru_cache(maxsize=None)
def _jax_eval_fn(n_nodes: int):
    """jit-compiled vmapped evaluator (cached per node count; array shapes
    re-specialize via jit's own shape cache)."""
    jax, jnp = jax_modules()

    @jax.jit
    def evaluate(net, avail, hard_demand, alive, edges, move_base, move_cost, P):
        def one(p):
            # An empty edge set gathers to an empty row; its sum is 0.0.
            # The move term adds +0.0 on zero-cost arenas (bitwise inert).
            netc = net[p[edges[:, 0]], p[edges[:, 1]]].sum() + jnp.where(
                p != move_base, move_cost, 0.0
            ).sum()
            used = jax.ops.segment_sum(hard_demand, p, num_segments=n_nodes)
            violc = jnp.maximum(used - avail, 0.0).sum()
            deadc = (~alive[p]).sum()
            return netc, violc, deadc

        return jax.vmap(one)(P)

    return evaluate


def _evaluate_jax(ba: BatchArena, P: np.ndarray, chunk: int) -> BatchEval:
    B = P.shape[0]
    net = np.zeros(B, dtype=np.float64)
    viol = np.zeros(B, dtype=np.float64)
    dead = np.zeros(B, dtype=np.int64)
    fn = _jax_eval_fn(ba.n_nodes)
    mb, mc = ba.move_arrays()
    with x64():
        # Chunked like the numpy path: the (chunk, E) gather is the working
        # set, so a huge batch never materializes one (B, E) intermediate.
        # At most two compiled shapes per batch size (full chunk + tail).
        for lo, hi in chunk_ranges(B, chunk):
            n, v, d = fetch(
                fn(
                    ba.net, ba.avail, ba.hard_demand, ba.alive, ba.edges,
                    mb, mc, P[lo:hi],
                ),
                "score",
            )
            net[lo:hi] = n
            viol[lo:hi] = v
            dead[lo:hi] = d
    return BatchEval(net=net, violation=viol, dead=dead)


def _evaluate_pallas(
    ba: BatchArena, P: np.ndarray, chunk: int, throughput_model
) -> BatchEval:
    """One fused kernel launch per chunk: netcost + capacity + dead (+
    throughput when a model is given) in a single pass over the block —
    instead of the two separate reductions the jax/numpy paths run."""
    from .kernels import fused_score  # jax-only import, deferred

    B = P.shape[0]
    net = np.zeros(B, dtype=np.float64)
    viol = np.zeros(B, dtype=np.float64)
    dead = np.zeros(B, dtype=np.int64)
    tp = np.zeros(B, dtype=np.float64) if throughput_model is not None else None
    for lo, hi in chunk_ranges(B, chunk):
        n, v, d, t = fused_score(ba, P[lo:hi], tm=throughput_model)
        net[lo:hi] = n
        viol[lo:hi] = v
        dead[lo:hi] = d
        if tp is not None:
            tp[lo:hi] = t
    return BatchEval(net=net, violation=viol, dead=dead, throughput=tp)


def evaluate_batch(
    ba: BatchArena,
    placements: np.ndarray,
    backend: str = "auto",
    chunk: int = 256,
    throughput_model=None,
) -> BatchEval:
    """Score a batch of candidate placements ``(B, T)`` (or one ``(T,)`` row).

    ``chunk`` bounds the per-call working set (the (chunk, E) edge gather)
    on *both* backends; results are independent of the chunking.  Passing a
    ``ThroughputModel`` (``search.throughput.compile_throughput``) also
    populates ``BatchEval.throughput`` with the per-candidate proxy.
    """
    P = np.ascontiguousarray(np.atleast_2d(placements))
    if P.shape[1] != ba.n_tasks:
        raise ValueError(
            f"placement batch has {P.shape[1]} tasks, arena has {ba.n_tasks}"
        )
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    resolved = resolve_backend(backend)
    if resolved == "pallas":
        # The fused kernel computes every term (throughput included) in one
        # pass per chunk — no second throughput_batch sweep needed.
        return _evaluate_pallas(ba, P, chunk, throughput_model)
    if resolved == "jax":
        out = _evaluate_jax(ba, P, chunk)
    else:
        out = _evaluate_numpy(ba, P, chunk)
    if throughput_model is not None:
        from .throughput import throughput_batch

        out = dataclasses.replace(
            out,
            throughput=throughput_batch(
                ba, throughput_model, P, backend=backend, chunk=chunk
            ),
        )
    return out
