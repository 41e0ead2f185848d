"""Chip smoke: the placement search's main path on one TPU chip, in one process.

Drives ``payload -> Nimbus -> rstorm-search / reconfig="search"`` at the
1000-task x 256-node flagship (a chain of 25 components x 40 tasks on
8 racks x 32 nodes, as in ``benchmarks/bench_search.py``) with
``backend="jax"`` and checks every result against the numpy oracle or the
greedy R-Storm baseline.  Four phases; each prints its cold
(compile-inclusive) and warm wall time and then its checks:

a. scorer parity: ``evaluate_batch`` and ``throughput_batch`` on B=1024
   candidates, jax on the chip against numpy on the host;
b. netcost plan: ``rstorm-search`` against greedy ``rstorm``;
c. throughput plan: ``rstorm-search`` with ``objective="throughput"``;
d. search rebalance after a node failure, against a greedy-mode twin.

It exits non-zero, printing no result, unless jax's first device is a TPU,
and on any failed check or exception.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

    python chip_smoke.py

It never starts a child process: the chip belongs to this one.  jax's
persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, or to
``.jax_cache/`` in the checkout.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.api import Nimbus, SchedulingPayload  # noqa: E402
from repro.core import BatchArena, PlacementArena  # noqa: E402
from repro.core.search.objective import evaluate_batch  # noqa: E402
from repro.core.search.throughput import (  # noqa: E402
    compile_throughput,
    throughput_batch,
)

#: The flagship: bench_search.flagship() / the scheduler-overhead 1000x256 case.
FLAGSHIP = {"components": 25, "parallelism": 40, "racks": 8, "nodes_per_rack": 32}
#: Candidates scored in phase (a).
BATCH = 1024
#: rstorm-search budget of phases (b) and (c).
CHAINS, STEPS = 64, 2000


class SmokeFailure(AssertionError):
    """A check of the smoke failed."""


def flagship_payload(
    components: int,
    parallelism: int,
    racks: int,
    nodes_per_rack: int,
    memory_mb: float = 65536.0,
    cpu: float = 6400.0,
) -> dict:
    """The chain topology on a homogeneous cluster, as a payload dict
    scheduled by greedy ``rstorm``."""
    comps = [
        {
            "id": f"c{i}",
            "is_spout": i == 0,
            "parallelism": parallelism,
            "memory_load_mb": 128.0,
            "cpu_load": 10.0,
        }
        for i in range(components)
    ]
    edges = [{"src": f"c{i}", "dst": f"c{i + 1}"} for i in range(components - 1)]
    return {
        "topology": {
            "id": f"chain{components}x{parallelism}",
            "components": comps,
            "edges": edges,
        },
        "cluster": {
            "racks": racks,
            "nodes_per_rack": nodes_per_rack,
            "memory_mb": memory_mb,
            "cpu": cpu,
        },
        "scheduler": {"name": "rstorm"},
    }


def _payload(base: dict, scheduler=None, simulate=False) -> SchedulingPayload:
    d = dict(base)
    if scheduler is not None:
        d["scheduler"] = scheduler
    if simulate:
        d["settings"] = {"simulate": True}
    return SchedulingPayload.from_dict(d)


def _search(chains: int, steps: int, backend: str, **extra) -> dict:
    kwargs = {"n_chains": chains, "steps": steps, "seed": 0, "backend": backend}
    kwargs.update(extra)
    return {"name": "rstorm-search", "kwargs": kwargs}


def _cold_warm(fn):
    """Run ``fn`` twice: (first result, second result, cold s, warm s)."""
    t0 = time.perf_counter()
    first = fn()
    t1 = time.perf_counter()
    second = fn()
    t2 = time.perf_counter()
    return first, second, t1 - t0, t2 - t1


def _check(tag: str, ok: bool, what: str) -> None:
    print(f"  [{tag}] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise SmokeFailure(f"[{tag}] {what}")


def _numpy_rescore(payload: SchedulingPayload, placements, dead=()):
    """numpy re-score of one placement against the full (pre-placement)
    capacity of a fresh cluster with ``dead`` nodes failed."""
    topology = payload.topology.to_topology()
    cluster = payload.cluster.to_cluster()
    for nid in dead:
        cluster.fail_node(nid)
    ba = BatchArena.from_arena(
        PlacementArena(cluster, topology), topology, dict(placements)
    )
    return evaluate_batch(ba, ba.encode(dict(placements)), backend="numpy")


def _check_feasible(tag: str, payload, placements, dead=()) -> None:
    e = _numpy_rescore(payload, placements, dead)
    _check(
        tag,
        float(e.violation[0]) == 0.0 and int(e.dead[0]) == 0,
        f"numpy re-score: violation={float(e.violation[0])} dead={int(e.dead[0])}",
    )


def phase_scorer(base: dict, batch: int = BATCH, seed: int = 0) -> None:
    """(a) jax-on-device scorer vs numpy on B greedy + seeded random rows.

    Two nodes are failed (the greedy placement's first node and a random
    one) and each random row draws from a random-width node subset, so the
    ``dead`` and ``violation`` terms are exercised, not only ``net``."""
    payload = _payload(base)
    greedy = Nimbus().plan(payload)
    topology = payload.topology.to_topology()
    cluster = payload.cluster.to_cluster()
    rng = np.random.Generator(np.random.Philox(seed))
    node_ids = sorted(cluster.nodes)
    dead = sorted({greedy.placements[min(greedy.placements)],
                   node_ids[int(rng.integers(len(node_ids)))]})
    for nid in dead:
        cluster.fail_node(nid)
    ba = BatchArena.from_arena(
        PlacementArena(cluster, topology), topology, greedy.placements
    )
    tm = compile_throughput(ba, topology, cluster)
    N, T = ba.n_nodes, ba.n_tasks
    # Log-uniform subset widths: about one row in eight packs everything
    # onto a single node.
    width = np.floor(float(N) ** rng.random((batch, 1)))
    perm = np.argsort(rng.random((batch, N)), axis=1)
    pick = (rng.random((batch, T)) * width).astype(np.intp)
    P = np.take_along_axis(perm, pick, axis=1)
    P[0] = ba.encode(greedy.placements)

    ej, ej2, cold, warm = _cold_warm(
        lambda: evaluate_batch(ba, P, backend="jax", chunk=batch)
    )
    t0 = time.perf_counter()
    en = evaluate_batch(ba, P, backend="numpy", chunk=batch)
    t_np = time.perf_counter() - t0
    print(
        f"[a] evaluate_batch B={batch} T={T} N={N}: jax cold {cold:.3f} s, "
        f"warm {warm:.3f} s; numpy {t_np:.3f} s",
        flush=True,
    )
    for term in ("net", "violation", "dead"):
        a, b = getattr(ej, term), getattr(en, term)
        _check("a", a.shape == (batch,) and np.array_equal(a, b),
               f"{term}: jax == numpy exactly on {batch} rows")
    _check("a", all(np.array_equal(getattr(ej, t), getattr(ej2, t))
                    for t in ("net", "violation", "dead")),
           "warm call == cold call")
    _check("a", int((en.violation > 0).sum()) > 0 and int((en.dead > 0).sum()) > 0,
           f"terms exercised: {int((en.violation > 0).sum())} rows overloaded, "
           f"{int((en.dead > 0).sum())} rows on dead nodes {dead}")

    tj, tj2, cold, warm = _cold_warm(
        lambda: throughput_batch(ba, tm, P, backend="jax", chunk=batch)
    )
    t0 = time.perf_counter()
    tn = throughput_batch(ba, tm, P, backend="numpy", chunk=batch)
    t_np = time.perf_counter() - t0
    print(
        f"[a] throughput_batch B={batch}: jax cold {cold:.3f} s, "
        f"warm {warm:.3f} s; numpy {t_np:.3f} s",
        flush=True,
    )
    _check("a", tj.shape == (batch,) and np.array_equal(np.isfinite(tj), np.isfinite(tn)),
           f"proxy shape ({batch},), finite where numpy is "
           f"({int(np.isfinite(tn).sum())} rows)")
    _check("a", np.array_equal(tj, tj2), "warm call == cold call")
    fin = np.isfinite(tn)
    diff = np.abs(tj[fin] - tn[fin])
    rel = diff / np.maximum(np.abs(tn[fin]), np.finfo(np.float64).tiny)
    print(
        f"  [a] info proxy jax vs numpy: {int((tj == tn).sum())}/{batch} rows "
        f"bit-equal, max abs diff {float(diff.max(initial=0.0))!r}, "
        f"max rel diff {float(rel.max(initial=0.0))!r}",
        flush=True,
    )


def phase_netcost_plan(base: dict, chains: int = CHAINS, steps: int = STEPS) -> None:
    """(b) rstorm-search (netcost) on the chip vs greedy and vs numpy."""
    nimbus = Nimbus()
    greedy = nimbus.plan(_payload(base))
    search = _payload(base, _search(chains, steps, "jax"))
    plan, again, cold, warm = _cold_warm(lambda: nimbus.plan(search))
    t0 = time.perf_counter()
    ref = nimbus.plan(_payload(base, _search(chains, steps, "numpy")))
    t_np = time.perf_counter() - t0
    print(
        f"[b] rstorm-search netcost {chains}x{steps}: cold {cold:.3f} s, "
        f"warm {warm:.3f} s; numpy backend {t_np:.3f} s",
        flush=True,
    )
    _check("b", plan.is_complete(), f"complete: {len(plan.placements)} placed")
    _check_feasible("b", search, plan.placements)
    _check("b", plan.network_cost <= greedy.network_cost,
           f"netcost {plan.network_cost!r} <= greedy {greedy.network_cost!r}")
    _check("b", again.placements == plan.placements, "warm plan == cold plan")
    print(
        f"  [b] info placement bit-identical to backend=numpy: "
        f"{plan.placements == ref.placements} (numpy netcost "
        f"{ref.network_cost!r})",
        flush=True,
    )


def phase_throughput_plan(base: dict, chains: int = CHAINS, steps: int = STEPS) -> None:
    """(c) rstorm-search with the throughput objective vs greedy."""
    nimbus = Nimbus()
    greedy = nimbus.plan(_payload(base, simulate=True))
    search = _payload(
        base, _search(chains, steps, "jax", objective="throughput"), simulate=True
    )
    plan, again, cold, warm = _cold_warm(lambda: nimbus.plan(search))
    tp, tp_g = plan.sim.sink_throughput, greedy.sim.sink_throughput
    print(
        f"[c] rstorm-search throughput {chains}x{steps}: cold {cold:.3f} s, "
        f"warm {warm:.3f} s",
        flush=True,
    )
    _check("c", plan.is_complete(), f"complete: {len(plan.placements)} placed")
    _check_feasible("c", search, plan.placements)
    _check("c", tp >= tp_g, f"simulated sink throughput {tp!r} >= greedy {tp_g!r}")
    _check("c", again.placements == plan.placements, "warm plan == cold plan")


def _failover(base: dict, reconfig: str, kwargs=None):
    """Submit, fail the most-loaded node, rebalance: (nimbus, result, s, node)."""
    nimbus = Nimbus(reconfig=reconfig, reconfig_kwargs=kwargs)
    plan = nimbus.submit(_payload(base))
    load: dict = {}
    for nid in plan.placements.values():
        load[nid] = load.get(nid, 0) + 1
    victim = min(load, key=lambda nid: (-load[nid], nid))
    nimbus.fail_node(victim)
    t0 = time.perf_counter()
    result = nimbus.rebalance()
    return nimbus, result, time.perf_counter() - t0, victim


def phase_rebalance(base: dict) -> None:
    """(d) reconfig="search" failover vs a greedy-mode twin."""
    kwargs = {"seed": 0, "backend": "jax"}
    twin, twin_result, _, _ = _failover(base, "greedy")
    (nimbus, result, cold, victim) = _failover(base, "search", kwargs)
    (_, again, warm, _) = _failover(base, "search", kwargs)
    print(
        f"[d] search rebalance after failing {victim}: cold {cold:.3f} s, "
        f"warm {warm:.3f} s; moved {result.moved_count()}, "
        f"unplaced {result.unplaced_count()}",
        flush=True,
    )
    overlap = sum(
        len(set(result.moved.get(t, ())) & set(result.unplaced.get(t, ())))
        for t in set(result.moved) | set(result.unplaced)
    )
    _check("d", overlap == 0, "moved and unplaced are disjoint")
    payload = _payload(base)
    tid = payload.topology.id
    _check_feasible("d", payload, nimbus.state.assignments[tid].placements,
                    dead=(victim,))
    tp = nimbus.simulate_all()[tid].sink_throughput
    tp_g = twin.simulate_all()[tid].sink_throughput
    _check("d", tp >= tp_g,
           f"simulated sink throughput {tp!r} >= greedy rebalance {tp_g!r} "
           f"(greedy moved {twin_result.moved_count()})")
    _check("d", again.to_dict() == result.to_dict(), "warm rebalance == cold rebalance")


def main() -> int:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: jax's first device is {devices[0].platform!r}, not a "
            "TPU; nothing was run",
            file=sys.stderr,
        )
        return 1
    from repro.core.search.backend import enable_compile_cache, resolve_backend

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    try:
        resolve_backend("pallas")
    except RuntimeError as e:
        print(f"backend='pallas' refused on the chip: {e}", flush=True)
    else:
        raise SmokeFailure("backend='pallas' was accepted on a TPU")
    base = flagship_payload(**FLAGSHIP)
    phase_scorer(base)
    phase_netcost_plan(base)
    phase_throughput_plan(base)
    phase_rebalance(base)
    d = devices[0]
    print(json.dumps(
        {"ok": True, "device": {"platform": d.platform, "kind": d.device_kind,
                                "count": len(devices)}}
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())
