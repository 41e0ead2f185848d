"""Batched placement-search subsystem (repro.core.search).

Covers: BatchArena compilation, the batched objective against the exact
dict-path evaluators, the shared swap-delta against full recomputation
(the regression the extraction from SwapAnnealer is pinned by), the
rstorm-search scheduler's never-worse-than-greedy guarantee, determinism,
jax/numpy golden equality, and the control-plane integration
(registry kwargs, Nimbus plan/submit/rebalance, ScenarioRunner replay).
"""

from pathlib import Path

import numpy as np
import pytest

from repro.core import (
    Assignment,
    BatchArena,
    Cluster,
    Component,
    NodeSpec,
    PlacementArena,
    SearchScheduler,
    Topology,
    emulab_cluster,
    evaluate_batch,
    get_scheduler,
    validate_scheduler_kwargs,
)
from repro.core.engine import swap_network_delta, swap_overload_delta
from repro.core.search import BatchAnnealer, HAS_JAX
from repro.core.search.anneal import (
    histogram_network_delta,
    scan_tables,
    sums_exactly,
    swap_proposals,
)
from repro.core.search.throughput import compile_throughput, throughput_batch
from repro.stream import Simulator, topologies as T

BACKENDS = ["numpy"] + (["jax"] if HAS_JAX else [])


def chain_topology(components=5, parallelism=4, mem=128.0, cpu=10.0):
    t = Topology(f"chain{components}x{parallelism}")
    prev = None
    for i in range(components):
        c = Component(f"c{i}", is_spout=(i == 0), parallelism=parallelism)
        c.set_memory_load(mem).set_cpu_load(cpu)
        t.add_component(c)
        if prev:
            t.add_edge(prev, c.id)
        prev = c.id
    return t


def compile_case(topo_factory=chain_topology, cluster_factory=emulab_cluster):
    topology, cluster = topo_factory(), cluster_factory()
    arena = PlacementArena(cluster, topology)
    avail0 = arena.snapshot()
    assignment = Assignment(topology_id=topology.id)
    get_scheduler("rstorm")._place_on_arena(arena, topology, assignment)
    ba = BatchArena.from_arena(
        arena, topology, dict(assignment.placements), avail0=avail0
    )
    return topology, cluster, arena, assignment, ba


def random_batch(ba, n, seed=0, alive_only=True):
    rng = np.random.Generator(np.random.Philox(seed))
    pool = np.flatnonzero(ba.alive) if alive_only else np.arange(ba.n_nodes)
    return pool[rng.integers(0, pool.size, size=(n, ba.n_tasks))]


# -- BatchArena compilation -------------------------------------------------------
def test_batch_arena_shapes_and_order():
    topology, cluster, arena, assignment, ba = compile_case()
    assert ba.tids == sorted(assignment.placements)
    assert ba.n_tasks == len(assignment.placements)
    assert ba.n_nodes == len(cluster.nodes)
    assert ba.hard_dims == ["memory_mb"]
    assert ba.net is arena.net  # shared, not copied
    assert ba.hard_demand.shape == (ba.n_tasks, 1)
    assert ba.adj.shape[0] == ba.n_tasks
    assert (ba.adj[ba.adj_mask] >= 0).all()
    # Every directed component edge appears as task pairs over placed tasks.
    assert ba.edges.shape[0] == sum(
        topology.components[s].parallelism * topology.components[d].parallelism
        for s, d in topology.edges
    )


def test_encode_decode_round_trip():
    *_, assignment, ba = compile_case()
    row = ba.encode(dict(assignment.placements))
    assert ba.decode(row) == dict(assignment.placements)


# -- objective vs exact dict-path evaluation --------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
def test_objective_matches_assignment_network_cost(backend):
    topology, cluster, arena, assignment, ba = compile_case(
        lambda: T.pageload(), lambda: emulab_cluster()
    )
    P = random_batch(ba, 16, seed=7)
    result = evaluate_batch(ba, P, backend=backend)
    for b in range(P.shape[0]):
        a = Assignment(topology.id, placements=ba.decode(P[b]))
        assert result.net[b] == a.network_cost(topology, cluster)
        # On a fresh cluster, availability == capacity, so zero violation
        # must coincide with the dict-path hard_violations check.
        assert (result.violation[b] == 0.0) == (
            a.hard_violations(topology, cluster) == []
        )
    assert (result.dead == 0).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_objective_flags_dead_nodes(backend):
    topology, cluster, arena, assignment, ba = compile_case()
    cluster.fail_node(ba.node_ids[0])
    arena2 = PlacementArena(cluster, topology)
    ba2 = BatchArena.from_arena(
        arena2, topology, dict(assignment.placements), avail0=arena2.snapshot()
    )
    P = np.zeros((1, ba2.n_tasks), dtype=np.intp)  # everything on the dead node
    result = evaluate_batch(ba2, P, backend=backend)
    assert result.dead[0] == ba2.n_tasks
    assert not result.feasible[0]


def test_greedy_seed_is_feasible_with_zero_violation():
    topology, cluster, arena, assignment, ba = compile_case()
    result = evaluate_batch(ba, ba.encode(dict(assignment.placements)))
    assert result.violation[0] == 0.0
    assert result.feasible[0]


# -- shared swap delta vs full recompute (regression for the extraction) ----------
def test_swap_delta_matches_full_recompute():
    topology, cluster, arena, assignment, ba = compile_case(
        lambda: T.diamond(True), lambda: emulab_cluster()
    )
    rng = np.random.Generator(np.random.Philox(3))
    P = random_batch(ba, 1, seed=11)[0]
    base = evaluate_batch(ba, P)
    used = ba.used(P)[0]
    for _ in range(50):
        i = int(rng.integers(0, ba.n_tasks))
        j = int((i + rng.integers(1, ba.n_tasks)) % ba.n_tasks)
        na, nb = int(P[i]), int(P[j])
        pa = P[np.where(ba.adj_mask[i], ba.adj[i], 0)]
        pb = P[np.where(ba.adj_mask[j], ba.adj[j], 0)]
        m_ab = int(((ba.adj[i] == j) & ba.adj_mask[i]).sum())
        dnet = swap_network_delta(
            ba.net, na, nb, pa, pb, m_ab, ba.adj_mask[i], ba.adj_mask[j]
        )
        dov = swap_overload_delta(
            ba.avail[na], ba.avail[nb], used[na], used[nb],
            ba.hard_demand[i], ba.hard_demand[j],
        )
        Q = P.copy()
        Q[i], Q[j] = P[j], P[i]
        full = evaluate_batch(ba, Q)
        assert dnet == full.net[0] - base.net[0]
        assert dov == pytest.approx(full.violation[0] - base.violation[0])


def test_sequential_annealer_tracked_cost_matches_recompute():
    """The SwapAnnealer, now running on the shared delta, must still land on
    a placement whose tracked cost equals the from-scratch evaluation."""
    import random
    from repro.core import SwapAnnealer

    topology, cluster, arena, assignment, ba = compile_case()
    ann = SwapAnnealer(arena, topology, dict(assignment.placements))
    placements = ann.run(300, random.Random(5))
    a = Assignment(topology.id, placements=placements)
    assert ann.cost() == a.network_cost(topology, cluster)


# -- batched annealer -------------------------------------------------------------
def test_swap_proposals_never_propose_identity():
    ii, jj = swap_proposals(17, 200, 8, seed=4)
    assert (ii != jj).all()
    ii2, jj2 = swap_proposals(17, 200, 8, seed=4)
    assert (ii == ii2).all() and (jj == jj2).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_annealer_chains_stay_feasible_from_greedy(backend):
    topology, cluster, arena, assignment, ba = compile_case()
    P0 = np.tile(ba.encode(dict(assignment.placements)), (8, 1))
    P = BatchAnnealer(ba, backend=backend).run(P0, steps=150, seed=2)
    result = evaluate_batch(ba, P, backend=backend)
    assert (result.violation == 0.0).all()
    assert (result.dead == 0).all()


def two_chains():
    """Two chains of unequal parallelism in one topology, as two tenants
    are: unequal degrees, so the padded adjacency has -1 entries."""
    t = Topology("two-chains")
    for name, par in (("a", 7), ("b", 3)):
        prev = None
        for i in range(3):
            c = Component(f"{name}{i}", is_spout=(i == 0), parallelism=par)
            c.set_memory_load(128.0).set_cpu_load(10.0)
            t.add_component(c)
            if prev:
                t.add_edge(prev, c.id)
            prev = c.id
    return t


def anneal_case(name):
    """``(ba, P0, steps, seed, k, t0)`` of one golden case of the netcost
    annealer, where jax must walk numpy's chains bit for bit."""
    steps, seed, k, t0 = 200, 13, 1, 2.0
    if name == "pageload":
        ba = compile_case(T.pageload, emulab_cluster)[-1]
        P0 = random_batch(ba, 16, seed=9)
    elif name == "padded":
        ba = compile_case(two_chains, emulab_cluster)[-1]
        assert not ba.adj_mask.all()
        P0 = random_batch(ba, 8, seed=3)
    elif name == "neighbours":
        # 12 tasks, 8 of them adjacent to 8 others: many proposals swap
        # direct neighbours (m_ab > 0).
        ba = compile_case(lambda: chain_topology(3, 4), emulab_cluster)[-1]
        P0 = random_batch(ba, 8, seed=4)
        ii, jj = swap_proposals(ba.n_tasks, steps, 8, seed)
        assert (ba.adj[ii] == jj[..., None]).any(axis=-1).mean() > 0.3
    elif name == "infeasible":
        # 20 tasks of 128 MB on six 512 MB nodes: random seeds overload.
        ba = compile_case(
            lambda: chain_topology(5, 4),
            lambda: Cluster.homogeneous(racks=2, nodes_per_rack=3, memory_mb=512.0),
        )[-1]
        P0 = random_batch(ba, 8, seed=5)
        assert (evaluate_batch(ba, P0, backend="numpy").violation > 0).any()
    elif name == "move-costs":
        ba = compile_case(T.pageload, emulab_cluster)[-1]
        rng = np.random.Generator(np.random.Philox(6))
        ba.move_base = np.flatnonzero(ba.alive)[
            rng.integers(0, ba.alive.sum(), size=ba.n_tasks)
        ]
        ba.move_cost = rng.integers(0, 8, size=ba.n_tasks) * 0.25
        P0 = random_batch(ba, 8, seed=6)
    elif name == "k8-tail":
        # 203 = 25 blocks of 8 and a tail of 3 single swaps.
        ba = compile_case(two_chains, emulab_cluster)[-1]
        P0 = random_batch(ba, 8, seed=7)
        steps, k = 203, 8
    elif name == "one-chain":
        ba = compile_case(T.pageload, emulab_cluster)[-1]
        P0 = random_batch(ba, 1, seed=8)
    elif name == "float64-table":
        # Distances float32 cannot hold (2**-30 steps), still on one grid
        # of a power of two, so every sum is exact in any order (the scan
        # refuses other tables); hill-climbing (t0 = 0) turns on their
        # last bits.
        ba = compile_case(T.pageload, emulab_cluster)[-1]
        n = np.arange(ba.n_nodes)
        ba.net = ba.net + (np.add.outer(n, n) % 5) * 2.0**-30
        assert not np.array_equal(ba.net.astype(np.float32), ba.net)
        P0 = random_batch(ba, 8, seed=10)
        t0 = 0.0
    else:
        raise KeyError(name)
    return ba, P0, steps, seed, k, t0


ANNEAL_CASES = [
    "pageload", "padded", "neighbours", "infeasible", "move-costs", "k8-tail",
    "one-chain", "float64-table",
]


@pytest.mark.skipif(not HAS_JAX, reason="jax not installed")
@pytest.mark.parametrize("case", ANNEAL_CASES)
def test_annealer_backends_golden_equal(case):
    ba, P0, steps, seed, k, t0 = anneal_case(case)
    numpy_run, jax_run = BatchAnnealer(ba, backend="numpy"), BatchAnnealer(ba, backend="jax")
    a = numpy_run.run(P0, steps=steps, seed=seed, t0=t0)
    b = jax_run.run(P0, steps=steps, seed=seed, t0=t0, multi_swap=k)
    assert (a == b).all()
    assert np.array_equal(np.asarray(numpy_run.accepted), np.asarray(jax_run.accepted))
    assert numpy_run.accepted_total() > 0
    ra = evaluate_batch(ba, a, backend="numpy")
    rb = evaluate_batch(ba, b, backend="jax")
    assert (ra.net == rb.net).all()
    assert (ra.violation == rb.violation).all()


@pytest.mark.parametrize("case", ["padded", "neighbours", "move-costs", "float64-table"])
def test_histogram_delta_equals_shared_delta(case):
    """The jax scan's delta, regrouped by node and read from the table the
    scan is given, equals the shared per-neighbour delta to the bit."""
    ba, P, *_ = anneal_case(case)
    net = scan_tables(ba)[0]
    bidx = np.arange(P.shape[0])
    ii, jj = swap_proposals(ba.n_tasks, 40, P.shape[0], seed=1)
    for i, j in zip(ii, jj):
        na, nb = P[bidx, i], P[bidx, j]
        mi, mj = ba.adj_mask[i], ba.adj_mask[j]
        pa = P[bidx[:, None], np.where(mi, ba.adj[i], 0)]
        pb = P[bidx[:, None], np.where(mj, ba.adj[j], 0)]
        m_ab = ((ba.adj[i] == j[:, None]) & mi).sum(axis=-1)
        counts = np.stack([
            np.bincount(pa[b][mi[b]], minlength=ba.n_nodes)
            - np.bincount(pb[b][mj[b]], minlength=ba.n_nodes)
            for b in bidx
        ])
        shared = swap_network_delta(ba.net, na, nb, pa, pb, m_ab, mi, mj)
        assert np.array_equal(
            histogram_network_delta(net, na, nb, counts, m_ab), shared
        )


@pytest.mark.skipif(not HAS_JAX, reason="jax not installed")
def test_scan_refuses_a_net_table_it_cannot_sum_exactly():
    """The scan sums distances by node, the numpy path by neighbour: with a
    distance of 0.1 the two orders round apart, so the jax scan refuses
    that table rather than walk other chains."""
    ba = compile_case(T.pageload, emulab_cluster)[-1]
    net, _, _, adj, move_base, _ = scan_tables(ba)
    assert net is ba.net and adj.dtype == move_base.dtype == np.int32
    assert ba.adj.dtype == np.intp  # the arena as it was
    assert sums_exactly(ba.net, 8 * (ba.adj.shape[1] + 1))
    # 0.1 is an odd multiple of 2**-55: two of it sum exactly, sixteen may not.
    assert sums_exactly(np.array([[0.1]]), 2)
    assert not sums_exactly(np.array([[0.1]]), 16)
    assert not sums_exactly(np.array([[1.0, 2.0**-60]]), 2)
    ba.net = ba.net.copy()
    ba.net[0, 1] = ba.net[1, 0] = 0.1
    with pytest.raises(ValueError, match="exact"):
        BatchAnnealer(ba, backend="jax").run(random_batch(ba, 2, seed=1), steps=4, seed=1)


# -- the registered scheduler -----------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("init", ["greedy", "random", "all-registered"])
def test_search_never_worse_than_greedy(init, backend):
    topology, cluster = T.pageload(), emulab_cluster()
    greedy = get_scheduler("rstorm").schedule(topology, cluster, commit=False)
    greedy_net = greedy.network_cost(topology, cluster)
    cluster.reset()
    s = get_scheduler(
        "rstorm-search", n_chains=12, steps=120, seed=1, init=init, backend=backend
    ).schedule(topology, cluster, commit=False)
    assert s.network_cost(topology, cluster) <= greedy_net
    assert s.hard_violations(topology, cluster) == []
    assert sorted(s.unassigned) == sorted(greedy.unassigned)
    assert set(s.placements) == set(greedy.placements)


def test_search_improves_on_flagship_overhead_case():
    """Acceptance: strictly lower network cost than greedy on the
    1000-task / 256-node case (small budget keeps the test fast)."""
    topo = chain_topology(25, 40)
    cluster = Cluster.homogeneous(
        racks=8, nodes_per_rack=32, memory_mb=65536.0, cpu=6400.0
    )
    greedy = get_scheduler("rstorm").schedule(topo, cluster, commit=False)
    cluster.reset()
    s = get_scheduler("rstorm-search", n_chains=16, steps=150, seed=0).schedule(
        topo, cluster, commit=False
    )
    assert s.network_cost(topo, cluster) < greedy.network_cost(topo, cluster)
    assert s.hard_violations(topo, cluster) == []


@pytest.mark.parametrize("backend", BACKENDS)
def test_search_deterministic(backend):
    topology, cluster = T.diamond(True), emulab_cluster()
    kw = dict(n_chains=10, steps=100, seed=42, backend=backend)
    a = get_scheduler("rstorm-search", **kw).schedule(topology, cluster, commit=False)
    cluster.reset()
    b = get_scheduler("rstorm-search", **kw).schedule(topology, cluster, commit=False)
    assert a.placements == b.placements


@pytest.mark.skipif(not HAS_JAX, reason="jax not installed")
def test_search_backends_agree_end_to_end():
    topology, cluster = T.pageload(), emulab_cluster()
    kw = dict(n_chains=12, steps=150, seed=3)
    a = get_scheduler("rstorm-search", backend="numpy", **kw).schedule(
        topology, cluster, commit=False
    )
    cluster.reset()
    b = get_scheduler("rstorm-search", backend="jax", **kw).schedule(
        topology, cluster, commit=False
    )
    assert a.placements == b.placements


@pytest.mark.skipif(not HAS_JAX, reason="jax not installed")
def test_x64_is_scoped():
    import jax.numpy as jnp

    from repro.core.search.backend import x64

    with x64():
        assert jnp.zeros(1).dtype == jnp.float64
    assert jnp.zeros(1).dtype == jnp.float32


@pytest.mark.skipif(not HAS_JAX, reason="jax not installed")
def test_compile_cache_env_wins_else_fixed_checkout_path(monkeypatch):
    import jax

    from repro.core.search import backend

    prev = jax.config.jax_compilation_cache_dir
    root = Path(backend.__file__).resolve().parents[4]
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert backend.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev  # nothing set
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = backend.enable_compile_cache()
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (root / ".gitignore").read_text().splitlines()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_search_degrades_to_greedy_on_trivial_topology():
    t = Topology("solo")
    t.add_component(Component("s", is_spout=True, parallelism=1))
    cluster = emulab_cluster()
    s = get_scheduler("rstorm-search", n_chains=4, steps=10).schedule(
        t, cluster, commit=False
    )
    cluster.reset()
    g = get_scheduler("rstorm").schedule(t, cluster, commit=False)
    assert s.placements == g.placements


# -- control-plane integration ----------------------------------------------------
def test_kwargs_schema_validation():
    assert validate_scheduler_kwargs("rstorm-search", {"n_chains": 8}) == []
    errs = validate_scheduler_kwargs(
        "rstorm-search", {"init": "genetic", "steps": 0, "bogus": 1}
    )
    assert len(errs) == 3
    with pytest.raises(TypeError):
        get_scheduler("rstorm-search", init="genetic")
    if HAS_JAX:
        assert SearchScheduler(backend="jax").backend == "jax"
    else:
        # Explicit jax on a jax-less box must fail loudly, not fall back.
        with pytest.raises(RuntimeError):
            SearchScheduler(backend="jax")
    assert SearchScheduler(backend="auto").backend == (
        "jax" if HAS_JAX else "numpy"
    )


def test_nimbus_plan_submit_rebalance_with_search():
    from repro.api import (
        ClusterSpec,
        Nimbus,
        RunSettings,
        SchedulerSpec,
        SchedulingPayload,
        TopologySpec,
    )

    payload = SchedulingPayload(
        topology=TopologySpec.from_topology(T.pageload()),
        cluster=ClusterSpec(preset="emulab_12"),
        scheduler=SchedulerSpec("rstorm-search", {"n_chains": 8, "steps": 80}),
        settings=RunSettings(simulate=False),
    )
    nim = Nimbus()
    plan = nim.plan(payload)
    assert plan.scheduler_name == "rstorm-search"
    assert not plan.committed and nim.cluster is None
    plan2 = nim.submit(payload)
    assert plan2.committed
    assert plan2.placements == plan.placements  # stateless plan == submit
    # Greedy rstorm on the same payload must not beat the search plan.
    greedy_nim = Nimbus()
    gplan = greedy_nim.plan(
        SchedulingPayload(
            topology=payload.topology,
            cluster=payload.cluster,
            scheduler=SchedulerSpec("rstorm"),
            settings=RunSettings(simulate=False),
        )
    )
    assert plan.network_cost <= gplan.network_cost
    # Lifecycle verbs keep working on a search-scheduled state.
    orphans = nim.fail_node(sorted(nim.cluster.nodes)[0])
    result = nim.rebalance()
    assert {tid for _, tid in orphans} == set(
        result.moved.get(plan.topology_id, [])
    ) | set(result.unplaced.get(plan.topology_id, []))


# -- throughput proxy (the §6 objective) --------------------------------------------
def tp_case(maker=T.pageload):
    topology, cluster, arena, assignment, ba = compile_case(
        maker, lambda: emulab_cluster()
    )
    tm = compile_throughput(ba, topology, cluster)
    return topology, cluster, assignment, ba, tm


@pytest.mark.parametrize("maker", [T.pageload, T.processing, lambda: T.linear(True)])
def test_throughput_proxy_deterministic(maker):
    topology, cluster, assignment, ba, tm = tp_case(maker)
    P = random_batch(ba, 12, seed=5)
    a = throughput_batch(ba, tm, P, backend="numpy")
    tm2 = compile_throughput(ba, topology, cluster)
    b = throughput_batch(ba, tm2, P, backend="numpy")
    assert (a == b).all()
    assert np.isfinite(a).all() and (a >= 0.0).all()


@pytest.mark.skipif(not HAS_JAX, reason="jax not installed")
@pytest.mark.parametrize(
    "maker",
    [T.pageload, T.processing, lambda: T.linear(True), lambda: T.star(False)],
)
def test_throughput_proxy_backends_bit_identical(maker):
    """Same golden-equality bar as evaluate_batch: the grid-quantized
    reductions make numpy and jax agree to the last bit."""
    topology, cluster, assignment, ba, tm = tp_case(maker)
    P = random_batch(ba, 16, seed=7)
    P[0] = ba.encode(dict(assignment.placements))
    a = throughput_batch(ba, tm, P, backend="numpy")
    b = throughput_batch(ba, tm, P, backend="jax")
    assert (a == b).all()


def test_throughput_proxy_matches_simulator_in_cpu_bound_regime():
    """Where the paper's §6.3.2 analysis is exact (uniform shuffle, CPU
    binding), the proxy *is* the simulator's answer for the greedy seed."""
    for maker in (lambda: T.linear(False), lambda: T.star(False)):
        topology, cluster, assignment, ba, tm = tp_case(maker)
        proxy = float(
            throughput_batch(ba, tm, ba.encode(dict(assignment.placements)))[0]
        )
        sim = Simulator(cluster).run(topology, assignment).sink_throughput
        assert proxy == pytest.approx(sim, rel=1e-6)


def test_evaluate_batch_populates_throughput_field():
    topology, cluster, assignment, ba, tm = tp_case()
    P = random_batch(ba, 6, seed=3)
    plain = evaluate_batch(ba, P, backend="numpy")
    assert plain.throughput is None
    full = evaluate_batch(ba, P, backend="numpy", throughput_model=tm)
    assert full.throughput is not None
    assert (full.throughput == throughput_batch(ba, tm, P, backend="numpy")).all()
    assert (full.net == plain.net).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_evaluate_batch_chunked_equals_unchunked(backend):
    """Regression: ``chunk`` used to be ignored on the jax path — a huge
    batch built one monolithic (B, E) gather.  Chunked results must be
    bit-identical to unchunked on both backends."""
    topology, cluster, assignment, ba, tm = tp_case()
    P = random_batch(ba, 11, seed=9)
    whole = evaluate_batch(ba, P, backend=backend, chunk=1024, throughput_model=tm)
    parts = evaluate_batch(ba, P, backend=backend, chunk=3, throughput_model=tm)
    assert (whole.net == parts.net).all()
    assert (whole.violation == parts.violation).all()
    assert (whole.dead == parts.dead).all()
    assert (whole.throughput == parts.throughput).all()
    with pytest.raises(ValueError):
        evaluate_batch(ba, P, backend=backend, chunk=0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_annealer_throughput_mode_feasible_and_never_below_seed_proxy(backend):
    # Shuffle-grouped topology: the annealer's uniform-split carried state
    # and the locality-aware evaluator coincide, so the hill-climb
    # guarantee (proxy never drops below the seed's) is exact.
    topology, cluster, assignment, ba, tm = tp_case(lambda: T.linear(True))
    greedy_row = ba.encode(dict(assignment.placements))
    P0 = np.tile(greedy_row, (6, 1))
    P = BatchAnnealer(ba, backend=backend).run(
        P0, steps=150, seed=4, objective="throughput", tm=tm
    )
    result = evaluate_batch(ba, P, backend=backend, throughput_model=tm)
    assert (result.violation == 0.0).all()
    seed_tp = throughput_batch(ba, tm, greedy_row, backend=backend)[0]
    assert (result.throughput >= seed_tp).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_annealer_throughput_mode_stays_feasible_on_local_groupings(backend):
    topology, cluster, assignment, ba, tm = tp_case()  # pageload: local_or_shuffle
    P0 = np.tile(ba.encode(dict(assignment.placements)), (6, 1))
    P = BatchAnnealer(ba, backend=backend).run(
        P0, steps=150, seed=4, objective="throughput", tm=tm
    )
    result = evaluate_batch(ba, P, backend=backend, throughput_model=tm)
    assert (result.violation == 0.0).all()
    assert (result.dead == 0).all()


def test_annealer_throughput_mode_requires_model():
    *_, ba, tm = tp_case()
    with pytest.raises(ValueError):
        BatchAnnealer(ba).run(np.zeros((1, ba.n_tasks), dtype=np.intp), 10, 0,
                              objective="throughput")
    with pytest.raises(ValueError):
        BatchAnnealer(ba).run(np.zeros((1, ba.n_tasks), dtype=np.intp), 10, 0,
                              objective="latency")


@pytest.mark.skipif(not HAS_JAX, reason="jax not installed")
@pytest.mark.parametrize(
    "maker", [T.pageload, T.processing, lambda: T.diamond(True)]
)
def test_annealer_throughput_mode_backends_golden_equal(maker):
    topology, cluster, assignment, ba, tm = tp_case(maker)
    P0 = random_batch(ba, 10, seed=11)
    P0[0] = ba.encode(dict(assignment.placements))
    a = BatchAnnealer(ba, backend="numpy").run(
        P0, steps=250, seed=13, objective="throughput", tm=tm
    )
    b = BatchAnnealer(ba, backend="jax").run(
        P0, steps=250, seed=13, objective="throughput", tm=tm
    )
    assert (a == b).all()


@pytest.mark.parametrize(
    "maker",
    [
        lambda: T.linear(True),
        lambda: T.linear(False),
        lambda: T.star(False),
        T.pageload,
        T.processing,
    ],
)
def test_search_throughput_objective_never_worse_in_simulated_sink_tp(maker):
    """The acceptance guarantee, measured where §6 measures: simulated sink
    throughput of the chosen placement vs the greedy R-Storm seed."""
    topology, cluster = maker(), emulab_cluster()
    greedy = get_scheduler("rstorm").schedule(topology, cluster, commit=False)
    cluster.reset()
    s = get_scheduler(
        "rstorm-search", n_chains=8, steps=150, seed=0, objective="throughput"
    ).schedule(topology, cluster, commit=False)
    cluster.reset()
    sim = Simulator(cluster)
    tp_s = sim.run(topology, s).sink_throughput
    tp_g = sim.run(topology, greedy).sink_throughput
    assert tp_s >= tp_g
    assert s.hard_violations(topology, cluster) == []


def test_search_throughput_objective_deterministic():
    topology, cluster = T.pageload(), emulab_cluster()
    kw = dict(n_chains=8, steps=120, seed=7, objective="throughput")
    a = get_scheduler("rstorm-search", **kw).schedule(topology, cluster, commit=False)
    cluster.reset()
    b = get_scheduler("rstorm-search", **kw).schedule(topology, cluster, commit=False)
    assert a.placements == b.placements


def test_search_objective_kwarg_registry_validation():
    assert validate_scheduler_kwargs(
        "rstorm-search", {"objective": "throughput"}
    ) == []
    errs = validate_scheduler_kwargs("rstorm-search", {"objective": "latency"})
    assert len(errs) == 1
    with pytest.raises(TypeError):
        get_scheduler("rstorm-search", objective="latency")


# -- unassigned recovery (bugfix regression) ----------------------------------------
def recovery_case():
    """Near-full two-node cluster where greedy's spread (CPU distance term)
    strands the big sink task, but a consolidated rearrangement frees the
    memory it needs."""
    t = Topology("recov")
    prev = None
    for k in range(3):
        comp = Component(f"c{k}", is_spout=(k == 0), parallelism=1)
        comp.set_memory_load(500.0).set_cpu_load(60.0)
        t.add_component(comp)
        if prev:
            t.add_edge(prev, comp.id)
        prev = comp.id
    x = Component("x", parallelism=1)
    x.set_memory_load(1100.0).set_cpu_load(10.0)
    t.add_component(x)
    t.add_edge(prev, "x")
    cl = Cluster(
        [NodeSpec(f"n{i}", "rack0", 100.0, 1500.0) for i in range(2)]
    )
    return t, cl


def test_search_recovers_task_greedy_stranded():
    """Regression: the search used to carry greedy's ``unassigned`` list
    through unchanged even when the annealed winner freed the capacity."""
    t, cl = recovery_case()
    greedy = get_scheduler("rstorm").schedule(t, cl, commit=False)
    assert greedy.unassigned == ["recov/x[0]"]  # the setup's premise
    cl.reset()
    s = get_scheduler(
        "rstorm-search", n_chains=12, steps=400, seed=0, init="random"
    ).schedule(t, cl, commit=False)
    assert s.is_complete(t)
    assert s.hard_violations(t, cl) == []


def test_search_recovery_is_deterministic_and_respects_budget():
    t, cl = recovery_case()
    kw = dict(n_chains=12, steps=400, seed=0, init="random")
    a = get_scheduler("rstorm-search", **kw).schedule(t, cl, commit=False)
    cl.reset()
    b = get_scheduler("rstorm-search", **kw).schedule(t, cl, commit=False)
    assert a.placements == b.placements
    assert a.unassigned == b.unassigned


def test_scenario_replay_with_search_is_deterministic():
    from repro.api import (
        ClusterSpec,
        NodeFailEvent,
        RebalanceEvent,
        ScenarioRunner,
        ScenarioSpec,
        SchedulerSpec,
        SubmitEvent,
    )

    spec = ScenarioSpec(
        name="search_failover",
        cluster=ClusterSpec(preset="emulab_12"),
        timeline=(
            SubmitEvent(
                topology=T.spec("pageload"),
                scheduler=SchedulerSpec(
                    "rstorm-search", {"n_chains": 8, "steps": 60, "seed": 5}
                ),
            ),
            NodeFailEvent(node_id="r0n0"),
            RebalanceEvent(),
        ),
    )
    t1 = ScenarioRunner(spec).run()
    t2 = ScenarioRunner(spec).run()
    assert t1.to_dict() == t2.to_dict()
