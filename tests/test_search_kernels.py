"""Golden-oracle tests for the fused Pallas candidate-scoring kernel.

The contract under test: ``fused_score`` (interpret mode) == jax-vmap ==
numpy **bit-identical** on every objective term — netcost, hard-capacity
violation, dead-node count, and the throughput proxy — across the §6
topology suite.  The dyadic-grid quantization of every throughput input
makes all float64 segment-sums exact regardless of accumulation order,
which is what lets three differently-ordered reductions agree to the bit
(see ``repro.core.search.kernels``).

Also pinned here: the host-side padding boundary (batches that are not a
block multiple, single-row batches, block sizes larger than the batch),
all-dead candidates, the ≥10k-candidates-in-one-call capacity the fused
path exists for, and the multi-swap annealer's bit-identity to the k=1
chain on both objectives.

Shape edge cases run twice: once as deterministic parametrized sweeps
(always on), and once property-style under hypothesis when it is
installed (the container may not ship it — those simply skip).
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.search import BatchAnnealer, evaluate_batch
from repro.core.search.kernels import DEFAULT_BLOCK_B, fused_score
from repro.core.search.throughput import compile_throughput, throughput_batch
from repro.stream import topologies as T

from tests.test_search import (
    ANNEAL_CASES,
    anneal_case,
    compile_case,
    emulab_cluster,
    random_batch,
)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAS_HYPOTHESIS = True
except ImportError:  # container may not ship hypothesis — satellite tests skip
    HAS_HYPOTHESIS = False

# The §6 suite (same topology set the benchmarks sweep).
SUITE = [
    ("linear_net", lambda: T.linear(True)),
    ("diamond_net", lambda: T.diamond(True)),
    ("star_net", lambda: T.star(True)),
    ("linear_cpu", lambda: T.linear(False)),
    ("diamond_cpu", lambda: T.diamond(False)),
    ("star_cpu", lambda: T.star(False)),
    ("pageload", T.pageload),
    ("processing", T.processing),
]


def kernel_case(maker, with_tm=True, cluster_factory=emulab_cluster):
    topology, cluster, arena, assignment, ba = compile_case(
        maker, cluster_factory
    )
    tm = compile_throughput(ba, topology, cluster) if with_tm else None
    return ba, tm


def assert_bit_identical(ba, tm, P, block_b=DEFAULT_BLOCK_B):
    """The three-backend golden-equality contract on one batch."""
    net_np = evaluate_batch(ba, P, backend="numpy", throughput_model=tm)
    net_jx = evaluate_batch(ba, P, backend="jax", throughput_model=tm)
    kn, kv, kd, kt = fused_score(
        ba, P, tm=tm, block_b=block_b, interpret=True
    )
    for oracle in (net_np, net_jx):
        assert np.array_equal(oracle.net, kn)
        assert np.array_equal(oracle.violation, kv)
        assert np.array_equal(oracle.dead, kd)
        if tm is not None:
            assert np.array_equal(oracle.throughput, kt)
    if tm is None:
        assert kt is None
    return kn, kv, kd, kt


# --------------------------------------------------------------------------
# three-backend golden equality across the §6 suite
# --------------------------------------------------------------------------


@pytest.mark.parametrize("name,maker", SUITE, ids=[n for n, _ in SUITE])
def test_fused_kernel_bit_identical_on_suite(name, maker):
    ba, tm = kernel_case(maker)
    # B=13 is deliberately not a multiple of the block: the padded tail
    # rows must not leak into (or corrupt) the first 13 outputs.
    P = random_batch(ba, 13, seed=11)
    assert_bit_identical(ba, tm, P)


@pytest.mark.parametrize("name,maker", SUITE, ids=[n for n, _ in SUITE])
def test_evaluate_batch_pallas_backend_on_suite(name, maker):
    ba, tm = kernel_case(maker)
    P = random_batch(ba, 13, seed=17)
    a = evaluate_batch(ba, P, backend="numpy", throughput_model=tm)
    b = evaluate_batch(ba, P, backend="pallas", throughput_model=tm)
    assert np.array_equal(a.net, b.net)
    assert np.array_equal(a.violation, b.violation)
    assert np.array_equal(a.dead, b.dead)
    assert np.array_equal(a.throughput, b.throughput)
    assert np.array_equal(a.feasible, b.feasible)
    tp = throughput_batch(ba, tm, P, backend="pallas")
    assert np.array_equal(a.throughput, tp)


def test_pallas_backend_chunking_is_invisible():
    ba, tm = kernel_case(T.pageload)
    P = random_batch(ba, 29, seed=3)
    whole = evaluate_batch(ba, P, backend="pallas", throughput_model=tm)
    chunked = evaluate_batch(
        ba, P, backend="pallas", throughput_model=tm, chunk=7
    )
    assert np.array_equal(whole.net, chunked.net)
    assert np.array_equal(whole.throughput, chunked.throughput)


# --------------------------------------------------------------------------
# padding / batch-shape edge cases (deterministic sweeps, always on)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("B", [1, 5, 8, 13, 16])
@pytest.mark.parametrize("block_b", [1, 3, 8, 16])
def test_padding_boundary_shapes(B, block_b):
    ba, tm = kernel_case(lambda: T.linear(True))
    P = random_batch(ba, B, seed=B * 31 + block_b)
    assert_bit_identical(ba, tm, P, block_b=block_b)


def test_star_max_degree_padding():
    # The star hub has the topology's maximum degree — the densest edge
    # gather rows — and parallelism=4 keeps T=n*4 off the block multiple.
    ba, tm = kernel_case(lambda: T.star(True))
    P = random_batch(ba, 9, seed=23)
    assert_bit_identical(ba, tm, P)


def test_all_dead_candidates():
    def crippled():
        c = emulab_cluster()
        for nid in sorted(c.nodes)[:4]:
            c.fail_node(nid)
        return c

    ba, _tm = kernel_case(
        lambda: T.linear(True), with_tm=False, cluster_factory=crippled
    )
    dead_nodes = np.flatnonzero(~ba.alive)
    assert dead_nodes.size > 0
    rng = np.random.Generator(np.random.Philox(5))
    P = dead_nodes[rng.integers(0, dead_nodes.size, size=(13, ba.n_tasks))]
    _, _, kd, _ = assert_bit_identical(ba, None, P)
    assert (kd == ba.n_tasks).all()  # every task on a dead node


def test_netcost_only_mode_matches_oracles():
    ba, _ = kernel_case(T.processing, with_tm=False)
    P = random_batch(ba, 13, seed=7)
    assert_bit_identical(ba, None, P)


# --------------------------------------------------------------------------
# capacity: ≥10k concurrent candidates in ONE fused call
# --------------------------------------------------------------------------


def test_ten_thousand_candidates_single_call():
    ba, tm = kernel_case(lambda: T.linear(True))
    B = 10_240
    P = random_batch(ba, B, seed=42)
    kn, kv, kd, kt = fused_score(ba, P, tm=tm, interpret=True)
    assert kn.shape == kv.shape == kd.shape == kt.shape == (B,)
    oracle = evaluate_batch(
        ba, P, backend="numpy", chunk=B, throughput_model=tm
    )
    assert np.array_equal(oracle.net, kn)
    assert np.array_equal(oracle.violation, kv)
    assert np.array_equal(oracle.dead, kd)
    assert np.array_equal(oracle.throughput, kt)


# --------------------------------------------------------------------------
# multi-swap annealing: k-fused chains are bit-identical to k=1
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "case,k",
    [pytest.param("diamond", k, id=str(k)) for k in (1, 4, 8)]
    + [pytest.param(c, 8, id=f"{c}-8") for c in ANNEAL_CASES if c != "k8-tail"],
)
def test_multi_swap_netcost_bit_identical(case, k):
    if case == "diamond":
        ba, _ = kernel_case(lambda: T.diamond(True), with_tm=False)
        P0, seed, t0 = random_batch(ba, 12, seed=2), 9, 2.0
    else:
        ba, P0, _, seed, _, t0 = anneal_case(case)
    # steps=30 is not a multiple of 4 or 8 — the k=1 tail chain runs too.
    numpy_run, jax_run = BatchAnnealer(ba, backend="numpy"), BatchAnnealer(ba, backend="jax")
    ref = numpy_run.run(P0, 30, seed=seed, t0=t0)
    out = jax_run.run(P0, 30, seed=seed, t0=t0, multi_swap=k)
    assert np.array_equal(ref, out)
    assert np.array_equal(np.asarray(numpy_run.accepted), np.asarray(jax_run.accepted))


@pytest.mark.parametrize("k", [1, 4])
def test_multi_swap_throughput_bit_identical(k):
    ba, tm = kernel_case(lambda: T.linear(True))
    P0 = random_batch(ba, 8, seed=2)
    ref = BatchAnnealer(ba, backend="numpy").run(
        P0, 30, seed=9, objective="throughput", tm=tm
    )
    out = BatchAnnealer(ba, backend="jax").run(
        P0, 30, seed=9, objective="throughput", tm=tm, multi_swap=k
    )
    assert np.array_equal(ref, out)


def test_multi_swap_pallas_backend_and_validation():
    ba, _ = kernel_case(lambda: T.linear(True), with_tm=False)
    P0 = random_batch(ba, 8, seed=4)
    ref = BatchAnnealer(ba, backend="numpy").run(P0, 20, seed=1)
    out = BatchAnnealer(ba, backend="pallas").run(P0, 20, seed=1, multi_swap=8)
    assert np.array_equal(ref, out)
    with pytest.raises(ValueError, match="multi_swap"):
        BatchAnnealer(ba, backend="numpy").run(P0, 20, seed=1, multi_swap=0)


def test_pallas_backend_refused_on_tpu_before_lowering(monkeypatch):
    """On a TPU the float64 kernel cannot lower: every pallas entry point
    raises the named error up front (never a Mosaic exception mid-search),
    while backend='jax' stays available."""
    from repro.api import Nimbus, SchedulingPayload
    from repro.core.search.backend import PALLAS_ON_TPU_ERROR, resolve_backend

    ba, tm = kernel_case(lambda: T.linear(True))
    P = random_batch(ba, 4, seed=2)
    payload = SchedulingPayload.from_dict({
        "topology": {"id": "t", "components": [
            {"id": "s", "is_spout": True, "parallelism": 2},
            {"id": "b", "parallelism": 2}], "edges": [{"src": "s", "dst": "b"}]},
        "cluster": {"preset": "emulab_12"},
        "scheduler": {"name": "rstorm-search", "kwargs": {"backend": "pallas"}},
    })
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_backend("jax") == "jax"
    calls = (
        lambda: resolve_backend("pallas"),
        lambda: evaluate_batch(ba, P, backend="pallas", throughput_model=tm),
        lambda: throughput_batch(ba, tm, P, backend="pallas"),
        lambda: BatchAnnealer(ba, backend="pallas"),
        lambda: fused_score(ba, P, tm=tm),
        lambda: Nimbus().plan(payload),
    )
    for call in calls:
        with pytest.raises(RuntimeError) as err:
            call()
        assert str(err.value) == PALLAS_ON_TPU_ERROR


# --------------------------------------------------------------------------
# property-style shape fuzzing (runs only where hypothesis is installed)
# --------------------------------------------------------------------------

if HAS_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        B=st.integers(min_value=1, max_value=40),
        block_b=st.integers(min_value=1, max_value=32),
        seed=st.integers(min_value=0, max_value=2**31 - 1),
    )
    def test_hypothesis_padding_never_leaks(B, block_b, seed):
        ba, tm = kernel_case(lambda: T.linear(True))
        P = random_batch(ba, B, seed=seed)
        assert_bit_identical(ba, tm, P, block_b=block_b)

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_hypothesis_padding_never_leaks():
        pass
