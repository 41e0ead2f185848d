"""Compile rehearsal of the placement search for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: these tests
lower and compile the search's device programs at the flagship widths
(T=1000 tasks, N=256 nodes, E=38,400 edges; ``chip_smoke.FLAGSHIP``) for a
described ``v5e:2x2`` topology, one chip of it.  What the chip's compiler
refuses, or what would not fit its 16 GB, fails here at no chip time.
Nothing runs, so nothing here is a time or a result.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library at a time, and under pytest-xdist
only the worker given this file does.  The persistent compile cache is off
around these compiles, since an entry written for a described chip cannot
be read back without one.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import SingleDeviceSharding  # noqa: E402

import chip_smoke  # noqa: E402
from repro.api import Nimbus  # noqa: E402
from repro.core import BatchArena, PlacementArena  # noqa: E402
from repro.core.search.anneal import (  # noqa: E402
    _jax_anneal_fn,
    scan_tables,
    swap_proposals,
)
from repro.core.search.backend import x64  # noqa: E402
from repro.core.search.kernels.fused_score import (  # noqa: E402
    DEFAULT_BLOCK_B,
    _fused_fn,
    _padded_inputs,
)
from repro.core.search.objective import _jax_eval_fn  # noqa: E402
from repro.core.search.throughput import compile_throughput  # noqa: E402

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def flagship():
    """(BatchArena, ThroughputModel) of the flagship, as chip_smoke builds it."""
    payload = chip_smoke._payload(chip_smoke.flagship_payload(**chip_smoke.FLAGSHIP))
    greedy = Nimbus().plan(payload)
    topology = payload.topology.to_topology()
    cluster = payload.cluster.to_cluster()
    ba = BatchArena.from_arena(
        PlacementArena(cluster, topology), topology, greedy.placements
    )
    assert (ba.n_tasks, ba.n_nodes, ba.edges.shape[0]) == (1000, 256, 38400)
    return ba, compile_throughput(ba, topology, cluster)


def _abstract(args, sharding):
    """Shapes and dtypes of ``args`` on the described chip (no arrays)."""
    return [
        jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype, sharding=sharding)
        for a in args
    ]


def _compile(fn, args, sharding):
    with x64():
        compiled = fn.lower(*_abstract(args, sharding)).compile()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert 0 < total < V5E_HBM_BYTES, mem
    return compiled


def _batch(ba, B, seed=0):
    rng = np.random.Generator(np.random.Philox(seed))
    return np.flatnonzero(ba.alive)[rng.integers(0, ba.n_nodes, size=(B, ba.n_tasks))]


def test_scorer_compiles_for_v5e(flagship, one_chip, no_persistent_cache):
    """The jax-vmap scorer at B=1024 (``chip_smoke`` phase a)."""
    ba, _ = flagship
    mb, mc = ba.move_arrays()
    args = (ba.net, ba.avail, ba.hard_demand, ba.alive, ba.edges, mb, mc,
            _batch(ba, chip_smoke.BATCH))
    _compile(_jax_eval_fn(ba.n_nodes), args, one_chip)


def _scan_args(ba, chains, steps, k):
    """The netcost scan's arguments as ``BatchAnnealer._run_jax`` passes
    them, for ``steps`` proposals fused ``k`` per scan element."""
    P0 = _batch(ba, chains)
    ii, jj = swap_proposals(ba.n_tasks, steps, chains, 0)
    return (
        *scan_tables(ba), P0.astype(np.int32), ba.used(P0),
        np.zeros(chains, dtype=np.int32),
        ii.astype(np.int32).reshape(steps // k, k, chains),
        jj.astype(np.int32).reshape(steps // k, k, chains),
        np.linspace(1.0, 0.0, steps).reshape(steps // k, k),
    )


def test_annealer_step_compiles_for_v5e(flagship, one_chip, no_persistent_cache):
    """The netcost ``lax.scan`` annealer, one proposal per scan element."""
    ba, _ = flagship
    args = _scan_args(ba, chip_smoke.CHAINS, chip_smoke.STEPS, 1)
    _compile(_jax_anneal_fn(1), args, one_chip)


def test_annealer_scan_gathers_for_v5e(flagship, one_chip, no_persistent_cache):
    """What each swap of the netcost scan gathers, at the plan cell's widths
    (B = 64 chains, ``max_deg`` = 80) and its fusion (k = 8).

    At first each swap gathered 16 arrays of (B, ``max_deg``): four net
    entries per neighbour, each in the two float32 halves of an emulated
    float64; the two adjacency rows in two u32 halves each, being int64;
    the two mask rows; the two rows of the neighbours' nodes.  Now 4: the
    adjacency rows and the neighbours' nodes, all int32, with no 64-bit
    integer left in the program; the net distances come as two rows of
    the table per chain, from the neighbours' histogram over nodes."""
    ba, _ = flagship
    B, k = 64, 8
    width = ba.adj.shape[1]
    assert width == 80
    text = _compile(_jax_anneal_fn(k), _scan_args(ba, B, 2000, k), one_chip).as_text()
    assert not re.search(r"\b[su]64\[", text)
    wide = re.findall(
        rf"=\s*(\w+)\[{B},{width}\]\{{[^}}]*\}}\s+gather\(", text
    )
    assert "u32" not in wide and "pred" not in wide
    assert 0 < len(wide) <= 4 * k, sorted(wide)


@pytest.mark.xfail(
    strict=True,
    reason="the fused Pallas scorer holds float64 accumulators and outputs "
    "and uses gather/scatter indexing, which the Mosaic TPU lowering "
    "refuses (ROADMAP Speed item 3); a 32-bit rewrite must flip this",
)
def test_fused_kernel_compiles_for_v5e(flagship, one_chip, no_persistent_cache):
    ba, tm = flagship
    base, tp_arrays = _padded_inputs(ba, tm)
    fn = _fused_fn(
        ba.n_nodes, max(tm.n_racks, 1), max(tm.ack.n_comp_edges, 1),
        tm.n_combos, tm.ack, tm.thrash_factor, tm.source_bound, tm.sink_rate,
        DEFAULT_BLOCK_B, True, False,
    )
    P = _batch(ba, chip_smoke.BATCH).astype(np.int32)
    _compile(fn, (P,) + base + tp_arrays, one_chip)
