"""CPU tests of the chip benchmark's yardsticks, at tiny sizes."""

from __future__ import annotations

import importlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import costs, model, reference, tiny, traffic

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _program(cfg, tenant=0, dead=()):
    from repro.api import SchedulingPayload

    payload = SchedulingPayload.from_dict(model.payload_dict(cfg, tenant, {"name": "rstorm"}))
    topology = payload.topology.to_topology()
    cluster = payload.cluster.to_cluster()
    for nid in dead:
        cluster.fail_node(nid)
    return payload, topology, cluster


@pytest.mark.parametrize("cell", ["chain1000.plan-netcost", "linear-pair.churn"])
def test_traffic_is_seeded_and_repeatable(cell):
    c = tiny.cell(cell)
    cfg, spec = tiny.config(c["config"]), tiny.spec(c["traffic"])

    def decisions(seed):
        d = traffic.make_driver(cfg, spec, seed)
        return [d.decide(i, d.prepare(i)) for i in range(3)]

    a, b = decisions(2**31 + 17), decisions(2**31 + 17)
    assert [x.placements for x in a] == [x.placements for x in b]
    seeds = {traffic.request_seed(s, i) for s in (1, 2**31 + 17, 2**40) for i in range(4)}
    assert len(seeds) == 12 and all(0 <= s < 2**31 for s in seeds)


@pytest.mark.parametrize("name", ["chain1000-n256", "linear-pair-n256"])
def test_reference_netcost_matches_the_numpy_scorer(name):
    from repro.core import BatchArena, PlacementArena
    from repro.core.search.objective import evaluate_batch

    cfg = tiny.config(name)
    for tenant in range(len(cfg["tenants"])):
        payload, topology, cluster = _program(cfg, tenant)
        tids = [t.id for t in topology.all_tasks()]
        nodes = sorted(cluster.nodes)
        rng = np.random.Generator(np.random.Philox(7 + tenant))
        seed_placement = {t: nodes[int(k)] for t, k in zip(tids, rng.integers(len(nodes), size=len(tids)))}
        ba = BatchArena.from_arena(PlacementArena(cluster, topology), topology, seed_placement)
        P = rng.integers(len(nodes), size=(16, ba.n_tasks))
        ev = evaluate_batch(ba, P, backend="numpy")
        mine = model.topology_from_spec(cfg["tenants"][tenant])
        plain = model.cluster_from_config(cfg)
        for row in range(P.shape[0]):
            placement = ba.decode(P[row])
            assert reference.network_cost(mine, plain, placement, cfg["net_distance"]) == ev.net[row]
            over = reference.violations([mine], plain, placement)["over"]
            assert (over > 0) == bool(ev.violation[row] > 0)


@pytest.mark.parametrize("name", ["chain1000-n256", "linear-pair-n256"])
def test_anneal_byte_count_is_a_lower_bound(name):
    """The least bytes per proposal never exceed what the program's scan
    reads for one proposal, and the degrees match its adjacency."""
    from repro.core import BatchArena, PlacementArena

    cfg = tiny.config(name)
    for tenant, spec in enumerate(cfg["tenants"]):
        payload, topology, cluster = _program(cfg, tenant)
        nodes = sorted(cluster.nodes)
        placement = {t.id: nodes[k % len(nodes)] for k, t in enumerate(topology.all_tasks())}
        ba = BatchArena.from_arena(PlacementArena(cluster, topology), topology, placement)
        degs = costs.task_degrees(spec)
        assert sorted(degs) == sorted(ba.adj_mask.sum(axis=1).tolist())
        width, dh = ba.adj.shape[1], ba.avail.shape[1]
        program = (
            2 * 4  # P[b, i], P[b, j] (int32 chains)
            + 2 * width * (ba.adj.itemsize + ba.adj_mask.itemsize)  # adjacency rows
            + 2 * width * 4  # neighbours' node ids
            + 2 * 2 * width * ba.net.itemsize  # net-distance entries
            + 2 * 2 * dh * ba.avail.itemsize  # capacity and use rows
        )
        least = costs.bytes_per_proposal(spec, hard_dims=dh)
        assert 0 < least <= program
    assert costs.anneal_bytes([(cfg["tenants"][0], 4, 10)]) == 40 * costs.bytes_per_proposal(cfg["tenants"][0])


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "chain1000.plan-netcost",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_unknown_device_kind_is_an_error():
    from benchmarks.chip.harness import NoChip, load_peaks

    assert load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(NoChip):
        load_peaks("TPU v9 imaginary")


def test_benchmark_json_finds_every_file_by_name():
    """A cell, configuration or per-layer metric is added as files plus
    BENCHMARK.json entries; this pins the lookups the harness makes."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        driver = traffic.load_traffic(w["traffic"])["driver"]
        assert issubclass(importlib.import_module(f"benchmarks.chip.drivers.{driver}").Driver, traffic.Driver)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names)) and "setup_s" in names
    for m in bench["per_layer"]:
        reader = __import__(f"benchmarks.chip.layers.{m['name']}", fromlist=["read"])
        assert callable(reader.read)


def _pair_config():
    """Two tasks in a chain of two components on 2 racks x 2 nodes of
    256 MB; each task holds 128 MB."""
    comp = lambda cid: {"id": cid, "parallelism": 1, "memory_load_mb": 128.0, "cpu_load": 10.0}
    return {
        "net_distance": {"same_node": 0.5, "same_rack": 1.0, "cross_rack": 2.0},
        "cluster": {"racks": 2, "nodes_per_rack": 2, "cpu": 400.0, "memory_mb": 256.0, "bandwidth": 100.0},
        "tenants": [{"id": "t", "components": [comp("a"), comp("b")], "edges": [{"src": "a", "dst": "b"}]}],
    }


@pytest.mark.parametrize("b_node,cost", [("r0n0", 0.5), ("r0n1", 1.0), ("r1n0", 2.0)])
def test_reference_prices_a_pair_by_where_it_sits(b_node, cost):
    cfg = _pair_config()
    topo = model.topology_from_spec(cfg["tenants"][0])
    placement = {"t/a[0]": "r0n0", "t/b[0]": b_node}
    assert reference.network_cost(topo, model.cluster_from_config(cfg), placement, cfg["net_distance"]) == cost


class _FixedDriver(traffic.Driver):
    """Judges a decision against a greedy answer given up front."""

    def __init__(self, cfg, greedy, greedy_unplaced=0):
        super().__init__(cfg, {}, 0)
        self._greedy = (greedy, greedy_unplaced)

    def greedy(self, d):
        return self._greedy


@pytest.mark.parametrize("placement,unplaced,dead,broken", [
    # an unknown node, a rack of its own: also dearer than greedy's same rack
    ({"t/a[0]": "r9n9", "t/b[0]": "r0n1"}, 0, (), {"invalid", "worse"}),
    ({"t/a[0]": "r0n0", "t/b[0]": "r0n1"}, 0, ("r0n0",), {"invalid"}),  # a dead node
    ({"t/a[0]": "r0n0", "t/b[0]": "r0n0"}, 0, (), set()),  # 256 MB on 256 MB: full, not over
    ({"t/a[0]": "r0n0"}, 1, (), {"invalid"}),  # one task unplaced that greedy placed
    ({"t/a[0]": "r0n0", "t/b[0]": "r1n0"}, 0, (), {"worse"}),  # cross rack against greedy's same rack
])
def test_judge_counts_each_broken_guarantee(placement, unplaced, dead, broken):
    from benchmarks.chip import checks

    cfg = _pair_config()
    driver = _FixedDriver(cfg, {"t": {"t/a[0]": "r0n0", "t/b[0]": "r0n1"}})
    topo = model.topology_from_spec(cfg["tenants"][0])
    cost = reference.network_cost(topo, model.cluster_from_config(cfg), placement, cfg["net_distance"])
    d = traffic.Decision(
        seconds=1.0, placements={"t": placement}, decided="t", tenant_of={"t": 0},
        reported_cost=cost, dead=dead, unplaced=unplaced,
    )
    judged = checks.judge(cfg, driver, [d])
    assert {k for k, v in judged["numbers"].items() if v > checks.LIMITS[k]} == broken
    assert judged["failed"] == (1 if broken else 0)
