"""The plain fluid re-score (``reference_tp.py``) against the program's own
fluid solver, and a throughput-objective cell run through the unmodified
harness, drivers and checks on the CPU at tiny sizes."""

from __future__ import annotations

import copy
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmarks.chip import checks, control, harness, model, reference_tp, tiny, traffic

ROOT = Path(__file__).resolve().parents[2]
NODES = [f"r{r}n{n}" for r in range(2) for n in range(4)]

#: A solve of the program's that meets its stopping rule lies within 1e-9 of
#: the fixed point (``reference_tp.TOLERANCE`` explains why); the sums of the
#: two sides differ in order only, some 1e-15.
SOLVE_TOL = reference_tp.TOLERANCE
#: The program's joint rounds stop once no tenant's λ moves by 1e-6 of the
#: largest λ; each tenant then lies within ten times that of the fixed point.
JOINT_TOL = 1e-5


#: What the fluid model needs of a component, stated in every test
#: configuration: 10 CPU points at 1,000 tuples/s, 100-byte tuples, one
#: tuple out per tuple in (the program's fallbacks where a payload is silent,
#: so both sides read the same numbers).
STATED = {"cpu_cost_per_tuple": 0.01, "tuple_bytes": 100.0, "emit_ratio": 1.0}


def _comp(cid, p, spout=False, **kw):
    return {"id": cid, "is_spout": spout, "parallelism": p, "memory_load_mb": 128.0,
            "cpu_load": 10.0, **STATED, **kw}


def _stated(cfg, pending):
    """``cfg`` with every tenant's spout pending and every component's
    fluid numbers stated."""
    for t in cfg["tenants"]:
        t["max_spout_pending"] = pending
        for c in t["components"]:
            c.update(STATED)
    return cfg


def _config(tenants, memory_mb=4096.0):
    """2 racks x 4 nodes at Storm's supervisor defaults (400 CPU points)."""
    return {
        "net_distance": {"same_node": 0.5, "same_rack": 1.0, "cross_rack": 2.0},
        "cluster": {"racks": 2, "nodes_per_rack": 4, "cpu": 400.0, "memory_mb": memory_mb,
                    "bandwidth": 100.0},
        "tenants": tenants,
    }


def _chain(tid, pending, p=4, **kw):
    return {"id": tid, "max_spout_pending": pending,
            "components": [_comp(f"c{i}", p, i == 0, **kw) for i in range(4)],
            "edges": [{"src": f"c{i}", "dst": f"c{i + 1}"} for i in range(3)]}


def _star(tid, pending):
    """Two spouts into a hub, the hub into two sinks (R-Storm's Star)."""
    return {"id": tid, "max_spout_pending": pending,
            "components": [_comp("s0", 4, True), _comp("s1", 4, True), _comp("hub", 4),
                           _comp("o0", 4), _comp("o1", 4)],
            "edges": [{"src": "s0", "dst": "hub"}, {"src": "s1", "dst": "hub"},
                      {"src": "hub", "dst": "o0"}, {"src": "hub", "dst": "o1"}]}


def _place(tenant, rule):
    out, j = {}, 0
    for c in tenant["components"]:
        for i in range(c["parallelism"]):
            out[f"{tenant['id']}/{c['id']}[{i}]"] = rule(c["id"], j)
            j += 1
    return out


def _program(cfg, placements):
    """The program's joint fluid solve of the tenants, by topology id."""
    from repro.api import SchedulingPayload
    from repro.core.assignment import Assignment
    from repro.stream import Simulator

    scheduled = []
    for k, t in enumerate(cfg["tenants"]):
        payload = SchedulingPayload.from_dict(model.payload_dict(cfg, k, {"name": "rstorm"}))
        topo = payload.topology.to_topology()
        scheduled.append((topo, Assignment(topo.id, placements=dict(placements[t["id"]]))))
    cluster = payload.cluster.to_cluster()
    return {tid: r.sink_throughput for tid, r in Simulator(cluster).run_many(scheduled).items()}


def _mine(cfg, placements, dtype=np.float64):
    tenant_of = {t["id"]: k for k, t in enumerate(cfg["tenants"])}
    return reference_tp.sink_throughputs(cfg, placements, tenant_of, dtype=dtype)


def _acked_chain():
    cfg = _config([_chain("a", pending=8)])
    return cfg, {"a": _place(cfg["tenants"][0], lambda cid, j: NODES[j % 8])}


def _cpu_star():
    cfg = _config([_star("s", pending=100_000)])
    hub_on_one_node = _place(cfg["tenants"][0], lambda cid, j: "r0n0" if cid == "hub" else NODES[1 + j % 7])
    return cfg, {"s": hub_on_one_node}


def _bandwidth():
    cfg = _config([_chain("b", pending=100_000, tuple_bytes=20_000.0)])
    return cfg, {"b": _place(cfg["tenants"][0], lambda cid, j: NODES[j % 8])}


def _thrashed():
    """Five 128 MB tasks on a 512 MB node."""
    cfg = _config([_chain("t", pending=100_000)], memory_mb=512.0)
    five_on_one_node = _place(cfg["tenants"][0], lambda cid, j: "r0n0" if j < 5 else NODES[1 + j % 7])
    return cfg, {"t": five_on_one_node}


# (case, the mechanism that binds in the program's solve)
SINGLE = [(_acked_chain, "ack"), (_cpu_star, "cpu"), (_bandwidth, "bandwidth"), (_thrashed, "cpu")]


@pytest.mark.parametrize("case,binding", SINGLE, ids=[c.__name__.strip("_") for c, _ in SINGLE])
def test_reference_tp_matches_the_programs_solve(case, binding):
    from repro.api import SchedulingPayload
    from repro.core.assignment import Assignment
    from repro.stream import Simulator

    cfg, placements = case()
    prog = _program(cfg, placements)
    mine = _mine(cfg, placements)
    (tid,) = placements
    assert mine[tid] > 0.0
    assert abs(prog[tid] - mine[tid]) <= SOLVE_TOL * mine[tid], (prog, mine)
    payload = SchedulingPayload.from_dict(model.payload_dict(cfg, 0, {"name": "rstorm"}))
    run = Simulator(payload.cluster.to_cluster()).run(
        payload.topology.to_topology(), Assignment(tid, placements=dict(placements[tid]))
    )
    assert run.binding == binding
    assert reference_tp.alone(cfg, tid, placements[tid], 0) == mine[tid]
    if case is _thrashed:
        assert run.thrashed_nodes == ["r0n0"]


def test_reference_tp_matches_the_programs_joint_solve():
    """Two tenants on shared nodes and racks: an ack-bound chain beside a
    bandwidth-bound one, each slowed by the other's NIC load."""
    cfg = _config([_chain("a", pending=8), _chain("b", pending=100_000, tuple_bytes=20_000.0)])
    placements = {
        "a": _place(cfg["tenants"][0], lambda cid, j: NODES[j % 8]),
        "b": _place(cfg["tenants"][1], lambda cid, j: NODES[(j + 3) % 8]),
    }
    prog, mine = _program(cfg, placements), _mine(cfg, placements)
    largest = max(mine.values())
    for tid in placements:
        assert abs(prog[tid] - mine[tid]) <= JOINT_TOL * largest, (prog, mine)
        # Sharing costs each tenant throughput against running alone.
        assert mine[tid] < reference_tp.alone(cfg, tid, placements[tid], "ab".index(tid))
    assert reference_tp.cluster_throughput(cfg, placements, {"a": 0, "b": 1}) == pytest.approx(
        sum(mine.values()), rel=1e-15
    )


def test_reference_tp_is_the_fixed_point_of_the_programs_model():
    """Near CPU saturation the ack bound falls steeply with λ; whatever the
    program's damped iteration returns there, the re-score's λ is the fixed
    point λ = min(hard bounds, pending / L(λ)) of the program's own model."""
    from repro.api import Nimbus, SchedulingPayload
    from repro.core.assignment import Assignment
    from repro.stream import Simulator
    from repro.stream.simulator import _TopologyLoad

    cfg = _stated(tiny.config("chain1000-n256"), 1000)
    payload = traffic.payload(cfg, 0, {"name": "rstorm"})
    placement = dict(Nimbus().plan(payload).placements)
    tid = cfg["tenants"][0]["id"]
    sp = SchedulingPayload.from_dict(model.payload_dict(cfg, 0, {"name": "rstorm"}))
    topo, cluster = sp.topology.to_topology(), sp.cluster.to_cluster()
    sim = Simulator(cluster)
    load = _TopologyLoad(topo, Assignment(tid, placements=placement), cluster)
    hard = min(load.source_bound(), sim._bandwidth_bound(load, []), sim._cpu_bound(load, [], []))
    sink_rate = sum(load.task_rate[t.id] for s in topo.sinks() for t in s.tasks(tid))
    lam = reference_tp.alone(cfg, tid, placement, 0) / sink_rate
    target = min(hard, load.pending() / sim._latency(load, lam, [], []))
    assert lam < hard
    assert abs(target - lam) <= 1e-9 * lam


@pytest.mark.parametrize("name", ["chain1000-n256", "linear-pair-n256"])
def test_a_float32_solve_fails_the_tolerance(name):
    """The control: the re-score itself in float32, on greedy's placements of
    each cell's tenants at full size (with the fluid numbers the tests
    state), misses the float64 fixed point by more than ``TOLERANCE``."""
    from repro.api import Nimbus

    cfg = _stated(model.load_config(name), 1000)
    for k, t in enumerate(cfg["tenants"]):
        placement = dict(Nimbus().plan(traffic.payload(cfg, k, {"name": "rstorm"})).placements)
        exact = reference_tp.alone(cfg, t["id"], placement, k)
        low = reference_tp.alone(cfg, t["id"], placement, k, dtype=np.float32)
        assert abs(low - exact) / exact > reference_tp.TOLERANCE


@pytest.mark.parametrize("field", reference_tp.TENANT_FIELDS + reference_tp.COMPONENT_FIELDS)
def test_reference_tp_refuses_an_unstated_number(field):
    """Storm's defaults state no spout pending, tuple size, emit ratio or
    tuple rate, so the re-score takes none of its own: a configuration that
    leaves one out is refused, naming it."""
    cfg, placements = _acked_chain()
    target = cfg["tenants"][0]
    if field not in reference_tp.TENANT_FIELDS:
        target = target["components"][2]
    del target[field]
    with pytest.raises(reference_tp.Unstated, match=field):
        _mine(cfg, placements)


def test_reference_tp_imports_nothing_of_the_program():
    code = (
        "import sys; import benchmarks.chip.reference_tp; "
        "bad = sorted(m for m in sys.modules if m == 'repro' or m.startswith('repro.')); "
        "assert not bad, bad"
    )
    env = {**os.environ, "PYTHONPATH": str(ROOT)}
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def _throughput_cell():
    """The flagship chain's tiny shape with 8 tuples in flight per spout
    task, so that the ack loop binds far from saturation, under the netcost
    cell's traffic with ``objective: throughput``."""
    cfg = _stated(tiny.config("chain1000-n256"), 8)
    spec = tiny.spec("plan-netcost")
    spec["scheduler"]["kwargs"]["objective"] = "throughput"
    cell = {"name": "chain1000.plan-throughput", "config": "chain1000-n256",
            "traffic": "plan-throughput", "chips": 1}
    return cell, cfg, spec


def _bench(cell):
    """The benchmark with the entry a throughput cell would add for its
    quality: ``placed_tp`` in that cell."""
    bench = copy.deepcopy(harness.load_benchmark())
    bench["end_to_end"].append({"name": "placed_tp", "unit": "tuples/s", "better": "higher",
                                "bound": 0.01, "source": "host_clock",
                                "workloads": [cell["name"]]})
    return bench


def _read(cell, cfg, spec, fault, seed=2**31 + 61):
    (r,) = control.read(cell, cfg, spec, fault, [seed], 0.5, _bench(cell),
                        tiny.peaks(), jax.devices(), log=lambda *a: None)
    return r


def test_a_throughput_cell_runs_through_the_unmodified_harness():
    cell, cfg, spec = _throughput_cell()
    sound = _read(cell, cfg, spec, "none")
    assert sound["correct"], sound
    assert sound["compared"] == {"invalid": 0.0, "worse": 0.0, "cost_gap": 0.0}
    assert sound["metrics"]["placed_tp"] > 0.0
    # Scrambled after the search, the decided chain loses throughput.
    broken = _read(cell, cfg, spec, "scramble")
    assert not broken["correct"], broken
    assert broken["compared"]["worse"] > reference_tp.TOLERANCE
    assert broken["metrics"]["placed_tp"] < sound["metrics"]["placed_tp"]


def test_throughput_judge_counts_a_loss_only_beyond_the_tolerance():
    cell, cfg, spec = _throughput_cell()
    driver = traffic.make_driver(cfg, spec, 5)
    greedy, _ = driver.greedy(None)
    tid = cfg["tenants"][0]["id"]
    base = reference_tp.alone(cfg, tid, greedy[tid], 0)
    # Every hop of the chain crosses racks.
    worse = _place(cfg["tenants"][0], lambda cid, j: NODES[4 * (int(cid[1:]) % 2) + j % 4])
    assert reference_tp.alone(cfg, tid, worse, 0) < base
    for placement, loses in ((greedy[tid], False), (worse, True)):
        d = traffic.Decision(seconds=1.0, placements={tid: placement}, decided=tid,
                             tenant_of={tid: 0})
        judged = checks.judge(cfg, driver, [d])
        assert (judged["numbers"]["worse"] > 0.0) == loses
        assert judged["metrics"]["placed_tp"] == reference_tp.alone(cfg, tid, placement, 0)
        assert "tp_gap" not in judged["numbers"]


class _FixedGreedy(traffic.Driver):
    """A throughput request judged against a greedy answer given up front."""

    def __init__(self, cfg, greedy):
        super().__init__(cfg, {"scheduler": {"kwargs": {"objective": "throughput"}}}, 0)
        self._greedy = greedy

    def greedy(self, d):
        return self._greedy, 0


def test_throughput_judge_ignores_network_cost():
    """The star's hub node caps its throughput; moving the sinks to the
    other rack costs network distance and no throughput."""
    cfg, placements = _cpu_star()
    near = placements["s"]
    far = {t: (f"r1n{int(t[-2]) % 4}" if "/o" in t else n) for t, n in near.items()}
    assert reference_tp.alone(cfg, "s", far, 0) == pytest.approx(
        reference_tp.alone(cfg, "s", near, 0), rel=1e-12
    )
    d = traffic.Decision(seconds=1.0, placements={"s": far}, decided="s", tenant_of={"s": 0})
    judged = checks.judge(cfg, _FixedGreedy(cfg, {"s": near}), [d])
    assert judged["numbers"]["worse"] == 0.0
    assert judged["metrics"]["placed_netcost"] > _FixedGreedy(cfg, None).netcost(
        {"s": model.topology_from_spec(cfg["tenants"][0])}, model.cluster_from_config(cfg), {"s": near}
    )


REPORTING_DRIVER = '''
import time
from benchmarks.chip import model, traffic
from benchmarks.chip.drivers import plan


class Driver(plan.Driver):
    """``plan`` with the program's simulated sink throughput reported."""

    def prepare(self, i):
        from repro.api import SchedulingPayload

        scheduler = traffic.with_seed(self.spec["scheduler"], traffic.request_seed(self.seed, i))
        d = model.payload_dict(self.cfg, self.tenant, scheduler)
        d["settings"] = {"simulate": True}
        return SchedulingPayload.from_dict(d)

    def decide(self, i, payload):
        t0 = time.perf_counter()
        out = self.nimbus.plan(payload)
        return traffic.Decision(
            seconds=time.perf_counter() - t0,
            placements={self.tid: dict(out.placements)},
            decided=self.tid,
            tenant_of={self.tid: self.tenant},
            reported_cost=float(out.network_cost),
            reported_tp=float(out.sim.sink_throughput),
            unplaced=len(out.unassigned),
        )
'''


FLOAT32_DRIVER = '''
import numpy as np
from benchmarks.chip import reference_tp
from benchmarks.chip.drivers import plan


class Driver(plan.Driver):
    """The control: ``plan`` reporting the plain re-score of its decided
    placement, computed in float32."""

    def decide(self, i, payload):
        d = super().decide(i, payload)
        d.reported_tp = reference_tp.alone(
            self.cfg, d.decided, d.placements[d.decided], self.tenant, d.dead, dtype=np.float32
        )
        return d
'''


def _read_reporting(tmp_path, source, fault):
    """A throughput cell whose driver, one new file, reports throughput."""
    from benchmarks.chip import drivers

    (tmp_path / "plan_reporting.py").write_text(source)
    drivers.__path__.append(str(tmp_path))
    try:
        cell, cfg, spec = _throughput_cell()
        return _read(cell, cfg, dict(spec, driver="plan_reporting"), fault)
    finally:
        drivers.__path__.remove(str(tmp_path))
        sys.modules.pop("benchmarks.chip.drivers.plan_reporting", None)


@pytest.mark.parametrize("fault,correct", [("none", True), ("misreport_tp", False)])
def test_a_reported_throughput_is_checked(tmp_path, fault, correct):
    """A driver that carries the program's reported throughput is one new
    file; ``tp_gap`` holds the report to the re-score."""
    r = _read_reporting(tmp_path, REPORTING_DRIVER, fault)
    assert r["correct"] == correct, r
    assert set(r["compared"]) == {"invalid", "worse", "cost_gap", "tp_gap"}
    if correct:
        assert r["compared"]["tp_gap"] <= SOLVE_TOL
    else:
        assert r["compared"]["tp_gap"] > 10 * checks.LIMITS["tp_gap"]


def test_a_float32_report_is_not_correct(tmp_path):
    """The float32 control through the harness and the checks: a report
    made by the re-score in float32 fails ``tp_gap``."""
    r = _read_reporting(tmp_path, FLOAT32_DRIVER, "none")
    assert not r["correct"], r
    assert r["compared"]["tp_gap"] > checks.LIMITS["tp_gap"]
    assert r["compared"]["worse"] == 0.0


TOY_READER = '''
def read(ctx):
    """Swap proposals of the traced throughput searches."""
    if not ctx.decisions:
        return None
    return float(sum(
        chains * steps
        for d in ctx.decisions
        for _, chains, steps, objective in ctx.driver.searches(d)
        if objective == "throughput"
    ))
'''


def test_a_layer_reader_counts_work_from_the_traced_decisions(tmp_path):
    """A per-layer metric of a new kernel is one new reader file that counts
    its work from ``ctx.decisions`` and ``ctx.driver``."""
    from benchmarks.chip import layers

    (tmp_path / "tp_proposals.py").write_text(TOY_READER)
    layers.__path__.append(str(tmp_path))
    cell, cfg, spec = _throughput_cell()
    bench = copy.deepcopy(harness.load_benchmark())
    bench["per_layer"].append({"name": "tp_proposals", "unit": "count", "better": "higher",
                               "source": "program_counter", "layer": "anneal",
                               "moves": "decision_s", "workloads": [cell["name"]]})
    lines = []
    try:
        res = harness.run_cell(
            cell, cfg, spec, seed=2**31 + 71, seconds=0.5, trace=True,
            t_start=time.perf_counter(), bench=bench, peaks=tiny.peaks(),
            devices=jax.devices(), log=lines.append,
        )
    finally:
        layers.__path__.remove(str(tmp_path))
        sys.modules.pop("benchmarks.chip.layers.tp_proposals", None)
    assert res["correct"], res
    (traced,) = [ln for ln in lines if ln.startswith("traced ")]
    n = int(traced.split()[1])
    kw = spec["scheduler"]["kwargs"]
    assert res["metrics"]["tp_proposals"]["value"] == n * kw["n_chains"] * kw["steps"]
