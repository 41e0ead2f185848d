"""The harness's whole run on the CPU at tiny sizes, the chip check skipped:
sound runs come out correct, and a timed path broken underneath comes out
not correct, once for each fault the cell can have."""

from __future__ import annotations

import sys
import time

import jax
import pytest

from benchmarks.chip import control, harness, tiny, traffic

#: Faults each cell can have (``control.py``): every cell's decisions can be
#: altered where they are produced (``overfill``, the control) or scrambled,
#: and the network cost they report can be misreported.
CELLS = ["chain1000.plan-netcost", "linear-pair.churn"]
CASES = [(c, f) for c in CELLS for f in ("overfill", "scramble", "miscount")]


def _run(cell_name: str, fault: str, seed: int = 2**31 + 99):
    cell = tiny.cell(cell_name)
    cfg, spec = tiny.config(cell["config"]), tiny.spec(cell["traffic"])
    bench = harness.load_benchmark()
    (reading,) = control.read(
        cell, cfg, spec, fault, [seed], 0.5, bench, tiny.peaks(), jax.devices(),
        log=lambda *a: None,
    )
    return reading


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = _run(cell, "none")
    assert r["correct"], r
    assert r["attempted"] >= 1
    assert all(v == 0.0 for v in r["compared"].values()), r


@pytest.mark.parametrize("cell,fault", CASES)
def test_broken_timed_path_is_not_correct(cell, fault):
    r = _run(cell, fault)
    assert not r["correct"], r
    assert "error" not in r, r
    assert any(v > 0.0 for v in r["compared"].values()), r


def test_result_line_has_the_contract_keys():
    cell = tiny.cell("linear-pair.churn")
    cfg, spec = tiny.config(cell["config"]), tiny.spec(cell["traffic"])
    res = harness.run_cell(
        cell, cfg, spec, seed=3, seconds=0.5, trace=False, t_start=time.perf_counter(),
        bench=harness.load_benchmark(), peaks=tiny.peaks(), devices=jax.devices(),
        log=lambda *a: None,
    )
    assert list(res)[-1] == "compared"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res
    assert set(res["metrics"]) == {"decision_s", "decision_p95_s", "placed_netcost", "setup_s"}
    assert res["attempted"] % 2 == 0  # the churn window closes on whole cycles
    assert set(res["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}


def test_float32_search_places_bit_for_bit_as_float64():
    """The program's lower-precision path, a netcost search without its
    float64 scope, gives the very placements of the sound path: the reason
    the control of these cells breaks a stated guarantee instead."""
    cfg, spec = tiny.config("chain1000-n256"), tiny.spec("plan-netcost")
    runs = {}
    for fault in ("none", "float32"):
        with control.planted(fault):
            d = traffic.make_driver(cfg, spec, 2**31 + 3)
            runs[fault] = [d.decide(i, d.prepare(i)).placements for i in range(2)]
    assert runs["none"] == runs["float32"]


NEW_DRIVER = '''
import time
from benchmarks.chip import traffic


class Driver(traffic.Driver):
    """Greedy R-Storm plans of tenant 0, closed loop."""

    def __init__(self, cfg, spec, seed):
        from repro.api import Nimbus

        super().__init__(cfg, spec, seed)
        self.nimbus, self.tid = Nimbus(), cfg["tenants"][0]["id"]

    def prepare(self, i):
        return traffic.payload(self.cfg, 0, self.spec["scheduler"])

    def decide(self, i, payload):
        t0 = time.perf_counter()
        plan = self.nimbus.plan(payload)
        return traffic.Decision(
            seconds=time.perf_counter() - t0,
            placements={self.tid: dict(plan.placements)},
            decided=self.tid,
            tenant_of={self.tid: 0},
            reported_cost=float(plan.network_cost),
            unplaced=len(plan.unassigned),
        )

    def greedy(self, d):
        return d.placements, d.unplaced

    def searches(self, d):
        return []
'''


def test_a_new_driver_is_a_new_file(tmp_path):
    """A kind of traffic the harness has never seen runs from one new
    driver file and a traffic spec that names it; no existing file changes."""
    from benchmarks.chip import drivers

    (tmp_path / "greedy_only.py").write_text(NEW_DRIVER)
    drivers.__path__.append(str(tmp_path))
    try:
        cell = {"name": "chain1000.greedy-only", "config": "chain1000-n256", "traffic": "greedy-only", "chips": 1}
        spec = {"driver": "greedy_only", "scheduler": {"name": "rstorm"}}
        res = harness.run_cell(
            cell, tiny.config("chain1000-n256"), spec, seed=5, seconds=0.2, trace=False,
            t_start=time.perf_counter(), bench=harness.load_benchmark(), peaks=tiny.peaks(),
            devices=jax.devices(), log=lambda *a: None,
        )
    finally:
        drivers.__path__.remove(str(tmp_path))
        sys.modules.pop("benchmarks.chip.drivers.greedy_only", None)
    assert res["correct"], res
    assert res["attempted"] >= 1
    assert set(res["metrics"]) >= {"decision_s", "setup_s"}
