"""The per-layer metrics read from the program's own spans (``spans.py`` and
its readers under ``layers/``): their values on a synthetic profile log, and
a traced run of each cell on the CPU at tiny sizes that reports them."""

from __future__ import annotations

import importlib
import time

import jax
import pytest

from benchmarks.chip import harness, tiny
from benchmarks.chip.harness import LayerContext

CELLS = ["chain1000.plan-netcost", "linear-pair.churn"]
#: The per-layer metrics read from the program's own spans.
SPAN_METRICS = ("api_self_s", "seed_s", "pick_s", "anneal_dispatch_s", "device_wait_s")

#: A plan decision's span tree as (name, wall seconds, children).
DECISION = ("nimbus.plan", 1.0, [
    ("nimbus.schedule", 0.9, [
        ("search.schedule", 0.89, [
            ("search.seed", 0.2, []),
            ("search.inits", 0.1, []),
            ("search.anneal", 0.5, [
                ("anneal.dispatch", 0.05, []),
                ("device.wait", 0.4, []),
                ("device.fetch", 0.01, []),
            ]),
            ("search.evaluate", 0.03, [
                ("device.wait", 0.02, []),
                ("device.fetch", 0.005, []),
            ]),
            ("search.pick", 0.04, []),
        ]),
    ]),
])
#: Churn's untimed greedy plan: no search, so no decision.
GREEDY = ("nimbus.plan", 0.3, [("nimbus.schedule", 0.25, [])])


def _synthetic_log():
    """Two decisions with a greedy plan between them, as the profile log
    holds them: children close, and so appear, before their parents."""
    from repro.obs import ProfiledSpan

    log, seq = [], iter(range(1000))

    def close(node, parent):
        name, wall_s, kids = node
        me = next(seq)
        for kid in kids:
            close(kid, me)
        log.append(ProfiledSpan(me, parent, name, {}, {}, wall_s))

    for node in (DECISION, GREEDY, DECISION):
        close(node, None)
    return log


@pytest.mark.parametrize(
    "reader, value",
    [
        ("api_self_s", 0.1),
        ("seed_s", 0.3),
        ("pick_s", 0.04),
        ("anneal_dispatch_s", 0.05),
        ("device_wait_s", 0.435),
    ],
)
def test_span_readers_on_a_synthetic_log(reader, value):
    read = importlib.import_module(f"benchmarks.chip.layers.{reader}").read
    log = _synthetic_log()
    assert read(LayerContext(None, 0, 2, {}), log) == pytest.approx(value)
    # The greedy plan between the decisions is no decision.
    assert read(LayerContext(None, 0, 1, {}), log) == pytest.approx(value)
    assert read(LayerContext(None, 0, 3, {}), log) is None
    assert read(LayerContext(None, 0, 1, {}), log[:3]) is None
    assert read(LayerContext(None, 0, 0, {}), log) is None


@pytest.mark.parametrize("cell_name", CELLS)
def test_traced_run_reads_the_programs_spans(cell_name):
    cell = tiny.cell(cell_name)
    cfg, spec = tiny.config(cell["config"]), tiny.spec(cell["traffic"])
    lines = []
    res = harness.run_cell(
        cell, cfg, spec, seed=2**31 + 7, seconds=0.5, trace=True, t_start=time.perf_counter(),
        bench=harness.load_benchmark(), peaks=tiny.peaks(), devices=jax.devices(),
        log=lines.append,
    )
    assert res["correct"], res
    assert res["metrics"]["compiles_in_window"]["value"] == 0.0
    spans = [res["metrics"][m]["value"] for m in SPAN_METRICS]
    assert all(v > 0.0 for v in spans), res["metrics"]
    # The five spans lie inside the decisions they are read from.
    (traced,) = [ln for ln in lines if ln.startswith("traced ")]
    mean = float(traced.rsplit("their mean ", 1)[1].split()[0])
    assert sum(spans) <= mean
