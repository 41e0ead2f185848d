"""Tests of the trace -> metrics reduction (``trace.py``) and its readers."""

from __future__ import annotations

from pathlib import Path

import pytest

from benchmarks.chip import trace
from benchmarks.chip.harness import LayerContext
from benchmarks.chip.layers import anneal_device_s, anneal_roofline, device_idle, score_device_s

RECORDED = Path(__file__).resolve().parent / "testdata" / "tiny_churn_2decisions.xplane.pb.gz"


def test_union_and_merge_of_intervals():
    ivs = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (10.0, 10.0)]
    assert trace.union_length(ivs) == pytest.approx(3.0)
    assert trace.merged(ivs) == [(0.0, 2.0), (3.0, 4.0), (10.0, 10.0)]
    assert trace.union_length([]) == 0.0


def test_module_groups():
    assert trace.module_group("jit_anneal(1234)") == "jit_anneal"
    assert trace.module_group("jit_evaluate.7") == "jit_evaluate"
    assert trace.module_group("jit_evaluate") == "jit_evaluate"


def test_reduce_synthetic_window():
    events = {
        "devices": [
            {
                "ops": [(1.0, 1.5), (1.4, 2.0), (5.0, 6.0)],
                "modules": [("jit_anneal(1)", 1.0, 2.0), ("jit_evaluate(2)", 5.0, 6.0)],
            }
        ],
        "host": [("reset", 0.0, 0.5), ("decision", 0.5, 4.0), ("decision", 4.5, 8.0)],
    }
    r = trace.reduce(events)
    assert r["window_s"] == pytest.approx(8.0)
    assert r["busy_s"] == pytest.approx(2.0)
    assert r["idle_share"] == pytest.approx(0.75)
    assert r["modules"]["jit_anneal"] == {"seconds": pytest.approx(1.0), "count": 1}
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["decision", pytest.approx(3.0)]  # 2.0 -> 5.0, the longest
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert sum(g[1] for g in gaps) == pytest.approx(8.0 - 2.0)
    ctx = LayerContext(r, 0, 2, 819e9 * 0.25, {"hbm_bytes_per_s": 819e9})
    assert device_idle.read(ctx) == pytest.approx(0.75)
    assert anneal_device_s.read(ctx) == pytest.approx(0.5)
    assert score_device_s.read(ctx) == pytest.approx(0.5)
    assert anneal_roofline.read(ctx) == pytest.approx(25.0)


def test_readers_find_nothing_without_a_trace():
    ctx = LayerContext(None, 0, 3, 1e6, {"hbm_bytes_per_s": 819e9})
    for reader in (device_idle, anneal_device_s, anneal_roofline, score_device_s):
        assert reader.read(ctx) is None


def test_recorded_chip_trace():
    """Two churn decisions of the tiny configuration, traced on one TPU v5e
    through the program's own path: the reduction finds the
    device plane, the annealer's and the scorers' modules, and an idle share
    inside (0, 1)."""
    events = trace.load_events(str(RECORDED))
    assert len(events["devices"]) == 1
    assert {n for n, _, _ in events["host"]} == {"decision", "reset"}
    r = trace.reduce(events)
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert 0.0 < r["idle_share"] < 1.0
    assert r["modules"]["jit_anneal"]["count"] >= 2
    assert r["modules"]["jit_evaluate"]["count"] >= 2
    module_s = sum(a["seconds"] for a in r["modules"].values())
    assert r["busy_s"] <= module_s * 1.0001
