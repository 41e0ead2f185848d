"""Tier-1 tests for the deterministic observability plane (:mod:`repro.obs`).

Covers the four contracts the plane ships with:

* registry semantics — typed create-or-get metrics, exact percentiles,
  deterministic export ordering, JSON-safe records;
* the zero-cost disabled path — a disabled hub hands out shared inert
  singletons and retains **zero** state, even through a full DES run;
* one-code-path percentiles — ``DesReport`` and the JSONL export read the
  same ``Histogram`` objects, so their p50/p95/p99 are equal by identity;
* determinism goldens — a fixed-seed payload exports byte-identical JSONL
  across fresh control-plane runs (§6 micro topology and Yahoo PageLoad),
  and instrumentation never changes placements, reports, or traces.
"""

from __future__ import annotations

import json

import pytest

from repro.api import (
    ClusterSpec,
    DesSettings,
    Nimbus,
    ObsSettings,
    RebalanceEvent,
    RunSettings,
    ScenarioRunner,
    ScenarioSpec,
    SchedulerSpec,
    SchedulingPayload,
    SubmitEvent,
    TopologySpec,
    get_scheduler,
)
from repro.core.cluster import Cluster, emulab_cluster
from repro.obs import (
    DEFAULT_BUCKETS,
    NULL_HUB,
    NULL_METRIC,
    NULL_SPAN,
    Histogram,
    MetricsHub,
    get_hub,
)
from repro.obs.report import main as report_main
from repro.stream import topologies as T
from repro.stream.des import DesConfig, DesExecutor


def _has_jax() -> bool:
    try:
        import jax  # noqa: F401

        return True
    except Exception:
        return False


# --------------------------------------------------------------------------
# registry semantics
# --------------------------------------------------------------------------


def test_registry_create_or_get_and_typed_records():
    hub = MetricsHub()
    c = hub.counter("x.count", topology="t")
    c.inc()
    c.inc(2)
    assert hub.counter("x.count", topology="t") is c  # create-or-get
    assert hub.counter("x.count", topology="u") is not c  # labels key
    g = hub.gauge("x.rate")
    assert g.value is None
    g.set(3.5)
    s = hub.series("x.curve")
    s.append(0, 1.0)
    s.append(1, 2.0)
    recs = {(r["kind"], r["name"], json.dumps(r["labels"], sort_keys=True)): r
            for r in hub.records()}
    assert recs[("counter", "x.count", '{"topology": "t"}')]["value"] == 3
    assert recs[("gauge", "x.rate", "{}")]["value"] == 3.5
    assert recs[("series", "x.curve", "{}")]["points"] == [[0, 1.0], [1, 2.0]]


def test_histogram_exact_percentiles_and_buckets():
    h = Histogram(DEFAULT_BUCKETS)
    for v in range(1, 101):
        h.observe(float(v))
    p50, p95, p99 = h.percentiles()
    # Exact (interpolated) percentiles over retained values — not bucket
    # midpoints: that is the registry's "exact p50/p95/p99" contract.
    assert p50 == 50.5 and p95 == 95.05 and p99 == 99.01
    assert h.mean() == pytest.approx(50.5)
    rec = h.record()
    assert rec["count"] == 100
    assert rec["p99"] == 99.01
    assert sum(rec["bucket_counts"]) == 100
    empty = Histogram()
    assert empty.percentiles() == (None, None, None)
    assert empty.mean() == 0.0


def test_export_is_sorted_json_safe_and_stable():
    def build():
        hub = MetricsHub()
        hub.counter("b.second").inc(1)
        hub.counter("a.first", node="n2").inc(2)
        hub.counter("a.first", node="n1").inc(3)
        hub.series("c.mixed", step=3).append(0, 1.0)
        hub.series("c.mixed", step="x").append(0, 2.0)  # mixed label types
        with hub.span("outer", phase="p") as sp:
            sp.set(items=2)
            with hub.span("inner"):
                pass
        return hub

    a, b = build().to_jsonl(), build().to_jsonl()
    assert a == b  # deterministic across fresh hubs
    lines = [json.loads(line) for line in a.strip().split("\n")]
    # Export order stringifies label values so mixed int/str labels still
    # sort totally — mirror that here.
    metric_idents = [
        (r["kind"], r["name"], tuple(sorted((k, str(v)) for k, v in r["labels"].items())))
        for r in lines
        if r["kind"] != "span"
    ]
    assert metric_idents == sorted(metric_idents)  # sorted export
    spans = [r for r in lines if r["kind"] == "span"]
    assert [s["name"] for s in spans] == ["outer", "inner"]
    assert spans[0]["parent"] is None and spans[1]["parent"] == spans[0]["seq"]
    assert spans[0]["meta"] == {"items": 2}
    assert all("wall_s" not in s for s in spans)  # excluded by default


def test_include_wall_adds_span_durations_only_on_request():
    hub = MetricsHub()
    with hub.span("timed"):
        pass
    rec = hub.records(include_wall=True)[-1]
    assert "wall_s" in rec and rec["wall_s"] >= 0.0


# --------------------------------------------------------------------------
# the disabled path: shared singletons, zero retained state
# --------------------------------------------------------------------------


def test_disabled_hub_hands_out_inert_singletons_and_keeps_no_state():
    hub = MetricsHub(enabled=False)
    assert hub.counter("x", a=1) is NULL_METRIC
    assert hub.gauge("y") is NULL_METRIC
    assert hub.series("z") is NULL_METRIC
    assert hub.histogram("h") is NULL_METRIC
    assert hub.span("s") is NULL_SPAN
    NULL_METRIC.inc()
    NULL_METRIC.set(1.0)
    NULL_METRIC.append(0, 1.0)
    NULL_METRIC.observe(2.0)
    with hub.span("s") as sp:
        sp.set(k=1)
    hub.attach("h2", Histogram())
    assert hub._metrics == {} and hub._spans == [] and hub._seq == 0
    assert hub.to_jsonl() == ""


def test_null_hub_retains_zero_state_through_a_des_run():
    cluster = emulab_cluster()
    topo = T.linear()
    assignment = get_scheduler("rstorm").schedule(topo, cluster, commit=False)
    # No activation: the DES resolves NULL_HUB ambiently and must leave it
    # untouched — that is the "disabled path is free" contract.
    DesExecutor(cluster, config=DesConfig(duration_s=0.1, seed=1)).run(
        topo, assignment
    )
    assert get_hub() is NULL_HUB
    assert NULL_HUB._metrics == {} and NULL_HUB._spans == [] and NULL_HUB._seq == 0


# --------------------------------------------------------------------------
# DES: one code path for report and telemetry percentiles
# --------------------------------------------------------------------------


def _des_run(hub=None, seed=7):
    cluster = emulab_cluster()
    topo = T.linear()
    assignment = get_scheduler("rstorm").schedule(topo, cluster, commit=False)
    ex = DesExecutor(cluster, config=DesConfig(duration_s=0.2, seed=seed))
    if hub is None:
        return ex.run(topo, assignment)
    with hub.activate():
        return ex.run(topo, assignment)


def test_des_report_and_export_share_percentiles():
    hub = MetricsHub()
    rep = _des_run(hub)
    recs = [json.loads(line) for line in hub.to_jsonl().strip().split("\n")]
    lat = [r for r in recs if r["kind"] == "histogram" and r["name"] == "des.latency_s"]
    qd = [r for r in recs if r["kind"] == "histogram" and r["name"] == "des.queue_depth"]
    assert len(lat) == 1 and len(qd) == 1
    # DesReport percentiles and exported percentiles are the same Histogram,
    # so equality is exact — no tolerance.
    assert lat[0]["p50"] == rep.p50_latency_s
    assert lat[0]["p95"] == rep.p95_latency_s
    assert lat[0]["p99"] == rep.p99_latency_s
    assert qd[0]["p50"] == rep.p50_queue_depth
    assert qd[0]["p99"] == rep.p99_queue_depth
    assert qd[0]["count"] == len(rep.queue_depth_trace)
    # The time-series plane rides along: per-task queue depth, cumulative
    # ledgers, per-node utilization.
    names = {r["name"] for r in recs}
    assert {"des.task_queue_depth", "des.dropped", "des.node_utilization",
            "des.sink_rate", "des.emitted", "des.acked"} <= names


def test_des_instrumentation_is_invisible_to_the_report():
    bare = _des_run()
    instrumented = _des_run(MetricsHub())
    assert instrumented.to_dict() == bare.to_dict()


def test_des_queue_depth_percentiles_match_trace():
    import numpy as np

    hub = MetricsHub()
    rep = _des_run(hub)
    if rep.queue_depth_trace:
        want = float(
            np.percentile(
                np.asarray(rep.queue_depth_trace, dtype=np.float64), 95.0
            )
        )
        assert rep.p95_queue_depth == want


# --------------------------------------------------------------------------
# determinism goldens: fixed seed -> byte-identical JSONL
# --------------------------------------------------------------------------


def _payload(topo_spec, export_path):
    return SchedulingPayload(
        topology=topo_spec,
        cluster=ClusterSpec(preset="emulab_12"),
        scheduler=SchedulerSpec(name="rstorm"),
        settings=RunSettings(
            simulate=True,
            sim_engine="des",
            des=DesSettings(duration_s=0.15, seed=11),
            obs=ObsSettings(enabled=True, export_path=str(export_path)),
        ),
    )


@pytest.mark.parametrize(
    "make_topo", [T.linear, T.pageload], ids=["micro_linear", "yahoo_pageload"]
)
def test_golden_byte_identical_jsonl_across_runs(make_topo, tmp_path):
    spec = TopologySpec.from_topology(make_topo())
    paths = [tmp_path / "run1.jsonl", tmp_path / "run2.jsonl"]
    plans = [Nimbus().plan(_payload(spec, p)) for p in paths]
    assert plans[0].placements == plans[1].placements
    a, b = paths[0].read_bytes(), paths[1].read_bytes()
    assert a and a == b, "fixed seed must export byte-identical telemetry"
    # Every line is minified sorted-key JSON (the byte-stability substrate).
    for line in a.decode().strip().split("\n"):
        rec = json.loads(line)
        assert line == json.dumps(rec, sort_keys=True, separators=(",", ":"))


def test_scenario_trace_unchanged_and_series_recorded():
    spec = ScenarioSpec(
        cluster=ClusterSpec(preset="emulab_12"),
        timeline=(
            SubmitEvent(
                topology=TopologySpec.from_topology(T.linear()),
                scheduler=SchedulerSpec(name="rstorm"),
            ),
            RebalanceEvent(),
        ),
        name="obs-scn",
    )
    hub = MetricsHub()
    with_hub = ScenarioRunner(spec, hub=hub).run()
    without = ScenarioRunner(spec).run()
    assert with_hub.to_dict() == without.to_dict()
    names = {r["name"] for r in hub.records()}
    assert {"scenario.step", "scenario.sink_throughput", "scenario.network_cost",
            "scenario.machines_used", "scenario.alive_nodes",
            "nimbus.submit", "nimbus.rebalance", "nimbus.simulate"} <= names
    # Per-interval series are keyed by timeline step, not time.
    (labels, series), = [
        (l, m) for l, m in hub.find("series", "scenario.machines_used")
    ]
    assert labels == {"scenario": "obs-scn"}
    assert [p[0] for p in series.points] == [0, 1]


# --------------------------------------------------------------------------
# search: instrumentation never perturbs placements
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "backend",
    ["numpy"] + (["jax"] if _has_jax() else []),
)
def test_search_placements_invariant_under_hub(backend):
    topo = T.linear()

    def run(hub=None):
        cluster = Cluster.homogeneous(
            racks=2, nodes_per_rack=4, cpu=400.0, memory_mb=4096.0
        )
        sched = get_scheduler(
            "rstorm-search", seed=5, n_chains=4, steps=40, multi_swap=4,
            backend=backend,
        )
        if hub is None:
            return sched.schedule(topo, cluster, commit=False)
        with hub.activate():
            return sched.schedule(topo, cluster, commit=False)

    bare = run()
    hub = MetricsHub()
    observed = run(hub)
    assert observed.placements == bare.placements
    names = {r["name"] for r in hub.records()}
    assert {"search.proposals", "search.accepted", "search.schedule",
            "search.seed", "search.inits", "search.anneal", "anneal.dispatch",
            "search.evaluate", "search.pick"} <= names
    # The counters count what the scan did: every chain's proposals, and
    # the accepted swaps the anneal span reads from the carried counts.
    (_, proposals), = hub.find("counter", "search.proposals")
    (_, accepted), = hub.find("counter", "search.accepted")
    assert proposals.value == 4 * 40
    assert 0 < accepted.value <= proposals.value
    (anneal,) = [r for r in hub.records() if r["name"] == "search.anneal"]
    assert anneal["meta"]["accepted"] == accepted.value
    # Telemetry itself is deterministic.
    hub2 = MetricsHub()
    run(hub2)
    assert hub2.to_jsonl() == hub.to_jsonl()


def _tiny_search(backend="jax"):
    cluster = Cluster.homogeneous(racks=2, nodes_per_rack=4, cpu=400.0, memory_mb=4096.0)
    sched = get_scheduler(
        "rstorm-search", seed=5, n_chains=4, steps=40, multi_swap=4, backend=backend
    )
    return sched.schedule(T.linear(), cluster, commit=False)


@pytest.mark.skipif(not _has_jax(), reason="jax not installed")
def test_enabled_hub_runs_the_same_scan_calls(monkeypatch):
    from repro.core.search import anneal

    calls = []
    real = anneal._jax_anneal_fn

    def counted(k):
        fn = real(k)

        def call(*args):
            calls.append(k)
            return fn(*args)

        return call

    monkeypatch.setattr(anneal, "_jax_anneal_fn", counted)
    bare = _tiny_search()
    n_bare = len(calls)
    with MetricsHub().activate():
        observed = _tiny_search()
    assert observed.placements == bare.placements
    # 40 proposals at multi_swap=4: one whole scan, never split by the hub.
    assert n_bare == 1
    assert len(calls) - n_bare == n_bare


# --------------------------------------------------------------------------
# the jax profiler: spans reach the trace while it captures, and only then
# --------------------------------------------------------------------------

#: Every span a jax-backend search decision opens.
SEARCH_SPANS = (
    "search.schedule", "search.seed", "search.inits", "search.anneal",
    "anneal.dispatch", "device.wait", "device.fetch", "search.evaluate",
    "search.pick",
)


def test_null_hub_keeps_no_state_when_no_profiler_captures():
    from repro.obs import profiled_spans

    before = profiled_spans()
    assert NULL_HUB.span("s") is NULL_SPAN
    assert _tiny_search(backend="numpy").placements
    if _has_jax():
        assert _tiny_search().placements
    assert NULL_HUB.records() == [] and NULL_HUB._stack == []
    assert profiled_spans() == before


def _host_events(trace_dir, names):
    import glob

    import jax

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        out.setdefault(e.name, []).append(
                            (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        )
    return {n: sorted(v) for n, v in out.items()}


@pytest.mark.skipif(not _has_jax(), reason="jax not installed")
def test_spans_reach_the_profilers_host_plane(tmp_path):
    import jax

    from repro.obs import profiled_spans

    _tiny_search()  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("decision"):
            # An enabled hub's spans join the same log: parent links hold
            # across hubs.
            with NULL_HUB.span("outer") as outer:
                with MetricsHub().span("inner") as inner:
                    pass
            _tiny_search()
    logged = profiled_spans()
    by_seq = {sp.seq: sp for sp in logged}
    (root,) = [sp for sp in logged if sp.name == "search.schedule"][-1:]
    tree = [root]
    for sp in reversed(logged):  # children close before their parents
        if sp.parent in {t.seq for t in tree}:
            tree.append(sp)
    assert {sp.name for sp in tree} == set(SEARCH_SPANS)
    assert {sp.labels.get("what") for sp in tree if sp.name == "device.wait"} == {
        "anneal", "score"
    }
    assert root.meta["improved"] in (True, False)
    (anneal_span,) = [sp for sp in tree if sp.name == "search.anneal"]
    assert 0 < anneal_span.meta["accepted"] <= anneal_span.meta["proposals"]
    (inner_logged,) = [sp for sp in logged if sp.name == "inner"][-1:]
    assert by_seq[inner_logged.parent].name == "outer"
    assert outer.recording and inner.wall_s >= 0.0

    events = _host_events(tmp_path, set(SEARCH_SPANS) | {"decision", "outer", "inner"})
    ((d0, d1),) = events["decision"]
    # Each name's trace events, in start order, are its logged spans in
    # open (seq) order: durations agree to within 1 ms, nesting holds.
    interval = {}
    for name in (*SEARCH_SPANS, "outer", "inner"):
        spans = sorted(
            (sp for sp in tree + [inner_logged, by_seq[inner_logged.parent]] if sp.name == name),
            key=lambda sp: sp.seq,
        )
        assert len(events[name]) == len(spans), name
        for sp, (s, e) in zip(spans, events[name]):
            assert d0 <= s <= e <= d1
            assert abs((e - s) - sp.wall_s) < 1e-3, name
            interval[sp.seq] = (s, e)
    for seq, (s, e) in interval.items():
        parent = by_seq[seq].parent
        if parent in interval:
            ps, pe = interval[parent]
            assert ps <= s <= e <= pe


# --------------------------------------------------------------------------
# settings plumbing
# --------------------------------------------------------------------------


def test_obs_settings_sparse_roundtrip():
    assert "obs" not in RunSettings().to_dict()
    rs = RunSettings(obs=ObsSettings(enabled=True, export_path="/tmp/x.jsonl"))
    d = rs.to_dict()
    assert d["obs"] == {"enabled": True, "export_path": "/tmp/x.jsonl"}
    rt = RunSettings.from_dict(json.loads(json.dumps(d)), "settings", [])
    assert rt.obs == rs.obs
    # include_wall only serializes when set (sparse).
    assert "include_wall" not in ObsSettings().to_dict()
    assert ObsSettings(include_wall=True).to_dict()["include_wall"] is True


def test_obs_settings_validation_reports_bad_fields():
    errors = ObsSettings(enabled=True, export_path="").validate("settings.obs")
    assert any("export_path" in e for e in errors)
    errors = RunSettings.from_dict(
        {"obs": {"enabled": "yes"}}, "settings", errs := []
    ) and errs
    assert any("enabled" in e for e in errs)


# --------------------------------------------------------------------------
# report CLI
# --------------------------------------------------------------------------


def _export_sample(path, seed=7):
    hub = MetricsHub()
    _des_run(hub, seed=seed)
    hub.export(str(path))
    return path


def test_report_cli_summarize_and_self_diff(tmp_path, capsys):
    p = _export_sample(tmp_path / "run.jsonl")
    assert report_main(["summarize", str(p), "--top", "3"]) == 0
    out = capsys.readouterr().out
    assert "des.latency_s" in out and "histograms" in out
    assert "top-3 hot nodes" in out
    assert report_main(["diff", str(p), str(p)]) == 0
    assert "identical telemetry" in capsys.readouterr().out


def test_report_swaps_per_s_reads_the_scans_wait(tmp_path, capsys):
    """swaps/s divides the annealer's proposals by its device.wait child's
    wall time, which leaves out dispatch and fetch; the span's own wall is
    the fallback where it has no such child."""
    spans = [
        {"kind": "span", "name": "search.anneal", "labels": {}, "seq": 0,
         "parent": None, "meta": {"proposals": 1000}, "wall_s": 1.0},
        {"kind": "span", "name": "device.wait", "labels": {"what": "anneal"},
         "seq": 1, "parent": 0, "meta": {}, "wall_s": 0.5},
        {"kind": "span", "name": "search.anneal", "labels": {}, "seq": 2,
         "parent": None, "meta": {"proposals": 1000}, "wall_s": 0.25},
    ]
    p = tmp_path / "spans.jsonl"
    p.write_text("".join(json.dumps(r) + "\n" for r in spans))
    assert report_main(["summarize", str(p)]) == 0
    out = capsys.readouterr().out
    assert "swaps_per_s=2e+03" in out and "swaps_per_s=4e+03" in out
    assert "swaps_per_s=1e+03" not in out


def test_report_cli_diff_flags_changed_run(tmp_path, capsys):
    a = _export_sample(tmp_path / "a.jsonl", seed=7)
    b = _export_sample(tmp_path / "b.jsonl", seed=8)
    rc = report_main(["diff", str(a), str(b)])
    out = capsys.readouterr().out
    assert rc == 1 and "~" in out
