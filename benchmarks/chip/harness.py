"""The benchmark's one harness: a cell of ``BENCHMARK.json``, run once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one chip.  It enables jax's persistent compile cache at a fixed
path in the checkout, builds the cell's configuration and traffic from their
files, warms up every shape the traffic uses with one real decision each,
then drives Nimbus in a closed loop with one client for ``--seconds``.
After the window it judges every decision (``checks.py``) and prints one
JSON line.  With ``--trace 1`` the window runs under the profiler and the
line carries the per-layer metrics (``layers/<metric>.py``) instead of the
end-to-end ones.

It exits non-zero and prints no result line when jax's first device is not
a TPU, when there are fewer chips than the cell asks for, or when the
device kind has no entry in ``peaks.json``.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Optional

import numpy as np

from . import checks, costs, model, trace as trace_mod, traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

#: A ``--trace 1`` run traces the window's first decisions up to this many
#: seconds (whole decisions) and runs the rest untraced: the profiler's
#: collection grows with the device's events (about 0.2 M per second of
#: the annealer's scan) and past some millions the device trace is cut.
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    """The machine cannot run the cell as it asks."""


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["kinds"]
    if device_kind not in table:
        raise NoChip(f"device kind {device_kind!r} has no entry in peaks.json")
    return table[device_kind]


def check_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"jax's first device is {devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax sees {len(devices)}")
    return devices


class CompileCounter:
    """Counts executables jax builds or loads from its persistent cache."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)
    HITS = ("/jax/compilation_cache/cache_hits",)

    def __init__(self):
        import jax.monitoring as mon

        self.count, self.active, self.mon = 0, False, mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1

    def _on_event(self, event, **kw):
        if self.active and event in self.HITS:
            self.count += 1

    def close(self):
        self.mon.unregister_event_duration_listener(self._on_duration)
        self.mon.unregister_event_listener(self._on_event)


class LayerContext:
    """What a per-layer reader may read."""

    def __init__(self, trace, compiles, traced_decisions, anneal_bytes, peaks):
        self.trace = trace
        self.compiles = compiles
        self.traced_decisions = traced_decisions
        self.anneal_bytes = anneal_bytes
        self.peaks = peaks


def _percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def _window(driver, seconds: float, trace: bool):
    """The measured window: closed loop, one client, until ``seconds`` have
    passed and the traffic's cycle is whole.  Returns a dict with the
    decisions, the process CPU seconds each took (host work against
    waiting), the window's seconds, the compiles in it, and for a traced
    run the trace directory, the traced seconds and decisions."""
    import jax

    cycle = driver.cycle
    counter = CompileCounter()
    out = {"tmpdir": None, "traced_s": None, "traced": 0}
    if trace:
        out["tmpdir"] = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(out["tmpdir"], profiler_options=opts)
    decisions, cpu = [], []
    counter.active = True
    i = 0
    w0 = time.perf_counter()
    deadline = w0 + seconds
    while not (time.perf_counter() >= deadline and i % cycle == 0):
        with jax.profiler.TraceAnnotation("reset"):
            prepared = driver.prepare(i)
        c0 = time.process_time()
        with jax.profiler.TraceAnnotation("decision"):
            decisions.append(driver.decide(i, prepared))
        cpu.append(time.process_time() - c0)
        i += 1
        if trace and out["traced_s"] is None and (
            time.perf_counter() - w0 >= min(seconds, TRACE_SECONDS) and i % cycle == 0
        ):
            out["traced_s"], out["traced"] = time.perf_counter() - w0, i
            jax.profiler.stop_trace()
    out["window_s"] = time.perf_counter() - w0
    counter.active = False
    counter.close()
    if trace and out["traced_s"] is None:
        out["traced_s"], out["traced"] = out["window_s"], i
        jax.profiler.stop_trace()
    out.update(decisions=decisions, cpu=cpu, compiles=counter.count)
    return out


def run_cell(
    cell: dict,
    cfg: dict,
    spec: dict,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    bench: dict,
    peaks: Optional[dict],
    devices,
    log=None,
    plant=contextlib.nullcontext,
) -> dict:
    """Set up, warm, measure and judge one cell; returns the result object.

    ``plant`` wraps set-up and the window (never the judging): the control
    runs (``control.py``) plant a fault in the program there."""
    from repro.core.search.backend import enable_compile_cache

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    log(f"compile cache: {enable_compile_cache()}")

    with plant():
        driver = traffic.make_driver(cfg, spec, seed)
        for i in driver.warm_indices():
            driver.decide(i, driver.prepare(i))
        setup_s = time.perf_counter() - t_start
        log(f"set-up {setup_s:.3f} s")
        w = _window(driver, seconds, trace)
    decisions, compiles, tmpdir = w["decisions"], w["compiles"], w["tmpdir"]
    reduced = None
    if trace:
        path = trace_mod.find_xplane(tmpdir)
        if path is not None:
            reduced = trace_mod.reduce(trace_mod.load_events(path), window_s=w["traced_s"])
        shutil.rmtree(tmpdir, ignore_errors=True)
    stats = devices[0].memory_stats() or {}
    memory_peak = max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices
    ) if stats else 0

    # Judge every decision of the window, once the window has closed.
    t_check = time.perf_counter()
    judged = checks.judge(cfg, driver, decisions)
    numbers = judged["numbers"]
    correct = checks.passed(numbers)
    log(f"checks took {time.perf_counter() - t_check:.3f} s")

    times = [d.seconds for d in decisions]
    log(f"decisions in the window: {len(times)} (p95 over {len(times)} samples) "
        f"in {w['window_s']!r} s")
    log("decision seconds: " + " ".join(f"{t:.4f}" for t in times))
    log("decision process cpu seconds: " + " ".join(f"{t:.3f}" for t in w["cpu"]))
    metrics = {}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    out = {}
    if not trace:
        values = {
            "decision_s": sum(times) / len(times),
            "decision_p95_s": _percentile(times, 95),
            "setup_s": setup_s,
            **judged["metrics"],
        }
        for m in bench["end_to_end"]:
            cells = m.get("workloads")
            if cells is not None and cell["name"] not in cells:
                continue
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        traced = decisions[: w["traced"]]
        anneal_bytes = sum(costs.anneal_bytes(driver.searches(d)) for d in traced)
        ctx = LayerContext(reduced, compiles, len(traced), anneal_bytes, peaks)
        log(f"traced {len(traced)} decisions in {w['traced_s']!r} s; their mean "
            f"{sum(d.seconds for d in traced) / max(len(traced), 1)!r} s")
        for m in bench["per_layer"]:
            cells = m.get("workloads")
            if cells is not None and cell["name"] not in cells:
                continue
            reader = importlib.import_module(f"{__package__}.layers.{m['name']}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            out["breakdown"] = reduced["breakdown"]
            log(f"trace: {reduced['device_events']} device events, "
                f"busy {reduced['busy_s']!r} s of {reduced['window_s']!r} s")
    log(f"compiles in the window: {compiles}")
    compared = {k: {"value": numbers[k], "limit": checks.LIMITS[k]} for k in sorted(numbers)}
    for k, v in compared.items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    result = {
        "correct": bool(correct),
        "attempted": len(decisions),
        "failed": judged["failed"],
        "metrics": metrics,
        "device": device,
    }
    result.update(out)
    result["compared"] = compared
    return result


def parse_args(argv):
    p = argparse.ArgumentParser(prog="run.py", description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; have {sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    try:
        devices = check_devices(int(cell["chips"]))
        peaks = load_peaks(devices[0].device_kind)
    except NoChip as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 3
    cfg = model.load_config(cell["config"])
    spec = traffic.load_traffic(cell["traffic"])
    result = run_cell(
        cell, cfg, spec, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=t_start, bench=bench, peaks=peaks, devices=devices,
    )
    # The compared numbers, each beside its limit, close standard error.
    for k, v in result["compared"].items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
