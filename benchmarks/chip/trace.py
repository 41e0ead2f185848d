"""Reduction of a profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``:

* device planes are the ``/device:TPU:<n>`` planes;
* busy time is the union of the intervals of the device's operations (the
  ``XLA Ops`` line, or ``XLA Modules`` where a plane has no op line),
  averaged over the device planes;
* module time is the summed duration of ``XLA Modules`` events, grouped by
  the jitted function's name (``jit_anneal(…)`` -> ``jit_anneal``);
* host spans are the benchmark's own ``TraceAnnotation``s (``decision``,
  ``reset``); an idle gap on the device is labelled by the span that covers
  its middle, ``between`` where none does.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_SPANS = ("decision", "reset")


def module_group(name: str) -> str:
    """``jit_anneal(123)`` -> ``jit_anneal``; ``jit_evaluate.3`` -> ``jit_evaluate``."""
    return re.split(r"[(.\s]", name, maxsplit=1)[0]


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by a set of half-open [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def find_xplane(directory: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def load_events(path: str) -> dict:
    """The parts of one trace the reduction reads, as plain lists (seconds).
    ``path`` is an ``.xplane.pb``, or one compressed with gzip (``.gz``).

    ``{"devices": [{"ops": [(s, e)], "modules": [(name, s, e)]}],
    "host": [(name, s, e)]}``"""
    import gzip

    import jax

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(
                        (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9) for e in line.events
                    )
                elif line.name == "XLA Modules":
                    modules.extend(
                        (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events
                    )
            devices.append({"ops": ops, "modules": modules})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        host.append((e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
    return {"devices": devices, "host": host}


def reduce(events: dict, window_s: Optional[float] = None) -> dict:
    """Device numbers of one traced window.

    ``window_s`` defaults to the span from the first host span's start to
    the last one's end."""
    host = events["host"]
    if host:
        w0, w1 = min(s for _, s, _ in host), max(e for _, _, e in host)
    else:
        starts = [s for d in events["devices"] for s, _ in d["ops"]]
        ends = [e for d in events["devices"] for _, e in d["ops"]]
        w0, w1 = (min(starts), max(ends)) if starts else (0.0, 0.0)
    if window_s is None:
        window_s = w1 - w0
    busy, modules, op_count = [], {}, 0
    device_intervals: List[Tuple[float, float]] = []
    for d in events["devices"]:
        ivs = d["ops"] or [(s, e) for _, s, e in d["modules"]]
        op_count += len(ivs)
        busy.append(union_length(ivs))
        device_intervals.extend(ivs)
        for name, s, e in d["modules"]:
            g = module_group(name)
            agg = modules.setdefault(g, {"seconds": 0.0, "count": 0})
            agg["seconds"] += e - s
            agg["count"] += 1
    n_dev = max(len(events["devices"]), 1)
    for agg in modules.values():
        agg["seconds"] /= n_dev
    busy_s = sum(busy) / n_dev
    # Idle gaps inside the window, labelled by the host span over their middle.
    gaps = []
    prev = w0
    for s, e in merged(device_intervals) + [(w1, w1)]:
        if s > prev:
            mid = 0.5 * (prev + s)
            label = next((n for n, hs, he in host if hs <= mid < he), "between")
            gaps.append((label, s - prev))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(
        ((g, a["seconds"]) for g, a in modules.items()), key=lambda x: -x[1]
    )
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "modules": modules,
        "device_events": op_count,
        "breakdown": {
            "device_ops": [[g, s] for g, s in top_ops[:10]],
            "idle_gaps": [[label, s] for label, s in gaps[:10]],
        },
    }
