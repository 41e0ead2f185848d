"""Readings that set the limits of ``checks.py``: the program as it is, its
control, and the faults a cell can have, each run through the harness's own
timed path on many seeds in one process.

    python3 benchmarks/chip/control.py --workload <cell> --fault <name> \
        --seeds 1,2,3 --seconds 10 [--out readings.jsonl]

``--fault none`` reads sound runs (the lower readings).  ``float32`` runs
the search without its float64 scope, the program's own lower-precision
path; a netcost search places the same tasks on the same nodes bit for bit
that way (its sums are exact in float32), so nothing can tell it from the
sound run, and the control is ``overfill``, which breaks a guarantee the
configuration states, hard capacity.  The faults:

* ``overfill`` — one task of each decided placement is moved, where the
  answer is produced, onto the node that holds the most memory;
* ``scramble`` — the decided topology's tasks trade nodes at random
  (a far worse placement);
* ``miscount`` — the reported network cost leaves out same-rack pairs;
* ``misreport_tp`` — the sink throughput the program's fluid solve reports
  is one part in a million high (a driver that carries the report reads it
  in ``tp_gap``; no cell's driver does yet).

The benchmark's own runs never load this module.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from typing import Dict

import numpy as np

FAULTS = ("none", "float32", "overfill", "scramble", "miscount", "misreport_tp")


def _overfill(placements: Dict[str, str], topology, cluster, used: Dict[str, float]) -> None:
    """Move the first task onto the node with the most memory used."""
    if not placements:
        return
    tid = sorted(placements)[0]
    target = max(
        (nid for nid, n in cluster.nodes.items() if n.alive and nid != placements[tid]),
        key=lambda nid: (used.get(nid, 0.0), nid),
    )
    placements[tid] = target


def _scramble(placements: Dict[str, str], topology, seed: int = 0) -> None:
    """The decided topology's tasks trade nodes at random, across components
    (the nodes' multiset is kept)."""
    rng = np.random.Generator(np.random.Philox(seed))
    ids = sorted(t.id for t in topology.all_tasks() if t.id in placements)
    nodes = [placements[t] for t in ids]
    for t, k in zip(ids, rng.permutation(len(ids))):
        placements[t] = nodes[k]


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` in the program for the duration of the block."""
    if fault == "none":
        yield
        return
    from repro.core.assignment import Assignment
    from repro.core.search import anneal, objective, throughput
    from repro.core.search.portfolio import SearchScheduler

    saved = []

    def patch(obj, name, value):
        saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if fault in ("overfill", "scramble"):
        orig_phases = SearchScheduler._schedule_phases

        def phases(self, topology, cluster, span):
            out = orig_phases(self, topology, cluster, span)
            if fault == "scramble":
                _scramble(out.placements, topology, self.seed)
            else:
                used = {}
                for nid, node in cluster.nodes.items():
                    cap = node.spec.memory_capacity_mb
                    used[nid] = cap - node.available.values.get("memory_mb", cap)
                mem = {t.id: topology.components[t.component_id].memory_load for t in topology.all_tasks()}
                for task, nid in out.placements.items():
                    used[nid] = used.get(nid, 0.0) + mem[task]
                _overfill(out.placements, topology, cluster, used)
            return out

        patch(SearchScheduler, "_schedule_phases", phases)
    elif fault == "miscount":
        orig_cost = Assignment.network_cost

        def network_cost(self, topology, cluster, live_only=False):
            full = orig_cost(self, topology, cluster, live_only)
            same_rack = 0.0
            for src, dst in topology.task_edges():
                a, b = self.placements.get(src.id), self.placements.get(dst.id)
                if a is not None and b is not None and a != b and (
                    cluster.nodes[a].rack_id == cluster.nodes[b].rack_id
                ):
                    same_rack += cluster.network_distance(a, b)
            return full - same_rack

        patch(Assignment, "network_cost", network_cost)
    elif fault == "misreport_tp":
        from repro.stream.simulator import Simulator

        orig_result = Simulator._result

        def result(self, *args, **kw):
            out = orig_result(self, *args, **kw)
            out.sink_throughput *= 1.0 + 1e-6
            return out

        patch(Simulator, "_result", result)
    elif fault == "float32":
        @contextlib.contextmanager
        def no_x64():
            yield

        for mod in (anneal, objective, throughput):
            if hasattr(mod, "x64"):
                patch(mod, "x64", no_x64)
    else:
        raise ValueError(f"unknown fault {fault!r}; choose from {FAULTS}")
    try:
        yield
    finally:
        for obj, name, value in reversed(saved):
            setattr(obj, name, value)


def read(cell, cfg, spec, fault: str, seeds, seconds: float, bench, peaks, devices, log=None):
    """One reading per seed: ``{"seed", "fault", "correct", "compared",
    "attempted", "metrics"}``."""
    from .harness import run_cell

    out = []
    for seed in seeds:
        t0 = time.perf_counter()
        try:
            res = run_cell(
                cell, cfg, spec, seed=seed, seconds=seconds, trace=False,
                t_start=t0, bench=bench, peaks=peaks, devices=devices, log=log,
                plant=lambda: planted(fault),
            )
        except Exception as e:  # a control that crashes has failed
            out.append({"seed": seed, "fault": fault, "correct": False, "error": repr(e)})
            continue
        out.append({
            "seed": seed,
            "fault": fault,
            "correct": res["correct"],
            "compared": {k: v["value"] for k, v in res["compared"].items()},
            "attempted": res["attempted"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        })
    return out


def main(argv=None) -> int:
    from . import harness, model, traffic

    p = argparse.ArgumentParser(prog="control.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--fault", action="append", choices=FAULTS, required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--fault-seeds", type=int, default=None,
                   help="faults other than 'none' read only the first N seeds")
    p.add_argument("--out")
    args = p.parse_args(argv)
    bench = harness.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    devices = harness.check_devices(int(cell["chips"]))
    peaks = harness.load_peaks(devices[0].device_kind)
    cfg = model.load_config(cell["config"])
    spec = traffic.load_traffic(cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",")]
    sink = open(args.out, "a") if args.out else None
    for fault in args.fault:
        use = seeds if fault == "none" or args.fault_seeds is None else seeds[: args.fault_seeds]
        for r in read(cell, cfg, spec, fault, use, args.seconds, bench, peaks, devices,
                      log=lambda *a: None):
            r["workload"] = args.workload
            line = json.dumps(r)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
    return 0


if __name__ == "__main__":
    from pathlib import Path

    root = Path(__file__).resolve().parents[2]
    sys.path[0] = str(root)
    sys.path.insert(1, str(root / "src"))
    from benchmarks.chip.control import main as _main

    sys.exit(_main())
