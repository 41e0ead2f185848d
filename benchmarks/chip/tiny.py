"""Tiny versions of the benchmark's configurations and traffic, for the CPU
tests: the same shapes of topology and cluster, a few tasks and nodes."""

from __future__ import annotations

import copy
import json

from . import model, traffic


def config(name: str) -> dict:
    cfg = copy.deepcopy(model.load_config(name))
    if name.startswith("chain"):
        t = cfg["tenants"][0]
        t["components"] = t["components"][:4]
        for c in t["components"]:
            c["parallelism"] = 4
        t["edges"] = t["edges"][:3]
        cfg["cluster"].update(racks=2, nodes_per_rack=4, memory_mb=512.0)
    else:
        for t, p in zip(cfg["tenants"], (6, 4)):
            for c in t["components"]:
                c["parallelism"] = p
        cfg["cluster"].update(racks=2, nodes_per_rack=4, memory_mb=512.0)
    return cfg


def spec(name: str) -> dict:
    s = copy.deepcopy(traffic.load_traffic(name))
    s["scheduler"]["kwargs"].update(n_chains=4, steps=64)
    return s


def cell(name: str) -> dict:
    from .harness import load_benchmark

    return {w["name"]: w for w in load_benchmark()["workloads"]}[name]


def peaks() -> dict:
    return json.loads((model.HERE / "peaks.json").read_text())["kinds"]["TPU v5 lite"]
