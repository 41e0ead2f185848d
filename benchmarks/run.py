# One function per paper table. Print ``name,us_per_call,derived`` CSV.
# ``--smoke`` runs the fast pure-Python subset (no jax/kernels, no seed
# scans) — what CI uses as a quick end-to-end pass over the control plane.
#
# Every run also writes a machine-readable artifact (BENCH_smoke.json /
# BENCH_full.json) with one record per emitted row, so the perf trajectory
# is tracked across PRs; CI uploads it as a build artifact.
from __future__ import annotations

import importlib
import inspect
import json
import sys
import traceback

from repro.core.search.backend import enable_compile_cache

from . import common

BENCHES = [
    "benchmarks.bench_network_bound",    # Fig 8
    "benchmarks.bench_cpu_bound",        # Fig 9 + 10
    "benchmarks.bench_yahoo",            # Fig 12
    "benchmarks.bench_multi_topology",   # Fig 13
    "benchmarks.bench_scenarios",        # §3/§6.5 dynamic scenario timelines
    "benchmarks.bench_rebalance",        # greedy vs search reconfiguration
    "benchmarks.bench_des",              # packet-level referee fidelity+scale
    "benchmarks.bench_scheduler_overhead",
    "benchmarks.bench_search",           # batched placement search vs greedy
    "benchmarks.bench_placement",        # mesh-placement quality (DESIGN §2.2)
    "benchmarks.bench_kernels",          # Pallas kernel oracles
]

SMOKE_BENCHES = [
    "benchmarks.bench_network_bound",
    "benchmarks.bench_yahoo",
    "benchmarks.bench_scenarios",   # failure/churn/scale-up timelines (~3 s)
    "benchmarks.bench_rebalance",   # greedy vs search reconfiguration
    "benchmarks.bench_des",         # DES fidelity vs solver (~2 s)
    "benchmarks.bench_search",      # tiny budget: 8 chains × 50 steps
]


def _invoke(mod, smoke: bool) -> None:
    """Call ``mod.run()``, passing ``smoke=`` to benches that take it."""
    if smoke and "smoke" in inspect.signature(mod.run).parameters:
        mod.run(smoke=True)
    else:
        mod.run()


def main() -> None:
    args = sys.argv[1:]
    smoke = "--smoke" in args
    unknown = [a for a in args if a != "--smoke"]
    if unknown:
        print(f"usage: python -m benchmarks.run [--smoke] (unknown: {unknown})", file=sys.stderr)
        sys.exit(2)
    enable_compile_cache()
    print("name,us_per_call,derived")
    common.ROWS.clear()
    failed = []
    for mod_name in SMOKE_BENCHES if smoke else BENCHES:
        common.CURRENT_BENCH = mod_name.rsplit(".", 1)[-1]
        try:
            mod = importlib.import_module(mod_name)
        except Exception:
            traceback.print_exc()
            failed.append(mod_name)
            continue
        try:
            _invoke(mod, smoke)
        except Exception:
            traceback.print_exc()
            failed.append(mod_name)
    artifact = f"BENCH_{'smoke' if smoke else 'full'}.json"
    with open(artifact, "w") as fh:
        json.dump(
            {"mode": "smoke" if smoke else "full", "failed": failed, "rows": common.ROWS},
            fh,
            indent=2,
        )
        fh.write("\n")
    print(f"wrote {artifact} ({len(common.ROWS)} rows)", file=sys.stderr)
    if failed:
        print(f"FAILED benches: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
