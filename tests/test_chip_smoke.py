"""``chip_smoke.py`` rehearsed on the CPU.

The script's phases run here at a tiny size (16 tasks on 8 nodes) with
``backend="jax"`` on XLA:CPU, so a change that breaks the chip smoke fails
in tier-1 rather than on the chip.  Its refusals are pinned too: without a
TPU, and without the rest of the repository, it exits non-zero and prints
no result.  The flagship payload it builds is the benchmark's flagship.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

import chip_smoke  # noqa: E402
from repro.api import Nimbus  # noqa: E402
from repro.core import get_scheduler  # noqa: E402

ROOT = Path(chip_smoke.__file__).resolve().parent

#: 4 components x 4 tasks on 2 racks x 4 nodes; 512 MB nodes hold four
#: 128 MB tasks each, so packed candidates overload (phase a needs some).
TINY = chip_smoke.flagship_payload(4, 4, 2, 4, memory_mb=512.0)

PHASES = {
    "scorer": lambda: chip_smoke.phase_scorer(TINY, batch=64),
    "netcost_plan": lambda: chip_smoke.phase_netcost_plan(TINY, 8, 100),
    "throughput_plan": lambda: chip_smoke.phase_throughput_plan(TINY, 8, 100),
    "rebalance": lambda: chip_smoke.phase_rebalance(TINY),
}


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_phase_passes_at_tiny_size(phase, capsys):
    PHASES[phase]()
    out = capsys.readouterr().out
    assert " ok   " in out and "FAIL" not in out


def _run_smoke(cwd: Path):
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"), "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "chip_smoke.py"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def _no_result(proc) -> bool:
    return proc.returncode != 0 and '"ok"' not in proc.stdout


def test_refuses_without_tpu():
    proc = _run_smoke(ROOT)
    assert _no_result(proc), proc.stdout
    assert "not a TPU" in proc.stderr


def test_refuses_without_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run_smoke(tmp_path)
    assert _no_result(proc), proc.stdout


def test_flagship_payload_is_the_benchmark_flagship():
    from benchmarks.bench_search import flagship

    payload = chip_smoke._payload(chip_smoke.flagship_payload(**chip_smoke.FLAGSHIP))
    plan = Nimbus().plan(payload)
    topo, cluster = flagship()
    greedy = get_scheduler("rstorm").schedule(topo, cluster, commit=False)
    assert plan.placements == greedy.placements
    assert plan.network_cost == greedy.network_cost(topo, cluster) == 28789.0
