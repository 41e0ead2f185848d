"""Deterministic-zone configuration: which lint rules apply to which files.

A *zone* is a set of path anchors plus the rule names enforced there.  Zones
overlap — a file's active rule set is the union over every zone that matches
it (``repro/core/search/anneal.py`` picks up both the core determinism rules
and the stricter hot-loop rules).

Matching is purely textual on posix path segments, so the linter works the
same whether it is handed ``src`` from the repo root, absolute paths, or a
single file.

Zone knowledge is also where repo-specific type facts live: the ``iter-order``
rule cannot infer that ``ResourceVector.dims`` returns a ``frozenset`` from a
different module, so the attribute names that are known set-valued across the
codebase are declared here (``SET_ATTRS``).
"""

from __future__ import annotations

import dataclasses
from pathlib import PurePosixPath
from typing import Tuple

#: Attribute names that return ``set``/``frozenset`` across the repo
#: (``ResourceVector.dims``/``.soft_dims``/``.hard``).  Iterating them
#: unsorted is exactly the hazard the iter-order rule exists to catch.
SET_ATTRS: Tuple[str, ...] = ("dims", "soft_dims", "hard")

#: The one module allowed to touch jax config: the scoped
#: ``jax.enable_x64(True)`` helper and the compile-cache setup.  Everything
#: else must use ``backend.x64()`` / ``backend.enable_compile_cache()``.
X64_ALLOWED: Tuple[str, ...] = ("repro/core/search/backend.py",)


@dataclasses.dataclass(frozen=True)
class Zone:
    """One deterministic zone: where it applies and what it enforces."""

    name: str
    anchors: Tuple[str, ...]  # path-segment anchors, e.g. "repro/core"
    rules: Tuple[str, ...]
    set_attrs: Tuple[str, ...] = ()


ZONES: Tuple[Zone, ...] = (
    # The scheduling core, the control-plane API, and the discrete-event
    # executor: everything that decides placements, serializes results, or
    # referees a placement's measured performance must be replay-
    # deterministic (the DES's bit-identical-trace contract hangs on it:
    # every random draw flows from one seeded Philox root).
    Zone(
        name="core",
        anchors=("repro/core", "repro/api", "repro/stream/des"),
        rules=(
            "unseeded-random",
            "iter-order",
            "float-sum",
            "np-reduce-dtype",
            "jax-purity",
            "x64-scope",
        ),
        set_attrs=SET_ATTRS,
    ),
    # The annealer step paths: beyond determinism, the hot-loop contract
    # (no deepcopy, no libm transcendentals, no wall-clock reads) and the
    # float64-only exactness contract apply.
    Zone(
        name="hot-loop",
        anchors=(
            "repro/core/engine",
            "repro/core/search",
            "repro/core/reconfig",
        ),
        rules=("hot-loop", "float32-literal"),
        set_attrs=SET_ATTRS,
    ),
    # The observability plane: telemetry must itself be deterministic (a
    # fixed seed exports byte-identical JSONL), so the registry/hub/report
    # code carries the core determinism rules plus hot-loop — wall-clock
    # reads are confined to the one allow-listed shim in ``obs/clock.py``.
    Zone(
        name="obs",
        anchors=("repro/obs",),
        rules=(
            "unseeded-random",
            "iter-order",
            "float-sum",
            "np-reduce-dtype",
            "hot-loop",
        ),
        set_attrs=SET_ATTRS,
    ),
    # Benchmarks and examples feed the committed quality baselines and the
    # documented replays — their numbers must be as reproducible as the
    # core's (timing columns are exempt by design, so no hot-loop rules).
    Zone(
        name="harness",
        anchors=("benchmarks", "examples"),
        rules=("unseeded-random", "iter-order", "jax-purity", "x64-scope"),
        set_attrs=SET_ATTRS,
    ),
    # The Pallas kernel layer (accelerator kernels and the fused search
    # scorer): no interpret=True left on at committed call sites, no
    # program_id-dependent accumulation order, no silently-truncating
    # grids.
    Zone(
        name="kernels",
        anchors=("repro/kernels", "repro/core/search/kernels"),
        rules=(
            "pallas-interpret",
            "pallas-accum-order",
            "pallas-grid-truncate",
        ),
        set_attrs=SET_ATTRS,
    ),
    # The *search* kernels additionally carry the three-backend golden-
    # equality contract (kernel == jax-vmap == numpy, bit-identical), so
    # their accumulators must be float64/exact-int.  The float32 flash
    # kernels under repro/kernels are deliberately outside this subzone.
    Zone(
        name="kernel-exactness",
        anchors=("repro/core/search/kernels",),
        rules=("pallas-accum-dtype",),
        set_attrs=SET_ATTRS,
    ),
)


def _norm(path: str) -> str:
    """Posix form with a leading slash so anchor matches are segment-exact."""
    return "/" + PurePosixPath(str(path).replace("\\", "/")).as_posix().lstrip("/")


def _matches(path: str, anchor: str) -> bool:
    p = _norm(path)
    a = "/" + anchor.strip("/")
    return (a + "/") in p or p.endswith(a)


def zones_for_path(path: str) -> Tuple[Zone, ...]:
    return tuple(
        z for z in ZONES if any(_matches(path, a) for a in z.anchors)
    )


def rules_for_path(path: str) -> Tuple[str, ...]:
    """Union of rule names active for ``path`` (empty → file not in a zone)."""
    out = []
    for z in zones_for_path(path):
        for r in z.rules:
            if r not in out:
                out.append(r)
    return tuple(out)


def set_attrs_for_path(path: str) -> Tuple[str, ...]:
    out = []
    for z in zones_for_path(path):
        for a in z.set_attrs:
            if a not in out:
                out.append(a)
    return tuple(out)


def x64_exempt(path: str) -> bool:
    """True for the scoped-x64 helper module itself."""
    return any(_matches(path, a) for a in X64_ALLOWED)
