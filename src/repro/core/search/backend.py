"""Backend gate for the batched search subsystem.

The objective and the annealing loop are written once against the shared
numpy-style array API and dispatched to either ``jax.numpy`` (vmapped /
jit-compiled, float64 via the scoped ``jax.enable_x64`` context so results
match the numpy path bit-for-bit on the CPU) or plain ``numpy``.  On a TPU,
whose float64 is emulated, the throughput proxy can differ from numpy in its
last bits (README "Determinism contract").  The container may not ship jax
at all — everything here degrades to the numpy path with identical outputs,
which the golden-equality tests pin.
"""

from __future__ import annotations

import contextlib
import importlib.util
import os
from pathlib import Path
from typing import Iterator, Optional, Tuple

from ...obs import get_hub

#: Availability is probed without importing: jax's ~1 s import cost must not
#: tax every ``import repro.core`` (the search registers eagerly there); the
#: actual module import is deferred to the first jax-backend call.
HAS_JAX = importlib.util.find_spec("jax") is not None

#: ``pallas`` is the fused single-pass scoring kernel
#: (:mod:`repro.core.search.kernels`) — jax-only, bit-identical to the
#: ``jax``/``numpy`` oracle paths by the same dyadic-grid exactness argument.
BACKENDS = ("auto", "jax", "numpy", "pallas")

#: Why ``backend="pallas"`` is refused on a TPU (raised before any lowering).
PALLAS_ON_TPU_ERROR = (
    "backend='pallas' cannot run on a TPU: the fused scoring kernel holds "
    "64-bit types (float64 accumulators and outputs), which the Mosaic TPU "
    "lowering refuses; use backend='jax' on the chip. A 32-bit rewrite of "
    "the kernel is ROADMAP Speed item 3."
)

#: Persistent compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed directory in the checkout (gitignored), resolved from this file.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[4] / ".jax_cache"


def on_tpu() -> bool:
    """True when jax's default backend is a TPU (imports jax)."""
    if not HAS_JAX:
        return False
    import jax

    return jax.default_backend() == "tpu"


def resolve_backend(name: str = "auto") -> str:
    """Map a requested backend to a concrete one, validating availability."""
    if name not in BACKENDS:
        raise ValueError(f"unknown backend {name!r}; choose from {BACKENDS}")
    if name == "auto":
        return "jax" if HAS_JAX else "numpy"
    if name in ("jax", "pallas") and not HAS_JAX:
        raise RuntimeError(
            f"backend={name!r} requested but jax is not importable; "
            "install jax or use backend='numpy'/'auto'"
        )
    if name == "pallas" and on_tpu():
        raise RuntimeError(PALLAS_ON_TPU_ERROR)
    return name


def chunk_ranges(n: int, chunk: int) -> Iterator[Tuple[int, int]]:
    """Yield ``(lo, hi)`` slice bounds covering ``range(n)`` in ``chunk``
    steps — the one chunking loop every evaluator backend shares, so the
    "results independent of chunking" contract has a single implementation
    (numpy, jax-vmap, and pallas paths all iterate these exact bounds)."""
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    for lo in range(0, n, chunk):
        yield lo, min(lo + chunk, n)


def jax_modules():
    """(jax, jax.numpy), imported lazily — only call after
    ``resolve_backend`` said 'jax'."""
    import jax
    import jax.numpy as jnp

    return jax, jnp


def fetch(x, what: str):
    """Wait for the device result ``x`` (an array or a tuple of them), then
    copy it to the host as numpy, each step in a span of its own:
    ``device.wait`` and ``device.fetch``, labelled ``what``."""
    jax, _ = jax_modules()
    hub = get_hub()
    with hub.span("device.wait", what=what):
        jax.block_until_ready(x)
    with hub.span("device.fetch", what=what):
        return jax.device_get(x)


@contextlib.contextmanager
def x64() -> Iterator[None]:
    """Scoped float64 for jax traces (global-config safe: the repo's Pallas
    kernels run float32 and must not see a process-wide x64 flip)."""
    if not HAS_JAX:  # numpy path — nothing to scope
        yield
    else:
        import jax

        with jax.enable_x64(True):
            yield


def enable_compile_cache() -> Optional[str]:
    """Turn on jax's persistent compilation cache; returns its directory
    (None without jax).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
    nothing else is set.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`, a fixed path, so a later process of the same
    checkout finds what an earlier one compiled.  Entry points that drive
    the chip call this before their first compile; library import does not.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env or not HAS_JAX:
        return env or None
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
