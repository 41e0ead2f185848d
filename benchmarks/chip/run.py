"""Entry point of the chip benchmark: ``python3 benchmarks/chip/run.py
--workload <cell> --seed <n> --seconds <s> --trace <0|1>`` from the root of
a checkout (see ``harness.py``)."""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# Every executable goes to the persistent cache, however fast it compiled,
# so that a cell's later runs compile nothing (jax reads this at import).
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

ROOT = Path(__file__).resolve().parents[2]
# Run as a script, Python puts this directory first on the path, where
# ``trace.py`` would shadow the standard library's module of that name.
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from benchmarks.chip.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
