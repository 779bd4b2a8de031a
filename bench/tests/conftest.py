"""The benchmark's own tests run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))


def rehearsal_root(tmp):
    """A checkout in ``tmp`` with the benchmark, the program and the
    repository's BENCHMARK.json."""
    import shutil

    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp / "src").symlink_to(BENCH.parent / "src")
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return tmp
