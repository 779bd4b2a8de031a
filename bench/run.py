"""Run one benchmark cell and print its result as the last line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  ``BENCHMARK.json`` there names the cell;
its configuration, traffic mix, limits and metric readers are files under
``bench/`` found by name (see ``bench/spec.py``).  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, the device's busy and traced seconds and a breakdown.

Without a TPU, or with fewer chips than the cell asks for, the run exits 2
and prints no result.  ``--tiny`` is a CPU rehearsal at the sizes of
``bench/tiny.json``: its last line names the CPU and carries no metric.

JAX's persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when
set, else ``.jax_cache/`` in the checkout, so only a checkout's first run
of a cell compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at tiny sizes; no device metric")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    import spec

    try:
        bench = spec.load_benchmark(ROOT)
        cell = spec.cell(bench, ROOT, args.workload)
    except (FileNotFoundError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    from repro.launch.train import setup_compile_cache

    setup_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if not args.tiny:
        if devices[0].platform != "tpu":
            print(f"bench: no TPU (JAX found {devices[0].platform}); "
                  "--tiny rehearses on the CPU", file=sys.stderr)
            return 2
        import peaks

        try:
            peaks.peaks(devices[0].device_kind)
        except KeyError as e:
            print(f"bench: {e}", file=sys.stderr)
            return 2
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chip(s); JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2

    import harness

    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         args.tiny, T_START, ROOT)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
