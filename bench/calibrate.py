"""Readings that the limits of a cell are set from.

    python bench/calibrate.py --workload <name> --seeds 1-12 \\
        --control-seeds 1-3 --fault-seeds 1-3 [--tiny] [--out FILE]

For every seed, in one process: the program's three checked steps
against the reference (the lower readings); for the control seeds, the
reference in float8 in the program's place (the upper readings); for the
fault seeds, the program with half of each batch left out, the mean taken
over the rest.  A state left unchanged reads 1 on ``update_gap`` by its
measure and needs no run.  Prints one JSON line per reading and writes
them all to ``--out``.  Runs no window and measures no time.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))


def seeds(text: str):
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def half_batch(batch):
    """Fault: labels of the second half of the rows masked, so the loss is
    the mean over the first half."""
    import jax

    n = batch["labels"].shape[0] // 2
    f = jax.jit(lambda b: {**b, "labels": b["labels"].at[n:].set(-1)},
                out_shardings={k: v.sharding for k, v in batch.items()})
    return f(batch)


def one_seed(h, cell, model, prog, seed, control, fault, tiny=False):
    traffic = h.sized(cell, tiny)[1]
    data = h.platform_setup(traffic, cell.chips, seed)
    out = []

    def program_run(fault_fn, loader):
        state = prog.init_state(seed)
        feed_it = prog.feed(loader)
        state, first = h.first_steps(prog, feed_it, state, seed, fault_fn)
        feed_it.close()
        rows = [h.host_batch(s.batch) for s in first.steps]
        for s in first.steps:
            s.batch = None
        del state
        return first, rows

    first, delivered = program_run(None, data.loader)
    ref_rows = h.reference_packs(data.docs, traffic["seq_len"])
    bad, where = h.check_rows(ref_rows, delivered,
                              h.epoch_rows(data.loader))
    B = delivered[0]["tokens"].shape[0]
    ref_batches = [{k: ref_rows[k][where[b * B:(b + 1) * B]]
                    for k in h.ROW_KEYS} for b in range(h.N_CHECKED)]
    t = time.perf_counter()
    ref = h.reference_readings(cell, model, seed, ref_batches)
    ref_s = time.perf_counter() - t
    prog_losses = [s.loss for s in first.steps]
    out.append({"kind": "program", "seed": seed, "rows_bad": bad,
                "reference_s": ref_s, "losses": prog_losses,
                "ref_losses": ref[0],
                **h.readings(prog_losses, first.g1, first.delta, ref)})
    if control:
        ctl = h.reference_readings(cell, model, seed, ref_batches,
                                   quant="fp8")
        out.append({"kind": "control_fp8", "seed": seed,
                    "losses": ctl[0],
                    **h.readings(ctl[0], ctl[1], ctl[2], ref)})
    if fault:
        first, _ = program_run(half_batch, data.new_loader())
        losses = [s.loss for s in first.steps]
        out.append({"kind": "fault_half_batch", "seed": seed,
                    "losses": losses,
                    **h.readings(losses, first.g1, first.delta, ref)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--fault-seeds", default="1-3")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import spec
    from repro.launch.train import setup_compile_cache

    setup_compile_cache()
    import jax

    import harness as h

    cell = spec.cell(spec.load_benchmark(BENCH.parent), BENCH.parent,
                     args.workload)
    if not args.tiny and jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    model, _ = h.sized(cell, args.tiny)
    prog = h.Program(cell, model, cell.chips)
    control, fault = seeds(args.control_seeds), seeds(args.fault_seeds)
    allr = []
    for seed in seeds(args.seeds):
        for r in one_seed(h, cell, model, prog, seed, seed in control,
                          seed in fault, args.tiny):
            print(json.dumps(r), flush=True)
            allr.append(r)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(allr, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
