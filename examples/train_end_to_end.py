"""End-to-end driver: platform -> snapshot -> train a ~100M-param model for
a few hundred steps on CPU, with mid-run checkpoint/restart through the
dataset manager.

The ~100M config is the stablelm family reduced to ~100M params (the same
code path as the full config).

Run:  PYTHONPATH=src python examples/train_end_to_end.py [--steps 200]
"""

import argparse
import dataclasses
import sys

sys.path.insert(0, "src")

from repro.configs import get_config
from repro.launch import train as train_mod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--kill-at", type=int, default=None)
    args = ap.parse_args()

    # ~100M-param member of the stablelm family: 8L, d=512, ff=2048.
    base = get_config("stablelm-1.6b")
    cfg100m = dataclasses.replace(
        base, n_layers=8, d_model=512, n_heads=8, n_kv_heads=8, head_dim=64,
        d_ff=2048, vocab_size=512)
    n = cfg100m.n_params()
    print(f"training config: {n/1e6:.1f}M params (stablelm family)")

    import repro.configs as configs

    # register the reduced config under a temporary arch id
    configs._MODULES["stablelm-100m"] = type(
        "M", (), {"CONFIG": cfg100m, "smoke_config": staticmethod(
            lambda: cfg100m)})

    argv = ["--arch", "stablelm-100m", "--steps", str(args.steps),
            "--batch", "8", "--seq-len", "128", "--lr", "1e-3",
            "--checkpoint-every", "50", "--log-every", "20"]
    if args.kill_at:
        argv += ["--kill-at", str(args.kill_at)]
    out = train_mod.main(argv)
    assert out["improved"], "loss did not improve"
    stats = out["loader_stats"]
    print(f"loader stats: mode={stats['mode']} "
          f"wait_fraction={stats['wait_fraction']:.3f} "
          f"batches={int(stats['batches'])} "
          f"pages_streamed={int(stats['pages_streamed'])} "
          f"peak_resident_ids={int(stats['peak_resident_ids'])}")
    print("OK: end-to-end training improved the loss and checkpointed "
          "through the platform")


if __name__ == "__main__":
    main()
