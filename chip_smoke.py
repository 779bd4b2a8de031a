"""Run the Fig. 1 training path once on a TPU chip and check what it gives.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # data-parallel training: 4 chips vs 1
    python chip_smoke.py --tiny       # the same phases on the CPU, small

One chip runs mamba2-1.3b at its published widths with its depth cut to
fit, through the entry points a user calls: ``Platform`` ingest, the
tokenize-pack workflow, ``plan()``, ``ShardedSnapshotLoader``,
``DeviceFeed``, the jitted train step and the checkpoint check-in.  Phases:

  (a) ingest and pack;
  (b) train steps: every loss finite, the last below the first;
  (c) the first step's bf16 loss against a float32 forward at
      ``precision="highest"`` on the same batch and weights;
  (d) checkpoint save, simulated crash, restore and resume (``--kill-at``):
      the restored state is bit-equal to the saved one and the loader
      resumes its stream;
  (e) each Pallas kernel's forward, compiled for the chip at real widths,
      against its ``ref.py``.

``--chips 4`` runs only the same train path on a 4-device mesh and on a
1-device mesh in this process, and compares their first losses.  Any failed
phase raises, so the exit code is non-zero; so is finding no TPU, unless
``--tiny``.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch import train as train_mod  # noqa: E402

ARCH = "mamba2-1.3b"
KEEP_LAYERS = 16          # of mamba2-1.3b's 48: weights + Adam state fit 16 GB
LOSS_RTOL = 1e-2          # bf16 step loss vs float32 "highest" forward
KERNEL_BOUND = 2e-2       # max |kernel - ref| / max |ref|, bf16 outputs


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="smoke configs and interpret-mode kernels, on CPU")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the data-parallel phase, 4 chips vs 1")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus, the weights and kernel inputs")
    return ap.parse_args(argv)


def run_args(tiny: bool, seed: int, steps: int, kill_at=None):
    """The train driver's own arguments for this run."""
    argv = ["--arch", ARCH, "--steps", str(steps), "--batch", "4",
            "--seq-len", "64" if tiny else "2048", "--seed", str(seed),
            "--log-every", "1",
            "--checkpoint-every", str(kill_at or steps + 1)]
    if kill_at:
        argv += ["--kill-at", str(kill_at)]
    return train_mod.build_parser().parse_args(argv)


def model_config(tiny: bool):
    from repro.configs import get_config, get_smoke_config

    if tiny:
        return get_smoke_config(ARCH)
    return dataclasses.replace(get_config(ARCH), n_layers=KEEP_LAYERS)


def train_runtime():
    import jax.numpy as jnp

    from repro.models import RuntimeConfig

    # The step is differentiated: name the XLA paths, never "auto".
    return RuntimeConfig(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                         attn_impl="xla", ssd_impl="xla", rglru_impl="xla",
                         remat="full")


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def phase_train(cfg, args, tiny: bool):
    """(a), (b), (d): the driver end to end, with a crash and a resume."""
    import numpy as np

    from repro.configs import get_config, get_smoke_config
    from repro.data import ShardedSnapshotLoader
    from repro.launch.mesh import make_local_mesh

    full = get_smoke_config(ARCH) if tiny else get_config(ARCH)
    print(f"config: {cfg.name} layers {cfg.n_layers} of {full.n_layers}, "
          f"d_model {cfg.d_model}, d_inner {cfg.d_inner}, ssd heads "
          f"{cfg.ssm_heads}x{cfg.ssm_head_dim}, state {cfg.ssm_state}, conv "
          f"{cfg.ssm_conv_width}, chunk {cfg.ssm_chunk}, vocab "
          f"{cfg.vocab_size}; batch {args.batch} x seq {args.seq_len}")
    out = train_mod.train(cfg, args, train_runtime(), make_local_mesh(1))

    # (a) ingest and pack
    packs = len(out["platform"].dataset("corpus/packed").checkout())
    check(packs >= args.batch, f"(a) only {packs} packs")
    print(f"(a) ingest+pack: {packs} packs in {out['ingest_s']:.3f} s")

    # (b) train steps
    losses = out["losses"]
    check(len(losses) == args.steps,
          f"(b) {len(losses)} of {args.steps} steps")
    check(all(np.isfinite(losses)), f"(b) non-finite loss in {losses}")
    check(losses[-1] < losses[0], f"(b) loss did not fall: {losses}")
    mem = out["memory"]
    print(f"(b) params {out['n_params']}; compile {out['compile_s']:.3f} s; "
          f"step s {[round(s, 4) for s in out['step_s']]}; "
          f"losses {[round(x, 5) for x in losses]}")
    print(f"(b) step program bytes: argument {mem.argument_size_in_bytes} "
          f"output {mem.output_size_in_bytes} alias {mem.alias_size_in_bytes} "
          f"temp {mem.temp_size_in_bytes}")

    # (d) checkpoint round trip and resume
    rs = out["restore"]
    check(rs["step"] == args.kill_at,
          f"(d) resumed at step {rs['step']}, not {args.kill_at}")
    check(rs["bit_equal"] is True, "(d) restored state differs from saved")
    plan = out["platform"].dataset("corpus/packed").plan()

    def fresh():
        return ShardedSnapshotLoader(plan, args.batch, args.seq_len,
                                     shuffle=args.shuffle,
                                     window_pages=args.window_pages)

    stream = fresh()
    for _ in range(args.kill_at):
        stream.next_batch()
    resumed = fresh()
    resumed.restore(rs["loader"])
    want, got = stream.next_batch(), resumed.next_batch()
    check(all(np.array_equal(want[k], got[k]) for k in want),
          "(d) the restored loader does not resume the stream")
    print(f"(d) checkpoint save s {[round(s, 3) for s in out['save_s']]}; "
          f"restore {rs['seconds']:.3f} s at step {rs['step']}; params and "
          f"optimizer state bit-equal; loader resumes at batch "
          f"{args.kill_at}")
    return out


def phase_reference(cfg, args, out) -> None:
    """(c) first bf16 step loss vs a float32 "highest" forward."""
    import jax

    from repro.models import build_model

    ref_rt = train_runtime().with_(compute_dtype=jax.numpy.float32)
    model = build_model(cfg, ref_rt)
    params0 = jax.jit(model.init)(jax.random.PRNGKey(args.seed))
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(lambda p, b: model.loss(p, b)[0])(
            params0, out["first_batch"]))
    got = out["losses"][0]
    rel = abs(got - ref) / abs(ref)
    print(f"(c) first-step loss bf16 {got:.6f} vs float32 highest "
          f"{ref:.6f}: rel diff {rel:.3e} (bound {LOSS_RTOL})")
    check(rel <= LOSS_RTOL, f"(c) rel diff {rel} > {LOSS_RTOL}")


def phase_kernels(tiny: bool, seed: int) -> None:
    """(e) each Pallas kernel's forward against its reference."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention import (attention_reference,
                                               flash_attention)
    from repro.kernels.rglru import rglru, rglru_reference
    from repro.kernels.ssd import ssd, ssd_reference

    impl = "pallas_interpret" if tiny else "pallas"
    B, S = 2, (128 if tiny else 2048)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, dtype=jnp.bfloat16, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def run(name, widths, kernel_fn, ref_fn, args):
        t = time.perf_counter()
        compiled = jax.jit(kernel_fn).lower(*args).compile()
        compile_s = time.perf_counter() - t
        t = time.perf_counter()
        got = jax.block_until_ready(compiled(*args))
        run_s = time.perf_counter() - t
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref_fn)(*args)
        errs = [rel_err(g, w) for g, w in zip(jax.tree.leaves(got),
                                              jax.tree.leaves(want))]
        print(f"(e) {name} {impl} {widths}: compile {compile_s:.3f} s, "
              f"first run {run_s:.4f} s, max err / max |ref| "
              f"{[f'{e:.3e}' for e in errs]} (bound {KERNEL_BOUND})")
        check(max(errs) <= KERNEL_BOUND, f"(e) {name} error {errs}")

    # SSD at mamba2-1.3b widths: 64 heads of 64, state 128, chunk 256.
    H, P, N, chunk = (4, 16, 32, 32) if tiny else (64, 64, 128, 256)
    log_a = jnp.log(jax.nn.sigmoid(normal((B, S, H), jnp.float32)) * 0.5
                    + 0.5)
    run("ssd", f"B{B} S{S} H{H} P{P} N{N} chunk{chunk}",
        lambda x, la, b, c, s0: ssd(x, la, b, c, s0, chunk=chunk, impl=impl),
        ssd_reference,
        (normal((B, S, H, P)), log_a, normal((B, S, N), scale=0.3),
         normal((B, S, N), scale=0.3),
         normal((B, H, P, N), jnp.float32, 0.1)))

    # Flash attention with packed segments at qwen2.5-32b widths.
    Hq, Hkv, D, blk = (4, 2, 32, 64) if tiny else (40, 8, 128, 128)
    seg = jnp.cumsum(jax.random.uniform(next(keys), (B, S)) < 0.002,
                     axis=1).astype(jnp.int32)
    run("flash_attention", f"B{B} S{S} Hq{Hq} Hkv{Hkv} D{D} segments",
        lambda q, k, v, s: flash_attention(
            q, k, v, causal=True, q_segments=s, kv_segments=s, impl=impl,
            block_q=blk, block_k=blk),
        lambda q, k, v, s: attention_reference(
            q, k, v, causal=True, q_segments=s, kv_segments=s),
        (normal((B, S, Hq, D)), normal((B, S, Hkv, D)),
         normal((B, S, Hkv, D)), seg))

    # RG-LRU at recurrentgemma-9b's lru_width.
    W, chunk = (128, 32) if tiny else (4096, 256)
    run("rglru", f"B{B} S{S} W{W}",
        lambda x, r, i, lam, h0: rglru(x, r, i, lam, h0, chunk=chunk,
                                       impl=impl),
        rglru_reference,
        (normal((B, S, W)), normal((B, S, W)), normal((B, S, W)),
         normal((W,), jnp.float32), normal((B, W), jnp.float32, 0.2)))


def phase_data_parallel(cfg, tiny: bool, seed: int) -> None:
    """Four chips vs one, same seed and global batch stream."""
    from repro.launch.mesh import make_local_mesh

    steps = 3
    losses = {}
    for n in (1, 4):
        out = train_mod.train(cfg, run_args(tiny, seed, steps),
                              train_runtime(), make_local_mesh(n))
        losses[n] = out["losses"]
        print(f"data-parallel {n} device(s): compile {out['compile_s']:.3f} "
              f"s; step s {[round(s, 4) for s in out['step_s']]}; losses "
              f"{[round(x, 6) for x in losses[n]]}")
        del out     # its platform holds a checkpoint in host memory
    rel = [abs(a - b) / abs(b) for a, b in zip(losses[4], losses[1])]
    print(f"data-parallel 4 vs 1: rel diff {[f'{r:.3e}' for r in rel]} "
          f"(bound {LOSS_RTOL})")
    check(len(rel) == steps and max(rel) <= LOSS_RTOL,
          f"4-device losses {losses[4]} vs 1-device {losses[1]}")


def main(argv=None) -> int:
    args = parse_args(argv)
    train_mod.setup_compile_cache()
    import jax

    devices = jax.devices()
    if not args.tiny and devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}; --tiny rehearses "
              "on the CPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips}: only {len(devices)} device(s)",
              file=sys.stderr)
        return 2
    print(f"device: {devices[0].platform} {devices[0].device_kind} "
          f"x{len(devices)}")
    cfg = model_config(args.tiny)
    if args.chips == 4:
        phase_data_parallel(cfg, args.tiny, args.seed)
    else:
        steps, kill_at = 7, 4
        run = run_args(args.tiny, args.seed, steps, kill_at)
        out = phase_train(cfg, run, args.tiny)
        phase_reference(cfg, run, out)
        del out
        phase_kernels(args.tiny, args.seed)
    stats = devices[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    print(f"peak_bytes_in_use: {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
