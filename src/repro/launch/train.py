"""End-to-end training driver: the paper's platform feeding a JAX trainer.

Flow (exactly Fig. 1 of the disclosure):
  1. raw text is checked into the dataset manager (pipeline A),
  2. a registered workflow (tokenize -> pack) produces the training
     snapshot (pipeline X),
  3. the trainer checks the snapshot out, trains with pjit on a mesh,
  4. checkpoints are checked back in as dataset versions with lineage
     (snapshot -> train run -> checkpoint), so revoking a raw record
     reports the checkpoints that transitively ingested it.

Fault tolerance: training resumes exactly from (checkpoint, loader state);
``--kill-at`` demonstrates a mid-run crash + restart recovering bit-exact.

``main`` runs the whole config (or ``--smoke``'s reduced one) in float32
on every local device; ``train`` is the same loop for a caller that brings
its own config, runtime and mesh, as ``chip_smoke.py`` does for a
depth-cut mamba2-1.3b in bf16 on one TPU chip.  The step is differentiated,
so it names XLA implementations: the Pallas kernels have no backward.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-1.3b \
        --steps 50 --smoke [--trace-out spans.json]
"""

from __future__ import annotations

import argparse
import os
import time
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs import get_config, get_smoke_config
from ..core import Pipeline, Record, Workflow
from ..core.lineage import NodeKind
from ..platform import Platform
from ..data import (DeviceFeed, PackComponent, ShardedSnapshotLoader,
                    SplitComponent, TokenizeComponent)
from ..models import RuntimeConfig, build_model
from ..train import (TrainConfig, load_checkpoint, make_train_step,
                     save_checkpoint)
from ..train.optimizer import OptimizerConfig, make_optimizer
from ..train.checkpoint import checkpoint_node_id, latest_step
from ..train.sharding import (ActivationSharding, ShardingRules, batch_specs,
                              named, opt_state_specs, param_specs)
from .mesh import make_local_mesh

REPO_ROOT = Path(__file__).resolve().parents[3]


def setup_compile_cache() -> str:
    """Keep JAX's persistent compilation cache at a fixed path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself); otherwise the cache goes to ``<repo>/.jax_cache``.  Call at the
    start of an entry point, before anything compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def synthetic_corpus(n_docs: int = 256, seed: int = 0):
    """Deterministic synthetic text corpus (no network in this container)."""
    rng = np.random.default_rng(seed)
    words = [f"w{i:03d}" for i in range(100)]
    docs = []
    for i in range(n_docs):
        n = int(rng.integers(20, 200))
        text = " ".join(rng.choice(words, size=n))
        docs.append(Record(f"doc-{i:05d}", text.encode(), {"lang": "en"}))
    return docs


def build_platform(seq_len: int, n_docs: int = 256, seed: int = 0):
    """Stand up the platform and run the Fig. 1 pipelines."""
    plat = Platform.open(actor="trainer")
    plat.dataset("corpus/raw").check_in(
        synthetic_corpus(n_docs, seed), actor="ingest",
        message="pipeline A: ingest")
    plat.register(Workflow(
        name="tokenize-pack",
        pipeline=Pipeline([SplitComponent(eval_fraction=0.0),
                           TokenizeComponent(),
                           PackComponent(seq_len=seq_len)], name="tok-pack"),
        input_dataset="corpus/raw",
        output_dataset="corpus/packed",
        n_shards=2,
    ))
    run = plat.run("tokenize-pack")
    assert run.state == "SUCCEEDED", run.error
    return plat, run


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-1.3b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic corpus and the weights")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="simulate a crash after N steps, then restart "
                         "from the platform checkpoint")
    ap.add_argument("--checkpoint-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--shuffle", default="auto",
                    choices=["auto", "global", "page_window"],
                    help="loader shuffle mode (auto: page-window streaming "
                         "above the size threshold, else legacy global)")
    ap.add_argument("--window-pages", type=int, default=8,
                    help="page-window shuffle width (pages per window)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record the program's spans (repro.obs) and write "
                         "them to PATH as Chrome trace events at exit")
    return ap


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)
    setup_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rt = RuntimeConfig(compute_dtype=jnp.float32, attn_impl="naive",
                       ssd_impl="xla", rglru_impl="xla")
    if not args.trace_out:
        return train(cfg, args, rt)
    obs.enable()
    try:
        return train(cfg, args, rt)
    finally:
        print(f"spans: {len(obs.spans())} -> {obs.export(args.trace_out)}")


def _tree_bit_equal(a, b) -> bool:
    def raw(x):
        return np.ascontiguousarray(np.asarray(x)).view(np.uint8)

    return all(np.array_equal(raw(x), raw(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def train(cfg, args: argparse.Namespace, rt: RuntimeConfig,
          mesh=None) -> dict:
    """Fig. 1 end to end: ingest, tokenize/pack, plan, feed, train, and
    check the checkpoints back in.  ``args`` are ``build_parser()``'s;
    ``mesh`` defaults to every local device."""
    for name in ("attn_impl", "ssd_impl", "rglru_impl"):
        impl = getattr(rt, name)
        if impl == "auto" or impl.startswith("pallas"):
            raise ValueError(f"{name}={impl!r}: the train step is "
                             "differentiated and the Pallas kernels have no "
                             "backward; name an XLA implementation")
    mesh = mesh if mesh is not None else make_local_mesh()
    rules = ShardingRules(mesh, batch_axes=("data",), fsdp_axis=None,
                          tp_axis=None)
    rt = rt.with_(act_sharding=ActivationSharding(rules))
    model = build_model(cfg, rt)

    t0 = time.perf_counter()
    plat, wf_run = build_platform(args.seq_len, n_docs=max(
        args.batch * 8, 128), seed=args.seed)
    dm = plat.manager
    snap = plat.dataset("corpus/packed").checkout()
    ingest_s = time.perf_counter() - t0
    print(f"platform: snapshot {snap.snapshot_id} with {len(snap)} packs "
          f"({ingest_s:.2f} s ingest + tokenize/pack)")

    # The loader feeds from the lazy plan (page-granular read surface; the
    # registered snapshot above carries lineage) — page-window streaming
    # never materializes the manifest, global mode is the legacy baseline.
    loader = ShardedSnapshotLoader(
        plat.dataset("corpus/packed").plan(), args.batch, args.seq_len,
        shuffle=args.shuffle, window_pages=args.window_pages)
    train_cfg = TrainConfig(optimizer=OptimizerConfig(
        name="adamw", lr=args.lr, warmup_steps=10, total_steps=args.steps))
    opt = make_optimizer(train_cfg.optimizer)

    key = jax.random.PRNGKey(args.seed)
    like_p = jax.eval_shape(lambda: model.init(key))
    like_o = jax.eval_shape(opt.init, like_p)
    pspecs = param_specs(like_p, rules)
    p_sh = named(mesh, pspecs)
    o_sh = named(mesh, opt_state_specs(like_o, like_p, pspecs, rules))
    n_params = sum(x.size for x in jax.tree.leaves(like_p))
    print(f"model: {cfg.name} {cfg.n_layers} layers d_model {cfg.d_model} "
          f"params {n_params} compute {jnp.dtype(rt.compute_dtype).name} "
          f"remat {rt.remat} on {mesh.devices.size} device(s)")
    print(f"step impls: attn={rt.attn_impl} ssd={rt.ssd_impl} "
          f"rglru={rt.rglru_impl}")
    params = jax.jit(model.init, out_shardings=p_sh)(key)
    opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)
    step_jit = jax.jit(make_train_step(model, train_cfg),
                       donate_argnums=(0, 1))
    run_node = f"train_run:{int(time.time())}"
    dm.lineage.add_node(run_node, NodeKind.WORKFLOW_RUN, kind_detail="train",
                        arch=cfg.name)
    dm.lineage.add_edge(snap.snapshot_id, run_node, "input_to")
    dm.lineage.flush()

    losses, step_s, save_s = [], [], []
    step = 0
    step_fn = None          # the train step, compiled for the first batch
    out = {"ingest_s": ingest_s, "n_params": n_params}

    from jax.sharding import NamedSharding

    def batch_shardings(host_batch):
        return {k: NamedSharding(mesh, s)
                for k, s in batch_specs(host_batch, rules).items()}

    def do_train(until: int):
        """Drive the step loop from the double-buffered device feed: the
        next batch's host decode AND device transfer overlap the current
        train_step, and each yielded batch carries the loader state that
        makes its checkpoint bit-exact to resume."""
        nonlocal params, opt_state, step, step_fn
        if step >= until:
            return
        feed_it = iter(DeviceFeed(loader, sharding_fn=batch_shardings))
        try:
            while step < until:
                batch, loader_state = next(feed_it)
                if step_fn is None:
                    t = time.perf_counter()
                    step_fn = step_jit.lower(params, opt_state,
                                             batch).compile()
                    out["compile_s"] = time.perf_counter() - t
                    out["memory"] = step_fn.memory_analysis()
                    out["first_batch"] = batch
                    print(f"compile: train step {out['compile_s']:.2f} s")
                t = time.perf_counter()
                with obs.span("train.dispatch"):
                    params, opt_state, metrics = step_fn(params, opt_state,
                                                         batch)
                with obs.span("train.loss_sync"):
                    losses.append(float(metrics["loss"]))   # waits for it
                step_s.append(time.perf_counter() - t)
                step += 1
                if step % args.log_every == 0 or step == until:
                    print(f"step {step:5d} loss {losses[-1]:.4f} "
                          f"({step_s[-1]:.3f} s)")
                if step % args.checkpoint_every == 0:
                    t = time.perf_counter()
                    with obs.span("train.save"):
                        cid = save_checkpoint(
                            dm, f"checkpoints/{cfg.name}", step, params,
                            opt_state, extra={"loader": loader_state},
                            data_snapshot_id=snap.snapshot_id,
                            run_node=run_node)
                    save_s.append(time.perf_counter() - t)
                    print(f"  checkpointed step {step} -> version {cid[:12]} "
                          f"({save_s[-1]:.2f} s)")
        finally:
            feed_it.close()   # stop decode workers; buffered batches drop

    if args.kill_at and args.kill_at < args.steps:
        do_train(args.kill_at)
        print(f"--- simulated crash at step {step}; restarting ---")
        # What the newest checkpoint holds, when it is of the crash step.
        saved = (jax.device_get((params, opt_state))
                 if latest_step(dm, f"checkpoints/{cfg.name}") == step
                 else None)
        del params, opt_state
        # Restart path: fresh process state, restore from the platform.
        t = time.perf_counter()
        params, opt_state, extra = load_checkpoint(
            dm, f"checkpoints/{cfg.name}", like_p, like_o,
            param_shardings=p_sh, opt_shardings=o_sh)
        jax.block_until_ready((params, opt_state))
        restore_s = time.perf_counter() - t
        loader.restore(extra["loader"])
        step = int(np.asarray(opt_state["step"]))
        bit_equal = (None if saved is None
                     else _tree_bit_equal(saved, (params, opt_state)))
        del saved
        out["restore"] = {"step": step, "seconds": restore_s,
                          "bit_equal": bit_equal, "loader": extra["loader"]}
        print(f"restored at step {step} in {restore_s:.2f} s, "
              f"bit-equal to the saved state: {bit_equal}, "
              f"loader {extra['loader']}")

    do_train(args.steps)

    with obs.span("train.save"):
        cid = save_checkpoint(dm, f"checkpoints/{cfg.name}", step, params,
                              opt_state, extra={"loader": loader.state()},
                              data_snapshot_id=snap.snapshot_id,
                              run_node=run_node)
    print(f"final checkpoint -> {cid[:12]}")
    ld_stats = loader.stats()
    print(f"loader: mode={ld_stats['mode']} "
          f"wait_fraction={ld_stats['wait_fraction']:.3f} "
          f"pages_streamed={int(ld_stats['pages_streamed'])} "
          f"peak_resident_ids={int(ld_stats['peak_resident_ids'])}")
    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    print(f"loss: first5={first:.4f} last5={last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    # lineage: the checkpoint's provenance reaches the raw corpus
    anc = dm.lineage.ancestors(checkpoint_node_id(f"checkpoints/{cfg.name}",
                                                  step))
    print(f"lineage ancestors of final checkpoint: {len(anc)} node(s)")
    out.update({"losses": losses, "step_s": step_s, "save_s": save_s,
                "steps": step, "dm": dm, "platform": plat, "checkpoint": cid,
                "improved": bool(last < first), "loader": loader,
                "loader_stats": ld_stats})
    return out


if __name__ == "__main__":
    main()
