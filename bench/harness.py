"""One run of one cell: the platform-fed training loop, timed and checked.

The entry the window drives is the program's own, in the order
``launch/train.py:train`` uses it: ``Platform.open`` and ``check_in`` of
the raw documents, the registered tokenize -> pack workflow, ``plan()`` ->
``ShardedSnapshotLoader`` -> ``DeviceFeed``, and ``build_model`` /
``make_optimizer`` / ``make_train_step`` compiled ahead of time.  The
harness owns only the client loop: next batch from the feed, step, loss on
the host.  In the window about ``AHEAD_S`` seconds of steps are dispatched
ahead of the loss the host waits for; when its time is up the window sends
nothing more, waits for every step sent, and reads the clock after that.

Set-up drives the compiled step through its first three steps on the
window's own feed and keeps what the check needs: each step's loss, the
first gradient as the optimizer holds it (AdamW's first moment after one
step, over 1 - b1), and the parameters' change after three steps.  The
window then goes on from step four with the same state.  After the window
the program's state is freed and the plain reference follows the same
three steps from the same seed.
"""

from __future__ import annotations

import gc
import hashlib
import json
import shutil
import sys
import time
from collections import deque
from dataclasses import dataclass, fields
from pathlib import Path
from typing import Any, Callable, Deque, Dict, List, Optional

import numpy as np

import corpus
import devtrace
import flops
import peaks
import reference
import weights
from spec import BENCH, Cell

N_CHECKED = 3                 # setup steps the reference follows
TRACE_STEPS = 8               # window steps in the traced run
AHEAD_S = 6.0                 # seconds of window steps dispatched ahead


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ sizes

def sized(cell: Cell, tiny: bool):
    """(model group, traffic) of the cell; ``--tiny`` swaps in the CPU
    rehearsal sizes of ``bench/tiny.json``."""
    model = dict(cell.config["model"])
    traffic = dict(cell.traffic)
    if tiny:
        small = json.loads((BENCH / "tiny.json").read_text())
        model.update(small["model"][model["pattern"][0]])
        traffic.update(small["traffic"])
    return model, traffic


def limits(cell: Cell, tiny: bool) -> Dict:
    """The cell's limits; ``--tiny`` runs are held to the rehearsal's own,
    set from readings at the tiny sizes."""
    if tiny:
        return json.loads((BENCH / "tiny.json").read_text())["limits"]
    return cell.limits


def ref_model(cell: Cell, model: Dict) -> Dict:
    """The model group with the numerics the reference needs."""
    return {**model, **cell.config["numerics"]}


def freeze(d: Dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()))


# --------------------------------------------------------------- platform

@dataclass
class Corpus:
    plat: Any
    loader: Any
    snapshot_id: str
    docs: List
    seconds: float
    new_loader: Callable[[], Any]


def platform_setup(traffic: Dict, chips: int, seed: int) -> Corpus:
    """Ingest and derive: check the raw documents in, run the registered
    tokenize -> pack workflow, plan the packed dataset and open the
    loader, as ``launch/train.py`` does."""
    from repro import Platform
    from repro.core import Pipeline, Record, Workflow
    from repro.data import (PackComponent, ShardedSnapshotLoader,
                            SplitComponent, TokenizeComponent)

    t = time.perf_counter()
    docs = corpus.documents(traffic, chips, seed)
    plat = Platform.open(actor="trainer")
    plat.dataset("corpus/raw").check_in(
        [Record(rid, text, {"lang": "en"}) for rid, text in docs],
        actor="ingest", message="pipeline A: ingest")
    plat.register(Workflow(
        name="tokenize-pack",
        pipeline=Pipeline([SplitComponent(eval_fraction=0.0),
                           TokenizeComponent(),
                           PackComponent(seq_len=traffic["seq_len"])],
                          name="tok-pack"),
        input_dataset="corpus/raw", output_dataset="corpus/packed",
        n_shards=traffic["workflow_shards"]))
    run = plat.run("tokenize-pack")
    if run.state != "SUCCEEDED":
        raise RuntimeError(f"tokenize-pack workflow: {run.error}")
    snap = plat.dataset("corpus/packed").checkout()
    plan = plat.dataset("corpus/packed").plan()

    def new_loader():
        return ShardedSnapshotLoader(
            plan, traffic["batch_per_chip"] * chips, traffic["seq_len"],
            shuffle=traffic["loader"]["shuffle"],
            window_pages=traffic["loader"]["window_pages"])

    return Corpus(plat, new_loader(), snap.snapshot_id, docs,
                  time.perf_counter() - t, new_loader)


def reference_packs(docs, seq_len: int) -> Dict[str, np.ndarray]:
    """The packs a plain reading of tokenize -> pack gives: byte tokens
    (+3) between BOS=1 and EOS=2, documents in record-id order, one running
    document index as the segment, positions restarting per document, cut
    into rows of ``seq_len + 1`` and padded with token 0, segment -1."""
    L = seq_len + 1
    toks, segs, poss = [], [], []
    for i, (_, text) in enumerate(docs):
        ids = np.frombuffer(text, np.uint8).astype(np.int32) + 3
        ids = np.concatenate([[1], ids, [2]]).astype(np.int32)
        toks.append(ids)
        segs.append(np.full(ids.size, i, np.int32))
        poss.append(np.arange(ids.size, dtype=np.int32))
    T, S, P = (np.concatenate(x) for x in (toks, segs, poss))
    pad = -T.size % L
    T = np.pad(T, (0, pad)).reshape(-1, L)
    S = np.pad(S, (0, pad), constant_values=-1).reshape(-1, L)
    P = np.pad(P, (0, pad)).reshape(-1, L)
    return {"tokens": T[:, :seq_len],
            "labels": np.where(S[:, :seq_len] >= 0, T[:, 1:], -1),
            "segments": S[:, :seq_len], "positions": P[:, :seq_len]}


ROW_KEYS = ("tokens", "labels", "segments", "positions")


def epoch_rows(loader) -> int:
    """Rows the loader delivers per epoch (it drops the ragged tail)."""
    return loader.snapshot.count() // loader.batch * loader.batch


def row_key(rows: Dict[str, np.ndarray], i: int) -> bytes:
    h = hashlib.sha1()
    for k in ROW_KEYS:
        h.update(np.ascontiguousarray(rows[k][i], np.int32).tobytes())
    return h.digest()


def check_rows(ref: Dict[str, np.ndarray], delivered: List[Dict],
               per_epoch: int):
    """(rows that are no reference pack, or repeat a row of the same epoch,
    the reference's index of every delivered row or -1).  An epoch is
    ``per_epoch`` rows: the loader's batches per epoch times the batch."""
    index = {row_key(ref, i): i for i in range(ref["tokens"].shape[0])}
    bad, seen, where = 0, set(), []
    for batch in delivered:
        for r in range(batch["tokens"].shape[0]):
            if len(where) % per_epoch == 0:
                seen = set()
            i = index.get(row_key(batch, r), -1)
            bad += i < 0 or i in seen
            seen.add(i)
            where.append(i)
    return bad, where


# ---------------------------------------------------------------- program

class Program:
    """The system under test, built once for a cell: model, optimizer,
    shardings, and the train step compiled for the feed's batches."""

    def __init__(self, cell: Cell, model: Dict, chips: int):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        from repro.configs.base import ModelConfig
        from repro.launch.mesh import make_local_mesh
        from repro.models import RuntimeConfig, build_model
        from repro.train import TrainConfig, make_train_step
        from repro.train.optimizer import OptimizerConfig, make_optimizer
        from repro.train.sharding import (ActivationSharding, ShardingRules,
                                          batch_specs, named,
                                          opt_state_specs, param_specs)

        mc = {k: v for k, v in model.items()
              if k in {f.name for f in fields(ModelConfig)}}
        mc["pattern"] = tuple(mc["pattern"])
        self.cfg = ModelConfig(**mc)
        rt = cell.config["runtime"]
        self.mesh = make_local_mesh(chips)
        rules = ShardingRules(self.mesh, batch_axes=("data",),
                              fsdp_axis=None, tp_axis=None)
        self.rt = RuntimeConfig(
            param_dtype=jnp.dtype(rt["param_dtype"]),
            compute_dtype=jnp.dtype(rt["compute_dtype"]),
            attn_impl=rt["attn_impl"], ssd_impl=rt["ssd_impl"],
            rglru_impl=rt["rglru_impl"], remat=rt["remat"],
            act_sharding=ActivationSharding(rules))
        self.model = build_model(self.cfg, self.rt)
        o = cell.config["optimizer"]
        self.opt_cfg = OptimizerConfig(**{
            k: v for k, v in o.items()
            if k in {f.name for f in fields(OptimizerConfig)}})
        self.b1 = o["b1"]
        self.opt = make_optimizer(self.opt_cfg)
        self.train_cfg = TrainConfig(optimizer=self.opt_cfg)

        key = weights.root_key(0)
        like_p = jax.eval_shape(self.model.init, key)
        mine = jax.eval_shape(lambda k: weights.make_params(model, k), key)
        if (jax.tree.structure(like_p) != jax.tree.structure(mine)
                or any(a.shape != b.shape for a, b in zip(
                    jax.tree.leaves(like_p), jax.tree.leaves(mine)))):
            raise RuntimeError("the benchmark's weight layout no longer "
                               "matches the program's parameter tree")
        self.like_p = like_p
        self.like_o = jax.eval_shape(self.opt.init, like_p)
        pspecs = param_specs(like_p, rules)
        self.p_sh = named(self.mesh, pspecs)
        self.o_sh = named(self.mesh, opt_state_specs(self.like_o, like_p,
                                                     pspecs, rules))
        self.n_params = sum(x.size for x in jax.tree.leaves(like_p))
        self._init_p = jax.jit(lambda k: weights.make_params(model, k),
                               out_shardings=self.p_sh)
        self._init_o = jax.jit(self.opt.init, out_shardings=self.o_sh)
        self._g1 = jax.jit(lambda o: weights.leaf_norms(o["m"]) / (1 - self.b1))
        self._delta = jax.jit(lambda p, k: weights.leaf_norms(jax.tree.map(
            jnp.subtract, p, weights.make_params(model, k))))
        self._step_jit = jax.jit(make_train_step(self.model, self.train_cfg),
                                 donate_argnums=(0, 1))
        self.step = None
        self.memory = None

        def batch_shardings(host_batch):
            return {k: NamedSharding(self.mesh, s)
                    for k, s in batch_specs(host_batch, rules).items()}

        self.batch_shardings = batch_shardings

    def init_state(self, seed: int):
        key = weights.root_key(seed)
        params = self._init_p(key)
        return params, self._init_o(params)

    def compile(self, params, opt_state, batch) -> float:
        t = time.perf_counter()
        self.step = self._step_jit.lower(params, opt_state, batch).compile()
        self.memory = self.step.memory_analysis()
        return time.perf_counter() - t

    def feed(self, loader):
        from repro.data import DeviceFeed

        return iter(DeviceFeed(loader, sharding_fn=self.batch_shardings))

    def g1_norms(self, opt_state) -> np.ndarray:
        return np.asarray(self._g1(opt_state))

    def delta_norms(self, params, seed: int) -> np.ndarray:
        return np.asarray(self._delta(params, weights.root_key(seed)))



# ------------------------------------------------------------- client loop

@dataclass
class Step:
    loss: float
    batch: Any
    loader_state: Dict
    t_ask: float          # host asks the feed for the batch
    t_got: float          # feed returned it
    t_sent: float         # step dispatched
    t_done: float         # loss on the host


def dispatch(prog: Program, feed_it, state, fault=None):
    """Next batch -> step, without waiting for it.  Returns (new state,
    Step with no loss yet, the step's metrics on the device)."""
    from jax.profiler import TraceAnnotation

    params, opt_state = state
    t_ask = time.perf_counter()
    with TraceAnnotation("feed_wait"):
        batch, loader_state = next(feed_it)
    t_got = time.perf_counter()
    if prog.step is None:
        prog.compile(params, opt_state, batch)
    with TraceAnnotation("dispatch"):
        params, opt_state, m = prog.step(params, opt_state,
                                         fault(batch) if fault else batch)
    return (params, opt_state), Step(float("nan"), batch, loader_state, t_ask,
                                     t_got, time.perf_counter(), 0.0), m


def settle(s: Step, m) -> None:
    """Wait for a dispatched step's loss on the host."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("loss_sync"):
        s.loss = float(m["loss"])
    s.t_done = time.perf_counter()


def client_step(prog: Program, feed_it, state, fault=None):
    """One turn of the client loop: next batch -> step -> loss on the
    host.  Returns (new state, Step)."""
    state, s, m = dispatch(prog, feed_it, state, fault)
    settle(s, m)
    return state, s


@dataclass
class FirstSteps:
    steps: List[Step]
    g1: np.ndarray
    delta: np.ndarray


def first_steps(prog: Program, feed_it, state, seed: int, fault=None):
    """Drive the program from the seed through the steps the reference
    follows; keep the readings the check compares."""
    steps, g1 = [], None
    for i in range(N_CHECKED):
        state, s = client_step(prog, feed_it, state, fault)
        steps.append(s)
        if i == 0:
            g1 = prog.g1_norms(state[1])
    delta = prog.delta_norms(state[0], seed)
    return state, FirstSteps(steps, g1, delta)


def host_batch(batch) -> Dict[str, np.ndarray]:
    return {k: np.asarray(batch[k]) for k in ROW_KEYS}


# -------------------------------------------------------------- reference

def reference_readings(cell: Cell, model: Dict, seed: int,
                       batches: List[Dict], quant: Optional[str] = None):
    """Losses of the first steps, the first clipped gradient's leaf norms
    and the leaf norms of the change after them, from the plain float32
    reference (``quant="fp8"``: the control) on device 0."""
    import jax
    import jax.numpy as jnp

    m = ref_model(cell, model)
    o = cell.config["optimizer"]
    dev = jax.devices()[0]
    key = weights.root_key(seed)
    with jax.default_device(dev):
        return _reference(model, m, o, key, batches, quant)


def _reference(model, m, o, key, batches, quant):
    import jax
    import jax.numpy as jnp

    params = jax.jit(lambda k: weights.make_params(model, k))(key)
    m1 = jax.tree.map(jnp.zeros_like, params)
    m2 = jax.tree.map(jnp.zeros_like, params)
    losses, g1 = [], None
    for t, rows in enumerate(batches, 1):
        rows = {k: jnp.asarray(v) for k, v in rows.items()}
        params, m1, m2, loss, gn = reference.train_step(
            params, rows, m1, m2, jnp.float32(t), m=freeze(m), o=freeze(o),
            quant=quant, block_rows=1)
        losses.append(float(loss))
        if t == 1:
            g1 = np.asarray(gn)
    del m1, m2
    delta = np.asarray(jax.jit(lambda p, k: weights.leaf_norms(jax.tree.map(
        jnp.subtract, p, weights.make_params(model, k))))(params, key))
    return losses, g1, delta


def worst_gap(got: np.ndarray, want: np.ndarray,
              keep: Optional[np.ndarray] = None) -> float:
    """Largest |got - want| over leaves, each against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    den = np.maximum(want, np.median(want))
    gap = np.abs(got - want) / den
    if keep is not None:
        gap = gap[keep]
    return float(np.max(gap)) if gap.size else 0.0


def readings(prog_losses, prog_g1, prog_delta, ref) -> Dict[str, float]:
    losses, g1, delta = ref
    keep = g1 >= 1e-3 * np.median(g1)     # leaves the reference moves
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(prog_losses,
                                                            losses)),
        "grad_gap": worst_gap(prog_g1, g1),
        "update_gap": worst_gap(prog_delta, delta, keep),
    }


# -------------------------------------------------------------------- run

class CompileCounter:
    """Counts traces and backend compilations while ``active``."""

    def __init__(self):
        import jax

        self.active, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if self.active and event.endswith(("backend_compile_duration",
                                           "jaxpr_trace_duration")):
            self.n += 1


def device_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(cell: Cell, seed: int, seconds: float, trace: bool, tiny: bool,
        t_start: float, root: Path) -> Dict:
    import jax
    from jax.profiler import TraceAnnotation

    chips = cell.chips
    devices = jax.devices()[:chips]
    model, traffic = sized(cell, tiny)
    seq, batch = traffic["seq_len"], traffic["batch_per_chip"] * chips
    compiles = CompileCounter()

    data = platform_setup(traffic, chips, seed)
    log(f"platform: {len(data.docs)} documents, "
        f"{data.loader.snapshot.count()} packs of {seq} in "
        f"{data.seconds:.3f} s")
    prog = Program(cell, model, chips)
    state = prog.init_state(seed)
    feed_it = prog.feed(data.loader)
    state, first = first_steps(prog, feed_it, state, seed)
    mem = prog.memory
    log(f"program: {prog.n_params} params on {chips} chip(s); step bytes "
        f"argument {mem.argument_size_in_bytes} output "
        f"{mem.output_size_in_bytes} alias {mem.alias_size_in_bytes} temp "
        f"{mem.temp_size_in_bytes}")
    setup_s = time.perf_counter() - t_start
    # Keep about AHEAD_S of steps in flight, so that a host that stands
    # still for a few seconds does not leave the chip idle; the last
    # checked step, which compiles nothing, gives the step's time.
    last = first.steps[-1]
    ahead = int(np.clip(np.ceil(AHEAD_S / (last.t_done - last.t_ask)),
                        1, 64))
    log(f"window: {ahead} step(s) in flight")

    # ---- the window
    trace_dir = root / ".bench_trace"
    trace_from = 2
    traced = None
    window: List[Step] = []
    gc_pauses: List[float] = []
    gc_t = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_t[0] = time.perf_counter()
        elif info.get("generation") == 2:
            gc_pauses.append(time.perf_counter() - gc_t[0])

    pending: Deque = deque()            # dispatched steps, loss not read

    def drain():
        while pending:
            settle(*pending.popleft())

    gc.callbacks.append(on_gc)
    compiles.active = True
    t0 = time.perf_counter()
    while True:
        i = len(window)
        if trace and i == trace_from:
            drain()
            jax.block_until_ready(state)
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            traced = TraceAnnotation(devtrace.WINDOW_SPAN)
            traced.__enter__()
            traced_t0 = time.perf_counter()
        state, s, m = dispatch(prog, feed_it, state)
        window.append(s)
        pending.append((s, m))
        if trace and traced is not None and i + 1 == trace_from + TRACE_STEPS:
            drain()
            jax.block_until_ready(state)
            traced_s = time.perf_counter() - traced_t0
            traced.__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced = None
        while len(pending) > ahead:
            settle(*pending.popleft())
        if time.perf_counter() - t0 >= seconds and traced is None:
            break
    drain()
    jax.block_until_ready(state)
    t_end = time.perf_counter()
    compiles.active = False
    gc.callbacks.remove(on_gc)
    log(f"full collections in the window: {len(gc_pauses)}, "
        f"{sum(gc_pauses):.4f} s, longest {max(gc_pauses, default=0):.4f} s")
    feed_it.close()
    peak = device_peak(devices)
    log(f"window: {len(window)} steps in {t_end - t0:.3f} s; "
        f"{compiles.n} trace(s) or compilation(s) inside it; peak {peak} B")
    done = [t0] + [s.t_done for s in window]
    step_s = [b - a for a, b in zip(done, done[1:])]
    slow = sorted(range(len(window)), key=lambda i: -step_s[i])[:3]
    log("longest steps (index, seconds since the loss before, feed wait, "
        "dispatch): " + ", ".join(
            f"({i}, {step_s[i]:.4f}, {window[i].t_got - window[i].t_ask:.4f}, "
            f"{window[i].t_sent - window[i].t_got:.4f})" for i in slow))
    losses = [s.loss for s in first.steps + window]
    bad_steps = [i for i, x in enumerate(losses) if not np.isfinite(x)]
    log("loss every 10th step: " + " ".join(
        f"{x:.4f}" for x in losses[::10]))
    if bad_steps:
        log(f"non-finite loss from step {bad_steps[0]} on "
            f"({len(bad_steps)} steps); losses before it "
            f"{[round(x, 4) for x in losses[max(0, bad_steps[0] - 4):bad_steps[0]]]}")

    delivered = [host_batch(s.batch) for s in first.steps + window]
    del state, s
    for s in first.steps + window:
        s.batch = None
    prog.step = None
    gc.collect()

    # ---- correctness, after the window
    numbers: Dict[str, float] = {"steps_nonfinite": len(bad_steps)}
    ref_rows = reference_packs(data.docs, seq)
    bad, where = check_rows(ref_rows, delivered, epoch_rows(data.loader))
    numbers["rows_bad"] = bad
    ref_batches = []
    for b in range(N_CHECKED):
        idx = where[b * batch:(b + 1) * batch]
        ref_batches.append({k: np.stack([
            ref_rows[k][i] if i >= 0 else delivered[b][k][r]
            for r, i in enumerate(idx)]) for k in ROW_KEYS})
    t = time.perf_counter()
    ref = reference_readings(cell, model, seed, ref_batches)
    log(f"reference: {time.perf_counter() - t:.3f} s; losses program "
        f"{[s.loss for s in first.steps]} reference {ref[0]}")
    numbers.update(readings([s.loss for s in first.steps], first.g1,
                            first.delta, ref))
    held = {"steps_nonfinite": 0, **limits(cell, tiny)}
    checks = {k: {"value": v, "limit": held.get(k)}
              for k, v in numbers.items()}
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    # ---- metrics
    window_s = t_end - t0
    tokens = len(window) * batch * seq
    raw = {
        "setup_s": setup_s,
        "platform_setup_s": data.seconds,
        "window_s": window_s,
        "chips": chips,
        "tokens": tokens,
        "step_s": step_s,
        "feed_wait_s": [s.t_got - s.t_ask for s in window],
        "flops_per_token": flops.per_token(model, seq),
        "peak_flops": None,
        "trace": None,
    }
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": chips, "memory_peak_bytes": peak}
    result: Dict[str, Any] = {
        "correct": correct, "attempted": len(window),
        "failed": sum(not np.isfinite(s.loss) for s in window)}
    breakdown = None
    if trace:
        path = devtrace.find_xplane(str(trace_dir))
        if path:
            dev_ev, host_ev, inventory = devtrace.events_from_profile(path)
            log(f"trace planes: {json.dumps(inventory)[:3000]}")
            raw["trace"] = devtrace.reduce(dev_ev, host_ev)
        shutil.rmtree(trace_dir, ignore_errors=True)
        raw["traced_steps"] = TRACE_STEPS
        raw["traced_s"] = traced_s
        raw["traced_tokens"] = TRACE_STEPS * batch * seq
    metrics: Dict[str, Dict] = {}
    if not tiny:
        raw["peak_flops"] = peaks.peaks(devices[0].device_kind)[
            "bf16_flops_per_s"]
        from spec import reader

        for m in (cell.per_layer if trace else cell.end_to_end):
            value = reader(m["name"], root)(raw)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if trace and raw["trace"]:
            device["busy_s"] = raw["trace"]["busy_s"]
            device["window_s"] = raw["trace"]["window_s"]
            breakdown = {"device_ops": raw["trace"]["device_ops"],
                         "idle_gaps": raw["trace"]["idle_gaps"]}
    else:
        log(f"cpu rehearsal (no device metric): window {window_s:.3f} s, "
            f"{len(window)} steps, setup {setup_s:.3f} s")
    result["metrics"] = metrics
    result["device"] = device
    if breakdown:
        result["breakdown"] = breakdown
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checks
    return result
