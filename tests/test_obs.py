"""The program's spans (repro.obs) and the named scopes of the train step."""

import glob
import json
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import obs
from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import flash_attention
from repro.models import RuntimeConfig, build_model
from repro.train import TrainConfig, make_train_step
from repro.train.optimizer import make_optimizer

STEP_SCOPES = {"embed", "layers", "head_loss", "optimizer"}


@pytest.fixture(autouse=True)
def clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ------------------------------------------------------------------ spans

def test_off_span_is_one_shared_noop():
    assert obs.span("a") is obs.span("b")
    with obs.span("a"):
        with obs.span("b"):
            pass
    assert obs.spans() == []


def test_off_count_is_a_noop_and_reset_clears_counts():
    obs.count("attn.pairs_live", 6)
    assert obs.counters() == {}
    obs.enable()
    obs.count("a", 2)
    obs.count("a")
    assert obs.counters() == {"a": 3}
    obs.reset()
    assert obs.counters() == {}


def test_nested_spans_keep_their_parent():
    obs.enable()
    with obs.span("outer"):
        with obs.span("inner"):
            pass
        with obs.span("inner2"):
            pass
    with obs.span("alone"):
        pass
    kept = {s.name: s for s in obs.spans()}
    assert [s.name for s in obs.spans()] == ["inner", "inner2", "outer",
                                             "alone"]
    assert kept["inner"].parent == kept["outer"].id
    assert kept["inner2"].parent == kept["outer"].id
    assert kept["outer"].parent is None and kept["alone"].parent is None
    o, i = kept["outer"], kept["inner"]
    assert o.start_ns <= i.start_ns <= i.end_ns <= o.end_ns
    obs.reset()
    assert obs.spans() == []


def test_worker_thread_spans_keep_their_thread():
    obs.enable()

    def work():
        with obs.span("loader.decode"):
            pass

    with obs.span("train.dispatch"):
        t = threading.Thread(target=work, name="loader-decode_0")
        t.start()
        t.join()
    kept = {s.name: s for s in obs.spans()}
    worker, main = kept["loader.decode"], kept["train.dispatch"]
    assert worker.thread_name == "loader-decode_0"
    assert worker.thread == t.ident != main.thread
    assert main.thread == threading.get_ident()
    # a span opened on another thread is not the child of this one's
    assert worker.parent is None


def test_export_writes_chrome_trace_events(tmp_path):
    obs.enable()
    with obs.span("feed.put"):
        with obs.span("inner"):
            pass
    path = obs.export(str(tmp_path / "spans.json"))
    with open(path) as fh:
        trace = json.load(fh)
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in spans] == ["inner", "feed.put"]
    assert all(e["dur"] >= 0 and e["tid"] == threading.get_ident()
               for e in spans)
    assert spans[0]["args"]["parent"] == spans[1]["args"]["id"]
    names = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert names[0]["args"]["name"] == threading.current_thread().name


def test_spans_lie_on_the_profilers_host_plane(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    obs.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with obs.span("train.dispatch"):
            y = f(x)
        with obs.span("train.loss_sync"):
            float(y)
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    names = [n for n, _, _ in events]
    assert "train.dispatch" in names and "train.loss_sync" in names
    (_, s, e), = [ev for ev in events if ev[0] == "train.dispatch"]
    call = [ev for ev in events if ev[0].startswith("PjitFunction")]
    assert call and all(s <= cs and ce <= e for _, cs, ce in call)
    assert len(obs.spans()) == 2


# --------------------------------------------------------- program spans

def test_platform_loader_and_feed_spans(tmp_path):
    from repro.data import DeviceFeed
    from repro.launch.train import build_platform
    from repro.data import ShardedSnapshotLoader

    obs.enable()
    plat, _ = build_platform(seq_len=32, n_docs=64)
    loader = ShardedSnapshotLoader(plat.dataset("corpus/packed").plan(), 4,
                                   32, decode_workers=2)
    feed = DeviceFeed(loader)
    it = iter(feed)
    for _ in range(3):
        next(it)
    it.close()
    names = {s.name for s in obs.spans()}
    assert {"platform.check_in", "workflow.run", "dataset.plan",
            "loader.read", "loader.decode", "loader.wait",
            "feed.put"} <= names
    workers = {s.thread_name for s in obs.spans()
               if s.name == "loader.decode"}
    assert all(n.startswith("loader-decode") for n in workers)
    assert feed.stats() == {"transfers": 4}
    st = loader.stats()
    assert st["read_time_s"] > 0 and st["decode_time_s"] > 0
    assert st["batches"] >= 3


@pytest.fixture
def keep_cache_dir():
    """``main`` places the persistent compilation cache; undo that for the
    tests that share this process."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_train_trace_out_writes_the_loop_spans(tmp_path, capsys,
                                               keep_cache_dir):
    from repro.launch.train import main

    path = tmp_path / "spans.json"
    out = main(["--arch", "stablelm-1.6b", "--smoke", "--steps", "2",
                "--batch", "2", "--seq-len", "32", "--checkpoint-every", "2",
                "--trace-out", str(path)])
    assert out["steps"] == 2
    printed = capsys.readouterr().out
    assert "loader: mode=" in printed and f"-> {path}" in printed
    events = json.loads(path.read_text())["traceEvents"]
    count = {}
    for e in events:
        if e["ph"] == "X":
            count[e["name"]] = count.get(e["name"], 0) + 1
    assert count["train.dispatch"] == count["train.loss_sync"] == 2
    assert count["train.save"] == 2      # the step-2 save and the final one
    assert count["workflow.run"] == 1 and count["loader.decode"] >= 2


# ---------------------------------------------------------- named scopes

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\S+)\s.*?([a-z][\w\-]*)\(.*'
    r'\bop_name="([^"]*)"')


def _scopes(op_name):
    """The scope names on an op_name path, transforms unwrapped
    (``transpose(jvp(attn_core))`` -> ``attn_core``)."""
    return [re.sub(r"^(?:[\w.\-]+\()+(.*?)\)*$", r"\1", part)
            for part in op_name.split("/")]


def _step_hlo(cfg, B=2, S=64):
    rt = RuntimeConfig(param_dtype=jnp.float32, compute_dtype=jnp.bfloat16,
                       attn_impl="xla", ssd_impl="xla", rglru_impl="xla",
                       remat="full", attn_block_q=16, attn_block_k=32)
    model = build_model(cfg, rt)
    tc = TrainConfig()
    like_p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    like_o = jax.eval_shape(make_optimizer(tc.optimizer).init, like_p)
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32)
             for k in ("tokens", "labels", "segments", "positions")}
    step = jax.jit(make_train_step(model, tc)).lower(like_p, like_o, batch)
    return step.compile().as_text(), like_p


def _tiny_attention():
    return ModelConfig(name="tiny-llama", family="dense", pattern=("attn",),
                       n_layers=3, d_model=64, n_heads=4, n_kv_heads=2,
                       head_dim=16, d_ff=128, vocab_size=512)


@pytest.mark.parametrize("make_cfg, block", [
    (_tiny_attention, {"attn_proj", "attn_core", "mlp"}),
    (lambda: get_smoke_config("mamba2-1.3b"), {"ssm"}),
    (lambda: get_smoke_config("recurrentgemma-9b"),
     {"rec", "attn_proj", "attn_core", "mlp"}),
], ids=["attention", "ssm", "recurrent"])
def test_train_step_carries_the_scope_vocabulary(make_cfg, block):
    hlo, _ = _step_hlo(make_cfg())
    found = set()
    for line in hlo.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            found.update(_scopes(m.group(4)))
    assert STEP_SCOPES | block <= found


def test_dynamic_slices_belong_to_the_layer_scan_or_a_block_scope():
    """Every dynamic-slice / dynamic-update-slice sits under ``layers``;
    the ones in no inner scope slice or stack the layer axis: a layer's
    parameters, its gradients, or the carried activations."""
    cfg = _tiny_attention()
    B, S, L = 2, 64, cfg.n_layers
    hlo, like_p = _step_hlo(cfg, B, S)
    per_layer = {tuple(x.shape[1:])
                 for x in jax.tree.leaves(like_p["blocks"])}
    per_layer.add((B, S, cfg.d_model))
    inner = {"attn_proj", "attn_core", "mlp"}
    n_inner = n_scan = 0
    for line in hlo.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(3) not in ("dynamic-slice",
                                       "dynamic-update-slice"):
            continue
        scopes = _scopes(m.group(4))
        assert "layers" in scopes or inner & set(scopes), line
        if inner & set(scopes):
            n_inner += 1
            continue
        n_scan += 1
        dims = tuple(int(d) for d in re.findall(r"\d+", m.group(2).split(
            "[", 1)[1].split("]", 1)[0]))
        lead = 1 if m.group(3) == "dynamic-slice" else L
        assert dims[0] == lead and dims[1:] in per_layer, line
    assert n_inner and n_scan


def test_score_block_products_carry_attn_core():
    """Every matmul that yields a score block (B·H·bq·bk elements, batch
    first, keys last; the compiler may merge the query axis with the head
    group) carries ``attn_core``: the forward's, its remat recompute's and
    the attention backward's own, which sits under the layer scan's
    transpose outside the remat recompute."""
    cfg = _tiny_attention()
    B, bq, bk = 2, 16, 32                       # _step_hlo's batch and blocks
    hlo, _ = _step_hlo(cfg, B)
    backward = 0
    for line in hlo.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(3) not in ("dot", "convolution"):
            continue
        dims = tuple(int(d) for d in re.findall(r"\d+", m.group(2).split(
            "[", 1)[1].split("]", 1)[0]))
        if (len(dims) < 3 or dims[0] != B or dims[-1] != bk
                or np.prod(dims) != B * cfg.n_heads * bq * bk):
            continue
        assert "attn_core" in _scopes(m.group(4)), line
        if ("transpose(" in m.group(4)
                and "rematted_computation" not in m.group(4)):
            backward += 1
    assert backward >= 2, "no score block of the backward found"


def test_attention_trace_counts_its_live_block_pairs():
    """One trace of the chunked attention at ``_step_hlo``'s shape (64
    positions, q blocks of 16, kv blocks of 32): the causal mask leaves 6
    pairs of the 4 x 2 grid."""
    obs.enable()
    x = jax.ShapeDtypeStruct((2, 64, 4, 16), jnp.float32)
    jax.eval_shape(lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl="xla", block_q=16, block_k=32), x, x, x)
    assert obs.counters() == {"attn.pairs_live": 6, "attn.pairs_all": 8}


def test_attention_trace_counts_narrow_operands():
    """The same trace at bfloat16: the products take narrow operands."""
    obs.enable()
    x = jax.ShapeDtypeStruct((2, 64, 4, 16), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: flash_attention(
        q, k, v, causal=True, impl="xla", block_q=16, block_k=32), x, x, x)
    assert obs.counters() == {"attn.pairs_live": 6, "attn.pairs_all": 8,
                              "attn.operands_narrow": 1}


def test_ssd_products_carry_ssm_ssd():
    """Every matmul of the SSD scan carries ``ssm/ssd``, in the forward,
    its remat recompute and the backward, so none is left to the layer
    scan: the chunked products and the state pass's are batch-first of
    rank 3 or more, where the projections around the scan are 2-D."""
    B = 2
    hlo, _ = _step_hlo(get_smoke_config("mamba2-1.3b"), B)
    phases = set()
    for line in hlo.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(3) not in ("dot", "convolution"):
            continue
        dims = tuple(int(d) for d in re.findall(r"\d+", m.group(2).split(
            "[", 1)[1].split("]", 1)[0]))
        if len(dims) < 3 or dims[0] != B:
            continue
        assert "ssm/ssd" in "/".join(_scopes(m.group(4))), line
        phases.add("remat" if "rematted_computation" in m.group(4) else
                   "backward" if "transpose(" in m.group(4) else "forward")
    assert phases == {"forward", "remat", "backward"}
