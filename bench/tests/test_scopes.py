"""Device time by the program's scopes, and idle gaps by its spans, on
hand-built traces."""

import pytest

import devtrace
import scopes
import spec

MS = 1_000_000      # ns

HLO = """\
HloModule jit_train_step

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %add.9 = f32[4]{0} add(%param_0.1, %param_0.1), metadata={op_name="jit(train_step)/jvp(layers)/while/body/closed_call/attn_core/add"}
}

%body.2 (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %fusion.1 = f32[4]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(layers)/while/body/closed_call/attn_core/add"}
  %dynamic-slice_fusion.2 = f32[1,4]{1,0} fusion(%gte.2, %i), kind=kLoop, metadata={op_name="jit(train_step)/transpose(jvp(layers))/while/body/dynamic_slice"}
  %convolution.3 = f32[4,4]{1,0} convolution(%a, %b), metadata={op_name="jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/transpose(jvp(mlp))/dot_general" source_file="x.py" source_line=3}
  %copy-start.5 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%fusion.1)
  ROOT %tuple.4 = (s32[], f32[4]) tuple(%i, %fusion.1)
}

%body.3 (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %fusion.11 = f32[4]{0} fusion(%gte.1), kind=kLoop, metadata={op_name="jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/transpose(jvp(attn_core))/while/body/mul"}
  %copy.12 = f32[4]{0} copy(%fusion.11)
  ROOT %tuple.13 = (s32[], f32[4]) tuple(%i, %copy.12), metadata={op_name="jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/attn_core/while/body/closed_call"}
}

ENTRY %main.5 (p0: f32[4]) -> f32[4] {
  %while.6 = (s32[], f32[4]) while(%t), condition=%cond.3, body=%body.2, metadata={op_name="jit(train_step)/jvp(layers)/while"}
  %fusion.7 = f32[] fusion(%x), kind=kLoop, metadata={op_name="jit(train_step)/head_loss/reduce_max"}
  %fusion.8 = f32[4]{0} fusion(%g), kind=kLoop, metadata={op_name="jit(train_step)/optimizer/mul"}
  %copy.9 = f32[4]{0} copy(%g)
  ROOT %fusion.10 = f32[4]{0} fusion(%t), kind=kLoop, metadata={op_name="jit(train_step)/embed/jit(_take)/gather"}
}
"""


def test_op_names_and_scope():
    names = scopes.op_names(HLO)
    assert names["fusion.1"].endswith("attn_core/add")
    got = {op: scopes.scope(n) for op, n in names.items()}
    # unnamed: the path its computation's named instructions share
    assert names["copy-start.5"] == "train_step/layers/while/body"
    assert got["copy-start.5"] == "layer_scan"
    assert got["copy.12"] == "attn_core"
    assert got["copy.9"] == "other"
    assert got["fusion.1"] == got["add.9"] == "attn_core"
    assert got["dynamic-slice_fusion.2"] == "layer_scan"
    assert got["convolution.3"] == "mlp"
    assert got["while.6"] == "layer_scan"
    assert got["fusion.7"] == "head_loss"
    assert got["fusion.8"] == "optimizer"
    assert got["fusion.10"] == "embed"
    assert scopes.scope("") == scopes.scope("jit(f)/mul") == "other"


def _trace():
    """Two steps of one device: a loop (with two body ops) and four ops
    outside it per step, plus one op outside the window."""
    host = [(devtrace.WINDOW_SPAN, 0, 100 * MS, (1, 0))]
    dev = []
    for base in (0, 50 * MS):
        dev += [("%while.6 = (s32[]) while(...)", base + 2 * MS, base + 22 * MS),
                ("%fusion.1 = f32[4] fusion(...)", base + 3 * MS, base + 9 * MS),
                ("%dynamic-slice_fusion.2 = f32[1,4] fusion(...)",
                 base + 9 * MS, base + 13 * MS),
                ("%convolution.3 = f32[4,4] convolution(...)",
                 base + 13 * MS, base + 21 * MS),
                ("fusion.7", base + 22 * MS, base + 25 * MS),
                ("fusion.8", base + 25 * MS, base + 30 * MS),
                ("copy.9", base + 30 * MS, base + 31 * MS),
                ("fusion.10", base + 0 * MS, base + 2 * MS)]
    dev.append(("fusion.8", 120 * MS, 130 * MS))
    return [dev], host


def test_ms_per_scope_adds_up_to_busy():
    dev, host = _trace()
    per_op = scopes.self_seconds(dev, host, scopes.op_names(HLO))
    ms = scopes.scope_ms(per_op, steps=2)
    want = {"embed": 2, "attn_core": 6, "layer_scan": 4 + 2, "mlp": 8,
            "head_loss": 3, "optimizer": 5, "other": 1}
    assert set(ms) == set(want)
    for k, v in want.items():
        assert abs(ms[k] - v) < 1e-9, (k, ms[k])
    busy = devtrace.reduce(dev, [h[:3] for h in host])["busy_s"]
    assert abs(sum(ms.values()) * 2 / 1e3 - busy) < 1e-12
    top = scopes.top_ops(per_op, top=3)
    assert [n for n, _ in top] == ["mlp:convolution.3", "attn_core:fusion.1",
                                   "optimizer:fusion.8"]


def test_scopes_find_nothing_without_their_inputs():
    dev, host = _trace()
    assert scopes.self_seconds(dev, host, {}) is None
    assert scopes.self_seconds(dev, [], scopes.op_names(HLO)) is None
    assert scopes.self_seconds([[]], host, scopes.op_names(HLO)) is None
    assert scopes.scope_ms(None, 8) is None and scopes.top_ops(None) == []
    assert scopes.idle_gaps(dev, []) == []


def test_reduce_is_unchanged_by_program_spans():
    dev, host = _trace()
    bench = [(devtrace.WINDOW_SPAN, 0, 100 * MS), ("feed_wait", 31 * MS, 50 * MS),
             ("dispatch", 50 * MS, 51 * MS), ("loss_sync", 82 * MS, 100 * MS)]
    program = [("loader.wait", 31 * MS, 45 * MS), ("feed.put", 45 * MS, 49 * MS),
               ("train.dispatch", 50 * MS, 51 * MS),
               ("loader.decode", 0, 100 * MS), ("workflow.run", 0, 1 * MS)]
    assert devtrace.reduce(dev, bench) == devtrace.reduce(dev, bench + program)


def test_idle_gaps_are_named_by_the_span_on_the_same_thread():
    main, worker = (1, 0), (1, 1)
    host = [(devtrace.WINDOW_SPAN, 0, 100 * MS, main),
            ("feed_wait", 0, 40 * MS, main),
            ("loader.wait", 0, 10 * MS, main),
            ("feed.put", 10 * MS, 12 * MS, main),
            # a worker's decode overlaps the first gap far more than the
            # consumer's wait does, on another thread
            ("loader.decode", 0, 40 * MS, worker),
            ("loss_sync", 60 * MS, 100 * MS, main),
            ("loader.read", 60 * MS, 100 * MS, worker)]
    dev = [[("fusion.1", 12 * MS, 60 * MS), ("fusion.2", 99 * MS, 100 * MS)]]
    gaps = scopes.idle_gaps(dev, host)
    assert gaps[0][0] == "loss_sync" and abs(gaps[0][1] - 0.039) < 1e-12
    assert gaps[1][0] == "feed_wait/loader.wait"
    assert abs(gaps[1][1] - 0.012) < 1e-12
    # the same gaps, by the benchmark's names alone
    plain = devtrace.reduce(dev, [h[:3] for h in host])["idle_gaps"]
    assert [n for n, _ in plain] == ["loss_sync", "feed_wait"]
    assert [s for _, s in plain] == [s for _, s in gaps]


def test_nested_program_spans_name_the_innermost():
    main = (1, 0)
    host = [(devtrace.WINDOW_SPAN, 0, 100 * MS, main),
            ("feed_wait", 0, 50 * MS, main),
            ("train.dispatch", 0, 50 * MS, main),
            ("feed.put", 1 * MS, 49 * MS, main)]
    gaps = scopes.idle_gaps([[("fusion.1", 50 * MS, 100 * MS)]], host)
    assert gaps == [["feed_wait/feed.put", 0.05]]


NEW_READERS = ("attn_core_ms", "attn_proj_ms", "mlp_ms", "head_loss_ms",
               "optimizer_ms", "layer_scan_ms", "derive_s")


@pytest.mark.parametrize("name", NEW_READERS)
def test_readers_find_nothing_without_their_input(name):
    read = spec.reader(name)
    assert read({"trace": None, "chips": 1}) is None
    assert read({"scope_ms": None, "program_spans": None}) is None
    assert read({"scope_ms": {}, "program_spans": []}) is None


def test_readers_read_their_scope_and_span():
    run = {"scope_ms": {"attn_core": 1.5, "attn_proj": 2.5, "mlp": 3.5,
                        "head_loss": 4.5, "optimizer": 5.5, "layer_scan": 6.5,
                        "other": 7.5},
           "program_spans": [("workflow.run", 10, 2_500_000_010),
                             ("loader.read", 0, 10)]}
    got = {n: spec.reader(n)(run) for n in NEW_READERS}
    assert got == {"attn_core_ms": 1.5, "attn_proj_ms": 2.5, "mlp_ms": 3.5,
                   "head_loss_ms": 4.5, "optimizer_ms": 5.5,
                   "layer_scan_ms": 6.5, "derive_s": 2.5}
