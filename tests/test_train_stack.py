"""Training-stack tests: optimizers, checkpoint round-trip through the
platform, elastic restore, loader determinism/resume, data components."""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, st

from repro.core import DatasetManager, MemoryBackend, ObjectStore, Record
from repro.data import (ByteTokenizer, PackComponent, ShardedSnapshotLoader,
                        TokenizeComponent, decode_packed)
from repro.core.transforms import Pipeline, RunContext
from repro.train.optimizer import (OptimizerConfig, global_norm, lr_at,
                                   make_optimizer)
from repro.train.checkpoint import (latest_step, load_checkpoint,
                                    save_checkpoint)

# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------


def _quad_params():
    return {"a": jnp.array([1.0, -2.0, 3.0]), "b": jnp.ones((4, 4)) * 2.0}


def _quad_loss(p):
    return sum(jnp.sum(x.astype(jnp.float32) ** 2)
               for x in jax.tree.leaves(p))


@pytest.mark.parametrize("name", ["adamw", "adafactor", "adamw8bit"])
def test_optimizer_reduces_quadratic(name):
    cfg = OptimizerConfig(name=name, lr=0.05, weight_decay=0.0,
                          warmup_steps=0, total_steps=1000,
                          schedule="constant", factored_min_dim=4)
    opt = make_optimizer(cfg)
    params = _quad_params()
    state = opt.init(params)
    loss0 = float(_quad_loss(params))
    for _ in range(60):
        grads = jax.grad(_quad_loss)(params)
        params, state = opt.update(grads, state, params)
    loss1 = float(_quad_loss(params))
    assert loss1 < loss0 * 0.2, (name, loss0, loss1)
    assert int(state["step"]) == 60


def test_adafactor_state_is_factored():
    cfg = OptimizerConfig(name="adafactor", factored_min_dim=4)
    opt = make_optimizer(cfg)
    params = {"w": jnp.ones((8, 16)), "b": jnp.ones((8,))}
    state = opt.init(params)
    assert set(state["v"]["w"]) == {"vr", "vc"}
    assert state["v"]["w"]["vr"].shape == (8,)
    assert state["v"]["w"]["vc"].shape == (16,)
    assert set(state["v"]["b"]) == {"v"}   # too small to factor


def test_adamw8bit_state_is_quantized():
    cfg = OptimizerConfig(name="adamw8bit", quant_block=16)
    opt = make_optimizer(cfg)
    params = {"w": jnp.ones((8, 16))}
    state = opt.init(params)
    assert state["m"]["w"]["q"].dtype == jnp.int8


def test_lr_schedule_warmup_cosine():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          schedule="cosine", min_lr_ratio=0.1)
    assert float(lr_at(cfg, jnp.asarray(0))) < 0.2
    assert float(lr_at(cfg, jnp.asarray(10))) == pytest.approx(1.0, abs=0.1)
    assert float(lr_at(cfg, jnp.asarray(100))) == pytest.approx(0.1, abs=0.01)


def test_global_norm_and_clip():
    from repro.train.optimizer import clip_by_norm

    tree = {"a": jnp.ones((10,)) * 3.0}
    norm = float(global_norm(tree))
    assert norm == pytest.approx((9 * 10) ** 0.5)
    clipped, n2 = clip_by_norm(tree, 1.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    assert float(n2) == pytest.approx(norm)


# ---------------------------------------------------------------------------
# checkpoint via the platform
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_with_lineage():
    dm = DatasetManager(ObjectStore(MemoryBackend()))
    params = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
              "nested": {"b": jnp.ones((2,), jnp.bfloat16)}}
    opt_state = {"m": {"w": jnp.zeros((3, 4)),
                       "nested": {"b": jnp.zeros((2,))}},
                 "step": jnp.asarray(7, jnp.int32)}
    cid = save_checkpoint(dm, "ckpt/test", 7, params, opt_state,
                          extra={"loader": {"step": 7}})
    assert cid
    like_p = jax.eval_shape(lambda: params)
    like_o = jax.eval_shape(lambda: opt_state)
    p2, o2, extra = load_checkpoint(dm, "ckpt/test", like_p, like_o)
    np.testing.assert_array_equal(np.asarray(p2["w"]), np.asarray(params["w"]))
    assert p2["nested"]["b"].dtype == jnp.bfloat16
    assert int(o2["step"]) == 7
    assert extra["loader"]["step"] == 7
    assert latest_step(dm, "ckpt/test") == 7
    # versioned: a later step becomes 'latest'
    save_checkpoint(dm, "ckpt/test", 9, params, opt_state)
    assert latest_step(dm, "ckpt/test") == 9
    # old step still addressable
    p3, _, _ = load_checkpoint(dm, "ckpt/test", like_p, rev="step-7")
    np.testing.assert_array_equal(np.asarray(p3["w"]), np.asarray(params["w"]))


def test_checkpoint_acl_enforced():
    from repro.core import AccessController, PermissionError_

    store = ObjectStore(MemoryBackend())
    acl = AccessController(store, open_world=True)
    dm = DatasetManager(store, acl=acl)
    params = {"w": jnp.ones((2, 2))}
    save_checkpoint(dm, "ckpt/locked", 1, params)
    acl.grant("trainer", "ckpt/locked", "ADMIN")
    like = jax.eval_shape(lambda: params)
    with pytest.raises(PermissionError_):
        load_checkpoint(dm, "ckpt/locked", like, actor="stranger")
    load_checkpoint(dm, "ckpt/locked", like, actor="trainer")


def test_elastic_restore_onto_mesh():
    """Checkpoint restores laid out for a (different) target mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    dm = DatasetManager(ObjectStore(MemoryBackend()))
    params = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    save_checkpoint(dm, "ckpt/elastic", 1, params)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    p2, _, _ = load_checkpoint(dm, "ckpt/elastic",
                               jax.eval_shape(lambda: params),
                               param_shardings=sh)
    assert p2["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(p2["w"]), np.asarray(params["w"]))


# ---------------------------------------------------------------------------
# data components + loader
# ---------------------------------------------------------------------------


def _packed_snapshot(n_docs=64, seq_len=32):
    dm = DatasetManager(ObjectStore(MemoryBackend()))
    docs = [Record(f"d{i:03d}", (f"document {i} " * (i % 7 + 1)).encode(), {})
            for i in range(n_docs)]
    dm.check_in("raw", docs, actor="t")
    snap_in = dm.checkout("raw", actor="t", register_snapshot=False)
    pipe = Pipeline([TokenizeComponent(), PackComponent(seq_len=seq_len)])
    out = pipe.run(list(snap_in), RunContext())
    dm.check_in("packed", out, actor="t")
    return dm, dm.checkout("packed", actor="t", register_snapshot=False)


def test_tokenizer_roundtrip():
    tok = ByteTokenizer()
    ids = tok.encode(b"hello world")
    assert ids[0] == 1 and ids[-1] == 2       # BOS/EOS
    assert tok.decode(ids) == b"hello world"


@settings(max_examples=30, deadline=None)
@given(data=st.binary(min_size=0, max_size=500))
def test_property_tokenizer_reversible(data):
    tok = ByteTokenizer()
    assert tok.decode(tok.encode(data)) == data


def test_packing_preserves_tokens():
    """No token of any document is lost or reordered by packing."""
    dm, snap = _packed_snapshot(n_docs=16, seq_len=24)
    tok = ByteTokenizer()
    all_packed = []
    for rid in snap.record_ids():
        tokens, segments, positions = decode_packed(snap.read(rid))
        assert tokens.shape == (25,)          # seq_len + 1
        # positions restart with each segment
        for s in np.unique(segments[segments >= 0]):
            seg_pos = positions[segments == s]
            assert seg_pos[0] == 0 or rid != snap.record_ids()[0]
        all_packed.append(tokens[segments >= 0])
    stream = np.concatenate(all_packed)
    # the packed stream must contain each doc's BOS..EOS in order
    n_bos = int((stream == 1).sum())
    n_eos = int((stream == 2).sum())
    assert n_bos == 16 and n_eos >= 15        # last EOS may be clipped


def test_loader_deterministic_and_sharded():
    _, snap = _packed_snapshot(n_docs=96, seq_len=16)
    l1 = ShardedSnapshotLoader(snap, batch_size=8, seq_len=16, seed=3)
    l2 = ShardedSnapshotLoader(snap, batch_size=8, seq_len=16, seed=3)
    b1, b2 = l1.next_batch(), l2.next_batch()
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # sharded: 2 shards' rows interleave to the global batch
    g = ShardedSnapshotLoader(snap, batch_size=8, seq_len=16, seed=3)
    s0 = ShardedSnapshotLoader(snap, batch_size=8, seq_len=16, seed=3,
                               shard_id=0, n_shards=2)
    s1 = ShardedSnapshotLoader(snap, batch_size=8, seq_len=16, seed=3,
                               shard_id=1, n_shards=2)
    gb, b0, b1_ = g.next_batch(), s0.next_batch(), s1.next_batch()
    np.testing.assert_array_equal(gb["tokens"][0::2], b0["tokens"])
    np.testing.assert_array_equal(gb["tokens"][1::2], b1_["tokens"])


def test_loader_resume_exact():
    _, snap = _packed_snapshot(n_docs=96, seq_len=16)
    l1 = ShardedSnapshotLoader(snap, batch_size=4, seq_len=16)
    for _ in range(5):
        l1.next_batch()
    state = l1.state()
    want = l1.next_batch()
    l2 = ShardedSnapshotLoader(snap, batch_size=4, seq_len=16)
    l2.restore(state)
    got = l2.next_batch()
    np.testing.assert_array_equal(want["tokens"], got["tokens"])
    np.testing.assert_array_equal(want["labels"], got["labels"])


def test_loader_refuses_wrong_snapshot():
    _, snap_a = _packed_snapshot(n_docs=32, seq_len=16)
    _, snap_b = _packed_snapshot(n_docs=40, seq_len=16)
    la = ShardedSnapshotLoader(snap_a, batch_size=4, seq_len=16)
    lb = ShardedSnapshotLoader(snap_b, batch_size=4, seq_len=16)
    with pytest.raises(ValueError, match="different snapshot"):
        lb.restore(la.state())


def test_loader_labels_shifted_and_masked():
    _, snap = _packed_snapshot(n_docs=32, seq_len=16)
    ld = ShardedSnapshotLoader(snap, batch_size=4, seq_len=16)
    b = ld.next_batch()
    tokens, _, _ = decode_packed(
        snap.read(_order_first(snap, ld)))
    # labels are tokens shifted by one wherever not masked
    unmasked = b["labels"] >= 0
    assert (b["labels"].shape == b["tokens"].shape)
    assert unmasked.any()


def _order_first(snap, loader):
    from repro.data.loader import _order

    return _order(snap.record_ids(), 0, loader.seed)[0]


def test_loader_epoch_reshuffles():
    _, snap = _packed_snapshot(n_docs=64, seq_len=16)
    ld = ShardedSnapshotLoader(snap, batch_size=32, seq_len=16)
    per_epoch = len(snap) // 32
    first_epoch0 = ld.next_batch()["tokens"].copy()
    for _ in range(per_epoch - 1):
        ld.next_batch()
    first_epoch1 = ld.next_batch()["tokens"]
    assert ld.epoch == 1
    assert not np.array_equal(first_epoch0, first_epoch1)
