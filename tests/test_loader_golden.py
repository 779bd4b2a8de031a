"""Golden determinism suite for the loader hot path.

The epoch-order cache and vectorized hashing are only admissible if the
batch stream is **bit-identical** to the pre-optimization loader: training
checkpoints store ``(snapshot digest, epoch, step)`` and restore assumes the
permutation is reproducible forever.  These tests pin the ordering and the
batch bytes against hardcoded digests generated from the reference
``_order`` implementation, so any silent data-order drift fails loudly.
"""

import hashlib
import io

import numpy as np
import pytest

from repro.core import Record
from repro.data import ShardedSnapshotLoader
from repro.data.components import encode_packed
from repro.data.loader import _order, _order_fast
from repro.platform import Platform

# -- golden constants (reference implementation, fixed inputs) --------------

GOLDEN_SEED = 7
# sha256("|".join(_order([f"rec-{i:05d}" for i in range(257)], epoch, 7)))
GOLDEN_ORDER_DIGESTS = {
    0: "bb42129ba47cd62095a1f0fda7704e5568a8507218c276fdbf63b49039da9704",
    1: "05cc901ea94c71be36f754ca661e9754574db86d680752e1de4e1ee17bbc9377",
}
# content digest of the 96-record golden snapshot (RPK1 payloads, whose bytes
# do not depend on the numpy version), then digests over its decoded batches
GOLDEN_SNAPSHOT_CONTENT = (
    "dbdc8ea4a27de06c0941f58a25fb5c3c35e97616114e2c625542562727b86c21")
GOLDEN_FIRST_BATCH = (
    "cd501dc7ce07b7ac7a4189114d62cfa13d3840c021c8cc8df54dbb9c6c74a184")
GOLDEN_LAST_BATCH_E0 = (
    "cd347ebb6ce73354f6f041dbcfd7a6e324564a88ca090381f9a15c68ce2176c2")
GOLDEN_FIRST_BATCH_E1 = (
    "15551456db199d01175dce697cb354187ffef1093806dd8d99a70b25eaa5b2b7")


def _packed_record(i: int, seq_len: int = 16) -> Record:
    rng = np.random.default_rng(1000 + i)
    L = seq_len + 1
    tokens = rng.integers(3, 259, size=L).astype(np.int32)
    segments = np.zeros(L, np.int32)
    segments[-3:] = -1
    positions = np.arange(L, dtype=np.int32)
    return Record(f"rec-{i:05d}", encode_packed(tokens, segments, positions),
                  {"format": "packed.bin"})


def _batch_digest(batch) -> str:
    h = hashlib.sha256()
    for k in sorted(batch):
        h.update(k.encode())
        h.update(np.ascontiguousarray(batch[k]).tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def golden_plan():
    plat = Platform.open(actor="golden")
    plat.dataset("g").check_in([_packed_record(i) for i in range(96)])
    return plat.dataset("g").plan()


# -- ordering ---------------------------------------------------------------


def test_fast_order_bit_identical_to_reference():
    ids = [f"rec-{i:05d}" for i in range(257)] + [f"x{i:03x}" for i in range(31)]
    for epoch in range(3):
        for seed in (0, 3, 7, 12345):
            assert _order_fast(ids, epoch, seed) == _order(ids, epoch, seed)
    assert _order_fast([], 0, 0) == []


def test_epoch_order_matches_golden_digest():
    ids = [f"rec-{i:05d}" for i in range(257)]
    for epoch, want in GOLDEN_ORDER_DIGESTS.items():
        got = hashlib.sha256(
            "|".join(_order_fast(ids, epoch, GOLDEN_SEED)).encode()).hexdigest()
        assert got == want
        # and the cached loader path serves the same permutation
    class _Snap:
        def record_ids(self):
            return list(ids)

        def content_digest(self):
            return "static"

    ld = ShardedSnapshotLoader(_Snap(), batch_size=1, seq_len=4,
                               seed=GOLDEN_SEED)
    for epoch, want in GOLDEN_ORDER_DIGESTS.items():
        first = ld._epoch_order(epoch)
        again = ld._epoch_order(epoch)
        assert first is again                  # cache hit, not recompute
        got = hashlib.sha256("|".join(first).encode()).hexdigest()
        assert got == want


# -- batch streams ----------------------------------------------------------


def test_golden_batches_bit_identical(golden_plan):
    ld = ShardedSnapshotLoader(golden_plan, batch_size=8, seq_len=16,
                               seed=GOLDEN_SEED)
    assert ld._content == GOLDEN_SNAPSHOT_CONTENT
    per_epoch = 96 // 8
    batches = [ld.next_batch() for _ in range(per_epoch + 1)]
    assert _batch_digest(batches[0]) == GOLDEN_FIRST_BATCH
    assert _batch_digest(batches[per_epoch - 1]) == GOLDEN_LAST_BATCH_E0
    assert _batch_digest(batches[per_epoch]) == GOLDEN_FIRST_BATCH_E1
    assert ld.epoch == 1


def test_cached_stream_equals_uncached_reference_stream(golden_plan):
    fast = ShardedSnapshotLoader(golden_plan, batch_size=8, seq_len=16,
                                 seed=GOLDEN_SEED)
    legacy = ShardedSnapshotLoader(golden_plan, batch_size=8, seq_len=16,
                                   seed=GOLDEN_SEED,
                                   cache_epoch_orders=False)
    for _ in range(96 // 8 + 2):  # cross the epoch boundary
        assert _batch_digest(fast.next_batch()) == \
            _batch_digest(legacy.next_batch())


def test_mid_epoch_restore_resumes_identical_stream(golden_plan):
    src = ShardedSnapshotLoader(golden_plan, batch_size=8, seq_len=16,
                                seed=GOLDEN_SEED)
    for _ in range(7):  # mid-epoch (per_epoch=12)
        src.next_batch()
    state = src.state()
    want = [_batch_digest(src.next_batch()) for _ in range(8)]  # crosses e1

    resumed = ShardedSnapshotLoader(golden_plan, batch_size=8, seq_len=16,
                                    seed=GOLDEN_SEED)
    resumed.restore(state)
    got = [_batch_digest(resumed.next_batch()) for _ in range(8)]
    assert got == want


def test_sharded_streams_unchanged_by_cache(golden_plan):
    whole = ShardedSnapshotLoader(golden_plan, batch_size=8, seq_len=16,
                                  seed=GOLDEN_SEED)
    shards = [ShardedSnapshotLoader(golden_plan, batch_size=8, seq_len=16,
                                    seed=GOLDEN_SEED, shard_id=i, n_shards=2)
              for i in range(2)]
    gb = whole.next_batch()
    b0, b1 = (s.next_batch() for s in shards)
    np.testing.assert_array_equal(gb["tokens"][0::2], b0["tokens"])
    np.testing.assert_array_equal(gb["tokens"][1::2], b1["tokens"])


# -- packed payload format ---------------------------------------------------


def test_encode_packed_roundtrip_and_npz_fallback():
    from repro.data.components import decode_packed, encode_packed

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 300, size=33).astype(np.int32)
    segments = rng.integers(-1, 4, size=33).astype(np.int32)
    positions = np.arange(33, dtype=np.int32)
    # raw v2 format
    t, s, p = decode_packed(encode_packed(tokens, segments, positions))
    np.testing.assert_array_equal(t, tokens)
    np.testing.assert_array_equal(s, segments)
    np.testing.assert_array_equal(p, positions)
    # legacy npz payloads (pre-existing checked-in datasets) still decode
    buf = io.BytesIO()
    np.savez(buf, tokens=tokens, segments=segments, positions=positions)
    t, s, p = decode_packed(buf.getvalue())
    np.testing.assert_array_equal(t, tokens)
    np.testing.assert_array_equal(s, segments)
    np.testing.assert_array_equal(p, positions)
    with pytest.raises(ValueError):
        encode_packed(tokens, segments[:-1], positions)


# -- prefetch iterator error path -------------------------------------------


class _ExplodingSnapshot:
    """Snapshot whose reads start failing after ``ok_reads`` payloads."""

    def __init__(self, plan, ok_reads: int):
        self._plan = plan
        self._left = ok_reads

    def record_ids(self):
        return self._plan.record_ids()

    def content_digest(self):
        return self._plan.content_digest()

    def read(self, rid):
        if self._left <= 0:
            raise RuntimeError("backend exploded")
        self._left -= 1
        return self._plan.read(rid)


def test_iter_surfaces_worker_error_without_hanging(golden_plan):
    snap = _ExplodingSnapshot(golden_plan, ok_reads=20)
    ld = ShardedSnapshotLoader(snap, batch_size=8, seq_len=16,
                               seed=GOLDEN_SEED, prefetch=1, timeout_s=10.0)
    it = iter(ld)
    with pytest.raises(RuntimeError, match="backend exploded"):
        for _ in range(50):
            next(it)


def test_iter_worker_exits_when_consumer_stops_early(golden_plan):
    import threading

    before = threading.active_count()
    ld = ShardedSnapshotLoader(golden_plan, batch_size=8, seq_len=16,
                               seed=GOLDEN_SEED, prefetch=1)
    it = iter(ld)
    next(it)
    it.close()  # generator finally: stop + drain + join the worker
    assert threading.active_count() <= before + 1
