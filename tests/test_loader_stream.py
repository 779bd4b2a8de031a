"""Page-window streaming loader suite: ordering goldens, the O(window)
memory contract, mid-window + epoch-boundary resume, the pipelined
iterator's failure surface, and the double-buffered device feed.

The golden digests pin the page-window batch stream the same way
``test_loader_golden.py`` pins the global stream: the order is part of the
checkpoint contract, so any drift must fail loudly.
"""

import hashlib
import threading
import time

import numpy as np
import pytest

from repro.core import Record
from repro.data import DeviceFeed, ShardedSnapshotLoader
from repro.data.components import encode_packed
from repro.data.loader import _PAGE_SURFACE, _order_fast, _page_perm
from repro.platform import Platform

SEED = 7
PAGE = 16          # manifest page fanout for the paged fixtures
N = 96             # records in the small fixture -> 6 pages
BATCH = 8
PER_EPOCH = N // BATCH

# -- golden constants (generated once from this fixture, then frozen) -------
GOLDEN_PAGES_DIGEST = (       # over RPK1 payloads (numpy-version independent)
    "bf14c68e72582ca095a02f1527105377093fab1033e13dbee2ad85e60b541573")
GOLDEN_PW_FIRST = (
    "e70a9699235ef74bae5ea2c8ae3d5f567fa71baff521fce9bd09aab980736f65")
GOLDEN_PW_LAST_E0 = (
    "d6f9b0cbb72e66cbe6aa8f358d8e19a85475963549521fcbbe6e9e646bbddb1e")
GOLDEN_PW_FIRST_E1 = (
    "c1ffa2c60945ab0fcf81280e2009c6177e36a0892fc3d3d46503c21a26bf0f71")


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


def _paged_plan(n=N, page=PAGE, name="s"):
    plat = Platform.open(actor="stream", page_size=page)
    plat.dataset(name).check_in([_packed_record(i) for i in range(n)])
    return plat.dataset(name).plan()


@pytest.fixture(scope="module")
def paged_plan():
    return _paged_plan()


def _loader(plan, mode, **kw):
    kw.setdefault("seed", SEED)
    return ShardedSnapshotLoader(plan, batch_size=BATCH, seq_len=16,
                                 shuffle=mode, **kw)


# -- ordering ---------------------------------------------------------------


def test_page_perm_deterministic_and_distinct():
    p0 = _page_perm(32, epoch=0, seed=SEED)
    assert p0 == _page_perm(32, epoch=0, seed=SEED)
    assert sorted(p0) == list(range(32))
    assert p0 != _page_perm(32, epoch=1, seed=SEED)   # reshuffled per epoch
    assert p0 != _page_perm(32, epoch=0, seed=SEED + 1)


def test_window_covering_all_pages_equals_global(paged_plan):
    """W >= n_pages degenerates to EXACTLY the legacy global permutation —
    the invariant that makes page_window a strict generalization."""
    pw = _loader(paged_plan, "page_window", window_pages=64)
    gl = _loader(paged_plan, "global")
    for _ in range(PER_EPOCH + 2):  # cross the epoch boundary
        assert _batch_digest(pw.next_batch()) == _batch_digest(gl.next_batch())


def test_page_window_golden_batches(paged_plan):
    ld = _loader(paged_plan, "page_window", window_pages=2)
    assert ld._content == GOLDEN_PAGES_DIGEST
    batches = [ld.next_batch() for _ in range(PER_EPOCH + 1)]
    assert _batch_digest(batches[0]) == GOLDEN_PW_FIRST
    assert _batch_digest(batches[PER_EPOCH - 1]) == GOLDEN_PW_LAST_E0
    assert _batch_digest(batches[PER_EPOCH]) == GOLDEN_PW_FIRST_E1
    assert ld.epoch == 1


def test_page_window_stream_is_a_permutation(paged_plan):
    """Each epoch visits every record exactly once (batch-aligned count)."""
    ld = _loader(paged_plan, "page_window", window_pages=2)
    groups, cum = ld._page_plan(0)
    assert cum[-1] == N
    ids = []
    for g in range(len(groups)):
        order, _ = ld._window(0, g)
        ids.extend(order)
    assert len(ids) == N and len(set(ids)) == N


def test_pipelined_iter_equals_next_batch(paged_plan):
    a = _loader(paged_plan, "page_window", window_pages=2)
    b = _loader(paged_plan, "page_window", window_pages=2)
    it = iter(a)
    try:
        for _ in range(PER_EPOCH + 3):
            assert _batch_digest(next(it)) == _batch_digest(b.next_batch())
    finally:
        it.close()


# -- memory contract --------------------------------------------------------


class _SurfaceOnly:
    """Exposes ONLY the page-granular feed surface; anything that would
    materialize the manifest raises.  Proves page_window mode never calls
    record_ids()/entries()/read() — the O(window) contract at the API."""

    def __init__(self, plan):
        self._plan = plan
        for m in _PAGE_SURFACE:
            setattr(self, m, getattr(plan, m))

    def __getattr__(self, name):  # record_ids, entries, read, read_batch...
        raise AssertionError(
            f"page_window loader touched forbidden surface: {name}")


def test_page_window_never_materializes_full_permutation():
    n, page, W = 512, 16, 4
    plan = _paged_plan(n=n, page=page, name="big")
    ld = ShardedSnapshotLoader(_SurfaceOnly(plan), batch_size=16, seq_len=16,
                               seed=SEED, shuffle="page_window",
                               window_pages=W)
    for _ in range(n // 16):   # one full epoch
        ld.next_batch()
    s = ld.stats()
    cap = ld._GROUP_CACHE_CAP * W * page   # 3 * 4 * 16 = 192 << 512
    assert 0 < s["peak_resident_ids"] <= cap < n
    assert s["pages_streamed"] >= n // page
    # the plan itself never materialized its entry list either
    assert plan._entries is None


# -- resume -----------------------------------------------------------------


@pytest.mark.parametrize("mode,kw", [
    ("global", {}),
    ("page_window", {"window_pages": 2}),
])
def test_mid_epoch_resume_bit_identical(paged_plan, mode, kw):
    src = _loader(paged_plan, mode, **kw)
    for _ in range(5):   # mid-epoch, mid-window (W=2 -> 32-record windows)
        src.next_batch()
    state = src.state()
    want = [_batch_digest(src.next_batch()) for _ in range(10)]  # crosses e1

    resumed = _loader(paged_plan, mode, **kw)
    resumed.restore(state)
    got = [_batch_digest(resumed.next_batch()) for _ in range(10)]
    assert got == want


@pytest.mark.parametrize("mode,kw", [
    ("global", {}),
    ("page_window", {"window_pages": 2}),
])
def test_epoch_boundary_resume_bit_identical(paged_plan, mode, kw):
    src = _loader(paged_plan, mode, **kw)
    for _ in range(PER_EPOCH):   # exactly at the epoch-1 boundary
        src.next_batch()
    state = src.state()
    # epoch advances when the first batch OF the new epoch is delivered,
    # so the boundary state is (epoch=0, step=PER_EPOCH) — legacy semantics
    assert state["epoch"] == 0 and state["step"] == PER_EPOCH
    want = [_batch_digest(src.next_batch()) for _ in range(3)]

    resumed = _loader(paged_plan, mode, **kw)
    resumed.restore(state)
    got = [_batch_digest(resumed.next_batch()) for _ in range(3)]
    assert got == want


def test_page_window_state_carries_cursor(paged_plan):
    ld = _loader(paged_plan, "page_window", window_pages=2)
    for _ in range(5):
        ld.next_batch()
    st = ld.state()
    assert st["shuffle"] == "page_window"
    assert st["window_pages"] == 2
    assert set(st["cursor"]) == {"group", "offset"}
    assert st["cursor"]["offset"] == 5 * BATCH - 32 * st["cursor"]["group"]


def test_restore_refuses_mode_and_window_mismatch(paged_plan):
    pw = _loader(paged_plan, "page_window", window_pages=2)
    gl = _loader(paged_plan, "global")
    with pytest.raises(ValueError, match="across shuffle modes"):
        gl.restore(pw.state())
    with pytest.raises(ValueError, match="across shuffle modes"):
        pw.restore(gl.state())
    other = _loader(paged_plan, "page_window", window_pages=4)
    with pytest.raises(ValueError, match="window_pages"):
        other.restore(pw.state())


def test_auto_mode_thresholds(paged_plan):
    small = ShardedSnapshotLoader(paged_plan, batch_size=BATCH, seq_len=16,
                                  shuffle="auto", auto_page_window_min=1000)
    assert small._mode == "global"
    big = ShardedSnapshotLoader(paged_plan, batch_size=BATCH, seq_len=16,
                                shuffle="auto", auto_page_window_min=10)
    assert big._mode == "page_window"


def test_page_window_requires_feed_surface():
    class _Bare:
        def record_ids(self):
            return ["a", "b"]

        def content_digest(self):
            return "x"

        def read(self, rid):
            return b""

    with pytest.raises(ValueError, match="page-granular feed surface"):
        ShardedSnapshotLoader(_Bare(), batch_size=1, seq_len=4,
                              shuffle="page_window")
    # and auto degrades to global instead of failing
    ld = ShardedSnapshotLoader(_Bare(), batch_size=1, seq_len=4,
                               shuffle="auto", auto_page_window_min=0)
    assert ld._mode == "global"


# -- failure surface --------------------------------------------------------


def test_stuck_shard_raises_descriptive_timeout(paged_plan):
    release = threading.Event()

    class _Stuck:
        def record_ids(self):
            return paged_plan.record_ids()

        def content_digest(self):
            return paged_plan.content_digest()

        def read(self, rid):
            release.wait(timeout=5.0)   # hang until the test lets go
            raise RuntimeError("unreachable in a passing test")

    ld = ShardedSnapshotLoader(_Stuck(), batch_size=BATCH, seq_len=16,
                               seed=SEED, prefetch=1, timeout_s=0.3)
    it = iter(ld)
    try:
        with pytest.raises(TimeoutError) as exc:
            next(it)
        msg = str(exc.value)
        assert "loader shard stuck" in msg
        assert paged_plan.content_digest()[:12] in msg
        assert "shard 0/1" in msg and "epoch 0" in msg and "step 0" in msg
    finally:
        release.set()   # unblock the worker so pytest exits promptly
        it.close()


# -- stats ------------------------------------------------------------------


def test_stats_report_wait_fraction_and_accounting(paged_plan):
    ld = _loader(paged_plan, "page_window", window_pages=2)
    it = iter(ld)
    try:
        for _ in range(6):
            next(it)
            time.sleep(0.002)   # consumer "train step": queue stays ahead
    finally:
        it.close()
    s = ld.stats()
    assert s["mode"] == "page_window" and s["window_pages"] == 2
    assert s["batches"] == 6
    assert 0.0 <= s["wait_fraction"] <= 1.0
    assert s["pages_streamed"] > 0 and s["peak_resident_ids"] > 0
    assert s["read_time_s"] >= 0 and s["decode_time_s"] > 0
    gl = _loader(paged_plan, "global")
    gl.next_batch()
    assert gl.stats()["mode"] == "global"
    assert gl.stats()["window_pages"] is None


# -- device feed ------------------------------------------------------------


def test_device_feed_matches_host_stream_and_pairs_state(paged_plan):
    ref = _loader(paged_plan, "page_window", window_pages=2)
    fed = _loader(paged_plan, "page_window", window_pages=2)
    feed = DeviceFeed(fed, depth=2)
    it = iter(feed)
    try:
        for i in range(PER_EPOCH + 2):
            dev_batch, state = next(it)
            host = {k: np.asarray(v) for k, v in dev_batch.items()}
            assert _batch_digest(host) == _batch_digest(ref.next_batch())
            # the paired state points just past THIS batch, even though
            # later batches are already buffered on device
            assert state["step"] == i + 1
            assert state["epoch"] == i // PER_EPOCH
    finally:
        it.close()
    assert feed.stats()["transfers"] >= PER_EPOCH + 2


def test_device_feed_restore_roundtrip(paged_plan):
    src = _loader(paged_plan, "page_window", window_pages=2)
    it = iter(DeviceFeed(src, depth=2))
    try:
        state = None
        for _ in range(7):
            _, state = next(it)
        want = [_batch_digest({k: np.asarray(v) for k, v in b.items()})
                for b, _ in (next(it) for _ in range(5))]
    finally:
        it.close()
    resumed = _loader(paged_plan, "page_window", window_pages=2)
    resumed.restore(state)
    got = [_batch_digest(resumed.next_batch()) for _ in range(5)]
    assert got == want


# -- streaming read surface (satellite) -------------------------------------


def test_plan_count_and_iter_record_ids_stay_lazy(paged_plan):
    plan = _paged_plan(name="lazy")
    assert plan.count() == N
    assert plan._entries is None           # count() came from the directory
    ids = list(plan.iter_record_ids())
    assert plan._entries is None           # streaming didn't materialize
    assert ids == [f"rec-{i:05d}" for i in range(N)]
    assert plan.record_ids() == ids        # compat wrapper, same answer
    assert plan.page_sizes() == [PAGE] * (N // PAGE)
    assert plan.page_count() == N // PAGE
    assert plan.pages_digest() == plan.pages_digest()


def test_snapshot_streaming_surface(paged_plan):
    snap = paged_plan.snapshot(register=False)
    assert snap.count() == N == len(list(snap.iter_record_ids()))
    assert snap.pages_digest() == snap.content_digest()
    sizes = snap.page_sizes()
    assert sum(sizes) == N
    pages = snap.read_pages(range(snap.page_count()))
    assert sum(len(p) for p in pages) == N


def test_filtered_plan_still_serves_page_surface():
    plat = Platform.open(actor="stream", page_size=PAGE)
    plat.dataset("flt").check_in([_packed_record(i) for i in range(N)])
    plan = plat.dataset("flt").plan(limit=40)
    assert plan.count() == 40              # falls back to entries
    assert sum(plan.page_sizes()) == 40
    digest = plan.pages_digest()
    assert digest == plan.content_digest() # degraded identity, still stable
