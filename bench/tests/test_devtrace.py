"""The trace reduction on a hand-built trace."""

import devtrace

MS = 1_000_000      # ns


def test_union_subtract_gaps():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert devtrace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert devtrace.gaps([(2, 4)], 0, 6) == [(0, 2), (4, 6)]


def test_reduce_busy_and_idle_gaps():
    host = [(devtrace.WINDOW_SPAN, 0, 100 * MS),
            ("feed_wait", 0, 10 * MS),
            ("dispatch", 10 * MS, 12 * MS),
            ("loss_sync", 12 * MS, 95 * MS),
            ("feed_wait", 95 * MS, 100 * MS)]
    dev0 = [("fusion.1", 12 * MS, 40 * MS),
            ("fusion.3", 35 * MS, 50 * MS),
            ("fusion.2", 50 * MS, 55 * MS),
            ("fusion.1", 150 * MS, 160 * MS)]       # outside the window
    dev1 = [("fusion.1", 12 * MS, 46 * MS),
            ("fusion.2", 46 * MS, 52 * MS),
            ("fusion.3", 40 * MS, 52 * MS)]
    r = devtrace.reduce([dev0, dev1], host)
    assert abs(r["window_s"] - 0.100) < 1e-12
    # device 0 busy 12..55 = 43 ms, device 1 busy 12..52 = 40 ms
    assert abs(r["busy_s"] - 0.0415) < 1e-12
    # device 0 idle: 0..12 (feed_wait 10 ms vs dispatch 2 ms), 55..100
    # (loss_sync 40 ms vs feed_wait 5 ms)
    assert [n for n, _ in r["idle_gaps"]] == ["loss_sync", "feed_wait"]
    assert [round(s, 12) for _, s in r["idle_gaps"]] == [0.045, 0.012]
    names = dict(r["device_ops"])
    assert abs(names["fusion.1"] - (0.028 + 0.034) / 2) < 1e-12
    # on device 1 fusion.2 nests in fusion.3's event: 12 - 6 ms self
    assert abs(names["fusion.3"] - (0.015 + 0.006) / 2) < 1e-12


def test_nested_ops_count_their_self_time():
    host = [(devtrace.WINDOW_SPAN, 0, 100 * MS)]
    dev = [("%while.1 = (f32[]) while(...)", 0, 50 * MS),
           ("%fusion.2 = f32[] fusion(...)", 10 * MS, 20 * MS),
           ("%copy.3 = f32[] copy(...)", 20 * MS, 30 * MS),
           ("%fusion.2 = f32[] fusion(...)", 60 * MS, 70 * MS)]
    r = devtrace.reduce([dev], host)
    ops = {n: round(t, 12) for n, t in r["device_ops"]}
    assert ops == {"while.1": 0.03, "fusion.2": 0.02, "copy.3": 0.01}
    assert abs(r["busy_s"] - 0.06) < 1e-12


def test_reduce_finds_nothing_without_window_or_ops():
    assert devtrace.reduce([[("op", 0, 1)]], []) is None
    assert devtrace.reduce([[]], [(devtrace.WINDOW_SPAN, 0, 1)]) is None


def test_metric_readers_find_nothing_without_a_trace():
    import spec

    run = {"trace": None, "chips": 1, "traced_steps": 8, "traced_s": None,
           "peak_flops": 197e12}
    assert spec.reader("device_idle_share")(run) is None
    assert spec.reader("mfu")(run) is None
    run["trace"] = {"window_s": 1.0, "busy_s": 0.75}
    assert abs(spec.reader("device_idle_share")(run) - 25.0) < 1e-9
