"""1 - busy / window over the traced steps: busy is the union of the
device operations' intervals in the profiler trace, averaged over the
chips (bench/devtrace.py)."""


def read(run):
    t = run["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
