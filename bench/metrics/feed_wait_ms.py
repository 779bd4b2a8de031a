"""Mean per window step of the host span around next() on the DeviceFeed
iterator."""


def read(run):
    waits = run["feed_wait_s"]
    return sum(waits) / len(waits) * 1e3 if waits else None
