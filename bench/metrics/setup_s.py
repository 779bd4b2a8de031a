"""Process start to the first step of the window: ingest + derive, weights
made on the device, compilation (from the persistent cache after the
first run) and the three steps the check follows."""


def read(run):
    return run["setup_s"]
