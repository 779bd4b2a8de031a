"""Host span around the raw documents' check_in, the tokenize -> pack
workflow run, the snapshot checkout and the loader's plan."""


def read(run):
    return run["platform_setup_s"]
