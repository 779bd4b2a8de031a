"""Tokens of every step completed in the window, over the window's
seconds and the chips."""


def read(run):
    if not run["step_s"]:
        return None
    return run["tokens"] / run["window_s"] / run["chips"]
