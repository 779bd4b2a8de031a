"""Device self time per traced step of the ops in scope ``mlp``: the
block's MLP or mixture of experts (bench/scopes.py)."""


def read(run):
    return (run.get("scope_ms") or {}).get("mlp")
