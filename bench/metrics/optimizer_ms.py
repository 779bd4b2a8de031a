"""Device self time per traced step of the ops in scope ``optimizer``:
the gradient clip and the parameter update (bench/scopes.py)."""


def read(run):
    return (run.get("scope_ms") or {}).get("optimizer")
