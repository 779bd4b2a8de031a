"""Device self time per traced step of the ops in scope ``head_loss``:
the final norm, the LM head and the cross entropy (bench/scopes.py)."""


def read(run):
    return (run.get("scope_ms") or {}).get("head_loss")
