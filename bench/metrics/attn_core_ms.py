"""Device self time per traced step of the ops in scope ``attn_core``:
the attention's score, softmax and value products, forward, remat
recompute and backward (bench/scopes.py)."""


def read(run):
    return (run.get("scope_ms") or {}).get("attn_core")
