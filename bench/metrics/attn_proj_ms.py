"""Device self time per traced step of the ops in scope ``attn_proj``:
the QKV projections, rotary and the output projection (bench/scopes.py)."""


def read(run):
    return (run.get("scope_ms") or {}).get("attn_proj")
