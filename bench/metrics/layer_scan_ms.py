"""Device self time per traced step of the ops under ``layers`` in no
inner scope: the layer scan's slicing and stacking of per-layer arrays,
its loop, and the block norms and residual adds (bench/scopes.py)."""


def read(run):
    return (run.get("scope_ms") or {}).get("layer_scan")
