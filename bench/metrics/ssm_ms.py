"""Device self time per traced step of the ops in scope ``ssm``: the
Mamba-2 mixer's projections, causal conv, gated norm and the SSD scan
(its inner ``ssd`` scope), forward, remat recompute and backward
(bench/scopes.py)."""


def read(run):
    return (run.get("scope_ms") or {}).get("ssm")
