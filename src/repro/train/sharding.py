"""Sharding rules -> PartitionSpecs for params, optimizer state and batches.

Parameters are matched by their tree path (names are stable across the
model zoo) and given PartitionSpecs built from a rule table.  An axis shards
a dim only where its size divides the dim, and axes the mesh lacks are
dropped.

Roles and their default mesh axes:
- batch        -> ("pod", "data")
- d_model rows of big matrices -> fsdp_axis "data"  (FSDP; gathered on use)
- d_ff / heads / vocab         -> tp_axis "model"   (tensor parallel)
- MoE experts  -> expert_axis "data" when it divides the expert count

With ``batch_axes=("data",), fsdp_axis=None, tp_axis=None`` on the 1-D mesh
of ``make_local_mesh`` every param is replicated and the batch is split over
"data": plain data parallelism.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingRules", "param_specs", "batch_specs",
           "opt_state_specs", "named", "constrain"]

PyTree = Any


class ShardingRules:
    """Maps logical roles to mesh axes."""

    def __init__(
        self,
        mesh: Mesh,
        *,
        batch_axes: Tuple[str, ...] = ("pod", "data"),
        fsdp_axis: Optional[str] = "data",
        tp_axis: Optional[str] = "model",
        expert_axis: Optional[str] = "data",
    ):
        self.mesh = mesh
        names = mesh.axis_names

        def _valid(axis):
            if isinstance(axis, tuple):
                axis = tuple(a for a in axis if a in names)
                return axis or None
            return axis if axis in names else None

        self.batch_axes = tuple(a for a in batch_axes if a in names)
        self.fsdp_axis = _valid(fsdp_axis)
        self.tp_axis = _valid(tp_axis)
        self.expert_axis = _valid(expert_axis)

    def size(self, axis) -> int:
        if axis is None:
            return 1
        if isinstance(axis, tuple):
            n = 1
            for a in axis:
                n *= self.mesh.shape[a]
            return n
        return self.mesh.shape[axis]

    def axis_if_divides(self, axis, dim: int):
        """axis may be a name or a tuple of names (multi-axis sharding)."""
        if axis is not None and dim > 0 and dim % self.size(axis) == 0:
            return axis
        return None

    def batch_spec_axes(self, batch: int):
        """Largest prefix of batch_axes whose product divides batch."""
        out = []
        prod = 1
        for a in self.batch_axes:
            if batch % (prod * self.size(a)) == 0:
                out.append(a)
                prod *= self.size(a)
        return tuple(out) if out else None


# ---------------------------------------------------------------------------
# Param rules (path-regex -> spec builder)
# ---------------------------------------------------------------------------


def _param_rule(path: str, shape: Tuple[int, ...], r: ShardingRules) -> P:
    """Assign a spec given the param path and shape.

    Paths look like: "embed", "blocks/pos0/attn/wq/w", "tail/tail0/mlp/wi",
    "blocks/pos0/moe/wi", "decoder/self_attn/wo/w", "lm_head", ...
    Leading stacked dims (scan repeats) are never sharded.
    """
    ndim = len(shape)
    stacked = path.startswith("blocks/") or path.startswith("decoder/") \
        or path.startswith("encoder/")
    lead: Tuple[Optional[str], ...] = (None,) if stacked else ()
    body = shape[1:] if stacked else shape
    nb = len(body)

    def spec(*axes):
        return P(*(lead + axes))

    fsdp, tp = r.fsdp_axis, r.tp_axis

    # ---- embeddings / heads -------------------------------------------------
    if re.fullmatch(r".*embed", path):
        return P(r.axis_if_divides(tp, shape[0]),
                 r.axis_if_divides(fsdp, shape[1]))
    if re.fullmatch(r".*lm_head", path):
        return P(r.axis_if_divides(fsdp, shape[0]),
                 r.axis_if_divides(tp, shape[1]))

    # ---- MoE ------------------------------------------------------------------
    if "/moe/" in path:
        if path.endswith("router"):
            return spec(r.axis_if_divides(fsdp, body[0]), None)
        # wi/wg/wo: (E, D, F) or (E, F, D)
        E = body[0]
        ea = r.axis_if_divides(r.expert_axis, E)

        def minus(axis, used):
            """axis with names already used removed (no duplicate axes)."""
            if axis is None:
                return None
            used_names = set(used if isinstance(used, tuple)
                             else ([] if used is None else [used]))
            names = axis if isinstance(axis, tuple) else (axis,)
            left = tuple(a for a in names if a not in used_names)
            return left if len(left) > 1 else (left[0] if left else None)

        if path.endswith(("wi", "wg")):
            d_axis = r.axis_if_divides(minus(fsdp, ea), body[1])
            return spec(ea, d_axis, r.axis_if_divides(tp, body[2]))
        d_axis = r.axis_if_divides(minus(fsdp, ea), body[2])
        return spec(ea, r.axis_if_divides(tp, body[1]), d_axis)

    # ---- biases / norms / vectors ------------------------------------------------
    if nb <= 1:
        return spec(*([None] * nb))

    # ---- attention projections ------------------------------------------------
    if re.search(r"(attn|self_attn|cross_attn)/w[qkv]/w$", path):
        return spec(r.axis_if_divides(fsdp, body[0]),
                    r.axis_if_divides(tp, body[1]))
    if re.search(r"(attn|self_attn|cross_attn)/wo/w$", path):
        return spec(r.axis_if_divides(tp, body[0]),
                    r.axis_if_divides(fsdp, body[1]))

    # ---- MLP ----------------------------------------------------------------------
    if re.search(r"mlp/(wi|wg)$", path):
        return spec(r.axis_if_divides(fsdp, body[0]),
                    r.axis_if_divides(tp, body[1]))
    if re.search(r"mlp/wo$", path):
        return spec(r.axis_if_divides(tp, body[0]),
                    r.axis_if_divides(fsdp, body[1]))

    # ---- SSM / recurrent ------------------------------------------------------------
    if re.search(r"ssm/in_proj$", path) or re.search(r"rec/(in_x|in_y)$", path):
        return spec(r.axis_if_divides(fsdp, body[0]),
                    r.axis_if_divides(tp, body[1]))
    if re.search(r"ssm/out_proj$", path) or re.search(r"rec/out$", path):
        return spec(r.axis_if_divides(tp, body[0]),
                    r.axis_if_divides(fsdp, body[1]))
    if re.search(r"rec/gate_[ri]$", path):
        return spec(r.axis_if_divides(fsdp, body[0]),
                    r.axis_if_divides(tp, body[1]))
    if re.search(r"(ssm|rec)/conv_w$", path):
        return spec(None, r.axis_if_divides(tp, body[1]))

    # ---- fallback: shard the biggest dim on tp if divisible ----------------------------
    axes = [None] * nb
    order = sorted(range(nb), key=lambda i: -body[i])
    for i in order:
        a = r.axis_if_divides(tp, body[i])
        if a:
            axes[i] = a
            break
    return spec(*axes)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_specs(params: PyTree, rules: ShardingRules) -> PyTree:
    """PartitionSpec tree mirroring ``params`` (works on ShapeDtypeStructs)."""

    def assign(path, leaf):
        return _param_rule(_path_str(path), tuple(leaf.shape), rules)

    return jax.tree_util.tree_map_with_path(assign, params)


def opt_state_specs(opt_state: PyTree, params: PyTree, pspecs: PyTree,
                    rules: ShardingRules) -> PyTree:
    """Optimizer-state specs: moment tensors mirror their param's spec.

    Handles: adamw (m/v mirror params), adafactor (vr/vc take the matching
    prefix of the param spec), adamw8bit (q/scale blocked — replicate; the
    flattening breaks alignment with named dims), and scalar steps.
    """
    flat_p, _ = jax.tree.flatten(params)
    flat_s = jax.tree.leaves(pspecs,
                             is_leaf=lambda x: isinstance(x, P))
    shape_to_spec: Dict[Tuple[int, ...], P] = {}
    for p, s in zip(flat_p, flat_s):
        shape_to_spec.setdefault(tuple(p.shape), s)

    def assign(leaf):
        shp = tuple(leaf.shape)
        if shp in shape_to_spec:
            return shape_to_spec[shp]
        if len(shp) == 0:
            return P()
        # factored adafactor stats: match a param spec prefix/suffix
        for pshape, s in shape_to_spec.items():
            if shp == pshape[:-1]:
                return P(*tuple(s)[:-1]) if len(tuple(s)) >= len(shp) else P()
            if shp == pshape[:-2] + pshape[-1:]:
                t = tuple(s)
                if len(t) == len(pshape):
                    return P(*(t[:-2] + t[-1:]))
        return P()  # int8 blocks, scales, anything else: replicate

    return jax.tree.map(assign, opt_state)


# ---------------------------------------------------------------------------
# Batch specs
# ---------------------------------------------------------------------------


def batch_specs(batch: PyTree, rules: ShardingRules) -> PyTree:
    """Inputs: batch dim over batch_axes; model-dim embeds optionally on tp."""

    def assign(path, leaf):
        b_axes = rules.batch_spec_axes(leaf.shape[0])
        rest = [None] * (len(leaf.shape) - 1)
        name = _path_str(path)
        if "frontend_embeds" in name and len(leaf.shape) == 3:
            rest[-1] = rules.axis_if_divides(rules.tp_axis, leaf.shape[-1])
        return P(b_axes, *rest)

    return jax.tree_util.tree_map_with_path(assign, batch)


def named(mesh: Mesh, spec_tree: PyTree) -> PyTree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def constrain(x, rules: ShardingRules, spec: P):
    return jax.lax.with_sharding_constraint(x, NamedSharding(rules.mesh, spec))


class ActivationSharding:
    """Constraint points the models call (via RuntimeConfig.act_sharding).

    Keeps GSPMD propagation on the rails: batch over the data axes and
    vocab (logits) over tp.  No-op when unset (CPU tests).
    """

    def __init__(self, rules: ShardingRules):
        self.rules = rules

    def _spec(self, x, last_axis):
        b_axes = self.rules.batch_spec_axes(x.shape[0])
        mid = [None] * (x.ndim - 2)
        return P(b_axes, *mid, last_axis)

    def hidden(self, x):
        """(B, S, D) residual-stream activations: batch over the data axes."""
        return constrain(x, self.rules, self._spec(x, None))

    def logits(self, x):
        """(B, S, V_pad) — vocab over tp (Megatron layout: no gather)."""
        r = self.rules
        return constrain(
            x, r, self._spec(x, r.axis_if_divides(r.tp_axis, x.shape[-1])))
