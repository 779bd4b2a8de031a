"""Name the traced device time by the program's layers.

The train step carries ``jax.named_scope`` names in the ``op_name``
metadata of its compiled HLO: ``embed``, ``layers`` (the layer scan),
inside a block ``attn_proj``, ``attn_core``, ``mlp``, ``ssm``, ``rec``,
then ``head_loss`` and ``optimizer``.  Each device op of the traced window
goes to the innermost of those scopes its instruction carries; an op
under ``layers`` and no inner scope goes to ``layer_scan`` (the scan's
slicing and stacking of the per-layer arrays, its loop, and the block's
norms and residual adds that no inner scope holds), an op with none to
``other``.  An instruction the compiler added without metadata takes the
path its computation's named instructions share.  Self times come from
``devtrace.self_times``, so the scopes add up to the traced busy time.

Idle gaps are named ``<benchmark span>/<program span>``: the benchmark's
span that overlaps the gap most (as ``devtrace.reduce`` names it), then,
of the program's spans (``repro.obs``) on that span's host thread, the one
that is the innermost open for the longest part of the gap.  A gap with no
program span open keeps the benchmark's name alone.

Like ``devtrace``, the reduction works on plain tuples, so that the tests
can hand it a trace built by hand.  Every function returns nothing
(``None`` or empty) for a program without scopes or spans.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import devtrace

VOCABULARY = ("embed", "layers", "attn_proj", "attn_core", "mlp", "ssm",
              "rec", "head_loss", "optimizer")
LAYER_SCAN = "layer_scan"
OTHER = "other"
PROGRAM_SPANS = ("platform.check_in", "dataset.plan", "workflow.run",
                 "loader.read", "loader.decode", "loader.wait", "feed.put",
                 "train.dispatch", "train.loss_sync", "train.save")

HostEvent = Tuple[str, float, float, object]   # name, start, end, thread

_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_WRAPPED = re.compile(r"^(?:[\w.\-]+\()+(.*?)\)*$")


def _parts(op_name: str) -> List[str]:
    """The path of an ``op_name``, transforms unwrapped:
    ``transpose(jvp(attn_core))`` is ``attn_core``."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        out.append(m.group(1) if m else part)
    return out


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its ``op_name`` path, over every computation of
    a compiled module's HLO text.  An instruction the compiler added
    without metadata (an async copy or slice, a buffer, a loop's tuple)
    takes the path that every named instruction of its computation shares,
    so that a copy inside the attention's loop is the attention's."""
    out: Dict[str, str] = {}
    unnamed: List[str] = []
    shared: Optional[List[str]] = None

    def close():
        for name in unnamed:
            out[name] = "/".join(shared or ())

    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            if line.rstrip().endswith("{") and not line.startswith(" "):
                close()                       # a computation starts
                unnamed, shared = [], None
            continue
        op = _OP_NAME.search(line)
        if op is None:
            unnamed.append(m.group(1))
            continue
        out[m.group(1)] = op.group(1)
        parts = _parts(op.group(1))
        if shared is None:
            shared = parts
        else:
            n = 0
            while n < min(len(shared), len(parts)) and shared[n] == parts[n]:
                n += 1
            shared = shared[:n]
    close()
    return out


def scope(op_name: str) -> str:
    """The innermost scope of the vocabulary on an ``op_name`` path."""
    found = None
    for name in _parts(op_name):
        if name in VOCABULARY:
            found = name
    if found is None:
        return OTHER
    return LAYER_SCAN if found == "layers" else found


def _window(host) -> Optional[Tuple[float, float]]:
    spans = [(e[1], e[2]) for e in host if e[0] == devtrace.WINDOW_SPAN]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def self_seconds(device_ops: Sequence[Sequence[devtrace.Event]], host,
                 names: Dict[str, str]) -> Optional[Dict[str, float]]:
    """Self seconds inside the traced window, averaged over the devices,
    per ``<scope>:<hlo name>``.  None without a window, ops or names."""
    win = _window(host)
    if win is None or not any(device_ops) or not names:
        return None
    lo, hi = win
    out: Dict[str, float] = defaultdict(float)
    for ops in device_ops:
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
               if min(e, hi) > max(s, lo)]
        for (n, _, _), t in zip(ops, devtrace.self_times(ops)):
            op = devtrace.short(n)
            key = f"{scope(names.get(op, ''))}:{op}"
            out[key] += t * 1e-9 / len(device_ops)
    return dict(out)


def scope_ms(per_op: Optional[Dict[str, float]], steps: int
             ) -> Optional[Dict[str, float]]:
    """Milliseconds per traced step in each scope that holds an op."""
    if not per_op:
        return None
    out: Dict[str, float] = defaultdict(float)
    for key, s in per_op.items():
        out[key.split(":", 1)[0]] += s * 1e3 / steps
    return dict(out)


def top_ops(per_op: Optional[Dict[str, float]], top: int = 10):
    if not per_op:
        return []
    ranked = sorted(per_op.items(), key=lambda x: -x[1])
    return [[k, s] for k, s in ranked[:top]]


def idle_gaps(device_ops: Sequence[Sequence[devtrace.Event]],
              host: Sequence[HostEvent], top: int = 10) -> List[List]:
    """The longest device-0 idle gaps of the window, each as
    ``[<benchmark span>/<program span>, seconds]``."""
    win = _window(host)
    if win is None or not any(device_ops):
        return []
    lo, hi = win
    bench = [e for e in host if e[0] in devtrace.HOST_SPANS]
    program = [e for e in host if e[0] in PROGRAM_SPANS]
    busy0 = devtrace.union((s, e) for _, s, e in device_ops[0])
    named = []
    for gs, ge in devtrace.gaps(busy0, lo, hi):
        label = _most(bench, gs, ge)
        if label is None:
            named.append([OTHER, (ge - gs) * 1e-9])
            continue
        inner = _innermost([e for e in program if e[3] == label[3]], gs, ge)
        name = label[0] if inner is None else f"{label[0]}/{inner}"
        named.append([name, (ge - gs) * 1e-9])
    named.sort(key=lambda x: -x[1])
    return named[:top]


def _most(events, gs: float, ge: float):
    """The event overlapping [gs, ge) most, the first on a tie, as
    ``devtrace.reduce`` picks it."""
    best, most = None, 0.0
    for e in events:
        ov = min(e[2], ge) - max(e[1], gs)
        if ov > most:
            best, most = e, ov
    return best


def _innermost(events, gs: float, ge: float) -> Optional[str]:
    """The name of the span that is innermost (the shortest open) for the
    longest part of [gs, ge); None where no span is open in it."""
    cuts = sorted({gs, ge} | {t for e in events for t in e[1:3]
                              if gs < t < ge})
    held: Dict[str, float] = defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        open_ = [e for e in events if e[1] <= a and b <= e[2]]
        if open_:
            held[min(open_, key=lambda e: e[2] - e[1])[0]] += b - a
    return max(held, key=held.get) if held else None


def host_events(path: str) -> List[HostEvent]:
    """The benchmark's and the program's spans on the host planes of one
    ``.xplane.pb`` file; the thread is the (plane, line) the profiler put
    the span on."""
    import jax

    keep = set(devtrace.HOST_SPANS) | set(PROGRAM_SPANS) | {
        devtrace.WINDOW_SPAN}
    out = []
    pd = jax.profiler.ProfileData.from_file(path)
    for p, plane in enumerate(pd.planes):
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns, (p, i))
                    for e in line.events if e.name in keep]
    return out
