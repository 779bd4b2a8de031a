"""Reduce a profiler trace to the numbers the metrics read.

- busy: the union of the intervals in which an operation ran on a device,
  inside the traced window (the benchmark's own ``bench.traced`` host
  span), averaged over the devices;
- idle gaps: the stretches of the window in which device 0 ran nothing,
  each named by the benchmark's host span that overlaps it most
  (``feed_wait``, ``dispatch``, ``loss_sync``; ``other`` where none does);
- device ops: self time per operation name (its time less that of the
  operations nested in it, as a loop's body ops are in the loop's event),
  averaged over the devices.

The reduction works on plain ``(name, start_ns, end_ns)`` tuples, so that
the tests can hand it a trace built by hand.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.traced"
HOST_SPANS = ("feed_wait", "dispatch", "loss_sync")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINES = ("XLA Ops",)

Event = Tuple[str, float, float]          # name, start_ns, end_ns


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> List[Tuple[float, float]]:
    """Parts of the (disjoint, sorted) intervals ``a`` not covered by
    ``b``."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float):
    return subtract([(lo, hi)], busy)


def short(name: str) -> str:
    """An HLO op event's instruction name: ``%fusion.3 = (...) fusion(...)``
    -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(ops: Sequence[Event]) -> List[float]:
    """Each op's time less that of the ops nested directly inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    inner = [0.0] * len(ops)
    stack: List[int] = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            inner[stack[-1]] += e - s
        stack.append(i)
    return [e - s - inner[i] for i, (_, s, e) in enumerate(ops)]


def reduce(device_ops: Sequence[Sequence[Event]], host: Sequence[Event],
           top: int = 10) -> Optional[Dict]:
    """``device_ops``: per device, its op events; ``host``: the host span
    events.  None where the window span or every device op is missing."""
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not windows or not any(device_ops):
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    window_s = (hi - lo) * 1e-9
    busy_s = []
    per_op: Dict[str, float] = defaultdict(float)
    for ops in device_ops:
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
               if min(e, hi) > max(s, lo)]
        busy_s.append(length(union((s, e) for _, s, e in ops)) * 1e-9)
        for (n, _, _), t in zip(ops, self_times(ops)):
            per_op[short(n)] += t * 1e-9 / len(device_ops)
    busy0 = union((s, e) for _, s, e in device_ops[0])
    spans = [(n, s, e) for n, s, e in host if n in HOST_SPANS]
    named = []
    for gs, ge in gaps(busy0, lo, hi):
        best, most = "other", 0.0
        for n, s, e in spans:
            ov = min(e, ge) - max(s, gs)
            if ov > most:
                best, most = n, ov
        named.append([best, (ge - gs) * 1e-9])
    named.sort(key=lambda x: -x[1])
    ops_top = sorted(per_op.items(), key=lambda x: -x[1])[:top]
    return {
        "window_s": window_s,
        "busy_s": sum(busy_s) / len(busy_s),
        "device_ops": [[n, s] for n, s in ops_top],
        "idle_gaps": named[:top],
    }


def events_from_profile(path: str):
    """(per-device op events, host span events, inventory) of one
    ``.xplane.pb`` file."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices, host, inventory = [], [], []
    for plane in pd.planes:
        lines = list(plane.lines)
        inventory.append([plane.name, [ln.name for ln in lines][:12]])
        if DEVICE_PLANE.match(plane.name):
            chosen = [ln for ln in lines if ln.name in OP_LINES] or [
                ln for ln in lines
                if ln.name not in ("Steps", "XLA Modules")]
            devices.append([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for ln in chosen for e in ln.events])
        elif plane.name.startswith("/host:"):
            for ln in lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in ln.events
                         if e.name in HOST_SPANS or e.name == WINDOW_SPAN]
    return devices, host, inventory


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    return found[-1] if found else None
