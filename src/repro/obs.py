"""The program's spans: named host intervals at the platform's and the
trainer's layer boundaries.

    from repro import obs

    with obs.span("loader.decode"):
        ...

Off by default: ``span`` then returns one shared object whose ``with``
does nothing, reads no clock and allocates nothing.  After ``enable()``
every span is kept in memory (name, start and end on ``perf_counter_ns``,
the enclosing span on the same thread, the thread) and also enters a
``jax.profiler.TraceAnnotation`` of its name, so that under a profiler
session it lies on the host plane, on the same clock as the device's
operations.  ``spans()`` returns what was kept, ``reset()`` clears it and
``export(path)`` writes it as Chrome trace events, which Perfetto opens.
``count(name, n)`` adds to a named total, also only when tracing is on;
``counters()`` returns the totals and ``reset()`` clears them too.

Span names are dotted, ``<layer>.<what>``:

- ``platform.check_in``, ``dataset.plan``: the platform's write and plan
  calls; ``workflow.run``: a registered workflow run (derive);
- ``loader.read``, ``loader.decode``: one batch on a decode worker;
  ``loader.wait``: the consumer waiting for the next batch;
- ``feed.put``: one batch's ``device_put``;
- ``train.dispatch``, ``train.loss_sync``, ``train.save``: the train
  loop's step dispatch, its wait for the loss, a checkpoint.

Counter names are dotted the same way:

- ``attn.pairs_live``, ``attn.pairs_all``: per trace of a chunked XLA
  attention call, the block pairs its scans run and those of its whole
  grid (``kernels/flash_attention/ops.py``).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Dict, List, NamedTuple, Optional

__all__ = ["Span", "span", "enable", "disable", "spans", "reset", "export",
           "count", "counters"]


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: Optional[int]      # id of the span open around it, same thread
    thread: int                # threading.get_ident()
    thread_name: str


class _Off:
    """The span of a program that is not tracing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()
_on = False
_annotation = None             # jax.profiler.TraceAnnotation, once enabled
_kept: List[Span] = []
_counts: Dict[str, int] = {}
_lock = threading.Lock()
_ids = itertools.count()
_local = threading.local()


class _On:
    __slots__ = ("name", "id", "parent", "start_ns", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.id = next(_ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _local.stack.pop()
        t = threading.current_thread()
        with _lock:
            _kept.append(Span(self.name, self.start_ns, end, self.id,
                              self.parent, t.ident, t.name))
        return False


def span(name: str):
    """A context manager timing ``name`` when tracing is on."""
    if not _on:
        return _OFF
    return _On(name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the total ``name`` when tracing is on."""
    if not _on:
        return
    with _lock:
        _counts[name] = _counts.get(name, 0) + n


def enable() -> None:
    global _on, _annotation
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    _on = True


def disable() -> None:
    global _on
    _on = False


def spans() -> List[Span]:
    """Every span closed since the last ``reset()``, in closing order."""
    with _lock:
        return list(_kept)


def counters() -> Dict[str, int]:
    """Every counter's total since the last ``reset()``."""
    with _lock:
        return dict(_counts)


def reset() -> None:
    with _lock:
        _kept.clear()
        _counts.clear()


def export(path: str) -> str:
    """Write the kept spans as Chrome trace-event JSON; returns ``path``."""
    pid = os.getpid()
    kept = spans()
    events = [{"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
               "args": {"name": tname}}
              for tid, tname in sorted({(s.thread, s.thread_name)
                                        for s in kept})]
    events += [{"name": s.name, "ph": "X", "pid": pid, "tid": s.thread,
                "ts": s.start_ns / 1e3, "dur": (s.end_ns - s.start_ns) / 1e3,
                "args": {"id": s.id, "parent": s.parent}} for s in kept]
    with open(path, "w") as fh:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, fh)
    return path
