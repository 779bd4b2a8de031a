"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric:
each lives in files of its own that this module finds by name.

- configuration ``<c>``: the ``file`` its ``configs`` entry gives;
- traffic mix ``<t>``: ``bench/traffic/<t>.json``;
- per-layer metric ``<m>``: ``bench/metrics/<m>.py``, whose ``read(run)``
  returns a number or None;
- the limits of a cell ``<w>``: ``bench/limits/<w>.json``.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    limits: Dict = field(default_factory=dict)


def load_benchmark(root: Path) -> Dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise FileNotFoundError(f"no BENCHMARK.json in {root}")
    return json.loads(path.read_text())


def _by_name(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; have "
                   f"{[e['name'] for e in entries]}")


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(spec: Dict, root: Path, name: str) -> Cell:
    w = _by_name(spec["workloads"], name, "workload")
    c = _by_name(spec["configs"], w["config"], "configuration")
    config = json.loads((root / c["file"]).read_text())
    bench = root / "bench"
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits_path = bench / "limits" / f"{name}.json"
    limits = (json.loads(limits_path.read_text())
              if limits_path.is_file() else {})
    e2e = [m for m in spec["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if _applies(m, name) and m["moves"] in reported]
    return Cell(name, int(w["chips"]), config, traffic, e2e, layer, limits)


def reader(metric: str, root: Path = BENCH.parent
           ) -> Callable[[Dict], Optional[float]]:
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
