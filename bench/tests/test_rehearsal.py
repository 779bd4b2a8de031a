"""A --tiny CPU rehearsal of every cell: a well-formed last line that names
the CPU and carries no device metric, with the check passing."""

import json

import pytest

import spec
from conftest import BENCH, rehearsal_root
from test_spec import run_cli

BENCHMARK = spec.load_benchmark(BENCH.parent)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_tiny_rehearsal(root, cell, trace):
    out = run_cli(root, "--workload", cell, "--seed", "2147483659",
                  "--seconds", "1", "--trace", trace, "--tiny")
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert list(last)[:3] == ["correct", "attempted", "failed"]
    assert list(last)[-1] == "checks"
    assert last["correct"] is True, last["checks"]
    assert last["metrics"] == {}
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == spec.cell(BENCHMARK, root, cell).chips
    assert last["attempted"] > 0 and last["failed"] == 0
    tail = out.stderr.strip().splitlines()[-len(last["checks"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in tail)
