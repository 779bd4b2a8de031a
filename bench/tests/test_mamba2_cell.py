"""A --tiny CPU rehearsal of ``mamba2.steady`` as the repository's own
BENCHMARK.json names it: the Mamba-2 configuration, the steady traffic
and the SSD on the program's training path, with the check passing."""

import json

import pytest

import spec
from conftest import BENCH, rehearsal_root
from test_spec import run_cli

CELL = "mamba2.steady"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal_root(tmp_path_factory.mktemp("checkout"))


def test_cell_is_in_the_benchmark(root):
    c = spec.cell(spec.load_benchmark(root), root, CELL)
    assert c.chips == 1 and c.config["name"] == "mamba2-1.3b"
    assert c.config["model"]["pattern"] == ["ssm"]
    assert c.config["runtime"]["ssd_impl"] == "xla"
    assert {m["name"] for m in c.end_to_end} == {
        "train_tokens_per_s", "step_p90_ms", "setup_s"}
    assert {m["name"] for m in c.per_layer} == {
        "platform_setup_s", "feed_wait_ms", "mfu", "device_idle_share"}
    assert set(c.limits) == {"rows_bad", "loss_gap", "grad_gap",
                             "update_gap"}


def test_tiny_run_is_correct(root):
    out = run_cli(root, "--workload", CELL, "--seed", "1882406783",
                  "--seconds", "1", "--tiny")
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True, last["checks"]
    assert last["checks"]["steps_nonfinite"]["value"] == 0
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
