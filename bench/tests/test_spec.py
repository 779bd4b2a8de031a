"""Cells, configurations, traffic mixes, limits and metric readers are
found by name; a cell added from new files alone runs."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import spec
from conftest import BENCH

ROOT = BENCH.parent


def run_cli(root, *args, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(root / ".jax_cache_test"),
           **(env_extra or {})}
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *args], cwd=root, env=env, capture_output=True,
                          text=True, timeout=600)


def test_lookup_by_name():
    bench = spec.load_benchmark(ROOT)
    names = [w["name"] for w in bench["workloads"]]
    assert names == ["tinyllama.steady"]
    c = spec.cell(bench, ROOT, "tinyllama.steady")
    assert c.chips == 1 and c.config["name"] == "tinyllama-1.1b"
    assert c.traffic["seq_len"] == 2048
    assert {m["name"] for m in c.end_to_end} == {
        "train_tokens_per_s", "step_p90_ms", "setup_s"}
    assert {m["name"] for m in c.per_layer} == {
        "platform_setup_s", "feed_wait_ms", "mfu", "device_idle_share"}
    assert c.limits["rows_bad"] == 0
    with pytest.raises(KeyError):
        spec.cell(bench, ROOT, "no.such.cell")


def test_every_named_metric_and_config_has_its_file():
    bench = spec.load_benchmark(ROOT)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.reader(m["name"]))
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        changed = set(cfg["reduced"]) | set(cfg.get("departures", {}))
        assert set(c["reduced"]) == changed
        assert changed <= set(cfg["published"])
    for w in bench["workloads"]:
        traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                             .read_text())
        assert (BENCH / traffic["documents"]).is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()


@pytest.fixture
def copy_root(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_new_cell_from_new_files_alone(copy_root):
    root = copy_root
    (root / "src").symlink_to(ROOT / "src")
    # new data: documents of at most 256 bytes, cut from the same sources
    short = []
    with open(root / "bench/corpora/cpython-3.12.12-lib.jsonl") as fh:
        for line in fh:
            doc = json.loads(line)
            short += [{"path": doc["path"], "text": p}
                      for p in doc["text"].split("\n\n") if 0 < len(p) <= 256]
            if len(short) > 2000:
                break
    (root / "bench/corpora/short.jsonl").write_text(
        "".join(json.dumps(d) + "\n" for d in short))
    traffic = json.loads((root / "bench/traffic/steady.json").read_text())
    traffic["documents"] = "corpora/short.jsonl"
    (root / "bench/traffic/shortdoc.json").write_text(json.dumps(traffic))
    (root / "bench/limits/mamba2.shortdoc.json").write_text(json.dumps(
        {"rows_bad": 0, "loss_gap": 3e-3, "grad_gap": 0.07,
         "update_gap": 0.018}))
    # a configuration whose file is there but that no cell runs yet
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "mamba2-1.3b",
        "source": "https://huggingface.co/state-spaces/mamba2-1.3b",
        "file": "bench/configs/mamba2-1.3b.json",
        "reduced": ["n_layer", "pad_vocab_size_multiple",
                    "residual_in_fp32", "norm_epsilon"],
        "why": "attention-free Mamba-2"})
    bench["workloads"].append({
        "name": "mamba2.shortdoc", "config": "mamba2-1.3b",
        "traffic": "shortdoc", "chips": 1, "why": "documents under 256"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tinyllama.steady" in m.get("workloads", []):
            m["workloads"].append("mamba2.shortdoc")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    c = spec.cell(spec.load_benchmark(root), root, "mamba2.shortdoc")
    assert c.traffic["documents"] == "corpora/short.jsonl"
    out = run_cli(root, "--workload", "mamba2.shortdoc", "--seed", "3",
                  "--seconds", "1", "--tiny")
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["device"]["platform"] == "cpu"
    assert "platform: " in out.stderr
    docs = int(out.stderr.split("platform: ")[1].split()[0])
    assert docs > 100                     # short documents, many of them


def test_checkout_without_the_program_prints_no_result(copy_root):
    out = run_cli(copy_root, "--workload", "tinyllama.steady", "--seed", "1",
                  "--seconds", "1")
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_no_tpu_exits_without_result():
    out = run_cli(ROOT, "--workload", "tinyllama.steady", "--seed", "1",
                  "--seconds", "1")
    assert out.returncode == 2 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr
