"""Both runners end to end at toy size on the CPU, behind the explicit
rehearsal switch; the refusal of anything but the chip; and a cell, a
configuration, a mix and a per-layer metric added as files plus one
entry each, with no edit to a file that was there."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import manifest as rules
from benchmarks import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _reported(manifest, group, cell):
    return {m["name"] for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell,trace", [
    ("ernie-base.pretrain", 1),
    ("gpt3-1.3b.serve-decode", 0),
    ("gpt3-1.3b.serve-chat", 1),
])
def test_rehearsal_walks_the_cell(manifest, tmp_path, capfd, cell, trace):
    out_file = tmp_path / "would_be.json"
    rc = bench_run.main(["--workload", cell, "--seed", "3000000001",
                         "--seconds", "2", "--trace", str(trace),
                         "--rehearse-cpu", str(out_file)])
    assert rc == 0
    stdout = capfd.readouterr().out
    assert "{" not in stdout, "a rehearsal prints no result line"
    would_be = json.loads(out_file.read_text())
    assert would_be["rehearsal"] is True
    result = would_be["result"]
    assert set(result) - {"breakdown"} == RESULT_KEYS
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["device"]) == DEVICE_KEYS      # no busy_s off the chip
    assert result["device"]["platform"] != "tpu"
    names = set(result["metrics"])
    if trace:
        # readers of the device trace find nothing on the CPU and are
        # left out; counters, series and host spans are all there
        declared = _reported(manifest, "per_layer", cell)
        from_trace = {m["name"] for m in manifest["per_layer"]
                      if m["source"] == "device_trace"}
        assert names == declared - from_trace
    else:
        assert names == _reported(manifest, "end_to_end", cell)
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float) and m["value"] >= 0, name


def _env(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)
    return env


def test_off_the_chip_is_refused_without_a_result(tmp_path):
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "ernie-base.pretrain", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=_env(tmp_path), capture_output=True, text=True, timeout=300)
    assert done.returncode == 2
    assert done.stdout.strip() == ""
    assert "measures the chip" in done.stderr


TOY_CONFIG = {
    "name": "toy-gpt", "source": "a test", "family": "gpt", "reduced": [],
    "model": {"vocab_size": 128, "hidden_size": 32, "num_layers": 1,
              "num_heads": 2, "max_seq_len": 64, "dropout": 0.0,
              "attn_dropout": 0.0},
    "serving": {"max_slots": 2, "cache_dtype": "bfloat16", "num_blocks": 0,
                "max_seq_len": 64, "queue_cap": 64,
                "request_timeout_s": 60.0},
}
TOY_MIX = {
    "runner": "serve", "loop": "open", "rate_rps": 6.0, "arrival": "burst",
    "burst_n": 3, "users": 0, "prompt_tokens": [3, 9],
    "answer_tokens": [2, 4], "drain_s": 20.0, "shape_seed": 3,
    "trace_start_s": 0.0, "trace_s": 0.5,
}
TOY_READER = '''"""Layer `admission`: requests answered, a counter."""


def read(run):
    return float(run["facts"]["answered"])
'''


def test_a_cell_is_added_as_files_and_one_entry(manifest, tmp_path):
    for name in ("benchmarks",):
        shutil.copytree(os.path.join(ROOT, name), tmp_path / name,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "tests" / "benchmark").mkdir(parents=True)
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks")
              .rglob("*") if p.is_file()}
    bench = tmp_path / "benchmarks"
    (bench / "configs" / "toy-gpt.json").write_text(json.dumps(TOY_CONFIG))
    (bench / "traffic" / "toy-mix.json").write_text(json.dumps(TOY_MIX))
    (bench / "metrics" / "toy_answered").with_suffix(".py") \
        .write_text(TOY_READER)
    grown = json.loads(json.dumps(manifest))
    grown["configs"].append({
        "name": "toy-gpt", "source": "a test",
        "file": "benchmarks/configs/toy-gpt.json", "reduced": [],
        "why": "shows that a configuration is a file"})
    grown["workloads"].append({
        "name": "toy-gpt.bursts", "config": "toy-gpt", "traffic": "toy-mix",
        "chips": 1, "why": "shows that a cell is an entry"})
    for m in grown["end_to_end"]:
        if m["name"] in ("request_p50_ms", "request_p90_ms"):
            m["workloads"].append("toy-gpt.bursts")
    grown["per_layer"].append({
        "name": "toy_answered", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "admission",
        "moves": "request_p50_ms", "workloads": ["toy-gpt.bursts"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(grown))
    assert rules.problems(grown, str(tmp_path)) == []

    out_file = tmp_path / "would_be.json"
    done = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload",
         "toy-gpt.bursts", "--seed", "5", "--seconds", "2", "--trace", "1",
         "--rehearse-cpu", str(out_file)],
        env=_env(tmp_path), capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == ""
    result = json.loads(out_file.read_text())["result"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 12
    assert result["metrics"]["toy_answered"] == {"value": 12.0,
                                                 "unit": "requests"}
    after = {p: p.read_bytes() for p in bench.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[p] == data for p, data in before.items()), \
        "a file that was there changed"
