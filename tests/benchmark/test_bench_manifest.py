"""BENCHMARK.json against every rule of the benchmark's contract, and
the validator against the breaches it exists to catch (PR 23 fell on a
layer name with a space before a single run)."""

import copy
import json
import os

import pytest

from benchmarks import manifest as rules

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_meets_every_rule(manifest):
    assert rules.problems(manifest, ROOT) == []


def test_every_file_a_cell_names_exists(manifest):
    bench = os.path.join(ROOT, manifest["paths"][0])
    for w in manifest["workloads"]:
        mix = os.path.join(bench, "traffic", w["traffic"] + ".json")
        with open(mix) as f:
            runner = json.load(f)["runner"]
        assert os.path.isfile(os.path.join(bench, "runners", runner + ".py"))
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
    for m in manifest["per_layer"]:
        assert os.path.isfile(os.path.join(bench, "metrics",
                                           m["name"] + ".py"))


def _set(path, value):
    def edit(m):
        node = m
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = value
    return edit


def _drop(path):
    def edit(m):
        node = m
        for key in path[:-1]:
            node = node[key]
        del node[path[-1]]
    return edit


def _append(path, value):
    def edit(m):
        node = m
        for key in path:
            node = node[key]
        node.append(value)
    return edit


BREACHES = {
    "layer_with_a_space": _set(["per_layer", 0, "layer"], "train step"),
    "unit_with_a_space": _set(["end_to_end", 1, "unit"], "tokens per s"),
    "unit_too_long": _set(["end_to_end", 1, "unit"], "tokens/s/chip/core"),
    "greek_unit": _set(["per_layer", 0, "unit"], "µs"),
    "name_with_a_slash": _set(["per_layer", 0, "name"], "input/wait"),
    "bound_over_the_limit": _set(["end_to_end", 1, "bound"], 0.2),
    "bound_under_one_percent": _set(["end_to_end", 1, "bound"], 0.001),
    "bound_on_a_layer_metric": _set(["per_layer", 0, "bound"], 0.05),
    "why_on_a_metric": _set(["end_to_end", 1, "why"], "because"),
    "moves_nothing": _set(["per_layer", 0, "moves"], "ttft_p95_ms"),
    "moves_a_metric_its_cell_lacks":
        _set(["per_layer", 0, "moves"], "serve_tokens_per_s"),
    "program_span_end_to_end":
        _set(["end_to_end", 1, "source"], "program_span"),
    "no_setup_s": _drop(["end_to_end", 0]),
    "two_four_chip_cells_of_three":
        lambda m: [w.__setitem__("chips", 4) for w in m["workloads"][:2]],
    "three_chips": _set(["workloads", 0, "chips"], 3),
    "why_over_200": _set(["workloads", 0, "why"], "x" * 201),
    "pair_twice": lambda m: m["workloads"].append(
        dict(m["workloads"][0], name="again")),
    "unknown_config": _set(["workloads", 0, "config"], "gpt5"),
    "run_seconds_too_long": _set(["run_seconds"], 52),
    "run_seconds_not_whole": _set(["run_seconds"], 40.5),
    "path_leaves_the_repo": _append(["paths"], "../elsewhere"),
    "command_names_a_file_outside_paths":
        _set(["command"], ["python3", "chip_smoke.py"]),
    "command_absolute": _set(["command"], ["/usr/bin/python3", "x"]),
    "reduced_names_a_width":
        _set(["configs", 0, "reduced"], ["hidden_size"]),
    "config_file_missing":
        _set(["configs", 0, "file"], "benchmarks/configs/none.json"),
    "extra_top_level_key": _set(["notes"], "x"),
    "metric_lists_unknown_cell":
        _set(["per_layer", 0, "workloads"], ["nowhere"]),
    "better_sideways": _set(["per_layer", 0, "better"], "sideways"),
}


@pytest.mark.parametrize("breach", sorted(BREACHES))
def test_validator_catches(manifest, breach):
    broken = copy.deepcopy(manifest)
    BREACHES[breach](broken)
    assert rules.problems(broken, ROOT), breach


def test_run_seconds_limit_is_what_24_cells_allow():
    assert rules.run_seconds_fits(51)
    assert not rules.run_seconds_fits(52)
