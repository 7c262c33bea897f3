"""The cell `sarvam-105b.serve-docqa`: its configuration against the
published widths, the operations count of `flops_latent_moe.py`, its
readers on made-up facts, the benchmark's copy of the reference, and
the cell walked on the CPU at toy size, traced and untraced."""

import json
import os

import pytest

from benchmarks import flops_latent_moe as flops
from benchmarks import harness
from benchmarks import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "sarvam-105b.serve-docqa"
# the source's config.json (the catalog's row), the numbers a cut may
# never touch
PUBLISHED_WIDTHS = {
    "hidden_size": 4096, "num_attention_heads": 64, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "q_head_dim": 192, "head_dim": 576, "moe_intermediate_size": 2048,
    "intermediate_size": 16384, "num_experts": 128,
    "num_experts_per_tok": 8, "num_shared_experts": 1,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
    "num_hidden_layers": 32, "vocab_size": 262144,
    "max_position_embeddings": 131072, "rms_norm_eps": 1e-06,
    "rope_theta": 10000,
}
# source key -> this repo's name in `model`
NAMES = {"num_attention_heads": "num_heads", "num_experts": "router_experts",
         "max_position_embeddings": "max_seq_len"}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "sarvam-105b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_published_widths_are_unchanged_and_the_cut_is_named(config):
    model = config["model"]
    for key, value in PUBLISHED_WIDTHS.items():
        assert config[key] == value, key         # the source, verbatim
        mine = NAMES.get(key, key)
        if mine in model and key not in ("num_hidden_layers", "vocab_size"):
            assert model[mine] == value, key     # what runs
    assert config["rope_scaling"] == model["rope_scaling"]
    assert config["reduced"] == ["num_layers", "num_experts", "vocab_size"]
    # the cut: 1 dense + 5 expert layers, 32 of 128 experts, a quarter
    # of the vocabulary; the guide's floors hold
    assert model["num_layers"] == 6 >= model["first_k_dense_replace"] + 4
    assert model["num_experts"] * model["ep_size"] \
        == model["router_experts"] == 128
    assert model["num_experts"] == 32 >= 8
    assert model["vocab_size"] * 4 == config["vocab_size"]
    assert set(config["assumed"]) >= {"router_scores", "use_qk_norm",
                                      "group_limited_selection",
                                      "served_dtype"}
    serving = config["serving"]
    assert serving["max_slots"] == 8 and serving["max_seq_len"] == 5120
    assert serving["num_blocks"] >= 10001
    assert serving["prefill_chunk"] in (32, 64, 128)


def test_the_mix_is_the_issues_parameters():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "doc-qa-shared-prefix.json")) as f:
        mix = json.load(f)
    want = {"loop": "open", "arrival": "poisson", "users": 24,
            "zipf_s": 1.0, "prefix_tokens": 4096,
            "prompt_tokens": [64, 512], "answer_tokens": [16, 64],
            "drain_s": 20.0, "trace_start_s": 10.0, "trace_s": 3.0,
            "shape_seed": 1, "runner": "serve_latent"}
    assert {k: mix[k] for k in want} == want
    assert isinstance(mix["rate_rps"], float) and mix["rate_rps"] > 0


def test_the_cell_and_its_metrics_are_appended(manifest):
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == "sarvam-105b"
    mine = [m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["serve_mfu.docqa", "step_ms.docqa",
                    "first_token_p50_ms.docqa", "expert_rows_per_step.docqa",
                    "expert_load_imbalance.docqa"]
    assert [m["name"] for m in manifest["per_layer"][-5:]] == mine
    # readers that were there and do the same job take the cell on
    shared = {m["name"] for m in manifest["per_layer"]
              if CELL in m["workloads"]} - set(mine)
    assert shared == {"queue_wait_p90_ms", "prefix_hit_share",
                      "device_idle_share.chat"}
    judged = {m["name"] for m in manifest["end_to_end"]
              if "workloads" not in m or CELL in m["workloads"]}
    assert judged == {"setup_s", "request_p50_ms", "request_p90_ms"}


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    with open(os.path.join(ROOT, "benchmarks",
                           "reference_latent_moe.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "paddle_tpu", "nlp", "reference",
                           "latent_moe.py")) as f:
        assert copy == f.read()


def test_flops_by_hand(config):
    m = config["model"]
    # attention projections of one layer, multiply-adds: W_q 4096 x
    # 12288, W_kva 4096 x 576, absorbing W_kvb 64 x 128 x 512 twice,
    # W_o 8192 x 4096
    attention = 4096 * 12288 + 4096 * 576 + 2 * 64 * 128 * 512 \
        + 8192 * 4096
    assert attention == 94_633_984
    shared_and_router = 3 * 4096 * 2048 + 4096 * 128
    dense = 3 * 4096 * 16384
    assert flops.linear_flops_per_token(m) \
        == 2 * (6 * attention + dense + 5 * shared_and_router)
    # the issue's arithmetic: 2 x 64 x (576 + 512) = 139 kFLOP a pair
    # a layer
    assert flops.attention_flops_per_pair(m) == 6 * 139_264
    assert flops.expert_flops_per_row(m) == 2 * 3 * 4096 * 2048
    assert flops.head_flops_per_row(m) == 2 * 4096 * 65536
    total = flops.window_flops(m, computed_tokens=10,
                               attn_context_tokens=1000, expert_rows=70,
                               tokens_out=3)
    assert total == 10 * flops.linear_flops_per_token(m) \
        + 1000 * 6 * 139_264 + 70 * 50_331_648 + 3 * 536_870_912
    # nothing computed, nothing required
    assert flops.window_flops(m, computed_tokens=0, attn_context_tokens=0,
                              expert_rows=0, tokens_out=0) == 0


def _read(metric, facts, config, peaks={"bf16_flops_per_s": 197e12}):
    run = {"facts": facts, "capture": None, "peaks": peaks,
           "config": config, "mix": {}, "chips": 1}
    return harness.load_module("metrics", metric).read(run)


def test_the_readers_on_made_up_facts(config):
    window = {"computed_tokens": 20000, "attn_context_tokens": 50_000_000,
              "expert_rows": 40000, "tokens_out": 2000, "steps": 1000,
              "seconds": 40.0,
              "expert_rows_by_expert": [[10, 30], [20, 20], [0, 0]]}
    facts = {"window": window, "step_s": [0.03, 0.05, 0.04]}
    need = flops.window_flops(config["model"], **{
        k: window[k] for k in ("computed_tokens", "attn_context_tokens",
                               "expert_rows", "tokens_out")})
    mfu = _read("serve_mfu.docqa", facts, config)
    assert mfu == pytest.approx(100 * need / (40.0 * 197e12))
    assert 0 < mfu < 100
    assert _read("step_ms.docqa", facts, config) == pytest.approx(40.0)
    assert _read("expert_rows_per_step.docqa", facts, config) \
        == pytest.approx(40000 / 1000 / 3)
    # busiest over mean: 30 / 20 and 20 / 20; a layer nothing reached
    # is left out
    assert _read("expert_load_imbalance.docqa", facts, config) \
        == pytest.approx((1.5 + 1.0) / 2)


@pytest.mark.parametrize("metric", [
    "serve_mfu.docqa", "step_ms.docqa", "expert_rows_per_step.docqa",
    "expert_load_imbalance.docqa", "device_idle_share.chat"])
def test_a_reader_with_nothing_to_read_returns_none(config, metric):
    """A program without this PR's counters (its parent) gives the
    readers nothing: None, and no error."""
    assert _read(metric, {}, config) is None
    assert _read(metric, {"window": {}}, config, peaks=None) is None


def _differing(flipped_at, positions=25, layers=5):
    import numpy as np

    out = np.zeros((positions, layers), np.int64)
    out[list(flipped_at), 2] = 2     # one held pick swapped for another
    return out


@pytest.mark.parametrize("gap_at, flipped_at, correct", [
    ({}, (), True),
    # a tie that fell the other way reads 0.1-0.3 and is allowed ...
    ({3: 0.3, 17: 0.12}, (3, 17), True),
    # ... but only where the picks do differ: the same gap behind the
    # reference's own picks is a fault
    ({3: 0.3, 17: 0.12}, (3,), False),
    ({9: 0.05}, (), False),
    # another position's logits, whatever the picks
    ({3: 1.4}, (3,), False),
    # a router that picks otherwise differs everywhere
    ({}, range(19), False),
    ({}, range(18), True),
    ({0: float("nan")}, (), False),
])
def test_judge_holds_each_position_by_its_picks(gap_at, flipped_at, correct):
    import numpy as np

    runner = harness.load_module("runners", "serve_latent")
    gaps = np.full(25, 0.02)
    for at, gap in gap_at.items():
        gaps[at] = gap
    ok, words = runner.judge(gaps, _differing(flipped_at), 0.04)
    assert ok is correct, words
    assert f"the {len(flipped_at)} (at most 75%) whose picks differ" in words


def test_held_picks_counts_the_share_of_each_span():
    import numpy as np

    from paddle_tpu.nlp.transformers import LatentMoEConfig

    runner = harness.load_module("runners", "serve_latent")
    cfg = LatentMoEConfig(router_experts=16, num_experts=4, ep_rank=1,
                          ep_size=4, num_experts_per_tok=2)
    # this share holds experts 4-7; two expert layers, three positions
    picks = [np.array([[4, 9], [5, 4], [0, 15]]),
             np.array([[7, 6], [3, 8], [7, 4]])]
    got = runner.held_picks(picks, [(0, 2), (2, 3)], cfg)
    assert got.tolist() == [[[2, 1, 0, 0], [0, 0, 1, 1]],
                            [[0, 0, 0, 0], [1, 0, 0, 1]]]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_walks_the_cell(manifest, tmp_path, capfd, trace,
                                  monkeypatch):
    # a capture directory of its own: the other files' traced rehearsals
    # may run beside this one in another worker
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    out_file = tmp_path / "would_be.json"
    rc = bench_run.main(["--workload", CELL, "--seed", "3000000001",
                         "--seconds", "2", "--trace", str(trace),
                         "--rehearse-cpu", str(out_file)])
    assert rc == 0
    captured = capfd.readouterr()
    assert "{" not in captured.out, "a rehearsal prints no result line"
    result = json.loads(out_file.read_text())["result"]
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] != "tpu"
    # held to the reference, not to another path of the program
    assert "rms |compiled - reference|" in captured.err
    # both controls have to come out not correct, and did
    assert "check pinned_control: ok one position off" in captured.err
    assert "check pinned_control_cache: ok" in captured.err
    assert "check pools_in_place: ok" in captured.err
    names = set(result["metrics"])
    if trace:
        declared = {m["name"] for m in manifest["per_layer"]
                    if CELL in m.get("workloads", [CELL])}
        # off the chip: no device trace, and no peak to take a share of
        assert names == declared - {"device_idle_share.chat",
                                    "serve_mfu.docqa"}
        assert result["metrics"]["expert_rows_per_step.docqa"]["value"] > 0
        assert result["metrics"]["expert_load_imbalance.docqa"]["value"] >= 1
        assert result["metrics"]["prefix_hit_share"]["value"] > 0
    else:
        assert names == {"setup_s", "request_p50_ms", "request_p90_ms"}
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float) and m["value"] >= 0, name
