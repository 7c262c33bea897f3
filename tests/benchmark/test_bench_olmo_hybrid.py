"""The cell `olmo-hybrid-7b.serve-sessions`: its configuration against
the published widths, its mix against the issue's parameters, the
operations count of `flops_hybrid.py` against the model's parameter
counts, its readers on made-up facts, the benchmark's copy of the
reference, the sessions' schedule, and the cell walked on the CPU at
toy size, traced and untraced. The cell, its configuration and its
metrics are found by NAME: what later PRs append behind them moves
nothing here."""

import json
import os

import numpy as np
import pytest

from benchmarks import flops_hybrid as flops
from benchmarks import harness
from benchmarks import manifest as rules
from benchmarks import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "olmo-hybrid-7b.serve-sessions"
CONFIG = "olmo-hybrid-7b"
MINE = {"step_ms.sessions", "serve_mfu.sessions",
        "first_token_p50_ms.sessions", "state_hit_share.sessions",
        "snapshot_ms.sessions"}
SHARED = {"queue_wait_p90_ms", "prefix_hit_share", "device_idle_share.chat"}
# the source's config.json (the catalog's row), every number of it
PUBLISHED = {
    "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_hidden_layers": 32, "num_attention_heads": 30,
    "num_key_value_heads": 30, "max_position_embeddings": 65536,
    "rms_norm_eps": 1e-06, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "attention_bias": False,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "model_type": "olmo_hybrid",
}
# source key -> this repo's name in `model`
NAMES = {"num_attention_heads": "num_heads",
         "linear_num_value_heads": "linear_num_heads",
         "max_position_embeddings": "max_seq_len"}
PERIOD = ["linear_attention"] * 3 + ["full_attention"]


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_published_widths_are_unchanged_and_the_cut_is_named(config):
    model = config["model"]
    for key, value in PUBLISHED.items():
        assert config[key] == value, key         # the source, verbatim
        mine = NAMES.get(key, key)
        if mine in model:
            assert model[mine] == value, key     # what runs
    assert config["layer_types"] == PERIOD * 8
    assert config["rope_parameters"] == {"rope_theta": None}
    assert config["reduced"] == ["num_layers"]
    # the cut is depth alone: four whole periods of the eight
    assert model["num_layers"] == 16
    assert model["layer_types"] == PERIOD * 4
    assert set(config["assumed"]) >= {
        "residual_path", "qk_norm", "rotary", "filter", "decay_init",
        "output_norm", "served_dtype", "initializer_range"}
    assert "two pipeline stages" in config["deployment"]
    serving = config["serving"]
    assert {k: serving[k] for k in (
        "max_slots", "max_seq_len", "block_size", "prefill_chunk",
        "num_blocks", "snapshot_entries", "cache_dtype", "weight_dtype",
        "state_dtype")} == {
        "max_slots": 8, "max_seq_len": 12288, "block_size": 16,
        "prefill_chunk": 64, "num_blocks": 4097, "snapshot_entries": 24,
        "cache_dtype": "bfloat16", "weight_dtype": "bfloat16",
        "state_dtype": "float32"}
    # a session's worst case fits a slot, and the pool holds 65,536
    assert 4096 + 96 + 20 * (256 + 96) <= serving["max_seq_len"]
    assert (serving["num_blocks"] - 1) * serving["block_size"] == 65536
    check = config["check"]
    assert (check["prompt_tokens"], check["decode_steps"],
            check["turn_tokens"], check["turn_decode_steps"]) \
        == (1031, 24, 200, 8)


def test_the_configuration_builds_the_model_it_names(config):
    from paddle_tpu.nlp.transformers import HybridLinearConfig

    cfg = HybridLinearConfig(**config["model"])
    assert cfg.count("linear_attention") == 12
    assert cfg.count("full_attention") == 4
    assert cfg.filter_columns == 11520
    toy = HybridLinearConfig(**harness._overlay(
        config, config["rehearsal"])["model"])
    assert toy.layer_types == tuple(PERIOD)


def test_the_mix_is_the_issues_parameters():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "multi-turn-sessions.json")) as f:
        mix = json.load(f)
    want = {"runner": "serve_sessions", "sessions": 8,
            "document_tokens": [2048, 4096], "turn_tokens": [64, 256],
            "answer_tokens": [32, 96], "jitter": 0.2, "drain_s": 20.0,
            "trace_start_s": 10.0, "trace_s": 3.0, "shape_seed": 1}
    assert {k: mix[k] for k in want} == want
    # four fifths of the swept knee, and never under two seconds
    assert isinstance(mix["turn_interval_s"], float) \
        and mix["turn_interval_s"] >= 2.0
    assert "rate_rps" not in mix


def test_the_cell_its_configuration_and_its_metrics_by_name(manifest):
    cell = _named(manifest["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, "multi-turn-sessions", 1)
    entry = _named(manifest["configs"], CONFIG)
    assert entry["reduced"] == ["num_layers"]
    assert entry["source"] == "https://huggingface.co/allenai/" \
        "Olmo-Hybrid-7B/blob/main/config.json"
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    mine = {m["name"] for m in manifest["per_layer"]
            if m.get("workloads") == [CELL]}
    assert mine == MINE
    reported = {m["name"] for m in manifest["per_layer"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == MINE | SHARED
    judged = {m["name"] for m in manifest["end_to_end"]
              if "workloads" not in m or CELL in m["workloads"]}
    assert judged == {"setup_s", "request_p50_ms", "request_p90_ms"}
    for name, layer, moves in (
            ("step_ms.sessions", "serve_step", "request_p50_ms"),
            ("serve_mfu.sessions", "serve_step", "request_p50_ms"),
            ("first_token_p50_ms.sessions", "prefill", "request_p50_ms"),
            ("state_hit_share.sessions", "cache", "request_p50_ms"),
            ("snapshot_ms.sessions", "cache", "request_p90_ms")):
        m = _named(manifest["per_layer"], name)
        assert (m["layer"], m["moves"]) == (layer, moves), name
    assert _named(manifest["per_layer"],
                  "state_hit_share.sessions")["better"] == "higher"
    assert rules.problems(manifest, ROOT) == []
    assert sum(1 for w in manifest["workloads"] if w["chips"] == 4) == 0


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    with open(os.path.join(ROOT, "benchmarks",
                           "reference_hybrid_linear.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "paddle_tpu", "nlp", "reference",
                           "hybrid_linear.py")) as f:
        assert copy == f.read()
    assert 'default_matmul_precision("highest")' in copy


def test_flops_against_the_parameter_counts(config):
    m = config["model"]
    # a linear layer's projections: W_q, W_k 3840 x 2880; W_v, W_gate
    # 3840 x 5760; W_o 5760 x 3840; two 3840 x 30 projections (the
    # 4-tap filter's 46,080 weights multiply elementwise and count
    # nothing): 88.7 M with them
    linear = 2 * 3840 * 2880 + 2 * 3840 * 5760 + 5760 * 3840 + 2 * 3840 * 30
    assert flops.linear_mixer_weights(m) == linear == 88_704_000
    assert 88.7e6 < linear + 4 * 11520 < 88.8e6
    assert flops.full_mixer_weights(m) == 4 * 3840 * 3840 == 58_982_400
    assert flops.swiglu_weights(m) == 3 * 3840 * 11008 == 126_812_160
    assert flops.layer_counts(m) == (12, 4)
    per_layer = (3 * (linear + 126_812_160) + 58_982_400 + 126_812_160) / 4
    assert round(per_layer / 1e6, 1) == 208.1        # the issue's count
    assert flops.linear_flops_per_token(m) == 2 * (
        12 * linear + 4 * 58_982_400 + 16 * 126_812_160)
    # the recurrence: k^T S, the rank-one update, S^T q over 30 heads
    # of 96 x 192
    assert flops.delta_rule_flops_per_token(m) \
        == 12 * 2 * 30 * 96 * 192 * 3 == 39_813_120
    assert flops.attention_flops_per_pair(m) == 4 * 2 * 2 * 3840
    assert flops.head_flops_per_row(m) == 2 * 3840 * 100352
    assert round(2 * 3840 * 100352 / 1e6, 1) == 770.7  # embedding + head
    total = flops.window_flops(m, computed_tokens=10,
                               attn_context_tokens=1000, tokens_out=3)
    assert total == 10 * (flops.linear_flops_per_token(m) + 39_813_120) \
        + 1000 * 61_440 + 3 * 770_703_360
    assert flops.window_flops(m, computed_tokens=0, attn_context_tokens=0,
                              tokens_out=0) == 0


def _read(metric, facts, config, peaks={"bf16_flops_per_s": 197e12}):
    run = {"facts": facts, "capture": None, "peaks": peaks,
           "config": config, "mix": {}, "chips": 1}
    return harness.load_module("metrics", metric).read(run)


def test_the_readers_on_made_up_facts(config):
    window = {"computed_tokens": 30000, "attn_context_tokens": 150_000_000,
              "tokens_out": 8000, "steps": 1200, "seconds": 40.0,
              "snapshot_s": 0.3}
    facts = {"window": window, "step_s": [0.03, 0.05, 0.04],
             "delta": {"prompt_tokens": 700_000,
                       "prefix_hit_tokens": 680_000,
                       "prefix_tokens_lost_to_state": 20_000}}
    need = flops.window_flops(config["model"], **{
        k: window[k] for k in ("computed_tokens", "attn_context_tokens",
                               "tokens_out")})
    mfu = _read("serve_mfu.sessions", facts, config)
    assert mfu == pytest.approx(100 * need / (40.0 * 197e12))
    assert 0 < mfu < 100
    assert _read("step_ms.sessions", facts, config) == pytest.approx(40.0)
    assert _read("state_hit_share.sessions", facts, config) \
        == pytest.approx(100 * 68 / 70)
    assert _read("snapshot_ms.sessions", facts, config) \
        == pytest.approx(0.25)
    assert _read("prefix_hit_share", facts, config) \
        == pytest.approx(100 * 68 / 70)


@pytest.mark.parametrize("metric", sorted(MINE))
def test_a_reader_with_nothing_to_read_returns_none(config, metric):
    """A program without this PR's counters and spans (its parent)
    gives the readers nothing: None, and no error."""
    assert _read(metric, {}, config) is None
    assert _read(metric, {"window": {}, "delta": {}}, config,
                 peaks=None) is None
    # the parent's delta has hits and no count of what state lost
    assert _read("state_hit_share.sessions",
                 {"delta": {"prefix_hit_tokens": 5}}, config) is None


def test_the_schedule_is_fixed_by_the_shape_seed_alone():
    runner = harness.load_module("runners", "serve_sessions")
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "multi-turn-sessions.json")) as f:
        mix = json.load(f)
    one = runner._sessions(mix, 1, 100352, 2.5, 40.0)
    two = runner._sessions(mix, 3000000001, 100352, 2.5, 40.0)
    assert [s.turns for s in one] == [s.turns for s in two]
    assert [s.document_n for s in one] == [s.document_n for s in two]
    assert not np.array_equal(one[0].document(), two[0].document())
    assert len(one) == 8 and sum(len(s.turns) for s in one) == 8 * 16
    first = sorted(s.turns[0][0] for s in one)
    # the sessions' first turns spread over one interval
    assert first[0] >= 0 and first[-1] < 2.5 * 1.2 and first[4] > 0.6
    for s in one:
        assert 2048 <= s.document_n <= 4096
        due = [t[0] for t in s.turns]
        assert all(0 <= d <= 40 for d in due)
        gaps = np.diff(due)
        assert gaps.min() > 2.5 * 0.59 and gaps.max() < 2.5 * 1.41
        assert all(64 <= t[1] <= 256 and 32 <= t[2] <= 96
                   for t in s.turns)
    # a longer window adds turns and moves none (but the last, which
    # the window's end may have cut short); a sweep's rate is an interval
    longer = runner._sessions(mix, 1, 100352, 2.5, 50.0)
    assert all(b.turns[:len(a.turns) - 1] == a.turns[:-1]
               and len(b.turns) == 20 for a, b in zip(one, longer))
    assert runner._interval(dict(mix, rate_rps=4.0)) == 2.0
    assert runner._interval(mix) == mix["turn_interval_s"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_walks_the_cell(manifest, tmp_path, capfd, trace,
                                  monkeypatch):
    # a capture directory of its own: the other files' traced rehearsals
    # may run beside this one in another worker
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))
    out_file = tmp_path / "would_be.json"
    rc = bench_run.main(["--workload", CELL, "--seed", "3000000001",
                         "--seconds", "3", "--trace", str(trace),
                         "--rehearse-cpu", str(out_file)])
    assert rc == 0
    captured = capfd.readouterr()
    assert "{" not in captured.out, "a rehearsal prints no result line"
    result = json.loads(out_file.read_text())["result"]
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] != "tpu"
    # held to the reference in both legs, and both controls came out
    # not correct
    for line in ("check pinned_logits: ok", "check resumed_logits: ok",
                 "check resumed_from_snapshot: ok the second turn hit 1",
                 "check pinned_control: ok one position off",
                 "check control_bf16_state: ok",
                 "check pools_in_place: ok",
                 "check no_compile_in_window: ok"):
        assert line in captured.err, line
    names = set(result["metrics"])
    if trace:
        # off the chip: no device trace, and no peak to take a share of
        assert names == (MINE | SHARED) - {"device_idle_share.chat",
                                           "serve_mfu.sessions"}
        # every turn resumed from its session's snapshot
        assert result["metrics"]["state_hit_share.sessions"]["value"] \
            == 100.0
        assert result["metrics"]["prefix_hit_share"]["value"] > 60
        assert result["metrics"]["snapshot_ms.sessions"]["value"] > 0
    else:
        assert names == {"setup_s", "request_p50_ms", "request_p90_ms"}
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float) and m["value"] >= 0, name
