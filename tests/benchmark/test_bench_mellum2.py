"""The cell `mellum2-12b-a2.5b.serve-code-assist`: its configuration
against the published widths, its mix against the issue's parameters,
the operations count of `flops_window_moe.py` against a hand count, its
readers on made-up facts, the benchmark's copy of the reference, the
two-class schedule, and the cell walked on the CPU at toy size, traced
and untraced. The cell, its configuration and its metrics are found by
NAME: what later PRs append behind them moves nothing here."""

import json
import os

import numpy as np
import pytest

from benchmarks import flops_window_moe as flops
from benchmarks import harness
from benchmarks import manifest as rules
from benchmarks import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mellum2-12b-a2.5b.serve-code-assist"
CONFIG = "mellum2-12b-a2.5b"
MIX = "code-assist-short-long"
MINE = {"step_ms.assist", "serve_mfu.assist", "first_token_p50_ms.assist",
        "attn_tiles_run_share.assist", "window_hit_share.assist",
        "expert_rows_per_step.assist", "expert_load_imbalance.assist"}
SHARED = {"queue_wait_p90_ms", "prefix_hit_share", "device_idle_share.chat"}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}
# the source's config.json (the catalog's row), every number of it
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
}
# source key -> this repo's name in `model`
NAMES = {"num_attention_heads": "num_heads",
         "num_key_value_heads": "num_kv_heads",
         "max_position_embeddings": "max_seq_len"}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def mix():
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           MIX + ".json")) as f:
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
        if mine in model and key != "num_hidden_layers":
            assert model[mine] == value, key     # what runs
    assert config["layer_types"] == PERIOD * 7
    assert config["mlp_layer_types"] == ["sparse"] * 28
    assert config["rope_parameters"] == ROPE == model["rope_parameters"]
    assert config["reduced"] == ["num_layers"]
    # the cut is depth alone: three whole periods of the seven
    assert model["num_layers"] == 12
    assert model["layer_types"] == PERIOD * 3
    assert set(config["assumed"]) >= {
        "residual_path", "qk_norm", "router_scores", "window",
        "intermediate_size", "mtp_head", "rotary_pairs", "yarn",
        "served_dtype", "initializer_range", "timed_weights"}
    assert "chips that share a layer: one" in config["deployment"]
    serving = config["serving"]
    assert {k: serving[k] for k in (
        "max_slots", "max_seq_len", "block_size", "prefill_chunk",
        "cache_dtype", "weight_dtype")} == {
        "max_slots": 8, "max_seq_len": 16384, "block_size": 16,
        "prefill_chunk": 64, "cache_dtype": "bfloat16",
        "weight_dtype": "bfloat16"}
    # the full group holds 163,840 tokens; the window group every live
    # slot's table, every context's last window and as much again
    blocks = serving["num_blocks"]
    assert (blocks["full"] - 1) * 16 == 163840
    assert blocks["window"] - 1 >= 2 * (8 * 69 + 12 * 70)
    # the longest request fits a slot
    assert 12288 + 512 + 96 <= serving["max_seq_len"]
    check = config["check"]
    assert (check["prompt_tokens"], check["decode_steps"],
            check["min_hit_tokens"]) == (2600, 24, 2048)
    assert check["prompt_tokens"] > 2 * 1024 + 64
    assert 0.03 < check["logit_tol"] < 0.08


def test_the_configuration_builds_the_model_it_names(config):
    from paddle_tpu.nlp.transformers import WindowMoEConfig

    cfg = WindowMoEConfig(**config["model"])
    assert cfg.count("sliding_attention") == 9
    assert cfg.count("full_attention") == 3
    assert cfg.kv_row == 512 and cfg.router_scoring == "softmax"
    toy = WindowMoEConfig(**harness._overlay(
        config, config["rehearsal"])["model"])
    assert toy.layer_types == tuple(PERIOD * 2)
    assert toy.sliding_window == 32


def test_the_mix_is_the_issues_parameters(mix):
    want = {"runner": "serve_two_class", "loop": "open",
            "arrival": "poisson", "contexts": 12,
            "context_tokens": [8192, 12288], "zipf_s": 1.0,
            "long_share": 0.6, "tail_tokens": [64, 512],
            "short_tokens": [128, 1024], "answer_tokens": [16, 96],
            "drain_s": 20.0, "trace_start_s": 10.0, "trace_s": 3.0,
            "shape_seed": 1}
    assert {k: mix[k] for k in want} == want
    # four fifths of the swept knee (PERF.md section 4), some 50
    # requests a window
    assert 40 <= mix["rate_rps"] * 40 <= 70


def test_the_cell_its_configuration_and_its_metrics_by_name(manifest):
    cell = _named(manifest["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, MIX, 1)
    entry = _named(manifest["configs"], CONFIG)
    assert entry["reduced"] == ["num_layers"]
    assert entry["source"] == "https://huggingface.co/JetBrains/" \
        "Mellum2-12B-A2.5B-Instruct/blob/main/config.json"
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
    for name, layer, source, better in (
            ("step_ms.assist", "serve_step", "program_span", "lower"),
            ("serve_mfu.assist", "serve_step", "program_counter", "higher"),
            ("first_token_p50_ms.assist", "prefill", "program_span",
             "lower"),
            ("attn_tiles_run_share.assist", "serve_step",
             "program_counter", "lower"),
            ("window_hit_share.assist", "cache", "program_counter",
             "higher"),
            ("expert_rows_per_step.assist", "experts", "program_counter",
             "higher"),
            ("expert_load_imbalance.assist", "experts", "program_counter",
             "lower")):
        m = _named(manifest["per_layer"], name)
        assert (m["layer"], m["source"], m["better"], m["moves"]) \
            == (layer, source, better, "request_p50_ms"), name
    assert rules.problems(manifest, ROOT) == []
    assert sum(1 for w in manifest["workloads"] if w["chips"] == 4) == 0


def test_the_benchmarks_reference_is_a_copy_of_the_repos():
    with open(os.path.join(ROOT, "benchmarks",
                           "reference_window_moe.py")) as f:
        copy = f.read()
    with open(os.path.join(ROOT, "paddle_tpu", "nlp", "reference",
                           "window_moe.py")) as f:
        assert copy == f.read()
    assert 'default_matmul_precision("highest")' in copy
    # written from the equations: nothing of the model is imported
    assert "paddle_tpu" not in copy.split('"""', 2)[2]


def test_flops_against_a_hand_count(config):
    m = config["model"]
    # attention 2304 x 4096 + 2 x 2304 x 512 + 4096 x 2304 = 21.23 M,
    # router 2304 x 64 = 0.15 M: 21.4 M a layer beside its experts
    assert flops.attention_weights(m) \
        == 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304 == 21_233_664
    assert flops.router_weights(m) == 2304 * 64 == 147_456
    assert round((21_233_664 + 147_456) / 1e6, 1) == 21.4
    # an expert 3 x 2304 x 896 = 6.19 M
    assert flops.expert_flops_per_row(m) == 2 * 3 * 2304 * 896
    assert round(3 * 2304 * 896 / 1e6, 2) == 6.19
    assert flops.layer_counts(m) == (9, 3)
    assert flops.linear_flops_per_token(m) == 2 * 12 * 21_381_120
    # a pair: the score and the context over 32 heads of 128
    assert flops.attention_flops_per_pair(m) == 2 * 2 * 32 * 128 == 16_384
    assert flops.head_flops_per_row(m) == 2 * 2304 * 98304
    # the whole stage: 12 x (21.4 M + 64 x 6.19 M) + 453 M = 5,466 M
    held = 12 * (21_381_120 + 64 * 3 * 2304 * 896 + 2 * 2304 + 2 * 128) \
        + 2 * 98304 * 2304 + 2304
    assert round(held / 1e6) == 5466
    total = flops.window_flops(
        m, computed_tokens=10, attn_context_tokens=100_000,
        attn_window_context_tokens=9_000, expert_rows=10 * 8 * 12,
        tokens_out=3)
    assert total == 10 * 2 * 12 * 21_381_120 \
        + (3 * 100_000 + 9 * 9_000) * 16_384 \
        + 960 * 2 * 3 * 2304 * 896 + 3 * 2 * 2304 * 98304
    assert flops.window_flops(
        m, computed_tokens=0, attn_context_tokens=0,
        attn_window_context_tokens=0, expert_rows=0, tokens_out=0) == 0
    # a token deep in a context costs a sliding layer at most its window
    deep, shallow = (flops.window_flops(
        m, computed_tokens=1, attn_context_tokens=t + 1,
        attn_window_context_tokens=min(t + 1, 1024), expert_rows=96,
        tokens_out=0) for t in (12_000, 1_023))
    assert deep - shallow == 3 * (12_001 - 1_024) * 16_384


def _read(metric, facts, config, peaks={"bf16_flops_per_s": 197e12}):
    run = {"facts": facts, "capture": None, "peaks": peaks,
           "config": config, "mix": {}, "chips": 1}
    return harness.load_module("metrics", metric).read(run)


def test_the_readers_on_made_up_facts(config):
    by_expert = [[10] * 63 + [74]] * 12
    window = {"computed_tokens": 20000, "attn_context_tokens": 150_000_000,
              "attn_window_context_tokens": 18_000_000,
              "expert_rows": 12 * 704, "tokens_out": 3000, "steps": 1300,
              "seconds": 40.0, "attn_key_tiles_full": 150_000,
              "attn_key_tiles_window": 58_500, "attn_key_tiles_max": 998_400,
              "expert_rows_by_expert": by_expert}
    facts = {"window": window, "step_s": [0.03, 0.05, 0.04],
             "delta": {"prompt_tokens": 400_000,
                       "prefix_hit_tokens": 360_000,
                       "prefix_tokens_lost_to_window": 40_000}}
    need = flops.window_flops(config["model"], **{
        k: window[k] for k in ("computed_tokens", "attn_context_tokens",
                               "attn_window_context_tokens", "expert_rows",
                               "tokens_out")})
    mfu = _read("serve_mfu.assist", facts, config)
    assert mfu == pytest.approx(100 * need / (40.0 * 197e12))
    assert 0 < mfu < 100
    assert _read("step_ms.assist", facts, config) == pytest.approx(40.0)
    assert _read("attn_tiles_run_share.assist", facts, config) \
        == pytest.approx(100 * 208_500 / 998_400)
    assert _read("window_hit_share.assist", facts, config) \
        == pytest.approx(90.0)
    assert _read("prefix_hit_share", facts, config) == pytest.approx(90.0)
    assert _read("expert_rows_per_step.assist", facts, config) \
        == pytest.approx(704 / 1300)
    assert _read("expert_load_imbalance.assist", facts, config) \
        == pytest.approx(74 * 64 / 704)


@pytest.mark.parametrize("metric", sorted(MINE))
def test_a_reader_with_nothing_to_read_returns_none(config, metric):
    """A program without this PR's counters and spans (its parent)
    gives the readers nothing: None, and no error."""
    assert _read(metric, {}, config) is None
    assert _read(metric, {"window": {}, "delta": {}}, config,
                 peaks=None) is None
    # the parent's counters: hits and tiles, none of this PR's
    old = {"window": {"computed_tokens": 5, "attn_context_tokens": 9,
                      "expert_rows": 3, "tokens_out": 1, "seconds": 1.0,
                      "attn_key_tiles_max": 7},
           "delta": {"prefix_hit_tokens": 5}}
    for name in ("serve_mfu.assist", "attn_tiles_run_share.assist",
                 "window_hit_share.assist"):
        assert _read(name, old, config) is None


def test_the_schedule_is_fixed_by_the_shape_seed_alone(mix):
    runner = harness.load_module("runners", "serve_two_class")
    vocab = 98304
    ctx_a = runner.contexts_of(mix, 1, vocab)
    ctx_b = runner.contexts_of(mix, 3000000001, vocab)
    assert [c.size for c in ctx_a] == [c.size for c in ctx_b]
    assert len(ctx_a) == 12
    assert all(8192 <= c.size <= 12288 for c in ctx_a)
    assert not np.array_equal(ctx_a[0], ctx_b[0])
    one = runner.schedule(mix, 40.0, 1, vocab, ctx_a)
    two = runner.schedule(mix, 40.0, 3000000001, vocab, ctx_b)
    assert len(one) == int(mix["rate_rps"] * 40)
    for a, b in zip(one, two):
        assert (a.due_s, a.client, a.prompt.size, a.max_new) \
            == (b.due_s, b.client, b.prompt.size, b.max_new)
    assert any(not np.array_equal(a.prompt, b.prompt)
               for a, b in zip(one, two))
    long_ = [i for i in one if i.client >= 0]
    short = [i for i in one if i.client < 0]
    assert 0.45 < len(long_) / len(one) < 0.75 and short
    for item in long_:
        ctx = ctx_a[item.client]
        assert np.array_equal(item.prompt[:ctx.size], ctx)
        assert 64 <= item.prompt.size - ctx.size <= 512
    assert all(128 <= i.prompt.size <= 1024 for i in short)
    assert all(16 <= i.max_new <= 96 for i in one)
    # the most asked context is asked most
    ranks = np.bincount([i.client for i in long_], minlength=12)
    assert ranks[0] == ranks.max()
    due = [i.due_s for i in one]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 40.0


def test_the_runners_judgement_holds_every_position():
    runner = harness.load_module("runners", "serve_two_class")
    same = np.zeros((4, 2, 3), np.int64)
    other = same.copy()
    other[1, 0, 0], other[1, 0, 1] = 1, -1
    gaps = np.asarray([0.01, 0.03, 0.02, 0.01])
    ok, words = runner.judge(gaps, same, other, 0.05)
    assert ok and "1 where a pick differs" in words
    assert "3.0000e-02" in words and "2.0000e-02" in words
    assert not runner.judge(gaps, same, other, 0.025)[0]
    assert not runner.judge(np.asarray([0.01, np.nan]), same[:2],
                            same[:2], 0.05)[0]


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
    # held to the reference on both asks, and both controls came out
    # not correct
    for line in ("check pinned_logits: ok", "check asked_again_logits: ok",
                 "check asked_again_hits_both_groups: ok the second ask "
                 "hit 120",
                 "check freed_behind_the_window: ok 12 window-group",
                 "check pinned_control: ok one position off",
                 "check pinned_control_fp8: ok",
                 "check pools_in_place: ok",
                 "check no_compile_in_window: ok"):
        assert line in captured.err, line
    names = set(result["metrics"])
    if trace:
        # off the chip: no device trace, and no peak to take a share of
        assert names == (MINE | SHARED) - {"device_idle_share.chat",
                                           "serve_mfu.assist"}
        assert result["metrics"]["window_hit_share.assist"]["value"] > 90
        assert result["metrics"]["prefix_hit_share"]["value"] > 50
        assert 0 < result["metrics"]["attn_tiles_run_share.assist"][
            "value"] <= 100
        assert result["metrics"]["expert_load_imbalance.assist"][
            "value"] >= 1.0
    else:
        assert names == {"setup_s", "request_p50_ms", "request_p90_ms"}
    for name, m in result["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float) and m["value"] >= 0, name


def test_the_timed_weights_are_the_draw_scaled_by_name(config):
    """Before the window the runner multiplies the embedding rows up to
    1.0 and every attention output projection down by GPT-2's rule at
    the published depth, on the device and in the served dtype; no
    other weight moves."""
    import jax.numpy as jnp

    factors = config["timed_weights"]
    assert factors == {
        "model.embed_tokens.weight":
            1.0 / config["model"]["initializer_range"],
        "attn.o_proj.weight":
            pytest.approx((2 * config["num_hidden_layers"]) ** -0.5)}
    runner = harness.load_module("runners", "serve_two_class")

    class Engine:
        _values = {
            "model.embed_tokens.weight": jnp.full((4, 2), 0.02, jnp.bfloat16),
            "model.layers.0.attn.o_proj.weight": jnp.ones((2, 2), jnp.float32),
            "model.layers.0.attn.qkv_proj.weight": jnp.ones((2, 6)),
            "lm_head.weight": jnp.ones((2, 4), jnp.bfloat16)}

    before = dict(Engine._values)
    runner.scale_weights(Engine, factors)
    after = Engine._values
    assert {k: (v.dtype, v.shape) for k, v in after.items()} \
        == {k: (v.dtype, v.shape) for k, v in before.items()}
    np.testing.assert_allclose(
        np.asarray(after["model.embed_tokens.weight"], np.float32), 1.0,
        rtol=1e-2)
    np.testing.assert_allclose(
        np.asarray(after["model.layers.0.attn.o_proj.weight"]), 56 ** -0.5,
        rtol=1e-6)
    for name in ("model.layers.0.attn.qkv_proj.weight", "lm_head.weight"):
        assert after[name] is before[name]
