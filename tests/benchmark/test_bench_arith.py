"""The benchmark's own arithmetic on fixed inputs: percentiles and the
spread rule, FLOP counts and MFU, and the traffic generator's promise
that a seed changes the tokens and the order but not the work."""

import json
import os

import numpy as np
import pytest

from benchmarks import flops, stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmarks")


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("p,want", [(0, 1.0), (50, 5.5), (90, 9.1),
                                    (100, 10.0)])
def test_percentile_matches_numpy(p, want):
    data = [7, 3, 10, 1, 9, 2, 8, 4, 6, 5]
    assert stats.percentile(data, p) == pytest.approx(want)
    assert stats.percentile(data, p) == pytest.approx(
        float(np.percentile(data, p)))


def test_percentile_refuses_nothing_to_read():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1.0], 101)


def test_spread_is_the_drivers_quartile_rule():
    # statistics.quantiles(n=4), exclusive method: wider than numpy's
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    assert stats.spread(values) == pytest.approx((104.25 - 100.75) / 102.5)
    q1, q3 = np.percentile(values, [25, 75])
    assert stats.spread(values) > (q3 - q1) / 102.5


def test_ernie_base_step_flops():
    per_token = flops.transformer_train_flops_per_token(
        hidden=768, layers=12, ffn=3072, vocab=18000, seq=512, head_dense=1)
    layer = 2 * (4 * 768 * 768 + 2 * 768 * 3072) + 4 * 512 * 768
    head = 2 * 768 * 18000 + 2 * 768 * 768
    assert per_token == 3 * (12 * layer + head)
    # 21.4 TFLOP for 64 x 512 tokens; 6 x 100M parameters + attention
    assert 64 * 512 * per_token == pytest.approx(21.39e12, rel=1e-3)


def test_causal_attention_counts_half():
    shape = dict(hidden=2048, layers=24, ffn=8192, vocab=50304, seq=2048)
    full = flops.transformer_fwd_flops_per_token(**shape)
    half = flops.transformer_fwd_flops_per_token(causal=True, **shape)
    assert full - half == 24 * 2 * 2048 * 2048


def test_mfu_percent():
    assert flops.mfu_percent(21.39e12, 0.3322, 197e12) == pytest.approx(
        32.68, abs=0.01)
    assert flops.mfu_percent(4 * 197e12, 1.0, 197e12, chips=4) \
        == pytest.approx(100.0)


@pytest.mark.parametrize("mix_name", ["chat-shared-prefix"])
def test_open_loop_same_work_for_every_seed(mix_name):
    mix = _mix(mix_name)
    a = traffic.open_loop(mix, 40, 3000000001, 50304)
    b = traffic.open_loop(mix, 40, 3000000001, 50304)
    c = traffic.open_loop(mix, 40, 17, 50304)
    assert len(a) == int(mix["rate_rps"] * 40)
    assert all((x.prompt == y.prompt).all() and x.due_s == y.due_s
               for x, y in zip(a, b))
    # another seed: the same sizes at the same moments, other tokens
    assert [(x.due_s, x.prompt.size, x.max_new) for x in a] \
        == [(x.due_s, x.prompt.size, x.max_new) for x in c]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, c))
    assert 0 < a[0].due_s and a[-1].due_s < 40
    assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))
    lo, hi = mix["prompt_tokens"]
    assert all(lo + mix["prefix_tokens"] <= x.prompt.size
               <= hi + mix["prefix_tokens"] for x in a)
    # a user's requests share that user's prefix and nothing after it
    by_user = {}
    for x in a:
        by_user.setdefault(x.client, []).append(x.prompt)
    shared = [p for p in by_user.values() if len(p) > 1]
    assert shared, "no user sent twice: the prefix cache would see nothing"
    n = mix["prefix_tokens"]
    assert all((p[0][:n] == q[:n]).all() for p in shared for q in p)


def test_burst_arrivals_keep_the_mean_rate():
    mix = dict(_mix("chat-shared-prefix"), arrival="burst", burst_n=8)
    times = traffic.arrival_times(mix, 40)
    assert len(times) == int(mix["rate_rps"] * 40)
    gaps = np.diff(times)
    assert (gaps < 0.02).sum() >= len(times) * 6 // 8
    assert times[-1] < 40


def test_closed_loop_deals_the_same_sequences():
    mix = _mix("decode-heavy")
    a = traffic.ClosedLoop(mix, 5, 50304)
    b = traffic.ClosedLoop(mix, 6, 50304)

    def sizes(gen):
        return sorted(tuple((i.prompt.size, i.max_new)
                            for i in (gen.next(c) for _ in range(5)))
                      for c in range(gen.clients))
    assert sizes(a) == sizes(b)
    lo, hi = mix["answer_tokens"]
    again = traffic.ClosedLoop(mix, 5, 50304)
    first = again.next(0)
    assert lo <= first.max_new <= hi
    assert first.prompt.min() >= 1 and first.prompt.max() < 50304
