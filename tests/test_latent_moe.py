"""The latent-attention + held-experts decoder against its plain float32
reference (`paddle_tpu/nlp/reference/latent_moe.py`), at small sizes on
the CPU with seeded float32 weights, and through `serving.SlotEngine`'s
cache seam.

Tolerances: program and reference both compute in float32 here, in
another order (grouped product against a loop over experts, absorbed
against expanded attention, online softmax against a whole one), so a
logit may differ by float32 rounding through a few layers: 2e-5
absolute on logits of standard deviation ~0.1 (measured 1e-7), and a
pick that ties within that rounding does not occur at these seeds.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, serving
from paddle_tpu.engine import state_values
from paddle_tpu.nlp.reference import latent_moe as ref
from paddle_tpu.nlp.transformers import (
    GPTConfig, GPTForPretraining, HeldExperts, LatentMoEConfig,
    LatentMoEForCausalLM,
)
from paddle_tpu.nlp.transformers.latent_moe import LatentAttention

ATOL = 2e-5
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "type": "deepseek_yarn"}
SIZES = dict(vocab_size=64, hidden_size=32, num_layers=3, num_heads=4,
             kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
             v_head_dim=8, intermediate_size=64, moe_intermediate_size=16,
             router_experts=8, num_experts_per_tok=2, rope_scaling=YARN,
             max_seq_len=256)


def _model(seed=3, **over):
    cfg = LatentMoEConfig(**{**SIZES, "ep_rank": 1, "ep_size": 2, **over})
    paddle.seed(seed)
    model = LatentMoEForCausalLM(cfg)
    model.eval()
    return cfg, model


def _tokens(seed, n, vocab=64):
    return np.random.RandomState(seed).randint(1, vocab, (n,)) \
        .astype(np.int32)


def _reference(model, cfg, tokens):
    return np.asarray(ref.forward(dict(state_values(model)), vars(cfg),
                                  tokens, wrap=jax.jit))


@pytest.fixture(scope="module")
def latent():
    return _model()


def _stepped(eng, prompt, max_new):
    """Drive one request through an idle engine from this thread; the
    logits handed to sampling after the last prefill step and after
    every decode step, and the answer."""
    fut = eng.submit(prompt, max_new_tokens=max_new, timeout=None)
    eng._admit()
    rows, seen = [], None
    while eng.active:
        eng._step()
        for s in eng._slots:
            if s is not None and s.state == "decode" \
                    and s.next_logits is not None \
                    and s.next_logits is not seen:
                seen = s.next_logits
                rows.append(np.asarray(seen).copy())
    return np.stack(rows), np.asarray(fut.result(10))


# -- the model against the reference ------------------------------------------


@pytest.mark.parametrize("share", [(0, 1), (0, 2), (1, 2), (3, 4)])
def test_full_forward_logits_match_the_reference(share):
    cfg, model = _model(ep_rank=share[0], ep_size=share[1])
    tokens = _tokens(0, 40)
    got = np.asarray(model(paddle.to_tensor(tokens[None, :]))._value)[0]
    want = _reference(model, cfg, tokens)
    assert want.std() > 0.05
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("chunk", [4, 16, 64])
def test_chunked_prefill_then_decode_through_the_latent_pool(latent, chunk):
    """A 40-token prompt crosses five blocks of 8 in chunks of `chunk`,
    then six tokens decode through the cache; every logits row the
    compiled step hands to sampling is the reference's full forward at
    that position. One program each, pools updated in place, and the
    model's counters say what was really computed."""
    cfg, model = latent
    eng = serving.SlotEngine(model, max_slots=3, max_seq_len=128,
                             block_size=8, prefill_chunk=chunk)
    assert eng._pools[0][0].shape == (eng.num_blocks, 8, 128)
    assert len(eng._pools) == 3 and len(eng._pools[0]) == 1
    eng.warmup()
    prompt = _tokens(0, 40)
    got, answer = _stepped(eng, prompt, 6)
    want = _reference(model, cfg, answer[:-1])
    np.testing.assert_allclose(got, want[prompt.size - 1:], atol=ATOL,
                               rtol=0)
    assert eng.compile_counts == {"decode": 1, "cow": 1}
    steps = eng.metrics.get("steps")
    assert eng.metrics.get("pool_inplace_steps") == steps > 0
    # 40 prompt tokens + 5 fed back, each against its own context
    assert eng.metrics.get("computed_tokens") == 45
    assert eng.metrics.get("attn_context_tokens") == 45 * 46 // 2
    # rows of the grouped product: what the full forward of the same
    # tokens counts, whatever the chunk (padding columns and the two
    # idle slots are no rows), and under the 2 picks x 45 tokens x 2
    # layers a chip that held every expert would compute
    _, rows = model.model(paddle.to_tensor(answer[None, :-1]))
    np.testing.assert_array_equal(eng.aux_totals["expert_rows"],
                                  np.asarray(rows))
    assert eng.metrics.get("expert_rows") == int(np.asarray(rows).sum())
    assert 0 < eng.metrics.get("expert_rows") < 2 * 45 * 2


def test_prefix_hit_and_copy_on_write_on_latent_blocks(latent):
    cfg, model = latent
    eng = serving.SlotEngine(model, max_slots=2, max_seq_len=128,
                             block_size=8, prefill_chunk=16,
                             prefix_cache=True)
    first = _tokens(1, 40)
    _stepped(eng, first, 3)
    # shares 3 whole blocks and 4 rows of the fourth, then diverges:
    # a prefix hit on latent blocks and a copy-on-write split
    second = np.concatenate([first[:28], _tokens(2, 20)])
    got, answer = _stepped(eng, second, 4)
    assert eng.metrics.get("prefix_hit_tokens") == 28
    assert eng.metrics.get("cow_splits") == 1
    want = _reference(model, cfg, answer[:-1])
    np.testing.assert_allclose(got, want[second.size - 1:], atol=ATOL,
                               rtol=0)
    assert eng.compile_counts == {"decode": 1, "cow": 1}


def test_the_steps_pick_is_the_argmax_of_the_row_it_leaves_behind(latent):
    """The latent model comes through the same step as GPT, so its
    greedy token is picked on the device too: at every step of a run
    in which one slot prefills 40 tokens in chunks while another
    decodes, the token committed is the first maximum of the row the
    slot's handle fetches, no row crosses unasked, a step reads back 4
    bytes a slot and the experts' counts, and the answers are the
    reference's greedy chains."""
    cfg, model = latent
    eng = serving.SlotEngine(model, max_slots=2, max_seq_len=128,
                             block_size=8, prefill_chunk=16)
    eng.warmup()
    prompts, new = [_tokens(4, 7), _tokens(5, 40)], [12, 5]
    futs = [eng.submit(p, max_new_tokens=n, timeout=None)
            for p, n in zip(prompts, new)]
    mixed = checked = 0
    while eng.active or eng.queue.depth:
        eng._admit()
        live = [s for s in eng._slots if s is not None]
        mixed += {s.state for s in live} == {"prefill", "decode"}
        want = [(s, len(s.tokens), int(np.argmax(np.asarray(s.next_logits))))
                for s in live if s.state == "decode"]
        fetched = eng.metrics.get("logit_rows_fetched")
        eng._step()
        assert eng.metrics.get("logit_rows_fetched") == fetched
        for s, at, token in want:
            assert s.tokens[at] == token
            checked += 1
    assert mixed >= 1 and checked == sum(new)
    for p, fut in zip(prompts, futs):
        answer = np.asarray(fut.result(10))
        chain = _reference(model, cfg, answer[:-1]).argmax(-1)
        np.testing.assert_array_equal(answer[p.size:], chain[p.size - 1:])
    m = eng.metrics
    assert m.get("device_picks") == m.get("tokens_out") == sum(new)
    # two slots' picks and `expert_rows` [2 expert layers, 4 held]
    assert m.get("readback_bytes") == m.get("steps") * (2 * 4 + 2 * 4 * 4)


def test_expert_groups_run_counts_a_steps_non_empty_groups(latent):
    """`expert_groups_run` after a step = the (layer, held expert)
    groups of that step's `expert_rows` that hold a row: what the
    grouped product's time follows. In `snapshot()` and the Prometheus
    text like every counter."""
    from paddle_tpu import observe

    cfg, model = latent
    eng = serving.SlotEngine(model, max_slots=2, max_seq_len=128,
                             block_size=8, prefill_chunk=16)
    futs = [eng.submit(_tokens(7, 40), max_new_tokens=4, timeout=None),
            eng.submit(_tokens(8, 5), max_new_tokens=6, timeout=None)]
    assert eng.metrics.get("expert_groups_run") == 0
    seen = set()
    while eng.active or eng.queue.depth:
        eng._admit()
        rows = np.array(eng.aux_totals.get("expert_rows", 0))
        groups = eng.metrics.get("expert_groups_run")
        eng._step()
        step_rows = np.asarray(eng.aux_totals["expert_rows"]) - rows
        assert step_rows.shape == (2, 4)
        ran = int((step_rows > 0).sum())
        assert eng.metrics.get("expert_groups_run") - groups == ran
        seen.add(ran)
    for fut in futs:
        fut.result(10)
    # a decode step of a row or two leaves groups empty, a prefill chunk
    # fills all eight
    assert min(seen) < 8 and max(seen) == 8
    total = eng.metrics.get("expert_groups_run")
    assert 0 < total < eng.metrics.get("expert_rows")
    assert eng.metrics.snapshot()["counters"]["expert_groups_run"] == total
    assert f"paddle_serving_expert_groups_run_total {total}" \
        in observe.prometheus_text(serving=eng.metrics)


def test_absorbed_attention_equals_expanded(latent):
    """One layer's attention: the expanded form over the whole sequence
    (`forward`) against the absorbed form over a paged pool
    (`forward_paged`), two slots at different positions."""
    cfg, model = latent
    attn: LatentAttention = model.model.layers[1].attn
    x = np.random.RandomState(5).randn(2, 24, cfg.hidden_size) \
        .astype(np.float32) * 0.5
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    want = np.asarray(attn(paddle.to_tensor(x), pos)._value)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
    pool = jnp.zeros((8, 8, cfg.cache_row_stored), jnp.float32)
    got, pool = attn.forward_paged(jnp.asarray(x), jnp.zeros((2,), jnp.int32),
                                   tables, pool)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6, rtol=0)
    # what a token left in the cache: [c | rot(k_r) | zeros]
    row = np.asarray(pool[1, 0])
    assert np.abs(row[:cfg.cache_row]).max() > 0
    assert not row[cfg.cache_row:].any()
    assert cfg.cache_row == 20 and cfg.cache_row_stored == 128


def test_yarn_angles_and_scale_against_hand_computed_values():
    """dim 64, theta 1e4, factor 40 over 4096 positions, beta 32 / 1.
    Pairs that turn more than 32 times keep their frequency: up to
    floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4)) = floor(10.47) = 10;
    pairs that turn less than once are divided by 40: from
    ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) = ceil(22.51) = 23; a
    linear ramp between."""
    scaling = dict(YARN, original_max_position_embeddings=4096)
    rotary = nn.RotaryEmbedding(64, 10000.0, scaling)
    f = rotary.inv_freq
    assert f.shape == (32,)
    np.testing.assert_allclose(f[0], 1.0, rtol=1e-6)
    np.testing.assert_allclose(f[10], 10000.0 ** (-20 / 64), rtol=1e-6)
    np.testing.assert_allclose(f[23], 10000.0 ** (-46 / 64) / 40, rtol=1e-6)
    np.testing.assert_allclose(f[31], 3.33380e-6, rtol=1e-5)
    ramp = (16 - 10) / 13
    np.testing.assert_allclose(
        f[16], 0.01 * (1 - ramp) + 0.01 / 40 * ramp, rtol=1e-6)
    # softmax scale 192^-0.5 * (0.1 ln 40 + 1)^2; cos and sin carry 1
    m = 0.1 * math.log(40) + 1
    assert rotary.attention_scale == pytest.approx(m * m)
    assert rotary.cos_sin_scale == pytest.approx(1.0)
    attn = LatentAttention(LatentMoEConfig(rope_scaling=scaling,
                                           num_layers=0, vocab_size=8,
                                           hidden_size=8, num_heads=1))
    assert attn.scale == pytest.approx(0.135234, rel=1e-5)
    # position 3 of pair 0 turns by 3 radians; the reference agrees
    x = np.zeros((1, 4, 1, 64), np.float32)
    x[..., 0] = 1.0
    out = np.asarray(rotary(jnp.asarray(x), jnp.arange(4)[None, :]))
    np.testing.assert_allclose(out[0, 3, 0, [0, 32]],
                               [math.cos(3), math.sin(3)], atol=1e-6)
    inv, factor, softmax = ref.yarn(64, 10000.0, scaling)
    np.testing.assert_allclose(np.asarray(inv), f, rtol=1e-6)
    assert (factor, softmax) == (pytest.approx(1.0), pytest.approx(m * m))
    # without scaling: plain RoPE
    plain = nn.RotaryEmbedding(8, 10000.0)
    np.testing.assert_allclose(plain.inv_freq,
                               10000.0 ** (-np.arange(0, 8, 2) / 8))
    assert plain.attention_scale == 1.0


# -- the share ----------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer():
    """Ranks 0-3 of ep_size 4, the shared expert counted once, equal
    the layer that holds all 16 experts; so does the reference's."""
    sizes = {**SIZES, "router_experts": 16, "num_experts_per_tok": 4}
    paddle.seed(11)
    whole = HeldExperts(LatentMoEConfig(**sizes))
    h = jnp.asarray(np.random.RandomState(7).randn(2, 9, 32), jnp.float32)
    want, whole_rows = whole(h)
    shared = np.asarray(whole.shared(paddle.to_tensor(h))._value)
    total, rows = np.zeros_like(shared), []
    for rank in range(4):
        part = HeldExperts(LatentMoEConfig(**sizes, ep_rank=rank, ep_size=4))
        held = slice(4 * rank, 4 * rank + 4)
        part.router.weight._value = whole.router.weight._value
        part.gate_up._value = whole.gate_up._value[held]
        part.down._value = whole.down._value[held]
        for mine, theirs in zip(part.shared.parameters(),
                                whole.shared.parameters()):
            mine._value = theirs._value
        y, r = part(h)
        total += np.asarray(y) - shared
        rows.append(np.asarray(r))
    np.testing.assert_allclose(total + shared, np.asarray(want), atol=2e-6,
                               rtol=0)
    # every pick lands on exactly one share: 18 tokens x 4 picks
    np.testing.assert_array_equal(np.concatenate(rows),
                                  np.asarray(whole_rows))
    assert int(np.concatenate(rows).sum()) == 18 * 4


def test_padding_columns_and_absent_picks_are_no_rows():
    sizes = {**SIZES, "router_experts": 16, "num_experts_per_tok": 4}
    paddle.seed(11)
    part = HeldExperts(LatentMoEConfig(**sizes, ep_rank=2, ep_size=4))
    h = jnp.asarray(np.random.RandomState(7).randn(12, 32), jnp.float32)
    _, every = part(h)
    valid = jnp.arange(12) < 5
    y, rows = part(h, valid)
    _, first5 = part(h[:5])
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(first5))
    assert int(rows.sum()) < int(every.sum()) < 12 * 4
    # a padding row still gets the shared expert's output, nothing routed
    sel, _ = part.route(h)
    absent = ~((sel >= 8) & (sel < 12)).any(axis=-1)
    lone = np.asarray(part.shared(paddle.to_tensor(h))._value)
    for t in range(12):
        if t >= 5 or bool(absent[t]):
            np.testing.assert_allclose(np.asarray(y)[t], lone[t], atol=1e-7)


# -- the grouped product's two forms ------------------------------------------

# 128 tokens x 2 picks = 256 sorted rows = two row tiles of the Pallas
# product; experts 128 x 128 wide in bfloat16, this share holds experts
# 4-7 of 8. Per case: the picks of each token, or None for the seeded
# router's own; the mask of real rows; the share; the rows each held
# expert must count (None: whatever the router gives).
_T, _PICKS = 128, 2
_PRODUCT_CASES = {
    # expert 4 empty, expert 5 one row, expert 6 rows 1-100, expert 7
    # rows 101-200 across the boundary at 128; 55 picks of absent
    # experts behind them
    "empty_one_straddle": (
        [(5, 1)] + [(6, 7)] * 100 + [(0, 1)] * 27, None, (1, 2),
        [0, 1, 100, 100]),
    # a serving step none of whose columns is real
    "all_behind": (None, np.zeros(_T, bool), (1, 2), [0, 0, 0, 0]),
    # every expert held, every row real: the groups fill the rows
    "all_real": (None, None, (0, 1), None),
    # the seeded router over this share, a third of the rows padding
    "router_and_padding": (None, np.arange(_T) % 3 > 0, (1, 2), None),
}


def _held_bf16(scoring, share, picks):
    sizes = {**SIZES, "hidden_size": 128, "moe_intermediate_size": 128,
             "num_experts_per_tok": _PICKS}
    cfg = LatentMoEConfig(**sizes, ep_rank=share[0], ep_size=share[1])
    cfg.router_scoring = scoring
    paddle.seed(5)
    paddle.set_default_dtype("bfloat16")
    try:
        layer = HeldExperts(cfg)
    finally:
        paddle.set_default_dtype("float32")
    h = np.random.RandomState(9).randn(_T, 128).astype(np.float32)
    if picks is not None:
        # logits = the row's first 8 features; its two picks stand out
        route = np.zeros((128, 8), np.float32)
        route[np.arange(8), np.arange(8)] = 1.0
        layer.router.weight._value = jnp.asarray(route, jnp.bfloat16)
        h *= 0.1
        for t, pair in enumerate(picks):
            h[t, list(pair)] = 4.0
    return layer, jnp.asarray(h, jnp.bfloat16)


def _pallas_in_place_of_ragged(monkeypatch):
    """What a program lowered for a TPU runs, here: the default branch
    of `grouped_product` becomes the Pallas kernel, interpreted."""
    import functools

    from paddle_tpu.nlp.transformers import latent_moe

    monkeypatch.setattr(
        latent_moe, "_ragged_product",
        functools.partial(latent_moe._pallas_product, interpret=True))


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
@pytest.mark.parametrize("case", list(_PRODUCT_CASES))
def test_pallas_grouped_product_equals_ragged_dot(case, scoring,
                                                  monkeypatch):
    """`HeldExperts.routed` with `megablox.gmm` under it against the
    same call with `lax.ragged_dot`: the same group sizes, and `y`
    within a rounding of bfloat16 (both accumulate in float32 and round
    once, in another order of summation)."""
    picks, valid, share, want_rows = _PRODUCT_CASES[case]
    layer, h = _held_bf16(scoring, share, picks)
    valid = None if valid is None else jnp.asarray(valid)
    y_ragged, rows_ragged = jax.jit(layer.routed)(h, valid)
    _pallas_in_place_of_ragged(monkeypatch)
    y_pallas, rows_pallas = jax.jit(layer.routed)(h, valid)
    traced = str(jax.make_jaxpr(layer.routed)(h, valid))
    assert "pallas_call" in traced and "ragged_dot" not in traced
    np.testing.assert_array_equal(np.asarray(rows_pallas),
                                  np.asarray(rows_ragged))
    if want_rows is not None:
        assert np.asarray(rows_ragged).tolist() == want_rows
    else:
        assert int(rows_ragged.sum()) > 64 and (rows_ragged > 0).all()
    a = np.asarray(y_ragged, np.float32)
    b = np.asarray(y_pallas, np.float32)
    assert np.isfinite(b).all()
    if int(rows_ragged.sum()):
        assert np.abs(a).max() > 0.01
    else:
        assert not a.any() and not b.any()
    np.testing.assert_allclose(b, a, atol=2 ** -8 * np.abs(a).max(),
                               rtol=2 ** -7)


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_gradients_agree_between_the_two_grouped_products(scoring,
                                                          monkeypatch):
    """`jax.grad` of a scalar of `y`, with respect to the rows and to
    every weight of the layer: `megablox.ops.gmm` carries its own
    backward (`tgmm`), and what it leaves unwritten for the rows behind
    the groups reaches no token's gradient."""
    from paddle_tpu.engine import functional_apply

    picks, _, share, _ = _PRODUCT_CASES["empty_one_straddle"]
    layer, h = _held_bf16(scoring, share, picks)
    values = dict(state_values(layer))
    probe = jnp.asarray(np.random.RandomState(2).randn(_T, 128),
                        jnp.float32)

    def scalar(values, h):
        y, _ = functional_apply(layer, values, lambda held: held.routed(h))
        return (y.astype(jnp.float32) * probe).sum()

    grads = jax.jit(jax.grad(scalar, argnums=(0, 1)))
    ragged = grads(values, h)
    _pallas_in_place_of_ragged(monkeypatch)
    pallas = jax.jit(jax.grad(scalar, argnums=(0, 1)))(values, h)
    flat_r, tree = jax.tree_util.tree_flatten(ragged)
    flat_p, tree_p = jax.tree_util.tree_flatten(pallas)
    assert tree == tree_p
    moved = 0
    for r, q in zip(flat_r, flat_p):
        r, q = np.asarray(r, np.float32), np.asarray(q, np.float32)
        assert np.isfinite(q).all()
        moved += bool(np.abs(r).max() > 0)
        np.testing.assert_allclose(q, r, atol=2 ** -6 * np.abs(r).max(),
                                   rtol=2 ** -5)
    # the rows, both expert stacks and the router at least
    assert moved >= 4
    # the empty expert's weights and the padding tokens' rows get none
    held = np.asarray(pallas[0]["gate_up"], np.float32)
    assert not held[0].any() and held[2].any()
    assert not np.asarray(pallas[1], np.float32)[101:].any()


def test_which_grouped_product_a_program_gets():
    """The form follows the platform the program is lowered for and the
    operands, nothing else: bfloat16 rows in whole row tiles lowered
    for a TPU carry the Pallas kernel, the same call lowered for the
    CPU, float32 operands and a handful of rows `ragged_dot`."""
    from paddle_tpu.nlp.transformers.latent_moe import (
        GMM_TILES, gmm_tiling, grouped_product,
    )

    def text(rows, dtype, platform):
        x = jax.ShapeDtypeStruct((rows, 256), dtype)
        w = jax.ShapeDtypeStruct((4, 256, 384), dtype)
        sizes = jax.ShapeDtypeStruct((4,), jnp.int32)
        return jax.jit(grouped_product).trace(x, w, sizes).lower(
            lowering_platforms=(platform,)).as_text()

    on_tpu = text(256, jnp.bfloat16, "tpu")
    assert "tpu_custom_call" in on_tpu and "ragged_dot" not in on_tpu
    # (for the CPU `ragged_dot` lowers to a masked dense product)
    assert "tpu_custom_call" not in text(256, jnp.bfloat16, "cpu")
    for rows, dtype in [(256, jnp.float32), (36, jnp.bfloat16)]:
        plain = text(rows, dtype, "tpu")
        assert "ragged_dot" in plain and "tpu_custom_call" not in plain
    # tiles: the swept shapes from the table, any other by the rule
    # (whole `k` down to a tile 256 columns wide, `tn` in whole lanes
    # under 2 MiB of bfloat16 a tile)
    assert gmm_tiling(4096, 2304, 1792) == GMM_TILES[2304, 1792]
    assert gmm_tiling(256, 256, 384) == (128, 256, 384)
    assert gmm_tiling(4096, 3072, 8192) == (128, 3072, 256)
    assert gmm_tiling(4096, 16384, 1024) == (128, 4096, 256)
    for (k, n), (tm, tk, tn) in GMM_TILES.items():
        assert 4096 % tm == 0 and tk <= k and tn <= n and tn % 128 == 0
        # forward: two weight tiles, two row tiles, two output tiles and
        # the float32 accumulator; backward (`tgmm`): the accumulator
        # and two output tiles are whole weight tiles. Both inside the
        # 16 MiB a kernel may use (compiled for the chip in
        # `test_v5e_compile.py`).
        assert 2 * 2 * (tk * tn + tm * tk + tm * tn) + 4 * tm * tn \
            < 16 << 20
        assert (4 + 2 * 2) * tk * tn + 2 * 2 * tm * (tk + tn) < 16 << 20


def test_vocabulary_slice_is_the_uncut_heads_first_rows():
    cfg, full = _model(vocab_size=64)
    cut_cfg, cut = _model(vocab_size=16)
    values = dict(state_values(full))
    for name, p in cut.state_dict().items():
        v = values[name]
        if name == "model.embed_tokens.weight":
            v = v[:16]
        elif name == "lm_head.weight":
            v = v[:, :16]
        p._value = v
    tokens = _tokens(4, 20, vocab=16)
    whole = np.asarray(full(paddle.to_tensor(tokens[None, :]))._value)[0]
    part = np.asarray(cut(paddle.to_tensor(tokens[None, :]))._value)[0]
    np.testing.assert_allclose(part, whole[:, :16], atol=1e-6, rtol=0)
    np.testing.assert_allclose(_reference(cut, cut_cfg, tokens), part,
                               atol=ATOL, rtol=0)


def test_weights_follow_the_default_dtype_and_are_held_once():
    paddle.set_default_dtype("bfloat16")
    try:
        cfg, model = _model()
    finally:
        paddle.set_default_dtype("float32")
    values = state_values(model)
    assert {str(v.dtype) for k, v in values.items()
            if not k.endswith("router_bias")} == {"bfloat16"}
    assert values["model.layers.1.mlp.router_bias"].dtype == jnp.float32
    eng = serving.SlotEngine(model, max_slots=2, max_seq_len=64,
                             block_size=8, prefill_chunk=8,
                             cache_dtype="bfloat16")
    # the engine serves the very arrays the layer holds
    assert all(eng._values[k] is values[k] for k in values)
    got, answer = _stepped(eng, _tokens(0, 20), 3)
    want = _reference(model, cfg, answer[:-1])
    # bfloat16 rounds every product's output to 8 bits: the band is of
    # that order, far from the 1.6 a position off reads
    gap = np.sqrt(((got - want[19:]) ** 2).mean(-1)) / want[19:].std(-1)
    assert gap.max() < 0.1
    snap = eng.metrics.snapshot()["model"]
    assert snap == {"kv_bytes_per_token": 3 * 128 * 2.0,
                    "weight_bytes": float(sum(v.nbytes
                                              for v in values.values())),
                    "experts_held": 4.0}
    assert nn.Linear(2, 2).weight.dtype == paddle.float32


# -- the seam -----------------------------------------------------------------


def test_named_scopes_reach_the_lowered_step(latent):
    _, model = latent
    eng = serving.SlotEngine(model, max_slots=2, max_seq_len=64,
                             block_size=8, prefill_chunk=4)
    tok = jnp.zeros((2, 4), jnp.int32)
    vec = jnp.zeros((2,), jnp.int32)
    batch, extras = eng._stage(tok, vec, vec)
    text = eng._decode.lower(eng._values, batch, eng._pools, extras) \
        .as_text(debug_info=True)
    for scope in ("latent.attend", "moe.route", "moe.experts",
                  "moe.shared"):
        assert scope in text, scope


def test_gpt_goes_through_the_same_seam():
    paddle.seed(0)
    gpt = GPTForPretraining(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
        max_seq_len=64, dropout=0.0, attn_dropout=0.0, use_parallel=False))
    layout = gpt.cache_layout()
    assert (layout.row_order, layout.layers, layout.head_axis) \
        == ("thd", 2, 2)
    assert layout.arrays == (("k", (4, 8)), ("v", (4, 8)))
    eng = serving.SlotEngine(gpt, max_slots=2, block_size=8)
    assert [a.shape for a in eng._pools[0]] == [(eng.num_blocks, 8, 4, 8)] * 2
    assert eng.kv_pool_bytes == eng.num_blocks * 8 * 2 * 2 * 4 * 8 * 4
    assert eng.metrics.snapshot()["model"]["kv_bytes_per_token"] \
        == 2 * 2 * 32 * 4
    prompt = _tokens(3, 11, vocab=97)
    _, answer = _stepped(eng, prompt, 4)
    want = np.asarray(gpt.generate(paddle.to_tensor(prompt[None, :]),
                                   max_new_tokens=4)._value)[0]
    np.testing.assert_array_equal(answer, want)
    assert eng.metrics.get("computed_tokens") == 14
    # what this block's step counts: the turns of its attention loop,
    # one a step where one tile covers the table of 64 positions
    steps = eng.metrics.get("steps")
    assert {k: int(v) for k, v in eng.aux_totals.items()} \
        == {"attn_key_tiles": steps, "attn_key_tiles_max": steps}


@pytest.mark.parametrize("path", ["migrate", "migrate_other_kind", "spill",
                                  "draft", "mesh"])
def test_side_paths_carry_a_latent_block_or_refuse_it_by_name(
        latent, path, tmp_path):
    cfg, model = latent
    kw = dict(max_slots=2, max_seq_len=128, block_size=8, prefill_chunk=8)
    prompt = _tokens(6, 30)
    if path == "spill":
        with pytest.raises(ValueError, match="'thd'.*'tc'"):
            serving.SlotEngine(model, spill_dir=str(tmp_path),
                               prefix_cache=True, **kw)
        return
    plain = serving.SlotEngine(model, prefix_cache=True, **kw)
    _, want = _stepped(plain, prompt, 5)
    if path == "migrate":
        payload = plain.export_prefix_blocks(prompt)
        assert payload["row_order"] == "tc" and payload["n_tokens"] == 24
        assert payload["layers"][0][0].shape == (3, 8, 128)
        other = serving.SlotEngine(model, prefix_cache=True, **kw)
        assert other.adopt_prefix_blocks(payload) == 24
        got, answer = _stepped(other, prompt, 5)
        assert other.metrics.get("prefix_hit_tokens") == 24
        np.testing.assert_array_equal(answer, want)
        np.testing.assert_allclose(
            got, _reference(model, cfg, answer[:-1])[prompt.size - 1:],
            atol=ATOL, rtol=0)
    elif path == "migrate_other_kind":
        # a K/V engine is handed latent blocks, a latent engine K/V
        # blocks: each refuses the other's by the rows' named order
        paddle.seed(0)
        gpt = GPTForPretraining(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=3, num_heads=4,
            max_seq_len=128, dropout=0.0, attn_dropout=0.0,
            use_parallel=False))
        kv = serving.SlotEngine(gpt, prefix_cache=True, **kw)
        _stepped(kv, prompt, 5)
        assert kv.adopt_prefix_blocks(
            plain.export_prefix_blocks(prompt)) == 0
        assert plain.adopt_prefix_blocks(
            kv.export_prefix_blocks(prompt)) == 0
        assert kv.export_prefix_blocks(prompt)["row_order"] == "thd"
    elif path == "draft":
        spec = serving.SlotEngine(model, spec_len=2, **kw)
        assert spec._spec.layout.row_order == "tc"
        spec.warmup()
        _, answer = _stepped(spec, prompt, 5)
        np.testing.assert_array_equal(answer, want)
        assert spec.compile_counts == {"decode": 1, "draft": 1, "cow": 1}
    else:
        if len(jax.devices()) < 2:
            pytest.skip("needs two devices")
        meshed = serving.SlotEngine(model, mesh="dp1.mp2", **kw)
        # a pool with no head axis is replicated, and says so
        assert meshed.mesh_info()["kv_sharded"] is False
        assert all(a.sharding.is_fully_replicated
                   for a in meshed._arrays(meshed._pools))
        _, answer = _stepped(meshed, prompt, 5)
        np.testing.assert_array_equal(answer, want)
