"""Compile-only guards: programs of the main paths compiled at their
real shapes for a TPU v5e that is described, not attached (the
on-chip-measurement guide, section 2). Nothing runs, so these say what
the chip's compiler makes of a program — its copies, its aliases, its
memory — and nothing about results or times.

The topology is described inside a fixture of this file and nowhere
else: only one process at a time may load the TPU's library, so every
test that needs it lives here, and a worker that cannot describe the
topology skips them.
"""

from __future__ import annotations

import os
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.nlp.transformers.gpt import GPTAttention
from paddle_tpu.nlp.transformers.latent_moe import (
    latent_attend_paged, latent_scatter,
)

# gpt3-1.3b behind benchmarks/configs/gpt3-1.3b.json: 16 heads of 128,
# 16 slots x 16 tokens a step, context 2048 in blocks of 16, a bf16
# pool of 1281 blocks a layer
NB, BS, NH, HD = 1281, 16, 16, 128
SLOTS, CHUNK, TABLE = 16, 16, 128
LAYERS = 2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever it is, no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kv_path(q, k, v, pos, tables, ks, vs):
    """`serving_step`'s KV path, two layers of it: the exact text of
    `GPTAttention._attend_paged` on each layer's pools, the second
    layer fed by the first as in the model."""
    attn = types.SimpleNamespace(head_dim=HD)
    out_ks, out_vs = [], []
    for k_pool, v_pool in zip(ks, vs):
        out, (k_pool, v_pool, _, _) = GPTAttention._attend_paged(
            attn, q, k, v, k_pool, v_pool, pos, tables)
        q = q + out._value
        out_ks.append(k_pool)
        out_vs.append(v_pool)
    return q, out_ks, out_vs


def _entry_instructions(hlo):
    """(name, dtype, dims, opcode) of the entry computation's
    instructions."""
    entry = hlo[hlo.index("ENTRY"):]
    entry = entry[:entry.index("\n}")]
    found = []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if m:
            dims = tuple(int(d) for d in m.group(3).split(",") if d)
            found.append((m.group(1), m.group(2), dims, m.group(4)))
    return found


def _all_shapes(hlo):
    """(dtype, dims) of every array the text names, in any computation:
    results, tuple elements and operands, the loop's body and the
    fusions' too."""
    return {(m.group(1), tuple(int(d) for d in m.group(2).split(",")))
            for m in re.finditer(r"\b([a-z]+\d+)\[([\d,]+)\]", hlo)}


def _grouped_products(hlo):
    """(instruction name, dims) of every Mosaic kernel of the compiled
    text. The experts' grouped product is one either way: XLA's own
    `lax.ragged_dot` is called `ragged-dot-...`, JAX's Pallas
    `megablox.gmm` `gmm` (`gmm.1`, ...), after the function that
    wraps the kernel."""
    return [(m.group(1), tuple(int(d) for d in m.group(2).split(",")))
            for m in re.finditer(
                r"%([\w.\-]+) = \w+\[([\d,]+)\]\S* custom-call\([^\n]*"
                r"custom_call_target=\"tpu_custom_call\"", hlo)]


def _assert_pallas_grouped_products(hlo, expert_layers, widths):
    """Two Pallas products an expert layer over the step's 4,096 sorted
    rows, gate-up then down, and `ragged_dot` nowhere."""
    kernels = _grouped_products(hlo)
    assert sorted(dims for _, dims in kernels) \
        == sorted([(4096, w) for w in widths] * expert_layers), kernels
    assert all(re.fullmatch(r"gmm(\.\d+)?", name) for name, _ in kernels), \
        kernels
    assert "ragged" not in hlo


def test_serving_kv_path_updates_donated_pools_in_place(one_chip,
                                                        no_compile_cache):
    """With the pool token-major and donated, the entry computation
    scatters into the parameter and returns it aliased: no copy of a
    whole pool and the pools' bytes aliased. (Head-major, the scatter
    over axes 0 and 2 cost a relayout copy in and one out for each
    pool, donated or not: 57 % of the step.) And the read is a loop
    over key tiles: nowhere in the program a slot-by-context view
    `[16, 2048, 16, 128]` or whole-context scores `[16, 16, 16, 2048]`
    (gathered, cast and contracted in float32 they were 73 % of it)."""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = spec((NB, BS, NH, HD), jnp.bfloat16)
    rows = spec((SLOTS, NH, CHUNK, HD), jnp.float32)
    compiled = jax.jit(_kv_path, donate_argnums=(5, 6)).lower(
        rows, rows, rows, spec((SLOTS,), jnp.int32),
        spec((SLOTS, TABLE), jnp.int32),
        [pool] * LAYERS, [pool] * LAYERS).compile()

    pool_bytes = 2 * LAYERS * NB * BS * NH * HD * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes

    hlo = compiled.as_text()
    instructions = _entry_instructions(hlo)
    assert len(instructions) > 10, "the entry computation was not parsed"
    pool_elems = NB * BS * NH * HD
    copies = [(name, dtype, dims) for name, dtype, dims, op in instructions
              if (op.startswith("copy") or name.startswith("copy"))
              and int(np.prod(dims)) == pool_elems]
    assert not copies, f"pool-sized copies are back: {copies}"
    entry = hlo[hlo.index("ENTRY"):]
    assert len(re.findall(r" while\(", entry)) == LAYERS
    # what is gone from the read path, wherever it might hide
    view_elems = SLOTS * TABLE * BS * NH * HD
    score_elems = SLOTS * NH * CHUNK * TABLE * BS
    shapes = _all_shapes(hlo)
    assert ("bf16", (NB, BS, NH, HD)) in shapes, "the text was not parsed"
    # (a tile of 256 keys has as many elements as the old scores:
    # those are told by their context-long axis)
    whole = [(dtype, dims) for dtype, dims in shapes
             if int(np.prod(dims)) == view_elems
             or (int(np.prod(dims)) == score_elems and TABLE * BS in dims)]
    assert not whole, f"context-sized tensors are back: {whole}"
    whole_view_f32 = view_elems * 4
    assert memory.temp_size_in_bytes < whole_view_f32 / 4


# sarvam-105b behind benchmarks/configs/sarvam-105b.json: 64 heads over
# a latent row of 512 + 64 columns stored 640 wide, 8 slots x 64 tokens
# a step, context 5120 in blocks of 16, a bf16 pool of 16,385 blocks
L_NB, L_SLOTS, L_CHUNK, L_NH, L_TABLE, L_RANK = 16385, 8, 64, 64, 320, 512


def _latent_path(q_cat, rows, pos, tables, pools):
    """The serving step's latent cache path, two layers of it:
    `LatentAttention.forward_paged`'s scatter through the table and the
    absorbed attention loop over the pool, the second layer fed by the
    first."""
    t_idx = pos[:, None] + jnp.arange(L_CHUNK)
    out = []
    for pool in pools:
        pool = latent_scatter(pool, rows, tables, t_idx)
        ctx = latent_attend_paged(q_cat, pool, tables, t_idx, L_RANK, 0.1)
        q_cat = q_cat.at[..., :L_RANK].add(ctx.astype(q_cat.dtype))
        out.append(pool)
    return q_cat, out


def _compile_latent_path(width, one_chip):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = spec((L_NB, BS, width), jnp.bfloat16)
    return jax.jit(_latent_path, donate_argnums=(4,)).lower(
        spec((L_SLOTS, L_CHUNK, L_NH, width), jnp.bfloat16),
        spec((L_SLOTS, L_CHUNK, width), jnp.bfloat16),
        spec((L_SLOTS,), jnp.int32), spec((L_SLOTS, L_TABLE), jnp.int32),
        [pool] * LAYERS).compile()


def _pool_copies(compiled, width):
    pool_elems = L_NB * BS * width
    return [(name, dims) for name, _, dims, op
            in _entry_instructions(compiled.as_text())
            if (op.startswith("copy") or name.startswith("copy"))
            and int(np.prod(dims)) == pool_elems]


def test_latent_pool_is_updated_in_place_at_its_stored_width(
        one_chip, no_compile_cache):
    """The latent pool `[16385, 16, 640]`, donated: scattered into and
    read tile by tile inside the attention loop with no copy of a whole
    pool, its bytes aliased, and no `[slots, heads x chunk, context]`
    score tensor (the loop's tile is 512 keys)."""
    compiled = _compile_latent_path(640, one_chip)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= LAYERS * L_NB * BS * 640 * 2
    assert not _pool_copies(compiled, 640)
    whole_scores = L_SLOTS * L_CHUNK * L_NH * L_TABLE * BS * 4
    assert memory.temp_size_in_bytes < whole_scores / 4


def test_latent_pool_at_its_logical_width_would_be_copied(
        one_chip, no_compile_cache):
    """Why the row is stored 640 wide: at 576 (not a multiple of the
    128 lanes) the compiler lays the pool out block-index-minor and
    copies all of it in and out of every layer's scatter."""
    compiled = _compile_latent_path(576, one_chip)
    assert len(_pool_copies(compiled, 576)) >= LAYERS


# -- the experts' grouped product ---------------------------------------------


@pytest.mark.parametrize("groups, k, n", [
    pytest.param(64, 2304, 2 * 896, id="mellum2-gate-up"),
    pytest.param(64, 896, 2304, id="mellum2-down"),
    pytest.param(32, 4096, 2 * 2048, id="sarvam-gate-up"),
    pytest.param(32, 2048, 4096, id="sarvam-down")])
def test_grouped_product_and_its_gradient_fit_fast_memory(
        groups, k, n, one_chip, no_compile_cache):
    """The four products the expert cells run, 4,096 sorted rows in
    bfloat16, with the tiles `gmm_tiling` gives them: forward `gmm`,
    and under `jax.grad` `gmm` again (transposed) and `tgmm`, whose
    float32 accumulator is a whole weight tile: Mosaic refuses a kernel
    over 16 MiB of fast memory (a 4 MiB weight tile compiles forward
    and not backward)."""
    from paddle_tpu.nlp.transformers.latent_moe import grouped_product

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def scalar(x, w, sizes):
        return grouped_product(x, w, sizes).astype(jnp.float32).sum()

    args = (spec((4096, k), jnp.bfloat16),
            spec((groups, k, n), jnp.bfloat16), spec((groups,), jnp.int32))

    def kernels_of(compiled):
        return sorted(re.sub(r"\.\d+$", "", name)
                      for name, _ in _grouped_products(compiled.as_text()))

    assert kernels_of(jax.jit(grouped_product).lower(*args).compile()) \
        == ["gmm"]
    kernels = kernels_of(jax.jit(jax.grad(scalar, argnums=(0, 1)))
                         .lower(*args).compile())
    # (the forward product of a sum is dead code: the transposed `gmm`
    # for the rows and `tgmm` for the weights are left)
    assert kernels == ["gmm", "tgmm"]


# -- a cell's whole serving step ----------------------------------------------

# The step of a benchmark cell, built from the cell's own configuration
# file at its published widths and compiled whole. At the cell's real
# depth the weights have to exist on the host first (5.3 GB of float32
# for gpt3-1.3b, 10.9 GB of bfloat16 and an 18 GB peak for sarvam-105b,
# a minute or two to draw them): those two cases are `slow`. Tier-1
# compiles the same step two layers deep, where everything the host and
# the step hand each other is the same: the one staged array, the pick,
# the logits. Per case: (configuration, layers or None for the file's
# own, the most bytes of temporaries the compile may report).
_MB = 1 << 20
_CELL_STEPS = [
    pytest.param("gpt3-1.3b", 2, 4 * _MB, id="gpt3-1.3b-2-layers"),
    pytest.param("sarvam-105b", 2, 107 * _MB, id="sarvam-105b-2-layers"),
    pytest.param("gpt3-1.3b", None, 32 * _MB, id="gpt3-1.3b",
                 marks=pytest.mark.slow),
    pytest.param("sarvam-105b", None, 107 * _MB, id="sarvam-105b",
                 marks=pytest.mark.slow),
]


def _cell_engine(name, layers):
    """The cell's `SlotEngine` as its runner builds it, but for the
    pools: two blocks here, the real ones are handed to the compile as
    shapes."""
    import json
    from pathlib import Path

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.nlp import transformers

    config = json.loads((Path(__file__).resolve().parent.parent
                         / "benchmarks" / "configs" / f"{name}.json")
                        .read_text())
    dep, sizes = config["serving"], dict(config["model"])
    if layers is not None:
        sizes["num_layers"] = layers
    paddle.seed(1)
    if config["family"] == "gpt":
        model = transformers.GPTForPretraining(
            transformers.GPTConfig(use_parallel=False, **sizes))
    else:
        build, sizes_of = {
            "latent_moe": (transformers.LatentMoEForCausalLM,
                           transformers.LatentMoEConfig),
            "hybrid_linear": (transformers.HybridLinearForCausalLM,
                              transformers.HybridLinearConfig),
            "window_moe": (transformers.WindowMoEForCausalLM,
                           transformers.WindowMoEConfig),
        }[config["family"]]
        was = paddle.get_default_dtype()
        paddle.set_default_dtype(dep["weight_dtype"])
        try:
            model = build(sizes_of(**sizes))
        finally:
            paddle.set_default_dtype(was)
    eng = serving.SlotEngine(
        model, max_slots=dep["max_slots"], max_seq_len=dep["max_seq_len"],
        block_size=dep.get("block_size"),
        num_blocks={name: 2 for name in dep["num_blocks"]}
        if isinstance(dep["num_blocks"], dict) else 2,
        prefill_chunk=dep.get("prefill_chunk"),
        cache_dtype=jnp.dtype(dep["cache_dtype"]),
        snapshot_entries=dep.get("snapshot_entries"))
    return eng, dep["num_blocks"], model.config.vocab_size


@pytest.mark.parametrize("name, layers, temp_limit", _CELL_STEPS)
def test_cell_step_returns_the_pick_beside_logits_that_are_not_copied(
        name, layers, temp_limit, one_chip, no_compile_cache):
    """`serving_step` as `_stage` feeds it: the host's arguments are one
    int32 array; the results are `pick` (`s32[slots]`), the logits
    (`f32[slots, V]`, still a result for the handle to fetch rows of)
    and the pools, every byte of them aliased to the arguments and none
    copied. The argmax reads the logits where the head's product left
    them, in fast memory, and they go out to their result buffer by one
    asynchronous copy beside it: no second one, and no relayout `copy`.
    What the program needs besides its arguments stays far from the
    size of a pool. (At the cells' real depth, `-m slow`: arguments
    9.293 GB, 4.030 GB aliased and 23.1 MB of temporaries for
    gpt3-1.3b, where the step before the pick had none; 12.935 GB,
    2.013 GB and 106.7 MB for sarvam-105b, 105.8 MB before its grouped
    product was the Pallas kernel: the kernel's group metadata.) The
    experts' grouped product of a program lowered for a TPU is
    `megablox.gmm`; gpt3-1.3b has none."""
    eng, num_blocks, vocab = _cell_engine(name, layers)
    slots = eng.max_slots

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    vec = np.zeros((slots,), np.int32)
    batch, extras = eng._stage(
        np.zeros((slots, eng.prefill_chunk), np.int32), vec, vec)
    # the previous step's pick, which a row that fed its token back on
    # the device takes its column 0 from: 4 bytes a slot, one select
    assert {k: (v.shape, v.dtype) for k, v in extras.items()} \
        == {"prev_pick": ((slots,), jnp.int32)}
    assert batch.dtype == np.int32
    assert batch.shape == (slots,
                           eng.prefill_chunk + 2 + eng.blocks_per_slot)
    shapes = eng._layout.pool_shapes(num_blocks, eng.block_size)
    pools = [tuple(jax.ShapeDtypeStruct(s, eng._pool_dtype,
                                        sharding=one_chip) for s in shapes)
             for _ in range(eng._layout.layers)]
    values = jax.tree_util.tree_map(spec, eng._values)
    compiled = eng._decode.lower(
        values, spec(batch), pools,
        jax.tree_util.tree_map(spec, extras)).compile()

    memory = compiled.memory_analysis()
    pool_bytes = eng._layout.layers * sum(
        int(np.prod(s)) for s in shapes) * eng._pool_dtype.itemsize
    held = sum(v.nbytes for v in eng._values.values()) + batch.nbytes \
        + extras["prev_pick"].nbytes
    assert memory.alias_size_in_bytes == pool_bytes
    assert abs(memory.argument_size_in_bytes - held - pool_bytes) < 1 << 16
    assert memory.temp_size_in_bytes < temp_limit

    hlo = compiled.as_text()
    entry = hlo[hlo.index("ENTRY"):]
    root = next(line for line in entry.splitlines()
                if line.lstrip().startswith("ROOT"))
    results = set(re.findall(r"(\w+)\[([\d,]*)\]",
                             root[:root.index("tuple(")]))
    assert ("s32", f"{slots}") in results, root[:400]
    assert ("f32", f"{slots},{vocab}") in results, root[:400]
    instructions = _entry_instructions(hlo)
    logits = [(op, name) for name, dtype, dims, op in instructions
              if dims == (slots, vocab) and op != "bitcast"]
    assert sorted(op for op, _ in logits) in (
        ["fusion"], ["copy-done", "fusion"]), logits
    moved = [line.split(" = ")[0].strip() for line in entry.splitlines()
             if re.search(r" copy(-start)?\(", line)
             and any(f"[{','.join(map(str, s))}]" in line for s in shapes)]
    assert not moved, f"pool-sized copies are back: {moved}"
    if name == "sarvam-105b":
        # every layer but the dense first: `[4096, 2 x 2048]` gate-up,
        # `[4096, 4096]` down
        _assert_pallas_grouped_products(
            hlo, eng.model.config.num_layers - 1, [4096, 4096])
    else:
        assert not _grouped_products(hlo)


@pytest.mark.parametrize("layers", [
    pytest.param(4, id="olmo-hybrid-7b-one-period"),
    pytest.param(None, id="olmo-hybrid-7b", marks=pytest.mark.slow)])
def test_hybrid_cell_step_updates_pools_and_state_arrays_in_place(
        layers, one_chip, no_compile_cache):
    """The step of a layout with state arrays is handed `{"blocks":
    pools, "state": state arrays}`: every byte of both is aliased to
    the arguments, none is copied, and what the program needs besides
    is no copy of a pool or of a layer's state array. The K and V
    rows keep 32 heads for the model's 30: declared `[30, 128]` the
    pools pad to 32 in the chip's tiles, the compiler keeps them in
    another layout and copies each whole round its scatter (seven
    480-512 MB temporaries, and the 16-layer step does not fit the
    chip). The float32 state `[rows, 30, 96, 192]` does pad (192 -> 256
    lanes), so the aliased bytes exceed the logical ones. And the row
    copy that takes, restores and resets a snapshot works in place
    with no temporaries at all. (At the cell's real depth, `-m slow`:
    arguments 13.699 GB, 5.497 GB aliased, 520 MB of temporaries.)"""
    eng, num_blocks, _ = _cell_engine("olmo-hybrid-7b", layers)
    slots = eng.max_slots

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    vec = np.zeros((slots,), np.int32)
    batch, extras = eng._stage(
        np.zeros((slots, eng.prefill_chunk), np.int32), vec, vec)
    shapes = eng._layout.pool_shapes(num_blocks, eng.block_size)
    assert shapes == [(4097, 16, 32, 128)] * 2
    pools = [tuple(jax.ShapeDtypeStruct(s, eng._pool_dtype,
                                        sharding=one_chip) for s in shapes)
             for _ in range(eng._layout.layers)]
    state = jax.tree_util.tree_map(spec, eng._state)
    rows = slots + eng.snapshot_entries + 1
    assert [a.shape for a in eng._state[0]] \
        == [(rows, 30, 96, 192), (rows, 3, 11520)]
    values = jax.tree_util.tree_map(spec, eng._values)
    compiled = eng._decode.lower(
        values, spec(batch), {"blocks": pools, "state": state},
        jax.tree_util.tree_map(spec, extras)).compile()

    memory = compiled.memory_analysis()
    pool_bytes = eng._layout.layers * sum(
        int(np.prod(s)) for s in shapes) * eng._pool_dtype.itemsize
    assert eng.state_bytes == rows * eng._layout.state_bytes_per_slot()
    aliased = memory.alias_size_in_bytes - pool_bytes
    assert eng.state_bytes <= aliased <= 1.4 * eng.state_bytes
    # temporaries: the step's own activations are under 100 MB; the
    # rest is slices of weights the compiler chooses to stage ahead of
    # their products (`bf16[5760,3840]`, `bf16[3840,11520]`, ...), not
    # copies of a pool or of a state array (held below by name)
    assert memory.temp_size_in_bytes < 640 * _MB

    hlo = compiled.as_text()
    entry = hlo[hlo.index("ENTRY"):]
    big = ["4097,16,32,128", f"{rows},30,96,192"]
    moved = [line.split(" = ")[0].strip() for line in entry.splitlines()
             if re.search(r" copy(-start)?\(", line)
             and any(f"[{dims}]" in line.split(" = ")[1].split("(")[0]
                     for dims in big)]
    assert not moved, f"pool- or state-sized copies: {moved}"
    assert not _grouped_products(hlo)

    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    helper = eng._snapshot.lower(state, scalar, scalar).compile() \
        .memory_analysis()
    assert helper.alias_size_in_bytes == aliased
    assert helper.temp_size_in_bytes == 0


@pytest.mark.parametrize("layers", [
    pytest.param(4, id="mellum2-12b-a2.5b-one-period"),
    pytest.param(None, id="mellum2-12b-a2.5b", marks=pytest.mark.slow)])
def test_two_group_cell_step_updates_both_groups_pools_in_place(
        layers, one_chip, no_compile_cache):
    """The step of a layout with TWO block groups: the full layers'
    pools `bf16[10241, 16, 512]` and the sliding layers' `bf16[4097,
    16, 512]`, K and V each, the 4 KV heads of a token side by side in
    a row of 512 columns. Every byte of both groups is aliased to the
    arguments at its LOGICAL size (a row declared `[4, 128]` pads 4 ->
    16 in the chip's bfloat16 tiles, four times the pool; 512 columns
    tile as they are) and no pool is copied. The host's one array
    carries both tables and the window group's base position. And the
    copy-on-write copy, one program over both groups, works in place
    (its temporaries hold a block of each pool, 161 KB). Every layer's
    two grouped products are the Pallas kernel. (At the cell's real
    depth, `-m slow`, 11 GB of host RAM: arguments 13.147 GB, 2.215 GB
    aliased, 26.1 MB of temporaries: 24.1 MB with `lax.ragged_dot`,
    the rest the kernel's group metadata; the limit is 32 MB.)"""
    eng, num_blocks, vocab = _cell_engine("mellum2-12b-a2.5b", layers)
    slots = eng.max_slots

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    vec = np.zeros((slots,), np.int32)
    batch, extras = eng._stage(
        np.zeros((slots, eng.prefill_chunk), np.int32), vec, vec)
    entries = eng._window.entries
    assert entries == (1024 + 64) // 16 + 1 == 69
    assert batch.shape == (slots, eng.prefill_chunk + 2
                           + eng.blocks_per_slot + entries + 1)
    eng._num_blocks = dict(num_blocks)
    shapes = eng._pool_shapes(eng._layout)
    kinds = eng.model.config.layer_types
    assert [layer for layer in shapes] == [
        [((10241 if kind == "full_attention" else 4097), 16, 512)] * 2
        for kind in kinds]
    pools = [tuple(jax.ShapeDtypeStruct(s, eng._pool_dtype,
                                        sharding=one_chip) for s in layer)
             for layer in shapes]
    values = jax.tree_util.tree_map(spec, eng._values)
    compiled = eng._decode.lower(
        values, spec(batch), pools,
        jax.tree_util.tree_map(spec, extras)).compile()

    memory = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(s)) for layer in shapes
                     for s in layer) * eng._pool_dtype.itemsize
    held = sum(v.nbytes for v in eng._values.values()) + batch.nbytes \
        + extras["prev_pick"].nbytes
    assert memory.alias_size_in_bytes == pool_bytes
    assert abs(memory.argument_size_in_bytes - held - pool_bytes) < 1 << 16
    assert memory.temp_size_in_bytes < 32 * _MB

    hlo = compiled.as_text()
    entry = hlo[hlo.index("ENTRY"):]
    big = ["10241,16,512", "4097,16,512"]
    moved = [line.split(" = ")[0].strip() for line in entry.splitlines()
             if re.search(r" copy(-start)?\(", line)
             and any(f"[{dims}]" in line.split(" = ")[1].split("(")[0]
                     for dims in big)]
    assert not moved, f"pool-sized copies: {moved}"
    _assert_pallas_grouped_products(hlo, len(kinds), [2 * 896, 2304])

    pair = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    helper = eng._cow.lower(pools, pair, pair).compile().memory_analysis()
    assert helper.alias_size_in_bytes == pool_bytes
    assert helper.temp_size_in_bytes < _MB      # a block a pool, staged


# ernie-base.pretrain behind benchmarks/configs/ernie-base.json and
# benchmarks/traffic/pretrain-mlm.json: 64 x 512 tokens a step
TRAIN_BATCH, TRAIN_SEQ = 64, 512


def _train_cell_step(layers, one_chip, monkeypatch):
    """The cell's `Engine` as its runner builds it, `layers` deep, and
    its step compiled from shapes for the described chip under the
    cell's bf16 autocast. A chip's dispatch takes the Mosaic kernels;
    here the platform is the CPU, so the test says so in their place."""
    import json
    from pathlib import Path

    from benchmarks.runners import train as runner
    from paddle_tpu import amp
    from paddle_tpu.engine import compile_step
    from paddle_tpu.framework import random as _random
    from paddle_tpu.ops import fused_loss, fused_ops

    monkeypatch.setattr(fused_ops, "_use_pallas", lambda seq_q=None: True)
    monkeypatch.setattr(fused_ops, "_interpret", lambda: False)
    monkeypatch.setattr(fused_loss, "_use_pallas_lm", lambda: True)
    monkeypatch.setattr(fused_loss, "_interpret", lambda: False)
    config = json.loads((Path(__file__).resolve().parent.parent
                         / "benchmarks" / "configs" / "ernie-base.json")
                        .read_text())
    config["model"]["num_layers"] = layers
    cfg, engine = runner._build(types.SimpleNamespace(config=config, seed=1))
    engine._build()

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    ids = jax.ShapeDtypeStruct((TRAIN_BATCH, TRAIN_SEQ), jnp.int32,
                               sharding=one_chip)
    state = engine.state
    protos = (*jax.tree.map(spec, (state.params, state.buffers,
                                   state.opt_state)),
              {"inputs": (ids,), "labels": (ids,)},
              spec(jnp.asarray(config["training"]["learning_rate"],
                               jnp.float32)),
              spec(_random.default_generator.next_key()))
    with amp.auto_cast(enable=True, dtype=config["training"]["autocast"]):
        return cfg, compile_step(engine._step_fn, protos)


@pytest.mark.parametrize("layers, peak_limit", [
    (2, 3.15e9),
    pytest.param(12, 15.3e9, marks=pytest.mark.slow),
])
def test_train_cell_step_draws_each_dropout_mask_once(
        layers, peak_limit, one_chip, no_compile_cache, monkeypatch):
    """`ernie-base.pretrain`'s step at its real widths: one
    `rng-bit-generator` of the mask's shape a dropout site (1 in the
    embeddings, 2 a layer), read by ONE instruction, the compare that
    makes the one-byte mask every other reader takes; and no fused
    computation derives a mask again (Threefry's `xor` over a
    `[64, 512, 768]` shape: 100 masks' worth for 25 sites before).
    Peak, arguments + temporaries + results - aliased (sandbox CPU,
    PR 39): 2 layers 3.105 GB (kept as 16 bits a position 3.206,
    Threefry 2.855); the cell's 12 layers 15.103 GB of the chip's
    16.909 (16 bits 15.812, Threefry 14.407), under `-m slow` (a
    minute). The Mosaic kernels are in it: flash forward, dq, dk/dv a
    layer and the LM-head loss's three."""
    cfg, compiled = _train_cell_step(layers, one_chip, monkeypatch)
    hlo = compiled.as_text()
    mask = (TRAIN_BATCH, TRAIN_SEQ, cfg.hidden_size)
    dims = ",".join(map(str, mask))

    draws = [(name, dtype, shape)
             for name, dtype, shape, op in _entry_instructions(hlo)
             if op == "rng-bit-generator"]
    assert len(draws) == 1 + 2 * layers, draws
    assert {(dtype, shape) for _, dtype, shape in draws} == {("u16", mask)}
    entry = hlo[hlo.index("ENTRY"):]
    for name, _, _ in draws:
        readers = re.findall(rf"%{re.escape(name)}[,)]", entry)
        assert len(readers) == 1, (name, len(readers))
    rederived = [line.strip()[:120] for line in hlo.splitlines()
                 if " xor(" in line and f"[{dims}]" in line]
    assert not rederived, rederived[:3]
    assert hlo.count("tpu_custom_call") >= 3 * layers + 3

    memory = compiled.memory_analysis()
    peak = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    print(f"train step, {layers} layers: compiled peak {peak} B "
          f"(temporaries {memory.temp_size_in_bytes} B)")
    assert peak < peak_limit, peak
