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
        out, (k_pool, v_pool, _) = GPTAttention._attend_paged(
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


def test_serving_kv_path_updates_donated_pools_in_place(one_chip,
                                                        no_compile_cache):
    """With the pool token-major and donated, the entry computation
    scatters into the parameter and returns it aliased: no copy of a
    whole pool, none of a slot-by-context view, and the pools' bytes
    aliased. (Head-major, the scatter over axes 0 and 2 cost a relayout
    copy in and one out for each pool, donated or not, and the view a
    transposing copy `bf16[16,16,128,16,128]`: 57 % of the step.)"""
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = spec((NB, BS, NH, HD), jnp.bfloat16)
    rows = spec((SLOTS, NH, CHUNK, HD), jnp.float32)
    compiled = jax.jit(_kv_path, donate_argnums=(5, 6)).lower(
        rows, rows, rows, spec((SLOTS,), jnp.int32),
        spec((SLOTS, TABLE), jnp.int32),
        [pool] * LAYERS, [pool] * LAYERS).compile()

    pool_bytes = 2 * LAYERS * NB * BS * NH * HD * 2
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes

    instructions = _entry_instructions(compiled.as_text())
    assert len(instructions) > 20, "the entry computation was not parsed"
    pool_elems = NB * BS * NH * HD
    view_elems = SLOTS * TABLE * BS * NH * HD
    copies = [(name, dtype, dims) for name, dtype, dims, op in instructions
              if (op.startswith("copy") or name.startswith("copy"))
              and int(np.prod(dims)) in (pool_elems, view_elems)]
    assert not copies, f"pool- or context-sized copies are back: {copies}"
    # what is left of the read path: one gather a pool, fed to the
    # contraction by a bitcast
    gathers = [i for i in instructions
               if i[2] == (TABLE * SLOTS, BS, NH, HD) and i[3] == "fusion"]
    assert len(gathers) == 2 * LAYERS
