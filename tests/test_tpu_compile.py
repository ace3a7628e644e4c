"""The serving path's Pallas kernels, and one Mixtral-8x7B decode step,
compiled for a described (not attached) TPU v5e at published widths.

Interpret mode does not apply the TPU's tiling rules, so the CPU parity
tests cannot show that a kernel lowers for the chip. These tests ask the
TPU compiler itself, from shapes alone: nothing runs and no device
memory is touched. The topology is described inside a module-scoped
fixture (never at import time), so every test worker collects the same
tests and only the worker that runs this file loads the TPU library.
"""
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attn, moe_gmm
from repro.models import model as M
from repro.models import transformer as T

# Mixtral-8x7B published widths (configs/mixtral_8x7b.py)
E, D, F = 8, 4096, 14336
H, KV, HD = 32, 8, 128
SLOTS = 20          # runtime slot bank: 4 logical devices x 5 slots
BATCH = 8
KV_BLOCK = 16
# capacity rows per expert: decode (8 tokens, cf 1.25), the benchmark
# cell's decode step (8 rows, cf 8), a 64-token prefill chunk over 8
# rows, and a drop-free cf=8 chunk (the cell's mixed step)
CAPACITIES = (3, 16, 160, 1024)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip land in the persistent cache but
    # cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel(text):
    """The kernel names the compiled program's Pallas calls carry in
    their ``kernel_metadata``, the handle a profiler trace shows."""
    return set(re.findall(r'"kernel":"(\w+)"', text))


def _result_ranks(text, kernel):
    """Ranks of the arrays the custom calls named `kernel` (the op name
    a trace shows, ``<kernel>.<n>``) return: a trace reader tells the
    grouped GMMs (rank 3) from decode attention by it."""
    ranks = []
    for line in text.splitlines():
        lhs, _, rhs = line.partition(" = ")
        op = lhs.split()[-1].lstrip("%").split(".")[0] if lhs else ""
        if " custom-call(" in rhs and op == kernel:
            shape = rhs.split(" ", 1)[0]
            ranks.append(len(shape[shape.index("[") + 1:
                                   shape.index("]")].split(",")))
    return ranks


@pytest.mark.parametrize("cap", CAPACITIES)
def test_gmm_compiles(one_chip, cap):
    s = partial(_spec, one_chip)
    text = _compiled_text(moe_gmm.gmm, s((E, cap, F), jnp.bfloat16),
                          s((E, F, D), jnp.bfloat16), s((E,), jnp.int32))
    assert "tpu_custom_call" in text
    assert _kernel(text) == {"moe_gmm"}


@pytest.mark.parametrize("cap", CAPACITIES)
def test_fused_gate_up_compiles(one_chip, cap):
    s = partial(_spec, one_chip)
    text = _compiled_text(moe_gmm.fused_gate_up,
                          s((E, cap, D), jnp.bfloat16),
                          s((E, D, F), jnp.bfloat16),
                          s((E, D, F), jnp.bfloat16), s((E,), jnp.int32))
    assert "tpu_custom_call" in text
    assert _kernel(text) == {"moe_ffn"}


@pytest.mark.parametrize("cap", CAPACITIES)
def test_gmm_quant_compiles(one_chip, cap):
    s = partial(_spec, one_chip)
    text = _compiled_text(moe_gmm.gmm_quant,
                          s((SLOTS, cap, F), jnp.bfloat16),
                          s((SLOTS, F, D), jnp.int8),
                          s((SLOTS, F), jnp.float32),
                          s((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text
    assert _kernel(text) == {"moe_gmm_quant"}
    assert _result_ranks(text, "moe_gmm_quant") == [3]


@pytest.mark.parametrize("cap", CAPACITIES)
def test_fused_gate_up_quant_compiles(one_chip, cap):
    s = partial(_spec, one_chip)
    text = _compiled_text(moe_gmm.fused_gate_up_quant,
                          s((SLOTS, cap, D), jnp.bfloat16),
                          s((SLOTS, D, F), jnp.int8),
                          s((SLOTS, D), jnp.float32),
                          s((SLOTS, D, F), jnp.int8),
                          s((SLOTS, D), jnp.float32),
                          s((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in text
    assert _kernel(text) == {"moe_ffn_quant"}
    assert _result_ranks(text, "moe_ffn_quant") == [3]


@pytest.mark.parametrize("seq", (320, 1280))
def test_decode_attention_compiles(one_chip, seq):
    s = partial(_spec, one_chip)
    text = _compiled_text(decode_attn.decode_attention,
                          s((BATCH, H, HD), jnp.bfloat16),
                          s((BATCH, seq, KV, HD), jnp.bfloat16),
                          s((BATCH, seq, KV, HD), jnp.bfloat16),
                          s((BATCH, seq), jnp.int32),
                          s((BATCH,), jnp.int32), s((BATCH,), jnp.int32))
    assert "tpu_custom_call" in text
    assert _kernel(text) == {"decode_attn"}


def test_decode_attention_paged_compiles(one_chip):
    s = partial(_spec, one_chip)
    nbs = 20
    nb = 1 + BATCH * nbs
    text = _compiled_text(decode_attn.decode_attention_paged,
                          s((BATCH, H, HD), jnp.bfloat16),
                          s((nb, KV_BLOCK, KV, HD), jnp.bfloat16),
                          s((nb, KV_BLOCK, KV, HD), jnp.bfloat16),
                          s((nb, KV_BLOCK), jnp.int32),
                          s((BATCH, nbs), jnp.int32),
                          s((BATCH,), jnp.int32), s((BATCH,), jnp.int32))
    assert "tpu_custom_call" in text
    assert _kernel(text) == {"decode_attn_paged"}


def test_mixtral_decode_step_compiles(one_chip):
    """One Mixtral-8x7B layer at published widths in bf16: the paged
    single-token decode step the serving engine jits, with the Pallas
    backend selected (on the chip 'auto' resolves to it)."""
    cfg = get_config("mixtral-8x7b").with_(num_layers=1, impl="pallas")
    nbs = 20
    nb = 1 + BATCH * nbs

    def placed(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = placed(jax.eval_shape(partial(M.init_params, cfg),
                                   jax.random.PRNGKey(0)))
    cache = placed(jax.eval_shape(
        partial(T.init_paged_cache, cfg, None, nb, KV_BLOCK)))
    s = partial(_spec, one_chip)
    batch = {"tokens": s((BATCH, 1), jnp.int32),
             "active": s((BATCH,), jnp.bool_),
             "block_tables": s((BATCH, nbs), jnp.int32),
             "new_counts": s((BATCH,), jnp.int32)}
    step = partial(T.decode_step, cfg, window=0, collect=False)
    compiled = jax.jit(step).lower(params, batch, cache,
                                   s((BATCH,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3   # attention + 2 GMMs
    assert {"decode_attn_paged", "moe_ffn"} <= _kernel(text)
    mem = compiled.memory_analysis()
    # one bf16 layer plus embeddings must fit the chip's 16 GiB
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30
