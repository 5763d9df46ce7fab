"""The main path's Pallas kernels compile for a TPU v5e chip at real widths.

Nothing runs: each test lowers and compiles for a v5e that is described,
not attached (``jax.experimental.topologies``), which is enough to catch
what the TPU compiler refuses and interpret mode accepts — block shapes
off the (8, 128) tiling, 64-bit types in a kernel.  Every test asserts
the kernel survived into the compiled program (``tpu_custom_call``), so a
silent fallback to the XLA reference would fail here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import compile as tc
from repro.core import operators as ops
from repro.core import vm
from repro.core.memory import Grant
from repro.core.verifier import verify
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.kernels.tiara_gather.kernel import tiara_gather_kernel

# InternLM2-1.8B decode at 8 slots x 2048 tokens: 16 KV heads' worth of
# queries over 8 KV heads of 128 lanes, 128-token pages, 16 per sequence
B, KVH, GROUP, HEAD_DIM, PAGE, PAGES_PER_SEQ = 8, 8, 2, 128, 128, 16
PREFILL = 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: an
    entry written for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_paged_attention_compiles_at_internlm2_widths(one_chip):
    n_pages = B * PAGES_PER_SEQ + 1          # + the engine's scratch page
    pool = (n_pages, PAGE, KVH, HEAD_DIM)
    fn = jax.jit(paged_attention_kernel)
    compiled = fn.lower(
        _shape(one_chip, (B, KVH, GROUP, HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, pool, jnp.bfloat16),
        _shape(one_chip, pool, jnp.bfloat16),
        _shape(one_chip, (B, PAGES_PER_SEQ), jnp.int32),
        _shape(one_chip, (B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_bf16_prefill(one_chip):
    qh = KVH * GROUP
    fn = jax.jit(flash_attention_kernel)
    compiled = fn.lower(
        _shape(one_chip, (1, qh, PREFILL, HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, (1, KVH, PREFILL, HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, (1, KVH, PREFILL, HEAD_DIM), jnp.bfloat16),
        _shape(one_chip, (1,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# f32 rows of 128 words; int32 rows of 2 x 8 words: the width the
# compiled trace's gather route hands the kernel for 64-bit graph nodes
@pytest.mark.parametrize("dtype,row_words", [
    (jnp.float32, 128), (jnp.int32, 2 * ops.NODE_WORDS)])
def test_tiara_gather_compiles(one_chip, dtype, row_words):
    fn = jax.jit(tiara_gather_kernel)
    compiled = fn.lower(
        _shape(one_chip, (4096, row_words), dtype),
        _shape(one_chip, (4096,), jnp.int32),
        _shape(one_chip, (1024,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_tiara_gather_refuses_64bit_pool():
    pool = np.zeros((4, 8), np.int64)
    with vm.x64(), pytest.raises(ValueError, match="32-bit"):
        tiara_gather_kernel(jnp.asarray(pool), jnp.arange(4),
                            jnp.arange(2), interpret=True)


def test_compiled_trace_gather_route_compiles(one_chip):
    """``impl="kernel"`` routes the trace compiler's row gathers through
    ``tiara_gather`` with a 64-bit pool, as 32-bit halves."""
    kv = ops.PagedKVFetch(n_blocks_pool=64, block_bytes=64,
                          max_req_blocks=4)
    rt = kv.regions()
    vop = verify(kv.build(rt), grant=Grant.all_of(rt), regions=rt)
    batch = 8
    with vm.x64():
        fn = tc.build_compiled(vop, rt, 1, batch, impl="kernel")
        compiled = fn.lower(
            _shape(one_chip, (1, rt.pool_words), jnp.int64),
            _shape(one_chip, (batch, 1), jnp.int64),
            _shape(one_chip, (batch,), jnp.int64),
            _shape(one_chip, (1,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
