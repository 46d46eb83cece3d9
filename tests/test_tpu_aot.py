"""The kernels of the train and serve paths compile for a TPU v5e.

Each test compiles one Pallas kernel with ``interpret=False`` for a v5e chip
that is described, not attached (``jax.experimental.topologies``), at the
real widths of qwen2-0.5b in bf16 (attention at a 2048-token prefill), and
checks that the Mosaic kernel (``tpu_custom_call``) is in the compiled HLO.
Nothing runs: these catch
what the chip's compiler would refuse (block shapes off the (8, 128)
tiling, casts Mosaic has no lowering for, scalars stored to VMEM) without a
chip.  The topology is described inside a fixture, so importing this file
never loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.qwen2_0_5b import CONFIG as QWEN
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.paged.gather import paged_gather
from repro.kernels.zo_fused import rows
from repro.kernels.zo_fused.kernel import (BLOCK_COLS, BLOCK_ROWS,
                                           zo_affine_2d, zo_affine_2d_batched)
from repro.kernels.zo_fused.multi import (zo_affine_chain_2d,
                                          zo_affine_multi_2d, zo_sqnorm_2d)

B = 8                                   # fzoo(8) streams
BLOCK = 16                              # serve launcher's KV block
HD = QWEN.d_model // QWEN.n_heads
# the largest leaf of qwen2-0.5b (the 151936 x 896 embedding), blocked
N_EMBED = QWEN.vocab_size * QWEN.d_model
ROWS = -(-N_EMBED // (BLOCK_ROWS * BLOCK_COLS)) * BLOCK_ROWS
SEL = tuple(range(0, ROWS // BLOCK_ROWS, 4))   # rows(block, k=4) plan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cases(s):
    x = _spec((ROWS, BLOCK_COLS), jnp.bfloat16, s)
    i0, f0 = _spec((), jnp.int32, s), _spec((), jnp.float32, s)
    iv, fv = _spec((B,), jnp.int32, s), _spec((B,), jnp.float32, s)
    kw = dict(interpret=False)
    return {
        "zo_affine_2d": (lambda x, k, a, b: zo_affine_2d(x, k, a, b, **kw),
                         (x, i0, f0, f0)),
        "zo_affine_2d_batched": (
            lambda x, k, a, b: zo_affine_2d_batched(x, k, a, b, **kw),
            (x, iv, f0, f0)),
        "zo_affine_multi_2d": (
            lambda x, k, a, b: zo_affine_multi_2d(x, k, a, b, **kw),
            (x, iv, fv, fv)),
        "zo_affine_chain_2d": (
            lambda x, k, a, b: zo_affine_chain_2d(x, k, a, b, **kw),
            (x, iv, fv, fv)),
        "zo_sqnorm_2d": (lambda k: zo_sqnorm_2d(N_EMBED, k, **kw), (i0,)),
        "zo_affine_multi_2d_rows": (
            lambda x, k, a, b: rows.zo_affine_multi_2d_rows(
                x, k, a, b, SEL, 100_000, 4, 1, True, **kw),
            (x, iv, fv, fv)),
        "paged_gather": (
            lambda pool, tab: paged_gather(pool, tab, BLOCK, **kw),
            (_spec((QWEN.n_layers, 65 * BLOCK, QWEN.n_kv_heads * HD),
                   jnp.bfloat16, s),
             _spec((4 * 8,), jnp.int32, s))),
        "flash_attention_bhsd": (
            lambda q, k, v: flash_attention_bhsd(q, k, v, **kw),
            (_spec((1, QWEN.n_heads, 2048, HD), jnp.bfloat16, s),
             _spec((1, QWEN.n_kv_heads, 2048, HD), jnp.bfloat16, s),
             _spec((1, QWEN.n_kv_heads, 2048, HD), jnp.bfloat16, s))),
    }


@pytest.mark.parametrize("kernel", [
    "zo_affine_2d", "zo_affine_2d_batched", "zo_affine_multi_2d",
    "zo_affine_chain_2d", "zo_sqnorm_2d", "zo_affine_multi_2d_rows",
    "paged_gather", "flash_attention_bhsd"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    fn, args = _cases(one_chip)[kernel]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
