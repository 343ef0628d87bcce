"""Every Pallas kernel compiles for a TPU v5e at published widths.

Interpret mode on the CPU accepts blocks that the TPU compiler refuses
(a block narrower than the (8, 128) tile on a dimension it does not
cover whole, a primitive Mosaic cannot lower).  These tests compile
each kernel for a described ``v5e:2x2`` topology — nothing runs, no
chip is needed — and check that the kernel is in the program as a
``tpu_custom_call``.  The topology is described inside a fixture, never
at import, so test collection is the same in every worker.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as FA
from repro.kernels import quant_matmul as QM
from repro.kernels import ssd_scan as SSD

GRANITE = get_config("granite-moe-1b-a400m")
MAMBA = get_config("mamba2-370m")
H, K, HD = GRANITE.num_heads, GRANITE.num_kv_heads, GRANITE.head_dim
B, BS, N_BLK = 8, 16, 128                 # 8 slots x 2048-token tables
N_PAGES = B * N_BLK
HBM_BYTES = 16 * 2 ** 30                  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a program compiled for a described chip is written to the
    # persistent cache but cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


def _compile_for_chip(fn, *args, **kwargs):
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < HBM_BYTES
    return compiled


# the served pool is in the activation dtype (bf16); f32 and int8 are
# the other layouts the kernels accept
POOL_DTYPES = ["float32", "bfloat16", "int8"]


def _pool(spec, dtype):
    pages = [spec((N_PAGES, BS, K, HD), dtype),
             spec((N_PAGES, BS, K, HD), dtype)]
    scales = ({"k_scale": spec((N_PAGES, BS, K)),
               "v_scale": spec((N_PAGES, BS, K))} if dtype == "int8" else {})
    return pages, scales


@pytest.mark.parametrize("dtype", POOL_DTYPES)
def test_paged_attention_compiles(spec, dtype):
    pages, scales = _pool(spec, dtype)
    _compile_for_chip(
        functools.partial(FA.paged_attention, scale=HD ** -0.5),
        spec((B, H, HD)), *pages, spec((B, N_BLK), jnp.int32),
        spec((B,), jnp.int32), **scales)


@pytest.mark.parametrize("dtype", POOL_DTYPES)
def test_paged_extend_attention_compiles(spec, dtype):
    S = 4
    pages, scales = _pool(spec, dtype)
    _compile_for_chip(
        functools.partial(FA.paged_extend_attention, scale=HD ** -0.5),
        spec((B, S, H, HD)), *pages, spec((B, S, K, HD)),
        spec((B, S, K, HD)), spec((B, N_BLK), jnp.int32),
        spec((B,), jnp.int32), **scales)


def test_flash_attention_compiles(spec):
    S = 2048
    _compile_for_chip(
        functools.partial(FA.flash_attention, scale=HD ** -0.5),
        spec((1, S, H, HD), jnp.bfloat16), spec((1, S, K, HD), jnp.bfloat16),
        spec((1, S, K, HD), jnp.bfloat16))


def test_quant_matmul_compiles(spec):
    _compile_for_chip(QM.quant_matmul, spec((128, 1024), jnp.bfloat16),
                      spec((1024, 4096), jnp.int8), spec((4096,)))


def test_ssd_scan_compiles(spec):
    cfg = MAMBA
    h, p, n, L = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, 1024
    _compile_for_chip(
        functools.partial(SSD.ssd_scan, chunk=cfg.ssm_chunk),
        spec((1, L, h, p)), spec((1, L, h)), spec((h,)),
        spec((1, L, n)), spec((1, L, n)))
