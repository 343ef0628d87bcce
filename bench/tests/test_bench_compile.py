"""Each configuration's served programs compile for a described TPU v5e
at the cells' own shapes, and fit its memory.

The decode and extend waves are the only programs a cell's window runs
(prompts enter as extend-wave chunks).  ``memory_analysis()`` counts one
program: the weights and the pool (its arguments) plus its temporaries
must leave room on the 16 GB chip.  This is the XLA:TPU compiler's own
allotment, which it holds to 15.75 GiB on a v5e and refuses beyond; the
device's ``peak_bytes_in_use`` after a run leaves the temporaries out
and reads several GiB lower, so it cannot size a configuration.
"""
import os
from functools import partial

import pytest

from bench import spec, weights

HBM = 16 * 2**30
HEADROOM = 0.9          # of the chip's memory, for the runtime and the rest

CONFIGS = sorted({c["config"] for c in spec.load_benchmark()["workloads"]})


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=False)
def compiled_kernels(monkeypatch):
    """The CPU would interpret the Pallas kernels; compile them."""
    import jax
    from repro.kernels import ops
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("wave", ["decode", "extend"])
def test_wave_compiles_and_fits(name, wave, one_chip, compiled_kernels):
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    from repro.serving.engine import EdgeServingEngine

    conf = spec.load_config(name)
    mcfg = spec.model_config(conf)
    scfg, _ = spec.serve_config(conf)
    stub = object.__new__(EdgeServingEngine)     # the waves read cfg, scfg
    stub.cfg, stub.scfg = mcfg, scfg
    B, T, bs = scfg.max_slots, scfg.max_len, scfg.kv_block_size
    n_blk = T // bs

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(weights.program_maker(conf, mcfg),
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(partial(M.init_paged_cache, mcfg, B, T,
                                           B * n_blk, bs)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    tables = i32(B, n_blk)
    if wave == "decode":
        fn = jax.jit(partial(EdgeServingEngine._decode_fn, stub),
                     static_argnames=("any_topk",), donate_argnums=(1,))
        lowered = fn.lower(
            params, cache, i32(B, 1), i32(B),
            jax.ShapeDtypeStruct((B,), jnp.float32, sharding=one_chip),
            i32(B), jax.ShapeDtypeStruct((2,), jnp.uint32,
                                         sharding=one_chip),
            tables, any_topk=False)
    else:
        K = max(scfg.spec_gamma, scfg.catch_chunk or 0)
        fn = jax.jit(partial(EdgeServingEngine._extend_fn, stub),
                     static_argnames=("need_logits",), donate_argnums=(1,))
        lowered = fn.lower(params, cache, i32(B, K), i32(B), i32(B), tables,
                           need_logits=False)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert total < HEADROOM * HBM, (
        f"{name} {wave}: arguments {mem.argument_size_in_bytes / 2**30:.2f}"
        f" GiB + temp {mem.temp_size_in_bytes / 2**30:.2f} GiB")
    if wave == "decode" and scfg.use_pallas_paged:
        assert "tpu_custom_call" in compiled.as_text()
