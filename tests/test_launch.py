"""Launch layer: sharding rules, input specs, HLO analysis.

These run on the single CPU device using AbstractMesh for rule checks
(no XLA_FLAGS forcing — see conftest).  The real 512-device lowering is
exercised by launch/dryrun.py, whose results land in EXPERIMENTS.md.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P
from repro.configs import ARCH_IDS, INPUT_SHAPES, applicable, get_config, \
    get_smoke_config
from repro.launch import sharding as sh
from repro.launch.hlo_analysis import analyze_hlo, parse_module, shape_bytes
from repro.launch.specs import input_specs
from repro.models import model as M

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(shape_tree, spec_tree, mesh):
    def ok(leaf, spec):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            size = sh._axsize(mesh, ax)
            assert leaf.shape[dim] % size == 0, \
                f"{leaf.shape} dim {dim} not divisible by {ax}={size}"
    jax.tree.map(ok, shape_tree, spec_tree,
                 is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("mesh", [MESH, MESH3], ids=["single", "multi"])
def test_param_specs_divisible_all_archs(arch, mesh):
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    for fsdp in (False, True):
        specs = sh.param_pspecs(cfg, mesh, shapes, fsdp=fsdp)
        _check_divisible(shapes, specs, mesh)


@pytest.mark.parametrize("arch", ["gemma3-1b", "kimi-k2-1t-a32b",
                                  "mamba2-370m", "zamba2-7b"])
def test_param_specs_actually_shard_big_tensors(arch):
    """Every >=2D tensor with a mesh-divisible dim must not be fully
    replicated (memory correctness at 1T scale)."""
    cfg = get_config(arch)
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    specs = sh.param_pspecs(cfg, MESH, shapes, fsdp=False)

    flat_shapes = jax.tree.leaves(shapes)
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    big_replicated = [
        (s.shape, sp) for s, sp in zip(flat_shapes, flat_specs)
        if s.size * 4 > 256e6 and all(a is None for a in sp)]
    assert not big_replicated, f"large replicated tensors: {big_replicated}"


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape_name", list(INPUT_SHAPES))
def test_cache_and_batch_specs_divisible(arch, shape_name):
    shape = INPUT_SHAPES[shape_name]
    cfg = M.specialize(get_config(arch), shape)
    ok, _ = applicable(cfg, shape)
    if not ok:
        pytest.skip("documented skip")
    specs = input_specs(cfg, shape)
    if shape.kind == "decode":
        cspecs = sh.cache_pspecs(cfg, MESH, specs["cache"],
                                 shape.global_batch)
        _check_divisible(specs["cache"], cspecs, MESH)
    else:
        bspecs = sh.batch_pspecs(cfg, MESH, specs)
        _check_divisible(specs, bspecs, MESH)


def test_input_specs_are_abstract():
    cfg = get_config("gemma3-1b")
    specs = input_specs(cfg, INPUT_SHAPES["decode_32k"])
    for leaf in jax.tree.leaves(specs):
        assert isinstance(leaf, jax.ShapeDtypeStruct)  # no allocation


# ---------------------------------------------------------------------------
# HLO analysis
# ---------------------------------------------------------------------------

def test_hlo_analyzer_counts_scan_trip():
    """Dot FLOPs inside a lax.scan are multiplied by the trip count
    (cost_analysis famously counts the body once)."""
    from jax import lax

    def f(x, ws):
        def body(h, w):
            return jnp.tanh(h @ w), None
        y, _ = lax.scan(body, x, ws)
        return y.sum()

    xs = jax.ShapeDtypeStruct((16, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    compiled = jax.jit(f).lower(xs, ws).compile()
    walked = analyze_hlo(compiled.as_text())
    analytic = 2 * 16 * 64 * 64 * 5
    assert walked.flops == pytest.approx(analytic, rel=0.05)
    ca = compiled.cost_analysis()
    assert ca["flops"] < walked.flops  # the bug we correct


def test_hlo_analyzer_bytes_sane():
    def f(a, b):
        return a @ b
    A = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    B = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    compiled = jax.jit(f).lower(A, B).compile()
    walked = analyze_hlo(compiled.as_text())
    lo = (128 * 256 + 256 * 128 + 128 * 128) * 4
    assert lo * 0.9 <= walked.bytes <= lo * 3


def test_shape_bytes_parse():
    assert shape_bytes("f32[128,256]{1,0}") == 128 * 256 * 4
    assert shape_bytes("bf16[8]") == 16
    assert shape_bytes("(f32[2,2], s32[4])") == 16 + 16
    assert shape_bytes("pred[]") == 1


def test_applicability_documented_skips():
    skipped = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        ok, why = applicable(cfg, INPUT_SHAPES["long_500k"])
        if not ok:
            skipped.append(arch)
            assert why
    assert set(skipped) == {"whisper-base", "internvl2-76b",
                            "kimi-k2-1t-a32b", "granite-moe-1b-a400m",
                            "phi3-medium-14b"}
