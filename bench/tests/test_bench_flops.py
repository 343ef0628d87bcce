"""``bench/flops.py`` against hand counts at the published widths, and
for every configuration of ``BENCHMARK.json`` counts that add up: over
the tokens of a chunk and over the rows of a decode-attention call."""
import pytest

from bench import flops, spec

CONFIGS = sorted(c["name"] for c in spec.load_benchmark()["configs"])


def test_granite_per_token_counts():
    c = spec.load_config("granite-moe-1b-a400m")
    # q 1024x16x64, k and v 1024x8x64, o 16x64x1024; router 1024x32;
    # 8 of 32 experts of 3 x 1024 x 512
    proj = 2 * (1024 * 1024 + 2 * 1024 * 512 + 1024 * 1024)
    layer = proj + 2 * 1024 * 32 + 8 * 2 * 3 * 1024 * 512
    head = 2 * 1024 * 49155
    assert flops.paged_attention_call(c, [100])[0] == 4 * 100 * 16 * 64
    assert flops.token_flops(c, 99, 1, 1) == (
        24 * (layer + 4 * 100 * 1024) + head)
    assert flops.token_flops(c, 0, 1, 0) == 24 * (layer + 4 * 1024)


def test_phi3_per_token_counts():
    c = spec.load_config("phi3-medium-14b-4l")
    proj = 2 * (5120 * 5120 + 2 * 5120 * 1280 + 5120 * 5120)
    layer = proj + 2 * 3 * 5120 * 17920
    # 340.8 M weights a layer, as the configuration's file says
    assert abs(layer / 2 / 1e6 - 340.8) < 0.1
    assert flops.token_flops(c, 0, 1, 0) == 4 * (layer + 4 * 1 * 40 * 128)
    assert flops.token_flops(c, 0, 1, 1) == (
        flops.token_flops(c, 0, 1, 0) + 2 * 5120 * 32064)


def test_paged_attention_call():
    c = spec.load_config("phi3-medium-14b-4l")
    fl, by = flops.paged_attention_call(c, [1000, 3000])
    assert fl == 4 * 4000 * 40 * 128
    # keys and values of 10 kv heads of 128 in bf16, query and output
    assert by == 2 * 4000 * 10 * 128 * 2 + 2 * (2 * 40 * 128 * 2)


@pytest.mark.parametrize("name", CONFIGS)
def test_counts_add_up(name):
    c = spec.load_config(name)
    one = lambda p, e=0: flops.token_flops(c, p, 1, e)
    head = one(0, 1) - one(0)
    assert head > 0 and one(100) > one(99) > 0
    for p0, n in ((0, 1), (0, 64), (1000, 128), (4000, 7)):
        assert flops.token_flops(c, p0, n, 1) == (
            sum(one(p0 + i) for i in range(n)) + head)
    a, b = flops.paged_attention_call(c, [17, 4096]), \
        flops.paged_attention_call(c, [300])
    both = flops.paged_attention_call(c, [17, 4096, 300])
    assert both == (a[0] + b[0], a[1] + b[1]) and min(b) > 0
