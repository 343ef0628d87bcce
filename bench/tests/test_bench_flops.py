"""``bench/flops.py`` against hand counts at the published widths."""
from bench import flops, spec


def test_granite_per_token_counts():
    c = spec.load_config("granite-moe-1b-a400m")
    # q 1024x16x64, k and v 1024x8x64, o 16x64x1024; router 1024x32;
    # 8 of 32 experts of 3 x 1024 x 512
    proj = 2 * (1024 * 1024 + 2 * 1024 * 512 + 1024 * 1024)
    assert flops.layer_matmul_flops(c) == (proj + 2 * 1024 * 32
                                           + 8 * 2 * 3 * 1024 * 512)
    assert flops.head_flops(c) == 2 * 1024 * 49155
    assert flops.attention_flops(c, 100) == 4 * 100 * 16 * 64
    assert flops.token_flops(c, 99, True) == (
        24 * (flops.layer_matmul_flops(c) + 4 * 100 * 1024)
        + 2 * 1024 * 49155)


def test_phi3_per_token_counts():
    c = spec.load_config("phi3-medium-14b-4l")
    proj = 2 * (5120 * 5120 + 2 * 5120 * 1280 + 5120 * 5120)
    assert flops.layer_matmul_flops(c) == proj + 2 * 3 * 5120 * 17920
    # 340.8 M weights a layer, as the configuration's file says
    assert abs(flops.layer_matmul_flops(c) / 2 / 1e6 - 340.8) < 0.1
    assert flops.token_flops(c, 0, False) == 4 * (
        flops.layer_matmul_flops(c) + 4 * 1 * 40 * 128)


def test_paged_attention_call():
    c = spec.load_config("phi3-medium-14b-4l")
    fl, by = flops.paged_attention_call(c, [1000, 3000])
    assert fl == 4 * 4000 * 40 * 128
    # keys and values of 10 kv heads of 128 in bf16, query and output
    assert by == 2 * 4000 * 10 * 128 * 2 + 2 * (2 * 40 * 128 * 2)
