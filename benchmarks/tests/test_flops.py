"""``flops.py`` against the two hand-worked figures."""

from benchmarks import flops


def test_gpt2_medium_is_2_42_gflop_a_token():
    got = flops.lm_per_token(layers=24, d_model=1024, vocab=50257,
                             seq_len=1024)
    # 6 x (12 x 24 x 1024^2 + 1024 x 50257) + 12 x 24 x 1024 x 1024
    assert got == 6 * (12 * 24 * 1024 ** 2 + 1024 * 50257) \
        + 12 * 24 * 1024 * 1024 == 2_422_708_224
    assert round(got / 1e9, 2) == 2.42


def test_resnet50_is_4_09_gmac_forward_and_24_5_gflop_a_step():
    kw = dict(stages=[3, 4, 6, 3], width=64, image=224, classes=1000)
    macs = flops.resnet_bottleneck_macs(stride_on_3x3=True, **kw)
    assert macs == 4_089_184_256          # the published 4.09 GMAC (v1.5)
    assert round(macs / 1e9, 2) == 4.09
    assert flops.resnet_per_image(stride_on_3x3=True, **kw) == 6 * macs
    assert round(6 * macs / 1e9, 1) == 24.5
    # the paper's own block strides its first 1x1 and is lighter
    assert flops.resnet_bottleneck_macs(stride_on_3x3=False, **kw) \
        == 3_857_973_248
