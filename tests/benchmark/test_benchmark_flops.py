"""The benchmark's shape functions against hand counts."""

import math

import pytest

from benchmarks.harness import flops, peaks

GPT2 = dict(n_layer=12, n_embd=768, n_head=12, n_inner=3072,
            vocab_size=50257)
RESNET = dict(stage_sizes=[3, 4, 6, 3], num_filters=64, num_classes=1000)


def test_gpt2_small_parameter_count_by_hand():
    attn = 4 * 768 * 768 + 4 * 768
    mlp = 768 * 3072 + 3072 + 3072 * 768 + 768
    norms = 2 * 2 * 768
    by_hand = 12 * (attn + mlp + norms) + 2 * 768 + 50257 * 768
    assert by_hand == 123_653_376
    assert flops.gpt2_matmul_params(GPT2) == by_hand


@pytest.mark.parametrize("seq,expected", [
    # 6 N + 12 L s d / 2, N = 123 653 376
    (1024, 741_920_256 + 56_623_104),
    (16384, 741_920_256 + 905_969_664),
])
def test_gpt2_small_train_flops_per_token(seq, expected):
    n = flops.gpt2_matmul_params(GPT2)
    assert flops.transformer_train_flops_per_token(
        n, 12, 768, seq, causal=True) == expected
    # a full mask needs twice the attention
    full = flops.transformer_train_flops_per_token(n, 12, 768, seq,
                                                   causal=False)
    assert full - 6 * n == 2 * (expected - 6 * n)


def test_resnet50_convolutions_by_hand():
    convs = flops.resnet50_convs(RESNET, 224)
    assert len(convs) == 53
    stem = convs[0]
    assert stem.macs == 112 * 112 * 7 * 7 * 3 * 64 == 118_013_952
    assert not stem.input_grad and all(c.input_grad for c in convs[1:])
    # first bottleneck at 56x56: 1x1 64->64, 3x3 64->64, 1x1 64->256 and the
    # 1x1 64->256 projection
    assert [c.macs for c in convs[1:5]] == [
        56 * 56 * 64 * 64, 56 * 56 * 9 * 64 * 64, 56 * 56 * 64 * 256,
        56 * 56 * 64 * 256]
    # first block of stage 2 strides on its 3x3 (v1.5): 1x1 at 56, 3x3 at 28
    stage2 = convs[11:15]
    assert (stage2[0].out_hw, stage2[1].out_hw, stage2[1].in_hw) == (56, 28,
                                                                     56)


def test_resnet50_forward_is_about_8_2_gflop_and_training_three_times():
    macs = flops.resnet50_forward_macs(RESNET, 224)
    assert 4.05e9 < macs < 4.13e9          # the usual "4.09 G multiply-adds"
    forward = 2.0 * macs
    assert 8.1e9 < forward < 8.3e9
    train = flops.resnet50_train_flops_per_image(RESNET, 224)
    # three passes, less the stem's input gradient that nobody needs
    assert train == 3 * forward - 2 * 118_013_952
    assert 24.2e9 < train < 24.6e9


def test_flash_required_operations_and_bytes_by_hand():
    ops, nbytes = flash_1k = flops.flash_train_required(
        8, 12, 1024, 64, causal=True, layers=12)
    product = 2 * 8 * 12 * 1024 * 1024 * 64 // 2
    assert ops == 12 * 7 * product == 541_165_879_296
    tensor, rows = 8 * 12 * 1024 * 64 * 2, 8 * 12 * 1024 * 4
    assert nbytes == 12 * (12 * tensor + 3 * rows)
    ops16, _ = flops.flash_train_required(1, 12, 16384, 64, causal=True,
                                          layers=12)
    # 1/8 of the rows, 16 times the sequence, squared: 32 times the work
    assert ops16 == 32 * flash_1k[0]


def test_conv_required_counts_each_pass_once():
    one = [flops.Conv(out_hw=4, kernel=3, cin=8, cout=16, in_hw=4,
                      input_grad=True)]
    ops, nbytes = flops.conv_train_required(one, batch=2)
    macs = 4 * 4 * 9 * 8 * 16
    assert ops == 2 * 2 * macs * 3
    x, y, w = 2 * 16 * 8 * 2, 2 * 16 * 16 * 2, 9 * 8 * 16
    assert nbytes == (x + 2 * w + y) + (x + y + 4 * w) + (y + 2 * w + x)
    no_grad = [one[0]._replace(input_grad=False)]
    assert flops.conv_train_required(no_grad, 2)[0] == 2 * 2 * macs * 2


def test_least_seconds_names_the_binding_peak():
    peak = peaks.Peak(100.0, 10.0, 1)
    assert flops.least_seconds(1000.0, 10.0, peak) == (10.0, "compute")
    assert flops.least_seconds(10.0, 1000.0, peak) == (100.0, "memory")


def test_peak_table_has_the_v5e_and_no_default(monkeypatch):
    v5e = peaks.peak_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bytes) == (197e12, 819e9)
    # no environment variable moves it
    monkeypatch.setenv("HVD_PEAK_FLOPS", "1")
    assert peaks.peak_for("TPU v5 lite").flops == 197e12
    with pytest.raises(RuntimeError, match="no published peak.*TPU v9"):
        peaks.peak_for("TPU v9")
    assert math.isclose(100 * 94617 * 798_543_360 / v5e.flops, 38.35,
                        abs_tol=0.01)
