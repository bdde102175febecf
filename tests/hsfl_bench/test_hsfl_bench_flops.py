"""bench/flops.py against counts made by hand, and the peaks table."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops, harness  # noqa: E402

VGG = json.loads((ROOT / "bench/configs/vgg16-cifar10.json").read_text())
LM = json.loads((ROOT / "bench/configs/smollm135m.json").read_text())


def test_vgg16_cifar_macs_per_image():
    # 3x3 convs at 32, 32 | 16, 16 | 8, 8, 8 | 4, 4, 4 | 2, 2, 2 pixels
    by_hand = [
        32 * 32 * 9 * 3 * 64, 32 * 32 * 9 * 64 * 64,
        16 * 16 * 9 * 64 * 128, 16 * 16 * 9 * 128 * 128,
        8 * 8 * 9 * 128 * 256, 8 * 8 * 9 * 256 * 256, 8 * 8 * 9 * 256 * 256,
        4 * 4 * 9 * 256 * 512, 4 * 4 * 9 * 512 * 512, 4 * 4 * 9 * 512 * 512,
        2 * 2 * 9 * 512 * 512, 2 * 2 * 9 * 512 * 512, 2 * 2 * 9 * 512 * 512,
        512 * 512, 512 * 512, 512 * 10,
    ]
    assert flops.vgg_layer_macs(VGG) == by_hand
    assert sum(by_hand) == 313_725_952
    # forward, both backward products, less the input gradient of conv 1
    assert flops.vgg_train_flops_per_image(VGG) == 2 * (3 * 313_725_952 - 1_769_472)


def test_smollm135m_flops_per_token():
    per_layer = 576 * 576 + 2 * 576 * 192 + 576 * 576 + 3 * 576 * 1536
    assert flops.lm_layer_matmul_params(LM) == per_layer == 3_538_944
    assert flops.lm_matmul_params(LM) == 30 * per_layer + 49152 * 576 == 134_479_872
    assert flops.lm_param_count(LM) == 49152 * 576 + 30 * (per_layer + 2 * 576) + 576
    # 6 x matmul weights per token, plus causal attention: query i sees i+1
    # keys, 4 h hd FLOPs per key per layer forward, x3 for training
    seq = 256
    attn = 3 * sum(4 * 30 * 9 * 64 * (i + 1) for i in range(seq))
    assert flops.lm_train_flops_per_sequence(LM, seq) == pytest.approx(
        6 * 134_479_872 * seq + attn, rel=1e-12)
    assert flops.lm_train_flops_per_sequence(LM, seq) / seq == pytest.approx(
        806_879_232 + 6 * 30 * 576 * 257, rel=1e-12)


def test_peaks_by_device_kind():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["flops_bf16"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.load_peaks("TPU v9 imaginary")
