"""`flops/` against hand counts."""

import json
import os

import pytest

import harness

CONFIGS = os.path.join(harness.HERE, "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, name + ".json")) as fh:
        return json.load(fh)


def test_resnet50_forward_is_8_2_gflop():
    flops = harness.load_module("flops", "resnet50")
    cfg = _cfg("resnet50")
    forward, stem = flops.forward_flops_per_image(cfg)
    # torchvision's ResNet-50 v1.5: 4.09 G multiply-adds an image.
    assert forward == pytest.approx(2 * 4.09e9, rel=0.005)
    # The stem by hand: 112 x 112 outputs, 7 x 7 x 3 taps, 64 filters.
    assert stem == 2 * 112 * 112 * 7 * 7 * 3 * 64
    # The last layer by hand.
    assert forward - flops.forward_flops_per_image(
        dict(cfg, num_classes=0))[0] == 2 * 2048 * 1000
    assert flops.required_flops_per_sample(cfg, {}) == 3 * forward - stem


def test_resnet50_count_is_the_programs():
    """The copy agrees with the original it was copied from."""
    from torchmpi_tpu.models import resnet

    flops = harness.load_module("flops", "resnet50")
    assert flops.forward_flops_per_image(_cfg("resnet50"))[0] == \
        resnet.flops_per_image(resnet.config(depth=50), image=224)


def test_mixtral_parameters_as_published():
    flops = harness.load_module("flops", "mixtral-8x7b")
    cfg = dict(_cfg("mixtral-8x7b"), num_hidden_layers=32)
    total, active = flops.parameters(cfg)
    assert total == pytest.approx(46.7e9, rel=0.002)     # "47B"
    assert active == pytest.approx(12.9e9, rel=0.002)    # "13B active"
    one = flops.parameters(_cfg("mixtral-8x7b"))[0]
    assert one == pytest.approx(1.713e9, rel=0.001)      # the cell's one layer


@pytest.mark.parametrize("seq_len, gflop, scores_share",
                         [(4096, 3.253, 0.031), (16384, 3.555, 0.113)])
def test_mixtral_required_flops_a_token(seq_len, gflop, scores_share):
    flops = harness.load_module("flops", "mixtral-8x7b")
    cfg = _cfg("mixtral-8x7b")
    parts = flops.forward_flops_per_token(cfg, seq_len)
    # By hand: two experts of three 4096 x 14336 products.
    assert parts["experts"] == 2 * 3 * 2 * 4096 * 14336
    assert parts["head"] == 2 * 4096 * 32000
    assert parts["attention_scores"] == 2 * 32 * 128 * (seq_len + 1)
    total = flops.required_flops_per_sample(cfg, {"seq_len": seq_len})
    assert total == 3 * sum(parts.values())
    assert total / 1e9 == pytest.approx(gflop, abs=0.001)
    assert parts["attention_scores"] / sum(parts.values()) == \
        pytest.approx(scores_share, abs=0.001)


def test_flash_required_is_causal_and_bound_by_flops():
    flops = harness.load_module("flops", "mixtral-8x7b")
    cfg = _cfg("mixtral-8x7b")
    peaks = harness.load_json("peaks.json")["TPU v5 lite"]
    for batch, seq_len in ((4, 4096), (1, 16384)):
        f, b = flops.flash_required(cfg, {"batch": batch, "seq_len": seq_len})
        pairs = batch * 32 * seq_len * (seq_len + 1) // 2
        assert f == 6 * 2 * 128 * pairs
        # Q, O, dO, dQ at 32 heads and K, V, dK, dV at 8, twice over.
        assert b == batch * seq_len * 128 * 2 * (6 * 32 + 6 * 8)
        assert f / peaks["bf16_flops_per_s"] > 5 * b / peaks["hbm_bytes_per_s"]
