"""The plain references against arithmetic written out by hand, small."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import harness


def _same_pad(size, k, stride):
    total = max((-(-size // stride) - 1) * stride + k - size, 0)
    return total // 2, total - total // 2


def conv_by_taps(x, w, stride):
    """A convolution as a sum over the kernel's taps of a strided slice times
    a matrix; TensorFlow's SAME padding."""
    kh, kw, _, _ = w.shape
    _, H, W, _ = x.shape
    ho, wo = -(-H // stride), -(-W // stride)
    xp = np.pad(x, ((0, 0), _same_pad(H, kh, stride),
                    _same_pad(W, kw, stride), (0, 0)))
    out = 0.0
    for i in range(kh):
        for j in range(kw):
            out = out + xp[:, i:i + (ho - 1) * stride + 1:stride,
                           j:j + (wo - 1) * stride + 1:stride, :] @ w[i, j]
    return out


@pytest.mark.parametrize("size, k, stride", [(8, 1, 1), (8, 3, 1), (8, 3, 2),
                                             (7, 3, 2), (16, 7, 2), (8, 1, 2)])
def test_resnet_reference_conv_is_a_sum_over_taps(size, k, stride):
    ref = harness.load_module("reference", "resnet50")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, size, size, 3), dtype=np.float32)
    w = rng.standard_normal((k, k, 3, 5), dtype=np.float32)
    np.testing.assert_allclose(ref.conv(jnp.asarray(x), jnp.asarray(w), stride),
                               conv_by_taps(x, w, stride), rtol=1e-5, atol=1e-5)


def test_resnet_reference_pool_and_norm_by_hand():
    ref = harness.load_module("reference", "resnet50")
    x = jnp.arange(16, dtype=jnp.float32).reshape(1, 4, 4, 1)
    # 3x3 windows, stride 2, SAME on 4 rows pads (0, 1): rows 0-2 and 2-3.
    assert ref.max_pool_3x3_s2(x).reshape(-1).tolist() == [10, 11, 14, 15]
    y = ref.batch_norm(x, {"scale": jnp.full((1,), 2.0),
                           "bias": jnp.full((1,), 1.0)})
    assert float(jnp.mean(y)) == pytest.approx(1.0, abs=1e-5)
    assert float(jnp.var(y)) == pytest.approx(4.0, rel=1e-4)


def test_mixtral_reference_capacity_is_gshards_queue():
    """6 tokens, 2 choices, 3 experts, capacity 4 x 1.0 = 4: first choices
    queue before second choices, each in token order."""
    ref = harness.load_module("reference", "mixtral-8x7b")
    cfg = {"num_local_experts": 3,
           "run": {"moe_group_size": 6, "capacity_factor": 1.0}}
    idx = jnp.array([[0, 1], [0, 1], [0, 2], [0, 1], [0, 1], [1, 0]])
    keep = np.asarray(ref.within_capacity(cfg, idx))
    # Expert 0: first choices of tokens 0-3 fill its 4 slots; token 4's first
    # choice and token 5's second are past capacity.  Expert 1: token 5's
    # first choice, then the second choices of tokens 0, 1, 3; token 4's is
    # the fifth.  Expert 2: one unit.
    assert keep.tolist() == [[1, 1], [1, 1], [1, 1], [1, 1], [0, 0], [1, 0]]


def test_mixtral_reference_is_dropless_when_capacity_allows():
    """With room for every unit the layer is the paper's
    sum_i softmax(top2(x Wg))_i SwiGLU_i(x), worked out token by token."""
    ref = harness.load_module("reference", "mixtral-8x7b")
    cfg = {"num_local_experts": 4, "num_experts_per_tok": 2,
           "run": {"moe_group_size": 8, "capacity_factor": 2.0}}
    rng = np.random.default_rng(1)
    x = rng.standard_normal((8, 6), dtype=np.float32)
    lp = {"router": rng.standard_normal((6, 4), dtype=np.float32),
          "w_gate": rng.standard_normal((4, 6, 5), dtype=np.float32),
          "w_up": rng.standard_normal((4, 6, 5), dtype=np.float32),
          "w_down": rng.standard_normal((4, 5, 6), dtype=np.float32)}
    y, aux = ref.moe(cfg, jax.tree.map(jnp.asarray, lp), jnp.asarray(x))
    want = np.zeros_like(x)
    for t in range(8):
        logits = x[t] @ lp["router"]
        top = np.argsort(-logits)[:2]
        g = np.exp(logits[top] - logits[top].max())
        g /= g.sum()
        for gi, e in zip(g, top):
            a, b = x[t] @ lp["w_gate"][e], x[t] @ lp["w_up"][e]
            want[t] += gi * ((a / (1 + np.exp(-a)) * b) @ lp["w_down"][e])
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-4)
    assert float(aux) > 0
