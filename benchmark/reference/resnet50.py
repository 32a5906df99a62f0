"""Plain reference for the `resnet50` configuration: forward, loss and
gradients in straightforward `jax.numpy`, float32.  A convolution is XLA's
own, in float32 at the highest precision, on the canonical 7x7 and 3x3
weights (not the program's space-to-depth repack); `tests/test_reference.py`
holds it to a sum over the kernel's taps written out by hand.  (Written out
that way here, the reference took 95 s to compile and 8 s of every run.)
Batch norm is the textbook two-pass form, not the program's E[x^2] - E[x]^2
scale-and-shift.  Nothing here imports the program; its parameter pytree
comes in as data and bf16 leaves are upcast where they are used.

Written from: He et al., "Deep Residual Learning for Image Recognition",
arXiv:1512.03385: Table 1, 50-layer column (7x7/2 stem of 64, 3x3/2 max
pool, bottleneck stages of 3, 4, 6, 3 blocks at widths 64..512 with four
times as many output channels, global average pool, 1000-way FC), section
3.3 option B (1x1 projection shortcuts where the shape changes), section
3.4 (batch normalisation after each convolution and before the activation);
Ioffe & Szegedy arXiv:1502.03167 for batch normalisation in training mode
(statistics of the batch, biased variance).

Departures, each where the paper is silent:
* v1.5: a stage's down-sampling stride sits on the bottleneck's 3x3 and not
  on its first 1x1 (Goyal et al. arXiv:1706.02677 section 5.3 note; what
  every current ResNet-50 result means, and what the program builds).
* Padding is TensorFlow's SAME: for an even input and stride 2 the extra row
  goes after the data ((2,3) for the 7x7, (0,1) for a 3x3).  torchvision pads
  (3,3) and (1,1).  The program pads SAME, and the choice moves no FLOP.
* The shortcut's projection is followed by batch normalisation, as in the
  authors' released model.
"""

import jax
import jax.numpy as jnp

# Why these tolerances.  The system runs 53 convolutions in bf16 (eps 2**-8)
# and normalises after each, so rounding noise neither grows nor dies along
# the depth; the reference is float32 at "highest".  Measured on TPU v5 lite
# over 28 seeds (PR 22; PERF.md section 6); each tolerance is two to three
# times the largest seen.
# logits: relative L2 error of each image's 1000 logits, 90th percentile
#   over the 16 images: 0.109 to 0.124.  That bf16 costs a tenth here is the
#   network's doing: the pooled features are positive and nearly alike, the
#   zero-mean FC weights cancel what they share, and the logits are what is
#   left.  An 8-bit float (sixteen times coarser) is past 1; so is a skipped
#   block.
# loss: at most 7.9e-3.  Gradient norm: at most 1.6e-2.
# leaf norms (every leaf apart, compare.py): 0.16 to 0.39, always a batch
#   norm scale or bias of 64 to 256 values: their gradients are differences
#   of large sums.  An untrained leaf is a difference of 1.
TOLERANCE = {
    "logits_rel_p90": 3e-1,
    "loss_rel": 2e-2,
    "grad_norm_rel": 5e-2,
    "leaf_norm_rel_max": 7.5e-1,
}

BN_EPS = 1e-5


def _f32(a):
    return a.astype(jnp.float32)


def conv(x, w, stride=1):
    """x: (N, H, W, C), w: (kh, kw, C, O): cross-correlation, SAME padding."""
    return jax.lax.conv_general_dilated(
        x, _f32(w), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def batch_norm(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * _f32(p["scale"]) \
        + _f32(p["bias"])


def max_pool_3x3_s2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME")


def bottleneck(p, x, stride):
    out = jax.nn.relu(batch_norm(conv(x, p["conv1"]), p["bn1"]))
    out = jax.nn.relu(batch_norm(conv(out, p["conv2"], stride), p["bn2"]))
    out = batch_norm(conv(out, p["conv3"]), p["bn3"])
    if "proj" in p:
        x = batch_norm(conv(x, p["proj"], stride), p["bn_proj"])
    return jax.nn.relu(out + x)


def forward(cfg, params, x):
    """x: (N, H, W, 3) -> logits (N, classes), batch statistics."""
    h = jax.nn.relu(batch_norm(conv(_f32(x), params["stem_conv"], 2),
                               params["stem_bn"]))
    h = max_pool_3x3_s2(h)
    strides = [2 if (stage > 0 and block == 0) else 1
               for stage, n in enumerate(cfg["stage_blocks"])
               for block in range(n)]
    for p, stride in zip(params["blocks"], strides, strict=True):
        h = bottleneck(p, h, stride)
    h = jnp.mean(h, axis=(1, 2))
    return h @ _f32(params["fc_w"]) + _f32(params["fc_b"])


def loss_and_grads(cfg, params, sample):
    """`sample = (images, labels)` -> (loss, logits, gradient pytree): what
    `compare.py` sets against the system's."""
    x, y = sample

    def loss_fn(p):
        logits = forward(cfg, p, x)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1)), logits

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    return loss, logits, grads
