"""ResNet (v1.5) in functional JAX — the reference's convnet config scaled up
(reference: examples/mnist/mnist.lua builds small convnets; BASELINE.json
config 2 is "ResNet-50 ImageNet, mpinn.synchronizeGradients data-parallel").

Design notes (TPU-first):
* NHWC layout — XLA's preferred conv layout on TPU; convs lower onto the MXU.
* ``dtype`` selects the compute precision; bfloat16 is the TPU default for
  the benchmark path (MXU-native), float32 for CPU tests.
* Static architecture (block kinds, strides) lives in a frozen
  :class:`Config`; parameter pytrees hold only arrays, so they pass cleanly
  through jit/grad/optimizers.  ``make_loss_fn(cfg)`` yields the
  ``loss_fn(params, batch)`` contract `AllReduceSGDEngine` expects.
* BatchNorm uses per-batch statistics in training mode.  Their scope follows
  the execution mode: under the eager rank-major engine the vmapped loss
  computes *per-replica* stats (local BN, like one-process-per-GPU in the
  reference); under the compiled engine the batch axis is globally sharded,
  so the same code lowers to *sync-BN* — XLA inserts small per-channel psums
  (negligible next to the gradient allreduce).  Running statistics for
  inference live in a separate ``state`` pytree.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ._common import num_params  # noqa: F401  (shared zoo helper)

Params = Dict[str, Any]

# depth -> (block kind, blocks per stage)
_CONFIGS = {
    18: ("basic", (2, 2, 2, 2)),
    34: ("basic", (3, 4, 6, 3)),
    50: ("bottleneck", (3, 4, 6, 3)),
    101: ("bottleneck", (3, 4, 23, 3)),
    152: ("bottleneck", (3, 8, 36, 3)),
}
_STAGE_WIDTHS = (64, 128, 256, 512)


@dataclasses.dataclass(frozen=True)
class Config:
    """Static architecture: hashable, safe to close over in jitted code."""

    kind: str                      # "basic" | "bottleneck"
    widths: Tuple[int, ...]        # width per block
    strides: Tuple[int, ...]       # stride per block
    stem_width: int
    n_classes: int
    in_channels: int
    # Space-to-depth stem (the MLPerf-ResNet TPU trick): compute the 7x7/2
    # stem conv as an arithmetically identical 4x4/1 conv on 2x2-block-to-
    # channel repacked input.  A C=3 conv wastes most MXU input lanes; the
    # repack quadruples channels and quarters the spatial extent.  Weights
    # stay in canonical (7, 7, C, W) form — the repack happens at trace time.
    stem_space_to_depth: bool = False

    @property
    def expansion(self) -> int:
        return 1 if self.kind == "basic" else 4


def config(depth: int = 50, n_classes: int = 1000, in_channels: int = 3,
           width_multiplier: float = 1.0,
           stem_space_to_depth: bool = False) -> Config:
    """``width_multiplier`` scales stage widths (tests use small fractions so
    the 8-device CPU mesh trains a ResNet-50-*shaped* net quickly)."""
    if depth not in _CONFIGS:
        raise ValueError(f"depth must be one of {sorted(_CONFIGS)}")
    kind, stages = _CONFIGS[depth]
    widths, strides = [], []
    for si, n_blocks in enumerate(stages):
        w = max(8, int(_STAGE_WIDTHS[si] * width_multiplier))
        for bi in range(n_blocks):
            widths.append(w)
            strides.append(2 if (si > 0 and bi == 0) else 1)
    return Config(
        kind=kind, widths=tuple(widths), strides=tuple(strides),
        stem_width=max(8, int(64 * width_multiplier)),
        n_classes=n_classes, in_channels=in_channels,
        stem_space_to_depth=stem_space_to_depth,
    )


# ----------------------------------------------------------------- primitives

def _conv_init(key, kh: int, kw: int, cin: int, cout: int, dtype) -> jax.Array:
    fan_in = kh * kw * cin
    w = jax.random.normal(key, (kh, kw, cin, cout), jnp.float32)
    return (w * np.sqrt(2.0 / fan_in)).astype(dtype)


def _bn_init(c: int, dtype) -> Params:
    return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}


def _bn_state(c: int) -> Params:
    return {"mean": jnp.zeros((c,), jnp.float32), "var": jnp.ones((c,), jnp.float32)}


# Every part of the step runs under a ``jax.named_scope`` (stem, conv, bn,
# residual, pool, fc_loss): metadata only, carried into the ``op_name`` of the
# compiled program's instructions (``transpose(...)`` round it on the backward
# pass), so that a device trace can be read by part (docs/observability.md).

@jax.named_scope("conv")
def _conv(x: jax.Array, w: jax.Array, stride: int = 1) -> jax.Array:
    return lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@jax.named_scope("stem")
def _stem_s2d(x: jax.Array, w7: jax.Array) -> jax.Array:
    """The 7x7 stride-2 SAME stem conv as an identical 4x4 stride-1 conv on
    space-to-depth input.

    Derivation (per spatial dim; SAME for k=7, s=2, even H pads (2, 3)):
    the output tap reads x[2i + di - 2] for di in [0, 7).  Writing
    di = 2U + a with U in [0, 4), a in {0, 1} gives x[2(i + U - 1) + a] —
    i.e. a 4-tap stride-1 conv with padding (1, 2) over the repacked array
    xs[p, (a, b, c)] = x[2p + a, 2q + b, c].  The 4x4 kernel is the 7x7
    padded to 8x8 (zeros at index 7) and regrouped the same way; the
    (a, b, c) channel orders of kernel and input match by construction.
    """
    N, H, W, C = x.shape
    if H % 2 or W % 2:
        raise ValueError(f"space-to-depth stem needs even H, W; got {H}x{W}")
    xs = (x.reshape(N, H // 2, 2, W // 2, 2, C)
           .transpose(0, 1, 3, 2, 4, 5)
           .reshape(N, H // 2, W // 2, 4 * C))
    kh, kw, cin, cout = w7.shape
    w8 = jnp.pad(w7, ((0, 8 - kh), (0, 8 - kw), (0, 0), (0, 0)))
    w4 = (w8.reshape(4, 2, 4, 2, cin, cout)     # (U, a, V, b, C, O)
             .transpose(0, 2, 1, 3, 4, 5)       # (U, V, a, b, C, O)
             .reshape(4, 4, 4 * cin, cout))
    return lax.conv_general_dilated(
        xs, w4, window_strides=(1, 1), padding=((1, 2), (1, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@jax.named_scope("bn")
def _batch_norm(x: jax.Array, p: Params, stats: Optional[Params], train: bool,
                eps: float = 1e-5, collect: Optional[list] = None) -> jax.Array:
    """Mixed-precision batch norm: statistics *accumulate* in f32 (via the
    reductions' accumulator dtype, E[x] and E[x^2]), but the normalization is
    a per-channel scale/shift applied in the compute dtype — no f32 copy of
    the activation is ever materialized.  On TPU this matters: an f32
    elementwise normalize doubles HBM traffic on every BN, and BN is ~25% of
    a bf16 ResNet-50 step (measured: 2310 -> 2799 img/s/chip on v5e)."""
    if train:
        mean = jnp.mean(x, axis=(0, 1, 2), dtype=jnp.float32)
        msq = jnp.mean(lax.square(x.astype(jnp.float32)), axis=(0, 1, 2))
        # E[x^2]-E[x]^2 can round negative in f32 when a channel is
        # near-constant at large magnitude; clamp so rsqrt stays finite
        # (jnp.var was non-negative by construction).
        var = jnp.maximum(msq - lax.square(mean), 0.0)
        if collect is not None:
            collect.append((mean, var))
    else:
        mean, var = stats["mean"], stats["var"]
    inv = lax.rsqrt(var + eps)
    w = p["scale"].astype(jnp.float32)
    scale = (inv * w).astype(x.dtype)
    shift = (p["bias"].astype(jnp.float32) - mean * inv * w).astype(x.dtype)
    return x * scale + shift


# --------------------------------------------------------------------- blocks

def _block_init(key, kind: str, cin: int, width: int, stride: int, dtype):
    if kind == "basic":
        k = jax.random.split(key, 3)
        cout = width
        p: Params = {
            "conv1": _conv_init(k[0], 3, 3, cin, width, dtype), "bn1": _bn_init(width, dtype),
            "conv2": _conv_init(k[1], 3, 3, width, width, dtype), "bn2": _bn_init(width, dtype),
        }
        s: Params = {"bn1": _bn_state(width), "bn2": _bn_state(width)}
    else:
        k = jax.random.split(key, 4)
        cout = width * 4
        p = {
            "conv1": _conv_init(k[0], 1, 1, cin, width, dtype), "bn1": _bn_init(width, dtype),
            "conv2": _conv_init(k[1], 3, 3, width, width, dtype), "bn2": _bn_init(width, dtype),
            "conv3": _conv_init(k[2], 1, 1, width, cout, dtype), "bn3": _bn_init(cout, dtype),
        }
        s = {"bn1": _bn_state(width), "bn2": _bn_state(width), "bn3": _bn_state(cout)}
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(k[-1], 1, 1, cin, cout, dtype)
        p["bn_proj"] = _bn_init(cout, dtype)
        s["bn_proj"] = _bn_state(cout)
    return p, s, cout


def _block_apply(kind: str, p: Params, s: Optional[Params], x: jax.Array,
                 stride: int, train: bool,
                 collect: Optional[list] = None) -> jax.Array:
    g = lambda name: s[name] if s is not None else None
    bn = lambda h, pn, sn: _batch_norm(h, p[pn], g(sn), train, collect=collect)
    if kind == "basic":
        out = _conv(x, p["conv1"], stride)
        out = jax.nn.relu(bn(out, "bn1", "bn1"))
        out = _conv(out, p["conv2"])
        out = bn(out, "bn2", "bn2")
    else:
        out = _conv(x, p["conv1"])
        out = jax.nn.relu(bn(out, "bn1", "bn1"))
        out = _conv(out, p["conv2"], stride)  # v1.5: stride on the 3x3
        out = jax.nn.relu(bn(out, "bn2", "bn2"))
        out = _conv(out, p["conv3"])
        out = bn(out, "bn3", "bn3")
    if "proj" in p:
        x = bn(_conv(x, p["proj"], stride), "bn_proj", "bn_proj")
    with jax.named_scope("residual"):
        return jax.nn.relu(out + x)


# ----------------------------------------------------------------- public API

def init(rng: jax.Array, cfg: Config, dtype=jnp.float32) -> Tuple[Params, Params]:
    """Build (params, state); ``state`` holds BN running statistics."""
    n_blocks = len(cfg.widths)
    keys = jax.random.split(rng, 2 + n_blocks)
    params: Params = {
        "stem_conv": _conv_init(keys[0], 7, 7, cfg.in_channels, cfg.stem_width, dtype),
        "stem_bn": _bn_init(cfg.stem_width, dtype),
        "blocks": [],
    }
    state: Params = {"stem_bn": _bn_state(cfg.stem_width), "blocks": []}

    cin = cfg.stem_width
    for bi in range(n_blocks):
        p, s, cin = _block_init(keys[1 + bi], cfg.kind, cin, cfg.widths[bi],
                                cfg.strides[bi], dtype)
        params["blocks"].append(p)
        state["blocks"].append(s)

    fc_w = jax.random.normal(keys[-1], (cin, cfg.n_classes), jnp.float32)
    params["fc_w"] = (fc_w * np.sqrt(1.0 / cin)).astype(dtype)
    params["fc_b"] = jnp.zeros((cfg.n_classes,), dtype)
    return params, state


def apply(cfg: Config, params: Params, x: jax.Array,
          state: Optional[Params] = None, train: bool = True,
          _collect: Optional[list] = None) -> jax.Array:
    """Forward pass; ``x`` is NHWC.  ``state`` (BN running stats) is required
    only when ``train=False``.  Logits come out in float32.  ``_collect``
    (internal) gathers per-BN batch statistics in traversal order for
    :func:`make_update_stats_fn`."""
    sblocks = state["blocks"] if state is not None else [None] * len(params["blocks"])

    if cfg.stem_space_to_depth:
        h = _stem_s2d(x, params["stem_conv"])
    else:
        with jax.named_scope("stem"):
            h = _conv(x, params["stem_conv"], stride=2)
    h = jax.nn.relu(_batch_norm(h, params["stem_bn"],
                                state["stem_bn"] if state else None, train,
                                collect=_collect))
    with jax.named_scope("pool"):
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")

    for p, s, stride in zip(params["blocks"], sblocks, cfg.strides):
        h = _block_apply(cfg.kind, p, s, h, stride, train, collect=_collect)

    with jax.named_scope("pool"):
        h = jnp.mean(h, axis=(1, 2))  # global average pool
    with jax.named_scope("fc_loss"):
        return (h.astype(jnp.float32) @ params["fc_w"].astype(jnp.float32)
                + params["fc_b"].astype(jnp.float32))


def make_update_stats_fn(cfg: Config, momentum: float = 0.9):
    """Jittable ``(params, state, x) -> new_state``: one training-mode
    forward whose per-BN batch statistics EMA-update the running stats.
    Call periodically (or every step) to keep ``state`` usable for
    ``train=False`` inference."""

    def ema(old, new):
        return momentum * old + (1.0 - momentum) * new

    def update(params: Params, state: Params, x: jax.Array) -> Params:
        collected: list = []
        apply(cfg, params, x, train=True, _collect=collected)
        it = iter(collected)

        def fold(stats: Params) -> Params:
            mean, var = next(it)
            return {"mean": ema(stats["mean"], mean), "var": ema(stats["var"], var)}

        # Same traversal order as apply: stem, then per block bn1, bn2,
        # (bn3), (bn_proj).
        new_state: Params = {"stem_bn": fold(state["stem_bn"]), "blocks": []}
        for sb in state["blocks"]:
            nb = {}
            for key in ("bn1", "bn2", "bn3", "bn_proj"):
                if key in sb:
                    nb[key] = fold(sb[key])
            new_state["blocks"].append(nb)
        remaining = sum(1 for _ in it)
        assert remaining == 0, f"stats traversal mismatch: {remaining} left"
        return new_state

    return update


def make_loss_fn(cfg: Config):
    """Mean softmax cross-entropy in training mode (local BN) — the
    ``loss_fn(params, batch)`` the engine consumes."""

    def loss_fn(params: Params, batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
        x, y = batch
        logits = apply(cfg, params, x, train=True)
        with jax.named_scope("fc_loss"):
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))

    return loss_fn


def make_accuracy_fn(cfg: Config, state: Optional[Params] = None):
    """Accuracy metric for ``engine.test``.  With ``state`` (BN running
    stats from :func:`make_update_stats_fn`) evaluation runs in inference
    mode (``train=False``) — the number that generalizes.  Without it the
    only legal mode is batch-stats normalization (``train=True``), whose
    result depends on eval-batch composition; use it for quick smoke
    checks only."""

    def accuracy(params: Params, batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
        x, y = batch
        logits = apply(cfg, params, x, state=state, train=state is None)
        return jnp.mean(jnp.argmax(logits, axis=-1) == y)

    return accuracy


def flops_per_image(cfg: Config, image: int = 224) -> int:
    """Analytic forward FLOPs per image (multiply-accumulate = 2 FLOPs),
    convolutions + final FC only — the same accounting the bench roofline
    uses (BN/ReLU/pool are bandwidth-bound and <1% of FLOPs).  A training
    step is ~3x this (forward + two backward matmul passes)."""

    def conv(h: int, w: int, kh: int, kw: int, cin: int, cout: int,
             stride: int) -> Tuple[int, int, int]:
        ho = -(-h // stride)  # SAME padding
        wo = -(-w // stride)
        return 2 * ho * wo * kh * kw * cin * cout, ho, wo

    total = 0
    fl, h, w = conv(image, image, 7, 7, cfg.in_channels, cfg.stem_width, 2)
    total += fl
    h, w = -(-h // 2), -(-w // 2)  # 3x3/2 maxpool
    cin = cfg.stem_width
    for width, stride in zip(cfg.widths, cfg.strides):
        if cfg.kind == "basic":
            fl, h, w = conv(h, w, 3, 3, cin, width, stride)
            total += fl
            fl, _, _ = conv(h, w, 3, 3, width, width, 1)
            total += fl
            cout = width
        else:
            fl, _, _ = conv(h, w, 1, 1, cin, width, 1)
            total += fl
            fl, h, w = conv(h, w, 3, 3, width, width, stride)
            total += fl
            fl, _, _ = conv(h, w, 1, 1, width, width * 4, 1)
            total += fl
            cout = width * 4
        if stride != 1 or cin != cout:
            total += 2 * h * w * cin * cout  # 1x1 projection at output res
        cin = cout
    total += 2 * cin * cfg.n_classes
    return total
