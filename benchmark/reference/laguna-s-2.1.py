"""Plain reference for the `laguna-s-2.1` configuration: forward, loss and
gradients in straightforward `jax.numpy`, float32 at the highest matmul
precision, softmax attention over whole rows of scores (the window a mask on
them) a block of rows at a time, the experts one after the other over all
tokens, the whole logits.  Nothing here imports the program; its parameter
pytree comes in as data (bfloat16 leaves are upcast where they are used).

Written from the published `config.json` of `poolside/Laguna-S-2.1`
(`model_type` `laguna`) as the issue that asked for this configuration wrote
the equations down, with Peng et al. arXiv:2309.00071 (YaRN) as
`transformers`' `_compute_yarn_parameters` computes it, Qiu et al.
arXiv:2505.06708 (the headwise gate on the attention output), DeepSeek-V3's
report arXiv:2412.19437 section 2.1.2 (sigmoid scores, normalised and scaled),
Su et al. arXiv:2104.09864 (rotary embedding), Zhang & Sennrich
arXiv:1910.07467 (RMSNorm), Shazeer arXiv:2002.05202 (SwiGLU), Loshchilov &
Hutter arXiv:1711.05101 (AdamW).

    block l (0-based):  h += Mixer_l(RMSNorm(h));  h += FFN_l(RMSNorm(h))
    eps 1e-6; final RMSNorm, untied head.

    Mixer, x the normed input, heads of d = 128, 8 KV heads; layer l is full
    (`layer_types[l]` "full_attention": H = 48) or sliding (H = 72, W = 512),
    H from `num_attention_heads_per_layer[l]`:
      q = x W_q -> H x d;  k = x W_k, v = x W_v -> 8 x d  (no bias);
      q, k = rot_l(q, p), rot_l(k, p);  head j reads KV head j // (H / 8);
      o_j = softmax(q_j k^T d^-1/2 over the keys i - W < c <= i on a sliding
        layer, c <= i on a full one) v   (row i sees itself and the W - 1
        keys before it: 512 keys, the `transformers` mask convention);
      g = sigmoid(x W_g), W_g: D x H, one number a head and token;
      out = concat(g_j o_j) W_o                       (H d -> 3072).
    rot on a sliding layer: all 128 channels, adjacent pairs (2i, 2i + 1) by
      the angle p * 10000^(-2i / 128).  On a full layer: the first 64 channels
      (`partial_rotary_factor` 0.5), the other 64 pass; over dim = 64, base
      500,000: f_i = base^(-2i / 64); c(n) = 64 ln(8192 / (2 pi n)) / (2 ln
      base); low = floor(c(32)) = 9, high = ceil(c(1)) = 18; ramp_i =
      clip((i - low) / (high - low), 0, 1); inv_freq_i = f_i (1 - ramp_i) +
      f_i / 128 ramp_i; cos and sin times `attention_factor` (1.4852...), so
      the rotated halves of q and k are each scaled and the passed ones not.
    FFN: layer 0 (`mlp_layer_types[0]` "dense") a SwiGLU of 12,288.  Every
      other: s = sigmoid(x W_r) over all 256 experts (no cap on the logits,
      no selection bias); the 10 largest; w = s[chosen] / sum s[chosen] * 2.5;
      y = sum_j w_j Expert_{e_j}(x) + Shared(x), each a SwiGLU of 1024.  This
      chip holds `num_experts` of the published experts, ids from
      `experts_held_first`: the sum runs over the chosen experts held here,
      the weights still normalised over all 10.  What the absent experts
      would add is left out here as in the program.
    loss = mean next-token NLL over the `vocab_size` rows held here.

The per-layer lists of the file keep their published 48 entries; the first
`num_hidden_layers` of them are the layers that run.

What the runner sets against the system (`TOLERANCE` and `STEP_TOLERANCE`,
below, say why each limit): `loss_and_grads` on a small sample (the loss, the
logits, every leaf's gradient norm, `wg`'s among them, through
`compare.check`); `loss_only` on a whole timed batch against the loss the
timed step returns, and its routed units an expert against the program's;
`adamw_first_step` on the gradient the timed step took against the weights
it returns.

Departures and assumptions are the configuration file's `assumed`.  Two
devices here are for memory alone and change no arithmetic: a
`jax.checkpoint` round each block, and `lax.map` over the rows of the scores
and over the experts.
"""

import math

import jax
import jax.numpy as jnp

# Why these limits.  The system multiplies in bfloat16 with float32
# accumulation, keeps its residual stream in bfloat16, rotates in float32 and
# rounds the result, runs the flash kernels' softmax, the gate's sigmoid and
# the router's scores in float32; the reference does all of it in float32 at
# "highest" precision.  Measured on TPU v5 lite at the published widths on 1
# x 2048 tokens (the timed batch's row: four windows deep, four chunks of the
# head), 23 readings over as many seeds (my chip runs, PR 40; PERF.md section
# 6).  Five controls, each through `compare.check` with these limits and,
# where it has a window, the runner's `band_rows_wrong`, each `ok` false: (a)
# the reference with its weights rounded to float8 e4m3 (3 mantissa bits by
# `lax.reduce_precision`, scaled by each tensor's largest entry; the nearest
# precision below bfloat16) in the program's place; the program with (b) the
# window left out, (c) a window of 513 keys, (d) the gate left out, (e) the
# plain rotation on the full layers.
# logits: relative L2 error of each token's 12,544 logits, 90th percentile
#   over the 2,048 rows: 0.0246 to 0.0255.  (a) reads 0.330 and 0.323, (b)
#   0.204, (d) 1.01, (e) 1.30; (c) 0.0260, as configured: one key in 513 is
#   under the rounding, and `band_rows_wrong` is what tells it.  The limit is
#   twice the largest reading (they lie within 4% of each other) and under a
#   sixth of (a)'s.
# loss: 9.6e-7 to 1.63e-4 (the first reading 1.09e-4); the limit is the
#   accepted cells', 4.6 times the first reading and 3.1 times the largest.
#   (a) reads 2.9e-4 and 8.3e-4, (b) 7.4e-5: a mean over 26 M logits forgives
#   rounding and a window alike; (d) reads 4.0e-3, (e) 3.8e-4.
# gradient norm: 4.7e-6 to 2.9e-4.  The precision hardly moves it ((a) reads
#   6.9e-4 and 1.3e-4), so the limit, the accepted cells', lies between the
#   readings and what a fault reads, (d) 0.076 and (e) 0.23; (b) reads
#   3.4e-3, under it.
# leaf norms: the gradient norm of every leaf, a run's leaves layer by layer:
#   0.0068 to 0.0309 (median 0.0159), the worst leaf a router's on all but
#   one reading: its gradient is not continuous in the activations (a token
#   whose tenth and eleventh scores lie within bfloat16's rounding goes to
#   another expert), and with 8 of 256 experts held it rests on a thirty-
#   second of the units.  (a) reads 0.066 and 0.074, (b) 0.140 (`wk` of a
#   sliding layer), (e) 0.68, (d) 1.0 (`wg`, left out).  The limit is 3.9
#   times the largest reading and 7.5 medians (the Kimi Linear cell's worst
#   leaf, a router on a thirty-second too, read 2.7 times its median over 63
#   seeds) and tells (b), (d) and (e).
TOLERANCE = {
    "logits_rel_p90": 5e-2,
    "loss_rel": 5e-4,
    "grad_norm_rel": 6e-3,
    "leaf_norm_rel_max": 1.2e-1,
}
# The timed step itself, its first call from the seeded weights on the first
# timed batch (1 x 16,384 tokens, AdamW; my chip runs, PR 40).
# loss: the step's against `loss_only`'s: 1.9e-6 to 8.7e-5 over 20 seeds; the
#   limit is the check sample's, 5.7 times the largest (the Kimi Linear and
#   GLM cells' 1.5e-4 would leave the first reading, 5.2e-5, under three
#   times of room, and the largest under two).
# change: of every leaf's change (a run's by layer) the norm, against
#   `adamw_first_step` on the gradient the step itself took, read from the
#   first moment it leaves (`mu / (1 - b1)`; the moments are bfloat16 here):
#   9.3e-4 to 1.36e-3 over 20 seeds.  With the step's own gradient both sides
#   agree on nearly every sign; at this rate an update is under half a unit
#   in the last place of most weights, so what is left is which of them it
#   moves. The limit lies between the readings and 1, which is what a state
#   left unchanged reads, with the room above the readings (7 times the
#   largest): it sees a wrong rule (a rate off by a hundredth 0.01, a leaf
#   skipped 1.0: `wg` left out of the step), not a gradient of the wrong
#   size: that is `TOLERANCE`'s.
# routing: the routed units of each of the 256 experts of the four expert
#   layers on that batch at the seeded weights, the program's router
#   (`llama.expert_unit_counts`, float32 scores on bfloat16 activations)
#   against this file's (`routed_units`): the units that go to another
#   expert, half the summed difference over a layer's k * T, the largest
#   layer: 0.0031 to 0.0038.  One expert a token too few is 0.05; the limit
#   is 4 times the largest reading and under a third of that.
# band: the rows of one sliding layer's logits that change, to the bit, when
#   two tokens of the check sample change (the runner's `band_rows_wrong`),
#   the program's against this file's: 0 rows differ, or the two windows are
#   not one.  No rounding enters (a key outside a row's band weighs exactly 0
#   on both sides), so it sees what the norms above cannot: a window of 513
#   keys reads 2, one row for each token; the window left out 765.
STEP_TOLERANCE = {
    "step_loss_rel": 5e-4,
    "update_norm_rel_max": 1e-2,
    "routing_l1_max": 1.5e-2,
    "band_rows_wrong": 0,
}

_LAYER_LEAVES = (
    "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "wg", "router",
    "shared_gate", "shared_up", "shared_down", "w_gate", "w_up", "w_down")
# Every leaf of a run keeps its layer axis: compare.py takes the gradient norm
# of each layer's part apart (a layer's held experts together).  As many runs
# as the published 48 layers have.
LEAF_AXES = {f"layers/{run}/{name}": 1 for run in range(24)
             for name in _LAYER_LEAVES}


def _f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


# ----------------------------------------------------------------- rotations

def yarn_range(rope, dim):
    """(low, high): the channel pairs between which YaRN's ramp runs."""
    base = rope["rope_theta"]
    original = rope["original_max_position_embeddings"]
    at = lambda turns: (dim * math.log(original / (2 * math.pi * turns))
                        / (2 * math.log(base)))
    return (max(math.floor(at(rope["beta_fast"])), 0),
            min(math.ceil(at(rope["beta_slow"])), dim - 1))


def inverse_frequencies(rope, head_dim):
    """(the dim // 2 inverse frequencies, the factor on cos and sin) of one
    entry of the file's `rope_parameters`, over its rotated channels `dim =
    head_dim * partial_rotary_factor`."""
    dim = int(head_dim * rope["partial_rotary_factor"])
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = rope["rope_theta"] ** (-2 * i / dim)
    if rope["rope_type"] == "default":
        return plain, 1.0
    assert rope["rope_type"] == "yarn", rope["rope_type"]
    low, high = yarn_range(rope, dim)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (plain * (1 - ramp) + plain / rope["factor"] * ramp,
            rope["attention_factor"])


def rotate(x, positions, rope):
    """x: (L, n, d), positions: (L,): channels (2i, 2i + 1) of the first
    `dim` of every one of the n heads turned by positions * inv_freq_i, cos
    and sin times the entry's factor; the channels after them pass."""
    L, n, d = x.shape
    inv_freq, factor = inverse_frequencies(rope, d)
    dim = 2 * inv_freq.shape[0]
    angle = _f32(positions)[:, None] * inv_freq
    cos, sin = (factor * jnp.cos(angle)[:, None, :],
                factor * jnp.sin(angle)[:, None, :])
    pairs = x[..., :dim].reshape(L, n, dim // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1).reshape(L, n, dim)
    return jnp.concatenate([turned, x[..., dim:]], axis=-1)


# -------------------------------------------------------------------- mixer

def attention(q, k, v, window=None, rows=128):
    """q: (L, H, d); k, v: (L, KV, d): softmax over the whole row of scores,
    `rows` query rows at a time; row i sees the keys c <= i, and with a
    `window` of them those with c > i - window."""
    L, H, d = q.shape
    group = H // k.shape[1]
    rows = min(rows, L)
    at = jnp.arange(L)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=0)
        qb = qb.reshape(rows, H // group, group, d)
        s = jnp.einsum("qcgd,kcd->cgqk", qb, k) / jnp.sqrt(jnp.float32(d))
        row = (start + jnp.arange(rows))[:, None]
        seen = at[None, :] <= row
        if window is not None:
            seen &= at[None, :] > row - window
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("cgqk,kcd->qcgd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(0, L, rows))
    return o.reshape(L, H, d)


def mixer(cfg, kind, lp, x, positions=None):
    """x: (L, D), one sequence, already normed; `kind` the layer's entry of
    `layer_types`; positions (L,), 0.. where not given."""
    L = x.shape[0]
    d, KV = cfg["head_dim"], cfg["num_key_value_heads"]
    H = lp["wq"].shape[-1] // d
    rope = cfg["rope_parameters"][kind]
    positions = jnp.arange(L) if positions is None else positions
    q = rotate((x @ _f32(lp["wq"])).reshape(L, H, d), positions, rope)
    k = rotate((x @ _f32(lp["wk"])).reshape(L, KV, d), positions, rope)
    v = (x @ _f32(lp["wv"])).reshape(L, KV, d)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    o = attention(q, k, v, window)
    gate = jax.nn.sigmoid(x @ _f32(lp["wg"]))               # (L, H)
    return (o * gate[..., None]).reshape(L, H * d) @ _f32(lp["wo"])


# --------------------------------------------------------------------- FFNs

def _choice(cfg, lp, x):
    """x: (T, D) -> the sigmoid scores (T, E) and the chosen experts (T, k)."""
    scores = jax.nn.sigmoid(x @ _f32(lp["router"]))
    return scores, jax.lax.top_k(scores, cfg["num_experts_per_tok"])[1]


def experts_ffn(cfg, lp, x):
    """x: (T, D).  The held experts' part of the routed sum, plus the shared
    expert."""
    E = cfg["published"]["num_experts"]
    first, held = cfg["experts_held_first"], cfg["num_experts"]
    scores, chosen = _choice(cfg, lp, x)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * cfg["moe_routed_scaling_factor"]
    # (T, E): a token's weight for each expert, 0 where it was not chosen.
    weight = jnp.sum(jax.nn.one_hot(chosen, E) * w[..., None], axis=1)

    def one(args):
        e, w_gate, w_up, w_down = args
        return weight[:, e, None] * swiglu(x, w_gate, w_up, w_down)

    y = jnp.sum(jax.lax.map(one, (first + jnp.arange(held), lp["w_gate"],
                                  lp["w_up"], lp["w_down"])), axis=0)
    return y + swiglu(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])


def routed_units(cfg, lp, x):
    """x: (T, D) -> (E,) int32: the routed units of each published expert,
    k * T in all, by the choice `experts_ffn` makes."""
    scores, chosen = _choice(cfg, lp, x)
    return jnp.zeros(scores.shape[1], jnp.int32).at[chosen.reshape(-1)].add(1)


def block(cfg, number, lp, h):
    """Block `number` (0-based) on h: (B, L, D); with the result the routed
    units of its experts (`routed_units`; None for a dense block)."""
    eps = cfg["rms_norm_eps"]
    kind = cfg["layer_types"][number]
    assert lp["wq"].shape[-1] == (cfg["num_attention_heads_per_layer"][number]
                                  * cfg["head_dim"])
    h = h + jax.vmap(lambda x: mixer(
        cfg, kind, lp, rms_norm(x, lp["attn_norm"], eps)))(h)
    x = rms_norm(h, lp["mlp_norm"], eps)
    if cfg["mlp_layer_types"][number] == "dense":
        return h + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    B, L, D = x.shape
    x = x.reshape(B * L, D)
    return (h + experts_ffn(cfg, lp, x).reshape(B, L, D),
            routed_units(cfg, lp, x))


def hidden(cfg, params, tokens):
    """tokens: (B, L) int32 -> the final normed states (B, L, D) float32 and
    the routed units of the expert blocks (blocks, E).  `params["layers"]` is
    the program's tuple of runs, each leaf led by the run's layers."""
    h = _f32(params["embed"])[tokens]
    number, units = 0, []
    for stack in params["layers"]:
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            h, routed = jax.checkpoint(
                lambda h, lp, number=number: block(cfg, number, lp, h))(
                    h, jax.tree.map(lambda a: a[i], stack))
            if routed is not None:
                units.append(routed)
            number += 1
    assert number == cfg["num_hidden_layers"]
    E = cfg["published"]["num_experts"]
    return (rms_norm(h, params["norm"], cfg["rms_norm_eps"]),
            jnp.stack(units) if units else jnp.zeros((0, E), jnp.int32))


def nll_of(logits, targets):
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                targets[..., None], axis=-1)[..., 0]


def loss_fn(cfg, params, tokens, targets):
    """(loss, logits)."""
    logits = hidden(cfg, params, tokens)[0] @ _f32(params["head"])
    return jnp.mean(nll_of(logits, targets)), logits


def loss_and_grads(cfg, params, sample):
    """`sample = (tokens, targets)` -> (loss, logits, gradient pytree): what
    `compare.py` sets against the system's."""
    tokens, targets = sample
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets), has_aux=True)(params)
    return loss, logits, grads


def loss_only(cfg, params, sample, rows=512):
    """The loss of a batch too large for its logits to be held at once: the
    same forward pass, and the NLL of `rows` positions at a time.  With it
    the expert blocks' routed units on that batch (`hidden`)."""
    tokens, targets = sample
    B, L = tokens.shape
    with jax.default_matmul_precision("highest"):
        h, units = hidden(cfg, params, tokens)
        head = _f32(params["head"])
        chunks = lambda a: a.reshape(B * L // rows, rows, *a.shape[2:])
        nll = jax.lax.map(lambda c: nll_of(c[0] @ head, c[1]),
                          (chunks(h), chunks(targets)))
        return jnp.mean(nll), units


def adamw_first_step(params, grads, opt):
    """The weights after AdamW's first step from zero moments: with the bias
    corrections the moments are g and g * g, so the step is
    -lr * (g / (|g| + eps) + weight_decay * w), in float32, every leaf alike
    (the gate's `wg` is a matrix like the others); the update is rounded to
    the weights' type and added there, as a trainer without master weights
    does."""
    b1, b2 = opt["b1"], opt["b2"]

    def leaf(w, g):
        w32, g32 = _f32(w), _f32(g)
        m = (1 - b1) * g32 / (1 - b1)
        v = (1 - b2) * g32 * g32 / (1 - b2)
        u = -opt["learning_rate"] * (m / (jnp.sqrt(v) + opt["eps"])
                                     + opt["weight_decay"] * w32)
        return w + u.astype(w.dtype)

    return jax.tree.map(leaf, params, grads)
