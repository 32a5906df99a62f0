"""Plain reference for the `mellum2-12b-a2.5b` configuration: forward, loss and
gradients in straightforward `jax.numpy`, float32 at the highest matmul
precision, on ONE device: softmax attention over whole rows of scores (the
band and the causal triangle masks on them) a block of rows at a time, all 64
experts one after the other over all the tokens (a masked loop: every expert
meets every token and the router's weight, 0 where the token did not choose
it, decides), the loss a block of rows at a time.  No kernel, no sorting, no
exchange: what the program spreads over four chips is one sum here.  Nothing
here imports the program; its parameter pytree comes in as data (bfloat16
leaves are upcast where they are used).

Written from the published `config.json` of
`JetBrains/Mellum2-12B-A2.5B-Instruct` (`model_type` `mellum`) as the issue
that asked for this configuration wrote the equations down, with Peng et al.
arXiv:2309.00071 (YaRN) as `transformers`' `_compute_yarn_parameters`
computes it, Su et al. arXiv:2104.09864 (rotary embedding), Zhang & Sennrich
arXiv:1910.07467 (RMSNorm), Shazeer arXiv:2002.05202 (SwiGLU), Shazeer et al.
arXiv:1701.06538 and Fedus et al. arXiv:2101.03961 (top-k softmax routing),
Loshchilov & Hutter arXiv:1711.05101 (AdamW).

    block l (0-based):  h += Mixer_l(RMSNorm(h));  h += FFN_l(RMSNorm(h))
    eps 1e-6; final RMSNorm, untied head of 98,304 rows.

    Mixer, x the normed input, 32 heads of d = 128 over 4 KV heads, no bias,
    no QK-norm, no gate; layer l is sliding (`layer_types[l]`
    "sliding_attention": W = 1,024) or full, the full layer LAST of every four:
      q = x W_q -> 32 x d;  k = x W_k, v = x W_v -> 4 x d;
      q, k = rot_l(q, p), rot_l(k, p);  head j reads KV head j // 8;
      o_j = softmax(q_j k^T d^-1/2 over the keys i - W < c <= i on a sliding
        layer, c <= i on a full one) v   (row i sees itself and the W - 1
        keys before it: 1,024 keys, the `transformers` mask convention);
      out = concat(o_j) W_o                           (4096 -> 2304).
    rot on a sliding layer (`rope_type` "default"): all 128 channels, adjacent
      pairs (2i, 2i + 1) by the angle p * 500000^(-2i / 128).  On a full layer
      (`rope_type` "yarn"), all 128 channels too: f_i = 500000^(-2i / 128);
      c(n) = 128 ln(8192 / (2 pi n)) / (2 ln 500000); low = floor(c(32)) = 18,
      high = ceil(c(1)) = 35; ramp_i = clip((i - low) / (high - low), 0, 1);
      inv_freq_i = f_i (1 - ramp_i) + f_i / 16 ramp_i; cos and sin times
      `attention_factor` (1.2772... = 0.1 ln 16 + 1), so q and k are each
      scaled by it.
    FFN, every layer (`mlp_layer_types` all "sparse"): p = softmax(x W_r) over
      all 64 experts in float32; the 8 largest; w = p[chosen] / sum p[chosen]
      (`norm_topk_prob`); y = sum_j w_j Expert_{e_j}(x), each a SwiGLU of 896.
      No shared expert, no dense layer, no capacity, no auxiliary loss.
    loss = mean next-token NLL over the 98,304 rows.

The per-layer lists of the file keep their published 28 entries; the first
`num_hidden_layers` of them are the layers that run.  A layer whose
`mlp_layer_types` entry is "dense" (none of this model's; the runner's band
probe builds one sliding layer with a thin SwiGLU) runs a SwiGLU of its
`w_gate`'s width.

What the runner sets against the system (`TOLERANCE` and `STEP_TOLERANCE`,
below, say why each limit): `loss_and_grads` on a small sample (the loss, the
logits of every `logit_stride`-th row, every leaf's gradient norm, through
`compare.check`'s formulas); `loss_only` on a whole timed batch against the
loss the timed step returns, and its routed units an expert against the
program's; `adamw_first_step` on the gradient the timed step took against the
weights it returns.

Departures and assumptions are the configuration file's `assumed`: no
multi-token-prediction module (the catalog's `described_as` names an "MTP
head", the `config` has no key for one), the rotary pairing, the context.
Devices here for memory alone, which change no arithmetic: a `jax.checkpoint`
round each block, each block of rows of the scores, each expert of the loop
and each block of rows of the loss, and `lax.map` or `lax.scan` over them.
"""

import math

import jax
import jax.numpy as jnp

# Why these limits.  The system multiplies in bfloat16 with float32
# accumulation, keeps its residual stream in bfloat16, rotates in float32 and
# rounds the result, runs the flash kernels' softmax and the router's softmax
# in float32, sends bfloat16 rows through the exchange and adds a token's
# eight results in float32; the reference does all of it in float32 at
# "highest" precision on one device.  Measured on four chips of TPU v5 lite
# at the published widths on 4 x 2,048 tokens, a row on every chip, so the
# compared step crossed the exchange: four readings on four seeds (my chip
# runs, PR 44; PERF.md section 6).  The control is the reference with its
# weights rounded to float8 e4m3 (3 mantissa bits by `lax.reduce_precision`,
# scaled by each tensor's largest entry: the nearest precision below
# bfloat16) in the program's place, three seeds on one chip: it reads `ok`
# false by the logits on every seed, and by the logits ALONE: it passes the
# loss, passes the gradient norm on one seed of three, and its leaf norms lie
# inside the sound readings.
# logits: relative L2 error of each sampled token's 98,304 logits (every
#   `logit_stride`-th row of the sample), 90th percentile.  As in the OLMoE
#   cell, whose router this is (top 8 of 64), a token's eighth and ninth
#   probabilities are often closer than the bfloat16 noise of the router's
#   input, such a token meets one other expert in the system than here, and a
#   row with a flip is off by 0.05 to 0.1 where a row without is off by 0.01;
#   with four such layers and every expert present more than a tenth of the
#   rows have one, so the 90th percentile lies among them (the Laguna cell's
#   0.05 holds where 8 of 256 experts are held and a flip mostly meets an
#   absent expert).  Shown, not only inferred (PR 44's second session, on the
#   CPU at the published widths on this sample, the program's bfloat16 forward
#   pass with full attention in the flash kernels' place; PERF.md section 6):
#   as the program routes itself 0.0977 at the seed where the chip read
#   0.0984; with every layer's choice of experts forced to this file's 0.0083,
#   the largest row 0.0106; 4 to 6% of the tokens meet another expert in each
#   layer.  Readings 0.0949, 0.0984, 0.0986, 0.1026; the float8
#   control 0.254, 0.257 and 0.264; the limit lies between the two, 1.5 times
#   the largest reading and 0.6 of the smallest control: the control reads 2.5
#   times the largest sound reading, under the 3 times a limit should have.
#   The file first carried the Laguna cell's 0.05, and the first chip reading
#   (0.095) failed it: this limit was set from the readings.  A limit near
#   0.02 needs a way to hand the program a choice of experts, or to read its
#   own a token, which `models/llama.py` does not have (ROADMAP.md M6).
# loss: 1.4e-5 to 3.9e-5 (the first reading 1.7e-5); the limit is the accepted
#   cells', 13 times the largest.  A mean over 805 M logits forgives rounding:
#   the control reads 1.7e-5 to 2.6e-4.
# gradient norm: 4.8e-4 to 1.7e-3; the accepted cells' limit, 3.5 times the
#   largest (the control 2.8e-3 to 9.2e-3: one seed of three passes it).  The
#   gradients are those of a program built for this sample (one row a chip),
#   not the timed executable's at two rows of 8,192 a chip.  A
#   fault in the exchange (a block sent to the wrong rank, a result added to
#   the wrong token) reads of order 1 here and in the logits.
# leaf norms: the gradient norm of every leaf, a run's leaves layer by layer,
#   a layer's 64 experts together: 0.0076 to 0.033, the worst leaf a router's
#   on three readings of four: its gradient is not continuous in the
#   activations (a token whose eighth and ninth probabilities lie within
#   bfloat16's rounding goes to another expert).  The limit is the Laguna
#   cell's, 3.6 times the largest reading; the control reads 0.030 to 0.044,
#   under it: this limit sees a leaf left out or a gradient that stayed on
#   the wrong rank (1.0), not the precision.
TOLERANCE = {
    "logits_rel_p90": 1.5e-1,
    "loss_rel": 5e-4,
    "grad_norm_rel": 6e-3,
    "leaf_norm_rel_max": 1.2e-1,
}
# The timed step itself, its first call from the seeded weights on the first
# timed batch (8 x 8,192 tokens, AdamW), and every timed step's exchange.
# loss: the step's against `loss_only`'s on that whole batch: 7.1e-6 to
#   2.2e-5 on four seeds; the check sample's limit, 22 times the largest.
# change: of every leaf's change (a run's by layer) the norm, against
#   `adamw_first_step` on the gradient the step itself took (`mu / (1 - b1)`,
#   the program's own first moment: the rule is checked, the gradient is not;
#   the moments are bfloat16 here): 6.06e-4 to 6.28e-4 on three seeds (4.1e-7
#   with float32 moments, one seed).  The limit lies between the readings and
#   1, which a state left unchanged reads (an expert whose gradient never
#   left its rank but whose update did not happen, a replicated leaf stepped
#   on one rank alone), with the room above the readings, 16 times the
#   largest.
# routing: the routed units of each of the 64 experts of the four layers on
#   that batch at the seeded weights, the program's routers (four ranks, each
#   on its own tokens, summed) against this file's on one device: the units
#   that go to another expert, half the summed difference over a layer's k *
#   T, the largest layer: 0.0020 to 0.0027.  One expert a token too few is
#   0.0625; the limit is 5.6 times the largest reading.
# dropped: in every timed step the units the exchange's passes delivered
#   (each sender's count of the rows it filled, each receiver's of the rows
#   its experts ran, a block the lesser), against 8 x tokens x layers, which
#   is what the routers chose: 0, or the exchange left one behind.
# band: the rows of one sliding layer's logits that change, to the bit, when
#   two tokens change (`step_tokens_mixed.band_rows_wrong`): 0 rows differ,
#   or the program's window and this file's are not one (1,024 keys with the
#   row's own).
STEP_TOLERANCE = {
    "step_loss_rel": 5e-4,
    "update_norm_rel_max": 1e-2,
    "routing_l1_max": 1.5e-2,
    "moe_units_dropped": 0,
    "band_rows_wrong": 0,
}

_LAYER_LEAVES = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "router",
                 "w_gate", "w_up", "w_down")
# Every leaf of a run keeps its layer axis: compare.py takes the gradient norm
# of each layer's part apart (a layer's experts together).  As many runs as
# the published 28 layers have.
LEAF_AXES = {f"layers/{run}/{name}": 1 for run in range(14)
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
    """(the head_dim // 2 inverse frequencies, the factor on cos and sin) of
    one entry of the file's `rope_parameters`: the whole head rotates."""
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    plain = rope["rope_theta"] ** (-2 * i / head_dim)
    if rope["rope_type"] == "default":
        return plain, 1.0
    assert rope["rope_type"] == "yarn", rope["rope_type"]
    low, high = yarn_range(rope, head_dim)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (plain * (1 - ramp) + plain / rope["factor"] * ramp,
            rope["attention_factor"])


def rotate(x, positions, rope):
    """x: (L, n, d), positions: (L,): channels (2i, 2i + 1) of every one of
    the n heads turned by positions * inv_freq_i, cos and sin times the
    entry's factor."""
    L, n, d = x.shape
    inv_freq, factor = inverse_frequencies(rope, d)
    angle = _f32(positions)[:, None] * inv_freq
    cos, sin = (factor * jnp.cos(angle)[:, None, :],
                factor * jnp.sin(angle)[:, None, :])
    pairs = x.reshape(L, n, d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(L, n, d)


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

    o = jax.lax.map(jax.checkpoint(block), jnp.arange(0, L, rows))
    return o.reshape(L, H, d)


def mixer(cfg, kind, lp, x):
    """x: (L, D), one sequence, already normed; `kind` the layer's entry of
    `layer_types`."""
    L = x.shape[0]
    d, KV = cfg["head_dim"], cfg["num_key_value_heads"]
    H = lp["wq"].shape[-1] // d
    rope = cfg["rope_parameters"][kind]
    positions = jnp.arange(L)
    q = rotate((x @ _f32(lp["wq"])).reshape(L, H, d), positions, rope)
    k = rotate((x @ _f32(lp["wk"])).reshape(L, KV, d), positions, rope)
    v = (x @ _f32(lp["wv"])).reshape(L, KV, d)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    return attention(q, k, v, window).reshape(L, H * d) @ _f32(lp["wo"])


# --------------------------------------------------------------------- FFNs

def _choice(cfg, lp, x):
    """x: (T, D) -> the probabilities (T, E) and the chosen experts (T, k)."""
    probs = jax.nn.softmax(x @ _f32(lp["router"]), axis=-1)
    return probs, jax.lax.top_k(probs, cfg["num_experts_per_tok"])[1]


def experts_ffn(cfg, lp, x):
    """x: (T, D): every expert on every token, weighted by the router's
    weight for it, 0 where the token did not choose it."""
    E = cfg["num_experts"]
    probs, chosen = _choice(cfg, lp, x)
    w = jnp.take_along_axis(probs, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    # (T, E): a token's weight for each expert, 0 where it was not chosen.
    weight = jnp.sum(jax.nn.one_hot(chosen, E) * w[..., None], axis=1)

    @jax.checkpoint
    def one(args):
        e, w_gate, w_up, w_down = args
        return weight[:, e, None] * swiglu(x, w_gate, w_up, w_down)

    return jax.lax.scan(lambda y, args: (y + one(args), None),
                        jnp.zeros_like(x), (jnp.arange(E), lp["w_gate"],
                                            lp["w_up"], lp["w_down"]))[0]


def routed_units(cfg, lp, x):
    """x: (T, D) -> (E,) int32: the routed units of each expert, k * T in
    all, by the choice `experts_ffn` makes."""
    probs, chosen = _choice(cfg, lp, x)
    return jnp.zeros(probs.shape[1], jnp.int32).at[chosen.reshape(-1)].add(1)


def block(cfg, number, lp, h):
    """Block `number` (0-based) on h: (B, L, D); with the result the routed
    units of its experts (`routed_units`; None for a dense block)."""
    eps = cfg["rms_norm_eps"]
    kind = cfg["layer_types"][number]
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
    return (rms_norm(h, params["norm"], cfg["rms_norm_eps"]),
            jnp.stack(units) if units
            else jnp.zeros((0, cfg["num_experts"]), jnp.int32))


def nll_of(logits, targets):
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                targets[..., None], axis=-1)[..., 0]


def _mean_nll(h, head, targets, rows):
    """Mean NLL of the states h (B, L, D) through `head`, `rows` positions at
    a time: the (rows, vocabulary) logits of one block are all that stand."""
    B, L, _ = h.shape
    rows = min(rows, B * L)
    chunks = lambda a: a.reshape(B * L // rows, rows, *a.shape[2:])
    nll = jax.lax.map(jax.checkpoint(lambda c: nll_of(c[0] @ head, c[1])),
                      (chunks(h), chunks(targets)))
    return jnp.mean(nll)


def loss_fn(cfg, params, tokens, targets):
    """(loss, logits): the whole logits, for a sample whose logits fit (the
    tests, the band probe)."""
    logits = hidden(cfg, params, tokens)[0] @ _f32(params["head"])
    return jnp.mean(nll_of(logits, targets)), logits


def loss_and_grads(cfg, params, sample, rows=512):
    """`sample = (tokens, targets)` -> (loss, logits, gradient pytree): what
    `compare.py` sets against the system's.  The loss is over every row, a
    block of `rows` at a time; the logits handed back are those of every
    `check_sample.logit_stride`-th position (1 where the file gives none:
    all), which is what fits beside the gradients at 98,304 rows a token."""
    tokens, targets = sample
    stride = cfg.get("check_sample", {}).get("logit_stride", 1)

    def both(p):
        h = hidden(cfg, p, tokens)[0]
        head = _f32(p["head"])
        return _mean_nll(h, head, targets, rows), h[:, ::stride] @ head

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(both, has_aux=True)(params)
    return loss, logits, grads


def loss_only(cfg, params, sample, rows=512):
    """The loss of a batch too large for its logits to be held at once: the
    same forward pass, and the NLL of `rows` positions at a time.  With it
    the expert blocks' routed units on that batch (`hidden`)."""
    tokens, targets = sample
    with jax.default_matmul_precision("highest"):
        h, units = hidden(cfg, params, tokens)
        return _mean_nll(h, _f32(params["head"]), targets, rows), units


def adamw_first_step(params, grads, opt):
    """The weights after AdamW's first step from zero moments: with the bias
    corrections the moments are g and g * g, so the step is
    -lr * (g / (|g| + eps) + weight_decay * w), in float32, every leaf alike;
    the update is rounded to the weights' type and added there, as a trainer
    without master weights does."""
    b1, b2 = opt["b1"], opt["b2"]

    def leaf(w, g):
        w32, g32 = _f32(w), _f32(g)
        m = (1 - b1) * g32 / (1 - b1)
        v = (1 - b2) * g32 * g32 / (1 - b2)
        u = -opt["learning_rate"] * (m / (jnp.sqrt(v) + opt["eps"])
                                     + opt["weight_decay"] * w32)
        return w + u.astype(w.dtype)

    return jax.tree.map(leaf, params, grads)
