"""Plain reference for the `kimi-linear-48b-a3b` configuration: forward, loss
and gradients in straightforward `jax.numpy`, float32 at the highest matmul
precision, the KDA recurrence token by token (`lax.scan`, no chunked form),
softmax attention over the whole (L, L) scores a block of rows at a time, the
experts one after the other over all tokens, the whole logits.  Nothing here
imports the program; its parameter pytree comes in as data (bfloat16 leaves
are upcast where they are used).

Written from "Kimi Linear: An Expressive, Efficient Attention Architecture"
(Moonshot AI, 2025-10) and the published `config.json` of
`moonshotai/Kimi-Linear-48B-A3B-Instruct`, as the issue that asked for this
configuration wrote the equations down; Yang et al. arXiv:2406.06484 (the
delta rule), Zhang & Sennrich arXiv:1910.07467 (RMSNorm), Shazeer
arXiv:2002.05202 (SwiGLU), Loshchilov & Hutter arXiv:1711.05101 (AdamW).

    block l:  h += Mixer_l(RMSNorm(h));  h += FFN_l(RMSNorm(h))      eps 1e-5
    final RMSNorm, untied head, no positional encoding of any kind.

    KDA mixer (layers in `kda_layers`; H heads of d = 128), x the normed input:
      q = L2(SiLU(Conv(x W_q))) d^-1/2, k = L2(SiLU(Conv(x W_k))),
      v = SiLU(Conv(x W_v)); Conv a causal depthwise convolution of 4 taps,
      y_t = sum_i w_i x_{t-3+i}, no bias; L2 over a head's channels.
      g_t = -exp(A_log[h]) softplus((x W_f_down W_f_up)_t + dt_bias)  (<= 0)
      beta_t = sigmoid(x W_b), one number a head.
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,
      S_0 = 0 (d x d a head);  o_t = S_t^T q_t.
      out = (RMSNorm_head(o_t) * sigmoid(x W_g_down W_g_up + b_g)) W_o.
    MLA mixer (layers in `full_attn_layers`; H heads):
      q = x W_q -> H x 192; [c; k_r] = x W_kva -> 512 + 64;
      [k_n; v] = RMSNorm(c) W_kvb -> H x (128 + 128); k_h = [k_n,h; k_r], the
      64 shared by all heads and not rotated (`mla_use_nope`);
      o = softmax(q k^T 192^-1/2 + causal) v;  out = o W_o.
    FFN: layer 1 a SwiGLU of 9216.  Every other layer: scores = sigmoid(x W_r)
      over all 256 experts; the 8 largest of scores + b (b the selection bias:
      it moves the choice alone); w = scores[chosen], w /= sum w + 1e-20,
      w *= 2.446; y = sum_j w_j Expert_{e_j}(x) + Shared(x), each a SwiGLU of
      1024.  This chip holds `num_experts` of the published experts, ids from
      `experts_held_first`: the sum runs over the chosen experts held here,
      the weights still normalised over all 8.  What the absent experts would
      add is left out here as in the program.
    loss = mean next-token NLL over the `vocab_size` rows held here.

Three things the runner sets against the system (`TOLERANCE` and
`STEP_TOLERANCE`, below, say why each limit): `loss_and_grads` on a small
sample (loss, logits, every leaf's gradient norm, through `compare.check`);
`loss_only` on a whole timed batch against the loss the timed step returns,
and its routed units an expert against the program's router's;
`adamw_first_step` on the gradient the timed step took against the weights
it returns, the selection bias unchanged to the bit.

Departures and assumptions are the configuration file's `assumed`.  Two
devices here are for memory alone and change no arithmetic: a
`jax.checkpoint` round each layer and round each 64 tokens of the token scan
(a scan over 2,048 tokens would keep 2,048 states of 2 MB a layer for its
gradient), and `lax.map` over the rows of the scores.
"""

import jax
import jax.numpy as jnp

# Why these limits.  The system multiplies in bfloat16 with float32
# accumulation, keeps its residual stream in bfloat16, runs the recurrence in
# chunks of 64 with the state and the decay sums in float32 and bfloat16
# operands, and the router's scores in float32; the reference does all of it
# in float32 at "highest" precision, token by token.  Measured on TPU v5 lite
# at the published widths on 1 x 2048 tokens (the timed batch's row, four
# chunks of the head), 63 readings over 63 seeds (my chip runs, PR 32: 33 in
# the first session, 30 in the second; PERF.md section 6 has them and the
# controls).  The control is the reference with its weights rounded to float8
# e4m3 (3 mantissa bits by `lax.reduce_precision`, scaled by each tensor's
# largest entry; the nearest precision below bfloat16) in the program's
# place, through `compare.check` with these limits, 4 seeds: `ok` false on
# each, by the logits' limit and by the gradient norm's.
# logits: relative L2 error of each token's 20,480 logits, 90th percentile
#   over the 2,048 rows: 0.0167 to 0.0376 (median 0.0257).  The control reads
#   0.279 to 0.286.  The limit is twice the largest reading and a quarter of
#   the control's smallest.  It also sees a mixer or an FFN left out or wrong
#   (tests/test_kimi_linear.py: the blocks against this file to 1e-5 in
#   float32).  What it cannot see, shown by three more controls (two seeds
#   each, the system with one quantity rounded to bfloat16): the recurrence's
#   state rounded after every chunk reads 0.0290 and 0.0233 where the same
#   seeds read 0.0284 and 0.0229 as configured (every product reads the state
#   as bfloat16 already, so only the carried sum's rounding is new); the decay
#   sums rounded 0.0409 and 0.0335; the router's scores rounded 0.0427 and
#   0.0301: less than seeds differ.  The router's scores are held to
#   `routing_l1_max` instead (`STEP_TOLERANCE`); a bfloat16 state or decay sum
#   no number of this comparison tells from the configuration's own rounding.
# loss: at most 1.5e-4 (median 4e-5); the limit is three times that.  The
#   control reads 8e-5 to 1.0e-3: a mean over 42 M logits forgives rounding,
#   as in the other cells.
# gradient norm: at most 5.5e-4 (median 2.2e-4).  The control reads 1.5e-3 to
#   3.9e-3.  The limit is 2.2 times the largest reading and 0.8 of the
#   control's smallest.
# leaf norms: the gradient norm of every leaf, a run's leaves layer by layer
#   (a selection bias's is 0 on both sides, which compares as no difference,
#   and 1.0 if the program gives it any): 0.015 to 0.148 (median 0.045), the
#   worst leaf a router's on 56 of 63 readings, the decay's `a_log` (32
#   numbers a layer) on six (to 0.027), an expert layer's `w_down` on one.  A
#   router's gradient is not continuous in the activations: a token whose
#   eighth and ninth scores lie within bfloat16's rounding of the activations
#   goes to another expert, and the router sees another gradient for it; with
#   8 of 256 experts held a layer's router gradient rests on some 3% of the
#   units.  Nine readings of 63 lie between 0.09 and 0.148 and the five
#   largest between 0.118 and 0.148, so the limit is twice the largest.  The
#   precision hardly moves this number (the control reads 0.139 to 0.163,
#   `a_log` its worst leaf on three seeds of four), so the limit lies between
#   the readings and what a fault reads: a leaf whose gradient is dropped 1.0,
#   a shared expert left out of the backward pass 1.0 on three leaves a layer.
TOLERANCE = {
    "logits_rel_p90": 7.5e-2,
    "loss_rel": 5e-4,
    "grad_norm_rel": 1.2e-3,
    "leaf_norm_rel_max": 3e-1,
}
# The timed step itself, its first call from the seeded weights on the first
# timed batch (1 x 16,384 tokens, AdamW; my chip runs, PR 32).
# loss: the step's against `loss_only`'s: 7.0e-6 to 4.2e-5 over 18 readings;
#   the limit is 3.5 times the largest.
# change: of every leaf's change (a run's by layer) the norm, against
#   `adamw_first_step` on the gradient the step itself took, read from the
#   first moment it leaves (`mu / (1 - b1)`, float32).  AdamW's first step is
#   -lr * (g / (|g| + eps) + decay * w); with the step's own gradient both
#   sides agree on every sign, and what is left is an update rounded to
#   bfloat16 the other way here and there: 3.9e-7 to 2.9e-6 over 7 readings.
#   The limit lies between that and 1, with the room above the readings: it
#   sees a wrong rule (a rate off by a hundredth 0.01, half the rate 0.5, a
#   leaf skipped 1.0, a selection bias stepped 1.0: that one is held to the
#   bit beside it), not a gradient of the wrong size: that is `TOLERANCE`'s,
#   on the check sample.
# routing: the routed units of each of the 256 experts of each expert layer
#   on that batch at the seeded weights, the program's router
#   (`llama.expert_unit_counts`) against this file's (`routed_units`): the
#   units that go to another expert, half the summed difference over a
#   layer's k * T, the largest layer.  As configured (float32 scores on
#   bfloat16 activations) 0.0015 to 0.0034 over the four layers of six seeds
#   and 0.0027 to 0.0031 the largest layer of seven more; with the router's
#   scores rounded to bfloat16 0.0103 to 0.0130 on those six, the largest
#   layer 0.0117 or more on each.  The limit is twice the
#   largest reading and 0.6 of the control's smallest.
STEP_TOLERANCE = {
    "step_loss_rel": 1.5e-4,
    "update_norm_rel_max": 1e-2,
    "routing_l1_max": 7e-3,
}

# Every leaf of a run keeps its layer axis: compare.py takes the gradient norm
# of each layer's part apart (a layer's held experts together).  More runs
# than any cut of the 27 layers has.
LEAF_AXES = {f"layers/{run}/{name}": 1 for run in range(16) for name in (
    "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "conv_q", "conv_k",
    "conv_v", "f_down", "f_up", "a_log", "dt_bias", "wb", "g_down", "g_up",
    "g_bias", "o_norm", "wkv_a", "kv_norm", "wkv_b", "router", "router_bias",
    "shared_gate", "shared_up", "shared_down", "w_gate", "w_up", "w_down")}


def _f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


# ------------------------------------------------------------------- mixers

def short_conv(x, w):
    """x: (L, C), w: (taps, C): y_t = sum_i w_i x_{t - taps + 1 + i}."""
    taps, L = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(xp[i:i + L] * _f32(w[i]) for i in range(taps))


def delta_rule(q, k, v, g, beta, block=64):
    """The recurrence token by token.  q, k, v, g: (L, H, d); beta: (L, H).
    Returns o (L, H, d).  The outer scan over blocks of tokens only bounds
    what the gradient keeps."""
    L, H, d = q.shape

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, :, None] * S                       # Diag(a) S
        seen = jnp.einsum("hk,hkv->hv", k_t, S)                # k^T S
        S = S + jnp.einsum("hk,hv->hkv", b_t[:, None] * k_t, v_t - seen)
        return S, jnp.einsum("hk,hkv->hv", q_t, S)

    pad = -L % block
    blocks = jax.tree.map(
        lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            -1, block, *a.shape[1:]), (q, k, v, g, beta))
    _, o = jax.lax.scan(jax.checkpoint(lambda S, xs: jax.lax.scan(token, S, xs)),
                        jnp.zeros((H, d, d), jnp.float32), blocks)
    return o.reshape(-1, H, d)[:L]


def kda_mixer(cfg, lp, x):
    """x: (L, D), one sequence."""
    lin = cfg["linear_attn_config"]
    H, d = lin["num_heads"], lin["head_dim"]
    L = x.shape[0]
    heads = lambda y: y.reshape(L, H, d)
    branch = lambda w, conv: heads(jax.nn.silu(
        short_conv(x @ _f32(lp[w]), lp[conv])))
    unit = lambda y: y * jax.lax.rsqrt(
        jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)
    q = unit(branch("wq", "conv_q")) * d ** -0.5
    k = unit(branch("wk", "conv_k"))
    v = branch("wv", "conv_v")
    f = (x @ _f32(lp["f_down"])) @ _f32(lp["f_up"]) + lp["dt_bias"]
    g = -jnp.exp(lp["a_log"])[None, :, None] * heads(jax.nn.softplus(f))
    beta = jax.nn.sigmoid(x @ _f32(lp["wb"]))
    o = delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid((x @ _f32(lp["g_down"])) @ _f32(lp["g_up"])
                          + _f32(lp["g_bias"]))
    o = rms_norm(o, lp["o_norm"], cfg["rms_norm_eps"]) * heads(gate)
    return o.reshape(L, H * d) @ _f32(lp["wo"])


def causal_attention(q, k, v, rows=512):
    """q, k: (L, H, dk); v: (L, H, dv): softmax over the whole row of scores,
    `rows` query rows at a time."""
    L, H, dk = q.shape
    rows = min(rows, L)
    at = jnp.arange(L)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(dk))
        seen = (start + jnp.arange(rows))[:, None] >= at[None, :]
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(0, L, rows))
    return o.reshape(L, H, v.shape[-1])


def mla_mixer(cfg, lp, x):
    L = x.shape[0]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, shared, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    q = (x @ _f32(lp["wq"])).reshape(L, H, nope + shared)
    latent = x @ _f32(lp["wkv_a"])
    c, k_r = latent[:, :r], latent[:, r:]
    kv = (rms_norm(c, lp["kv_norm"], cfg["rms_norm_eps"])
          @ _f32(lp["wkv_b"])).reshape(L, H, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.repeat(k_r[:, None, :], H, axis=1)], axis=-1)
    o = causal_attention(q, k, kv[..., nope:])
    return o.reshape(L, H * vd) @ _f32(lp["wo"])


# --------------------------------------------------------------------- FFNs

def experts_ffn(cfg, lp, x):
    """x: (T, D).  The held experts' part of the routed sum, plus the shared
    expert."""
    E = cfg["published"]["num_experts"]
    k = cfg["num_experts_per_token"]
    first, held = cfg["experts_held_first"], cfg["num_experts"]
    scores = jax.nn.sigmoid(x @ _f32(lp["router"]))             # (T, E)
    chosen = jax.lax.top_k(scores + lp["router_bias"], k)[1]    # (T, k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    # (T, E): a token's weight for each expert, 0 where it was not chosen.
    weight = jnp.sum(jax.nn.one_hot(chosen, E) * w[..., None], axis=1)

    def one(args):
        e, w_gate, w_up, w_down = args
        return weight[:, e, None] * swiglu(x, w_gate, w_up, w_down)

    y = jnp.sum(jax.lax.map(one, (first + jnp.arange(held), lp["w_gate"],
                                  lp["w_up"], lp["w_down"])), axis=0)
    if cfg["num_shared_experts"]:
        y = y + swiglu(x, lp["shared_gate"], lp["shared_up"],
                       lp["shared_down"])
    return y


def routed_units(cfg, lp, x):
    """x: (T, D) -> (E,) int32: the routed units of each published expert,
    k * T in all, by the choice `experts_ffn` makes."""
    scores = jax.nn.sigmoid(x @ _f32(lp["router"]))
    chosen = jax.lax.top_k(scores + lp["router_bias"],
                           cfg["num_experts_per_token"])[1]
    return jnp.zeros(scores.shape[1], jnp.int32).at[chosen.reshape(-1)].add(1)


def layer(cfg, number, lp, h):
    """Block `number` (1-based, as the file's lists) on h: (B, L, D); with
    it the routed units of its experts (`routed_units`; None for a dense
    layer)."""
    eps = cfg["rms_norm_eps"]
    lin = cfg["linear_attn_config"]
    mixer = kda_mixer if number in lin["kda_layers"] else mla_mixer
    assert (number in lin["kda_layers"]) != (number in lin["full_attn_layers"])
    h = h + jax.vmap(lambda x: mixer(cfg, lp, rms_norm(
        x, lp["attn_norm"], eps)))(h)
    x = rms_norm(h, lp["mlp_norm"], eps)
    if number <= cfg["first_k_dense_replace"]:
        return h + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    B, L, D = x.shape
    x = x.reshape(B * L, D)
    return (h + experts_ffn(cfg, lp, x).reshape(B, L, D),
            routed_units(cfg, lp, x))


def hidden(cfg, params, tokens):
    """tokens: (B, L) int32 -> the final normed states (B, L, D), float32,
    and the expert layers' routed units (layers, E).  `params["layers"]` is
    the program's tuple of runs, each leaf led by the run's layers; the
    layers are taken in order."""
    h = _f32(params["embed"])[tokens]
    number, units = 0, []
    for run in params["layers"]:
        for i in range(jax.tree.leaves(run)[0].shape[0]):
            number += 1
            h, routed = jax.checkpoint(
                lambda h, lp, number=number: layer(cfg, number, lp, h))(
                    h, jax.tree.map(lambda a: a[i], run))
            if routed is not None:
                units.append(routed)
    assert number == cfg["num_hidden_layers"]
    return rms_norm(h, params["norm"], cfg["rms_norm_eps"]), jnp.stack(units)


def nll_of(logits, targets):
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                targets[..., None], axis=-1)[..., 0]


def loss_fn(cfg, params, tokens, targets):
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
    the expert layers' routed units on that batch (`hidden`)."""
    tokens, targets = sample
    B, L = tokens.shape
    with jax.default_matmul_precision("highest"):
        h, units = hidden(cfg, params, tokens)
        head = _f32(params["head"])
        nll = jax.lax.map(lambda c: nll_of(c[0] @ head, c[1]),
                          (h.reshape(B * L // rows, rows, -1),
                           targets.reshape(-1, rows)))
        return jnp.mean(nll), units


def adamw_first_step(params, grads, opt):
    """The weights after AdamW's first step from zero moments: with the bias
    corrections the moments are g and g * g, so the step is
    -lr * (g / (|g| + eps) + weight_decay * w), in float32; the update is
    rounded to the weights' type and added there, as a trainer without master
    weights does.  The selection biases are left as they are: the published
    balancing rule owns them, outside the gradient."""
    b1, b2 = opt["b1"], opt["b2"]

    def leaf(path, w, g):
        if getattr(path[-1], "key", None) == "router_bias":
            return w
        w32, g32 = _f32(w), _f32(g)
        m = (1 - b1) * g32 / (1 - b1)
        v = (1 - b2) * g32 * g32 / (1 - b2)
        u = -opt["learning_rate"] * (m / (jnp.sqrt(v) + opt["eps"])
                                     + opt["weight_decay"] * w32)
        return w + u.astype(w.dtype)

    return jax.tree_util.tree_map_with_path(leaf, params, grads)
