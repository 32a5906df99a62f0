"""Plain reference for the `ouro-2.6b` configuration: forward, loss and
gradients in straightforward `jax.numpy`, float32, a Python loop over the
recurrent steps round a `lax.scan` over the layers of a pass, full softmax
attention, the whole logits of every step, no kernel, no chunk.  Nothing here
imports the program; its parameter pytree comes in as data (bf16 leaves are
upcast where they are used).

Written from: "Scaling Latent Reasoning via Looped Language Models" (Ouro,
ByteDance Seed and others, 2025-10) and the published `config.json` of
`ByteDance/Ouro-2.6B` (`total_ut_steps` 4, `early_exit_threshold` 1), as the
issue that asked for this configuration wrote the equations down; Su et al.
arXiv:2104.09864 (RoPE), Zhang & Sennrich arXiv:1910.07467 (RMSNorm), Shazeer
arXiv:2002.05202 (SwiGLU), Loshchilov & Hutter arXiv:1711.05101 (AdamW).  With
T recurrent steps, N layers and weights that every step shares:

    layer l on h (sandwich normalisation, four RMSNorms a layer):
        a  = h + Norm_attn_post,l(Attn_l(Norm_attn_pre,l(h)))
        h' = a + Norm_ffn_post,l(SwiGLU_l(Norm_ffn_pre,l(a)))
      Attn: q, k, v = xW_q, xW_k, xW_v; RoPE (theta 1e6) on q and k; causal
      softmax attention at scale 1/sqrt(128) over 16 heads; W_o; no bias.
      SwiGLU(x) = (silu(xW_gate) * xW_up) W_down.
    the loop: h_0 = E[tokens]; for t = 1..T:
        h_t = Norm_final(layer_N(... layer_1(h_{t-1})))
      the same N layers and positions, the final norm inside the loop.
    at every step: logits z_t = h_t W_head, and the exit gate
        lambda_t = sigmoid(h_t w_g + b_g), one number a token.
    exit distribution of a token: S_0 = 1, S_t = prod_{j<=t} (1 - lambda_j);
        p_t = lambda_t S_{t-1} for t < T, p_T = S_{T-1}.
    loss = mean over tokens of [ sum_t p_t nll_t - beta H(p) ],
        nll_t = -log softmax(z_t)[target], H(p) = -sum_t p_t log p_t.

Three things the runner sets against the system (`TOLERANCE`, below, says why
each limit): `loss_and_grads` on a small sample (loss, every step's logits,
every leaf's gradient norm, through `compare.check`); `loss_only` on a whole
timed batch, whose logits the chip cannot hold at once, against the loss the
timed step returns; `adamw_first_step` on that batch's gradient against the
weights the timed step returns.

Departures from the published model, each because the catalog's file leaves it
open and the program under test fixes it this way (the configuration's file
lists them under `assumed`):
* RoPE rotates adjacent pairs (2i, 2i+1), the RoFormer paper's form; released
  code rotates (i, i+d/2).  A fixed permutation of the q/k projection columns,
  invisible on seeded weights.  As in the other two references.
* The sandwich norms, the final norm inside the loop, the gate's form (one
  `Linear(hidden -> 1)` with bias for all steps) and the loss are the paper's
  and the released modelling file's as the issue recalled them; beta = 0.1 is
  the paper's first-stage value as recalled.
"""

import jax
import jax.numpy as jnp

# Why these limits.  The system multiplies in bf16 with float32 accumulation
# (eps 2**-8 a rounded activation), keeps its states in bf16 through 32 layer
# applications, and sums a shared weight's four gradient contributions in bf16;
# the reference does all of it in float32 at "highest" precision.  Measured on
# TPU v5 lite at the published widths on 2 x 1024 tokens (the timed batch's rows,
# two chunks of the head) over 30 seeds (my chip runs, PR 30; PERF.md section
# 6).  The control is the reference with its weights rounded to float8 e4m3
# (scaled by each tensor's largest entry; the nearest precision below bf16)
# through `compare.check` with these limits, 3 seeds: `ok` false on each.
# logits: relative L2 error of each token's 49,152 logits at each of the four
#   recurrent steps, 90th percentile over the 8,192 rows: 0.0203 to 0.0264.  No
#   routing, so no row flips: the error is that of some 300 bf16 roundings of
#   the state on the way through 32 layer applications.  The control reads
#   0.243 to 0.262.  The limit is 2.3 times the largest reading and a quarter
#   of the control's smallest.
# loss: at most 5.8e-5 (median 1.4e-5); the limit is about three times that.
#   A dropped entropy term moves the loss by beta * H(p), 0.6% at the tests'
#   sizes (the control 1.3e-4 to 2.8e-4: a mean over 400 M logits forgives
#   rounding).
# gradient norm: at most 1.8e-3 (median 6.5e-4).  The limit is six times
#   that: on 1 x 512 tokens one seed of twenty read six times the median, and
#   the driver draws hundreds (the control 4.9e-3 to 1.4e-2).
# leaf norms: the gradient norm of every leaf, the stack's leaves layer by
#   layer, at most 0.0040 (median 0.0026), the gate's weight 0.0064, the
#   gate's bias as `compared` hands it over 0.018 (0.012, 0.0094 and 0.0090
#   next: one number's error, a sum over the tokens that does not average
#   out as a norm's does); the limit is 3.3 times that.  A gate whose gradient
#   is stopped reads 1.0, a shared weight's gradient from one step only 0.96,
#   a bias gradient dropped 0.40, at the tests' sizes (the control 0.039 to
#   0.081).  The bias's one number as it is read up to 0.24 off on 1 x 512
#   tokens and up to 0.025 on 2 x 1024, the worst where it nearly cancels
#   (|g_b| a fifth of the weight gradient's root mean square); the same code
#   in float32 (full attention) against this reference on the same float32
#   weights reads 0.0086 there and 8e-5 to 2.2e-4 on three other seeds, every
#   other leaf under 1.3e-4: the precision's noise, which a fault is not.
TOLERANCE = {
    "logits_rel_p90": 6e-2,
    "loss_rel": 2e-4,
    "grad_norm_rel": 1e-2,
    "leaf_norm_rel_max": 6e-2,
}
# The timed step itself, its first call from the seeded weights on the first
# timed batch (2 x 4096 tokens, eight chunks of 8 x 512 rows, AdamW), the same
# 30 seeds.  A training cell's loss and the norm of its weights' change hardly
# move with the precision, so each limit is about three times the largest
# reading alone.
# loss: the step's against `loss_only`'s, at most 2.2e-5 (median 9e-6: a mean
#   over four times the tokens of the sample); the control 6.1e-5 to 1.3e-4.
# change: of every leaf's change (the stack's by layer) the norm, against
#   `adamw_first_step` on the gradient of the program's own loss: 2.6e-4 to
#   8.1e-4, the worst always a norm's leaf.  AdamW's first step is -lr * (g /
#   (|g| + eps) + decay * w): where w is 1 an entry moves by 1.1 or 0.9 lr by
#   the sign of g, and the step's own gradient and the one computed apart
#   differ in the sign of a few entries near zero.  So this limit sees a wrong
#   rule (half the rate 0.5, no decay 5e-3 on those leaves, a leaf skipped or
#   stepped twice), not a gradient of the wrong size: that is `TOLERANCE`'s.
STEP_TOLERANCE = {
    "step_loss_rel": 7e-5,
    "update_norm_rel_max": 2.5e-3,
}

# Every leaf of the stack keeps its layer axis: compare.py takes the gradient
# norm of each layer's part apart.
LEAF_AXES = {"layers/" + name: 1 for name in (
    "attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm",
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")}


def _f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (L, heads, d).  Rotate each adjacent pair by position * theta_i."""
    L, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def attention(cfg, lp, x):
    """Causal attention on one sequence x: (L, D)."""
    L = x.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    q = rope((x @ _f32(lp["wq"])).reshape(L, H, hd), cfg["rope_theta"])
    k = rope((x @ _f32(lp["wk"])).reshape(L, KV, hd), cfg["rope_theta"])
    v = (x @ _f32(lp["wv"])).reshape(L, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(L, H * hd) @ _f32(lp["wo"])


def swiglu(lp, x):
    return ((jax.nn.silu(x @ _f32(lp["w_gate"])) * (x @ _f32(lp["w_up"])))
            @ _f32(lp["w_down"]))


def layer(cfg, lp, h):
    """One sandwich-normed block on h: (B, L, D)."""
    eps = cfg["rms_norm_eps"]
    a = h + rms_norm(jax.vmap(lambda x: attention(
        cfg, lp, rms_norm(x, lp["attn_norm"], eps)))(h),
        lp["attn_post_norm"], eps)
    return a + rms_norm(swiglu(lp, rms_norm(a, lp["mlp_norm"], eps)),
                        lp["mlp_post_norm"], eps)


def states(cfg, params, tokens):
    """tokens: (B, L) int32 -> the normed states (T, B, L, D) of the T
    recurrent steps, float32."""
    h = _f32(params["embed"])[tokens]                        # (B, L, D)
    out = []
    for _ in range(cfg["total_ut_steps"]):
        # The layers in order.  A scan and not a Python loop for the compiler's
        # sake alone: 32 layer applications written out, with their gradients,
        # took it 150 s of every run's set-up (my chip runs, PR 30).  A
        # checkpoint round a layer for the memory's sake alone: at 2 x 1024
        # tokens the 32 applications' scores and products kept for the
        # gradient are over 20 GB.  The same arithmetic either way.
        h, _ = jax.lax.scan(
            jax.checkpoint(lambda x, lp: (layer(cfg, lp, x), None)), h,
            params["layers"])
        h = rms_norm(h, params["norm"], cfg["rms_norm_eps"])
        out.append(h)
    return jnp.stack(out)


def gate_logits(params, h):
    return h @ _f32(params["gate_w"]) + _f32(params["gate_b"])


def forward(cfg, params, tokens):
    """tokens: (B, L) int32 -> (logits (T, B, L, V), gate logits (T, B, L)),
    float32, of the T recurrent steps."""
    h = states(cfg, params, tokens)
    return h @ _f32(params["head"]), gate_logits(params, h)


def nll_of(logits, targets):
    """-log softmax(logits)[target]: (..., V) and (...) -> (...)."""
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                targets[..., None], axis=-1)[..., 0]


def exit_distribution(gates):
    """(T, B, L) gate logits -> (T, B, L) exit probabilities."""
    lam = jax.nn.sigmoid(gates)
    p, stay = [], jnp.ones_like(lam[0])                      # S_0 = 1
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(p + [stay])                             # p_T = S_{T-1}


def expected_exit_loss(cfg, nll, gates):
    """(T, B, L) NLL and gate logits -> the loss."""
    p = exit_distribution(gates)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0)
                    - cfg["exit_entropy_coef"] * entropy)


def loss_fn(cfg, params, tokens, targets):
    logits, gates = forward(cfg, params, tokens)
    nll = nll_of(logits, jnp.broadcast_to(targets, logits.shape[:-1]))
    return expected_exit_loss(cfg, nll, gates), logits


def loss_and_grads(cfg, params, sample):
    """`sample = (tokens, targets)` -> (loss, logits of all T steps stacked
    on a leading axis, gradient pytree): what `compare.py` sets against the
    system's."""
    tokens, targets = sample
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets), has_aux=True)(params)
    return loss, logits, grads


def loss_only(cfg, params, sample, rows=512):
    """The loss of a batch too large for its logits to be held at once (2 x
    4096 tokens: 6.4 GB over the four steps): the same forward pass, one
    sequence after another, and the NLL of `rows` positions at a time."""
    tokens, targets = sample
    B, L = tokens.shape
    head = _f32(params["head"])

    def one(pair):
        h = states(cfg, params, pair[0][None])[:, 0]         # (T, L, D)
        chunks = (h.reshape(h.shape[0], L // rows, rows, -1).swapaxes(0, 1),
                  pair[1].reshape(L // rows, rows))
        nll = jax.lax.map(lambda c: nll_of(
            c[0] @ head, jnp.broadcast_to(c[1], c[0].shape[:-1])), chunks)
        return nll.swapaxes(0, 1).reshape(-1, L), gate_logits(params, h)

    with jax.default_matmul_precision("highest"):
        nll, gates = jax.lax.map(one, (tokens, targets))     # (B, T, L) each
        return expected_exit_loss(cfg, nll.swapaxes(0, 1), gates.swapaxes(0, 1))


def compared(grads):
    """The gradient tree as `compare.py` takes its leaf norms: every leaf as
    it is but the gate's bias, which goes beside the root mean square of the
    gate's weight gradient.  The bias's gradient is ONE number, the sum over
    all token-steps of terms that nearly cancel at seeded weights (the four
    steps' NLL of a token are nearly equal, and sum_t dp_t = 0), so its
    relative error has no bound: 0.24 and 0.10 among 20 seeds on the chip
    with nothing wrong (`TOLERANCE`'s notes).  The weight's gradient sums the
    same terms, each times a normed state of unit mean square, so its root
    mean square is the size the bias's gradient has where its terms do not
    cancel: the pair's norm moves by the bias's error over that size, and a
    bias gradient that is dropped still moves it (`tests/test_ouro.py`)."""
    g_w, g_b = _f32(grads["gate_w"]), _f32(grads["gate_b"])
    return {**grads, "gate_b": jnp.stack(
        [g_b.reshape(()), jnp.sqrt(jnp.mean(g_w * g_w))])}


def adamw_first_step(params, grads, opt):
    """The weights after AdamW's first step from zero moments: with the bias
    corrections the moments are g and g * g, so the step is
    -lr * (g / (|g| + eps) + weight_decay * w), in float32; the update is
    rounded to the weights' type and added there, as a trainer without master
    weights does (the configuration's `assumed`)."""
    b1, b2 = opt["b1"], opt["b2"]

    def leaf(w, g):
        w32, g32 = _f32(w), _f32(g)
        m = (1 - b1) * g32 / (1 - b1)
        v = (1 - b2) * g32 * g32 / (1 - b2)
        u = -opt["learning_rate"] * (m / (jnp.sqrt(v) + opt["eps"])
                                     + opt["weight_decay"] * w32)
        return w + u.astype(w.dtype)

    return jax.tree.map(leaf, params, grads)
