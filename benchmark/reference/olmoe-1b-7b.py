"""Plain reference for the `olmoe-1b-7b` configuration: forward, loss and
gradients in straightforward `jax.numpy`, float32, no kernel, no sort, no
grouped matmul, no capacity.  Nothing here imports the program; its parameter
pytree comes in as data (bf16 leaves are upcast where they are used, the
expert weights one expert at a time, so no float32 copy of the model is held).

Written from: Muennighoff et al., "OLMoE: Open Mixture-of-Experts Language
Models", arXiv:2409.02060 (section 2: `y = sum_{i in Top-k(r(x))}
softmax(r(x))_i E_i(x)`, dropless token choice; section 3 and table 1:
QK-norm, the load-balancing loss at 0.01, the router z-loss at 0.001), the
published `config.json` of `allenai/OLMoE-1B-7B-0125-Instruct`
(`norm_topk_prob` false: the k weights are the softmax over all 64 experts,
not renormalised), Su et al. arXiv:2104.09864 (RoPE), Zhang & Sennrich
arXiv:1910.07467 (RMSNorm), Fedus et al. arXiv:2101.03961 eq. 4-6 (the
load-balancing loss), Zoph et al. arXiv:2202.08906 eq. 5 (the z-loss).

The block, on a sequence x of L tokens:
    a  = RMSNorm(x)
    q  = RMSNorm_q(a W_q), k = RMSNorm_k(a W_k)   over all 2048 columns,
                                                  then split into heads
    x += softmax(causal(RoPE(q) RoPE(k)^T / sqrt(128))) (a W_v) W_o
    m  = RMSNorm(x)
    p  = softmax(m W_r)                           over the 64 experts, float32
    x += sum_{e in top8(p)} p_e W_down,e (silu(m W_gate,e) * m W_up,e)
No capacity, no routing group: every token meets all eight of its experts.

Departures from the published model, each because the paper or the file
leaves it open and the program under test fixes it this way:
* RoPE rotates adjacent pairs (2i, 2i+1), the RoFormer paper's form; the
  released code rotates (i, i+d/2).  The two differ by a fixed permutation
  of the q/k projection columns (and of the q/k norm weights), invisible on
  seeded weights.  As in Mixtral's reference.
* The load-balancing loss counts a token's FIRST choice (Switch, eq. 4-6),
  the form the program has; the paper's counts all eight choices.  It is
  taken over the whole sample as one batch.
* `clip_qkv` is null in this release and is not written here.
"""

import jax
import jax.numpy as jnp

# Why these tolerances.  The system multiplies in bf16 with float32
# accumulation (eps 2**-8 per rounded activation), the reference in float32
# at "highest" precision.  Measured on TPU v5 lite at the published widths on
# 1 x 512 tokens over 33 seeds (my chip runs, PR 26; PERF.md section 6).
# logits: relative L2 error of each token's 50,304 logits, 90th percentile
#   over the 512 tokens: 0.012 to 0.056, where Mixtral's top-2 of 8 reads
#   0.010.  Top-8 of 64 has more near-ties: a token's eighth and ninth router
#   probabilities are often closer than the bf16 noise of the router's input,
#   and such a token meets one other expert in the system than here.  A row
#   without a flip is off by 0.009 to 0.011 (the median over rows, every
#   seed); a row with one by 0.05 to 0.09 (the 99th percentile never passed
#   0.095), and 5.7 to 10.4% of the rows have one, so the 90th percentile
#   sits where the flipped rows begin and swings with their share.  The limit
#   is above any flipped row, 1.8 times the largest reading, so that only a
#   fault that moves most rows passes it: the reference with its weights
#   rounded to float8 e4m3 (per-tensor scaled; the nearest precision below
#   bf16) reads 0.125 to 0.132 (every row 0.097 or more), the system skipping
#   its busiest expert 0.13 to 0.20, renormalising 0.71: not correct.  A
#   skipped expert that few tokens choose reads 0.055 to 0.08 and is NOT
#   caught here: on seeded weights the routing of one sequence is lopsided
#   (one expert is chosen by nearly every token, several by none); at float32
#   tests/test_olmoe.py catches it.
# loss: at most 2.0e-4; the limit is five times that.  A missing z-loss reads
#   1.8e-3 (fp8 weights 4e-5 to 8e-4: a mean over 25.8 M logits forgives
#   rounding).
# gradient norm: at most 3.7e-3; the limit is three times that.
#   Renormalising reads 0.31 (fp8 weights 3e-4 to 2.6e-3, forgiven likewise).
# leaf norms: the gradient norm of every leaf, the expert leaves layer by
#   layer: at most 0.021, always the router; the limit is three times that.
#   Without QK-norm the norm weights get no gradient (1.0), renormalising
#   reads 0.60, a skipped busy expert 0.036 to 0.14 (fp8 weights 0.016 to
#   0.039).  Expert by expert, as Mixtral's file takes them, the norms are no
#   measure here: on 512 tokens a tenth of the 128 experts see no unit or
#   one, and one flipped unit is then a difference of 1.0 (3 of 12 seeds).
TOLERANCE = {
    "logits_rel_p90": 1.0e-1,
    "loss_rel": 1e-3,
    "grad_norm_rel": 1.2e-2,
    "leaf_norm_rel_max": 6e-2,
}

# Leaves that stack independent parts on their leading axes: compare.py takes
# the gradient norm of each layer's part apart (not of each expert's: above).
LEAF_AXES = {"layers/w_gate": 1, "layers/w_up": 1, "layers/w_down": 1}


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
    """Causal attention with QK-norm on one sequence x: (L, D)."""
    L = x.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    q = rms_norm(x @ _f32(lp["wq"]), lp["q_norm"], eps)      # all H*hd columns
    k = rms_norm(x @ _f32(lp["wk"]), lp["k_norm"], eps)
    q = rope(q.reshape(L, H, hd), cfg["rope_theta"])
    k = rope(k.reshape(L, KV, hd), cfg["rope_theta"])
    v = (x @ _f32(lp["wv"])).reshape(L, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(L, H * hd) @ _f32(lp["wo"])


def moe(cfg, lp, x):
    """Sparse mixture of SwiGLU experts on tokens x: (T, D) ->
    (y, load-balancing loss, z-loss).  A loop over the experts: expert e
    adds its output on every token, weighted by p_e where the token chose it
    and by zero where it did not."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = x @ _f32(lp["router"])                          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(idx, E) * top[..., None], axis=1)

    @jax.checkpoint    # keep no float32 copy of an expert for the backward
    def one_expert(y, ew):
        w1, w3, w2, w_e = ew
        h = jax.nn.silu(x @ _f32(w1)) * (x @ _f32(w3))
        return y + w_e[:, None] * (h @ _f32(w2)), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                        (lp["w_gate"], lp["w_up"], lp["w_down"], weight.T))
    first = jnp.mean(jax.nn.one_hot(idx[:, 0], E), axis=0)   # f_i
    balance = E * jnp.sum(first * jnp.mean(probs, axis=0))   # E sum f_i P_i
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, balance, z


def forward(cfg, params, tokens):
    """tokens: (B, L) int32 -> (logits (B, L, V) float32, layer-mean
    load-balancing loss, layer-mean z-loss)."""
    eps = cfg["rms_norm_eps"]
    B, L = tokens.shape
    n = cfg["num_hidden_layers"]
    h = _f32(params["embed"])[tokens]                        # (B, L, D)
    balance = z = 0.0
    for i in range(n):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = h + jax.vmap(lambda x: attention(
            cfg, lp, rms_norm(x, lp["attn_norm"], eps)))(h)
        y, b_i, z_i = moe(cfg, lp,
                          rms_norm(h, lp["mlp_norm"], eps).reshape(B * L, -1))
        h = h + y.reshape(h.shape)
        balance, z = balance + b_i / n, z + z_i / n
    logits = rms_norm(h, params["norm"], eps) @ _f32(params["head"])
    return logits, balance, z


def loss_fn(cfg, params, tokens, targets):
    logits, balance, z = forward(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return (nll + cfg["router_aux_loss_coef"] * balance
            + cfg["router_z_loss_coef"] * z), logits


def loss_and_grads(cfg, params, sample):
    """`sample = (tokens, targets)` -> (loss, logits, gradient pytree): what
    `compare.py` sets against the system's."""
    tokens, targets = sample
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets), has_aux=True)(params)
    return loss, logits, grads
