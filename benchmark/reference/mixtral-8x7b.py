"""Plain reference for the `mixtral-8x7b` configuration: forward, loss and
gradients in straightforward `jax.numpy`, float32, no kernel, no dispatch
tensors, no batching tricks.  Nothing here imports the program; its parameter pytree
comes in as data (bf16 leaves are upcast where they are used, the expert
weights one expert at a time, so no float32 copy of the model is held).

Written from: Jiang et al., "Mixtral of Experts", arXiv:2401.04088 (section
2: the decoder block, `y = sum_i Softmax(Top2(x W_g))_i SwiGLU_i(x)`), the
published `config.json`, Su et al. arXiv:2104.09864 (RoPE), Zhang & Sennrich
arXiv:1910.07467 (RMSNorm), Ainslie et al. arXiv:2305.13245 (GQA), Fedus et
al. arXiv:2101.03961 eq. 4-6 (the auxiliary load-balancing loss).

Departures from the published model, each because the paper leaves it open
and the program under test fixes it this way:
* RoPE rotates adjacent pairs (2i, 2i+1), the RoFormer paper's form; the
  released checkpoint's code rotates (i, i+d/2).  The two differ by a fixed
  permutation of the q/k projection columns, invisible on seeded weights.
* The auxiliary loss counts a token's first choice only (Switch, eq. 4-6);
  the released training code is not public.  It is taken over the whole
  sample as one batch; the program takes it per routing group of 512 tokens,
  which is the same thing on the 512-token sample the benchmark checks.
* Expert capacity.  The published model drops no token.  The program's
  training path routes GShard's way (Lepikhin et al. arXiv:2006.16668,
  Algorithm 1): within a routing group each expert takes `capacity` units,
  all first choices queue before all second choices, each in token order,
  and a unit past capacity contributes nothing.  On seeded random weights
  that is no detail: the attention output (rms 0.16) swamps the embedding
  (rms 0.02) and is shared by neighbouring tokens, so routing is lopsided and
  13 to 23% of the units of a 512-token sample are past a capacity of 160
  (PR 22, counted in float32 at the published widths, seeds 0-2).  So the
  reference applies the same published rule, from the configuration file's
  `run.capacity_factor` and `run.moe_group_size`, and checks the arithmetic
  of the system; that the system is not dropless is the configuration's
  stated departure, not something this check can absolve.
"""

import math

import jax
import jax.numpy as jnp

# Why these tolerances.  The system multiplies in bf16 with float32
# accumulation (eps 2**-8 per rounded activation), the reference in float32
# at "highest" precision.  Measured on TPU v5 lite over 13 seeds (PR 22;
# PERF.md section 6); each tolerance is about three times the largest seen.
# logits: relative L2 error of each token's 32000 logits, 90th percentile
#   over the 512 tokens: 0.0095 to 0.0107.  An 8-bit float (eps 2**-4,
#   sixteen times coarser) would give about 0.16, and a skipped expert
#   changes a quarter of the rows outright.  A token whose second and third
#   router logits are closer than bf16 noise may go to another expert in the
#   system than here, and moves the capacity queue behind it by one: a few
#   rows in 512 then differ by tens of percent and sit above the percentile.
# loss: at most 7.4e-4.  Gradient norm: at most 1.8e-3.  Means over 16 M
#   logits and sums over 1.7 G gradient terms, so rounding noise averages
#   out and these catch a wrong scale or a missing term (a skipped expert
#   removes 1/8 of the expert gradients).
# leaf norms: the gradient norm of every leaf, and of every expert apart
#   (compare.py), relative to the reference's: at most 0.030, always the
#   router or an expert.  A dead expert or an untrained leaf is a difference
#   of 1.  An expert that few tokens reach (31 of 1024 units on one seed)
#   moves by percents when one unit goes elsewhere.
TOLERANCE = {
    "logits_rel_p90": 4e-2,
    "loss_rel": 2e-3,
    "grad_norm_rel": 2e-2,
    "leaf_norm_rel_max": 1.5e-1,
}

# Leaves that stack independent parts on their leading axes (layers x
# experts): compare.py takes the gradient norm of each part apart.
LEAF_AXES = {"layers/w_gate": 2, "layers/w_up": 2, "layers/w_down": 2}


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
    """Causal grouped-query attention on one sequence x: (L, D)."""
    L = x.shape[0]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = rope((x @ _f32(lp["wq"])).reshape(L, H, hd), cfg["rope_theta"])
    k = rope((x @ _f32(lp["wk"])).reshape(L, KV, hd), cfg["rope_theta"])
    v = (x @ _f32(lp["wv"])).reshape(L, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return o.reshape(L, H * hd) @ _f32(lp["wo"])


def within_capacity(cfg, idx):
    """idx: (T, k) chosen experts -> (T, k) 1.0 where the unit is within its
    expert's capacity in its routing group, else 0.0 (GShard, Algorithm 1)."""
    T, k = idx.shape
    E = cfg["num_local_experts"]
    G = min(T, cfg["run"]["moe_group_size"])
    if T % G:
        raise ValueError(f"{T} tokens do not divide into groups of {G}")
    capacity = min(G, math.ceil(cfg["run"]["capacity_factor"] * k * G / E))
    # (groups, k * G): a group's first choices in token order, then its second.
    queue = idx.reshape(T // G, G, k).transpose(0, 2, 1).reshape(T // G, k * G)
    onehot = jax.nn.one_hot(queue, E)
    position = jnp.sum((jnp.cumsum(onehot, axis=1) - onehot) * onehot, axis=-1)
    keep = (position < capacity).astype(jnp.float32)
    return keep.reshape(T // G, k, G).transpose(0, 2, 1).reshape(T, k)


def moe(cfg, lp, x):
    """Sparse mixture of SwiGLU experts on tokens x: (T, D) -> (y, aux)."""
    E, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    logits = x @ _f32(lp["router"])                          # (T, E)
    top, idx = jax.lax.top_k(logits, k)
    gate = jax.nn.softmax(top, axis=-1)                      # Softmax(Top2)
    gate = gate * within_capacity(cfg, idx)
    weight = jnp.sum(jax.nn.one_hot(idx, E) * gate[..., None], axis=1)

    @jax.checkpoint    # keep no float32 copy of an expert for the backward
    def one_expert(y, ew):
        w1, w3, w2, w_e = ew
        h = jax.nn.silu(x @ _f32(w1)) * (x @ _f32(w3))
        return y + w_e[:, None] * (h @ _f32(w2)), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                        (lp["w_gate"], lp["w_up"], lp["w_down"], weight.T))
    probs = jax.nn.softmax(logits, axis=-1)
    first = jnp.mean(jax.nn.one_hot(idx[:, 0], E), axis=0)   # f_i
    aux = E * jnp.sum(first * jnp.mean(probs, axis=0))       # E sum f_i P_i
    return y, aux


def forward(cfg, params, tokens):
    """tokens: (B, L) int32 -> (logits (B, L, V) float32, mean aux loss)."""
    eps = cfg["rms_norm_eps"]
    B, L = tokens.shape
    h = _f32(params["embed"])[tokens]                        # (B, L, D)
    aux = 0.0
    for i in range(cfg["num_hidden_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = h + jax.vmap(lambda x: attention(
            cfg, lp, rms_norm(x, lp["attn_norm"], eps)))(h)
        y, a = moe(cfg, lp, rms_norm(h, lp["mlp_norm"], eps).reshape(B * L, -1))
        h = h + y.reshape(h.shape)
        aux = aux + a / cfg["num_hidden_layers"]
    logits = rms_norm(h, params["norm"], eps) @ _f32(params["head"])
    return logits, aux


def loss_fn(cfg, params, tokens, targets):
    logits, aux = forward(cfg, params, tokens)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return nll + cfg["router_aux_loss_coef"] * aux, logits


def loss_and_grads(cfg, params, sample):
    """`sample = (tokens, targets)` -> (loss, logits, gradient pytree): what
    `compare.py` sets against the system's."""
    tokens, targets = sample
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets), has_aux=True)(params)
    return loss, logits, grads
