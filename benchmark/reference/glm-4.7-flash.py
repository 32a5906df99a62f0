"""Plain reference for the `glm-4.7-flash` configuration: forward, both losses
and the gradients in straightforward `jax.numpy`, float32 at the highest
matmul precision, softmax attention over the whole (L, L) scores a block of
rows at a time, the experts one after the other over all tokens, the whole
logits of the main model and of the prediction module.  Nothing here imports
the program; its parameter pytree comes in as data (bfloat16 leaves are upcast
where they are used).

Written from the published `config.json` of `zai-org/GLM-4.7-Flash`
(`model_type` `glm4_moe_lite`) as the issue that asked for this configuration
wrote the equations down, with DeepSeek-V2 (arXiv:2405.04434, section 2.1:
latent attention with a query latent and a decoupled rotary key) and
DeepSeek-V3's technical report (arXiv:2412.19437: section 2.1.2, sigmoid
scores and the selection bias; section 2.2, multi-token prediction) for the
mechanisms the file only names; Su et al. arXiv:2104.09864 (rotary embedding),
Zhang & Sennrich arXiv:1910.07467 (RMSNorm), Shazeer arXiv:2002.05202
(SwiGLU), Loshchilov & Hutter arXiv:1711.05101 (AdamW).

    block l:  h += MLA_l(RMSNorm(h));  h += FFN_l(RMSNorm(h))         eps 1e-5
    final RMSNorm, untied head.

    MLA (every layer; H = 20 heads), x the normed input, p the positions:
      c_q = RMSNorm(x W_qa)                      (768);
      [q_n; q_r] = c_q W_qb                      -> H x (192 + 64);
      [c_kv; k_r] = x W_kva                      (512 + 64);
      [k_n; v] = RMSNorm(c_kv) W_kvb             -> H x (192 + 256);
      q_r = rope(q_r, p), k_r = rope(k_r, p): k_r is ONE 64-wide part, rotated
      once and shared by the heads; all 64 channels rotate
      (`partial_rotary_factor` 1), in adjacent pairs (2i, 2i + 1) by the angle
      p * theta^(-2i / 64), theta 1e6;
      o = softmax([q_n; q_r] [k_n; k_r]^T 256^-1/2 + causal) v   (H x 256);
      out = concat(o) W_o                        (5120 -> 2048).
    FFN: layer 1 a SwiGLU of 10,240.  Every other layer: s = sigmoid(x W_r)
      over all 64 experts; the 4 largest of s + b (b the selection bias: it
      moves the choice alone; `n_group` 1, so grouped top-k is top-k);
      w = s[chosen], w /= sum w + 1e-20, w *= 1.8;
      y = sum_j w_j Expert_{e_j}(x) + Shared(x), each a SwiGLU of 1536.  This
      chip holds `n_routed_experts` of the published experts, ids from
      `experts_held_first`: the sum runs over the chosen experts held here,
      the weights still normalised over all 4.  What the absent experts would
      add is left out here as in the program.
    Multi-token prediction, one module (`num_nextn_predict_layers` 1), with
      h_i the last block's output BEFORE the final norm and t_{i+1} the next
      token (`targets[i]` in the `(tokens, targets)` contract):
      h'_i = [RMSNorm(Emb(t_{i+1}); enorm); RMSNorm(h_i; hnorm)] W_eh
      (4096 -> 2048); one more block of the expert kind with weights of its
      own; RMSNorm(.; norm); the SAME head and the SAME embedding; NLL against
      t_{i+2} = targets[i + 1], the mean over the L - 1 positions that have
      one.
    loss = mean next-token NLL + `mtp_loss_weight` * the module's, both over
      the `vocab_size` rows held here.

What the runner sets against the system (`TOLERANCE` and `STEP_TOLERANCE`,
below, say why each limit): `loss_and_grads` on a small sample (the loss, the
main logits and the module's as 2 B sequences of rows, every leaf's gradient
norm, through `compare.check`); `loss_only` on a whole timed batch against the
loss the timed step returns, and its routed units an expert, the module's
router the last row, against the program's; `adamw_first_step` on the gradient
the timed step took against the weights it returns, every selection bias
unchanged to the bit.

Departures and assumptions are the configuration file's `assumed`.  Two
devices here are for memory alone and change no arithmetic: a `jax.checkpoint`
round each block, and `lax.map` over the rows of the scores and over the
experts.
"""

import jax
import jax.numpy as jnp

# Why these limits.  The system multiplies in bfloat16 with float32
# accumulation, keeps its residual stream in bfloat16, rotates in float32 and
# rounds the result, runs the flash kernels' softmax in float32 and the
# router's scores in float32; the reference does all of it in float32 at
# "highest" precision.  Measured on TPU v5 lite at the published widths on
# 1 x 2048 tokens (the timed batch's row, four chunks of the head), 15
# readings over as many seeds (my chip runs, PR 38; PERF.md section 6).  Three
# controls, each through `compare.check` with these limits, each `ok` false:
# (a) the reference with its weights rounded to float8 e4m3 (3 mantissa bits
# by `lax.reduce_precision`, scaled by each tensor's largest entry; the
# nearest precision below bfloat16) in the program's place, two seeds; (b) the
# program without the rotation; (c) the program with the module's loss left
# out (`mtp_coef` 0).
# logits: relative L2 error of each token's 19,360 logits, the main model's
#   rows and the module's, 90th percentile over the 4,096 rows: 0.0150 to
#   0.0188.  Control (a) reads 0.317 and 0.329, (b) 0.721 (a rotation left
#   out moves three quarters of the rows), (c) as configured (the logits do
#   not see a loss).  The limit is 2.7 times the largest reading and under a
#   sixth of (a)'s smallest.
# loss: 7.6e-6 to 2.06e-4 (the first reading 1.05e-4); the limit is the
#   accepted cells', 4.8 times the first reading and 2.4 times the largest.
#   (a) reads 2.7e-4 and 4.1e-4: a mean over 79 M logits forgives rounding,
#   as in the other cells; (c) reads 0.231 and (b) 2.9e-4.
# gradient norm: 5e-5 to 2.13e-3 (median 6.6e-4).  The precision hardly
#   moves it ((a) reads 3.28e-3 and 3.38e-3, 1.5 times the largest reading),
#   so the limit lies between the readings and what a fault reads, (b)
#   1.10e-2 and (c) 3.27e-2: 2.8 times the largest reading, 0.55 of (b).
# leaf norms: the gradient norm of every leaf, a run's leaves layer by layer,
#   the module's leaves among them (a selection bias's is 0 on both sides):
#   0.011 to 0.0557, the worst leaf a router's on every reading (the
#   module's on three).  A router's gradient is not continuous in the
#   activations (a token whose fourth and fifth scores lie within bfloat16's
#   rounding goes to another expert), and with 8 of 64 experts held it rests
#   on an eighth of the units; the Kimi Linear cell, on a thirty-second, read
#   to 0.148 over 63 seeds with a median like this cell's largest.  (a) reads
#   0.093 and 0.200, (b) 0.224, (c) 1.0 (`mtp/enorm` and every leaf the module
#   alone feeds).  The limit is 2.7 times the largest reading and tells (b),
#   (c) and one of (a)'s two.
TOLERANCE = {
    "logits_rel_p90": 5e-2,
    "loss_rel": 5e-4,
    "grad_norm_rel": 6e-3,
    "leaf_norm_rel_max": 1.5e-1,
}
# The timed step itself, its first call from the seeded weights on the first
# timed batch (1 x 16,384 tokens, AdamW; my chip runs, PR 38).
# loss: the step's against `loss_only`'s: 7e-8 to 2.4e-5 over 13 seeds;
#   the limit is the Kimi Linear cell's, 6 times the largest.
# change: of every leaf's change (a run's by layer) the norm, against
#   `adamw_first_step` on the gradient the step itself took, read from the
#   first moment it leaves (`mu / (1 - b1)`, float32).  AdamW's first step is
#   -lr * (g / (|g| + eps) + decay * w); with the step's own gradient both
#   sides agree on every sign, and what is left is an update rounded to
#   bfloat16 the other way here and there: 3.9e-7 to 1.0e-6 on eight runs at a
#   rate of 3e-4, and at the configuration's 3e-6, where the update no longer
#   rides on how a product was rounded, 0 on eight runs of ten (the other
#   two under the limit; the log cut their exponents).  The limit lies
#   between that and 1, which is what a state left unchanged reads, with the
#   room above the readings: it sees a wrong rule (a rate off by a hundredth
#   0.01, a leaf skipped 1.0, a selection bias stepped 1.0: that one is held
#   to the bit beside it), not a gradient of the wrong size: that is
#   `TOLERANCE`'s.  At the configuration's warm-up rate (3e-6) a bfloat16
#   weight moves only where it is small (under 1e-3: about one in thirty
#   does, by one step of its type, so a leaf's change grows as the rate to
#   the power 1.5); the float32 leaves all move.
# routing: the routed units of each of the 64 experts of the four expert
#   layers and of the module's on that batch at the seeded weights, the
#   program's router (`llama.expert_unit_counts`, float32 scores on bfloat16
#   activations) against this file's (`routed_units`): the units that go to
#   another expert, half the summed difference over a layer's k * T, the
#   largest layer: 0.0024 to 0.0034 over 13 seeds.  The precision below
#   moves it little here: this file's own units with its logits rounded to
#   bfloat16 differ from its float32 ones by 0.0011 on each of two seeds,
#   under the readings, which carry the bfloat16 activations.  So the limit,
#   the Kimi Linear cell's and twice the largest reading, lies between the
#   readings and what a fault in the choice reads: one expert a token too
#   few is 0.125.
STEP_TOLERANCE = {
    "step_loss_rel": 1.5e-4,
    "update_norm_rel_max": 1e-2,
    "routing_l1_max": 7e-3,
}

_LAYER_LEAVES = (
    "attn_norm", "mlp_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
    "wkv_b", "wo", "router", "router_bias", "shared_gate", "shared_up",
    "shared_down", "w_gate", "w_up", "w_down")
# Every leaf of a run keeps its layer axis: compare.py takes the gradient norm
# of each layer's part apart (a layer's held experts together).  The module's
# layer is a run of one.  More runs than any cut of the 47 layers has.
LEAF_AXES = {
    **{f"layers/{run}/{name}": 1 for run in range(4)
       for name in _LAYER_LEAVES},
    **{f"mtp/layer/{name}": 1 for name in _LAYER_LEAVES}}


def _f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


# -------------------------------------------------------------------- mixer

def rotate(x, positions, theta):
    """x: (L, n, d), positions: (L,): channels (2i, 2i + 1) of every one of
    the n parts turned by positions * theta^(-2i / d)."""
    L, n, d = x.shape
    angle = (_f32(positions)[:, None]
             * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    pairs = x.reshape(L, n, d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(L, n, d)


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


def mla_mixer(cfg, lp, x, positions=None, rotated=True):
    """x: (L, D), one sequence; positions (L,), 0.. where not given.
    `rotated=False` leaves the rotation out: the tests' and the controls'."""
    L = x.shape[0]
    H, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rot, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    positions = jnp.arange(L) if positions is None else positions
    c_q = rms_norm(x @ _f32(lp["wq_a"]), lp["q_norm"], eps)
    q = (c_q @ _f32(lp["wq_b"])).reshape(L, H, nope + rot)
    latent = x @ _f32(lp["wkv_a"])
    c_kv, k_r = latent[:, :r], latent[:, None, r:]             # (L, 1, rot)
    kv = (rms_norm(c_kv, lp["kv_norm"], eps)
          @ _f32(lp["wkv_b"])).reshape(L, H, nope + vd)
    q_n, q_r = q[..., :nope], q[..., nope:]
    if rotated:
        q_r, k_r = rotate(q_r, positions, theta), rotate(k_r, positions, theta)
    q = jnp.concatenate([q_n, q_r], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_r, H, axis=1)], axis=-1)
    o = causal_attention(q, k, kv[..., nope:])
    return o.reshape(L, H * vd) @ _f32(lp["wo"])


# --------------------------------------------------------------------- FFNs

def _choice(cfg, lp, x):
    """x: (T, D) -> the sigmoid scores (T, E) and the chosen experts (T, k)."""
    scores = jax.nn.sigmoid(x @ _f32(lp["router"]))
    return scores, jax.lax.top_k(scores + lp["router_bias"],
                                 cfg["num_experts_per_tok"])[1]


def experts_ffn(cfg, lp, x):
    """x: (T, D).  The held experts' part of the routed sum, plus the shared
    expert."""
    E = cfg["published"]["n_routed_experts"]
    first, held = cfg["experts_held_first"], cfg["n_routed_experts"]
    scores, chosen = _choice(cfg, lp, x)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    # (T, E): a token's weight for each expert, 0 where it was not chosen.
    weight = jnp.sum(jax.nn.one_hot(chosen, E) * w[..., None], axis=1)

    def one(args):
        e, w_gate, w_up, w_down = args
        return weight[:, e, None] * swiglu(x, w_gate, w_up, w_down)

    y = jnp.sum(jax.lax.map(one, (first + jnp.arange(held), lp["w_gate"],
                                  lp["w_up"], lp["w_down"])), axis=0)
    if cfg["n_shared_experts"]:
        y = y + swiglu(x, lp["shared_gate"], lp["shared_up"],
                       lp["shared_down"])
    return y


def routed_units(cfg, lp, x):
    """x: (T, D) -> (E,) int32: the routed units of each published expert,
    k * T in all, by the choice `experts_ffn` makes."""
    scores, chosen = _choice(cfg, lp, x)
    return jnp.zeros(scores.shape[1], jnp.int32).at[chosen.reshape(-1)].add(1)


def block(cfg, dense, lp, h, rotated=True):
    """One block on h: (B, L, D), `dense` its FFN's kind; with the result the
    routed units of its experts (`routed_units`; None for a dense block)."""
    eps = cfg["rms_norm_eps"]
    h = h + jax.vmap(lambda x: mla_mixer(
        cfg, lp, rms_norm(x, lp["attn_norm"], eps), rotated=rotated))(h)
    x = rms_norm(h, lp["mlp_norm"], eps)
    if dense:
        return h + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    B, L, D = x.shape
    x = x.reshape(B * L, D)
    return (h + experts_ffn(cfg, lp, x).reshape(B, L, D),
            routed_units(cfg, lp, x))


def _blocks(cfg, stacks, first_number, h, rotated):
    """The blocks of `stacks` (each leaf led by its layers) in order, numbered
    from `first_number` (1-based); a block up to `first_k_dense_replace` is
    dense.  Returns h, the expert blocks' routed units and the next number."""
    number, units = first_number, []
    for stack in stacks:
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            dense = number <= cfg["first_k_dense_replace"]
            h, routed = jax.checkpoint(
                lambda h, lp, dense=dense: block(cfg, dense, lp, h, rotated))(
                    h, jax.tree.map(lambda a: a[i], stack))
            if routed is not None:
                units.append(routed)
            number += 1
    return h, units, number


def hidden(cfg, params, tokens, next_tokens, rotated=True):
    """tokens, next_tokens: (B, L) int32 -> the main model's final normed
    states and the module's (B, L, D each, float32), and the routed units of
    the expert blocks, the module's last (blocks, E).  `params["layers"]` is
    the program's tuple of runs, each leaf led by the run's layers."""
    eps = cfg["rms_norm_eps"]
    embed = _f32(params["embed"])
    h, units, number = _blocks(cfg, params["layers"], 1, embed[tokens],
                               rotated)
    assert number - 1 == cfg["num_hidden_layers"]
    main = rms_norm(h, params["norm"], eps)
    assert cfg["num_nextn_predict_layers"] == 1
    mp = params["mtp"]
    x = jnp.concatenate([rms_norm(embed[next_tokens], mp["enorm"], eps),
                         rms_norm(h, mp["hnorm"], eps)], axis=-1)
    x, more, _ = _blocks(cfg, [mp["layer"]], number, x @ _f32(mp["w_eh"]),
                         rotated)
    return main, rms_norm(x, mp["norm"], eps), jnp.stack(units + more)


def nll_of(logits, targets):
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                targets[..., None], axis=-1)[..., 0]


def mtp_targets(targets):
    """The module's targets and their weights: position i is held to
    `targets[i + 1]`; the last has none and weighs 0, the others 1 over their
    count."""
    B, L = targets.shape
    weights = jnp.where(jnp.arange(L) < L - 1, 1.0 / (B * (L - 1)), 0.0)
    return jnp.roll(targets, -1, axis=1), jnp.broadcast_to(weights, (B, L))


def loss_fn(cfg, params, tokens, targets, mtp_weight=None, rotated=True):
    """(loss, logits): the logits of the main model and then the module's, 2 B
    sequences of rows."""
    weight = cfg["mtp_loss_weight"] if mtp_weight is None else mtp_weight
    main, module, _ = hidden(cfg, params, tokens, targets, rotated)
    head = _f32(params["head"])
    logits, logits_mtp = main @ head, module @ head
    later, weights = mtp_targets(targets)
    loss = (jnp.mean(nll_of(logits, targets))
            + weight * jnp.sum(weights * nll_of(logits_mtp, later)))
    return loss, jnp.concatenate([logits, logits_mtp])


def loss_and_grads(cfg, params, sample, **how):
    """`sample = (tokens, targets)` -> (loss, logits, gradient pytree): what
    `compare.py` sets against the system's."""
    tokens, targets = sample
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, tokens, targets, **how),
            has_aux=True)(params)
    return loss, logits, grads


def loss_only(cfg, params, sample, rows=512):
    """The loss of a batch too large for its logits to be held at once: the
    same forward pass, and the NLL of `rows` positions at a time.  With it
    its two parts (main, module's, unweighted) and the expert blocks' routed
    units on that batch (`hidden`)."""
    tokens, targets = sample
    B, L = tokens.shape
    with jax.default_matmul_precision("highest"):
        main, module, units = hidden(cfg, params, tokens, targets)
        head = _f32(params["head"])
        later, weights = mtp_targets(targets)
        chunks = lambda a: a.reshape(B * L // rows, rows, *a.shape[2:])
        nll = lambda h, t: jax.lax.map(
            lambda c: nll_of(c[0] @ head, c[1]), (chunks(h), chunks(t)))
        nll_main = jnp.mean(nll(main, targets))
        nll_mtp = jnp.sum(chunks(weights) * nll(module, later))
        return (nll_main + cfg["mtp_loss_weight"] * nll_mtp, units,
                (nll_main, nll_mtp))


def adamw_first_step(params, grads, opt):
    """The weights after AdamW's first step from zero moments: with the bias
    corrections the moments are g and g * g, so the step is
    -lr * (g / (|g| + eps) + weight_decay * w), in float32; the update is
    rounded to the weights' type and added there, as a trainer without master
    weights does.  The selection biases, the module's too, are left as they
    are: the published balancing rule owns them, outside the gradient."""
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
