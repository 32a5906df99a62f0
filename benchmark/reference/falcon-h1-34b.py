"""Plain reference for the `falcon-h1-34b` configuration: forward, loss and
gradients in straightforward `jax.numpy`, float32 at the highest matmul
precision, softmax attention over whole rows of scores under a mask, the
state-space branch as a token-by-token scan, every multiplier applied
literally where the equations put it, the whole logits, no kernel.  Nothing
here imports the program; its parameter pytree comes in as data (bfloat16
leaves are upcast where they are used).

Written from the published `config.json` of `tiiuae/Falcon-H1-34B-Instruct`
(`model_type` `falcon_h1`) as the issue that asked for this configuration
wrote the equations down, with Dao and Gu arXiv:2405.21060 (Mamba-2: the
scan, the scalar decay a head, the groups that share B and C, the gated
norm), Su et al. arXiv:2104.09864 (rotary embedding), Zhang & Sennrich
arXiv:1910.07467 (RMSNorm), Shazeer arXiv:2002.05202 (SwiGLU).  For the state
h (5120 wide), every layer alike, eps 1e-5:

    x = RMSNorm(h), then two branches on the SAME x, summed:
    h += 0.0375 * Attn(1 * x) + 0.08838834764831845 * SSM(0.25 * x)
    h += 0.011160714285714284 * W_down(silu(0.1767766952966369 * W_gate x')
                                       * W_up x'),  x' = RMSNorm(h)
    (`attention_out_multiplier`, `attention_in_multiplier`,
    `ssm_out_multiplier`, `ssm_in_multiplier`, `mlp_multipliers`); the
    embedding's rows times `embedding_multiplier` 5.656854249492381; a final
    RMSNorm; an untied head, its logits times `lm_head_multiplier` 0.0078125.

    Attn, 20 query heads and 4 K/V heads of d = 128, no bias:
      q = x W_q;  k = (x W_k) * `key_multiplier` 0.011048543456039804, BEFORE
      the rotation;  v = x W_v;  q and k rotated whole at theta 1e11
      (channels (2i, 2i + 1) by the angle p * theta^(-2i / 128));  head j
      reads K/V head j // 5;  o_j = softmax(q_j k^T d^-1/2 over the keys c <=
      i) v;  out = concat(o_j) W_o.
    SSM (Mamba-2), 32 heads of P = 128 channels, 2 groups of 16 heads that
    share B and C, state N = 256:
      [z 4096 | x 4096 | B 2 x 256 | C 2 x 256 | dt 32] = (u W_in) with its
      five sections times `ssm_multipliers` (0.3536, 0.25, 0.1768, 0.5,
      0.3536, the file's exact values);
      [x | B | C] <- silu(bias + sum_i w_i [x | B | C]_{t - 3 + i}), a causal
      depthwise convolution of 4 taps over those 5120 channels;
      dt_t = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) dt_t), a scalar
      a head;  S_t = a_t S_{t-1} + dt_t x_t B_t^T  (128 x 256 a head, zero
      at the start);  y_t = S_t C_t + D x_t;
      out = (RMSNorm_group(y * silu(z)) * w) W_out, the mean square over each
      group's 2048 channels, the gate before the norm (`mamba_rms_norm` true,
      `mamba_norm_before_gate` false).
    loss = mean next-token NLL over the `vocab_size` rows held here.

What the runner sets against the system (`TOLERANCE` and `MORE_TOLERANCE`,
below, say why each limit): `loss_and_grads` on the check sample (the loss,
the logits, every leaf's gradient norm, through `compare.check`; and what
each branch of each layer adds to the residual, against the program's own
account of the same, `llama.branch_contributions`); `sgd_first_step` on that
gradient, against one step of the step that is timed; `scan`, the recurrence
alone and its gradient on inputs whose every rounded product is exact,
against the program's chunked form.

Departures and assumptions are the configuration file's `assumed`.  Three
devices here are for memory alone and change no arithmetic: the gradient is
taken a layer at a time from saved layer inputs (a layer's weights cast up,
used and dropped: four layers at once are 8.2 GB in float32, their gradients
as much again), `lax.map` over the rows of the scores, and the scan's
backward pass keeps the state of every 32nd token and forms the others
again.
"""

import jax
import jax.numpy as jnp

# Why these limits.  The system multiplies in bfloat16 with float32
# accumulation, keeps its residual stream in bfloat16, rotates, normalises,
# convolves and scans in float32 (dt, the decay sums and the state stay
# float32 from chunk to chunk) and rounds each result; the reference does all
# of it in float32 at "highest" precision.  Every reading below was read on
# TPU v5 lite at the published widths on 1 x 512 tokens (four chunks of the
# scan, one of the head), through these comparisons (my chip runs, PR 48;
# PERF.md section 6): nineteen seeds of the program as it is (eleven in the
# first session, whose tree formed the chunk recurrence as one product, eight
# in the third, on the tree that carries it in a loop; the two read alike),
# and controls, each `ok` false: (a) the reference with its weights rounded
# to float8 e4m3 (3 mantissa bits by `lax.reduce_precision`, scaled by each
# tensor's largest entry: the nearest precision below bfloat16) in the
# program's place, at two seeds; the program, through `run.py` itself
# (`tests/planted_fault.py`), with (b) `ssm_out_multiplier` dropped, (c)
# `key_multiplier` dropped, (d) the chunk-entry states rounded to bfloat16,
# (e) the decay sums rounded to bfloat16, (f) dt rounded to bfloat16, (g) the
# cotangent of the state that leaves each chunk rounded to bfloat16; the step
# (h) handing its weights back unchanged, (i) taken up the gradient, (j)
# leaving a leaf out.
# logits: relative L2 error of each token's 32,640 logits, 90th percentile
#   over the 512 rows: 0.00591 to 0.00594.  (a) reads 0.0530 and 0.0529, (b)
#   1.16, (c) 0.102; (d) to (g) 0.00591 to 0.00593, as configured.  The limit
#   is 3.4 times the largest reading (they lie within 0.4% of each other) and
#   under two fifths of (a)'s.
# loss: 0 to 4.6e-7, five units in float32's last place of ln(32,640) at
#   most (the logits are 2^-7 of a unit product, so the loss is ln(32,640)
#   to five digits whatever the stack does, and the precision hardly moves
#   it: (a) reads 5.5e-7 and 4.3e-6).  The limit is this cell's own, six
#   times the largest reading and a quarter of what (b) reads, 1.3e-5 and
#   2.6e-5; the accepted cells' 5e-4 would stand twenty times over (b).
# gradient norm: 2.0e-5 to 3.8e-5; (a) reads 1.1e-3 at both seeds, thirty
#   times the largest reading: the limit lies between the two, eight times
#   the one and under a third of the other; (b) reads 0.54, (c) 5.7e-3.
# leaf norms: the gradient norm of every leaf, a run's leaves layer by layer:
#   0.0025 to 0.0102 (median 0.0058), the worst leaf `ssm_a_log` or
#   `ssm_dt_bias` of layer 0 on every reading (32 numbers, a head's decay:
#   the one gradient that rests on the scan's own term alone).  (a) reads
#   0.0755 and 0.0482, (b) 0.84, (c) 0.99 (`wk`); (e) 0.0399 on the first
#   tree and 0.0278 on this one, under the limit: the scan's probe is what
#   sees (e); (d), (f), (g) 0.0107, 0.0073 and 0.0072, as configured.  The
#   limit is 2.9 times the largest reading, 5 medians, and three fifths of
#   (a)'s smaller reading.
TOLERANCE = {
    "logits_rel_p90": 2e-2,
    "loss_rel": 3e-6,
    "grad_norm_rel": 3e-4,
    "leaf_norm_rel_max": 3e-2,
}
# What the runner compares beside `compare.check`.
# branches: of the first and of the last layer, what the attention branch,
#   the state-space branch and the FFN each add to the residual (multiplier
#   and all), the program's against this file's: the relative L2 error over
#   the sample, each against its OWN norm, the largest of the six: 0.00846
#   to 0.00851 (the last layer's FFN; attention 0.0029 to 0.0054, the
#   state-space branch 0.0043 to 0.0080).  (a) reads 0.078 at both seeds, (b)
#   11.2 (10.3 in the first layer: 1 / 0.0884 - 1), (c) 1.01; the others as
#   configured.  The limit is 3.5 times the readings and under two fifths of
#   (a)'s.  One branch left out reads 1.
# update: one step of the step function that is timed (at the sample's
#   shape) from the seeded weights, against `sgd_first_step` on THIS file's
#   gradient: `|ours - theirs| / |theirs - seeded|` over the whole tree and
#   over every leaf of 2^20 numbers a layer or more that this file's step
#   moves in 256 numbers or more, the largest.  Eight seeds: the whole tree
#   0.093 to 0.114, the worst leaf read 0.117 to 0.197.  That is no rounding
#   of a thousandth, and why: a bfloat16 weight moves only where `lr |g|`
#   passes half a unit in its last place, |w| < about 400 lr |g|; the
#   gradient is 9e-7 a number (norm 0.0407 over 2.05 G), so this file's step
#   moves 87 thousand of the 2.05 G numbers (43 thousand of `ssm_in`'s 189 M,
#   1.4 thousand of `w_gate`'s 440 M), and most of the moved norm lies where
#   the step is ONE unit in the last place: there the two sides part
#   wherever a gradient's last digits carry a number over a rounding edge,
#   a share `eps` of the moved numbers for gradients that differ by `eps` a
#   number, which reads `sqrt(eps / 2)`, 0.1 at the 2% that bfloat16
#   products leave.  Why a leaf needs 256 moved numbers to be read: `wq` and
#   `wk` stand behind `key_multiplier`, their step lies under half a unit
#   nearly everywhere and moves 11 to 30 of `wq`'s 52 M numbers and 4 to 14
#   of `wk`'s 10.5 M; one number that one side carries and the other does
#   not is then a fifth of the leaf's whole step, and `wq` read 0.0 on five
#   seeds, 0.19 on one and 0.62 on one with nothing wrong (`wk` 0.0 or
#   0.046).  The next smallest leaves move 981 (`wv`) and 1,087 (`wo`) at
#   least.  (h) reads 1.0 on every leaf and on the tree, (i) 1.99 to 2.01,
#   (j) 1.0 on that leaf (`ssm_out`, 17.8 thousand moved) and 0.18 on the
#   whole tree; nothing else sees (h), (i) or (j).  The limit lies two and a
#   half times over the largest reading and at half of (h)'s, so it sees a
#   step not taken, taken the wrong way or skipping a large leaf, not a rate
#   off by a tenth: the window's losses would not see that either.
# scan: the program's chunked scan alone (`ops.ssd.ssd`) and its gradient at
#   the published head shapes on 512 tokens of the runner's probe (whole
#   numbers, every decay a power of two, half steps), against `scan` and its
#   gradient: for each output the L2 norm of how far ours lies from this
#   file's float32 number BEYOND what rounding that number to the type ours
#   comes in costs (`beyond_rounding`), over the norm of this file's; the
#   largest over the outputs that come rounded (y, dx) and over those in
#   float32 (ddt, dA, dD; there it is the relative L2 error).  24 seeds: 1.9e-8
#   to 2.0e-7 (median 6e-8) and 2.0e-6 to 3.8e-6 (`dA` every time): float32's
#   own rounding, of which a rounded output keeps what carries a number over
#   a bfloat16 edge, twice its distance from the edge.  (d) reads 2.8e-5 to
#   8.6e-5 and 1.0e-4 to 2.3e-4, (g) 1.4e-5 to 2.2e-5 and 4.5e-5 to 8.1e-5
#   (three seeds each), (e) 3.6e-3, 5.2e-3 and 8.9e-3, 1.8e-2, (f) 1.2e-4,
#   3.5e-4 and 1.7e-3, 1.9e-3 (two seeds): each over both limits, and
#   NOTHING else sees (d), (f) or (g): their logits, leaves and branches read
#   as configured.  The first limit stands five times over the largest
#   reading and thirteen under the smallest fault's, the second three and a
#   half times from both.  The plain relative difference of the two ROUNDED
#   numbers, which the second session's tree read, counts a whole unit in the
#   last place for each carried number: 3.0e-5 to 1.9e-4 on eight seeds
#   against 2.7e-4 for (g), and its limit of 1.5e-4, set from three CPU
#   seeds, refused two sound seeds of eight.  dB and dC are not read: the
#   chunked form rounds the cotangent of `C B^T` to the inputs' type by its
#   design, and they read 8.7e-4 to 1.5e-3 as they stand.
MORE_TOLERANCE = {
    "branch_rel_max": 3e-2,
    "update_rel_max": 0.5,
    "scan_rel_max": 1e-6,
    "scan_f32_rel_max": 1.3e-5,
}

_LAYER_LEAVES = (
    "attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "ssm_in", "ssm_conv",
    "ssm_conv_bias", "ssm_a_log", "ssm_dt_bias", "ssm_d", "ssm_norm",
    "ssm_out", "w_gate", "w_up", "w_down")
# Every leaf of the one run keeps its layer axis: compare.py takes the
# gradient norm of each layer's part apart.
LEAF_AXES = {f"layers/0/{name}": 1 for name in _LAYER_LEAVES}


def _f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, positions, theta):
    """x: (L, n, d), positions: (L,): channels (2i, 2i + 1) of every one of
    the n heads turned by positions * theta^(-2i / d)."""
    L, n, d = x.shape
    inv_freq = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = _f32(positions)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    pairs = x.reshape(L, n, d // 2, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(L, n, d)


# ---------------------------------------------------------------- attention

def attention(q, k, v, rows=128):
    """q: (L, H, d); k, v: (L, KV, d): softmax over the whole row of scores,
    `rows` query rows at a time; row i sees the keys c <= i."""
    L, H, d = q.shape
    group = H // k.shape[1]
    rows = min(rows, L)
    at = jnp.arange(L)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=0)
        qb = qb.reshape(rows, H // group, group, d)
        s = jnp.einsum("qcgd,kcd->cgqk", qb, k) / jnp.sqrt(jnp.float32(d))
        seen = at[None, :] <= (start + jnp.arange(rows))[:, None]
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("cgqk,kcd->qcgd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(block, jnp.arange(0, L, rows)).reshape(L, H, d)


def attn_branch(cfg, lp, x):
    """x: (L, D), one sequence, already normed -> Attn(in_multiplier * x)."""
    L = x.shape[0]
    d, H, KV = (cfg["head_dim"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    x = x * cfg["attention_in_multiplier"]
    positions, theta = jnp.arange(L), cfg["rope_theta"]
    q = rotate((x @ _f32(lp["wq"])).reshape(L, H, d), positions, theta)
    k = rotate(((x @ _f32(lp["wk"])) * cfg["key_multiplier"]).reshape(
        L, KV, d), positions, theta)
    v = (x @ _f32(lp["wv"])).reshape(L, KV, d)
    return attention(q, k, v).reshape(L, H * d) @ _f32(lp["wo"])


# -------------------------------------------------------- state-space branch

_SEGMENT = 32


def scan(x, dt, A, B, C, D):
    """The recurrence token by token, float32.  x: (L, H, P); dt: (L, H), the
    step sizes after their softplus; A: (H,), negative; B, C: (L, G, N), head
    h of group h // (H / G); D: (H,) -> y (L, H, P).  The backward pass keeps
    the state that enters every `_SEGMENT`-th token (the whole sequence where
    that does not divide it)."""
    L, H, P = x.shape
    heads = lambda a: jnp.repeat(a, H // a.shape[1], axis=1)
    B, C = heads(B), heads(C)                               # (L, H, N)

    def token(S, t):
        x_t, dt_t, B_t, C_t = t
        S = (jnp.exp(A * dt_t)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t) + D[:, None] * x_t

    segment = jax.checkpoint(lambda S, ts: jax.lax.scan(token, S, ts))
    n = L // _SEGMENT if L % _SEGMENT == 0 else 1
    _, y = jax.lax.scan(
        segment, jnp.zeros((H, P, B.shape[-1]), jnp.float32),
        jax.tree.map(lambda a: a.reshape(n, L // n, *a.shape[1:]),
                     (x, dt, B, C)))
    return y.reshape(L, H, P)


def ssm_branch(cfg, lp, x):
    """x: (L, D), one sequence, already normed -> SSM(in_multiplier * x)."""
    L = x.shape[0]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N, taps = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    inner = cfg["mamba_d_ssm"]
    assert inner == H * P
    proj = (x * cfg["ssm_in_multiplier"]) @ _f32(lp["ssm_in"])
    widths = (inner, inner, G * N, G * N, H)
    assert proj.shape[-1] == sum(widths)
    parts, at = [], 0
    for width, multiplier in zip(widths, cfg["ssm_multipliers"]):
        parts.append(proj[:, at:at + width] * multiplier)
        at += width
    z, xs, Bs, Cs, dt = parts
    conved = jnp.concatenate([xs, Bs, Cs], axis=-1)
    padded = jnp.pad(conved, ((taps - 1, 0), (0, 0)))
    w = _f32(lp["ssm_conv"])
    conved = jax.nn.silu(_f32(lp["ssm_conv_bias"]) + sum(
        w[i] * padded[i:i + L] for i in range(taps)))
    y = scan(conved[:, :inner].reshape(L, H, P),
             jax.nn.softplus(dt + lp["ssm_dt_bias"]), -jnp.exp(lp["ssm_a_log"]),
             conved[:, inner:inner + G * N].reshape(L, G, N),
             conved[:, inner + G * N:].reshape(L, G, N), lp["ssm_d"])
    gated = (y.reshape(L, inner) * jax.nn.silu(z)).reshape(L, G, inner // G)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    return (normed.reshape(L, inner) * lp["ssm_norm"]) @ _f32(lp["ssm_out"])


# -------------------------------------------------------------------- blocks

def block(cfg, lp, h):
    """One block on h: (B, L, D); with the result what the attention branch,
    the state-space branch and the FFN each added to the residual."""
    eps = cfg["rms_norm_eps"]
    x = rms_norm(h, lp["attn_norm"], eps)
    a = cfg["attention_out_multiplier"] * jax.vmap(
        lambda x: attn_branch(cfg, lp, x))(x)
    s = cfg["ssm_out_multiplier"] * jax.vmap(
        lambda x: ssm_branch(cfg, lp, x))(x)
    h = h + a + s
    x = rms_norm(h, lp["mlp_norm"], eps)
    on_gate, on_out = cfg["mlp_multipliers"]
    g = on_out * ((jax.nn.silu(on_gate * (x @ _f32(lp["w_gate"])))
                   * (x @ _f32(lp["w_up"]))) @ _f32(lp["w_down"]))
    return h + g, (a, s, g)


def _layers(cfg, params):
    """The layers' parameter trees in order, out of the program's tuple of
    runs (each leaf led by the run's layers)."""
    layers = [jax.tree.map(lambda a: a[i], stack)
              for stack in params["layers"]
              for i in range(jax.tree.leaves(stack)[0].shape[0])]
    assert len(layers) == cfg["num_hidden_layers"]
    return layers


def embedded(cfg, embed, tokens):
    return _f32(embed)[tokens] * cfg["embedding_multiplier"]


def nll_of(logits, targets):
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                targets[..., None], axis=-1)[..., 0]


def head_loss(cfg, norm, head, h, targets):
    """(loss, logits) from the stack's last state."""
    logits = (rms_norm(h, norm, cfg["rms_norm_eps"]) @ _f32(head)
              * cfg["lm_head_multiplier"])
    return jnp.mean(nll_of(logits, targets)), logits


def loss_and_grads(cfg, params, sample):
    """`sample = (tokens, targets)` -> (loss, logits, gradient pytree, added):
    the first three are what `compare.py` sets against the system's; `added`
    is {"attn", "ssm", "ffn"}: (layers, B, L, D) float32 each, what each
    branch of each layer adds to the residual on the way.  The gradient a
    layer at a time, last layer first, each from its saved input; a layer's
    gradients leave its block in the type of its weights."""
    tokens, targets = sample
    with jax.default_matmul_precision("highest"):
        layers = _layers(cfg, params)
        states, added = [embedded(cfg, params["embed"], tokens)], []
        for lp in layers:
            h, parts = block(cfg, lp, states[-1])
            states.append(h)
            added.append(parts)
        loss, pull, logits = jax.vjp(
            lambda norm, head, h: head_loss(cfg, norm, head, h, targets),
            params["norm"], params["head"], states.pop(), has_aux=True)
        dnorm, dhead, dh = pull(jnp.ones((), jnp.float32))
        grads = []
        for lp in reversed(layers):
            _, pull = jax.vjp(lambda lp, h: block(cfg, lp, h)[0], lp,
                              states.pop())
            dlp, dh = pull(dh)
            grads.append(dlp)
        _, pull = jax.vjp(lambda embed: embedded(cfg, embed, tokens),
                          params["embed"])
        (dembed,) = pull(dh)
    grads.reverse()
    at, stacks = 0, []
    for stack in params["layers"]:
        n = jax.tree.leaves(stack)[0].shape[0]
        stacks.append(jax.tree.map(lambda *a: jnp.stack(a), *grads[at:at + n]))
        at += n
    added = {name: jnp.stack(parts)
             for name, parts in zip(("attn", "ssm", "ffn"), zip(*added))}
    return loss, logits, {"embed": dembed, "layers": tuple(stacks),
                          "norm": dnorm, "head": dhead}, added


def sgd_first_step(params, grads, lr):
    """The configuration's optimizer as its file states it, plain SGD on
    weights of their own type: every leaf `p - lr * g` in float32, rounded
    ONCE to the leaf's type.  The rounding is `reduce_precision`'s: inside
    one program the compiler is free to drop a pair of converts and carry
    the step unrounded, which would move every number of a leaf where the
    weights' type moves one in five thousand."""
    def leaf(p, g):
        to = jnp.finfo(p.dtype)
        return jax.lax.reduce_precision(_f32(p) - lr * _f32(g), to.nexp,
                                        to.nmant).astype(p.dtype)

    return jax.tree.map(leaf, params, grads)
