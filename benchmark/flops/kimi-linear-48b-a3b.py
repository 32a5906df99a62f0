"""Operations and bytes the `kimi-linear-48b-a3b` configuration requires,
from shapes alone: matrix products only (2 FLOPs a multiply-accumulate), the
latent layers' causal attention counted once (a query at position i meets i+1
keys), the KDA recurrence as its chunked form at 64 tokens a chunk counts it,
the routed units that land on the experts held here at their expectation under
uniform routing (k * held / published experts a token), the shared expert, the
slice of the head held here, no recomputation, no row padded to a tile.  The
numerator of `mfu`, `kda_roofline` and `moe_experts_roofline`.
"""

CHUNK = 64          # the chunked recurrence's chunk (ops/kda.py)


def _kinds(cfg):
    """[(mixer, ffn)] of the layers that run: the first `num_hidden_layers`
    of the published lists."""
    lin = cfg["linear_attn_config"]
    return [("kda" if i in lin["kda_layers"] else "mla",
             "dense" if i <= cfg["first_k_dense_replace"] else "moe")
            for i in range(1, cfg["num_hidden_layers"] + 1)]


def _count(cfg, kind, at=0):
    return sum(k[at] == kind for k in _kinds(cfg))


def kda_recurrence_flops_per_token(cfg):
    """One KDA layer's recurrence, a token, forward, term by term for a chunk
    of C tokens and a head of d channels: five products of C x C x d (M = K
    K^T and P = Q K^T under their decays, W = T (K e^G), T V, P U), three of C
    x d x d (W S, (Q e^G) S, (K e^{G_C - G})^T U), and the unit triangular
    inverse by substitution, C^3 / 3 multiply-adds."""
    lin = cfg["linear_attn_config"]
    C, d = CHUNK, lin["head_dim"]
    chunk = 5 * 2 * C * C * d + 3 * 2 * C * d * d + 2 * C ** 3 // 3
    return lin["num_heads"] * chunk / C


def forward_flops_per_token(cfg, seq_len):
    """{part: FLOPs} of one token's forward pass at sequence length
    `seq_len`, averaged over the positions of the sequence."""
    D = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    H, qk, vd, r = (cfg["num_attention_heads"],
                    cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"], cfg["kv_lora_rank"])
    HK, hd = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    F, E = cfg["moe_intermediate_size"], cfg["published"]["num_experts"]
    n_kda, n_mla = _count(cfg, "kda"), _count(cfg, "mla")
    n_moe = _count(cfg, "moe", 1)
    held_a_token = cfg["num_experts_per_token"] * cfg["num_experts"] / E
    return {
        # q, k, v, o; the decay's and the gate's low-rank pairs; beta.
        "kda_projections": n_kda * 2 * (4 * D * HK + 2 * (D * hd + hd * HK)
                                        + D * lin["num_heads"]),
        "kda_recurrence": n_kda * kda_recurrence_flops_per_token(cfg),
        "mla_projections": n_mla * 2 * (
            D * H * qk + D * (r + cfg["qk_rope_head_dim"])
            + r * H * (cfg["qk_nope_head_dim"] + vd) + H * vd * D),
        # QK^T over keys of `qk` and PV over values of `vd`, (seq_len + 1) / 2
        # keys a query on average.
        "mla_scores": n_mla * H * (qk + vd) * (seq_len + 1),
        "dense_ffn": _count(cfg, "dense", 1) * 3 * 2 * D * cfg["intermediate_size"],
        "router": n_moe * 2 * D * E,
        "routed_experts_held": n_moe * held_a_token * 3 * 2 * D * F,
        "shared_expert": n_moe * cfg["num_shared_experts"] * 3 * 2 * D * F,
        "head": 2 * D * cfg["vocab_size"],
    }


def required_flops_per_sample(cfg, traffic):
    """Forward and backward passes of one token: every product has an input
    gradient and a weight (or second-operand) gradient of its own size."""
    return 3 * sum(forward_flops_per_token(cfg, traffic["seq_len"]).values())


def parameters(cfg):
    """(parameters held on this chip, of them those one token uses)."""
    D = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    H, taps = lin["num_heads"], lin["short_conv_kernel_size"]
    HK, hd = H * lin["head_dim"], lin["head_dim"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r, A = cfg["kv_lora_rank"], cfg["num_attention_heads"]
    kda = (4 * D * HK + 3 * taps * HK + 2 * (D * hd + hd * HK) + 2 * HK
           + D * H + H + hd)
    mla = (D * A * qk + D * (r + cfg["qk_rope_head_dim"]) + r
           + r * A * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
           + A * cfg["v_head_dim"] * D)
    expert = 3 * D * cfg["moe_intermediate_size"]
    E = cfg["published"]["num_experts"]
    moe_fixed = D * E + E + cfg["num_shared_experts"] * expert
    mixers = _count(cfg, "kda") * kda + _count(cfg, "mla") * mla
    fixed = (mixers + cfg["num_hidden_layers"] * 2 * D
             + _count(cfg, "dense", 1) * 3 * D * cfg["intermediate_size"]
             + _count(cfg, "moe", 1) * moe_fixed
             + 2 * cfg["vocab_size"] * D + D)
    n_moe = _count(cfg, "moe", 1)
    used = cfg["num_experts_per_token"] * cfg["num_experts"] / E
    return (fixed + n_moe * cfg["num_experts"] * expert,
            fixed + n_moe * used * expert)


def kda_required(cfg, traffic):
    """(FLOPs, bytes) the KDA layers' recurrence of one training step
    requires, what runs under the `kda` scope: forward and both gradients of
    the chunked form's products; bytes: q, k, v (bf16), the log-decay (f32)
    and beta read in each direction, o written and its cotangent read, the
    five gradients written, and the chunk-entry states (f32, d x d a head and
    chunk), which the backward pass holds, written once and read once.  Bytes
    bound it: 3.4 GB a layer at 16,384 tokens is 4.1 ms at the HBM peak where
    its 0.29 TFLOP are 1.5 ms at the bf16 peak."""
    lin = cfg["linear_attn_config"]
    tokens = traffic["batch"] * traffic["seq_len"]
    H, d = lin["num_heads"], lin["head_dim"]
    layers = _count(cfg, "kda")
    flops = 3 * layers * tokens * kda_recurrence_flops_per_token(cfg)
    wide = tokens * H * d
    chunks = traffic["batch"] * -(-traffic["seq_len"] // CHUNK)
    forward = 3 * wide * 2 + wide * 4 + tokens * H * 4 + wide * 2
    backward = forward + 3 * wide * 2 + wide * 4 + tokens * H * 4
    states = 2 * chunks * H * d * d * 4
    return flops, layers * (forward + backward + states)


def mla_required(cfg, traffic):
    """(FLOPs, bytes) the latent-attention layers of one training step
    require, what runs under the `mla` scope: the four projections and the
    causal scores (QK^T, PV; dV, dP, dQ, dK), forward and both gradients;
    bytes: the weights read in each direction and their gradients written, q,
    k, v and o and their gradients once each (bf16).  FLOPs bound it."""
    tokens = traffic["batch"] * traffic["seq_len"]
    parts = forward_flops_per_token(cfg, traffic["seq_len"])
    flops = 3 * tokens * (parts["mla_projections"] + parts["mla_scores"])
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    r, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    weights = (D * H * qk + D * (r + cfg["qk_rope_head_dim"])
               + r * H * (cfg["qk_nope_head_dim"] + vd) + H * vd * D) * 2
    rows = tokens * H * (2 * qk + 2 * vd) * 2
    return flops, _count(cfg, "mla") * (3 * weights + 2 * rows)


def experts_required(cfg, traffic):
    """(FLOPs, bytes) the routed experts held here require of one training
    step, what runs under `moe.experts`: gate, up and down for the units that
    land on held experts (their expectation under uniform routing), forward
    and both gradients; bytes: the held experts' weights read in each
    direction and their gradients written, the units' rows in and out of each
    product once in each direction (bf16)."""
    tokens = traffic["batch"] * traffic["seq_len"]
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    units = (tokens * cfg["num_experts_per_token"] * cfg["num_experts"]
             / cfg["published"]["num_experts"])
    layers = _count(cfg, "moe", 1)
    flops = 3 * layers * units * 3 * 2 * D * F
    weights = cfg["num_experts"] * 3 * D * F * 2
    rows = units * (2 * D + 3 * F) * 2
    return flops, layers * (3 * weights + 2 * rows)
