"""Operations and bytes the `glm-4.7-flash` configuration requires, from
shapes alone: matrix products only (2 FLOPs a multiply-accumulate), the latent
layers' causal attention counted once (a query at position i meets i+1 keys),
the routed units that land on the experts held here at their expectation under
uniform routing (k * held / published experts a token), the shared expert, the
slice of the head held here, the multi-token-prediction module counted once:
one more latent expert layer, the projection of the embedding and the state
side by side, and a second pass over the head.  No recomputation, no row
padded to a tile (the head's 19,360 columns are 151.25 tiles of 128: the
ragged last tile is not counted), the module's last row, which carries no
loss, counted like the others, as it is run.  The numerator of `mfu`,
`mla_roofline`, `mla_flash_roofline` and `moe_experts_roofline`.
"""


def _layers(cfg):
    """(latent layers, dense FFNs, expert layers) that run: the stack's and
    the module's one more expert layer."""
    n, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    mtp = cfg["num_nextn_predict_layers"]
    return n + mtp, dense, n - dense + mtp


def _mla_weights(cfg):
    """Parameters of one latent mixer's five projections."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (D * rq + rq * H * (nope + rope) + D * (r + rope)
            + r * H * (nope + vd) + H * vd * D)


def forward_flops_per_token(cfg, seq_len):
    """{part: FLOPs} of one token's forward pass at sequence length
    `seq_len`, averaged over the positions of the sequence."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    F, E = cfg["moe_intermediate_size"], cfg["published"]["n_routed_experts"]
    n_mla, n_dense, n_moe = _layers(cfg)
    held_a_token = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / E
    mtp = cfg["num_nextn_predict_layers"]
    return {
        "mla_projections": n_mla * 2 * _mla_weights(cfg),
        # QK^T over keys of `qk` and PV over values of `v_head_dim`,
        # (seq_len + 1) / 2 keys a query on average.
        "mla_scores": n_mla * H * (qk + cfg["v_head_dim"]) * (seq_len + 1),
        "dense_ffn": n_dense * 3 * 2 * D * cfg["intermediate_size"],
        "router": n_moe * 2 * D * E,
        "routed_experts_held": n_moe * held_a_token * 3 * 2 * D * F,
        "shared_expert": n_moe * cfg["n_shared_experts"] * 3 * 2 * D * F,
        "mtp_projection": mtp * 2 * 2 * D * D,
        "head": (1 + mtp) * 2 * D * cfg["vocab_size"],
    }


def required_flops_per_sample(cfg, traffic):
    """Forward and backward passes of one token: every product has an input
    gradient and a weight (or second-operand) gradient of its own size."""
    return 3 * sum(forward_flops_per_token(cfg, traffic["seq_len"]).values())


def parameters(cfg):
    """(parameters held on this chip, of them those one token uses)."""
    D = cfg["hidden_size"]
    n_mla, n_dense, n_moe = _layers(cfg)
    mla = _mla_weights(cfg) + cfg["q_lora_rank"] + cfg["kv_lora_rank"]
    expert = 3 * D * cfg["moe_intermediate_size"]
    E = cfg["published"]["n_routed_experts"]
    moe_fixed = D * E + E + cfg["n_shared_experts"] * expert
    mtp = cfg["num_nextn_predict_layers"]
    fixed = (n_mla * (mla + 2 * D)
             + n_dense * 3 * D * cfg["intermediate_size"]
             + n_moe * moe_fixed + 2 * cfg["vocab_size"] * D + D
             + mtp * (2 * D * D + 3 * D))
    used = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / E
    return (fixed + n_moe * cfg["n_routed_experts"] * expert,
            fixed + n_moe * used * expert)


def scores_required(cfg, traffic):
    """(FLOPs, bytes) the causal scores of the latent layers of one training
    step require, what the flash kernels under `mla` run: QK^T and PV forward,
    dV, dP, dQ and dK backward (the backward kernel forms S again: not
    counted), over keys and values of 256; bytes: q, k, v and o and their
    gradients once each (bf16).  FLOPs bound it by far."""
    tokens = traffic["batch"] * traffic["seq_len"]
    parts = forward_flops_per_token(cfg, traffic["seq_len"])
    H = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    rows = tokens * H * (2 * qk + 2 * cfg["v_head_dim"]) * 2
    return 3 * tokens * parts["mla_scores"], _layers(cfg)[0] * 2 * rows


def mla_required(cfg, traffic):
    """(FLOPs, bytes) the latent-attention layers of one training step
    require, what runs under the `mla` scope: the five projections (the query
    latent's two among them) and the causal scores, forward and both
    gradients; bytes: the weights read in each direction and their gradients
    written, q, k, v and o and their gradients once each (bf16).  FLOPs bound
    it."""
    tokens = traffic["batch"] * traffic["seq_len"]
    parts = forward_flops_per_token(cfg, traffic["seq_len"])
    flops = 3 * tokens * (parts["mla_projections"] + parts["mla_scores"])
    weights = _layers(cfg)[0] * 3 * _mla_weights(cfg) * 2
    return flops, weights + scores_required(cfg, traffic)[1]


def experts_required(cfg, traffic):
    """(FLOPs, bytes) the routed experts held here require of one training
    step, what runs under `moe.experts`: gate, up and down for the units that
    land on held experts (their expectation under uniform routing), forward
    and both gradients; bytes: the held experts' weights read in each
    direction and their gradients written, the units' rows in and out of each
    product once in each direction (bf16)."""
    tokens = traffic["batch"] * traffic["seq_len"]
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    units = (tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
             / cfg["published"]["n_routed_experts"])
    layers = _layers(cfg)[2]
    flops = 3 * layers * units * 3 * 2 * D * F
    weights = cfg["n_routed_experts"] * 3 * D * F * 2
    rows = units * (2 * D + 3 * F) * 2
    return flops, layers * (3 * weights + 2 * rows)
