"""Operations and bytes the `mixtral-8x7b` configuration requires, from
shapes alone: matrix products only (2 FLOPs a multiply-accumulate), causal
attention counted once (a query at position i meets i+1 keys), the two
experts a token is routed to and not the eight, no recomputation and no
capacity padding.  The numerator of `mfu` and of `flash_roofline`.
"""


def forward_flops_per_token(cfg, seq_len):
    """{part: FLOPs} of one token's forward pass at sequence length
    `seq_len`, averaged over the positions of the sequence."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layers = cfg["num_hidden_layers"]
    return {
        "attention_projections": layers * 2 * (2 * d * h * hd + 2 * d * kv * hd),
        # QK^T and PV: 2 products x 2 FLOPs x (seq_len + 1) / 2 keys on average.
        "attention_scores": layers * 2 * h * hd * (seq_len + 1),
        "router": layers * 2 * d * cfg["num_local_experts"],
        "experts": layers * cfg["num_experts_per_tok"] * 3 * 2 * d * f,
        "head": 2 * d * cfg["vocab_size"],
    }


def required_flops_per_sample(cfg, traffic):
    """Forward and backward passes of one token: every product has an input
    gradient and a weight (or second-operand) gradient of its own size, the
    first layer's included, since the embedding below it is trained."""
    return 3 * sum(forward_flops_per_token(cfg, traffic["seq_len"]).values())


def parameters(cfg):
    """(all parameters, parameters one token uses) of the configuration."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    shared = 2 * d * h * hd + 2 * d * kv * hd + d * e + 2 * d   # + router, norms
    expert = 3 * d * f
    ends = 2 * cfg["vocab_size"] * d + d                        # embed, head, norm
    layers = cfg["num_hidden_layers"]
    return (layers * (shared + e * expert) + ends,
            layers * (shared + k * expert) + ends)


def flash_required(cfg, traffic):
    """(FLOPs, bytes) the causal attention of one training step requires of
    the flash kernels, over all layers: forward QK^T and PV, backward dV, dP,
    dQ and dK (the scores a flash backward forms again are recomputation and
    do not count).  Bytes: Q, K, V and O read or written once in each
    direction at the widths the model has (K and V at their own head count),
    plus the gradients of the four."""
    b, n = traffic["batch"], traffic["seq_len"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    pairs = b * h * n * (n + 1) // 2
    flops = cfg["num_hidden_layers"] * 6 * 2 * hd * pairs
    elem = 2                                                    # bf16
    q_or_o, k_or_v = b * n * h * hd * elem, b * n * kv * hd * elem
    forward = 2 * q_or_o + 2 * k_or_v                # read Q K V, write O
    backward = 4 * q_or_o + 4 * k_or_v               # read Q K V O dO, write dQ dK dV
    return flops, cfg["num_hidden_layers"] * (forward + backward)
