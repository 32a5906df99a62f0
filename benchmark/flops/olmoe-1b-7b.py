"""Operations and bytes the `olmoe-1b-7b` configuration requires, from shapes
alone: matrix products only (2 FLOPs a multiply-accumulate), causal attention
counted once (a query at position i meets i+1 keys), the eight experts a token
is routed to and not the sixty-four, no recomputation and no padding of an
expert's rows to a tile.  The numerator of `mfu` and of
`moe_experts_roofline`.
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
        "router": layers * 2 * d * cfg["num_experts"],
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
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    # projections, router, the two block norms, the q and k norms
    shared = (2 * d * h * hd + 2 * d * kv * hd + d * e + 2 * d
              + h * hd + kv * hd)
    expert = 3 * d * f
    ends = 2 * cfg["vocab_size"] * d + d                        # embed, head, norm
    layers = cfg["num_hidden_layers"]
    return (layers * (shared + e * expert) + ends,
            layers * (shared + k * expert) + ends)


def flash_required(cfg, traffic):
    """(FLOPs, bytes) the causal attention of one training step requires of
    the flash kernels, over all layers: forward QK^T and PV, backward dV, dP,
    dQ and dK; Q, K, V and O read or written once in each direction, plus the
    gradients of the four (as `flops/mixtral-8x7b.py` counts them)."""
    b, n = traffic["batch"], traffic["seq_len"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    pairs = b * h * n * (n + 1) // 2
    flops = cfg["num_hidden_layers"] * 6 * 2 * hd * pairs
    elem = 2                                                    # bf16
    q_or_o, k_or_v = b * n * h * hd * elem, b * n * kv * hd * elem
    return flops, cfg["num_hidden_layers"] * (6 * q_or_o + 6 * k_or_v)


def experts_required(cfg, traffic):
    """(FLOPs, bytes) the three expert products (gate, up, down) of one
    training step require, over all layers, forward and backward: each of the
    k * tokens routed units meets one expert's three matrices, and each
    product has an input gradient and a weight gradient of its own size.
    Bytes, in bf16: forward, each expert's three matrices read once, the
    units' rows read for gate and up, the hidden rows written twice and read
    once, the output rows written; backward, every one of those read again
    and its gradient written, the weights' gradients written once.  The
    bound is FLOPs (intensity about 1,000 FLOPs a byte against the chip's
    240)."""
    tokens = traffic["batch"] * traffic["seq_len"]
    units = tokens * cfg["num_experts_per_tok"]
    d, f, e = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"]
    layers = cfg["num_hidden_layers"]
    flops = layers * 3 * units * 3 * 2 * d * f
    elem = 2
    weights = e * 3 * d * f * elem
    rows = units * (2 * d + 3 * f) * elem      # x in, y out; gate, up, hidden
    forward = weights + rows
    backward = weights + 2 * rows + weights    # read W and rows, write grads
    return flops, layers * (forward + backward)
