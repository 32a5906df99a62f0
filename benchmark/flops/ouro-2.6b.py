"""Operations and bytes the `ouro-2.6b` configuration requires, from shapes
alone: matrix products only (2 FLOPs a multiply-accumulate), causal attention
counted once (a query at position i meets i+1 keys), every layer, the head and
the exit gate counted once for each of the `total_ut_steps` recurrent steps
(the loop is the model, not a recomputation), no recomputation.  The numerator
of `mfu` and of `ut_stack_roofline`.
"""


def forward_flops_per_token(cfg, seq_len):
    """{part: FLOPs} of one token's forward pass at sequence length
    `seq_len`, averaged over the positions of the sequence, all recurrent
    steps."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    applications = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    return {
        "attention_projections": applications * 2 * (2 * d * h * hd
                                                     + 2 * d * kv * hd),
        # QK^T and PV: 2 products x 2 FLOPs x (seq_len + 1) / 2 keys on average.
        "attention_scores": applications * 2 * h * hd * (seq_len + 1),
        "swiglu": applications * 3 * 2 * d * f,
        "head": cfg["total_ut_steps"] * 2 * d * cfg["vocab_size"],
        "exit_gate": cfg["total_ut_steps"] * 2 * d,
    }


def required_flops_per_sample(cfg, traffic):
    """Forward and backward passes of one token: every product has an input
    gradient and a weight (or second-operand) gradient of its own size, the
    first layer's included, since the embedding below it is trained."""
    return 3 * sum(forward_flops_per_token(cfg, traffic["seq_len"]).values())


def parameters(cfg):
    """(all parameters, parameters one token uses): the same, a dense model;
    each of a layer's is used `total_ut_steps` times."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layer = 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f + 4 * d
    ends = 2 * cfg["vocab_size"] * d + d + d + 1     # embed, head, norm, gate
    total = cfg["num_hidden_layers"] * layer + ends
    return total, total


def flash_required(cfg, traffic):
    """(FLOPs, bytes) the causal attention of one training step requires of
    the flash kernels, over all layer applications: forward QK^T and PV,
    backward dV, dP, dQ and dK; Q, K, V and O read or written once in each
    direction, plus the gradients of the four (as `flops/olmoe-1b-7b.py`
    counts them)."""
    b, n = traffic["batch"], traffic["seq_len"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    applications = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    pairs = b * h * n * (n + 1) // 2
    elem = 2                                                    # bf16
    q_or_o, k_or_v = b * n * h * hd * elem, b * n * kv * hd * elem
    return (applications * 6 * 2 * hd * pairs,
            applications * (6 * q_or_o + 6 * k_or_v))


def stack_required(cfg, traffic):
    """(FLOPs, bytes) the looped stack of one training step requires: the
    projections, scores and SwiGLU products of every layer application,
    forward and both gradients, nothing recomputed.  Bytes, in bf16: each
    layer's weights read once forward and once backward in each application
    and their gradients written once in each, the tokens' rows in and out of
    each product once in each direction.  The bound is FLOPs (intensity about
    1,600 FLOPs a byte against the chip's 240)."""
    tokens = traffic["batch"] * traffic["seq_len"]
    parts = forward_flops_per_token(cfg, traffic["seq_len"])
    flops = 3 * tokens * (parts["attention_projections"]
                          + parts["attention_scores"] + parts["swiglu"])
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    applications = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    elem = 2
    weights = (2 * d * h * hd + 2 * d * kv * hd + 3 * d * f) * elem
    # x, q, k, v, o, the projection's output; x, gate, up, hidden, output
    rows = tokens * (3 * d + 2 * h * hd + 2 * kv * hd + 3 * f) * elem
    return flops, applications * (3 * weights + 3 * rows)
