"""Operations and bytes the `falcon-h1-34b` configuration requires, from
shapes alone: matrix products only (2 FLOPs a multiply-accumulate); causal
attention counted once (a query at position i meets i + 1 keys, no block
rounded up); the state-space scan as its chunked form at the file's
`mamba_chunk_size` counts it, the causal half of the chunk-local products
alone; the slice of the head held here.  No recomputation, no row padded to
a tile, no convolution, norm or gate (they are no matrix products).  The
numerator of `mfu`, `full_flash_roofline` and `ssd_roofline`.
"""


def _ssm_widths(cfg):
    """(inner channels, the projection's outputs) of the state-space branch:
    [z | x | B | C | dt]."""
    inner = cfg["mamba_d_ssm"]
    shared = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return inner, 2 * inner + 2 * shared + cfg["mamba_n_heads"]


def _attn_weights(cfg):
    D, d = cfg["hidden_size"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return D * H * d + 2 * D * KV * d + H * d * D


def scan_flops_per_token(cfg):
    """FLOPs of one token of one layer's scan, forward, in the chunked form,
    averaged over a chunk's rows: row i of a chunk of Q meets i + 1 rows of
    it (the causal half with the diagonal, (Q + 1) / 2 on average) in a
    group's `C B^T` (N wide) and in a head's `M U` (P wide); it writes `u
    B^T` into the head's P x N state once and reads `S C` out of it once."""
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    met = (cfg["mamba_chunk_size"] + 1) / 2
    return 2 * (met * (G * N + H * P) + 2 * H * P * N)


def forward_flops_per_token(cfg, seq_len):
    """{part: FLOPs} of one token's forward pass at sequence length
    `seq_len`, averaged over the positions of the sequence."""
    D, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    inner, projected = _ssm_widths(cfg)
    return {
        "ffn": n * 3 * 2 * D * cfg["intermediate_size"],
        "ssm_projections": n * 2 * (D * projected + inner * D),
        "attn_projections": n * 2 * _attn_weights(cfg),
        # QK^T and PV over heads of d: 2 * 2 * d FLOPs a key and head.
        "full_scores": n * cfg["num_attention_heads"] * 4 * cfg["head_dim"]
        * (seq_len + 1) / 2,
        "scan": n * scan_flops_per_token(cfg),
        "head": 2 * D * cfg["vocab_size"],
    }


def required_flops_per_sample(cfg, traffic):
    """Forward and backward passes of one token: every product has an input
    gradient and a weight (or second-operand) gradient of its own size."""
    return 3 * sum(forward_flops_per_token(cfg, traffic["seq_len"]).values())


def parameters(cfg):
    """Parameters held on this chip; one token uses all of them."""
    D = cfg["hidden_size"]
    inner, projected = _ssm_widths(cfg)
    conved = projected - inner - cfg["mamba_n_heads"]
    ssm = (D * projected + inner * D + (cfg["mamba_d_conv"] + 1) * conved
           + 3 * cfg["mamba_n_heads"] + inner)
    layer = (_attn_weights(cfg) + ssm + 3 * D * cfg["intermediate_size"]
             + 2 * D)
    return cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * D + D


def full_scores_required(cfg, traffic):
    """(FLOPs, bytes) the causal scores of one training step require, what
    the flash kernels under `attn` run: QK^T and PV forward, dV, dP, dQ and
    dK backward (the backward kernel forms S again: not counted) over the
    causal triangle, no block rounded up; bytes: q and o at 20 heads, k and v
    at 4 (repeating them to the query heads would be executed, not
    required), and their gradients, once each (bf16).  FLOPs bound it."""
    tokens = traffic["batch"] * traffic["seq_len"]
    flops = 3 * tokens * forward_flops_per_token(
        cfg, traffic["seq_len"])["full_scores"]
    rows = cfg["num_hidden_layers"] * 2 * (cfg["num_attention_heads"]
                                           + cfg["num_key_value_heads"])
    return flops, 2 * tokens * rows * cfg["head_dim"] * 2


def ssd_required(cfg, traffic):
    """(FLOPs, bytes) the state-space scans of one training step REQUIRE,
    whatever implements them (what runs under `ssd`): the chunked form's
    products forward and both gradients, the causal half of the chunk-local
    ones alone; bytes a token and layer: x (bf16), B, C (bf16, a group's),
    dt (float32) read and y written forward; x, B, C, dt and y's gradient
    read and the four gradients written backward; the state that enters each
    chunk (H x P x N float32 a chunk of Q tokens) written once forward and
    read once backward.  Nothing chunk-local (the decay sums, C B^T, M)
    counts: a kernel keeps it on the chip.  Bytes bound it: 113 KB a token
    and layer at the published shapes, 3.70 GB and 4.5 ms of the HBM's peak
    a step of 8,192 tokens, against 0.47 TFLOP and 2.4 ms of the MXU's."""
    tokens = traffic["batch"] * traffic["seq_len"]
    n = cfg["num_hidden_layers"]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    inputs = 2 * H * P + 2 * 2 * G * N + 4 * H        # x, B, C, dt
    states = 4 * H * P * N / cfg["mamba_chunk_size"]
    each_way = inputs + 2 * H * P + states            # with y or its gradient
    flops = 3 * tokens * n * scan_flops_per_token(cfg)
    return flops, tokens * n * (2 * each_way + inputs)
