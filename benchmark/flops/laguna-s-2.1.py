"""Operations and bytes the `laguna-s-2.1` configuration requires, from shapes
alone: matrix products only (2 FLOPs a multiply-accumulate); a full layer's
causal attention counted once (a query at position i meets i + 1 keys), a
sliding layer's band counted once (it meets min(i + 1, window) keys: 504 of
512 a row on average at 16,384), no block of either rounded up to a tile; the
routed units that land on the experts held here at their expectation under
uniform routing (k * held / published experts a token), the shared expert,
the slice of the head held here.  No recomputation, no row padded to a tile.
The numerator of `mfu`, `full_flash_roofline`, `swa_flash_roofline` and
`moe_experts_roofline`.

The file's per-layer lists keep their published 48 entries; the first
`num_hidden_layers` of them are the layers that run.
"""


def _layers(cfg):
    """[(query heads, sliding?, dense FFN?)] of the layers that run."""
    n = cfg["num_hidden_layers"]
    return [(heads, kind == "sliding_attention", ffn == "dense")
            for heads, kind, ffn in zip(
                cfg["num_attention_heads_per_layer"][:n],
                cfg["layer_types"][:n], cfg["mlp_layer_types"][:n])]


def _mixer_weights(cfg, heads):
    """Parameters of one mixer's five projections: q, k, v, gate, output."""
    D, d, KV = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    return D * heads * d + 2 * D * KV * d + D * heads + heads * d * D


def _keys_a_row(seq_len, window=None):
    """Keys a query meets, averaged over the positions of a sequence."""
    if window is None or window >= seq_len:
        return (seq_len + 1) / 2
    return (window * (window + 1) / 2 + (seq_len - window) * window) / seq_len


def forward_flops_per_token(cfg, seq_len):
    """{part: FLOPs} of one token's forward pass at sequence length
    `seq_len`, averaged over the positions of the sequence."""
    D, d = cfg["hidden_size"], cfg["head_dim"]
    F, E = cfg["moe_intermediate_size"], cfg["published"]["num_experts"]
    layers = _layers(cfg)
    n_moe = sum(not dense for _, _, dense in layers)
    held_a_token = cfg["num_experts_per_tok"] * cfg["num_experts"] / E
    # QK^T and PV over heads of `d`: 2 * 2 * d FLOPs a key and head.
    scores = lambda sliding, window: sum(
        heads * 4 * d * _keys_a_row(seq_len, window)
        for heads, s, _ in layers if s == sliding)
    return {
        "projections": sum(2 * _mixer_weights(cfg, h) for h, _, _ in layers),
        "full_scores": scores(False, None),
        "swa_scores": scores(True, cfg["sliding_window"]),
        "dense_ffn": (len(layers) - n_moe) * 3 * 2 * D
        * cfg["intermediate_size"],
        "router": n_moe * 2 * D * E,
        "routed_experts_held": n_moe * held_a_token * 3 * 2 * D * F,
        "shared_expert": n_moe * 3 * 2 * D
        * cfg["shared_expert_intermediate_size"],
        "head": 2 * D * cfg["vocab_size"],
    }


def required_flops_per_sample(cfg, traffic):
    """Forward and backward passes of one token: every product has an input
    gradient and a weight (or second-operand) gradient of its own size."""
    return 3 * sum(forward_flops_per_token(cfg, traffic["seq_len"]).values())


def parameters(cfg):
    """(parameters held on this chip, of them those one token uses)."""
    D = cfg["hidden_size"]
    layers = _layers(cfg)
    n_moe = sum(not dense for _, _, dense in layers)
    expert = 3 * D * cfg["moe_intermediate_size"]
    E = cfg["published"]["num_experts"]
    fixed = (sum(_mixer_weights(cfg, h) + 2 * D for h, _, _ in layers)
             + (len(layers) - n_moe) * 3 * D * cfg["intermediate_size"]
             + n_moe * (D * E + 3 * D * cfg["shared_expert_intermediate_size"])
             + 2 * cfg["vocab_size"] * D + D)
    used = cfg["num_experts_per_tok"] * cfg["num_experts"] / E
    return (fixed + n_moe * cfg["num_experts"] * expert,
            fixed + n_moe * used * expert)


def _scores_required(cfg, traffic, part, sliding):
    tokens = traffic["batch"] * traffic["seq_len"]
    flops = 3 * tokens * forward_flops_per_token(cfg, traffic["seq_len"])[part]
    # q and o at the layer's heads, k and v at the KV heads the file gives,
    # each once forward, and each one's gradient once (bf16).
    rows = sum(2 * (heads + cfg["num_key_value_heads"])
               for heads, s, _ in _layers(cfg) if s == sliding)
    return flops, 2 * tokens * rows * cfg["head_dim"] * 2


def full_scores_required(cfg, traffic):
    """(FLOPs, bytes) the causal scores of the full layers of one training
    step require, what the flash kernels under `attn` outside `swa` run: QK^T
    and PV forward, dV, dP, dQ and dK backward (the backward kernel forms S
    again: not counted) over the causal triangle, no block rounded up; bytes:
    q and o at 48 heads, k and v at 8 (repeating them to the query heads is
    executed, not required), and their gradients, once each.  FLOPs bound it
    by far."""
    return _scores_required(cfg, traffic, "full_scores", False)


def window_scores_required(cfg, traffic):
    """(FLOPs, bytes) the band of the sliding layers of one training step
    requires, what the flash kernels under `swa` run: as
    `full_scores_required` over min(i + 1, window) keys a row, no edge block
    counted whole.  FLOPs bound it too, by less: 13.9 ms of the chip's peak
    against 4.9 ms of its HBM bandwidth at L = 16,384."""
    return _scores_required(cfg, traffic, "swa_scores", True)


def experts_required(cfg, traffic):
    """(FLOPs, bytes) the routed experts held here require of one training
    step, what runs under `moe.experts`: gate, up and down for the units that
    land on held experts (their expectation under uniform routing), forward
    and both gradients; bytes: the held experts' weights read in each
    direction and their gradients written, the units' rows in and out of each
    product once in each direction (bf16)."""
    tokens = traffic["batch"] * traffic["seq_len"]
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    units = (tokens * cfg["num_experts_per_tok"] * cfg["num_experts"]
             / cfg["published"]["num_experts"])
    layers = sum(not dense for _, _, dense in _layers(cfg))
    flops = 3 * layers * units * 3 * 2 * D * F
    weights = cfg["num_experts"] * 3 * D * F * 2
    rows = units * (2 * D + 3 * F) * 2
    return flops, layers * (3 * weights + 2 * rows)
