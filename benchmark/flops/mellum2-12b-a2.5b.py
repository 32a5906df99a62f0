"""Operations and bytes the `mellum2-12b-a2.5b` configuration requires, from
shapes alone: matrix products only (2 FLOPs a multiply-accumulate); the full
layer's causal attention counted once (a query at position i meets i + 1
keys), a sliding layer's band counted once (it meets min(i + 1, window) keys:
960 of 1,024 a row on average at 8,192), no block of either rounded up to a
tile; the 8 experts a token is routed to and not the 64; the whole head.  No
recomputation, no row padded to a tile or to a pass of the exchange.  All of
it is a TOKEN's: `mfu` divides the host's rate by its chips, so a chip's
share of a step is a quarter of the step's.  The numerator of `mfu`,
`full_flash_roofline`, `swa_flash_roofline`, `moe_experts_roofline` and
`moe_exchange_roofline`.

The file's per-layer lists keep their published 28 entries; the first
`num_hidden_layers` of them are the layers that run.
"""


def _sliding(cfg):
    """[sliding?] of the layers that run."""
    n = cfg["num_hidden_layers"]
    assert set(cfg["mlp_layer_types"][:n]) == {"sparse"}
    return [kind == "sliding_attention" for kind in cfg["layer_types"][:n]]


def _mixer_weights(cfg):
    """Parameters of one mixer's four projections: q, k, v, output."""
    D, d = cfg["hidden_size"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * D * H * d + 2 * D * KV * d


def _keys_a_row(seq_len, window=None):
    """Keys a query meets, averaged over the positions of a sequence."""
    if window is None or window >= seq_len:
        return (seq_len + 1) / 2
    return (window * (window + 1) / 2 + (seq_len - window) * window) / seq_len


def forward_flops_per_token(cfg, seq_len):
    """{part: FLOPs} of one token's forward pass at sequence length
    `seq_len`, averaged over the positions of the sequence."""
    D, d, H = cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"]
    sliding = _sliding(cfg)
    layers = len(sliding)
    # QK^T and PV over heads of `d`: 2 * 2 * d FLOPs a key and head.
    scores = lambda window: H * 4 * d * _keys_a_row(seq_len, window)
    return {
        "projections": layers * 2 * _mixer_weights(cfg),
        "full_scores": sliding.count(False) * scores(None),
        "swa_scores": sliding.count(True) * scores(cfg["sliding_window"]),
        "router": layers * 2 * D * cfg["num_experts"],
        "experts": (layers * cfg["num_experts_per_tok"] * 3 * 2 * D
                    * cfg["moe_intermediate_size"]),
        "head": 2 * D * cfg["vocab_size"],
    }


def required_flops_per_sample(cfg, traffic):
    """Forward and backward passes of one token: every product has an input
    gradient and a weight (or second-operand) gradient of its own size."""
    return 3 * sum(forward_flops_per_token(cfg, traffic["seq_len"]).values())


def _chips(traffic):
    """Chips that share a step: the product of the mesh's axes."""
    n = 1
    for size in traffic["mesh"].values():
        n *= size
    return n


def parameters(cfg, ep=1):
    """(parameters a chip holds with the experts of a layer divided over `ep`
    chips and everything else whole on each, of them those one token uses)."""
    D = cfg["hidden_size"]
    layers = len(_sliding(cfg))
    expert = 3 * D * cfg["moe_intermediate_size"]
    fixed = (layers * (_mixer_weights(cfg) + 2 * D + D * cfg["num_experts"])
             + 2 * cfg["vocab_size"] * D + D)
    return (fixed + layers * cfg["num_experts"] // ep * expert,
            fixed + layers * cfg["num_experts_per_tok"] * expert)


def _scores_required(cfg, traffic, part, sliding):
    """A CHIP's share of a step: its rows of the batch."""
    tokens = traffic["batch"] * traffic["seq_len"] / _chips(traffic)
    flops = 3 * tokens * forward_flops_per_token(cfg, traffic["seq_len"])[part]
    # q and o at the query heads, k and v at the KV heads, each once forward,
    # and each one's gradient once (bf16).
    rows = _sliding(cfg).count(sliding) * 2 * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"])
    return flops, 2 * tokens * rows * cfg["head_dim"] * 2


def full_scores_required(cfg, traffic):
    """(FLOPs, bytes) the causal scores of the full layer of one training
    step require of one chip, what its flash kernels under `attn` outside
    `swa` run: QK^T and PV forward, dV, dP, dQ and dK backward (the backward
    kernel forms S again: not counted) over the causal triangle, no block
    rounded up; bytes: q and o at 32 heads, k and v at 4, and their
    gradients, once each.  FLOPs bound it by far."""
    return _scores_required(cfg, traffic, "full_scores", False)


def window_scores_required(cfg, traffic):
    """(FLOPs, bytes) the band of the sliding layers of one training step
    requires of one chip, what its flash kernels under `swa` run: as
    `full_scores_required` over min(i + 1, window) keys a row, no edge block
    counted whole.  FLOPs bound it too."""
    return _scores_required(cfg, traffic, "swa_scores", True)


def experts_required(cfg, traffic):
    """(FLOPs, bytes) the routed experts require of one chip in one training
    step, what runs under `moe.experts` there: gate, up and down for the
    units that land on its experts at their expectation under uniform routing
    (k * tokens / chips: with every expert on the host that is a chip's share
    of all of them), forward and both gradients; bytes: the held experts'
    weights read in each direction and their gradients written, the units'
    rows in and out of each product once in each direction (bf16)."""
    chips = _chips(traffic)
    units = (traffic["batch"] * traffic["seq_len"]
             * cfg["num_experts_per_tok"] / chips)
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = len(_sliding(cfg))
    flops = 3 * layers * units * 3 * 2 * D * F
    weights = cfg["num_experts"] // chips * 3 * D * F * 2
    rows = units * (2 * D + 3 * F) * 2
    return flops, layers * (3 * weights + 2 * rows)


def exchange_rows_uniform(cfg, traffic):
    """Routed units a chip sends to OTHER chips in one exchange of one layer
    under uniform routing: its k * tokens / chips units less the 1 / chips of
    them whose expert it holds itself."""
    chips = _chips(traffic)
    units = (traffic["batch"] * traffic["seq_len"]
             * cfg["num_experts_per_tok"] / chips)
    return units * (chips - 1) / chips


def exchange_required(cfg, traffic, rows_sent=None):
    """Bytes one chip has to SEND over the interconnect in one training step
    for the routing it was given: `rows_sent` units leave it for another
    chip's experts in a layer's exchange (the mean over the step's layers and
    the chips, as the program's routers decided; under uniform routing where
    none is given, `exchange_rows_uniform`), each a row of `hidden_size`
    bfloat16 numbers, and a layer needs four such exchanges a step: the rows
    out and the results back, the results' gradients out and the rows'
    gradients back.  The implementation's own choices are not counted: rows
    a pass sends empty, the rows sent out a second time for the backward
    pass (it keeps none of what arrived), the router's weights (4 bytes a
    unit) and the counts."""
    if rows_sent is None:
        rows_sent = exchange_rows_uniform(cfg, traffic)
    return (len(_sliding(cfg)) * 4 * rows_sent * cfg["hidden_size"] * 2)
