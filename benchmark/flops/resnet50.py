"""Operations the `resnet50` configuration requires, from shapes alone.

A copy of the arithmetic of `torchmpi_tpu/models/resnet.py:flops_per_image`
(2 FLOPs a multiply-accumulate; convolutions and the FC layer; batch norm,
ReLU and pooling are under 1% and are left out), kept here so that no later
PR can move the numerator of `mfu`.  He et al. give 3.8 GFLOPs for the
50-layer net counting a multiply-add as one (arXiv:1512.03385, Table 1):
about 4.1 G multiply-adds for v1.5, 8.2 GFLOP here.
"""


def _conv(h, w, k, cin, cout, stride):
    ho, wo = -(-h // stride), -(-w // stride)       # SAME padding
    return 2 * ho * wo * k * k * cin * cout, ho, wo


def forward_flops_per_image(cfg):
    """(whole forward pass, the stem convolution alone), per image."""
    size = cfg["image_size"]
    stem, h, w = _conv(size, size, 7, cfg["in_channels"], cfg["stem_width"], 2)
    total = stem
    h, w = -(-h // 2), -(-w // 2)                   # 3x3/2 max pool
    cin = cfg["stem_width"]
    for stage, (n, width) in enumerate(
            zip(cfg["stage_blocks"], cfg["stage_widths"], strict=True)):
        for block in range(n):
            stride = 2 if (stage > 0 and block == 0) else 1
            cout = width * cfg["bottleneck_expansion"]
            total += _conv(h, w, 1, cin, width, 1)[0]
            fl, h, w = _conv(h, w, 3, width, width, stride)   # v1.5
            total += fl
            total += _conv(h, w, 1, width, cout, 1)[0]
            if stride != 1 or cin != cout:
                total += 2 * h * w * cin * cout     # 1x1 projection shortcut
            cin = cout
    total += 2 * cin * cfg["num_classes"]
    return total, stem


def required_flops_per_sample(cfg, traffic):
    """Forward and backward passes of one image.  The backward pass of a
    matrix product is two products of the forward's size (input gradient and
    weight gradient); the stem has no input gradient to compute, since
    nothing is trained below the image."""
    del traffic                                     # no shape comes from it
    forward, stem = forward_flops_per_image(cfg)
    return 3 * forward - stem
