"""The one generator of traffic.  A mix is a JSON file of parameters under
`traffic/`; everything here is drawn from `--seed`, so the same seed gives
the same inputs.  A training cell's traffic is its batches.

`generator: "images"`: `distinct_batches` host batches, rank-major
`(chips, per_chip_batch, H, W, C)`, cycled by the runner.  Pixels are seeded
bytes normalised to about zero mean and unit range, as a decoded and
normalised photograph is, looked up in a table of 256 values that the
type they are served in holds exactly: a tenth of a second for a batch of
128, where drawing normals and rounding them takes three.
`generator: "tokens"`: `distinct_batches` pairs of `(batch, seq_len)` int32
tokens and targets, uniform over the vocabulary.
"""

import numpy as np


def images(traffic, cfg, seed, chips, n_batches=None, per_chip=None):
    """[(x, y)]: x (chips, b, H, W, C) in `cfg["dtype"]`, y (chips, b) int32."""
    import ml_dtypes

    rng = np.random.default_rng(seed)
    n = traffic["distinct_batches"] if n_batches is None else n_batches
    b = traffic["per_chip_batch"] if per_chip is None else per_chip
    size, c = cfg["image_size"], cfg["in_channels"]
    dtype = np.dtype(getattr(ml_dtypes, cfg["dtype"], cfg["dtype"]))
    table = ((np.arange(256, dtype=np.float32) - 128.0) / 64.0).astype(dtype)
    bits = table.view(f"u{dtype.itemsize}")      # take() is fast on integers
    shape = (chips, b, size, size, c)
    out = []
    for _ in range(n):
        pixels = np.frombuffer(rng.bytes(int(np.prod(shape))), np.uint8)
        labels = rng.integers(0, cfg["num_classes"], (chips, b), dtype=np.int32)
        out.append((np.take(bits, pixels).view(dtype).reshape(shape), labels))
    return out


def tokens(traffic, cfg, seed, n_batches=None, batch=None, seq_len=None):
    """[(tokens, targets)]: (batch, seq_len) int32 each, on the host."""
    rng = np.random.default_rng(seed)
    n = traffic["distinct_batches"] if n_batches is None else n_batches
    shape = (traffic["batch"] if batch is None else batch,
             traffic["seq_len"] if seq_len is None else seq_len)
    return [(rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32),
             rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32))
            for _ in range(n)]
