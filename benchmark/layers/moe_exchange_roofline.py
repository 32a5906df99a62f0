"""`moe_exchange_roofline` (collectives): the least time the interconnect
could take for the bytes a chip has to send in a step's exchanges, over
`moe_exchange_ms`.  The bytes are the routing's, not the implementation's
(`flops/<config>.py:exchange_required` on the counter `moe_exchange_rows`, the
units a chip's routers sent to other chips' experts in a layer's exchange as
the timed steps counted them: a row of the hidden width in bfloat16, four
exchanges a layer; no row a pass sends empty, no row sent a second time for
the backward pass); the peak is `peaks.json`'s `ici_bits_per_s` / 8, all of a
chip's links in one direction.  `None` where `moe_exchange_ms` is, or the
configuration's flops file has no such function."""

import harness


def read(obs):
    ms = harness.load_module("layers", "moe_exchange_ms").read(obs)
    required = getattr(obs["flops"], "exchange_required", None)
    rows = obs["counters"].get("moe_exchange_rows")
    if not ms or not obs["peaks"] or required is None or rows is None:
        return None
    nbytes = required(obs["cfg"], obs["traffic"], rows)
    return 100.0 * nbytes / (obs["peaks"]["ici_bits_per_s"] / 8) / (ms / 1e3)
