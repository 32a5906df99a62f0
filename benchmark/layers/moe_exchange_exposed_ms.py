"""`moe_exchange_exposed_ms` (collectives): the part of `moe_exchange_ms`
during which no other operation ran on that device: what the expert layer's
grouped matmuls, gathers and scatter-adds do not hide
(`runners/step_tokens_ep.py:exchange_ms`).  A pass of the exchange hands its
rows to the experts that wait for them, so what is hidden is what the
compiler overlaps of its own accord.  `None` where `moe_exchange_ms` is."""


def read(obs):
    return (obs["counters"].get("exchange_ms") or {}).get("exposed")
