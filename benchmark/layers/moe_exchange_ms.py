"""`moe_exchange_ms` (collectives): time a step in which an instruction of
the expert layers' exchange was under way on a device, mean over the devices:
every all-to-all that `parallel.moe.exchange` issues under the scope
`moe.exchange`, rows and router weights out, results back, and the same for
the gradients, forward and backward, found by that scope through the runner's
join of the capture with the executable's `op_name`s
(`runners/step_tokens_ep.py:exchange_ms`), not by the instruction's name: on
the chip these instructions are named `all_to_all.N`, which
`trace_reduce.is_collective` (names that start with `all-to-all`) does not
match, and `ragged-all-to-all` it would not match either.  Synchronous
instructions count while they run on the TensorCore's line, asynchronous ones
from start to done.  `None` where the program has no such scope (a parent
without the exchange) or the join found nothing."""


def read(obs):
    return (obs["counters"].get("exchange_ms") or {}).get("under_way")
