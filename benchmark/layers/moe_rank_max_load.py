"""`moe_rank_max_load` (model step): the routed units the fullest rank's
experts were sent over the mean of the ranks, the largest over the timed
steps: 1 is perfect balance.  From the units the exchange's passes delivered
to every rank in each timed step (the step's own fourth result,
`llama.make_train_step(..., with_delivered=True)`, read after the window).  With experts sharded over `ep`
a step waits for its fullest rank: where `moe_max_load` says how lopsided the
experts are, this says what the step pays for it.  `None` where the program
counts no deliveries."""


def read(obs):
    return obs["counters"].get("moe_rank_max_load")
