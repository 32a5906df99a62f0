"""`moe_max_load` (model step): the routed units of the busiest expert over
the mean of its layer's experts (k * tokens / experts), the largest over the
layers: 1 is perfect balance.  From the program's own router on the first
timed batch (`llama.expert_unit_counts`, read once in set-up).  A dropless
step does the same work whatever this reads; a step whose time follows it
has tiles that follow the expert loads."""


def read(obs):
    counts = obs["counters"].get("expert_unit_counts")
    if not counts:
        return None
    return max(max(layer) * len(layer) / sum(layer) for layer in counts)
