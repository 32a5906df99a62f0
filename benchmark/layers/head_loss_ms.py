"""`head_loss_ms` (model step): device self time a step under the `head_loss`
scope of the loss: the output head's products over the vocabulary, the
softmax passes and the loss, forward and gradient alike, from the runner's
join (`runners/step_tokens_adamw.py:scope_ms`).  `None` where it found
nothing."""


def read(obs):
    return (obs["counters"].get("scope_ms") or {}).get("head_loss")
