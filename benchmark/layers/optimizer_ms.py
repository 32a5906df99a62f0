"""`optimizer_ms` (model step): device self time a step under the `optimizer`
scope of `make_train_step`: AdamW's pass over weights, gradients and both
moments, from the runner's join (`runners/step_tokens_adamw.py:scope_ms`).
`None` where it found nothing."""


def read(obs):
    return (obs["counters"].get("scope_ms") or {}).get("optimizer")
