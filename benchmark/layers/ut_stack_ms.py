"""`ut_stack_ms` (model step): device self time a step in the looped stack:
under the `attn`, `ffn` and `final_norm` scopes of the step program, every
layer application of every recurrent step, forward, backward and recomputed
alike, from the runner's join (`runners/step_tokens_looped.py:scope_ms`).
`None` where the join left none of the three."""

STACK = ("attn", "ffn", "final_norm")


def read(obs):
    found = obs["counters"].get("scope_ms") or {}
    parts = [found[scope] for scope in STACK if scope in found]
    return sum(parts) if parts else None
