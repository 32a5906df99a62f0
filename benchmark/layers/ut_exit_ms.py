"""`ut_exit_ms` (model step): device self time a step under the `exit_gate`
scope: the gate's product at every recurrent step, the exit distribution, the
tokens' weights and the entropy term, forward and gradient, from the runner's
join (`runners/step_tokens_looped.py:scope_ms`).  `None` where it found
nothing."""


def read(obs):
    return (obs["counters"].get("scope_ms") or {}).get("exit_gate")
