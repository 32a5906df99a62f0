"""`moe_ms` (model step): device self time a step under all `moe.*` scopes of
the step program (router, dispatch, experts, combine; forward, backward and
the forward recomputed under `remat="dots"`), from the runner's join of the
capture's `XLA Ops` events to the executable's `op_name`s
(`runners/step_tokens_adamw.py:scope_ms`).  `None` where that join found
nothing."""


def read(obs):
    found = obs["counters"].get("scope_ms") or {}
    parts = [ms for scope, ms in found.items() if scope.startswith("moe.")]
    return sum(parts) if parts else None
