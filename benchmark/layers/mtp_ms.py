"""`mtp_ms` (model step): device self time a step of every instruction whose
scope path holds `mtp`, the outer name round a multi-token-prediction module:
its read of the embedding, the two norms, `W_eh`, the module's layer (latent
attention, router, held and shared experts), its final norm and its pass over
the vocabulary, forward, backward and recomputed alike.  The inner-scope
metrics (`mla_ms`, `moe_ms`, `head_loss_ms`) count the same instructions under
their own names, so this is a second cut of the same step and no part of a
sum with them.  From the runner's second join of the capture
(`runners/step_tokens_latent.py`, `instruction_scopes(hlo, ("mtp",))`);
`None` where it found nothing, or the program has no such scope."""


def read(obs):
    return (obs["counters"].get("mtp_scope_ms") or {}).get("mtp")
