"""The comparison that decides `correct`: the system against the plain
reference of its configuration, on seeded weights and a seeded sample, at
the published widths, outside the timed window.

Both sides hand over `(loss, logits, gradient pytree)` for the same
parameters and sample.  Each is reduced inside one jitted program to the
loss, the logits and the gradient norm of every leaf, so no second copy of
the gradients outlives its program.  The logits are compared row by row (a
token, an image): the relative L2 error of each row, and of those the 90th
percentile, so that a few rows that legitimately differ (a token that bf16
noise sent to another expert) do not decide, and a quarter of the rows (a
skipped expert) do.  The reference module states the tolerances and why
(`TOLERANCE`), and which leaves stack independent parts (`LEAF_AXES`:
leading axes to keep, e.g. layers x experts).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def leaf_norms(grads, keep_axes):
    """{leaf name: float32 norms}: one norm a leaf, or one for each index of
    the leaf's first `keep_axes[name]` axes."""
    out = {}
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        keep = keep_axes.get(name, 0)
        out[name] = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)),
                                     axis=tuple(range(keep, g.ndim))))
    return out


def _reduced(fn, params, sample, keep_axes):
    def program(p, s):
        loss, logits, grads = fn(p, s)
        return (loss.astype(jnp.float32), logits.astype(jnp.float32),
                leaf_norms(grads, keep_axes))

    loss, logits, norms = jax.jit(program)(params, sample)
    return (float(loss), np.asarray(logits),
            {k: np.asarray(v, np.float64) for k, v in norms.items()})


def _rel(a, b):
    return abs(a - b) / max(abs(a), abs(b), 1e-30)


def check(system_fn, reference_fn, params, sample, tolerance, keep_axes=None):
    """Run both sides and compare.  Returns the four differences, the two
    losses and `ok`; a value that is not finite is a failure."""
    keep_axes = keep_axes or {}
    s_loss, s_logits, s_norms = _reduced(system_fn, params, sample, keep_axes)
    r_loss, r_logits, r_norms = _reduced(reference_fn, params, sample, keep_axes)
    if sorted(s_norms) != sorted(r_norms):
        raise ValueError("system and reference differ in their leaves: "
                         f"{sorted(set(s_norms) ^ set(r_norms))}")
    total = lambda norms: math.sqrt(sum(float(np.sum(v * v))
                                        for v in norms.values()))
    worst_leaf, worst = "", 0.0
    for name, r in r_norms.items():
        d = float(np.max(np.abs(s_norms[name] - r)
                         / np.maximum(np.maximum(np.abs(r), np.abs(s_norms[name])),
                                      1e-30)))
        if not d <= worst:        # also catches NaN
            worst_leaf, worst = name, d
    rows = r_logits.reshape(-1, r_logits.shape[-1])
    row_err = (np.linalg.norm(s_logits.reshape(rows.shape) - rows, axis=1)
               / np.maximum(np.linalg.norm(rows, axis=1), 1e-30))
    found = {
        "logits_rel_p90": float(np.percentile(row_err, 90)),
        "loss_rel": _rel(s_loss, r_loss),
        "grad_norm_rel": _rel(total(s_norms), total(r_norms)),
        "leaf_norm_rel_max": worst,
    }
    ok = all(math.isfinite(found[k]) and found[k] <= tolerance[k]
             for k in tolerance)
    return {**found, "worst_leaf": worst_leaf, "loss_system": s_loss,
            "loss_reference": r_loss, "grad_norm_reference": total(r_norms),
            "ok": bool(ok)}
