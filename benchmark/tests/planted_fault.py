"""`python3 benchmark/tests/planted_fault.py <fault> <run.py's arguments>`:
the harness's own run of the `falcon-h1-34b-l8k` cell with ONE fault planted
in the program, for the controls of the cell's `correct` (on the chip at the
published widths; `tests/test_falcon.py` runs three of them at the rehearsal
sizes).  `none` plants nothing.  The faults: `no_ssm_out`, `no_key` (a
multiplier dropped); `state_bf16`, `leaving_bf16`, `decay_bf16`, `dt_bf16`
(bfloat16 where the scan has float32: the state that enters a chunk, the
cotangent of the state that leaves it, the decay sums, the step sizes);
`unchanged`, `wrong_sign`, `a_leaf_left_out` (the step that is timed hands
its weights back as they were, steps up the gradient, skips `ssm_out`)."""

import os
import runpy
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCHMARK, os.path.dirname(BENCHMARK)]
fault, sys.argv = sys.argv[1], [os.path.join(BENCHMARK, "run.py"),
                                *sys.argv[2:]]

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.ops import ssd

# bfloat16's values in float32; by `reduce_precision`, which the compiler may
# not drop as it may a pair of converts
r = lambda a: jax.lax.reduce_precision(a.astype(jnp.float32), 8, 7)
carried, local, whole = ssd._entry_states, ssd._local, ssd._ssd_chunks
config, make = llama.Config, llama.make_train_step


def sums_rounded(*inputs):
    cum, _, u, cb, _ = local(*inputs)
    rows = jnp.moveaxis(r(cum), 2, -1)
    seen = jnp.tril(jnp.ones((rows.shape[-1],) * 2, bool))
    return (r(cum), r(cum)[:, :, -1], u, cb, jnp.exp(jnp.where(
        seen, rows[..., :, None] - rows[..., None, :], -jnp.inf)))


def unchanged(*a, **k):
    step = make(*a, **k)

    def faulty(params, state, tokens, targets):
        return (jax.tree.map(lambda p: p + 0, params), state,
                step(params, state, tokens, targets)[2])

    return jax.jit(faulty, donate_argnums=(0,))


def a_leaf_left_out(*a, **k):
    step = make(*a, **k)

    def faulty(params, state, tokens, targets):
        stepped, state, loss = step(params, state, tokens, targets)
        stepped["layers"][0]["ssm_out"] = params["layers"][0]["ssm_out"] + 0
        return stepped, state, loss

    return jax.jit(faulty, donate_argnums=(0,))


if fault == "no_ssm_out":
    llama.Config = lambda **kw: config(**{**kw, "ssm_out_multiplier": 1.0})
elif fault == "no_key":
    llama.Config = lambda **kw: config(**{**kw, "key_multiplier": 1.0})
elif fault == "state_bf16":
    ssd._entry_states = lambda last, wrote, reverse=False: (
        carried(last, wrote, True) if reverse else r(carried(last, wrote)))
elif fault == "leaving_bf16":
    ssd._entry_states = lambda last, wrote, reverse=False: (
        r(carried(last, wrote, True)) if reverse else carried(last, wrote))
elif fault == "decay_bf16":
    ssd._local = sums_rounded
elif fault == "dt_bf16":
    ssd._ssd_chunks = lambda x, dt, *rest: whole(x, r(dt), *rest)
elif fault == "unchanged":
    llama.make_train_step = unchanged
elif fault == "wrong_sign":
    llama.make_train_step = lambda *a, lr, **k: make(*a, lr=-lr, **k)
elif fault == "a_leaf_left_out":
    llama.make_train_step = a_leaf_left_out
else:
    assert fault == "none", fault
runpy.run_path(sys.argv[0], run_name="__main__")
