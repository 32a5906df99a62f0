"""State-space duality (SSD): Mamba-2's selective state-space scan in its
chunked form, forward and a hand-written backward, as XLA products.

The recurrence, for one head with ``P`` channels and a state ``S`` (P x N,
float32, zero at the start), a decay that is a SCALAR a head and token, ``a_t
= exp(A dt_t)`` with ``A < 0`` and ``dt_t > 0``, and the write and read
vectors ``B_t`` and ``C_t`` (N wide) that the heads of a group share::

    S_t = a_t S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

(Dao and Gu, "Transformers are SSMs", arXiv:2405.21060).  A token-by-token
``lax.scan`` of it (:func:`ssd_recurrent`) is what
``benchmark/reference/falcon-h1-34b.py`` and the tests hold this file to;
nothing here runs one.

The chunked form (chunks of ``chunk`` tokens, 128 in Falcon-H1; ``G_i`` the
sum of ``A dt`` over the chunk's rows up to and with i, ``S0`` the state that
enters the chunk, ``u_j = dt_j x_j``)::

    M_ij = (C_i . B_j) exp(G_i - G_j)        (i >= j, else 0)
    Y    = M U + Diag(e^G) C S0^T
    S'   = e^{G_last} S0 + (U * e^{G_last - G})^T B

``C B^T`` is a group's (the heads of a group share it), ``M`` a head's.
Every exponent is a difference ``G_i - G_j`` with i >= j, or ``G_i`` itself,
so never positive: ``e^{-G}`` alone overflows float32 within a chunk of
strong decay.  ``M``, ``U`` and the chunk's own contribution to the state are
products over all chunks at once; the states that enter the chunks are
carried chunk to chunk (:func:`_entry_states`: a ``lax.scan`` of one
multiply-add of the state a chunk, the one loop here).  ``dt``, the decay
sums and the state are float32, and the products that read a state take it
as float32 at ``Precision.HIGHEST``; the other products take operands of the
inputs' type (``M`` and ``U`` rounded to it) and accumulate in float32.

The gradient is a hand-written rule (``jax.custom_vjp``): the backward pass
holds the inputs and the chunk-entry states, forms the chunk-local part again
(``G``, ``C B^T``, ``M``), runs the recurrence backward once over the chunks
(the cotangent of the state that leaves each chunk: the same loop, last
chunk first) and differentiates the products.  The forward rule names what a
caller's ``jax.checkpoint`` must keep so that the forward scan never runs
twice (``SSD_RESIDUAL_NAMES``, as ``ops.kda`` names its own): ``ssd_y``, the
output, (B, L, H, P) in the inputs' type, and ``ssd_state``, the state that
enters each chunk, (B, L / chunk, H, P, N) float32: at Falcon-H1-34B's 32
heads of 128 x 256 and chunks of 128, 268 MB a layer of 8,192 tokens beside
an output of 67 MB.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

CHUNK = 128

# What the forward rule names (``checkpoint_name``): the output, which the
# layer reads again, and the chunk-entry states, which the backward pass
# reads.  A policy that does not keep both runs the forward scan again.
SSD_RESIDUAL_NAMES = ("ssd_y", "ssd_state")

_F32 = jnp.float32
_EXACT = lax.Precision.HIGHEST


def n_chunks(seq_len: int, chunk: int = CHUNK) -> int:
    """Chunks one sequence of ``seq_len`` tokens runs (the last one padded)."""
    return -(-seq_len // chunk)


def _dot(spec, a, b, exact: bool = False):
    """An einsum accumulated in float32; ``exact``: float32 operands at the
    highest precision (the products that read a state)."""
    if exact:
        return jnp.einsum(spec, a.astype(_F32), b.astype(_F32),
                          precision=_EXACT, preferred_element_type=_F32)
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


def _local(x, dt, A, B, C):
    """The chunk-local part, every chunk at once.  x (b, c, q, g, r, p), dt
    (b, c, q, g, r) float32, A (g, r), B and C (b, c, q, g, n) -> the decay
    sums ``G`` and the chunk's last (float32), ``U`` in x's type, a group's
    ``C B^T`` and a head's ``exp(G_i - G_j)`` (0 above the diagonal), both
    float32 (b, c, g, [r,] q, k)."""
    Q = x.shape[2]
    cum = jnp.cumsum(A * dt, axis=2)
    u = (x.astype(_F32) * dt[..., None]).astype(x.dtype)
    cb = _dot("bcqgn,bckgn->bcgqk", C, B)
    rows = jnp.moveaxis(cum, 2, -1)                         # (b, c, g, r, q)
    seen = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(seen, rows[..., :, None] - rows[..., None, :],
                              -jnp.inf))
    return cum, cum[:, :, -1], u, cb, decay


def _to_end(cum, last):
    """exp(G_last - G_j): what a row's write is worth when the chunk ends."""
    return jnp.exp(last[:, :, None] - cum)


def _entry_states(last, written, reverse: bool = False):
    """The recurrence over the chunks, ``S[z + 1] = exp(last[z]) S[z] +
    written[z]`` from zero, carried chunk to chunk: -> the state that ENTERS
    each chunk, (b, c, g, r, p, n) float32 as ``written`` is; the products
    with a decay a head are float32 element by element, and every exponent
    is one chunk's ``last``, so never positive.  ``reverse``: the recurrence
    run backward, ``T[z - 1] = exp(last[z]) T[z] + written[z]`` from zero at
    the last chunk, the cotangent of the state that LEAVES each chunk from
    what each later chunk's reads add (``written`` then the cotangents of the
    entry states).  One pass over ``written`` and one over the result, 268 MB
    each a layer at Falcon-H1's 8,192 tokens, in as many steps as the
    sequence has chunks.  The body names its own scope: a loop inside a
    ``custom_vjp`` rule under ``jax.checkpoint`` keeps no name of its
    callers'."""
    def chunk(S, z):
        with jax.named_scope("ssd"):
            keep, wrote = z
            return jnp.exp(keep)[..., None, None] * S + wrote, S

    _, entered = lax.scan(chunk, jnp.zeros_like(written[:, 0]),
                          (jnp.moveaxis(last, 1, 0),
                           jnp.moveaxis(written, 1, 0)), reverse=reverse)
    return jnp.moveaxis(entered, 0, 1)


def _forward(x, dt, A, B, C, D):
    """-> y (x's shape and type, rounded once) and the chunk-entry states."""
    cum, last, u, cb, decay = _local(x, dt, A, B, C)
    M = (cb[:, :, :, None] * decay).astype(x.dtype)
    w = (u.astype(_F32) * _to_end(cum, last)[..., None]).astype(x.dtype)
    states = _entry_states(last, _dot("bckgrp,bckgn->bcgrpn", w, B))
    y = (_dot("bcgrqk,bckgrp->bcqgrp", M, u)
         + jnp.exp(cum)[..., None] * _dot("bcqgn,bcgrpn->bcqgrp", C, states,
                                          exact=True)
         + D[:, :, None] * x.astype(_F32))
    return y.astype(x.dtype), states


def _backward(x, dt, A, B, C, D, states, dy):
    """The cotangents of x, dt, A, B, C and D from the inputs, the
    chunk-entry states and y's cotangent: the recurrence over the chunks run
    once, last to first."""
    cum, last, u, cb, decay = _local(x, dt, A, B, C)
    xf, uf, dyf = x.astype(_F32), u.astype(_F32), dy.astype(_F32)
    Mf = cb[:, :, :, None] * decay
    e = jnp.exp(cum)
    # y's second term, Diag(e^G) C S0^T.
    dye = (dyf * e[..., None]).astype(x.dtype)
    dcum = e * jnp.sum(dyf * _dot("bcqgn,bcgrpn->bcqgrp", C, states,
                                  exact=True), axis=-1)
    dC = _dot("bcqgrp,bcgrpn->bcqgn", dye, states, exact=True)
    # The states: S' = e^{G_last} S0 + W^T B, last chunk first.
    leaving = _entry_states(last, _dot("bcqgrp,bcqgn->bcgrpn", dye, C),
                            reverse=True)
    dlast = jnp.exp(last) * jnp.sum(leaving * states, axis=(-2, -1))
    to_end = _to_end(cum, last)
    w = (uf * to_end[..., None]).astype(x.dtype)
    dw = _dot("bckgn,bcgrpn->bckgrp", B, leaving, exact=True)
    dB = _dot("bckgrp,bcgrpn->bckgn", w, leaving, exact=True)
    du = dw * to_end[..., None]
    worth = jnp.sum(dw * uf, axis=-1) * to_end
    dlast = dlast + jnp.sum(worth, axis=2)
    dcum = dcum - worth
    # y's first term, M U with M = (C B^T) * exp(G_i - G_j).
    dM = _dot("bcqgrp,bckgrp->bcgrqk", dy, u)
    du = du + _dot("bcgrqk,bcqgrp->bckgrp", Mf.astype(x.dtype), dy)
    through = dM * Mf                   # d/d(G_i - G_j), 0 above the diagonal
    dcum = dcum + jnp.moveaxis(jnp.sum(through, axis=-1)
                               - jnp.sum(through, axis=-2), -1, 2)
    dcb = jnp.sum(dM * decay, axis=3).astype(x.dtype)
    dC = dC + _dot("bcgqk,bckgn->bcqgn", dcb, B)
    dB = dB + _dot("bcgqk,bcqgn->bckgn", dcb, C)
    # G = cumsum(A dt) and U = dt x.
    dcum = dcum.at[:, :, -1].add(dlast)
    dla = jnp.flip(jnp.cumsum(jnp.flip(dcum, 2), axis=2), 2)
    ddt = jnp.sum(du * xf, axis=-1) + dla * A
    dA = jnp.sum(dla * dt, axis=(0, 1, 2))
    dx = (du * dt[..., None] + D[:, :, None] * dyf).astype(x.dtype)
    dD = jnp.sum(dyf * xf, axis=(0, 1, 2, 5))
    return dx, ddt, dA, dB.astype(B.dtype), dC.astype(C.dtype), dD


@jax.custom_vjp
def _ssd_chunks(x, dt, A, B, C, D):
    return _forward(x, dt, A, B, C, D)[0]


def _ssd_chunks_fwd(x, dt, A, B, C, D):
    y, states = _forward(x, dt, A, B, C, D)
    y, states = map(checkpoint_name, (y, states), SSD_RESIDUAL_NAMES)
    return y, (x, dt, A, B, C, D, states)


def _ssd_chunks_bwd(saved, dy):
    return _backward(*saved, dy)


_ssd_chunks.defvjp(_ssd_chunks_fwd, _ssd_chunks_bwd)


@jax.named_scope("ssd")
def ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
        C: jax.Array, D: jax.Array, chunk: int = CHUNK) -> jax.Array:
    """The scan over a sequence from a zero state.  x: (B, L, H, P) in the
    compute type; ``dt`` (B, L, H) float32, the step sizes, > 0; ``A`` (H,)
    float32, < 0; ``B`` and ``C`` (B, L, G, N), G dividing H, heads ``h`` of
    group ``h // (H / G)``; ``D`` (H,) float32, the skip.  Returns y (B, L,
    H, P).  L is padded to whole chunks here (a padded token writes nothing
    and decays nothing: dt = 0) and cropped again."""
    Bt, L, H, P = x.shape
    G = B.shape[2]
    R, nc = H // G, n_chunks(L, chunk)

    def chunks(a, *tail):
        a = jnp.pad(a, ((0, 0), (0, -L % chunk)) + ((0, 0),) * (a.ndim - 2))
        return a.reshape(Bt, nc, chunk, *tail)

    y = _ssd_chunks(chunks(x, G, R, P), chunks(dt.astype(_F32), G, R),
                    A.astype(_F32).reshape(G, R), chunks(B, G, B.shape[-1]),
                    chunks(C, G, C.shape[-1]), D.astype(_F32).reshape(G, R))
    return y.reshape(Bt, nc * chunk, H, P)[:, :L]


def conv_silu(x: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """The branch's way in: a causal depthwise convolution along the
    sequence, one filter and one bias a channel, then SiLU.  x (B, L, C), w
    (taps, C), bias (C,) -> ``silu(bias + sum_i w[i] * x_{t - taps + 1 +
    i})``, the last tap on the token itself; float32 inside, x's type out."""
    taps, L = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(_F32), ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + L] * w[i].astype(_F32) for i in range(taps))
    return jax.nn.silu(y + bias.astype(_F32)).astype(x.dtype)


def gated_norm(y: jax.Array, z: jax.Array, w: jax.Array, groups: int,
               eps: float) -> jax.Array:
    """The branch's way out: ``RMSNorm_group(y * silu(z)) * w``, the gate
    BEFORE the norm (``norm_before_gate`` false), the mean square over each
    of the ``groups`` groups of channels.  y, z (B, L, C), w (C,)."""
    g = y.astype(_F32) * jax.nn.silu(z.astype(_F32))
    by_group = g.reshape(*g.shape[:-1], groups, -1)
    by_group = by_group * lax.rsqrt(
        jnp.mean(by_group * by_group, axis=-1, keepdims=True) + eps)
    return (by_group.reshape(g.shape) * w).astype(y.dtype)


def ssd_recurrent(x, dt, A, B, C, D):
    """The same function token by token, float32: the recurrence as the
    module docstring writes it.  For tests; no program runs it."""
    Bt, L, H, P = x.shape
    G = B.shape[2]
    f = lambda a: jnp.moveaxis(a.astype(_F32), 1, 0)
    heads = lambda a: jnp.repeat(a, H // G, axis=2)     # (L, B, H, N)

    def step(S, t):
        x_t, dt_t, B_t, C_t = t
        S = (jnp.exp(A * dt_t)[..., None, None] * S
             + jnp.einsum("bhp,bhn->bhpn", dt_t[..., None] * x_t, B_t,
                          precision=_EXACT))
        return S, (jnp.einsum("bhpn,bhn->bhp", S, C_t, precision=_EXACT)
                   + D[:, None] * x_t)

    _, y = lax.scan(step, jnp.zeros((Bt, H, P, B.shape[-1]), _F32),
                    (f(x), f(dt), heads(f(B)), heads(f(C))))
    return jnp.moveaxis(y, 0, 1)
