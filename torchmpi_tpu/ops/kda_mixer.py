"""A KDA layer round its recurrence: the way in (the projections' outputs ->
q, k, v and the log-decay g) and the way out (the recurrence's output, normed
a head and gated), each one fused pass over its arrays forward and one
backward, with the recurrence (``ops.kda.kda``) between them.

The way in, a channel c of head h, row t (x the output of a projection, w a
causal filter of ``taps`` rows, the last on the token itself)::

    c_t = sum_i w[i, c] x[t - taps + 1 + i, c]      s_t = silu(c_t)
    q_t = s_t / sqrt(sum_head s_t^2 + 1e-6) * head_dim ** -0.5   (k: no scale)
    v_t = s_t
    g_t = -exp(a_log[h]) * softplus(f_t + dt_bias[c])

and the way out, o the recurrence's output and z the gate's pre-activation::

    y_t = round(o_t / sqrt(mean_head o_t^2 + eps) * o_norm)
    out_t = y_t * sigmoid(z_t)

Float32 inside; q, k, v, y and out are rounded to the inputs' type where
they stand above, g stays float32.

Which form runs is read from the head width alone, as ``ops.kda`` reads it
(``kda._takes_kernel``), on every backend (off the TPU through the Pallas
interpreter):

* ``head_dim % 128 == 0`` (Kimi Linear's 128): four Mosaic kernels a layer,
  ``kda_pre`` and ``kda_post`` and, the gradient written out by hand
  (``jax.custom_vjp``), ``kda_pre_bwd`` and ``kda_post_bwd``, on a grid of
  (batch, groups of heads, blocks of rows: ``_PRE_TILE``, ``_POST_TILE``), in
  the (B, L, H * D) layout the layer keeps and the recurrence's kernels
  read.  A step loads its block of each array once, takes its heads one
  after the other in a loop, and writes each result once; a
  head's norm is a sum along its own 128 lanes, the convolution's earlier
  rows are the last of a ``HALO``-row view of the same operand that ends
  where the block starts (zeros before the sequence), and no float32 copy,
  padded copy or broadcast scale stands in HBM.  The backward kernels form
  the forward values again from the inputs (the rule's residuals are its
  inputs alone: what a layer's checkpoint recomputes anyway), walk the row
  blocks from the last so that the convolution's gradient reaches back over
  a block's edge through a VMEM scratch, and sum the per-channel gradients
  (the filters', ``dt_bias``'s, ``a_log``'s, ``o_norm``'s) over the rows in
  an output block that stays in VMEM along that axis.
* other widths: :func:`pre_plain` and :func:`post_plain`, the same lines as
  ``jax.numpy`` with autodiff's gradient.  No configuration has such a
  width: this form is the kernels' oracle in ``tests/``.

A Mosaic kernel is not partitioned by the compiler: on a mesh of several
devices the caller runs :func:`kda_mixer` whole inside a ``shard_map`` over
the batch and the heads (``models.llama._kda_sharded``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda import _off_tpu, _takes_kernel, kda

# (rows of the sequence, heads) a grid step takes, the heads one after the
# other: the way in's two kernels and the way out's.
_PRE_TILE = (128, 8)
_POST_TILE = (256, 8)
HALO = 16               # rows of the view before a block: a bfloat16 tile's
_F32 = jnp.float32


# ------------------------------------------------------------ the plain form

def short_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """A causal depthwise convolution along the sequence, one filter a
    channel and no bias: x (B, L, C), w (taps, C) -> ``y_t = sum_i w[i] *
    x_{t - taps + 1 + i}``, the last tap on the token itself; float32."""
    taps, L = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(_F32), ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, i:i + L] * w[i].astype(_F32) for i in range(taps))


def pre_plain(xq, xk, xv, f, conv_q, conv_k, conv_v, a_log, dt_bias):
    """The way in as XLA's own operations; see :func:`kda_pre`."""
    B, L, _ = xq.shape
    H = a_log.shape[0]
    heads = lambda a: a.reshape(B, L, H, -1)
    branch = lambda x, w: heads(jax.nn.silu(short_conv(x, w)))
    unit = lambda y: y * lax.rsqrt(
        jnp.sum(y * y, axis=-1, keepdims=True) + 1e-6)
    sq = branch(xq, conv_q)
    q = (unit(sq) * sq.shape[-1] ** -0.5).astype(xq.dtype)
    k = unit(branch(xk, conv_k)).astype(xk.dtype)
    v = branch(xv, conv_v).astype(xv.dtype)
    g = (-jnp.exp(a_log)[:, None]
         * heads(jax.nn.softplus(f.astype(_F32) + dt_bias)))
    return tuple(a.reshape(B, L, -1) for a in (q, k, v, g))


def post_plain(o, z, o_norm, *, eps: float):
    """The way out as XLA's own operations; see :func:`kda_post`."""
    heads = lambda a: a.reshape(*a.shape[:2], -1, o_norm.shape[0])
    of = heads(o).astype(_F32)
    y = (of * lax.rsqrt(jnp.mean(of * of, axis=-1, keepdims=True) + eps)
         * o_norm).astype(o.dtype)
    gate = jax.nn.sigmoid(heads(z).astype(_F32))
    return (y.astype(_F32) * gate).astype(o.dtype).reshape(o.shape)


# --------------------------------------------------------------- the kernels
#
# A grid step holds a block of rows of a few heads' lanes of every operand
# and takes the heads one after the other in a loop (:func:`_each_head`: one
# head of code a kernel, so the width of a block costs no tracing), each a
# tile of rows x D float32 values (rows on sublanes, a head's channels on
# lanes).  The per-channel parameters of the way in come as one float32
# table of ``_TABLE`` rows, and their gradients leave as one: the three
# filters (taps rows each), ``-exp(a_log)`` a channel, ``dt_bias``.

_TABLE = 16


def _table_rows(taps: int):
    """Rows of the table: where branch j's filter starts, the decay's scale
    and its bias."""
    if 3 * taps + 2 > _TABLE:
        raise ValueError(f"a filter of {taps} taps: the table of per-channel "
                         f"parameters holds three of at most "
                         f"{(_TABLE - 2) // 3}")
    return (0, taps, 2 * taps), 3 * taps, 3 * taps + 1


def _sublane(n: int):
    return lax.broadcasted_iota(jnp.int32, (8, n), 0)


def _earlier_rows(x, before, taps: int):
    """``[x_{t - taps + 1}, ..., x_{t - 1}, x_t]`` for the rows t of a tile x
    (R, n) float32, ``before`` (8, n) the 8 rows that precede it."""
    out = []
    for s in range(taps - 1, 0, -1):
        xs = pltpu.roll(x, s, 0)
        top = jnp.where(_sublane(x.shape[1]) < s, pltpu.roll(before, s, 0),
                        xs[:8])
        out.append(jnp.concatenate([top, xs[8:]], axis=0))
    return out + [x]


def _later_rows(x, after, taps: int):
    """``[x_t, x_{t + 1}, ..., x_{t + taps - 1}]``, ``after`` (8, n) the 8
    rows that follow the tile."""
    R = x.shape[0]
    out = [x]
    for s in range(1, taps):
        xs = pltpu.roll(x, R - s, 0)
        low = jnp.where(_sublane(x.shape[1]) >= 8 - s,
                        pltpu.roll(after, 8 - s, 0), xs[R - 8:])
        out.append(jnp.concatenate([xs[:R - 8], low], axis=0))
    return out


def _branch(x, halo, w, first):
    """A projection's tile through its filter and the SiLU: the filter's
    inputs (a list of taps tiles), the convolution c, ``sigmoid(c)`` and
    ``silu(c)``.  ``halo`` holds the HALO rows before the tile (taken as
    zeros where ``first``), w the filter (taps, n) float32."""
    taps = w.shape[0]
    before = jnp.where(first, 0.0, halo[HALO - 8:].astype(_F32))
    xs = _earlier_rows(x.astype(_F32), before, taps)
    c = sum(w[i:i + 1] * xs[i] for i in range(taps))
    sig = jax.nn.sigmoid(c)
    return xs, c, sig, c * sig


def _unit(s):
    """A head's rows at unit length, and the scale that took them there."""
    r = lax.rsqrt(jnp.sum(s * s, axis=-1, keepdims=True) + 1e-6)
    return s * r, r


def _decay(f, bias):
    """``softplus(f + bias)``, which the decay's scale multiplies, and its
    argument."""
    ff = f.astype(_F32) + bias
    return jax.nn.softplus(ff), ff


def _each_head(lanes: int, D: int):
    """Runs ``body(n)`` for the lanes ``n`` of each head of a block in turn,
    in a loop: one head's code a kernel, whatever the block's width."""
    def run(body):
        def step(h, _):
            body(pl.ds(pl.multiple_of(h * D, D), D))
        lax.fori_loop(0, lanes // D, step, None)
    return run


def _pre_kernel(xq, hq, xk, hk, xv, hv, f, tab, q, k, v, g, *, D: int,
                taps: int):
    first = pl.program_id(2) == 0
    filters, a_row, b_row = _table_rows(taps)

    @_each_head(q.shape[1], D)
    def _(n):
        outs = []
        for x, halo, at in zip((xq, xk, xv), (hq, hk, hv), filters):
            outs.append(_branch(x[:, n], halo[:, n], tab[at:at + taps, n],
                                first)[-1])
        q[:, n] = (_unit(outs[0])[0] * D ** -0.5).astype(q.dtype)
        k[:, n] = _unit(outs[1])[0].astype(k.dtype)
        v[:, n] = outs[2].astype(v.dtype)
        g[:, n] = tab[a_row:a_row + 1, n] * _decay(
            f[:, n], tab[b_row:b_row + 1, n])[0]


def _pre_bwd_kernel(xq, hq, xk, hk, xv, hv, f, tab, dq, dk, dv, dg,
                    dxq, dxk, dxv, df, dtab, carry, *, D: int, taps: int):
    """The row blocks from the last: ``carry`` holds, a branch, the first 8
    rows of the convolution's cotangent of the block that follows."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        carry[...] = jnp.zeros_like(carry)
        dtab[...] = jnp.zeros_like(dtab)

    first = pl.program_id(2) == pl.num_programs(2) - 1
    filters, a_row, b_row = _table_rows(taps)
    rowsum = lambda a: jnp.sum(a, axis=0, keepdims=True)

    @_each_head(dq.shape[1], D)
    def _(n):
        for j, (x, halo, d, dx) in enumerate((
                (xq, hq, dq, dxq), (xk, hk, dk, dxk), (xv, hv, dv, dxv))):
            w = tab[filters[j]:filters[j] + taps, n]
            xs, c, sig, s = _branch(x[:, n], halo[:, n], w, first)
            ds = d[:, n].astype(_F32)
            if j == 0:
                ds = ds * D ** -0.5
            if j < 2:                       # q, k: s -> s * r, the unit scale
                unit, r = _unit(s)
                ds = r * (ds - unit * jnp.sum(ds * unit, -1, keepdims=True))
            dc = ds * sig * (1.0 + c * (1.0 - sig))
            for i in range(taps):
                at = filters[j] + i
                dtab[at:at + 1, n] += rowsum(dc * xs[i])
            later = _later_rows(dc, carry[j, :, n], taps)
            carry[j, :, n] = dc[:8]
            dx[:, n] = sum(w[i:i + 1] * later[taps - 1 - i]
                           for i in range(taps)).astype(dx.dtype)
        soft, ff = _decay(f[:, n], tab[b_row:b_row + 1, n])
        dgv = dg[:, n]
        dff = dgv * tab[a_row:a_row + 1, n] * jax.nn.sigmoid(ff)
        df[:, n] = dff.astype(df.dtype)
        dtab[a_row:a_row + 1, n] += rowsum(dgv * soft)
        dtab[b_row:b_row + 1, n] += rowsum(dff)


def _normed(o, w, eps: float):
    """A head's tile at unit mean square, the scale that took it there, and
    the normed output ``round(unit * w)`` as the gate multiplies it."""
    of = o.astype(_F32)
    r = lax.rsqrt(jnp.mean(of * of, axis=-1, keepdims=True) + eps)
    unit = of * r
    return unit, r, (unit * w).astype(o.dtype).astype(_F32)


def _post_kernel(o, z, w, out, *, D: int, eps: float):
    @_each_head(out.shape[1], D)
    def _(n):
        y = _normed(o[:, n], w[...], eps)[-1]
        out[:, n] = (y * jax.nn.sigmoid(z[:, n].astype(_F32))).astype(
            out.dtype)


def _post_bwd_kernel(o, z, w, dout, do, dz, dw, *, D: int, eps: float):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dw[...] = jnp.zeros_like(dw)

    @_each_head(do.shape[1], D)
    def _(n):
        unit, r, y = _normed(o[:, n], w[...], eps)
        gate = jax.nn.sigmoid(z[:, n].astype(_F32))
        d = dout[:, n].astype(_F32)
        dz[:, n] = (d * y * gate * (1.0 - gate)).astype(dz.dtype)
        dy = (d * gate).astype(do.dtype).astype(_F32)  # y's rounding, back
        dw[:, n] += jnp.sum(dy * unit, axis=0, keepdims=True)
        dn = dy * w[...]
        do[:, n] = (r * (dn - unit * jnp.mean(dn * unit, -1, keepdims=True))
                    ).astype(do.dtype)


def _heads_a_step(H: int, tile) -> int:
    return next(n for n in range(min(H, tile[1]), 0, -1) if H % n == 0)


def _row_block(L: int, tile):
    """Rows a grid step takes and ``L`` padded to whole steps."""
    R = min(tile[0], -(-L // HALO) * HALO)
    return R, -(-L // R) * R


def _kernel_call(kernel, name: str, H: int, tile, outs, *ins, reverse: bool,
                 scratch=(), interpret: bool):
    """One of the four kernels on the grid (B, H / heads a step, row
    blocks), last block first if ``reverse`` (the row axis then runs in
    turn).  ``ins`` and ``outs`` name what each operand is: ``"sequence"``
    (B, L, H * D), a block of rows and a step's heads' lanes; ``"halo"``, the
    HALO rows of a sequence operand that end where the block starts (the
    first block's: any, its kernel takes zeros); ``"table"`` (rows, H * D)
    and, as a result, (B, rows, H * D) summed over the grid's row axis;
    ``"head"`` (1, D), whole."""
    B, L, C = ins[0][1].shape
    D, lanes = C // H, C // H * _heads_a_step(H, tile)
    R = _row_block(L, tile)[0]
    N = L // R
    at = (lambda n: N - 1 - n) if reverse else (lambda n: n)

    def spec(kind, a, out=False):
        if kind == "sequence":
            return pl.BlockSpec((None, R, lanes),
                                lambda b, h, n: (b, at(n), h))
        if kind == "halo":
            return pl.BlockSpec(
                (None, HALO, lanes), lambda b, h, n: (
                    b, jnp.maximum(at(n) * (R // HALO) - 1, 0), h))
        if kind == "head":
            return pl.BlockSpec(a.shape, lambda b, h, n: (0, 0))
        if out:
            return pl.BlockSpec((None, a.shape[1], lanes),
                                lambda b, h, n: (b, 0, h))
        return pl.BlockSpec((a.shape[0], lanes), lambda b, h, n: (0, h))

    return pl.pallas_call(
        kernel,
        grid=(B, C // lanes, N),
        in_specs=[spec(kind, a) for kind, a in ins],
        out_specs=[spec(kind, a, True) for kind, a in outs],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for _, a in outs],
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary" if reverse else "parallel")),
        interpret=interpret,
        name=name,              # the kernel's name in the compiled program
    )(*(a for _, a in ins))


def _like(a, dtype=None):
    return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype)


def _with_halos(*xs):
    return [pair for x in xs for pair in (("sequence", x), ("halo", x))]


# Jitted, as ``ops.kda``'s calls are and for its reason: a program's layers,
# the pass a checkpoint replays and the reference check share one trace and
# one lowering of each body.
@functools.partial(jax.jit, static_argnames=("H", "taps", "interpret"))
def _pre_call(xq, xk, xv, f, table, *, H: int, taps: int, interpret: bool):
    """``kda_pre``: (B, L, H * D) each, L whole row blocks, and the table ->
    q, k, v in their type and g float32."""
    D = xq.shape[2] // H
    return _kernel_call(
        functools.partial(_pre_kernel, D=D, taps=taps), "kda_pre", H,
        _PRE_TILE, [("sequence", _like(x)) for x in (xq, xk, xv)]
        + [("sequence", _like(f, _F32))],
        *_with_halos(xq, xk, xv), ("sequence", f), ("table", table),
        reverse=False, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("H", "taps", "interpret"))
def _pre_bwd_call(xq, xk, xv, f, table, dq, dk, dv, dg, *, H: int, taps: int,
                  interpret: bool):
    """``kda_pre_bwd``: the cotangents of q, k, v and g -> those of the four
    inputs and of the table (summed over the batch)."""
    B, _, C = xq.shape
    D = C // H
    *grads, dtable = _kernel_call(
        functools.partial(_pre_bwd_kernel, D=D, taps=taps), "kda_pre_bwd", H,
        _PRE_TILE, [("sequence", _like(x)) for x in (xq, xk, xv, f)]
        + [("table", jax.ShapeDtypeStruct((B, *table.shape), _F32))],
        *_with_halos(xq, xk, xv), ("sequence", f), ("table", table),
        *(("sequence", d) for d in (dq, dk, dv, dg)), reverse=True,
        scratch=[pltpu.VMEM((3, 8, D * _heads_a_step(H, _PRE_TILE)), _F32)],
        interpret=interpret)
    return (*grads, jnp.sum(dtable, axis=0))


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _post_call(o, z, w, *, eps: float, interpret: bool):
    """``kda_post``: o, z (B, L, H * D), L whole row blocks, w (1, D)."""
    D = w.shape[1]
    return _kernel_call(
        functools.partial(_post_kernel, D=D, eps=eps), "kda_post",
        o.shape[2] // D, _POST_TILE, [("sequence", _like(o))], ("sequence", o),
        ("sequence", z), ("head", w), reverse=False, interpret=interpret)[0]


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _post_bwd_call(o, z, w, dout, *, eps: float, interpret: bool):
    """``kda_post_bwd``: the cotangent of the gated output -> those of o, z
    and w."""
    B, _, C = o.shape
    D = w.shape[1]
    do, dz, dw = _kernel_call(
        functools.partial(_post_bwd_kernel, D=D, eps=eps), "kda_post_bwd",
        C // D, _POST_TILE, [("sequence", _like(o)), ("sequence", _like(z)),
                 ("table", jax.ShapeDtypeStruct((B, 1, C), _F32))],
        ("sequence", o), ("sequence", z), ("head", w), ("sequence", dout),
        reverse=True, interpret=interpret)
    return do, dz, jnp.sum(dw.reshape(-1, D), axis=0, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _pre(xq, xk, xv, f, table, H: int, taps: int):
    return _pre_call(xq, xk, xv, f, table, H=H, taps=taps,
                     interpret=_off_tpu())


def _pre_fwd(xq, xk, xv, f, table, H, taps):
    return _pre(xq, xk, xv, f, table, H, taps), (xq, xk, xv, f, table)


def _pre_bwd(H, taps, saved, cotangents):
    return _pre_bwd_call(*saved, *cotangents, H=H, taps=taps,
                         interpret=_off_tpu())


_pre.defvjp(_pre_fwd, _pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _post(o, z, w, eps: float):
    return _post_call(o, z, w, eps=eps, interpret=_off_tpu())


def _post_fwd(o, z, w, eps):
    return _post(o, z, w, eps), (o, z, w)


def _post_bwd(eps, saved, dout):
    return _post_bwd_call(*saved, dout, eps=eps, interpret=_off_tpu())


_post.defvjp(_post_fwd, _post_bwd)


def _padded_rows(L: int, tile):
    """Pads a (B, L, C) array with zero rows to whole row blocks."""
    more = _row_block(L, tile)[1] - L
    return lambda a: jnp.pad(a, ((0, 0), (0, more), (0, 0)))


# ------------------------------------------------------------- the two ways

def kda_pre(xq, xk, xv, f, conv_q, conv_k, conv_v, a_log, dt_bias):
    """The way in.  xq, xk, xv: the q, k and v projections' outputs (B, L, H
    * D) and ``f`` the decay's low-rank projection, in the compute type;
    ``conv_*`` (taps, H * D) the three filters; ``a_log`` (H,) and
    ``dt_bias`` (H * D,) float32.  Returns q, k, v (B, L, H * D) in the
    compute type, q and k of unit length a head and q scaled by ``D **
    -0.5``, and g float32, as :func:`ops.kda.kda` takes them."""
    H, D = a_log.shape[0], xq.shape[2] // a_log.shape[0]
    if not _takes_kernel(D):
        return pre_plain(xq, xk, xv, f, conv_q, conv_k, conv_v, a_log, dt_bias)
    taps, L = conv_q.shape[0], xq.shape[1]
    b_row = _table_rows(taps)[-1]
    table = jnp.concatenate(
        [w.astype(_F32) for w in (conv_q, conv_k, conv_v)]
        + [jnp.repeat(-jnp.exp(a_log), D)[None], dt_bias.astype(_F32)[None],
           jnp.zeros((_TABLE - b_row - 1, H * D), _F32)])
    outs = _pre(*map(_padded_rows(L, _PRE_TILE), (xq, xk, xv, f)), table, H,
                taps)
    return tuple(a[:, :L] for a in outs)


def kda_post(o, z, o_norm, *, eps: float):
    """The way out.  ``o`` (B, L, H * D) the recurrence's output and ``z``
    the gate's pre-activation, in the compute type; ``o_norm`` (D,) float32.
    Returns ``rms_norm(o a head) * sigmoid(z)`` in the compute type."""
    if not _takes_kernel(o_norm.shape[0]):
        return post_plain(o, z, o_norm, eps=eps)
    L = o.shape[1]
    pad = _padded_rows(L, _POST_TILE)
    return _post(pad(o), pad(z), o_norm.astype(_F32)[None], eps)[:, :L]


def kda_mixer(xq, xk, xv, f, beta, z, conv_q, conv_k, conv_v, a_log, dt_bias,
              o_norm, *, eps: float):
    """A KDA layer between its projections: the way in, the recurrence
    (scope ``kda``) and the way out, (B, L, H * D) throughout; ``beta`` (B,
    L, H) float32.  Every step is a channel's or a head's own, so on a mesh
    it runs whole on a device's rows and heads
    (``models.llama._kda_sharded``)."""
    heads = lambda a: a.reshape(*a.shape[:2], a_log.shape[0], -1)
    q, k, v, g = kda_pre(xq, xk, xv, f, conv_q, conv_k, conv_v, a_log,
                         dt_bias)
    o = kda(heads(q), heads(k), heads(v), heads(g), beta)
    return kda_post(o.reshape(xq.shape), z, o_norm, eps=eps)
