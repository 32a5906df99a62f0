"""Pallas kernel tests (interpreter path on the CPU mesh; the same kernel
compiles on TPU — bench.py exercises that)."""

import collections
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.ops import flash_attention
from torchmpi_tpu.ops.flash_attention import (
    _bwd_form, _flash_bh, _flash_bh_bwd, _bwd_vmem_bytes, _pair_kind,
    flash_bwd_block, flash_fwd_block)
from torchmpi_tpu.parallel import sequence as seq

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py


def _qkv(B=2, L=64, H=4, D=16, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, L, H, D), jnp.float32)
                 for _ in range(3))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        want = jax.vmap(lambda q, k, v: seq.full_attention(q, k, v, causal=causal)
                        )(q, k, v)
        got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_uneven_blocks(self):
        """block sizes that tile L in different counts still agree."""
        q, k, v = _qkv(L=96)
        want = jax.vmap(lambda q, k, v: seq.full_attention(q, k, v, causal=True)
                        )(q, k, v)
        got = flash_attention(q, k, v, causal=True, block_q=32, block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_indivisible_seq_raises(self):
        q, k, v = _qkv(L=60)
        with pytest.raises(ValueError):
            flash_attention(q, k, v, block_q=16, block_k=16)

    def test_mismatched_shapes_raise(self):
        q, k, v = _qkv()
        with pytest.raises(ValueError):
            flash_attention(q, k[:, :, :2], v)

    def test_llama_flash_path_matches_full(self):
        from torchmpi_tpu.models import llama

        cfg = llama.tiny()
        params = llama.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, cfg.vocab, (2, 32)), jnp.int32)
        want = llama.apply(cfg, params, tokens, attn="full")
        got = llama.apply(cfg, params, tokens, attn="flash")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ flash backward

def _pallas_calls(jaxpr):
    """Names of the ``pallas_call``s of a jaxpr, in order, sub-jaxprs
    (scan, checkpoint, custom_vjp, pjit) included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _pallas_calls(sub)
    return names


def _reference(q, k, v, do, causal):
    """``o`` and ``(dq, dk, dv)`` of plain attention by autodiff, (B, L, H,
    D) layout: ``llama._causal_attention`` where causal and square,
    ``seq.full_attention`` (the same mask, rows >= cols) where not."""
    from torchmpi_tpu.models import llama

    if causal and q.shape == k.shape:
        scale = 1.0 / np.sqrt(q.shape[-1])
        ref = lambda q, k, v: llama._causal_attention(q, k, v, scale)
    else:
        ref = jax.vmap(
            lambda q, k, v: seq.full_attention(q, k, v, causal=causal))
    o, vjp = jax.vjp(ref, q, k, v)
    return o, vjp(do)


def _bh(x):
    """(B, L, H, D) -> (B*H, L, D), the kernels' layout."""
    B, L, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, L, D)


class TestFlashBackward:
    """The one ``flash_bwd`` kernel against the two streaming kernels it
    replaces (still the form past a VMEM budget) and against autodiff of
    plain attention, float32 on the interpreter."""

    @pytest.mark.parametrize("Lq,Lk,block_q,block_k,causal", [
        (64, 64, 16, 16, True), (64, 64, 16, 16, False),  # several blocks
        (96, 96, 32, 16, True), (96, 96, 16, 32, True),   # block_q != block_k
        (96, 96, 48, 16, False),
        (32, 96, 16, 32, True), (96, 32, 32, 16, True),   # Lq != Lk
        (197, 197, 197, 197, False),                      # ViT: one block
    ])
    def test_matches_two_kernels_and_autodiff(self, Lq, Lk, block_q, block_k,
                                              causal):
        q, do = _qkv(L=Lq)[0], _qkv(L=Lq, seed=1)[0]
        _, k, v = _qkv(L=Lk, seed=2)
        kw = dict(causal=causal, block_q=block_q, block_k=block_k,
                  interpret=True)
        o, lse = flash_fwd_block(_bh(q), _bh(k), _bh(v), **kw)
        delta = jnp.sum(_bh(do) * o, axis=-1, keepdims=True)
        args = (_bh(q), _bh(k), _bh(v), _bh(do), lse, delta)
        fused = _flash_bh_bwd(*args, **kw)
        two = _flash_bh_bwd(*args, **kw, vmem_budget=0)
        want_o, want = _reference(q, k, v, do, causal)
        np.testing.assert_allclose(np.asarray(o), np.asarray(_bh(want_o)),
                                   rtol=1e-5, atol=1e-5)
        for name, a, b, w in zip(("dq", "dk", "dv"), fused, two, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5, err_msg=name)
            np.testing.assert_allclose(np.asarray(a), np.asarray(_bh(w)),
                                       rtol=1e-5, atol=1e-5, err_msg=name)

    def test_custom_vjp_uses_it(self):
        """``jax.grad`` of ``flash_attention`` runs the one kernel and
        agrees with autodiff of plain attention."""
        q, k, v = _qkv()
        do = _qkv(seed=1)[0]
        f = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            block_q=16, block_k=32)
        got = jax.vjp(f, q, k, v)[1](do)
        for a, w in zip(got, _reference(q, k, v, do, True)[1]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                       rtol=1e-5, atol=1e-5)
        jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(f, *a[:3])[1](a[3]))(
            q, k, v, do)
        assert _pallas_calls(jaxpr.jaxpr) == ["flash_fwd", "flash_bwd"]

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_ring_call_external_lse_f32_out(self, dtype):
        """The ring's call: local Q (32 rows) against one K/V chunk of 64 at
        a time, the GLOBAL lse and delta supplied, float32 partials.  The
        chunks' dq summed and their dk, dv side by side are the gradients
        of attention over the whole K/V."""
        q = _qkv(L=32)[0]
        _, k, v = _qkv(L=128, seed=2)
        do = _qkv(L=32, seed=1)[0]
        q, k, v, do = (x.astype(dtype).astype(jnp.float32)
                       for x in (q, k, v, do))
        D = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        lse = jax.nn.logsumexp(s, axis=-1).reshape(-1, 32, 1)
        o, want = _reference(q, k, v, do, False)
        delta = jnp.sum(_bh(do) * _bh(o), axis=-1, keepdims=True)

        def chunks(**extra):
            out = [flash_bwd_block(
                _bh(q).astype(dtype), _bh(k)[:, c:c + 64].astype(dtype),
                _bh(v)[:, c:c + 64].astype(dtype), _bh(do).astype(dtype),
                lse, delta, causal=False, block_q=16, block_k=32,
                interpret=True, out_dtype=jnp.float32, **extra)
                for c in (0, 64)]
            assert all(g.dtype == jnp.float32 for part in out for g in part)
            return (out[0][0] + out[1][0],
                    jnp.concatenate([out[0][1], out[1][1]], axis=1),
                    jnp.concatenate([out[0][2], out[1][2]], axis=1))

        for a, w in zip(chunks(), want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(_bh(w)),
                                       rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("Lq,fused", [(65536, True), (131072, False)])
    def test_form_follows_shape(self, Lq, fused):
        """Past the VMEM budget (read from the shapes: a local chunk of
        about 80k rows at D=128) the two streaming kernels are the form."""
        x = jax.ShapeDtypeStruct((1, Lq, 128), jnp.bfloat16)
        row = jax.ShapeDtypeStruct((1, Lq, 1), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda *a: flash_bwd_block(
            *a, causal=True, interpret=True))(x, x, x, x, row, row)
        assert _pallas_calls(jaxpr.jaxpr) == (
            ["flash_bwd"] if fused else ["flash_bwd_dq", "flash_bwd_dkv"])

    def test_budget_is_the_threshold(self):
        x = jax.ShapeDtypeStruct((2, 64, 16), jnp.float32)
        row = jax.ShapeDtypeStruct((2, 64, 1), jnp.float32)
        need = (_bwd_vmem_bytes(16, 16, 16, jnp.float32, jnp.float32)
                + 2 * 64 * 16 * 4)          # and dq: 64 rows, two buffers

        def calls(budget):
            return _pallas_calls(jax.make_jaxpr(lambda *a: _flash_bh_bwd(
                *a, causal=True, block_q=16, block_k=16, interpret=True,
                vmem_budget=budget))(x, x, x, x, row, row).jaxpr)

        assert calls(need) == ["flash_bwd"]
        assert calls(need - 1) == ["flash_bwd_dq", "flash_bwd_dkv"]


# ------------------------------------------------------------ grouped queries

def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs (custom_vjp, pjit) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _bf16_ulp(x):
    """The spacing of bfloat16 at the magnitude of float32 ``x``."""
    return 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(jnp.abs(x), 1e-30))) - 7)


# H, KV, D, Dv, window, B: the Laguna layers' 72 and 48 heads over 8 with and
# without a window, every head its own K/V (Ouro, OLMoE; GLM's 256-wide), a
# latent layer's 192 and 128, alone and grouped, and a batch of 2.
GROUPED = [(72, 8, 128, 128, 24, 1), (72, 8, 128, 128, None, 1),
           (48, 8, 128, 128, 24, 1), (48, 8, 128, 128, None, 2),
           (16, 16, 128, 128, None, 2), (20, 20, 256, 256, None, 1),
           (4, 4, 192, 128, None, 2), (8, 2, 192, 128, 24, 2)]


@pytest.mark.parametrize("H,KV,D,Dv,window,B", GROUPED)
def test_kv_at_their_own_heads_against_kv_repeated(H, KV, D, Dv, window, B):
    """``flash_attention`` with K and V at ``KV`` heads, bfloat16, against
    the same kernels fed K and V repeated to the query heads (the form
    before PR 41): o and dq equal to the bit; dk and dv within one bfloat16
    ulp of the float32 sum over a group of the gradients a query head (equal
    to the bit where every head has its own); and the step's jaxpr holds the
    two kernels, their K and V operands at ``B * KV`` heads, and no
    broadcast of anything to q's size."""
    L, rep = 64, H // KV
    rng = np.random.RandomState(H + D)
    q, k, v, do = (jnp.asarray(rng.randn(B, L, h, d), jnp.bfloat16)
                   for h, d in ((H, D), (KV, D), (KV, Dv), (H, Dv)))
    ours = lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=32, block_k=32)
    both = lambda q, k, v, do: (lambda o, vjp: (o, *vjp(do)))(
        *jax.vjp(ours, q, k, v))
    o, dq, dk, dv = jax.jit(both)(q, k, v, do)
    assert o.shape == (B, L, H, Dv) and dk.shape == k.shape

    bh = lambda x: jnp.repeat(x, H // x.shape[2], axis=2).transpose(
        0, 2, 1, 3).reshape(B * H, L, x.shape[3])
    back = lambda x: x.reshape(B, H, L, -1).transpose(0, 2, 1, 3)
    how = dict(causal=True, block_q=32, block_k=32, interpret=True,
               window=window)

    @jax.jit
    def repeated(q, k, v, do):      # o, dq, and float32 dk, dv a QUERY head
        o, lse = _flash_bh(bh(q), bh(k), bh(v), **how)
        delta = jnp.sum(bh(do).astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1, keepdims=True)
        args = (bh(q), bh(k), bh(v), bh(do), lse, delta)
        _, k32, v32 = _flash_bh_bwd(*args, **how, out_dtype=jnp.float32)
        return (back(o), back(_flash_bh_bwd(*args, **how)[0]), back(k32),
                back(v32))

    want_o, want_dq, k32, v32 = repeated(q, k, v, do)
    np.testing.assert_array_equal(o, want_o)
    np.testing.assert_array_equal(dq, want_dq)
    for name, got, head in (("dk", dk, k32), ("dv", dv, v32)):
        want = jnp.sum(head.reshape(B, L, KV, rep, -1), axis=3)
        if rep == 1:
            np.testing.assert_array_equal(got, want.astype(jnp.bfloat16),
                                          err_msg=name)
        off = jnp.abs(got.astype(jnp.float32) - want)
        assert bool(jnp.all(off <= _bf16_ulp(want))), name

    eqns = list(_eqns(jax.make_jaxpr(both)(q, k, v, do).jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == ["flash_fwd", "flash_bwd"]
    for call in calls:
        assert [x.aval.shape[0] for x in call.invars[:3]] == [
            B * H, B * KV, B * KV]
    # dq, dk, dv: the K/V heads' own gradients leave the one kernel
    assert [x.aval.shape[0] for x in calls[1].outvars] == [
        B * H, B * KV, B * KV]
    assert not [e for e in eqns if e.primitive.name == "broadcast_in_dim"
                and e.outvars[0].aval.size >= q.size]


@pytest.mark.parametrize("form", ["group", "one", "streamed"])
def test_a_groups_dk_and_dv_in_every_backward_form(form):
    """Three query heads a K/V head: ``flash_bwd`` with the group's float32
    dk and dv in its VMEM, ``flash_bwd`` writing them a query head where
    those blocks do not fit the budget, and the streamed pair, the two
    summed over the group after the kernels; each form is the one the
    budget names, and all give the gradients of plain attention."""
    (B, L, H, D), KV = (2, 64, 6, 16), 2
    q, do = _qkv(L=L, H=H)[0], _qkv(L=L, H=H, seed=1)[0]
    _, k, v = _qkv(L=L, H=KV, seed=2)
    kw = dict(causal=True, block_q=16, block_k=16, interpret=True)
    sizes = (L, L, D, D, 16, 16, jnp.float32, jnp.float32, H // KV)
    budget = {"group": _bwd_form(*sizes, 2 ** 40)[1],
              "one": _bwd_form(*sizes, 2 ** 40)[1] - 1, "streamed": 0}[form]
    assert _bwd_form(*sizes, budget)[0] == form
    o, lse = flash_fwd_block(_bh(q), _bh(k), _bh(v), **kw)
    delta = jnp.sum(_bh(do) * o, axis=-1, keepdims=True)
    args = (_bh(q), _bh(k), _bh(v), _bh(do), lse, delta)
    jaxpr = jax.make_jaxpr(lambda *a: _flash_bh_bwd(
        *a, **kw, vmem_budget=budget))(*args)
    assert _pallas_calls(jaxpr.jaxpr) == (
        ["flash_bwd_dq", "flash_bwd_dkv"] if form == "streamed"
        else ["flash_bwd"])
    got = _flash_bh_bwd(*args, **kw, vmem_budget=budget)
    want_o, want = _reference(q, k, v, do, True)
    np.testing.assert_allclose(o, _bh(want_o), rtol=1e-5, atol=1e-5)
    for name, a, w in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == _bh(w).shape, name
        np.testing.assert_allclose(a, _bh(w), rtol=1e-5, atol=1e-5,
                                   err_msg=name)


@functools.lru_cache(maxsize=None)
def _frozen_kernels():
    """``tests/flash_attention_pr41.py``: the kernels before they had two
    bodies, by path (it is no test module and no package's), loaded once."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "flash_attention_pr41.py")
    spec = importlib.util.spec_from_file_location("flash_attention_pr41", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# name: query heads, K/V heads, L, Lk, causal, window, block_q, block_k, the
# backward's form, and the pairs of one head that run whole and masked.
# D = 64 and Dv = 32: a scale of 1/8, exact in every rounding (with another
# the CPU's compiler contracts ``s * scale - m`` into one rounding where no
# select stands between them, which the chip's vector unit cannot).
TWO_BODIES = {
    "causal, 4 blocks a side": (2, 2, 256, 256, True, None, 64, 64, "one",
                                6, 4),
    "causal, blocks that differ": (2, 2, 256, 256, True, None, 64, 128,
                                   "one", 2, 4),
    "window 512, tiles of 256": (2, 2, 2048, 2048, True, 512, 256, 256,
                                 "one", 7, 14),
    "window 512, tiles of 512": (2, 2, 2048, 2048, True, 512, 512, 512,
                                 "one", 0, 7),
    "four query heads a K/V head": (4, 1, 256, 256, True, None, 64, 64,
                                    "group", 6, 4),
    "a group, written a query head": (4, 1, 256, 256, True, None, 64, 64,
                                      "one", 6, 4),
    "a group, streamed": (4, 1, 256, 256, True, None, 64, 64, "streamed",
                          6, 4),
    "a window, streamed": (2, 2, 512, 512, True, 100, 64, 32, "streamed",
                           7, 35),
    "the ring's chunk": (2, 2, 128, 256, False, None, 64, 64, "one", 8, 0),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(TWO_BODIES))
def test_two_bodies_a_kernel_are_the_one_body_they_replace(case, dtype):
    """A pair no mask edge crosses runs a body without the in-block mask,
    q, k, v and do reach the products in their own dtype, the scale is taken
    on the Q tile and the forward keeps its row statistics on the lanes: o,
    lse, dq, dk and dv against the kernels of PR 41 (one body, the mask on
    every pair, float32 operands, the scale on the score block, (bq, 1)
    statistics; ``tests/flash_attention_pr41.py``), interpret mode, every
    backward form.  float32 inputs: equal to the bit.  bfloat16 inputs: p
    and ds stay float32 in the interpreter, as they did; q . k^T and
    do . v^T are now products of bfloat16 operands, which the CPU sums in
    another order (lse moves by 1e-7), so a result rounded to bfloat16 may
    land on the next value: inside one bfloat16 step of the parent's (and
    1e-5 of the largest entry, for sums that cancel), lse inside 1e-6.  The
    shapes hold pairs of all three kinds: whole, masked, not run, as the
    mask itself says, and ``_pair_kind`` names them so."""
    H, KV, L, Lk, causal, window, bq, bk, form, whole, masked = (
        TWO_BODIES[case])
    D, Dv = 64, 32
    runs, full = _pair_kind(causal, np.arange(0, L, bq)[:, None], bq,
                            np.arange(0, Lk, bk)[None], bk, window, L)
    back = np.arange(L)[:, None] - np.arange(Lk)[None]
    seen = ((back >= 0) & (back < (window or Lk)) if causal
            else np.ones((L, Lk), bool)).reshape(L // bq, bq, Lk // bk, bk)
    some, every = seen.any(axis=(1, 3)), seen.all(axis=(1, 3))
    np.testing.assert_array_equal(runs, some)       # True alone: not causal
    np.testing.assert_array_equal(full, every)
    assert (int(every.sum()), int((some & ~every).sum())) == (whole, masked)

    rng = np.random.RandomState(L + H)
    q, k, v, do = (jnp.asarray(rng.randn(*shape), dtype) for shape in (
        (H, L, D), (KV, Lk, D), (KV, Lk, Dv), (H, L, Dv)))
    how = dict(causal=causal, block_q=bq, block_k=bk, interpret=True,
               window=window)
    sizes = (L, Lk, D, Dv, bq, bk, dtype, dtype, H // KV)
    budget = {"group": 2 ** 40, "one": _bwd_form(*sizes, 2 ** 40)[1] - (
        1 if H > KV else 0), "streamed": 0}[form]
    assert _bwd_form(*sizes, budget)[0] == form

    frozen = _frozen_kernels()
    # both backward passes from the frozen forward's residuals: a step of
    # bfloat16 in o would move delta, and the gradients with it
    o, lse = frozen._flash_bh(q, k, v, **how)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    got, want = ((*fwd(q, k, v, **how), *bwd(q, k, v, do, lse, delta, **how,
                                             vmem_budget=budget))
                 for fwd, bwd in ((_flash_bh, _flash_bh_bwd),
                                  (frozen._flash_bh, frozen._flash_bh_bwd)))
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        if dtype == jnp.float32:
            np.testing.assert_array_equal(a, b, err_msg=name)
        elif a.dtype == jnp.float32:                       # lse
            np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=name)
        else:
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            step = _bf16_ulp(b) + 1e-5 * jnp.max(jnp.abs(b))   # sums near 0
            assert bool(jnp.all(jnp.abs(a - b) <= step)), name


@pytest.mark.parametrize("remat", ["dots", "none", "full"])
def test_train_step_forms_scores_once_each_way(remat):
    """The step of ``make_train_step(attn="flash")`` holds one forward and
    one backward kernel a layer (the two layers of ``moe_tiny`` are inlined,
    so the step's jaxpr holds each layer's own) under every remat policy:
    the layer's checkpoint keeps the kernel's ``o`` and ``lse``
    (``flash_attention.RESIDUAL_NAMES``) under ``"dots"`` and under
    ``"full"`` alike (``llama._wrap_remat``), so no policy replays the
    forward kernel."""
    from torchmpi_tpu.models import llama
    from torchmpi_tpu.parallel import mesh as pmesh

    cfg = llama.moe_tiny()
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = llama.make_train_step(cfg, mesh, attn="flash", remat=remat)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    calls = _pallas_calls(
        jax.make_jaxpr(step)(params, None, tokens, tokens).jaxpr)
    assert cfg.n_layers <= llama._INLINE_MAX_LAYERS
    assert sorted(calls) == (["flash_bwd"] * cfg.n_layers
                             + ["flash_fwd"] * cfg.n_layers)


def _grouped_products(jaxpr, found=None):
    """How many grouped matmuls a jaxpr holds, by form (megablox's jitted
    ``gmm`` and ``tgmm`` on one device, ``ragged_dot_general`` under GSPMD),
    how many flash kernels and how many ``checkpoint_name``s by name."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        kind = eqn.primitive.name
        if kind == "pallas_call":
            found[eqn.params["name"]] += 1          # megablox's have none
            continue
        if kind == "name" or (kind == "jit" and eqn.params["name"] in
                              ("gmm", "tgmm")):
            found[eqn.params["name"]] += 1
        elif kind == "ragged_dot_general":
            found[kind] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _grouped_products(sub, found)
    return found


@pytest.mark.parametrize("remat,formed", [("dots", 1), ("full", 2)])
@pytest.mark.parametrize("devices", [1, 2], ids=["gmm", "ragged_dot"])
def test_train_step_forms_each_grouped_product_once(devices, remat, formed):
    """The dropless sorted expert layer's step requires 9 grouped matmuls a
    layer (gate, up and down, each forward, for its rows' gradient and for
    its weights').  ``remat="dots"`` runs those and no other: the gate and up
    products carry names (``llama.GROUPED_DOT_NAMES``) that the policy keeps
    as the dots they are, whether megablox's ``gmm`` forms them (one device)
    or ``lax.ragged_dot`` (GSPMD), and nothing reads the down product's
    output again.  ``"full"`` keeps the flash kernel's ``o`` and ``lse`` and
    not those two, so it forms gate and up a second time (``formed``)."""
    from torchmpi_tpu.models import llama
    from torchmpi_tpu.parallel import mesh as pmesh

    cfg = dataclasses.replace(llama.moe_tiny(), capacity_factor=None)
    mesh = pmesh.make_mesh({"dp": devices}, devices=jax.devices()[:devices])
    step = llama.make_train_step(cfg, mesh, attn="flash", remat=remat)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    found = _grouped_products(
        jax.make_jaxpr(step)(params, None, tokens, tokens).jaxpr)
    layers = cfg.n_layers
    # down forward and the three gradients of the rows, gate and up `formed`
    # times each; the three gradients of the weights
    if devices == 1:
        assert found["gmm"] == (4 + 2 * formed) * layers
        assert found["tgmm"] == 3 * layers
        assert found["ragged_dot_general"] == 0
    else:
        assert found["ragged_dot_general"] == (7 + 2 * formed) * layers
        assert found["gmm"] == found["tgmm"] == 0
    for name in llama.GROUPED_DOT_NAMES:
        assert found[name] == formed * layers
    assert found["flash_fwd"] == found["flash_bwd"] == layers


# ---------------------------------------------- remat keeps kernels' outputs

@functools.cache
def _flash_loss_and_grads(model, remat):
    """Loss and gradients of a tiny model through the flash kernels (and,
    for the dropless sorted expert layer, megablox's ``gmm``) in interpret
    mode, under one remat policy or one for each recurrent step."""
    from torchmpi_tpu.models import llama

    cfg = {"sorted": dataclasses.replace(llama.moe_tiny(), capacity_factor=None,
                                         moe_z_coef=1e-3),
           "looped": dataclasses.replace(llama.tiny(), n_kv_heads=4, ut_steps=3,
                                         sandwich_norm=True, exit_gate=True,
                                         exit_entropy_coef=0.1)}[model]
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens, targets = (jax.random.randint(jax.random.PRNGKey(i), (2, 64), 0,
                                          cfg.vocab) for i in (1, 2))
    return jax.jit(jax.value_and_grad(llama.make_loss_fn(
        cfg, attn="flash", remat=remat, loss_chunk=32)))(
            params, (tokens, targets))


@pytest.mark.parametrize("model,remat", [
    ("sorted", "dots"), ("sorted", "full"),
    ("looped", "dots"), ("looped", "full"), ("looped", ("full", "dots", "none")),
], ids=["sorted-dots", "sorted-full", "looped-dots", "looped-full",
        "looped-a-policy-a-step"])
def test_remat_with_kernels_changes_no_value(model, remat):
    """What a policy keeps of a kernel (``llama._wrap_remat``: the flash
    kernel's ``o`` and ``lse`` under ``"dots"`` and ``"full"``, the grouped
    matmul's gate and up products under ``"dots"``) is what a replay would
    have formed: loss and every gradient leaf are those of ``remat="none"``,
    for the dropless sorted expert layer (megablox's ``gmm`` in interpret
    mode) and for a looped model with a policy for each recurrent step."""
    (want, want_g), (got, got_g) = (_flash_loss_and_grads(model, r)
                                    for r in ("none", remat))
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    want_g, got_g = (jax.tree_util.tree_leaves_with_path(g)
                     for g in (want_g, got_g))
    assert [k for k, _ in want_g] == [k for k, _ in got_g]
    for (key, w), (_, g) in zip(want_g, got_g):
        assert np.any(np.asarray(w) != 0), jax.tree_util.keystr(key)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-6, err_msg=jax.tree_util.keystr(key))
