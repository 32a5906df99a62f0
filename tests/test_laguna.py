"""Laguna-S-2.1-style stacks (``llama.laguna_s_2_1``): window and full softmax
layers in a pattern with head counts of their own, heads wider than the
state's share, a flash kernel that walks the band alone, YaRN on half of a
full layer's head, a gate a head on the attention output, a chip's share of
256 sigmoid-routed experts beside a shared one, against the plain reference
the benchmark keeps (``benchmark/reference/laguna-s-2.1.py``, which imports
nothing of the program).  The shares of the experts, the mesh, the remat
policies, the published layers and the names in the device program are in
``tests/test_laguna_stack.py``.  Small widths, float32, the CPU."""

import dataclasses
import importlib.util
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.ops.flash_attention import (_band_k_map, _band_q_map,
                                              _flash_bh, _flash_bh_bwd,
                                              blocks_met, flash_attention,
                                              operand_plan)
from torchmpi_tpu.parallel import mesh as pmesh

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = llama.laguna_s_2_1()
YARN = (8.0, 32, 32.0, 1.0, 1.2079441541679836)


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "reference", "laguna-s-2.1.py")
    spec = importlib.util.spec_from_file_location("laguna_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def laguna_tiny(n_layers=5, n_experts=16, held=(0, 4), k=3, window=24,
                **more):
    """The published pattern's first ``n_layers`` layers at toy widths: heads
    of 16 on a state of 48 (4 full heads, 6 window heads, 2 KV heads)."""
    return dataclasses.replace(
        PUBLISHED, vocab=128, d_model=48, n_layers=n_layers, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=32, dense_d_ff=96, max_seq=256,
        n_experts=n_experts, expert_top_k=k, swa_heads=6, swa_window=window,
        rope_yarn=YARN, layer_kinds=PUBLISHED.layer_kinds[:n_layers],
        experts_held=held, **more)


def file_of(cfg):
    """The configuration file's keys the reference reads, for ``cfg``."""
    first, held = cfg.experts_held or (0, cfg.n_experts)
    factor, original, fast, slow, attention = cfg.rope_yarn
    names = {"attn": "full_attention", "swa": "sliding_attention"}
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "head_dim": cfg.head_dim, "num_key_value_heads": cfg.n_kv_heads,
        "rms_norm_eps": cfg.norm_eps, "sliding_window": cfg.swa_window,
        "layer_types": [names[m] for m, _ in cfg.layer_kinds],
        "mlp_layer_types": ["dense" if f == "dense" else "sparse"
                            for _, f in cfg.layer_kinds],
        "num_attention_heads_per_layer": [
            llama.softmax_heads(cfg, m) for m, _ in cfg.layer_kinds],
        "rope_parameters": {
            "full_attention": {
                "rope_theta": cfg.rope_theta, "rope_type": "yarn",
                "factor": factor, "original_max_position_embeddings": original,
                "beta_fast": fast, "beta_slow": slow,
                "attention_factor": attention,
                "partial_rotary_factor": cfg.rope_fraction},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": cfg.swa_rope_theta,
                "partial_rotary_factor": 1}},
        "published": {"num_experts": cfg.n_experts},
        "num_experts": held, "experts_held_first": first,
        "num_experts_per_tok": cfg.expert_top_k,
        "norm_topk_prob": cfg.moe_renormalize,
        "moe_routed_scaling_factor": cfg.routed_scale,
    }


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def layer_of(params, run, i=0):
    return jax.tree.map(lambda a: a[i], params["layers"][run])


@pytest.fixture(scope="module")
def five():
    cfg = laguna_tiny()
    return cfg, llama.init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def sample():
    return (jax.random.randint(jax.random.PRNGKey(1), (2, 96), 0, 128),
            jax.random.randint(jax.random.PRNGKey(2), (2, 96), 0, 128))


@pytest.fixture(scope="module")
def plain(five, reference, sample):
    cfg, params = five
    return jax.jit(lambda p, s: reference.loss_and_grads(file_of(cfg), p, s))(
        params, sample)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs after their own."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _kernels(jaxpr, found):
    """Names of a jaxpr's ``pallas_call`` s, in order, sub-jaxprs included."""
    found += [eqn.params["name"] for eqn in _eqns(jaxpr)
              if eqn.primitive.name == "pallas_call"]
    return found


# ------------------------------------------------------ the windowed kernel

def masked_plain(q, k, v, window=None):
    """Softmax over the keys ``i - window < j <= i`` of whole score rows."""
    L = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None]
    seen = j <= i if window is None else (j <= i) & (j > i - window)
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


@pytest.fixture(scope="module")
def qkv():
    return tuple(jax.random.normal(jax.random.PRNGKey(i), (1, 256, 2, 32))
                 for i in range(3))


# Windows smaller than, equal to, not a multiple of and larger than a block;
# one key; blocks that differ; a window past the sequence.
WINDOWS = [(16, 32, 32), (32, 32, 32), (33, 32, 32), (40, 32, 32),
           (100, 32, 32), (1, 32, 32), (64, 32, 64), (64, 64, 32),
           (48, 64, 64), (300, 32, 32)]


@pytest.mark.parametrize("window,bq,bk", WINDOWS)
def test_the_windowed_kernels_against_the_masked_plain_form(qkv, window, bq,
                                                            bk):
    """Forward and the one backward kernel in interpret mode."""
    ours = lambda *a: flash_attention(*a, causal=True, window=window,
                                      block_q=bq, block_k=bk)
    want = lambda *a: masked_plain(*a, window=window)
    assert rel(ours(*qkv), want(*qkv)) < 1e-5
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    grads = jax.grad(loss(ours), argnums=(0, 1, 2))
    names = _kernels(jax.make_jaxpr(grads)(*qkv).jaxpr, [])
    assert names == ["flash_fwd", "flash_bwd"]
    for g, w in zip(grads(*qkv), jax.grad(loss(want), (0, 1, 2))(*qkv)):
        # one key a row: its weight is 1 whatever q and k, their gradients 0
        assert rel(g, w) < 1e-5 or float(jnp.max(jnp.abs(g - w))) < 1e-5


@pytest.mark.parametrize("window,bq,bk", [(40, 32, 32), (64, 32, 64),
                                          (64, 64, 32), (16, 32, 32)])
def test_the_streamed_backward_kernels_under_a_window(qkv, window, bq, bk):
    """``flash_bwd_dq`` and ``flash_bwd_dkv`` (the form taken where the
    resident dq block does not fit) give the one kernel's gradients."""
    bh = lambda a: a.transpose(0, 2, 1, 3).reshape(2, 256, 32)
    q, k, v = map(bh, qkv)
    how = dict(causal=True, block_q=bq, block_k=bk, interpret=True,
               window=window)
    o, lse = _flash_bh(q, k, v, **how)
    do = jnp.cos(o)
    delta = jnp.sum(do * o, axis=-1, keepdims=True)
    one = _flash_bh_bwd(q, k, v, do, lse, delta, **how)
    streamed = jax.make_jaxpr(lambda *a: _flash_bh_bwd(
        *a, vmem_budget=0, **how))(q, k, v, do, lse, delta)
    assert _kernels(streamed.jaxpr, []) == ["flash_bwd_dq", "flash_bwd_dkv"]
    two = _flash_bh_bwd(q, k, v, do, lse, delta, vmem_budget=0, **how)
    want = jax.vjp(lambda *a: masked_plain(*a, window=window), *qkv)[1](
        do.reshape(1, 2, 256, 32).transpose(0, 2, 1, 3))
    for a, b, w in zip(one, two, want):
        assert rel(a, b) < 1e-6
        assert rel(a, bh(w)) < 1e-5


def test_without_a_window_the_kernels_are_what_they_were(qkv):
    """``window=None`` and a window no shorter than the sequence give the
    causal call's output to the bit, and the same program."""
    causal = flash_attention(*qkv, causal=True, block_q=32, block_k=32)
    for window in (None, 256, 1000):
        got = flash_attention(*qkv, causal=True, window=window, block_q=32,
                              block_k=32)
        np.testing.assert_array_equal(got, causal)
    text = lambda **kw: re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(
        jax.grad(lambda *a: jnp.sum(flash_attention(
            *a, causal=True, block_q=64, block_k=64, **kw))))(*qkv)))
    assert text() == text(window=256)
    assert text() != text(window=64)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(*qkv, causal=False, window=8)


@pytest.mark.parametrize("shapes,want", [
    # a Laguna sliding layer and a full one: the group's sums in the kernel
    ((1, 16384, 72, 8, 128, 128, jnp.bfloat16, 512),
     dict(kv_repeat=9, dkv_in_kernel=True, repeated_bytes=0,
          copied_bytes=2 * 80 * 16384 * 256 * 2)),
    ((1, 16384, 48, 8, 128, 128),
     dict(kv_repeat=6, dkv_in_kernel=True, repeated_bytes=0,
          copied_bytes=2 * 56 * 16384 * 256 * 2)),
    # 64k rows: dq alone fits, dk and dv are written a query head and summed
    ((1, 65536, 32, 8, 128, 128),
     dict(kv_repeat=4, dkv_in_kernel=False,
          repeated_bytes=24 * 65536 * 256 * 2)),
    # every head its own K/V: Kimi Linear's latent layer, GLM's, Ouro
    ((1, 16384, 32, 32, 192, 128),
     dict(kv_repeat=1, dkv_in_kernel=False, repeated_bytes=0,
          copied_bytes=4 * 32 * 16384 * 320 * 2)),
    ((1, 16384, 20, 20, 256, 256), dict(kv_repeat=1, repeated_bytes=0)),
    ((2, 4096, 16, 16, 128, 128, jnp.float32),
     dict(kv_repeat=1, copied_bytes=4 * 16 * 2 * 4096 * 256 * 4))])
def test_what_a_call_moves_for_layout_alone(shapes, want):
    """``operand_plan``, from shapes: nothing is repeated to the query heads
    while a group's float32 dk and dv fit ``flash_bwd``'s VMEM; before PR 41
    a sliding layer's call repeated K and V and summed dk and dv at 72 heads,
    1,074 MB."""
    plan = operand_plan(*shapes)
    assert sorted(plan) == ["copied_bytes", "dkv_in_kernel", "kv_repeat",
                            "repeated_bytes"]
    assert {name: plan[name] for name in want} == want
    assert 2 * (72 - 8) * 16384 * 256 * 2 == 1_073_741_824


def test_the_steps_kernels_read_kv_at_their_own_heads(five, sample):
    """The five-layer step's jaxpr: every flash kernel, forward and
    backward, of a full layer (4 heads) and of a window layer (6) takes K
    and V at the 2 K/V heads, ``flash_bwd`` gives dk and dv there, and
    nothing between the projections and a kernel is broadcast to q's
    size."""
    cfg, params = five
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = llama.make_train_step(cfg, mesh, attn="flash", remat="full",
                                 loss_chunk=32)
    B, L = sample[0].shape
    found = list(_eqns(jax.make_jaxpr(step)(params, None, *sample).jaxpr))
    calls = [e for e in found if e.primitive.name == "pallas_call"
             and str(e.params["name"]).startswith("flash")]
    assert len(calls) == 2 * cfg.n_layers
    for call in calls:
        heads = [x.aval.shape[0] for x in call.invars[:3]]
        assert heads in ([B * 4, B * 2, B * 2], [B * 6, B * 2, B * 2])
        if call.params["name"] == "flash_bwd":
            assert [x.aval.shape[0] for x in call.outvars] == heads
    q_size = B * L * 4 * cfg.head_dim
    assert not [e for e in found if e.primitive.name == "broadcast_in_dim"
                and e.outvars[0].aval.shape[-1] == cfg.head_dim
                and e.outvars[0].aval.size >= q_size]


def test_the_rotation_is_the_sliced_form_to_the_bit():
    """``rope`` and ``rope_scaled`` meet a pair's partner through a product
    with a 0/1/-1 matrix (``llama._rotate_pairs``); unjitted, every value
    and every float32 gradient is that of slicing the channels at a stride
    of two, in float32 and bfloat16."""
    def sliced(x, positions, inv_freq, factor=1.0):
        r = 2 * len(inv_freq)
        angles = positions[:, None].astype(jnp.float32) * jnp.asarray(inv_freq)
        cos = factor * jnp.cos(angles)[None, :, None, :]
        sin = factor * jnp.sin(angles)[None, :, None, :]
        x1 = x[..., 0:r:2].astype(jnp.float32)
        x2 = x[..., 1:r:2].astype(jnp.float32)
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        out = out.reshape(*x.shape[:-1], r).astype(x.dtype)
        return jnp.concatenate([out, x[..., r:]], axis=-1)

    positions = jnp.arange(48) * 37
    for dtype, shape in ((jnp.float32, (2, 48, 6, 128)),
                         (jnp.bfloat16, (1, 48, 3, 128)),
                         (jnp.bfloat16, (2, 48, 1, 64))):
        x = jax.random.normal(jax.random.PRNGKey(3), shape, dtype)
        d = shape[-1]
        whole = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        np.testing.assert_array_equal(
            llama.rope(x, positions, 10000.0), sliced(x, positions, whole))
        half = llama.yarn_inv_freq(d // 2, 500000.0, *YARN[:-1])
        np.testing.assert_array_equal(
            llama.rope_scaled(x, positions, half, YARN[-1]),
            sliced(x, positions, half, YARN[-1]))
        if dtype == jnp.float32:
            grad = lambda f: jax.grad(lambda x: jnp.sum(jnp.sin(f(x))))(x)
            np.testing.assert_array_equal(
                grad(lambda x: llama.rope(x, positions, 10000.0)),
                grad(lambda x: sliced(x, positions, whole)))


@pytest.mark.parametrize("L,window,tile,inner,most,edge", [
    (16384, 512, 512, 2, 2, 63 / 32), (2048, 512, 512, 2, 2, 7 / 4),
    (4096, 1024, 1024, 2, 2, 7 / 4), (4096, 300, 256, 3, 3, 45 / 16),
    (16384, 4096, 1024, 5, 5, 28 / 16), (16384, None, 1024, 16, 16, 1.0)])
def test_a_q_block_runs_the_bands_k_blocks(L, window, tile, inner, most, edge):
    """From the index map the forward kernel is given: a Q block of a
    windowed call names the band's K blocks and no other, its grid is as
    long as the band is wide, and the tile comes from ``(L, window)``; a
    causal call's Q block names the triangle's.  ``edge_blocks_mean``: the
    blocks of those that run the masked body, by the kernel's predicates: a
    window under two tiles leaves no other (every block the band names
    holds one of its edges), a window of four tiles the diagonal's
    and the left edge's of 4.375, a causal call the diagonal's one of 8.5."""
    met = blocks_met(L, window)
    assert (met["tile"], met["grid_inner"], met["k_blocks_max"]) == (
        tile, inner, most)
    assert met["edge_blocks_mean"] == edge
    causal = blocks_met(L)
    assert causal["k_blocks_max"] == causal["q_blocks"] == L // causal["tile"]
    assert causal["k_blocks_mean"] == (causal["q_blocks"] + 1) / 2
    assert causal["edge_blocks_mean"] == 1.0
    if window is None:
        return
    assert 1.0 < met["edge_blocks_mean"] <= met["k_blocks_mean"]
    # a whole pair's first key is in the band of its last row
    assert (met["edge_blocks_mean"] == met["k_blocks_mean"]) == (
        window < 2 * tile - 1)
    # the band's: ceil((window - 1) / tile) + 1 blocks, fewer at the start
    assert met["k_blocks_max"] == -(-(window - 1) // tile) + 1
    assert met["k_blocks_mean"] < met["k_blocks_max"]
    # both sides' index maps stay inside the band and never past the end
    n = L // tile
    k_of, k_width = _band_k_map(window, tile, tile, n)
    q_of, q_width = _band_q_map(window, tile, tile, n, n)
    ids = np.arange(n)[:, None]
    ks = np.asarray(k_of(ids, np.arange(k_width)[None]))
    qs = np.asarray(q_of(ids, np.arange(q_width)[None]))
    assert ks.min() == 0 and qs.max() == n - 1
    assert np.all(ks <= ids) and np.all(ks * tile + tile > ids * tile - window)
    assert np.all(qs >= ids) and np.all(
        qs * tile < ids * tile + tile - 1 + window)


# ------------------------------------------------------------ the rotations

def test_yarn_at_the_published_numbers(reference):
    """``low`` 9, ``high`` 18 and the 32 frequencies of the full layers."""
    rope = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5}
    assert reference.yarn_range(rope, 64) == (9, 18)
    assert PUBLISHED.rope_yarn[-1] == pytest.approx(0.1 * np.log(128) + 1)
    got = llama.yarn_inv_freq(64, 500000.0, 128.0, 8192, 32.0, 1.0)
    plain_f = 500000.0 ** (-np.arange(32) / 32)
    assert got.shape == (32,)
    np.testing.assert_allclose(got[:10], plain_f[:10], rtol=1e-6)
    np.testing.assert_allclose(got[18:], plain_f[18:] / 128, rtol=1e-6)
    ramp = (np.arange(10, 18) - 9) / 9
    np.testing.assert_allclose(
        got[10:18], plain_f[10:18] * (1 - ramp) + plain_f[10:18] / 128 * ramp,
        rtol=1e-6)
    want, factor = reference.inverse_frequencies(rope, 128)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert factor == 1.4852030263919618


def test_the_partial_rotation_leaves_the_passed_half(reference):
    cfg = laguna_tiny()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 4, 16))
    positions = jnp.arange(40) + 5
    full = llama._rotation(cfg, "attn")(x, positions)
    np.testing.assert_array_equal(full[..., 8:], x[..., 8:])
    assert float(jnp.min(jnp.abs(full[:, 1:, :, :8] - x[:, 1:, :, :8]))) > 0
    rope = file_of(cfg)["rope_parameters"]
    for b in range(2):
        assert rel(full[b], reference.rotate(x[b], positions,
                                             rope["full_attention"])) < 1e-6
    # position 0 turns nothing and still carries the attention factor
    at_zero = llama._rotation(cfg, "attn")(x, jnp.zeros(40, int))
    np.testing.assert_allclose(at_zero[..., :8], YARN[-1] * x[..., :8],
                               rtol=1e-6)
    whole = llama._rotation(cfg, "swa")(x, positions)
    np.testing.assert_array_equal(
        whole, llama.rope(x, positions, cfg.swa_rope_theta))
    assert rel(whole[0], reference.rotate(x[0], positions,
                                          rope["sliding_attention"])) < 1e-6


# ------------------------------------------------- the gate and the layers

def test_the_gates_forward_and_gradient():
    o = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 6, 16))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 48))
    wg = jax.random.normal(jax.random.PRNGKey(2), (48, 6)) * 0.3
    want = lambda o, x, wg: o * jax.nn.sigmoid(
        jnp.einsum("bld,dh->blh", x, wg))[..., None]
    assert rel(llama._gate_heads(o, x, wg), want(o, x, wg)) < 1e-6
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    for g, w in zip(jax.grad(loss(llama._gate_heads), (0, 1, 2))(o, x, wg),
                    jax.grad(loss(want), (0, 1, 2))(o, x, wg)):
        assert rel(g, w) < 1e-5
    # a gate of its own a head: head 0's column moves head 0's output alone
    moved = llama._gate_heads(o, x, wg.at[:, 0].add(1.0))
    np.testing.assert_array_equal(moved[:, :, 1:],
                                  llama._gate_heads(o, x, wg)[:, :, 1:])


@pytest.mark.parametrize("attn", ["full", "flash"])
@pytest.mark.parametrize("run,kind,heads", [(0, "full_attention", 4),
                                            (1, "sliding_attention", 6)])
def test_a_layer_of_each_kind_against_the_reference(five, reference, attn,
                                                    run, kind, heads):
    """A full and a sliding layer's mixer, with head counts of their own over
    the same two KV heads: forward and every leaf's gradient."""
    cfg, params = five
    mixer = cfg.layer_kinds[run if run == 0 else 1][0]
    lp = layer_of(params, run)
    assert lp["wq"].shape == (48, heads * 16) and lp["wg"].shape == (48, heads)
    assert lp["wk"].shape == (48, 2 * 16)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 48))
    impls = llama._mixer_impls(cfg, attn, None)

    def ours(lp, h):
        return llama._attention_block(cfg, lp, h, jnp.arange(64),
                                      impls[mixer], mixer=mixer) - h

    def want(lp, h):
        x = reference.rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        return jax.vmap(
            lambda x: reference.mixer(file_of(cfg), kind, lp, x))(x)

    assert rel(ours(lp, h), want(lp, h)) < 1e-5
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    got, wanted = (jax.grad(loss(f), (0, 1))(lp, h) for f in (ours, want))
    for name in ("wq", "wk", "wv", "wo", "wg", "attn_norm"):
        assert rel(got[0][name], wanted[0][name]) < 1e-4, name
    assert rel(got[1], wanted[1]) < 1e-4


def test_five_layers_against_the_reference(five, reference, sample, plain):
    """Loss, logits and every leaf's gradient of the five-layer cut (a dense
    full layer, three sliding expert layers, a full expert layer)."""
    cfg, params = five
    assert llama.layer_runs(cfg) == (("attn", "dense", 1), ("swa", "moe", 3),
                                     ("attn", "moe", 1))
    loss, grads = jax.jit(jax.value_and_grad(llama.make_loss_fn(
        cfg, attn="flash", remat="full", loss_chunk=32)))(params, sample)
    logits = llama.apply(cfg, params, sample[0], attn="flash")
    want_loss, want_logits, want_grads = plain
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert rel(logits, want_logits) < 1e-5
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == len(jax.tree.leaves(want_grads))
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        assert rel(g, w) < 2e-4, jax.tree_util.keystr(path)
    counts = llama.expert_unit_counts(cfg, params, sample[0])
    assert counts.shape == (4, cfg.n_experts)
    np.testing.assert_array_equal(
        counts, reference.hidden(file_of(cfg), params, sample[0])[1])


@pytest.mark.parametrize("change,least", [
    (dict(swa_window=96), 1e-3), (dict(swa_window=25), 1e-5),
    (dict(attn_gate=False), 1e-2), (dict(rope_yarn=None), 1e-3)],
    ids=["no-window", "one-key-more", "no-gate", "plain-rotation"])
def test_what_the_controls_change_shows(five, sample, plain, change, least):
    """The faults the benchmark's controls plant (the window left out, a
    window one key wider, the gate left out, the plain rotation) each move the
    logits off the reference's by more than rounding does."""
    cfg, params = five
    logits = jax.jit(lambda p, t: llama.apply(
        dataclasses.replace(cfg, **change), p, t, attn="flash"))(
            params, sample[0])
    assert rel(logits, plain[1]) > least


@pytest.mark.parametrize("change,wrong", [
    ({}, 0), (dict(swa_window=25), 2), (dict(swa_window=23), 2),
    (dict(swa_window=128), 128 - 19 - 24 - 24)],
    ids=["configured", "one-key-more", "one-key-fewer", "no-window"])
def test_the_band_probe_counts_the_rows_a_wrong_window_moves(
        five, reference, change, wrong):
    """What rounding hides from every norm the benchmark compares, a window
    one key off, the runner's probe counts to the row: one sliding layer's
    logits change, to the bit, on the rows whose band holds a changed token
    and on no other, in the program and in the reference alike."""
    path = os.path.join(ROOT, "benchmark", "runners", "step_tokens_mixed.py")
    spec = importlib.util.spec_from_file_location("laguna_runner", path)
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    cfg, _ = five
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("dp",))
    got = runner.band_rows_wrong(
        dataclasses.replace(cfg, **change), file_of(cfg), reference, mesh,
        dict(attn="flash", remat="full"), 5, jnp.float32, 128)
    assert got == wrong
