"""The loop of passes under a Mellum2-style stack's experts on an ``ep`` axis
(``llama._ep_experts``; the rest of the stack is in ``tests/test_mellum2.py``,
whose fixtures and helpers these share; the driver hands a worker a file at a
time, and the two together were a third of the suite's time limit): routings
that need no overflow pass, one, a few and a dozen, every unit delivered and
the loss and gradients the reference's; planted faults counted; each body of
the loop traced once a shape."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.parallel import moe as pmoe

from test_mellum2 import (PUBLISHED, assert_grads, ep_mesh, file_of, four,
                          reference, sample)  # noqa: F401 — fixtures too

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py


def _biased(params, experts, by=50.0):
    """The stack with routers that send every token to ``experts``: their
    columns read a channel the embedding holds at a constant."""
    layers = tuple({**run, "router": jnp.zeros_like(run["router"]).at[
        :, 0, jnp.asarray(experts)].set(by)} for run in params["layers"])
    return {**params, "layers": layers,
            "embed": params["embed"].at[:, 0].set(5.0)}


def _stepped(cfg, params, mesh, sample):
    """``((loss, delivered), grads)`` of the training loss on ``mesh``, as
    ``make_train_step`` takes them."""
    return jax.jit(jax.value_and_grad(llama._loss_and_delivered(
        cfg, mesh, "flash", "none", 32), has_aux=True))(
            llama.shard_params(params, mesh, cfg), sample)


def _overflow_passes(cfg, params, tokens, rows):
    """The overflow passes each layer's exchange takes on ``ep`` = 4, as
    ``parallel.moe.pass_plan`` counts them from each rank's routed units
    (a rank's rows routed alone, on one device)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    routed = jax.jit(lambda rank: llama.expert_unit_counts(cfg, params, rank))
    units = jnp.stack([routed(rank) for rank in tokens.reshape(
        4, -1, tokens.shape[1])])
    planned = jax.jit(shard_map(
        lambda u: jax.vmap(lambda a: jnp.stack(
            pmoe.pass_plan(a, rows, "ep")[3:]))(u[0])[None],
        mesh=ep_mesh(), in_specs=P("ep"), out_specs=P("ep"),
        check_vma=False))(units)                  # (ranks, layers, 2)
    passes, overflow = np.moveaxis(np.asarray(planned), -1, 0)
    assert (passes == passes[0]).all() and (passes == 1 + overflow).all()
    pairs = np.asarray(units).reshape(4, cfg.n_layers, 4, -1).sum(-1)
    np.testing.assert_array_equal(
        overflow[0], -(-np.maximum(pairs.max(axis=(0, 2)) - rows[0], 0)
                       // rows[1]))
    return overflow[0].tolist()


@pytest.mark.parametrize("experts,rows,overflow", [
    (None, 128, [0] * 4), (None, 80, [1] * 4), (None, 64, [2, 2, 3, 3]),
    ((4, 5), 64, [12] * 4), ((4, 5), 32, [14] * 4), ((4, 5), 16, [15] * 4)],
    ids=["no-overflow", "one", "seeded", "share", "half", "eighth"])
def test_nothing_is_dropped_under_heavy_imbalance(four, reference, sample,
                                                  monkeypatch, experts, rows,
                                                  overflow):
    """The two-size loop for routings that need no overflow pass (the seeded
    routers under a first pass of twice the share), one (at five quarters of
    it, which its overflow's rows do not divide: the experts' blocks are then
    the two sizes' greatest common divisor), two and three (at the share),
    and twelve and more: a router biased so that every token chooses rank 2's
    two experts, so every unit of every rank goes to one rank, four times a
    first pass's rows and more.  The exchange takes as many overflow passes
    as that needs, every unit is delivered, and the loss and every gradient
    are still the reference's on one device."""
    cfg, params = four
    if experts:
        params = _biased(params, experts)
    mesh = ep_mesh()
    tokens = sample[0].size // 4
    assert llama.ep_pass_rows(cfg, tokens, 4) == 64   # k * tokens / ep
    assert llama.ep_overflow_rows(cfg, tokens, 4) == 16     # a quarter
    assert llama.ep_overflow_rows(PUBLISHED, 2 * 8192, 4) == 32768 // 4
    monkeypatch.setattr(llama, "ep_pass_rows", lambda *_: rows)
    sizes = rows, llama.ep_overflow_rows(cfg, tokens, 4)
    assert _overflow_passes(cfg, params, sample[0], sizes) == overflow
    (loss, delivered), grads = _stepped(cfg, params, mesh, sample)
    want_loss, _, want_grads = jax.jit(
        lambda p, s: reference.loss_and_grads(file_of(cfg), p, s))(
            params, sample)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert_grads(grads, want_grads, 1e-3)
    whole = cfg.n_layers * cfg.expert_top_k * tokens
    np.testing.assert_array_equal(delivered.sum(axis=0), [whole] * 4)
    if experts:
        want = np.zeros((4, 4), np.int64)
        want[2] = whole
        np.testing.assert_array_equal(delivered, want)


def _a_pass_short(units, rows, axis):
    plan = pmoe.pass_plan(units, rows, axis)
    return (*plan[:3], plan[3] - 1)


def _an_overflow_pass_short(units, rows, axis):
    *plan, passes, overflow = pmoe.pass_plan(units, rows, axis)
    return (*plan, passes - jnp.minimum(overflow, 1), overflow)


def _too_few_filled(k, R, n_tokens, order, first, sent, lo,
                    whole=llama._ep_pass):
    token, unit = whole(k, R, n_tokens, order, first, sent, lo)
    last = (jnp.arange(R) == R - 1) & (lo == 0)
    return jnp.where(last, n_tokens, token), jnp.where(last, n_tokens * k, unit)


def _too_few_run(R, arrived, lo, whole=llama._ep_arrived):
    rows, kept = whole(R, arrived, lo)
    return rows & ((jnp.arange(R) > 0) | (lo > 0))[:, None], kept


@pytest.fixture
def traced_anew():
    """The forward and the backward pass of ``llama._ep_experts`` are jitted,
    and a cached trace would hide a function patched under them (and outlive
    the patch): JAX's caches are cleared round the test."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("name,planted,short", [
    ("_pass_plan", _a_pass_short, None),
    ("_pass_plan", _an_overflow_pass_short, 4 * 4 * 16),
    ("_ep_pass", _too_few_filled, 4 * 4),
    ("_ep_arrived", _too_few_run, 4 * 4),
], ids=["a-pass-short", "an-overflow-pass-short", "a-row-not-sent",
        "a-row-not-run"])
def test_a_dropped_unit_is_counted(four, sample, traced_anew, monkeypatch,
                                   name, planted, short):
    """``delivered`` is counted in the passes, so each way of losing a unit
    shows: a pass too few, an overflow pass too few where the first pass ran
    (at most a quarter of the share from every pair of ranks), a row the
    sender's gather leaves out of every block of a layer's first pass, a row
    the receiver's mask leaves out.  (The routers' counts read ``k *
    tokens`` a layer in all four.)"""
    cfg, params = four
    monkeypatch.setattr(llama, name, planted)
    (_, delivered), _ = _stepped(cfg, params, ep_mesh(), sample)
    whole = cfg.n_layers * cfg.expert_top_k * sample[0].size
    assert 0 < int(delivered.sum()) < whole
    if short:
        assert whole - int(delivered.sum()) <= cfg.n_layers * short


def test_a_body_is_traced_once_a_shape(four, traced_anew, monkeypatch):
    """Building the ``ep`` = 4 train step under ``remat="full"`` traces each
    body of the exchange's loop once: two calls of ``_held_swiglu`` (forward,
    the share's pass and the overflow's) and two of ``_held_swiglu_bwd``
    (backward, which since PR 47 is written out and forms gate and up again
    through ``_grouped_matmul``, its other six kernels called directly), ten
    ``_grouped_matmul``s, whatever the number of layers, replays and passes.
    (Staged once a layer they were 9 and 27 with ONE pass size: PERF.md
    section 6, PR 46; four and twelve while the backward bodies went through
    ``jax.vjp(_held_swiglu)``.)"""
    import optax

    cfg, params = four
    calls = dict.fromkeys(("_held_swiglu", "_held_swiglu_bwd",
                           "_grouped_matmul"), 0)

    def counted(name, whole):
        def call(*args, **kwargs):
            calls[name] += 1
            return whole(*args, **kwargs)
        return call

    for name in calls:
        monkeypatch.setattr(llama, name, counted(name, getattr(llama, name)))
    mesh = ep_mesh()
    optimizer = optax.adamw(1e-2)
    p = llama.shard_params(params, mesh, cfg)
    tokens = jnp.zeros((8, 64), jnp.int32)
    text = llama.make_train_step(
        cfg, mesh, attn="flash", optimizer=optimizer, remat="full",
        loss_chunk=32, with_delivered=True).lower(
            p, optimizer.init(p), tokens, tokens).as_text()
    assert calls == {"_held_swiglu": 2, "_held_swiglu_bwd": 2,
                     "_grouped_matmul": 10}
    # and the module names fewer exchanges with two sizes than it did with
    # one size staged once a layer (44)
    assert text.count("all_to_all") < 44
