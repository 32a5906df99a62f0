"""Mellum2-12B-A2.5B-style stacks (``llama.mellum2_12b_a2_5b``): window and
full softmax layers three to one with the full layer last, YaRN on the whole
head, and in every layer dropless softmax-routed experts that on a mesh with
an ``ep`` axis are sharded over it, their routed units sent to the rank of
their expert and brought back by an exchange (``llama._moe_ffn_ep``), against
the plain reference the benchmark keeps
(``benchmark/reference/mellum2-12b-a2.5b.py``, which imports nothing of the
program and runs on one device).  Small widths with the published ratios (a
period of sliding, sliding, sliding, full; 8 experts over ``ep`` = 4, 2 a
token), float32, the suite's host devices.  The loop of passes under
imbalance and planted faults is in ``tests/test_mellum2_passes.py``."""

import dataclasses
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.parallel import mesh as pmesh
from torchmpi_tpu.parallel import moe as pmoe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = llama.mellum2_12b_a2_5b()
YARN = (8.0, 32, 32.0, 1.0, 1.2079441541679836)


def _module(kind, name):
    path = os.path.join(ROOT, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"mellum2_{kind}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _module("reference", "mellum2-12b-a2.5b")


def mellum_tiny(n_layers=4, n_experts=8, k=2, window=24, **more):
    """The published pattern's first ``n_layers`` layers at toy widths: 4
    heads of 16 over 2 KV heads on a state of 48."""
    return dataclasses.replace(
        PUBLISHED, vocab=128, d_model=48, n_layers=n_layers, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=32, max_seq=256, n_experts=n_experts,
        expert_top_k=k, swa_window=window, rope_yarn=YARN,
        layer_kinds=PUBLISHED.layer_kinds[:n_layers], **more)


def file_of(cfg):
    """The configuration file's keys the reference reads, for ``cfg``."""
    factor, original, fast, slow, attention = cfg.rope_yarn
    names = {"attn": "full_attention", "swa": "sliding_attention"}
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "head_dim": cfg.head_dim, "num_key_value_heads": cfg.n_kv_heads,
        "rms_norm_eps": cfg.norm_eps, "sliding_window": cfg.swa_window,
        "layer_types": [names[m] for m, _ in cfg.layer_kinds],
        "mlp_layer_types": ["dense" if f == "dense" else "sparse"
                            for _, f in cfg.layer_kinds],
        "rope_parameters": {
            "full_attention": {
                "rope_theta": cfg.rope_theta, "rope_type": "yarn",
                "factor": factor, "original_max_position_embeddings": original,
                "beta_fast": fast, "beta_slow": slow,
                "attention_factor": attention},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": cfg.swa_rope_theta}},
        "num_experts": cfg.n_experts,
        "num_experts_per_tok": cfg.expert_top_k,
        "norm_topk_prob": cfg.moe_renormalize,
    }


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def layer_of(params, run, i=0):
    return jax.tree.map(lambda a: a[i], params["layers"][run])


def ep_mesh(axes=None):
    axes = axes or {"ep": 4}
    return pmesh.make_mesh(axes, devices=jax.devices()[:int(np.prod(
        list(axes.values())))])


@pytest.fixture(scope="module")
def four():
    cfg = mellum_tiny()
    return cfg, llama.init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def sample():
    return (jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, 128),
            jax.random.randint(jax.random.PRNGKey(2), (8, 64), 0, 128))


@pytest.fixture(scope="module")
def plain(four, reference, sample):
    cfg, params = four
    return jax.jit(lambda p, s: reference.loss_and_grads(file_of(cfg), p, s))(
        params, sample)


def assert_grads(got, want, limit):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert rel(a, b) < limit, jax.tree_util.keystr(path)


# ------------------------------------------------------------- the preset

def test_the_published_28_layers_build():
    """The preset is the published file: 28 layers in periods of sliding,
    sliding, sliding, full (the full layer LAST), 14 runs, experts in every
    layer and no shared one, 12.15 G parameters of which 2.5 G a token."""
    cfg = PUBLISHED
    assert cfg.layer_kinds == ((("swa", "moe"),) * 3 + (("attn", "moe"),)) * 7
    assert [n for *_, n in llama.layer_runs(cfg)] == [3, 1] * 7
    assert (cfg.swa_window, cfg.n_experts, cfg.expert_top_k, cfg.d_ff,
            cfg.vocab, cfg.head_dim) == (1024, 64, 8, 896, 98304, 128)
    assert llama.softmax_heads(cfg, "swa") == llama.softmax_heads(
        cfg, "attn") == 32
    assert cfg.capacity_factor is None and not cfg.n_shared_experts
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    total = sum(a.size for a in jax.tree.leaves(shapes))
    experts = sum(a.size for run in shapes["layers"]
                  for name, a in run.items() if name.startswith("w_"))
    assert total == 28 * 417_747_456 + 2 * 98304 * 2304 + 2304    # 12.15 G
    assert total - experts + experts * 8 // 64 == 2_439_053_568     # A2.5B
    flops = _module("flops", "mellum2-12b-a2.5b")
    import json
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2-12b-a2.5b.json")) as fh:
        file = json.load(fh)
    cut = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0),
                                            dataclasses.replace(
        cfg, n_layers=4, layer_kinds=cfg.layer_kinds[:4])))
    held, used = flops.parameters(file, ep=4)
    whole = sum(a.size for a in jax.tree.leaves(cut))
    assert flops.parameters(file)[0] == whole
    assert held == 934_891_776 and used == 736_710_912


def test_yarn_at_the_published_numbers(reference):
    """``low`` 18, ``high`` 35 and the 64 frequencies of the full layers, by
    hand: the whole head rotates; the factor is 0.1 ln 16 + 1."""
    rope = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}
    assert reference.yarn_range(rope, 128) == (18, 35)
    assert PUBLISHED.rope_yarn == (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert PUBLISHED.rope_yarn[-1] == pytest.approx(0.1 * np.log(16) + 1)
    assert PUBLISHED.rope_fraction == 1
    got = llama.yarn_inv_freq(128, 500000.0, 16.0, 8192, 32.0, 1.0)
    plain_f = 500000.0 ** (-np.arange(64) / 64)
    np.testing.assert_allclose(got[:19], plain_f[:19], rtol=1e-6)
    np.testing.assert_allclose(got[35:], plain_f[35:] / 16, rtol=1e-6)
    ramp = (np.arange(19, 35) - 18) / 17
    np.testing.assert_allclose(
        got[19:35], plain_f[19:35] * (1 - ramp) + plain_f[19:35] / 16 * ramp,
        rtol=1e-6)
    want, factor = reference.inverse_frequencies(rope, 128)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert factor == 1.2772588722239782
    # A sliding layer's are the plain ones at the same base.
    want, factor = reference.inverse_frequencies(
        {"rope_type": "default", "rope_theta": 500000}, 128)
    np.testing.assert_allclose(want, plain_f, rtol=1e-6)
    assert factor == 1.0 and PUBLISHED.swa_rope_theta == 500000.0


# ------------------------------------------- one device against the reference

@pytest.mark.parametrize("attn", ["full", "flash"])
def test_four_layers_against_the_reference(four, sample, plain, attn):
    """One device: the loss, the logits and every leaf's gradient of one
    whole period are the plain reference's."""
    cfg, params = four
    loss, grads = jax.jit(jax.value_and_grad(llama.make_loss_fn(
        cfg, attn=attn, loss_chunk=32)))(params, sample)
    logits = llama.apply(cfg, params, sample[0], attn=attn)
    want_loss, want_logits, want_grads = plain
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert rel(logits, want_logits) < 1e-5
    assert_grads(grads, want_grads, 2e-4)


@pytest.mark.parametrize("change,wrong", [
    ({}, 0), (dict(swa_window=25), 2), (dict(swa_window=23), 2)],
    ids=["configured", "one-key-more", "one-key-fewer"])
def test_the_band_at_its_edge(four, reference, change, wrong):
    """The runner's probe on this model's sliding layer: the rows of its
    logits that two changed tokens move, to the bit, the program's against
    the reference's; a window one key off is one row a token."""
    runner = _module("runners", "step_tokens_mixed")
    cfg, _ = four
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("dp",))
    got = runner.band_rows_wrong(
        dataclasses.replace(cfg, **change), file_of(cfg), reference, mesh,
        dict(attn="flash", remat="full"), 5, jnp.float32, 128)
    assert got == wrong


# ---------------------------------------------------------- the ep layer

@pytest.mark.parametrize("axes", [{"ep": 4}, {"dp": 2, "ep": 4},
                                  {"ep": 2, "tp": 2}],
                         ids=["ep4", "dp2-ep4", "ep2-tp2"])
def test_on_an_ep_axis_against_one_device_and_the_reference(four, sample,
                                                            plain, axes):
    """The same on a mesh with an ``ep`` axis: the batch's rows sharded over
    it (and ``dp``), 2 of the 8 experts a rank, the units exchanged; the
    loss, the logits and every gradient are one device's and the
    reference's, the experts' gradients left on their ranks, and every
    routed unit was delivered."""
    cfg, params = four
    mesh = ep_mesh(axes)
    rows = tuple(a for a in ("dp", "ep") if a in axes)
    assert llama.batch_spec(cfg, mesh) == jax.sharding.PartitionSpec(
        rows if len(rows) > 1 else rows[0], None)
    sharded = llama.shard_params(params, mesh, cfg)
    assert sharded["layers"][0]["w_up"].sharding.shard_shape(
        (3, 8, 48, 32))[1] == 8 // axes["ep"]
    loss, grads = jax.jit(jax.value_and_grad(llama.make_loss_fn(
        cfg, mesh, attn="flash", loss_chunk=32)))(sharded, sample)
    want_loss, want_logits, want_grads = plain
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    logits, (_, delivered) = jax.jit(lambda p, t: llama.apply(
        cfg, p, t, mesh=mesh, attn="flash", return_aux=True))(
            sharded, sample[0])
    assert rel(logits, want_logits) < 1e-5
    assert_grads(grads, want_grads, 2e-4)
    assert grads["layers"][0]["w_up"].sharding.spec[1] == "ep"
    units = cfg.expert_top_k * sample[0].size
    assert delivered.shape == (axes["ep"],) * 2
    assert int(delivered.sum()) == cfg.n_layers * units      # none dropped
    counts = jax.jit(lambda p, t: llama.expert_unit_counts(
        cfg, p, t, mesh=mesh, attn="flash"))(sharded, sample[0])
    assert counts.shape == (4, 8) and (counts.sum(axis=1) == units).all()
    np.testing.assert_array_equal(
        np.asarray(counts).reshape(4, axes["ep"], -1).sum(axis=(0, 2)),
        np.asarray(delivered).sum(axis=1))


def test_the_shares_add_up(four, reference):
    """The layer on ``ep`` = 4 is the sum of the four shares a chip alone
    would compute (``Config(experts_held=...)``: the held experts' part, the
    weights normalised over all k choices), and both are the uncut
    reference's layer; a share's weights are the whole layer's experts."""
    cfg, params = four
    full = layer_of(params, 0)
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 32, cfg.d_model))
    xt = x.reshape(-1, cfg.d_model)
    want = reference.experts_ffn(file_of(cfg), full, xt)
    total = 0.0
    for first in range(0, cfg.n_experts, 2):
        share = dataclasses.replace(cfg, experts_held=(first, 2))
        lp = layer_of(llama.init(jax.random.PRNGKey(0), share), 0)
        np.testing.assert_array_equal(lp["w_up"], full["w_up"][first:first + 2])
        total = total + llama._moe_ffn(share, lp, x)[0].reshape(xt.shape)
    assert rel(total, want) < 1e-5
    mesh = ep_mesh()
    spec = jax.tree.map(lambda s: jax.sharding.PartitionSpec(*s[1:]),
                        llama.param_specs(cfg)["layers"][0],
                        is_leaf=lambda s: isinstance(
                            s, jax.sharding.PartitionSpec))
    got, (_, delivered) = jax.jit(lambda lp, x: llama._moe_ffn(
        cfg, lp, x, mesh=mesh))(llama.shard_by_specs(full, mesh, spec), x)
    assert rel(got.reshape(xt.shape), want) < 1e-5
    assert rel(got.reshape(xt.shape), total) < 1e-5
    assert int(delivered.sum()) == cfg.expert_top_k * xt.shape[0]


def test_a_share_of_the_experts_is_refused_on_an_ep_axis(four):
    cfg, _ = four
    share = dataclasses.replace(cfg, experts_held=(0, 2))
    with pytest.raises(NotImplementedError, match="an ep axis has no form "
                       "yet for a chip's share of the experts"):
        llama._moe_ffn(share, None, jnp.zeros((4, 8, 48)), mesh=ep_mesh())
    with pytest.raises(ValueError, match="does not divide over the mesh"):
        llama.apply(cfg, llama.init(jax.random.PRNGKey(0), cfg),
                    jnp.zeros((3, 32), jnp.int32), mesh=ep_mesh())


def test_the_exchange_is_its_own_inverse_and_transpose():
    """``parallel.moe.exchange``: block j goes to rank j; twice is the
    identity; a cotangent goes back by the same call."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = ep_mesh()
    x = jnp.arange(4 * 4 * 3, dtype=jnp.float32).reshape(16, 3)
    on = lambda f: jax.jit(shard_map(
        lambda a: f(a.reshape(4, 1, 3)).reshape(4, 3), mesh=mesh,
        in_specs=P("ep"), out_specs=P("ep"), check_vma=False))
    once = on(lambda a: pmoe.exchange(a, "ep"))(x)
    np.testing.assert_array_equal(
        np.asarray(once).reshape(4, 4, 3),
        np.asarray(x).reshape(4, 4, 3).transpose(1, 0, 2))
    np.testing.assert_array_equal(
        on(lambda a: pmoe.exchange(pmoe.exchange(a, "ep"), "ep"))(x), x)
    g = jax.grad(lambda a: jnp.sum(on(
        lambda b: pmoe.exchange(b, "ep"))(a) * x))(jnp.ones_like(x))
    np.testing.assert_array_equal(g, once)


def test_the_train_step_on_ep_moves_every_leaf_and_counts_what_it_delivered(
        four):
    """``make_train_step`` on ``ep`` = 4 with AdamW against one device: the
    same loss and the same weights after a step (what every rank holds alike
    stepped alike, the experts where they are), and ``with_delivered`` adds
    the units the exchange delivered (None where nothing is exchanged)."""
    import optax

    cfg, params = four
    tokens = jax.random.randint(jax.random.PRNGKey(3), (8, 64), 0, cfg.vocab)
    targets = jnp.roll(tokens, -1, 1)
    optimizer = optax.adamw(1e-2)
    stepped = {}
    for name, axes in (("one", {"dp": 1}), ("ep", {"ep": 4})):
        mesh = ep_mesh(axes)
        p = llama.shard_params(jax.tree.map(jnp.copy, params), mesh, cfg)
        step = llama.make_train_step(
            cfg, mesh, attn="flash", optimizer=optimizer, remat="full",
            loss_chunk=32, with_delivered=True)
        stepped[name] = step(p, optimizer.init(p), tokens, targets)
    (p1, _, loss1, none), (p4, _, loss4, delivered) = (stepped["one"],
                                                       stepped["ep"])
    assert none is None
    assert float(loss4) == pytest.approx(float(loss1), rel=1e-5)
    assert int(delivered.sum()) == 4 * cfg.expert_top_k * tokens.size
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(p4),
                            jax.tree.leaves(p1)):
        np.testing.assert_allclose(a, b, atol=2e-4, err_msg=str(path))
