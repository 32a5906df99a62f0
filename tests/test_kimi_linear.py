"""Kimi-Linear-style stacks (``llama.kimi_linear_48b_a3b``): the KDA and
latent-attention blocks, the sigmoid router and a chip's share of the experts
against the plain reference the benchmark keeps
(``benchmark/reference/kimi-linear-48b-a3b.py``, which imports nothing of the
program), the runs a stack is built from, the published 27 layers, the
homogeneous presets as they were.  The chunked recurrence, its kernels and
flash with values of their own width are in
``tests/test_kimi_linear_kernels.py``, the stack whole in
``tests/test_kimi_linear_stack.py``, the remat policies in
``tests/test_kimi_linear_remat.py``: four files, so that the driver's
workers, which take a file at a time, share what was the suite's longest.
Small widths, float32, the CPU."""

import dataclasses
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama

from test_kimi_linear_kernels import rel

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = llama.kimi_linear_48b_a3b()


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "reference",
                        "kimi-linear-48b-a3b.py")
    spec = importlib.util.spec_from_file_location("kimi_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def kimi_tiny(n_layers=5, n_experts=8, held=(0, 2), k=2):
    """The published pattern's first ``n_layers`` layers at toy widths."""
    return dataclasses.replace(
        PUBLISHED, vocab=128, d_model=64, n_layers=n_layers, n_heads=4,
        n_kv_heads=4, d_ff=32, dense_d_ff=96, max_seq=256,
        n_experts=n_experts, expert_top_k=k, kda_heads=4, kda_head_dim=16,
        kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, layer_kinds=PUBLISHED.layer_kinds[:n_layers],
        experts_held=held)


def file_of(cfg):
    """The configuration file's keys the reference reads, for ``cfg``."""
    kda = [i + 1 for i, (m, _) in enumerate(PUBLISHED.layer_kinds)
           if m == "kda"]
    mla = [i + 1 for i, (m, _) in enumerate(PUBLISHED.layer_kinds)
           if m == "mla"]
    first, held = cfg.experts_held or (0, cfg.n_experts)
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads, "rms_norm_eps": cfg.norm_eps,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "first_k_dense_replace": 1,
        "linear_attn_config": {
            "kda_layers": kda, "full_attn_layers": mla,
            "num_heads": cfg.kda_heads, "head_dim": cfg.kda_head_dim,
            "short_conv_kernel_size": cfg.kda_conv},
        "published": {"num_experts": cfg.n_experts},
        "num_experts": held, "experts_held_first": first,
        "num_experts_per_token": cfg.expert_top_k,
        "num_shared_experts": cfg.n_shared_experts,
        "moe_renormalize": cfg.moe_renormalize,
        "routed_scaling_factor": cfg.routed_scale,
    }


def layer_of(params, run, i=0):
    return jax.tree.map(lambda a: a[i], params["layers"][run])


# ------------------------------------------------- blocks against the reference

@pytest.fixture(scope="module")
def model():
    cfg = kimi_tiny()
    return cfg, llama.init(jax.random.PRNGKey(0), cfg)


def test_kda_block_against_the_reference(model, reference):
    cfg, params = model
    lp = layer_of(params, 1)                 # a KDA layer of the moe run
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 96, cfg.d_model))
    want = jax.vmap(lambda s: reference.kda_mixer(file_of(cfg), lp, s))(x)
    mixer = llama._kda_sharded(None, cfg.kda_heads, cfg.norm_eps)
    assert rel(llama._kda_block(cfg, lp, x, mixer), want) < 1e-5


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_mla_block_against_the_reference(model, reference, attn):
    cfg, params = model
    assert llama.layer_runs(cfg)[2][:2] == ("mla", "moe")
    lp = layer_of(params, 2)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, cfg.d_model))
    want = jax.vmap(lambda s: reference.mla_mixer(file_of(cfg), lp, s))(x)
    impl = llama._mixer_impls(cfg, attn, None)["mla"]
    assert rel(llama._mla_block(cfg, lp, x, impl), want) < 1e-5


def test_sigmoid_router_with_a_bias_that_changes_the_choice(model, reference):
    """The bias moves the top-k choice and nothing else: weights are the
    chosen scores without it, renormalised and scaled; the whole layer (all
    experts held) is the reference's; the bias's gradient is exactly zero."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, experts_held=None)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    lp = layer_of(params, 1)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, cfg.d_model))
    xt = x.reshape(-1, cfg.d_model)
    weight, expert, counts, _ = llama._route_tokens(cfg, lp, xt)
    unbiased = llama._route_tokens(
        cfg, {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}, xt)[1]
    assert int(jnp.sum(jnp.sort(expert) != jnp.sort(unbiased))) > 0
    assert int(jnp.sum(counts)) == cfg.expert_top_k * 64
    np.testing.assert_allclose(jnp.sum(weight, axis=-1), cfg.routed_scale,
                               rtol=1e-6)
    want = reference.experts_ffn(file_of(cfg), lp, xt)
    got, _ = llama._moe_ffn(cfg, lp, x)
    assert rel(got.reshape(want.shape), want) < 1e-5
    g = jax.grad(lambda b: jnp.sum(llama._moe_ffn(
        cfg, {**lp, "router_bias": b}, x)[0] ** 2))(lp["router_bias"])
    assert float(jnp.max(jnp.abs(g))) == 0.0


@pytest.mark.parametrize("held", [2, 4])
def test_the_shares_add_up(reference, held):
    """Over all ``n_experts / held`` shares of a layer, the held experts'
    parts, with the shared expert counted once, sum to the uncut reference's
    layer output; a share's weights are the uncut layer's experts."""
    whole = kimi_tiny(held=None)
    full = layer_of(llama.init(jax.random.PRNGKey(0), whole), 1)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 64, whole.d_model))
    xt = x.reshape(-1, whole.d_model)
    want = reference.experts_ffn(file_of(whole), full, xt)
    shared = reference.swiglu(xt, full["shared_gate"], full["shared_up"],
                              full["shared_down"])
    total = 0.0
    for first in range(0, whole.n_experts, held):
        cfg = kimi_tiny(held=(first, held))
        lp = layer_of(llama.init(jax.random.PRNGKey(0), cfg), 1)
        np.testing.assert_array_equal(lp["w_up"],
                                      full["w_up"][first:first + held])
        part, _ = llama._moe_ffn(cfg, lp, x)
        assert rel(part.reshape(xt.shape), reference.experts_ffn(
            file_of(cfg), lp, xt)) < 1e-5
        total = total + part.reshape(xt.shape) - shared
    assert rel(total + shared, want) < 1e-5


@pytest.mark.parametrize("bias, passes", [(0.0, 1), (10.0, 8)])
def test_every_held_unit_is_computed(reference, monkeypatch, bias, passes):
    """The held experts take their units a pass of static rows at a time, as
    many passes as arrived: a bias that sends every token to the two held
    experts of 32 makes 128 units where a pass takes 16, and the layer's
    output and every gradient are still the reference's, as they are where
    one pass is enough."""
    cfg = kimi_tiny(n_experts=32, held=(0, 2))
    assert llama.held_pass_rows(cfg, 64) == 32
    assert llama.held_pass_rows(kimi_tiny(), 64) == 128   # never over k * T
    monkeypatch.setattr(llama, "_HELD_PASS_OVER_SHARE", 2)
    assert llama.held_pass_rows(cfg, 64) == 16
    lp = layer_of(llama.init(jax.random.PRNGKey(0), cfg), 1)
    lp["router_bias"] = jnp.zeros(32).at[:2].set(bias)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 64, cfg.d_model))
    arrived = llama._route_tokens(cfg, lp, x[0])[2][:2]
    assert -(-int(jnp.sum(arrived)) // 16) == passes
    ours = lambda lp, x: jnp.sum(jnp.sin(llama._moe_ffn(cfg, lp, x)[0]))
    theirs = lambda lp, x: jnp.sum(jnp.sin(reference.experts_ffn(
        file_of(cfg), lp, x[0])))
    got, want = (jax.value_and_grad(f, argnums=(0, 1))(lp, x)
                 for f in (ours, theirs))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    assert rel(got[1][1], want[1][1]) < 1e-5
    for name in lp:
        if name in want[1][0] and name != "router_bias":
            assert rel(got[1][0][name], want[1][0][name]) < 1e-5, name


# ------------------------------------------------------------- the stack

def test_the_published_pattern_builds_its_runs():
    kinds = PUBLISHED.layer_kinds
    assert len(kinds) == 27 and kinds[0] == ("kda", "dense")
    assert [i + 1 for i, (m, _) in enumerate(kinds) if m == "mla"] == [
        4, 8, 12, 16, 20, 24, 27]
    assert all(f == "moe" for _, f in kinds[1:])
    runs = llama.layer_runs(PUBLISHED)
    assert len(runs) == 15 and sum(n for *_, n in runs) == 27
    assert runs[:4] == (("kda", "dense", 1), ("kda", "moe", 2),
                        ("mla", "moe", 1), ("kda", "moe", 3))
    assert max(n for *_, n in runs) <= llama._INLINE_MAX_LAYERS
    assert llama.layer_runs(llama.olmoe_1b_7b()) == (("attn", "moe", 16),)
    with pytest.raises(ValueError, match="neither list"):
        llama.layer_kinds(3, [1, 2], [], 1)


def test_the_published_27_layers_build_and_run():
    cfg = kimi_tiny(n_layers=27)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    assert len(params["layers"]) == 15
    specs = llama.param_specs(cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == \
        jax.tree.structure(jax.tree.map(lambda s: 0, specs,
                                        is_leaf=lambda s: isinstance(
                                            s, jax.sharding.PartitionSpec)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, cfg.vocab)
    logits = jax.jit(lambda p, t: llama.apply(cfg, p, t))(params, tokens)
    assert logits.shape == (1, 64, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    counts = llama.expert_unit_counts(cfg, params, tokens)
    assert counts.shape == (26, cfg.n_experts)
    assert [int(c) for c in counts.sum(axis=1)] == [2 * 64] * 26


@pytest.mark.parametrize("name,leaves,checksum,loss", [
    ("tiny", 12, 55238.10294479898, 6.17431116104126),
    ("moe_tiny", 13, 156614.26303055455, 6.086923122406006),
    ("olmoe", 15, 208743.01844608856, 6.17168664932251),
    ("looped", 16, 99763.4812040137, 5.88877534866333)])
@pytest.mark.usefixtures("full_optimisation")
def test_a_homogeneous_configuration_is_what_it_was(name, leaves, checksum,
                                                    loss):
    """One run, the parameter tree and the weights for a seed that the
    commit before runs existed gave (numbers taken from it), and its loss."""
    cfg = {
        "tiny": llama.tiny(), "moe_tiny": llama.moe_tiny(),
        "olmoe": dataclasses.replace(
            llama.moe_tiny(), n_kv_heads=4, capacity_factor=None,
            moe_renormalize=False, moe_z_coef=1e-3, qk_norm=True),
        "looped": dataclasses.replace(
            llama.tiny(), n_kv_heads=4, ut_steps=3, sandwich_norm=True,
            exit_gate=True, exit_entropy_coef=0.1)}[name]
    assert len(llama.layer_runs(cfg)) == 1
    params = llama.init(jax.random.PRNGKey(7), cfg)
    assert isinstance(params["layers"], dict)
    flat = jax.tree.leaves(params)
    assert len(flat) == leaves
    total = sum(np.sum(np.abs(np.asarray(a, np.float64))) * (i + 1)
                for i, a in enumerate(flat))
    assert total == pytest.approx(checksum, rel=1e-12)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    got = jax.jit(llama.make_loss_fn(cfg, attn="flash", remat="dots",
                                     loss_chunk=16))(params, (tokens, tokens))
    assert float(got) == pytest.approx(loss, rel=1e-6)
