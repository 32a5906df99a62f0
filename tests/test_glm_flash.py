"""GLM-4.7-Flash-style stacks (``llama.glm_4_7_flash``): rotary latent
attention with a query latent, flash attention at heads of 256, the
multi-token-prediction module and its loss, a chip's share of 64 sigmoid-routed
experts beside a shared one, against the plain reference the benchmark keeps
(``benchmark/reference/glm-4.7-flash.py``, which imports nothing of the
program); the remat policies, the frozen selection biases, the refusals and
the names in the device program.  Small widths, float32, the CPU."""

import dataclasses
import importlib.util
import os
import re

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.ops.flash_attention import flash_attention
from torchmpi_tpu.parallel import mesh as pmesh

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = llama.glm_4_7_flash()


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "reference", "glm-4.7-flash.py")
    spec = importlib.util.spec_from_file_location("glm_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def glm_tiny(n_layers=5, n_experts=8, held=(0, 2), k=2, **more):
    """The published pattern's first ``n_layers`` layers and the module at
    toy widths; keys and values of different widths, as published."""
    return dataclasses.replace(
        PUBLISHED, vocab=128, d_model=64, n_layers=n_layers, n_heads=4,
        n_kv_heads=4, d_ff=32, dense_d_ff=96, max_seq=256,
        n_experts=n_experts, expert_top_k=k, q_lora_rank=40, kv_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
        layer_kinds=PUBLISHED.layer_kinds[:n_layers], experts_held=held,
        **more)


def file_of(cfg):
    """The configuration file's keys the reference reads, for ``cfg``."""
    first, held = cfg.experts_held or (0, cfg.n_experts)
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "q_lora_rank": cfg.q_lora_rank,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "first_k_dense_replace": 1,
        "published": {"n_routed_experts": cfg.n_experts},
        "n_routed_experts": held, "experts_held_first": first,
        "num_experts_per_tok": cfg.expert_top_k,
        "n_shared_experts": cfg.n_shared_experts,
        "norm_topk_prob": cfg.moe_renormalize,
        "routed_scaling_factor": cfg.routed_scale,
        "num_nextn_predict_layers": cfg.mtp_layers,
        "mtp_loss_weight": cfg.mtp_coef,
    }


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def layer_of(params, run, i=0):
    return jax.tree.map(lambda a: a[i], params["layers"][run])


@pytest.fixture(scope="module")
def model():
    """A dense layer, an expert layer and the module: every kind there is."""
    cfg = glm_tiny(n_layers=2)
    return cfg, llama.init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def five():
    cfg = glm_tiny()
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


# ------------------------------------------------------ the latent mixer

@pytest.mark.parametrize("attn", ["full", "flash"])
def test_rotary_latent_block_against_the_reference(model, reference, attn):
    """Forward and every leaf's gradient of the latent mixer with a query
    latent and the rotation, and the reference without the rotation is
    another function (the control the benchmark runs)."""
    cfg, params = model
    assert llama.layer_runs(cfg) == (("mla", "dense", 1), ("mla", "moe", 1))
    lp = layer_of(params, 1)
    assert "wq" not in lp and lp["wq_a"].shape == (64, 40)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, cfg.d_model))
    positions = jnp.arange(64)
    impl = llama._mixer_impls(cfg, attn, None)["mla"]
    ours = lambda lp, x: llama._mla_block(cfg, lp, x, impl, positions)
    theirs = lambda lp, x: jax.vmap(
        lambda s: reference.mla_mixer(file_of(cfg), lp, s))(x)
    want = theirs(lp, x)
    assert rel(ours(lp, x), want) < 1e-5
    assert rel(jax.vmap(lambda s: reference.mla_mixer(
        file_of(cfg), lp, s, rotated=False))(x), want) > 0.1
    loss = lambda f: lambda lp, x: jnp.sum(jnp.sin(f(lp, x)))
    got = jax.grad(loss(ours), argnums=(0, 1))(lp, x)
    wanted = jax.grad(loss(theirs), argnums=(0, 1))(lp, x)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(wanted)):
        name = jax.tree_util.keystr(path)
        if any(used in name for used in ("wq_a", "q_norm", "wq_b", "wkv_a",
                                         "kv_norm", "wkv_b", "wo", "[1]")):
            assert rel(g, w) < 1e-4, name
        else:
            assert float(jnp.max(jnp.abs(g))) == 0.0, name


def test_a_shift_of_all_positions_changes_nothing(model):
    """Rotary scores depend on the distance alone."""
    cfg, params = model
    lp = layer_of(params, 1)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, cfg.d_model))
    impl = llama._mixer_impls(cfg, "full", None)["mla"]
    at = lambda shift: llama._mla_block(cfg, lp, x, impl,
                                        jnp.arange(64) + shift)
    assert rel(at(37), at(0)) < 1e-5
    off = dataclasses.replace(cfg, mla_rope=False)
    assert rel(llama._mla_block(off, lp, x, impl, jnp.arange(64)), at(0)) > 0.1


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128)])
def test_flash_at_heads_of_256(blocks):
    """``Dk = Dv = 256``, two whole registers a row: the forward kernel and
    the one backward kernel in interpret mode against the plain form."""
    L, H, D = 256, 2, 256
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, L, H, D))
               for i in range(3))
    scale = D ** -0.5
    ours = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=blocks[0], block_k=blocks[1],
        scale=scale)
    plain_form = lambda q, k, v: llama._causal_attention(q, k, v, scale)
    assert rel(ours(q, k, v), plain_form(q, k, v)) < 1e-5
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    names = []
    jaxpr = jax.make_jaxpr(jax.grad(loss(ours), argnums=(0, 1, 2)))(q, k, v)
    _kernels(jaxpr.jaxpr, names)
    assert names == ["flash_fwd", "flash_bwd"]
    for g, w in zip(jax.grad(loss(ours), argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss(plain_form), argnums=(0, 1, 2))(q, k, v)):
        assert rel(g, w) < 1e-5


def _kernels(jaxpr, found):
    """Names of a jaxpr's ``pallas_call`` s, in order, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernels(sub, found)
    return found


# ----------------------------------------------------------- the module

def test_the_modules_loss_and_the_sums_of_both_paths(model, sample):
    """The loss is the main model's plus ``mtp_coef`` times the module's, and
    the embedding's, the head's and the stack's gradients are each the sum of
    the gradients of the two parts; the module's own leaves see the module's
    part alone."""
    cfg, params = model
    loss_of = lambda c: jax.jit(jax.value_and_grad(llama.make_loss_fn(
        c, attn="flash", loss_chunk=32)))
    whole, grads = loss_of(cfg)(params, sample)
    main, g_main = loss_of(dataclasses.replace(cfg, mtp_coef=0.0))(
        params, sample)

    def module_alone(p, s):     # the module's part, by the program's pieces
        h = llama.apply(cfg, p, s[0], attn="flash", return_hidden=True,
                        mtp_tokens=s[1])
        return llama._mtp_loss_parts(cfg, p, h, s[1], 32)[1]

    module, g_module = jax.jit(jax.value_and_grad(module_alone))(params,
                                                                 sample)
    assert float(module) > 0.1
    assert float(whole) == pytest.approx(float(main + module), rel=1e-6)
    parts = llama.mtp_loss_parts(cfg, params, sample, attn="flash",
                                 loss_chunk=32)
    assert float(parts[0]) == pytest.approx(float(main), rel=1e-6)
    assert float(parts[1]) == pytest.approx(float(module) / 0.3, rel=1e-6)
    for name in ("embed", "head", "norm"):
        assert float(jnp.max(jnp.abs(g_module[name]))) > (
            0 if name != "norm" else -1)
        assert rel(grads[name], g_main[name] + g_module[name]) < 1e-5, name
    for got, a, b in zip(jax.tree.leaves(grads["layers"]),
                         jax.tree.leaves(g_main["layers"]),
                         jax.tree.leaves(g_module["layers"])):
        assert rel(got, a + b) < 1e-5 or float(jnp.max(jnp.abs(got))) == 0
    # the final norm feeds the main head alone; the module reads h before it
    assert float(jnp.max(jnp.abs(g_module["norm"]))) == 0.0
    for g, m in zip(jax.tree.leaves(g_main["mtp"]),
                    jax.tree.leaves(grads["mtp"])):
        assert float(jnp.max(jnp.abs(g))) == 0.0
    assert rel(grads["mtp"]["w_eh"], g_module["mtp"]["w_eh"]) < 1e-5


def test_without_its_weight_the_module_changes_nothing(model, sample):
    """``mtp_coef`` 0 gives the main model's loss, and the main logits are
    the same whether the module runs or not."""
    cfg, params = model
    off = dataclasses.replace(cfg, mtp_coef=0.0)
    bare = dataclasses.replace(cfg, mtp_layers=0)
    stack = {k: v for k, v in params.items() if k != "mtp"}
    want = llama.make_loss_fn(bare, attn="flash", loss_chunk=32)(stack, sample)
    got = llama.make_loss_fn(off, attn="flash", loss_chunk=32)(params, sample)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    alone = llama.apply(cfg, params, sample[0])
    main, module = llama.apply(cfg, params, sample[0], mtp_tokens=sample[1])
    np.testing.assert_array_equal(main, alone)
    np.testing.assert_array_equal(alone, llama.apply(bare, stack, sample[0]))
    assert module.shape == main.shape and rel(module, main) > 0.5


def test_five_layers_and_the_module_against_the_reference(five, reference,
                                                          sample, plain):
    """Loss, the main logits and the module's, and every leaf's gradient of
    the five-layer cut (a share of the experts, the chunked head twice)
    against the plain reference."""
    cfg, params = five
    assert llama.layer_runs(cfg) == (("mla", "dense", 1), ("mla", "moe", 4))
    want_loss, want_logits, want = plain
    loss_fn = llama.make_loss_fn(cfg, attn="flash", loss_chunk=32)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, sample)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    logits = jnp.concatenate(jax.jit(lambda p, s: llama.apply(
        cfg, p, s[0], attn="flash", mtp_tokens=s[1]))(params, sample))
    assert logits.shape == want_logits.shape == (4, 96, 128)
    assert rel(logits[:2], want_logits[:2]) < 1e-4
    assert rel(logits[2:], want_logits[2:]) < 1e-4
    flat = jax.tree_util.tree_flatten_with_path(want)[0]
    assert len(flat) == len(jax.tree.leaves(grads)) == 3 + 12 + 2 * 17 + 4
    for (path, w), g in zip(flat, jax.tree.leaves(grads)):
        if path[-1].key == "router_bias":   # moves the choice alone
            assert float(jnp.max(jnp.abs(g))) == 0.0 == float(jnp.max(w))
        else:
            assert rel(g, w) < 2e-3, jax.tree_util.keystr(path)
    # the controls the benchmark runs read as faults here too
    for how in ({"mtp_weight": 0.0}, {"rotated": False}):
        other = jax.jit(lambda p, s, how=how: reference.loss_fn(
            file_of(cfg), p, *s, **how)[0])(params, sample)
        assert abs(float(other) - float(loss)) > 1e-3 * float(loss), how


def test_the_unit_counts_have_a_row_for_the_module(model, reference, sample):
    cfg, params = model
    counts = llama.expert_unit_counts(cfg, params, sample[0],
                                      mtp_tokens=sample[1])
    assert counts.shape == (2, cfg.n_experts)
    assert [int(c) for c in counts.sum(axis=1)] == [2 * 2 * 96] * 2
    want = reference.hidden(file_of(cfg), params, *sample)[2]
    np.testing.assert_array_equal(counts, want)
    with pytest.raises(ValueError, match="mtp_tokens"):
        llama.expert_unit_counts(cfg, params, sample[0])


@pytest.mark.parametrize("n_experts,held", [(16, 2), (8, 2), (8, 1)])
def test_the_shares_add_up(reference, n_experts, held):
    """Over all ``n_experts / held`` shares of a layer (eight of them, as the
    deployment's eight chips, and four), the held experts' parts, with the
    shared expert counted once, sum to the uncut reference's layer output; a
    share's weights are the uncut layer's experts."""
    whole = glm_tiny(n_experts=n_experts, held=None)
    full = layer_of(llama.init(jax.random.PRNGKey(0), whole), 1)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 64, whole.d_model))
    xt = x.reshape(-1, whole.d_model)
    want = reference.experts_ffn(file_of(whole), full, xt)
    shared = reference.swiglu(xt, full["shared_gate"], full["shared_up"],
                              full["shared_down"])
    total = 0.0
    for first in range(0, n_experts, held):
        cfg = glm_tiny(n_experts=n_experts, held=(first, held))
        lp = layer_of(llama.init(jax.random.PRNGKey(0), cfg), 1)
        np.testing.assert_array_equal(lp["w_up"],
                                      full["w_up"][first:first + held])
        part, _ = llama._moe_ffn(cfg, lp, x)
        assert rel(part.reshape(xt.shape), reference.experts_ffn(
            file_of(cfg), lp, xt)) < 1e-5
        total = total + part.reshape(xt.shape) - shared
    assert rel(total + shared, want) < 1e-5


# ------------------------------------------------------- mesh, remat, AdamW

def test_four_devices_against_one():
    """Under GSPMD on dp x tp the stack and the module give one device's loss
    and gradients, the flash kernels in a ``shard_map`` over the batch and
    the heads."""
    cfg = glm_tiny(n_layers=2)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab)
    sample = (tokens, jnp.roll(tokens, -1, 1))
    loss_of = lambda mesh: jax.jit(jax.value_and_grad(llama.make_loss_fn(
        cfg, mesh, attn="flash", loss_chunk=32)))
    alone = loss_of(None)(params, sample)
    mesh = pmesh.make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    loss, grads = loss_of(mesh)(llama.shard_params(params, mesh, cfg), sample)
    np.testing.assert_allclose(loss, alone[0], rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(alone[1])):
        assert rel(a, b) < 1e-4 or float(jnp.max(jnp.abs(b))) == 0.0, \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("remat", ["dots", "full"])
def test_remat_gives_the_gradients_and_replays_no_kernel(model, sample, remat):
    """``"dots"`` and ``"full"`` give ``"none"``'s gradients, and the step
    holds each of the three latent layers' flash kernels (the module's is
    the third) once forward and once backward."""
    cfg, params = model
    grads = lambda r: jax.jit(jax.grad(llama.make_loss_fn(
        cfg, attn="flash", remat=r, loss_chunk=32)))(params, sample)
    for g, w in zip(jax.tree.leaves(grads(remat)),
                    jax.tree.leaves(grads("none"))):
        assert rel(g, w) < 1e-4 or float(jnp.max(jnp.abs(w))) == 0.0
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = llama.make_train_step(cfg, mesh, attn="flash", remat=remat,
                                 loss_chunk=32)
    tokens = jnp.zeros((1, 160), jnp.int32)
    shapes = jax.eval_shape(lambda: params)
    flash = [n for n in _kernels(jax.make_jaxpr(step)(
        shapes, None, tokens, tokens).jaxpr, []) if n and "flash" in n]
    assert flash == ["flash_fwd"] * 3 + ["flash_bwd"] * 3


def test_adamw_leaves_every_selection_bias_alone(model, sample):
    """Weight decay would move a bias whose gradient is zero: the step hands
    every ``router_bias``, the module's too, back to the bit and steps the
    router beside it."""
    cfg, params = model
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    optimizer = optax.adamw(1e-2, weight_decay=0.1)
    step = llama.make_train_step(cfg, mesh, optimizer=optimizer, attn="flash",
                                 remat="full", loss_chunk=32)
    stepped, _, loss = step(jax.tree.map(jnp.copy, params),
                            optimizer.init(params), *sample)
    assert np.isfinite(float(loss))
    pairs = [(stepped["layers"][1], params["layers"][1]),
             (stepped["mtp"]["layer"], params["mtp"]["layer"])]
    for new, old in pairs:
        np.testing.assert_array_equal(new["router_bias"], old["router_bias"])
        assert float(jnp.max(jnp.abs(old["router_bias"]))) > 0
        assert float(jnp.max(jnp.abs(new["router"] - old["router"]))) > 0
    for name in ("enorm", "hnorm", "w_eh", "norm"):
        assert float(jnp.max(jnp.abs(
            stepped["mtp"][name] - params["mtp"][name]))) > 0, name


# ------------------------------------------------ the published model, names

def test_the_published_47_layers_and_the_module_build():
    assert llama.layer_runs(PUBLISHED) == (("mla", "dense", 1),
                                           ("mla", "moe", 46))
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0),
                                               PUBLISHED, jnp.bfloat16))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 30.5e9 < count < 30.7e9
    layer = shapes["mtp"]["layer"]
    assert shapes["mtp"]["w_eh"].shape == (4096, 2048)
    assert layer["wq_a"].shape == (1, 2048, 768)
    assert layer["wq_b"].shape == (1, 768, 20 * 256)
    assert layer["wkv_a"].shape == (1, 2048, 512 + 64)
    assert layer["wkv_b"].shape == (1, 512, 20 * (192 + 256))
    assert layer["wo"].shape == (1, 20 * 256, 2048)
    assert layer["w_gate"].shape == (1, 64, 2048, 1536)
    # the attention's 21.76 M parameters a layer
    assert sum(int(np.prod(layer[k].shape)) for k in (
        "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")
        ) == 21_759_232
    specs = llama.param_specs(PUBLISHED)
    is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, shapes)) == \
        jax.tree.structure(jax.tree.map(lambda s: 0, specs, is_leaf=is_spec))
    # all 47 layers (the 46 scanned) and the module at toy widths
    cfg = glm_tiny(n_layers=47)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, cfg.vocab)
    loss = jax.jit(llama.make_loss_fn(cfg, attn="flash", remat="full",
                                      loss_chunk=32))(params, (tokens, tokens))
    assert np.isfinite(float(loss))
    counts = llama.expert_unit_counts(cfg, params, tokens, mtp_tokens=tokens)
    assert counts.shape == (47, cfg.n_experts)


@pytest.mark.usefixtures("full_optimisation")
def test_kimi_linear_is_what_it_was():
    """With the new fields at their defaults the Kimi Linear preset builds
    the parameter tree and the weights for a seed that the commit before this
    model gave, and its loss (numbers taken from that commit)."""
    kimi = llama.kimi_linear_48b_a3b()
    assert (kimi.q_lora_rank, kimi.mla_rope, kimi.mtp_layers) == (0, False, 0)
    cfg = dataclasses.replace(
        kimi, vocab=128, d_model=64, n_layers=5, n_heads=4, n_kv_heads=4,
        d_ff=32, dense_d_ff=96, max_seq=256, n_experts=8, expert_top_k=2,
        kda_heads=4, kda_head_dim=16, kv_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, layer_kinds=kimi.layer_kinds[:5],
        experts_held=(0, 2))
    params = llama.init(jax.random.PRNGKey(7), cfg)
    assert "mtp" not in params and "wq" in params["layers"][2]
    flat = jax.tree.leaves(params)
    assert len(flat) == 91
    total = sum(np.sum(np.abs(np.asarray(a, np.float64))) * (i + 1)
                for i, a in enumerate(flat))
    assert total == pytest.approx(1154216.0615806908, rel=1e-12)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 128)
    got = jax.jit(llama.make_loss_fn(cfg, attn="flash", remat="dots",
                                     loss_chunk=16))(params, (tokens, tokens))
    assert float(got) == pytest.approx(5.376918792724609, rel=1e-6)


def test_the_programs_names(model, sample):
    """``mtp`` outermost round the module's copy of the names every layer
    has, its embedding read and its pass over the vocabulary; ``mla`` inside
    ``attn``, with the rotation; forward and backward."""
    cfg, params = model
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = llama.make_train_step(cfg, mesh, attn="flash", remat="full",
                                 loss_chunk=32)
    shapes = jax.eval_shape(lambda: params)
    names = set(re.findall(r'loc\("([^"]+)"', step.lower(
        shapes, None, *sample).as_text(debug_info=True)))
    part = lambda scope: re.compile(
        r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    for scope in ("embed", "attn", "mla", "ffn", "moe.router", "moe.dispatch",
                  "moe.experts", "moe.combine", "moe.shared", "final_norm",
                  "head_loss", "optimizer", "mtp"):
        assert any(part(scope).search(n) for n in names), scope
    inside = [n for n in names if part("mtp").search(n)]
    for scope in ("embed", "attn", "mla", "moe.router", "moe.experts",
                  "moe.shared", "final_norm", "head_loss"):
        assert any(re.search(r"mtp\)*/(.*/)?" + re.escape(scope), n)
                   for n in inside), scope
    # outermost: nothing of the model's own scopes lies round it
    assert not any(re.search(r"(attn|mla|head_loss|ffn)\)*/(.*/)?mtp", n)
                   for n in inside)
    assert any("mtp" in n and "flash_fwd" in n for n in names)
    assert any("mtp" in n and "flash_bwd" in n for n in names)
    assert any("mtp" in n and "transpose(" in n for n in names)
    outside = [n for n in names if not part("mtp").search(n)]
    for scope in ("head_loss", "mla", "moe.experts", "embed"):
        assert any(part(scope).search(n) for n in outside), scope
