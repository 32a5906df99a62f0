"""Falcon-H1-style stacks (``llama.falcon_h1_34b``): an attention branch and a
Mamba-2 state-space branch side by side on one normed input in every layer,
constants on the embedding, the logits, both branches, the five sections of
the branch's projection and the FFN, the scan in chunks (``ops.ssd``), against
the token-by-token scan and the plain reference the benchmark keeps
(``benchmark/reference/falcon-h1-34b.py``, which imports nothing of the
program).  Small widths with the published ratios (2 groups, the heads a
multiple of them, a state twice a head's width, several chunks a sequence,
the chunk dividing it), float32, the CPU."""

import dataclasses
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.ops import ssd

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = llama.falcon_h1_34b()
SEQ, CHUNK = 64, 16


@pytest.fixture(scope="module")
def reference():
    path = os.path.join(ROOT, "benchmark", "reference", "falcon-h1-34b.py")
    spec = importlib.util.spec_from_file_location("falcon_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def falcon_tiny(n_layers=2, vocab=128, **more):
    """The published layer at toy widths: 4 + 2 attention heads of 16, 4
    state-space heads of 8 in 2 groups, a state of 8 x 16, chunks of 16."""
    return dataclasses.replace(
        PUBLISHED, vocab=vocab, d_model=48, n_layers=n_layers, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=96, max_seq=256, ssm_heads=4,
        ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_chunk=CHUNK,
        layer_kinds=PUBLISHED.layer_kinds[:n_layers], **more)


def file_of(cfg):
    """The configuration file's keys the reference reads, for ``cfg``."""
    return {
        "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
        "head_dim": cfg.head_dim, "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps, "vocab_size": cfg.vocab,
        "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
        "mamba_d_ssm": cfg.ssm_heads * cfg.ssm_head_dim,
        "mamba_n_groups": cfg.ssm_groups, "mamba_d_state": cfg.ssm_state,
        "mamba_d_conv": cfg.ssm_conv, "mamba_chunk_size": cfg.ssm_chunk,
        "embedding_multiplier": cfg.embed_multiplier,
        "lm_head_multiplier": cfg.head_multiplier,
        "attention_in_multiplier": cfg.attn_in_multiplier,
        "attention_out_multiplier": cfg.attn_out_multiplier,
        "key_multiplier": cfg.key_multiplier,
        "ssm_in_multiplier": cfg.ssm_in_multiplier,
        "ssm_out_multiplier": cfg.ssm_out_multiplier,
        "ssm_multipliers": list(cfg.ssm_multipliers),
        "mlp_multipliers": list(cfg.ffn_multipliers)}


def batch_of(cfg, seed=1, rows=2, seq=SEQ):
    tokens, targets = jax.random.randint(
        jax.random.PRNGKey(seed), (2, rows, seq), 0, cfg.vocab)
    return tokens, targets


def scan_inputs(seed, strong=False, batch=2, seq=SEQ + 8, H=4, P=8, G=2, N=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    dt = jax.nn.softplus(normal(ks[1], batch, seq, H) + (3.0 if strong else -2.0))
    A = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0,
                                    maxval=np.log(16.0)))
    return (normal(ks[0], batch, seq, H, P), dt, A * (20 if strong else 1),
            normal(ks[3], batch, seq, G, N), normal(ks[4], batch, seq, G, N),
            normal(ks[5], H))


def close(ours, theirs, rtol):
    scale = float(jnp.max(jnp.abs(theirs))) + 1e-30
    assert bool(jnp.all(jnp.isfinite(ours)))
    assert float(jnp.max(jnp.abs(ours - theirs))) <= rtol * scale


# ------------------------------------------------------------------ the scan

def _chunked(*args):
    return ssd.ssd(*args, chunk=CHUNK)


@jax.jit(static_argnums=0)
def _scan_and_gradients(f, weight, *args):
    return (f(*args), *jax.grad(lambda *a: jnp.sum(f(*a) * weight),
                                argnums=range(6))(*args))


@pytest.mark.parametrize("strong", [False, True], ids=["decay", "strong_decay"])
def test_scan_matches_the_token_by_token_scan(strong):
    """Forward and every gradient, 72 tokens in chunks of 16 (the last one
    padded; a token reads states written chunks before it).  With strong
    decay (A dt down to -1,000 a token) exp(-G) alone would overflow: every
    exponent here is a difference that is never positive, so nothing does."""
    args = scan_inputs(0, strong)
    weight = jax.random.normal(jax.random.PRNGKey(7), args[0].shape)
    with jax.default_matmul_precision("highest"):
        (y, *grads), (want, *wanted) = (
            _scan_and_gradients(f, weight, *args)
            for f in (_chunked, ssd.ssd_recurrent))
    close(y, want, 1e-5)
    for ours, theirs in zip(grads, wanted):
        # Under strong decay A's gradient is 1e-15 of x's on both sides.
        assert bool(jnp.all(jnp.isfinite(ours)))
        if not strong or float(jnp.max(jnp.abs(theirs))) > 1e-6:
            close(ours, theirs, 1e-5)


def test_scan_carries_the_state_across_a_chunk_boundary():
    """One written token, no decay: every later token of every later chunk
    reads it back whole."""
    L, H, P, G, N = 3 * CHUNK, 2, 4, 1, 8
    x = jnp.zeros((1, L, H, P)).at[0, 3].set(1.0)
    B = jnp.zeros((1, L, G, N)).at[0, 3, 0, 5].set(1.0)
    C = jnp.zeros((1, L, G, N)).at[:, :, 0, 5].set(1.0)
    y = jax.jit(_chunked)(x, jnp.ones((1, L, H)), jnp.zeros((H,)), B, C,
                          jnp.zeros((H,)))
    np.testing.assert_array_equal(np.asarray(y[0, :, 0, 0]),
                                  (np.arange(L) >= 3).astype(np.float32))


def test_scan_keeps_its_named_residuals_and_runs_forward_once():
    """Under a policy that keeps ``SSD_RESIDUAL_NAMES`` the gradient's program
    holds the forward pass's four products and its one loop over the chunks
    once; under one that keeps nothing, twice."""
    args = scan_inputs(1)
    policy = jax.checkpoint_policies.save_only_these_names(
        *ssd.SSD_RESIDUAL_NAMES)
    loss = lambda *a: jnp.sum(ssd.ssd(*a, chunk=CHUNK) ** 2)
    count = lambda f, what: str(jax.make_jaxpr(f)(*args)).count(what)
    for what, forward in (("dot_general", 4), (" scan[", 1)):
        assert count(loss, what) == forward
        kept = count(jax.grad(jax.checkpoint(loss, policy=policy)), what)
        assert count(jax.grad(jax.checkpoint(loss)), what) == kept + forward
    # One loop each way and no third.
    assert count(jax.grad(jax.checkpoint(loss, policy=policy)), " scan[") == 2


# ----------------------------------------------------------------- the model

def _seeded_weights(cfg, seed=0):
    return jax.jit(lambda key: llama.init(key, cfg))(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def seeded():
    cfg = falcon_tiny()
    return cfg, _seeded_weights(cfg), batch_of(cfg)


def _loss(cfg, params, batch):
    """Eagerly: the constants are arguments of the operations, so the
    fourteen cases below compile nothing after the first."""
    return float(llama.make_loss_fn(cfg, attn="full")(params, batch))


@pytest.fixture(scope="module")
def sensitive(seeded):
    """The seeded model where every constant shows in the loss: logits of
    order 1 (at the published 2^-7 the loss is ln(vocab) to five digits
    whatever the stack does), steps of order 1 and a skip of 0.05 (as seeded,
    dt <= 0.1 and D = 1, the scan is a hundredth of the branch's output and
    the gated norm takes a factor on it away)."""
    cfg, params, batch = seeded
    cfg = dataclasses.replace(cfg, head_multiplier=1.0, n_layers=1,
                              layer_kinds=cfg.layer_kinds[:1])
    run = jax.tree.map(lambda a: a[:1], params["layers"][0])
    run = dict(run, ssm_dt_bias=run["ssm_dt_bias"] + 3.0,
               ssm_d=run["ssm_d"] * 0.05)
    params = dict(params, layers=(run,))
    return cfg, params, batch, _loss(cfg, params, batch)


@pytest.fixture(scope="module")
def system(seeded):
    """(loss, gradients, logits) of the seeded model on the seeded batch."""
    cfg, params, batch = seeded
    loss_fn = llama.make_loss_fn(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, grads), logits = jax.jit(lambda p, s: (
            jax.value_and_grad(loss_fn)(p, s), llama.apply(cfg, p, s[0])))(
                params, batch)
    return loss, grads, logits


@pytest.fixture(scope="module")
def referenced(reference, seeded):
    """The reference's (loss, logits, gradients, what each branch added)."""
    cfg, params, batch = seeded
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, s: reference.loss_and_grads(
            file_of(cfg), p, s))(params, batch)


def test_model_matches_the_reference(referenced, system):
    """Loss, logits and every leaf's gradient against the reference's
    layer-at-a-time gradient (a remat policy and the chunked head: the step
    below)."""
    loss, grads, logits = system
    want_loss, want_logits, want, _ = referenced
    np.testing.assert_allclose(loss, want_loss, rtol=2e-5)
    close(logits, want_logits, 2e-4)
    assert (jax.tree.structure(grads) == jax.tree.structure(want))
    for (path, ours), theirs in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(theirs))) > 0, path
        close(ours, theirs, 2e-3)


def test_branch_contributions_match_the_reference(referenced, seeded):
    """What the reference's blocks add on their way to the loss."""
    cfg, params, (tokens, _) = seeded
    theirs = referenced[3]
    with jax.default_matmul_precision("highest"):
        ours = jax.jit(lambda p, t: llama.branch_contributions(cfg, p, t))(
            params, tokens)
    for name in ("attn", "ssm", "ffn"):
        assert ours[name].shape == (cfg.n_layers, *tokens.shape, cfg.d_model)
        close(ours[name], theirs[name], 2e-4)


def _one_scaled(values, at, by):
    return tuple(v * (by if i == at else 1.0) for i, v in enumerate(values))


# Each constant doubled (dt's section times 8: it adds to a bias of 3).
MULTIPLIERS = {
    "embedding": dict(embed_multiplier=2 * PUBLISHED.embed_multiplier),
    "head": dict(head_multiplier=2.0),
    "attention_in": dict(attn_in_multiplier=2.0),
    "attention_out": dict(
        attn_out_multiplier=2 * PUBLISHED.attn_out_multiplier),
    "key": dict(key_multiplier=2 * PUBLISHED.key_multiplier),
    "ssm_in": dict(ssm_in_multiplier=2 * PUBLISHED.ssm_in_multiplier),
    "ssm_out": dict(ssm_out_multiplier=2 * PUBLISHED.ssm_out_multiplier),
    **{f"ssm_section_{name}": dict(ssm_multipliers=_one_scaled(
        PUBLISHED.ssm_multipliers, at, 8.0 if name == "dt" else 2.0))
       for at, name in enumerate(("z", "x", "B", "C", "dt"))},
    "ffn_gate": dict(ffn_multipliers=_one_scaled(
        PUBLISHED.ffn_multipliers, 0, 2.0)),
    "ffn_out": dict(ffn_multipliers=_one_scaled(
        PUBLISHED.ffn_multipliers, 1, 2.0)),
}


@pytest.mark.parametrize("name", sorted(MULTIPLIERS))
def test_each_multiplier_moves_the_loss(sensitive, name):
    """Fourteen constants of the forward pass, none folded into a weight and
    none dropped: each one perturbed changes the loss (float32 resolves 1e-7
    of it)."""
    cfg, params, batch, loss = sensitive
    moved = _loss(dataclasses.replace(cfg, **MULTIPLIERS[name]), params, batch)
    assert abs(moved - loss) > 2e-6 * abs(loss)


def test_multipliers_are_taken_in_float32():
    """A constant times a bfloat16 array is formed in float32 and rounded
    once; rounded to bfloat16 first, 0.0375 would be 0.03760 for every
    element alike."""
    x = jnp.full((8,), 3.0, jnp.bfloat16)
    assert float(llama._times(x, 0.0375)[0]) == float(
        jnp.asarray(3.0 * 0.0375, jnp.bfloat16))
    assert llama._times(x, 1.0) is x


def test_vocabulary_slice(reference, seeded, system):
    """A slice of the vocabulary is a smaller vocabulary (here 128 rows where
    the preset has 261,120): ids from the slice, logits and the loss over it,
    nothing of the rows left out."""
    cfg, params, (tokens, targets) = seeded
    loss, grads, logits = system
    assert params["embed"].shape == grads["embed"].shape == (128, cfg.d_model)
    assert params["head"].shape == grads["head"].shape == (cfg.d_model, 128)
    assert logits.shape == (*tokens.shape, 128)
    np.testing.assert_allclose(
        loss, jnp.mean(reference.nll_of(logits, targets)), rtol=1e-5)


def test_one_sgd_step_lowers_the_loss(devices):
    from torchmpi_tpu.parallel import make_mesh

    cfg = falcon_tiny(n_layers=1)
    mesh = make_mesh({"dp": 1}, devices=devices[:1])
    params = llama.shard_params(_seeded_weights(cfg), mesh, cfg)
    tokens, targets = batch_of(cfg, rows=1)
    step = llama.make_train_step(cfg, mesh, lr=0.5, remat="full",
                                 loss_chunk=32)
    params, _, first = step(params, None, tokens, targets)
    _, _, second = step(params, None, tokens, targets)
    assert np.isfinite(float(first)) and float(second) < float(first)


def test_published_geometry():
    """The preset's parameter count is the model's, 33.64 G: a layer 430.12 M
    (attention 31.46 M, the state-space branch 68.35 M, the FFN 330.30 M, two
    norms), embedding and head 1,336.9 M each."""
    assert PUBLISHED.n_layers == 72 and len(PUBLISHED.layer_kinds) == 72
    four = dataclasses.replace(PUBLISHED, n_layers=4,
                               layer_kinds=PUBLISHED.layer_kinds[:4])
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), four))
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    (run,) = shapes["layers"]
    assert count(run) == 4 * 430_120_032
    assert run["ssm_in"].shape == (4, 5120, 9248)
    assert run["ssm_conv"].shape == (4, 4, 5120)
    assert count(shapes) == 4 * 430_120_032 + 2 * 261_120 * 5120 + 5120
    assert 72 * 430_120_032 + 2 * 261_120 * 5120 + 5120 == 33_642_516_224
    assert llama.layer_runs(PUBLISHED) == (("attn+ssm", "dense", 72),)
