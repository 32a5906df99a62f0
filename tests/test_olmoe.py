"""OLMoE-style configurations (`llama.Config(capacity_factor=None,
moe_renormalize=False, moe_z_coef>0, qk_norm=True)`): the dropless sorted
dispatch, QK-norm and the router z-loss, against the benchmark's plain
reference (`benchmark/reference/olmoe-1b-7b.py`, loaded by path: it imports
nothing of the program) and against the one-hot dispatch at C = G.  Small
sizes, float32, on the CPU; the published widths are compared on the chip by
the benchmark's runner.
"""

import dataclasses
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama, llama_decode
from torchmpi_tpu.parallel import make_mesh

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

E, K, LAYERS = 8, 4, 2
CFG = llama.Config(vocab=256, d_model=64, n_layers=LAYERS, n_heads=4,
                   n_kv_heads=4, d_ff=32, max_seq=128, rope_theta=1e4,
                   n_experts=E, expert_top_k=K, capacity_factor=None,
                   moe_renormalize=False, moe_aux_coef=0.01, moe_z_coef=1e-3,
                   qk_norm=True)
# The same model as the reference reads it: the keys of the configuration file.
REF_CFG = {"hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": LAYERS,
           "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
           "num_experts": E, "num_experts_per_tok": K, "norm_topk_prob": False,
           "rope_theta": 1e4, "rms_norm_eps": 1e-5, "router_aux_loss_coef": 0.01,
           "router_z_loss_coef": 1e-3}

# float32 on both sides, the same products in another order: differences are
# rounding, 1e-6 of a value's scale.  The mildest of the four faults below,
# a dropped z-loss, moves the loss by 0.001 x mean(logsumexp^2), about 4e-3
# of 5.6 (7e-4 relative), so 2e-5 tells them apart with room on both sides.
RTOL = 2e-5


def _benchmark_module(kind, name):
    """A file of the benchmark, loaded by path."""
    spec = importlib.util.spec_from_file_location(
        name.replace("-", "_"),
        os.path.join(ROOT, "benchmark", kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _benchmark_module("reference", "olmoe-1b-7b")


@pytest.fixture(scope="module")
def expected(reference, weights, sample):
    """The reference's (loss, logits, gradients), computed once."""
    return reference.loss_and_grads(REF_CFG, weights, sample)


@pytest.fixture(scope="module")
def weights():
    """Seeded weights with norm weights off 1 and a router wide enough that
    the k-th and (k+1)-th choices are not rounding apart."""
    p = llama.init(jax.random.PRNGKey(0), CFG)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    lay = dict(p["layers"])
    for name in ("q_norm", "k_norm", "attn_norm", "mlp_norm"):
        lay[name] = 1.0 + 0.3 * jax.random.normal(next(keys), lay[name].shape)
    lay["router"] = 0.5 * jax.random.normal(next(keys), lay["router"].shape)
    return {**p, "layers": lay}


@pytest.fixture(scope="module")
def sample():
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 128), 0, CFG.vocab)
    targets = jax.random.randint(jax.random.PRNGKey(3), (2, 128), 0, CFG.vocab)
    return tokens, targets


def _system(cfg, params, sample, attn="full", loss_chunk=64):
    """(loss, logits, gradients) through the normal path, with the layer's
    remat and the chunked loss the benchmark's cell runs."""
    loss_fn = llama.make_loss_fn(cfg, attn=attn, remat="dots",
                                 loss_chunk=loss_chunk)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, sample)
    return loss, jax.jit(lambda p, t: llama.apply(cfg, p, t, attn=attn))(
        params, sample[0]), grads


def _worst(system, ref):
    """The largest difference of loss, logits and every leaf's gradient,
    each relative to the scale of the reference's."""
    (s_loss, s_logits, s_grads), (r_loss, r_logits, r_grads) = system, ref
    scaled = lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    out = {"loss": abs(float(s_loss) - float(r_loss)) / abs(float(r_loss)),
           "logits": scaled(s_logits, r_logits)}
    flat_s = jax.tree_util.tree_flatten_with_path(s_grads)[0]
    for (path, s), r in zip(flat_s, jax.tree.leaves(r_grads)):
        out["grad " + jax.tree_util.keystr(path)] = scaled(s, r)
    return out


@pytest.mark.parametrize("attn", ["full", "flash"])
def test_system_matches_the_plain_reference(expected, weights, sample, attn):
    """(a) logits, loss and every leaf's gradient, flash in interpret mode
    and full attention."""
    found = _worst(_system(CFG, weights, sample, attn), expected)
    assert max(found.values()) < RTOL, found
    assert len(found) == 2 + len(jax.tree.leaves(weights))


@pytest.mark.parametrize("chunks", [1, 8])
def test_chunked_head_matches_the_plain_reference(expected, weights, sample,
                                                  chunks):
    """The head's gradients, taken in the chunk's forward pass, under the
    cell's flash kernels and layer remat: one chunk and eight (two above)."""
    found = _worst(_system(CFG, weights, sample, "flash",
                           loss_chunk=sample[0].shape[1] // chunks), expected)
    assert max(found.values()) < RTOL, found


def test_head_loss_ms_reads_the_join():
    """`benchmark/layers/head_loss_ms.py` (tier-1 does not collect
    `benchmark/tests/`): the `head_loss` entry of the runner's join, `None`
    where the join left nothing."""
    read = _benchmark_module("layers", "head_loss_ms").read
    assert read({"counters": {}}) is None
    assert read({"counters": {"scope_ms": {"optimizer": 21.3}}}) is None
    assert read({"counters": {"scope_ms": {"head_loss": 61.5}}}) == 61.5


def test_kernel_calls_reads_the_runners_counter():
    """`benchmark/layers/kernel_calls.py`: the count the token runners leave
    of the compiled step's `tpu_custom_call`s, 0 where the program holds no
    kernel, `None` where a runner left no count (the ResNet cells')."""
    read = _benchmark_module("layers", "kernel_calls").read
    assert read({"counters": {}}) is None
    assert read({"counters": {"kernel_calls": 0}}) == 0
    assert read({"counters": {"kernel_calls": 22, "scope_ms": {}}}) == 22


def _skip_an_expert(params):
    lay = dict(params["layers"])
    lay["w_down"] = lay["w_down"].at[:, 3].set(0.0)
    return {**params, "layers": lay}


FAULTS = {
    "renormalised": (dataclasses.replace(CFG, moe_renormalize=True), None),
    "no_z_loss": (dataclasses.replace(CFG, moe_z_coef=0.0), None),
    "no_qk_norm": (dataclasses.replace(CFG, qk_norm=False), None),
    "skipped_expert": (CFG, _skip_an_expert),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_tolerance_catches(expected, weights, sample, fault):
    """(a) the tolerance is tight enough: a system that renormalises the
    top-k weights, drops the z-loss, skips an expert or skips QK-norm is
    outside it."""
    cfg, damage = FAULTS[fault]
    found = _worst(_system(cfg, damage(weights) if damage else weights, sample),
                   expected)
    assert max(found.values()) > 10 * RTOL, found


def _lopsided_layer():
    """One layer's MoE leaves and tokens whose routing is lopsided by
    construction: every entry of x is positive, expert 5's router column is
    negative (no unit), experts 0 and 1 large (every token's first two
    choices)."""
    cfg = dataclasses.replace(CFG, n_layers=1)
    lp = jax.tree.map(lambda a: a[0],
                      llama.init(jax.random.PRNGKey(4), cfg)["layers"])
    router = 0.05 * jax.random.normal(jax.random.PRNGKey(5), (cfg.d_model, E))
    lp["router"] = (router.at[:, 5].set(-0.2).at[:, 0].set(0.3)
                    .at[:, 1].set(0.25))
    x = 0.5 + jnp.abs(jax.random.normal(jax.random.PRNGKey(6),
                                        (2, 64, cfg.d_model)))
    return cfg, lp, x


def test_sorted_dispatch_equals_one_hot_at_full_capacity():
    """(b) `_moe_ffn_sorted` against the one-hot dispatch with C = G (a
    capacity factor of E / k in one routing group) on the same weights:
    output, aux terms and the gradients of tokens and of every MoE leaf."""
    cfg, lp, x = _lopsided_layer()
    one_hot = dataclasses.replace(cfg, capacity_factor=E / K,
                                  moe_group_size=x.shape[0] * x.shape[1])
    counts = llama._route_tokens(cfg, lp, x.reshape(-1, cfg.d_model))[2]
    assert int(counts[5]) == 0 and int(counts[0]) == 128 == int(counts[1])

    def value(c):
        def f(lp, x):
            y, aux = llama._moe_ffn(c, lp, x)
            return jnp.sum(y * jnp.cos(jnp.arange(y.size).reshape(y.shape))) \
                + jnp.sum(aux), (y, aux)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(lp, x)

    ((_, (y_s, aux_s)), g_s), ((_, (y_o, aux_o)), g_o) = value(cfg), value(one_hot)
    np.testing.assert_allclose(y_s, y_o, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(aux_s, aux_o, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_s), jax.tree.leaves(g_o)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))
    # the expert nobody chose gets no gradient, and is not NaN
    assert float(jnp.max(jnp.abs(g_s[0]["w_gate"][5]))) == 0.0


def test_every_routed_unit_is_counted(weights, sample):
    """(c) dropless is a fact of the program: each layer's per-expert unit
    counts sum to k * T, and the busiest expert is at or over the mean."""
    counts = np.asarray(jax.jit(lambda p, t: llama.expert_unit_counts(
        CFG, p, t))(weights, sample[0]))
    assert counts.shape == (LAYERS, E)
    assert (counts.sum(axis=1) == K * sample[0].size).all()
    assert (counts.max(axis=1) * E / counts.sum(axis=1) >= 1.0).all()


# The loss and the gradient norm of `moe_tiny` configurations at the parent
# commit of the PR that brought the dropless path (float32 on the CPU, the
# seeds below): Mixtral-style routing takes the path it took, bit for bit.
# Since PR 41 the rotation's products are summed in another order under the
# CPU compiler (`llama._rotate_pairs`; `tests/test_ouro.py` says how): two
# of the six numbers moved by one bit, pinned again at that PR.
PINNED = {(4, 2): ("0x1.8366820000000p+2", "0x1.7e7bf60000000p+3"),
          (4, 1): ("0x1.8a8c320000000p+2", "0x1.215e060000000p+3"),
          (8, 2): ("0x1.846f020000000p+2", "0x1.7a6d480000000p+3")}


@pytest.mark.parametrize("experts,k", sorted(PINNED))
@pytest.mark.usefixtures("full_optimisation")
def test_capacity_routing_is_bit_equal_to_before(experts, k):
    """(d) `capacity_factor` 1.25 in routing groups, renormalised for k > 1."""
    cfg = llama.moe_tiny(n_experts=experts, k=k)
    assert cfg.capacity_factor == 1.25 and cfg.moe_renormalize
    p = llama.init(jax.random.PRNGKey(7), cfg)
    batch = tuple(jax.random.randint(jax.random.PRNGKey(s), (2, 64), 0, cfg.vocab)
                  for s in (8, 9))
    loss, grads = jax.jit(jax.value_and_grad(llama.make_loss_fn(
        cfg, attn="full", remat="dots", loss_chunk=32)))(p, batch)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    assert (float(loss).hex(), float(norm).hex()) == PINNED[experts, k]


def test_prefill_and_decode_apply_qk_norm(weights, sample):
    """(e) the cache path runs the same QK-norm as the training forward:
    prefill's last logits and the next decode step's agree with teacher
    forcing, which ignoring the norm's weights would not."""
    tokens = sample[0][:, :33]
    full = llama.apply(CFG, weights, tokens)
    cache = llama_decode.init_kv_cache(CFG, tokens.shape[0], 64)
    logits, cache = jax.jit(lambda p, c, t: llama_decode._prefill(CFG, p, c, t))(
        weights, cache, tokens[:, :32])
    np.testing.assert_allclose(logits, full[:, 31], rtol=1e-4, atol=1e-4)
    step, _ = jax.jit(lambda p, c, t: llama_decode._decode_step(
        CFG, p, c, t, jnp.full(t.shape, 32)))(weights, cache, tokens[:, 32])
    np.testing.assert_allclose(step, full[:, 32], rtol=1e-4, atol=1e-4)
    plain = llama.apply(dataclasses.replace(CFG, qk_norm=False), weights, tokens)
    assert float(jnp.max(jnp.abs(plain[:, 32] - full[:, 32]))) > 1e-2


@pytest.mark.parametrize("axes", [{"dp": 2, "tp": 2}, {"ep": 2},
                                  {"ep": 2, "tp": 2}],
                         ids=["dp2-tp2", "ep2", "ep2-tp2"])
def test_dropless_on_a_mesh(weights, sample, axes):
    """Under GSPMD on dp x tp the sorted dispatch gives one device's loss
    and gradients, the auxiliary and z terms with it; on an `ep` axis too
    (the batch's two rows over it, 32 of the 64 experts a rank, the units
    exchanged), which was refused until the sorted dispatch had a form for
    experts sharded over chips."""
    alone = jax.jit(jax.value_and_grad(llama.make_loss_fn(CFG)))(weights, sample)
    mesh = make_mesh(axes, devices=jax.devices()[:int(np.prod(
        list(axes.values())))])
    sharded = llama.shard_params(weights, mesh, CFG)
    loss, grads = jax.jit(jax.value_and_grad(
        llama.make_loss_fn(CFG, mesh)))(sharded, sample)
    np.testing.assert_allclose(loss, alone[0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(alone[1])):
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))
