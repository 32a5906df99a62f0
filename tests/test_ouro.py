"""Looped configurations (`llama.Config(ut_steps=T, sandwich_norm=True,
exit_gate=True, exit_entropy_coef=beta)`, Ouro-style): one stack of layers run
T times with shared weights, a post-norm on each branch, the final norm inside
the loop, a head and an exit gate at every recurrent step and the
expected-exit loss, against the benchmark's plain reference
(`benchmark/reference/ouro-2.6b.py`, loaded by path: it imports nothing of the
program).  Small sizes, float32, on the CPU; the published widths are compared
on the chip by the benchmark's runner.
"""

import dataclasses
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama
from torchmpi_tpu.parallel import make_mesh

pytestmark = pytest.mark.usefixtures("quick_compiles")    # conftest.py

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

T, LAYERS = 4, 2
CFG = llama.Config(vocab=256, d_model=64, n_layers=LAYERS, n_heads=4,
                   n_kv_heads=4, d_ff=96, max_seq=128, rope_theta=1e6,
                   norm_eps=1e-6, ut_steps=T, sandwich_norm=True,
                   exit_gate=True, exit_entropy_coef=0.1)
# The same model as the reference reads it: the keys of the configuration file.
REF_CFG = {"hidden_size": 64, "intermediate_size": 96,
           "num_hidden_layers": LAYERS, "num_attention_heads": 4,
           "num_key_value_heads": 4, "head_dim": 16, "rope_theta": 1e6,
           "rms_norm_eps": 1e-6, "total_ut_steps": T, "exit_entropy_coef": 0.1}

# float32 on both sides, the same products in another order: differences are
# rounding, under 1e-5 of a value's scale (the largest over the forms below is
# 6.3e-6).  The mildest of the seven faults, a dropped entropy term, moves the
# loss by 0.1 x H(p), about 0.1 of 6 (2e-2 relative), so 2e-5 tells them apart
# with room on both sides.
RTOL = 2e-5


def _benchmark_module(kind, name):
    """A file of the benchmark, loaded by path."""
    spec = importlib.util.spec_from_file_location(
        name.replace("-", "_").replace(".", "_"),
        os.path.join(ROOT, "benchmark", kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _benchmark_module("reference", "ouro-2.6b")


@pytest.fixture(scope="module")
def weights():
    """Seeded weights with every norm weight off 1 and a gate wide enough
    that no exit probability is rounding from 0 or 1."""
    p = llama.init(jax.random.PRNGKey(0), CFG)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    lay = dict(p["layers"])
    for name in ("attn_norm", "mlp_norm", "attn_post_norm", "mlp_post_norm"):
        lay[name] = 1.0 + 0.3 * jax.random.normal(next(keys), lay[name].shape)
    return {**p, "layers": lay,
            "norm": 1.0 + 0.3 * jax.random.normal(next(keys), p["norm"].shape),
            "gate_w": 0.5 * jax.random.normal(next(keys), (CFG.d_model,)),
            "gate_b": jnp.full((1,), 0.3)}


@pytest.fixture(scope="module")
def sample():
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, CFG.vocab)
    targets = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, CFG.vocab)
    return tokens, targets


@pytest.fixture(scope="module")
def expected(reference, weights, sample):
    """The reference's (loss, logits of all T steps, gradients), once."""
    return jax.jit(lambda p, s: reference.loss_and_grads(REF_CFG, p, s))(
        weights, sample)


def _system(cfg, params, sample, attn="full", loss_chunk=32, remat="full",
            layer_loop=None):
    """(loss, all steps' logits, gradients) through the normal path."""
    loss_fn = llama.make_loss_fn(cfg, attn=attn, remat=remat,
                                 loss_chunk=loss_chunk, layer_loop=layer_loop)
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, sample)
    logits = jax.jit(lambda p, t: llama.apply(
        cfg, p, t, attn=attn, layer_loop=layer_loop, all_steps=True))(
            params, sample[0])
    return loss, logits, grads


def _worst(system, ref):
    """The largest difference of loss, logits and every leaf's gradient,
    each relative to the scale of the reference's."""
    (s_loss, s_logits, s_grads), (r_loss, r_logits, r_grads) = system, ref
    scaled = lambda a, b: float(jnp.max(jnp.abs(a - b))
                                / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))
    out = {"loss": abs(float(s_loss) - float(r_loss)) / abs(float(r_loss))}
    if s_logits.shape == r_logits.shape:
        out["logits"] = scaled(s_logits, r_logits)
    flat_s = jax.tree_util.tree_flatten_with_path(s_grads)[0]
    for (path, s), r in zip(flat_s, jax.tree.leaves(r_grads)):
        out["grad " + jax.tree_util.keystr(path)] = scaled(s, r)
    return out


MIXED = ("full", "dots", "none", "dots")     # a policy for each recurrent step


@pytest.mark.parametrize("attn,loss_chunk,remat,layer_loop", [
    ("full", 0, "none", "scan"),
    ("full", 32, "dots", "unroll"),
    ("flash", 32, "full", None),
    ("flash", 32, MIXED, "scan"),
    ("flash", 0, MIXED, "unroll"),
])
def test_system_matches_the_plain_reference(expected, weights, sample, attn,
                                            loss_chunk, remat, layer_loop):
    """Loss, the logits of all T steps and every leaf's gradient: full
    attention and flash in interpret mode, the dense head and the chunked one,
    every remat form (one policy, or one for each recurrent step) and both
    forms of the layer loop."""
    found = _worst(_system(CFG, weights, sample, attn, loss_chunk, remat,
                           layer_loop), expected)
    assert max(found.values()) < RTOL, found
    assert len(found) == 2 + len(jax.tree.leaves(weights))
    assert expected[1].shape == (T, 2, 64, CFG.vocab)


def test_apply_returns_the_last_steps_logits(weights, sample):
    """`early_exit_threshold` 1: inference never leaves early."""
    every = llama.apply(CFG, weights, sample[0], all_steps=True)
    np.testing.assert_array_equal(llama.apply(CFG, weights, sample[0]),
                                  every[-1])
    with pytest.raises(ValueError, match="recurrent steps"):
        llama.apply(CFG, weights, sample[0], remat=("full", "dots"))


# ------------------------------------------------------------------ faults
# Each fault is written into the plain reference, where it is one line of
# plain Python: the distance from the system to a reference with the fault is
# the distance from the reference to a system with it.

def _no_post_norm(ref):
    def layer(cfg, lp, h):
        eps = cfg["rms_norm_eps"]
        a = h + ref.rms_norm(jax.vmap(lambda x: ref.attention(
            cfg, lp, ref.rms_norm(x, lp["attn_norm"], eps)))(h),
            lp["attn_post_norm"], eps)
        return a + ref.swiglu(lp, ref.rms_norm(a, lp["mlp_norm"], eps))
    return {"layer": layer}


def _stack(ref, cfg, layers, h):
    for i in range(cfg["num_hidden_layers"]):
        h = ref.layer(cfg, jax.tree.map(lambda a: a[i], layers), h)
    return h


def _norm_outside_the_loop(ref):
    def forward(cfg, params, tokens):
        h = params["embed"][tokens]
        logits, gates = [], []
        for _ in range(cfg["total_ut_steps"]):
            h = _stack(ref, cfg, params["layers"], h)    # the raw state goes on
            n = ref.rms_norm(h, params["norm"], cfg["rms_norm_eps"])
            logits.append(n @ params["head"])
            gates.append(ref.gate_logits(params, n))
        return jnp.stack(logits), jnp.stack(gates)
    return {"forward": forward}


def _one_steps_weight_gradient(ref):
    def forward(cfg, params, tokens):
        h = params["embed"][tokens]
        logits, gates = [], []
        for t in range(cfg["total_ut_steps"]):
            last = t == cfg["total_ut_steps"] - 1
            layers = (params["layers"] if last
                      else jax.lax.stop_gradient(params["layers"]))
            h = ref.rms_norm(_stack(ref, cfg, layers, h), params["norm"],
                             cfg["rms_norm_eps"])
            logits.append(h @ params["head"])
            gates.append(ref.gate_logits(params, h))
        return jnp.stack(logits), jnp.stack(gates)
    return {"forward": forward}


def _gate_gradient_stopped(ref):
    exit_distribution = ref.exit_distribution
    return {"exit_distribution":
            lambda gates: jax.lax.stop_gradient(exit_distribution(gates))}


def _head_at_the_last_step_only(ref):
    def loss_fn(cfg, params, tokens, targets):
        logits, _ = ref.forward(cfg, params, tokens)
        logp = jax.nn.log_softmax(logits[-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None],
                                             axis=-1)), logits
    return {"loss_fn": loss_fn}


FAULTS = {
    "one_step_fewer": ({**REF_CFG, "total_ut_steps": T - 1}, None),
    "a_post_norm_left_out": (REF_CFG, _no_post_norm),
    "final_norm_outside_the_loop": (REF_CFG, _norm_outside_the_loop),
    "entropy_term_dropped": ({**REF_CFG, "exit_entropy_coef": 0.0}, None),
    "gate_gradient_stopped": (REF_CFG, _gate_gradient_stopped),
    "shared_weight_gradient_from_one_step": (REF_CFG,
                                             _one_steps_weight_gradient),
    "head_at_the_last_step_only": (REF_CFG, _head_at_the_last_step_only),
}


@pytest.fixture(scope="module")
def found_system(weights, sample):
    return _system(CFG, weights, sample)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_tolerance_catches(monkeypatch, reference, found_system, weights,
                               sample, fault):
    """The tolerance is tight enough: T - 1 steps, a post-norm left out, the
    final norm outside the loop, the entropy term dropped, the gate's gradient
    stopped, a shared weight's gradient from one step only, or the head at the
    last step only, is outside it."""
    ref_cfg, damage = FAULTS[fault]
    for name, fn in (damage(reference) if damage else {}).items():
        monkeypatch.setattr(reference, name, fn)
    faulty = jax.jit(lambda p, s: reference.loss_and_grads(ref_cfg, p, s))(
        weights, sample)
    found = _worst(found_system, faulty)
    assert max(found.values()) > 10 * RTOL, found
    # ... and outside at least one of the limits the chip's comparison has
    # (`benchmark/compare.py` with the reference's `TOLERANCE`), which bf16 is
    # allowed; a step too few hands its last logits twice to keep the shape.
    loss, logits, grads = faulty
    logits = jnp.concatenate([logits, logits[-1:]])[:T]
    limits = _limits(reference, found_system, (loss, logits, grads), weights,
                     sample)
    assert not limits["ok"], limits


def _limits(reference, system, plain, weights, sample):
    """`benchmark/compare.py` on two finished sides, as the runner calls it:
    the reference's `TOLERANCE`, its `LEAF_AXES`, the gradients `compared`."""
    compare = _benchmark_module(".", "compare")
    side = lambda found: lambda p, s: (found[0], found[1],
                                       reference.compared(found[2]))
    return compare.check(side(system), side(plain), weights, sample,
                         reference.TOLERANCE, reference.LEAF_AXES)


def test_the_gates_bias_stays_in_the_comparison(reference, found_system,
                                                expected, weights, sample):
    """`compared` sets the bias's one number beside the size its terms have
    where they do not cancel: the sides agree as they are, and a bias gradient
    dropped, or stopped with the weight's left whole, fails the leaf limit."""
    assert _limits(reference, found_system, expected, weights, sample)["ok"]
    loss, logits, grads = found_system
    dropped = {**grads, "gate_b": jnp.zeros_like(grads["gate_b"])}
    limits = _limits(reference, (loss, logits, dropped), expected, weights,
                     sample)
    assert not limits["ok"] and limits["worst_leaf"] == "gate_b", limits
    assert limits["leaf_norm_rel_max"] > 2 * reference.TOLERANCE[
        "leaf_norm_rel_max"]
    pair = reference.compared(grads)["gate_b"]
    assert pair.shape == (2,) and float(pair[0]) == float(grads["gate_b"][0])
    np.testing.assert_allclose(pair[1], jnp.sqrt(jnp.mean(
        grads["gate_w"] ** 2)), rtol=1e-6)


def test_the_references_loss_alone_is_its_loss(reference, expected, weights,
                                               sample):
    """`loss_only`, one sequence after another and the NLL of a few rows at a
    time, is the loss `loss_and_grads` takes from the whole logits."""
    for rows in (16, 64):
        alone = jax.jit(lambda p, s: reference.loss_only(REF_CFG, p, s, rows))(
            weights, sample)
        np.testing.assert_allclose(alone, expected[0], rtol=1e-6)


OPT = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "weight_decay": 0.1}


def test_the_steps_first_update_is_the_references(reference, weights, sample):
    """One `make_train_step` under `optax.adamw` from zero moments against
    `adamw_first_step` on the gradient of the program's loss: every leaf's
    change agrees to rounding; half the learning rate, no weight decay (the
    norms' leaves, whose weights are near 1) or no bias correction do not."""
    import optax

    mesh = make_mesh({"dp": 1}, devices=jax.devices()[:1])
    kinds = dict(remat=MIXED, loss_chunk=32)
    optimizer = optax.adamw(**OPT)
    step = llama.make_train_step(CFG, mesh, optimizer=optimizer, **kinds)
    grads = jax.jit(jax.grad(llama.make_loss_fn(CFG, mesh, **kinds)))(
        weights, sample)
    copy = jax.tree.map(jnp.copy, weights)        # the step takes its own
    stepped, _, _ = step(copy, optimizer.init(copy), *sample)

    def worst(opt, g=grads):
        want = reference.adamw_first_step(weights, g, opt)
        return max(float(jnp.max(jnp.abs(
            jnp.linalg.norm(a - w) - jnp.linalg.norm(b - w))
            / jnp.linalg.norm(b - w)))
            for a, b, w in zip(*map(jax.tree.leaves,
                                    (stepped, want, weights))))

    assert worst(OPT) < 1e-5
    assert worst({**OPT, "learning_rate": 1.5e-4}) > 0.4
    assert worst({**OPT, "weight_decay": 0.0}) > 1e-3
    # What it cannot see: with the moments corrected the first step is -lr *
    # g / (|g| + eps), so the gradient's size hardly moves it.  The gradient
    # is the other comparison's.
    assert worst(OPT, jax.tree.map(lambda g: 100.0 * g, grads)) < 1e-3



# ------------------------------------------------------- the loss's parts

def test_exit_distribution_sums_to_one_and_one_step_is_the_plain_loss(
        weights, sample):
    p = llama.exit_distribution(CFG, weights, sample[0])
    assert p.shape == (T, 2, 64) and float(jnp.min(p)) > 0.0
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, rtol=1e-6)
    # T = 1: every token leaves at the one step, the entropy is 0, and the
    # loss is the mean NLL of a model with no gate.
    one = dataclasses.replace(CFG, ut_steps=1)
    plain = dataclasses.replace(one, exit_gate=False, exit_entropy_coef=0.0)
    bare = {k: v for k, v in weights.items() if not k.startswith("gate_")}
    for chunk in (0, 32):
        gated, grads = jax.value_and_grad(llama.make_loss_fn(
            one, loss_chunk=chunk))(weights, sample)
        np.testing.assert_allclose(
            gated, llama.make_loss_fn(plain, loss_chunk=chunk)(bare, sample),
            rtol=1e-6)
        assert float(jnp.max(jnp.abs(grads["gate_w"]))) == 0.0
        assert float(grads["gate_b"][0]) == 0.0


def test_weighted_chunked_head_equals_the_dense_weighted_loss():
    """`_chunked_nll` with weights: the value, and the gradients of the head,
    of the states and of the weights (the tokens' NLL), against the dense head
    under plain autodiff; scaled by a cotangent that is not 1."""
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    head = 0.1 * jax.random.normal(keys[0], (32, 96))
    h = jax.random.normal(keys[1], (6, 64, 32))
    targets = jax.random.randint(keys[2], (6, 64), 0, 96)
    weights = jax.random.uniform(keys[3], (6, 64)) / 100.0

    def value(chunk):
        return jax.jit(jax.value_and_grad(
            lambda hd, x, w: 3.0 * llama._nll_from_hidden(hd, x, targets,
                                                          chunk, w),
            argnums=(0, 1, 2)))(head, h, weights)

    (dense, g_dense), (chunked, g_chunked) = value(0), value(16)
    np.testing.assert_allclose(chunked, dense, rtol=1e-6)
    for a, b in zip(g_chunked, g_dense):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-6 * float(jnp.max(jnp.abs(b))))
    logp = jax.nn.log_softmax(h @ head, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(g_chunked[2], 3.0 * nll, rtol=1e-5)


# The logits' absolute sum, and the loss and the gradient norm with the dense
# head and the chunked one, of `llama.tiny()` at two depths at the parent
# commit of the PR that brought the loop (float32 on the CPU, the seeds
# below): a model with one step, no sandwich norm and no gate takes the path
# it took, bit for bit.
# Since PR 41 the rotation meets a pair's partner through a product with a
# 0/1/-1 matrix and no longer through slices at a stride of two
# (`llama._rotate_pairs`): the same products, bit-equal unjitted, summed in
# another order where the CPU compiler contracts them, so the last bits moved
# once (the loss by at most 1 ulp, the sums by 2); pinned again at that PR.
PINNED = {2: ("0x1.93d0940000000p+14", "0x1.79a7220000000p+2",
              "0x1.7676460000000p+3", "0x1.79a71c0000000p+2",
              "0x1.7676460000000p+3"),
          6: ("0x1.9344540000000p+14", "0x1.74ccda0000000p+2",
              "0x1.7d995a0000000p+3", "0x1.74ccdc0000000p+2",
              "0x1.7d995a0000000p+3")}


@pytest.mark.parametrize("depth", sorted(PINNED))
@pytest.mark.usefixtures("full_optimisation")
def test_one_step_is_bit_equal_to_before(depth):
    """Depth 2 is inlined, depth 6 scanned; `remat="dots"`."""
    cfg = dataclasses.replace(llama.tiny(), n_layers=depth)
    assert cfg.ut_steps == 1 and not cfg.sandwich_norm and not cfg.exit_gate
    p = llama.init(jax.random.PRNGKey(7), cfg)
    assert sorted(p) == ["embed", "head", "layers", "norm"]
    batch = tuple(jax.random.randint(jax.random.PRNGKey(s), (2, 64), 0, cfg.vocab)
                  for s in (8, 9))
    logits = jax.jit(lambda p, t: llama.apply(cfg, p, t))(p, batch[0])
    found = [float(jnp.sum(jnp.abs(logits))).hex()]
    for chunk in (0, 32):
        loss, grads = jax.jit(jax.value_and_grad(llama.make_loss_fn(
            cfg, attn="full", remat="dots", loss_chunk=chunk)))(p, batch)
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        found += [float(loss).hex(), float(norm).hex()]
    assert tuple(found) == PINNED[depth]


def test_looped_on_a_mesh(weights, sample):
    """Under GSPMD on dp x tp the looped step gives one device's loss and
    gradients: the post-norms run over a row-sharded product's sum."""
    alone = jax.jit(jax.value_and_grad(llama.make_loss_fn(
        CFG, loss_chunk=32)))(weights, sample)
    mesh = make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    sharded = llama.shard_params(weights, mesh, CFG)
    loss, grads = jax.jit(jax.value_and_grad(llama.make_loss_fn(
        CFG, mesh, loss_chunk=32)))(sharded, sample)
    np.testing.assert_allclose(loss, alone[0], rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(alone[1])):
        np.testing.assert_allclose(a, b, rtol=1e-3,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))))


# ------------------------------------------------- the benchmark's readers

def test_the_readers_read_the_join(monkeypatch):
    """`benchmark/layers/` (tier-1 does not collect `benchmark/tests/`): the
    cell's three readers and the accepted `head_loss_ms` and `optimizer_ms`
    on the runner's join, `None` where it left nothing."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "benchmark"))
    read = {name: _benchmark_module("layers", name).read for name in (
        "ut_stack_ms", "ut_stack_roofline", "ut_exit_ms", "head_loss_ms",
        "optimizer_ms")}
    flops = _benchmark_module("flops", "ouro-2.6b")
    cfg = {"hidden_size": 2048, "intermediate_size": 5632, "head_dim": 128,
           "num_attention_heads": 16, "num_key_value_heads": 16,
           "num_hidden_layers": 8, "total_ut_steps": 4, "vocab_size": 49152}
    obs = {"counters": {}, "peaks": None, "cfg": cfg, "flops": flops,
           "traffic": {"batch": 2, "seq_len": 4096}}
    assert all(r(obs) is None for r in read.values())
    obs["counters"]["scope_ms"] = {"embed": 2.0}
    assert all(r(obs) is None for r in read.values())
    obs["counters"]["scope_ms"] = {"attn": 300.0, "ffn": 600.0,
                                   "final_norm": 54.43, "head_loss": 200.0,
                                   "exit_gate": 1.5, "optimizer": 20.0}
    obs["peaks"] = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert read["ut_stack_ms"](obs) == pytest.approx(954.43)
    assert read["head_loss_ms"](obs) == 200.0
    assert read["optimizer_ms"](obs) == 20.0
    assert read["ut_exit_ms"](obs) == 1.5
    # 94.01 TFLOP at 197 TFLOP/s are 477.2 ms: half of the 954.43.
    assert read["ut_stack_roofline"](obs) == pytest.approx(50.0, abs=0.01)
