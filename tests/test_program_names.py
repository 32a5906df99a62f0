"""Names in the device program (docs/observability.md): the parts of a step
run under ``jax.named_scope`` and the flash kernels have a ``name``, so the
``op_name`` of every instruction of the compiled program says which part it
belongs to, and ``transpose(...)`` round it says backward.  The names are
metadata: these tests read them off the lowered step programs."""

import dataclasses
import re

import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.engine import AllReduceSGDEngine
from torchmpi_tpu.models import llama, resnet
from torchmpi_tpu.parallel import mesh as pmesh
from torchmpi_tpu.runtime import config


def _op_names(lowered):
    """What the lowering gives every operation for its ``op_name``."""
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def _carried(names, scope, inside=""):
    """Whether some operation runs under ``scope`` (as a path component,
    bare or inside ``jvp(...)``, ``vmap(...)``, ``transpose(...)``), and
    under ``inside`` too where that is given.  A ``/`` of ``inside`` also
    matches the ``)/`` that closes a ``jvp(scope)``: a forward operation of
    an inlined layer reads ``jvp(attn)/flash_fwd``, of a scanned one
    ``jvp()/while/body/closed_call/attn/flash_fwd``."""
    part = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    within = re.compile(re.escape(inside).replace("/", r"\)?/"))
    return any(part.search(n) and within.search(n) for n in names)


MOE = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine")


def _olmoe_tiny():
    """Dropless sorted dispatch and QK-norm, at `moe_tiny`'s sizes."""
    return dataclasses.replace(llama.moe_tiny(), n_kv_heads=4,
                               capacity_factor=None, moe_renormalize=False,
                               moe_z_coef=1e-3, qk_norm=True)


@pytest.mark.parametrize("depth", [2, llama._INLINE_MAX_LAYERS + 1],
                         ids=["inlined", "scanned"])
@pytest.mark.parametrize("make_cfg,ffn,absent", [
    (llama.moe_tiny, MOE, ("ffn", "attn.qk_norm")),
    (llama.tiny, ("ffn",), MOE + ("attn.qk_norm",)),
    (_olmoe_tiny, MOE + ("attn.qk_norm",), ("ffn",))],
    ids=["mixtral", "dense", "olmoe"])
def test_llama_train_step_carries_scope_and_kernel_names(make_cfg, ffn,
                                                         absent, depth):
    """The names are the same whichever form `llama.apply` gives the layer
    loop (inlined to `_INLINE_MAX_LAYERS` layers, scanned beyond)."""
    cfg = dataclasses.replace(make_cfg(), n_layers=depth)
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = llama.make_train_step(cfg, mesh, attn="flash", remat="dots",
                                 loss_chunk=32)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    lowered = step.lower(params, None, tokens, tokens)
    names = _op_names(lowered)
    # (inlined, the layers' forward operations are the step's own; scanned,
    # the body is a function of its own and its names start at the scope)
    inlined = any(n.startswith("jit(step)/jvp(attn)/") for n in names)
    assert inlined == (depth <= llama._INLINE_MAX_LAYERS)
    # An inlined layer's checkpoint stands behind an optimization barrier,
    # or the compiler merges the recomputation with the forward pass; a
    # scanned layer's needs none (`llama._wrap_remat`).
    assert ("optimization_barrier" in lowered.as_text()) == inlined
    for scope in ("embed", "attn", "head_loss", "optimizer") + ffn:
        assert _carried(names, scope), scope
    for scope in absent:
        assert not _carried(names, scope), scope
    # The two kernels, under the attention's scope.  The backward one runs
    # only in the checkpointed layer's backward pass; the forward one only
    # in the forward pass, not again in the layer's recomputation, because
    # remat="dots" keeps the kernel's o and lse.
    for kernel in ("flash_fwd", "flash_bwd"):
        assert _carried(names, kernel, inside="attn/" + kernel), kernel
    assert not _carried(names, "flash_bwd_dq")
    assert not _carried(names, "flash_bwd_dkv")
    assert _carried(names, "flash_bwd", inside="checkpoint/attn/")
    assert not _carried(names, "flash_bwd", inside="rematted_computation")
    assert not _carried(names, "flash_fwd", inside="rematted_computation")
    assert _carried(names, "attn", inside="rematted_computation")
    for scope in ("embed", "head_loss"):
        assert _carried(names, scope, inside="transpose(jvp(" + scope), scope
    assert not _carried(names, "optimizer", inside="transpose(")
    if "attn.qk_norm" in ffn:
        # QK-norm sits inside the attention's scope; the sorted dispatch's
        # grouped matmuls (megablox's `gmm`, and `tgmm` for the weights'
        # gradients) are under moe.experts: forward and backward, and none in
        # the layer's recomputation, where the SwiGLU between them still is:
        # remat="dots" keeps the gate and up products by their names
        # (`llama.GROUPED_DOT_NAMES`), and nothing reads the down product's.
        assert _carried(names, "attn.qk_norm", inside="attn/attn.qk_norm")
        for kernel in ("gmm", "tgmm"):
            assert _carried(names, kernel,
                            inside="checkpoint/moe.experts/jit("), kernel
            assert not _carried(names, kernel, inside="rematted_computation")
        assert _carried([n for n in names if "checkpoint" not in n], "gmm",
                        inside="moe.experts/jit(")
        assert _carried(names, "moe.experts",
                        inside="rematted_computation/moe.experts")


def _looped_tiny():
    """One stack run three times, sandwich norms, an exit gate: at `tiny`'s
    sizes."""
    return dataclasses.replace(llama.tiny(), n_kv_heads=4, ut_steps=3,
                               sandwich_norm=True, exit_gate=True,
                               exit_entropy_coef=0.1)


@pytest.mark.parametrize("depth", [2, llama._INLINE_MAX_LAYERS + 1],
                         ids=["inlined", "scanned"])
def test_looped_train_step_carries_scope_names(depth):
    """A looped model's step: the per-step final norm and the exit gate have
    scopes of their own, forward and backward; the stack's names are the
    dense model's, and neither a step under `"full"` nor one under `"dots"`
    runs its forward kernel again, while `"full"` recomputes the rest."""
    cfg = dataclasses.replace(_looped_tiny(), n_layers=depth)
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = llama.make_train_step(cfg, mesh, attn="flash",
                                 remat=("full", "full", "dots"), loss_chunk=32)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    names = _op_names(step.lower(params, None, tokens, tokens))
    for scope in ("embed", "attn", "ffn", "final_norm", "exit_gate",
                  "head_loss", "optimizer"):
        assert _carried(names, scope), scope
    for scope in MOE + ("attn.qk_norm",):
        assert not _carried(names, scope), scope
    for scope in ("final_norm", "exit_gate", "head_loss"):
        assert _carried(names, scope, inside="transpose(jvp(" + scope), scope
    assert _carried(names, "ffn", inside="rematted_computation/ffn")
    assert not _carried(names, "optimizer", inside="transpose(")
    # the gate's product over all steps' states is the gate's, not the head's
    assert _carried(names, "exit_gate", inside="exit_gate)/tbld,d->tbl")
    assert not _carried(names, "head_loss", inside="tbld,d->tbl")
    assert _carried(names, "flash_fwd", inside="attn/flash_fwd")
    assert not _carried(names, "flash_fwd", inside="rematted_computation")
    assert _carried(names, "attn", inside="rematted_computation")
    assert _carried(names, "flash_bwd", inside="checkpoint/attn/")


def _two_branch_tiny():
    """An attention branch and a state-space branch side by side, at toy
    widths: the published layer of `llama.falcon_h1_34b`."""
    published = llama.falcon_h1_34b()
    return dataclasses.replace(
        published, vocab=128, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=96, max_seq=256, ssm_heads=4, ssm_head_dim=8,
        ssm_state=16, ssm_chunk=16, layer_kinds=published.layer_kinds[:2])


@pytest.mark.parametrize("remat", ["dots", "full"])
def test_two_branch_train_step_carries_scope_names(remat):
    """`ssm` stands BESIDE `attn`; in it `ssm.conv`, `ssd` (the scan alone)
    and `ssm.norm`; the scan runs forward once and backward once under either
    policy (its output and chunk-entry states are kept by name), its one
    loop over the chunks with it, the loop's body under `ssd` each way, while
    the convolution and the norm round it are formed again."""
    cfg = _two_branch_tiny()
    mesh = pmesh.make_mesh({"dp": 1}, devices=jax.devices()[:1])
    step = llama.make_train_step(cfg, mesh, attn="flash", remat=remat,
                                 loss_chunk=32)
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    names = _op_names(step.lower(params, None, tokens, tokens))
    for scope in ("embed", "attn", "ssm", "ssm.conv", "ssd", "ssm.norm",
                  "ffn", "final_norm", "head_loss", "optimizer"):
        assert _carried(names, scope), scope
    for scope in MOE + ("kda", "mla", "swa"):
        assert not _carried(names, scope), scope
    for inner in ("ssm.conv", "ssd", "ssm.norm"):
        assert _carried(names, inner, inside="ssm/" + inner), inner
        assert not _carried(names, inner, inside="attn/"), inner
    assert not _carried(names, "ssm", inside="attn/ssm")
    # The scan's products: forward in the forward pass, backward in the
    # checkpointed layer's backward pass, none in the recomputation.
    products = [n for n in names if _carried([n], "ssd")
                and n.endswith("dot_general")]
    assert [n for n in products if "jvp(ssm)/ssd/" in n]
    assert [n for n in products if "checkpoint/ssm/ssd/" in n]
    assert not [n for n in products if "rematted_computation" in n]
    # The loop over the chunks.  Forward it stands in a `custom_vjp` rule
    # under `jax.checkpoint` and keeps no name of its callers': the body
    # names `ssd` itself, which is what a join by scope finds.
    assert {"ssd/exp", "ssd/mul", "ssd/add"} <= names
    assert [n for n in names if "checkpoint/ssm/ssd/while/body/" in n]
    assert not [n for n in names if "rematted_computation" in n
                and "while" in n and _carried([n], "ssd")]
    for formed_again in ("ssm.conv", "ssm.norm"):
        assert _carried(names, formed_again, inside="rematted_computation")
    assert _carried(names, "flash_fwd", inside="attn/flash_fwd")
    assert not _carried(names, "flash_fwd", inside="rematted_computation")


RESNET = ("stem", "conv", "bn", "residual", "pool", "fc_loss")


@pytest.mark.parametrize("rings", [False, True], ids=["gspmd", "rings"])
def test_resnet_engine_step_carries_scope_names(world, rings):
    config.set("use_pallas_collectives", rings)
    cfg = resnet.config(depth=18, n_classes=10, width_multiplier=0.25)
    params = jax.eval_shape(
        lambda: resnet.init(jax.random.PRNGKey(0), cfg)[0])
    engine = AllReduceSGDEngine(resnet.make_loss_fn(cfg), lr=0.1, comm=world)
    step = engine._build_compiled_step(world)
    names = _op_names(step.lower(
        params, None, jax.ShapeDtypeStruct((16, 32, 32, 3), jnp.float32),
        jax.ShapeDtypeStruct((16,), jnp.int32)))
    for scope in RESNET:
        assert _carried(names, scope), scope
        assert _carried(names, scope, inside="transpose(jvp(" + scope), scope
    assert _carried(names, "optimizer")
    assert not _carried(names, "optimizer", inside="transpose(")
    # The explicit rings are a part of the program and have a name; GSPMD's
    # all-reduces are put in by the partitioner, after this text, and take
    # the name of the backward operation whose result they sum.
    assert _carried(names, "grad_sync") == rings
