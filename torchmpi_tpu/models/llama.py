"""Llama-family decoder-only transformer (RMSNorm, RoPE, SwiGLU, GQA) and its
training path on a dp x tp x sp (x ep) mesh: ``Config`` and the presets,
:func:`init`, :func:`param_specs` and :func:`shard_params`, the blocks,
:func:`apply`, :func:`make_loss_fn`, :func:`make_train_step`.  Decoding is
``llama_decode``, the pipeline schedules ``llama_pipeline``; both import this
module and it imports neither.

* Layer parameters are **stacked** (leading ``n_layers`` axis).  A shallow
  stack is inlined, a deeper one a ``lax.scan`` over layers (one compiled
  block; :func:`apply`, ``_INLINE_MAX_LAYERS``).  A looped model
  (``Config(ut_steps=T)``, :func:`ouro_2_6b`) runs the one stack T times with
  shared weights, a head and an exit gate at every step.  A stack whose
  layers differ in kind (``Config(layer_kinds=...)``: KDA linear-attention
  and latent-attention layers, :func:`kimi_linear_48b_a3b`; window and full
  softmax layers with head counts, rotations and a key window of their own,
  :func:`laguna_s_2_1`; an attention branch and a Mamba-2 state-space
  branch side by side on one normed input in every layer, with constants on
  the forward pass, :func:`falcon_h1_34b`) is a sequence of homogeneous runs
  (:func:`layer_runs`), ``params["layers"]`` a tuple of such stacks, each run
  inlined or scanned by its own length.  A multi-token-prediction module
  (``Config(mtp_layers=1)``, :func:`glm_4_7_flash`) is one more layer after
  the stack with a loss of its own through the same embedding and head
  (:func:`_mtp_input`).
* :func:`param_specs` returns the PartitionSpec pytree for Megatron-style
  tensor parallelism (qkv/gate/up column-sharded, o/down row-sharded): under
  pjit GSPMD inserts the one-psum-per-block collectives the hand-written
  shard_map forms in parallel/tp.py produce.  Activations carry
  ``with_sharding_constraint`` annotations: batch on ``dp``, sequence on
  ``sp``.
* Attention is pluggable: ``attn="full"`` (GSPMD partitions heads over tp),
  ``attn="flash"`` (Pallas kernels, ops/flash_attention.py), or
  ``attn="ring"`` (shard_map ring attention over ``sp`` for long contexts,
  parallel/sequence.py).
* Mixture-of-experts FFN (``Config(n_experts=E, expert_top_k=k)``,
  :func:`mixtral_8x7b`): GShard dispatch/combine einsums with expert weights
  sharded over ``ep`` (:func:`_moe_ffn`) and a Switch load-balance aux loss
  through the layer loop.  A dropless configuration sorts its routed units
  for a grouped matmul (:func:`_moe_ffn_sorted`), with sigmoid scoring, a
  selection bias, a shared expert and a chip's share of the experts
  (``Config(experts_held=...)``, :func:`_held_experts`) where the
  configuration has them; on an ``ep`` axis its tokens are sharded over the
  axis too (:func:`batch_spec`) and meet the rank of their experts there
  (:func:`_moe_ffn_ep`, :func:`mellum2_12b_a2_5b`): on an axis no wider than
  the choices a token the tokens are gathered over it and the partial sums
  come home, past that the sorted units go out and come back by an exchange.
* What each consumer of a configuration (decode, prefill, generation, the
  pipeline schedules, the rings, :func:`expert_unit_counts`) cannot run yet is
  one table, ``_LACKS``, read by one :func:`_refuse`.

Compute dtype is configurable (bfloat16 for TPU, float32 for CPU tests);
norms, softmax, and the loss run in f32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import AXIS_DP, AXIS_EP, AXIS_SP, AXIS_TP
from ..parallel.moe import exchange as _exchange, pass_plan as _pass_plan, \
    route_topk as _route_topk
from ._common import dense_init as _dense, mesh_spec as _mesh_spec, \
    num_params, shard_by_specs, stack_dense

Params = Dict[str, Any]
# A remat policy's name, or one for each recurrent step of a looped model.
Remat = Union[str, Sequence[str]]


@dataclasses.dataclass(frozen=True)
class Config:
    vocab: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 4            # GQA: kv heads <= heads
    d_ff: int = 1408
    max_seq: int = 2048
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    # Mixture-of-experts FFN (0 = dense SwiGLU).  With n_experts > 0 every
    # layer's FFN becomes `n_experts` SwiGLU experts routed top-k
    # (Mixtral-style), expert weights sharded over the `ep` mesh axis.
    n_experts: int = 0
    expert_top_k: int = 2
    # Per-expert slots = cf * k * G / E; None = dropless: no capacity and no
    # routing group, every routed unit reaches its expert (_moe_ffn_sorted).
    capacity_factor: Optional[float] = 1.25
    moe_aux_coef: float = 0.01     # load-balance aux-loss weight
    moe_group_size: int = 512      # tokens per routing group (GShard groups)
    moe_renormalize: bool = True   # top-k>1 weights renormalised over the k
    moe_z_coef: float = 0.0        # router z-loss weight (ST-MoE eq. 5)
    # RMSNorm over the whole q and k projections before the heads are split
    # (OLMoE): two more leaves a layer, ``q_norm`` and ``k_norm``.
    qk_norm: bool = False
    # A looped ("universal transformer") model (Ouro, :func:`ouro_2_6b`):
    # the one stack of layers runs ``ut_steps`` times with the same weights,
    # the final norm after every pass, so the normed state is what the next
    # pass reads, and the head reads every pass's state.
    ut_steps: int = 1
    # Sandwich normalisation: an RMSNorm on each branch's output too, before
    # the residual add; two more leaves a layer, ``attn_post_norm`` and
    # ``mlp_post_norm``.
    sandwich_norm: bool = False
    # An exit gate at every recurrent step, ``sigmoid(h @ w + b)``, one number
    # a token: a Linear(d_model -> 1) with bias, shared by the steps, the
    # leaves ``gate_w`` (d_model,) and ``gate_b`` (1,); and the expected-exit
    # loss of :func:`make_loss_fn`, whose entropy term has the weight
    # ``exit_entropy_coef``.
    exit_gate: bool = False
    exit_entropy_coef: float = 0.0
    # A stack that is not homogeneous (Kimi Linear,
    # :func:`kimi_linear_48b_a3b`): for every layer its mixer (``"attn"``, the
    # softmax attention over ``n_heads`` heads of ``head_dim``; ``"kda"``,
    # :func:`_kda_block`; ``"mla"``, :func:`_mla_block`; ``"swa"`` and
    # ``"attn+ssm"``, below) and its FFN
    # (``"dense"`` or ``"moe"``); :func:`layer_kinds` builds it from a
    # configuration file's lists.  None: every layer ``"attn"`` with the FFN
    # that ``n_experts`` says, one run, the parameter tree it always had.
    layer_kinds: Optional[Tuple[Tuple[str, str], ...]] = None
    # KDA layers: heads of ``kda_head_dim`` for q, k and v alike, a causal
    # depthwise convolution of ``kda_conv`` taps on each, and two low-rank
    # pairs (d_model -> kda_head_dim -> heads * kda_head_dim) for the decay
    # and the output gate.
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv: int = 4
    # MLA layers (``n_heads`` heads): keys of ``qk_nope_head_dim`` from the
    # latent of ``kv_lora_rank`` beside ``qk_rope_head_dim`` that all heads
    # share, values of ``v_head_dim``.  ``q_lora_rank`` 0: q projected whole
    # (Kimi Linear); else through a latent of that width with a norm of its
    # own (leaves ``wq_a``, ``q_norm``, ``wq_b`` in ``wq``'s place).
    # ``mla_rope``: the shared key part, once, and each head's last
    # ``qk_rope_head_dim`` query channels go through :func:`rope` at
    # ``rope_theta``; off, nothing is rotated (Kimi Linear's NoPE layers).
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    q_lora_rank: int = 0
    mla_rope: bool = False
    # A multi-token-prediction module (DeepSeek-V3's report, section 2.2, at
    # depth 1; :func:`_mtp_input`): ``mtp_layers`` 0 or 1 layers of the
    # stack's last kind after the stack, under ``params["mtp"]``, and
    # ``mtp_coef`` times their next-but-one-token loss added to the loss.
    mtp_layers: int = 0
    mtp_coef: float = 0.3
    # A "dense" layer's SwiGLU width in a stack whose experts are ``d_ff``.
    dense_d_ff: int = 0
    # Experts every token meets, beside the routed ones (scope ``moe.shared``):
    # one SwiGLU of width ``n_shared_experts * d_ff``.
    n_shared_experts: int = 0
    # The router (:func:`_route_tokens`): ``"softmax"`` over the experts, or
    # ``"sigmoid"`` scores; ``router_bias``: a selection bias a expert, added
    # for the top-k choice alone (leaf ``router_bias``, never stepped);
    # ``routed_scale`` multiplies the combine weights.
    router_act: str = "softmax"
    router_bias: bool = False
    routed_scale: float = 1.0
    # A chip's share of the experts: ``(first, count)``, a contiguous range of
    # expert ids whose weights are held here.  The router stays ``n_experts``
    # wide and the weights are normalised over all k choices; the layer
    # returns the held experts' part (:func:`_held_experts`).  None: all.
    experts_held: Optional[Tuple[int, int]] = None
    # The softmax layers' head width; 0: ``d_model // n_heads``, derived and
    # written here (so ``dataclasses.replace`` with another ``d_model`` or
    # ``n_heads`` passes ``head_dim=0`` too).  A width of its own makes q
    # ``n_heads * head_dim`` wide on any state (Laguna-S-2.1: 48 heads of 128
    # on 3072).
    head_dim: int = 0
    # Window layers (``"swa"`` in ``layer_kinds``): softmax attention over
    # ``swa_heads`` heads (0: ``n_heads``) of ``head_dim`` and the same
    # ``n_kv_heads``, where row i sees keys ``i - swa_window < j <= i`` (its
    # own among the ``swa_window``), every channel rotated at
    # ``swa_rope_theta``, nothing scaled.
    swa_heads: int = 0
    swa_window: int = 0
    swa_rope_theta: float = 10000.0
    # The full (``"attn"``) layers' rotation: the first ``rope_fraction`` of a
    # head's channels at ``rope_theta``, the others pass; with ``rope_yarn``
    # ``(factor, original length, beta_fast, beta_slow, attention factor)``
    # the frequencies are YaRN's (:func:`yarn_inv_freq`) and cos and sin carry
    # the attention factor.  1.0 and None: :func:`rope` as it always was.
    rope_fraction: float = 1.0
    rope_yarn: Optional[Tuple[float, int, float, float, float]] = None
    # A gate a head on the attention output, ``sigmoid(x @ wg)`` of the normed
    # layer input, before the output projection (leaf ``wg`` (d_model, heads)
    # of every softmax layer; scope ``attn.gate``).
    attn_gate: bool = False
    # Two mixers side by side in a layer (``"attn+ssm"`` in ``layer_kinds``;
    # Falcon-H1, :func:`falcon_h1_34b`): the softmax attention of an
    # ``"attn"`` layer and a Mamba-2 state-space branch
    # (:func:`_ssm_block`) read ONE normed input and their outputs are summed
    # into the residual.  The branch: ``ssm_heads`` heads of ``ssm_head_dim``
    # channels, ``ssm_groups`` groups of heads sharing their B and C, a state
    # of ``ssm_head_dim x ssm_state`` a head, a causal depthwise convolution
    # of ``ssm_conv`` taps with bias, the scan in chunks of ``ssm_chunk``.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # Constants of the forward pass (they scale gradients too, so they are
    # not folded into seeded weights), each 1 where a model has none: on the
    # embedding's rows and on the logits; on the attention branch's input,
    # on its keys before the rotation and on its output; on the state-space
    # branch's input, on the five sections of its projection (gate, x, B, C,
    # dt) and on its output; on the dense SwiGLU's gate (inside the SiLU)
    # and on its output.
    embed_multiplier: float = 1.0
    head_multiplier: float = 1.0
    attn_in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attn_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, float, float, float, float] = (1.0,) * 5
    ssm_out_multiplier: float = 1.0
    ffn_multipliers: Tuple[float, float] = (1.0, 1.0)

    def __post_init__(self):
        # ``head_dim`` is the softmax ("attn", "swa") layers'; a stack without
        # one (GLM-4.7-Flash: 20 latent heads on 2048) need not divide.
        softmax = self.layer_kinds is None or any(
            mixer in ("attn", "swa", "attn+ssm")
            for mixer, _ in self.layer_kinds)
        assert (self.head_dim or not softmax
                or self.d_model % self.n_heads == 0)
        if not self.head_dim:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        assert self.n_heads % self.n_kv_heads == 0
        assert self.swa_heads % self.n_kv_heads == 0
        # Channels rotate in pairs.
        assert 0 < self.rope_fraction <= 1
        assert not softmax or self.head_dim * self.rope_fraction % 2 == 0
        assert self.ut_steps >= 1
        if self.n_experts:
            assert 1 <= self.expert_top_k <= self.n_experts
            assert self.capacity_factor is None or self.capacity_factor > 0
        assert self.router_act in ("softmax", "sigmoid")
        if (self.router_act == "sigmoid" or self.router_bias
                or self.n_shared_experts or self.experts_held):
            # Written for the sorted, dropless expert layer alone.
            assert self.n_experts and self.capacity_factor is None
            assert self.moe_aux_coef == 0 and self.moe_z_coef == 0
        if self.experts_held:
            first, count = self.experts_held
            assert 0 <= first and count >= 1
            assert first + count <= self.n_experts
        if self.layer_kinds is not None:
            assert len(self.layer_kinds) == self.n_layers
            assert self.ut_steps == 1 and not self.sandwich_norm
            assert not self.qk_norm
            for mixer, ffn in self.layer_kinds:
                assert mixer in ("attn", "swa", "kda", "mla",
                                 "attn+ssm"), mixer
                assert ffn in ("dense", "moe"), ffn
                assert ffn == "dense" or self.n_experts
                assert mixer != "swa" or self.swa_window >= 1
                assert mixer != "attn+ssm" or (
                    self.ssm_heads and self.ssm_head_dim and self.ssm_state
                    and self.ssm_heads % self.ssm_groups == 0)
        if self.attn_gate:
            # The gate is a leaf of a run (:func:`_init_run`).
            assert self.layer_kinds is not None
        two = self.layer_kinds is not None and any(
            mixer == "attn+ssm" for mixer, _ in self.layer_kinds)
        # The branches' constants are read where the two branches meet.
        assert two or (self.attn_in_multiplier == self.key_multiplier
                       == self.attn_out_multiplier == self.ssm_in_multiplier
                       == self.ssm_out_multiplier == 1
                       and set(self.ssm_multipliers) == {1})
        assert len(self.ssm_multipliers) == 5
        # The FFN's are the dense SwiGLU's.
        assert not self.n_experts or set(self.ffn_multipliers) == {1}
        if self.q_lora_rank or self.mla_rope:
            assert self.layer_kinds is not None and self.kv_lora_rank
        assert self.mtp_layers in (0, 1)
        if self.mtp_layers:
            # The module is a layer of a stack of runs; its router has no
            # auxiliary term to add to the stack's mean.
            assert self.layer_kinds is not None
            assert self.moe_aux_coef == 0 and self.moe_z_coef == 0


def llama3_8b() -> Config:
    """Llama-3-8B geometry."""
    return Config(vocab=128256, d_model=4096, n_layers=32, n_heads=32,
                  n_kv_heads=8, d_ff=14336, max_seq=8192, rope_theta=500000.0)


def mixtral_8x7b() -> Config:
    """Mixtral-8x7B geometry: 8 SwiGLU experts per layer, top-2 routed."""
    return Config(vocab=32000, d_model=4096, n_layers=32, n_heads=32,
                  n_kv_heads=8, d_ff=14336, max_seq=8192, rope_theta=1e6,
                  n_experts=8, expert_top_k=2)


def olmoe_1b_7b() -> Config:
    """OLMoE-1B-7B geometry (arXiv:2409.02060): 64 SwiGLU experts of width
    1024 a layer, 8 a token, dropless, combine weights not renormalised,
    QK-norm, router z-loss."""
    return Config(vocab=50304, d_model=2048, n_layers=16, n_heads=16,
                  n_kv_heads=16, d_ff=1024, max_seq=4096, rope_theta=1e4,
                  n_experts=64, expert_top_k=8, capacity_factor=None,
                  moe_renormalize=False, moe_z_coef=1e-3, qk_norm=True)


def ouro_2_6b() -> Config:
    """Ouro-2.6B geometry ("Scaling Latent Reasoning via Looped Language
    Models", 2025): 48 dense SwiGLU layers run four times with shared
    weights, sandwich norms, a head and an exit gate at every recurrent step,
    trained on the expected-exit loss with an entropy weight of 0.1."""
    return Config(vocab=49152, d_model=2048, n_layers=48, n_heads=16,
                  n_kv_heads=16, d_ff=5632, max_seq=65536, rope_theta=1e6,
                  norm_eps=1e-6, ut_steps=4, sandwich_norm=True,
                  exit_gate=True, exit_entropy_coef=0.1)


def layer_kinds(n_layers: int, kda_layers: Sequence[int],
                full_attn_layers: Sequence[int],
                first_k_dense_replace: int) -> Tuple[Tuple[str, str], ...]:
    """``Config.layer_kinds`` for the first ``n_layers`` layers of a model
    whose file lists its KDA and its latent-attention layers (1-based, as
    ``linear_attn_config`` does) and says how many leading layers are dense
    (every other one a mixture of experts)."""
    kda, mla = set(kda_layers), set(full_attn_layers)
    if kda & mla:
        raise ValueError(f"layers {sorted(kda & mla)} are listed twice")
    kinds = []
    for i in range(1, n_layers + 1):
        if i not in kda | mla:
            raise ValueError(f"layer {i} is in neither list")
        kinds.append(("kda" if i in kda else "mla",
                      "dense" if i <= first_k_dense_replace else "moe"))
    return tuple(kinds)


def kimi_linear_48b_a3b() -> Config:
    """Kimi-Linear-48B-A3B geometry ("Kimi Linear: An Expressive, Efficient
    Attention Architecture", Moonshot AI, 2025-10): 27 layers, KDA
    linear-attention layers three to one with latent attention without
    positions, a dense first layer, then 256 sigmoid-routed experts of width
    1024, 8 a token, beside a shared one; no positional encoding at all."""
    return Config(vocab=163840, d_model=2304, n_layers=27, n_heads=32,
                  n_kv_heads=32, d_ff=1024, dense_d_ff=9216, max_seq=1048576,
                  norm_eps=1e-5, n_experts=256, expert_top_k=8,
                  capacity_factor=None, moe_aux_coef=0.0,
                  moe_renormalize=True, n_shared_experts=1,
                  router_act="sigmoid", router_bias=True, routed_scale=2.446,
                  kda_heads=32, kda_head_dim=128, kda_conv=4,
                  kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128,
                  layer_kinds=layer_kinds(
                      27, [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                           21, 22, 23, 25, 26], [4, 8, 12, 16, 20, 24, 27], 1))


def glm_4_7_flash() -> Config:
    """GLM-4.7-Flash geometry (``zai-org/GLM-4.7-Flash``, ``glm4_moe_lite``):
    47 layers of rotary latent attention with a query latent of 768 and heads
    of 256 (192 + 64 rotated; values 256), a dense first layer of 10240, then
    64 sigmoid-routed experts of width 1536, 4 a token, scaled by 1.8, beside
    a shared one, and one multi-token-prediction layer after the stack."""
    return Config(vocab=154880, d_model=2048, n_layers=47, n_heads=20,
                  n_kv_heads=20, d_ff=1536, dense_d_ff=10240, max_seq=202752,
                  rope_theta=1e6, norm_eps=1e-5, n_experts=64, expert_top_k=4,
                  capacity_factor=None, moe_aux_coef=0.0,
                  moe_renormalize=True, n_shared_experts=1,
                  router_act="sigmoid", router_bias=True, routed_scale=1.8,
                  kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
                  v_head_dim=256, q_lora_rank=768, mla_rope=True,
                  layer_kinds=(("mla", "dense"),) + (("mla", "moe"),) * 46,
                  mtp_layers=1, mtp_coef=0.3)


def window_layer_kinds(layer_types: Sequence[str],
                       mlp_layer_types: Sequence[str]
                       ) -> Tuple[Tuple[str, str], ...]:
    """``Config.layer_kinds`` from a configuration file's two lists, one
    entry a layer each: ``"full_attention"`` or ``"sliding_attention"``, and
    ``"dense"`` or ``"sparse"``."""
    mixers = {"full_attention": "attn", "sliding_attention": "swa"}
    ffns = {"dense": "dense", "sparse": "moe"}
    if len(layer_types) != len(mlp_layer_types):
        raise ValueError(f"{len(layer_types)} layer types for "
                         f"{len(mlp_layer_types)} FFN types")
    for names, known in ((layer_types, mixers), (mlp_layer_types, ffns)):
        for name in names:
            if name not in known:
                raise ValueError(f"{name!r} is none of {sorted(known)}")
    return tuple((mixers[t], ffns[f])
                 for t, f in zip(layer_types, mlp_layer_types))


def laguna_s_2_1() -> Config:
    """Laguna-S-2.1 geometry (``poolside/Laguna-S-2.1``, ``laguna``): 48
    layers on a 3072-wide state, heads of 128 over 8 KV heads; every fourth
    layer from the first attends to all earlier keys with 48 heads, half of
    each rotated with YaRN-scaled frequencies at theta 500,000, the others to
    the last 512 with 72 heads rotated whole at theta 10,000; a sigmoid gate a
    head on the attention output; a dense first layer of 12,288, then 256
    sigmoid-routed experts of width 1024, 10 a token, their normalised
    weights scaled by 2.5, beside a shared one."""
    return Config(vocab=100352, d_model=3072, n_layers=48, n_heads=48,
                  n_kv_heads=8, head_dim=128, d_ff=1024, dense_d_ff=12288,
                  max_seq=1048576, rope_theta=500000.0, norm_eps=1e-6,
                  n_experts=256, expert_top_k=10, capacity_factor=None,
                  moe_aux_coef=0.0, moe_renormalize=True, n_shared_experts=1,
                  router_act="sigmoid", router_bias=False, routed_scale=2.5,
                  swa_heads=72, swa_window=512, swa_rope_theta=10000.0,
                  rope_fraction=0.5,
                  rope_yarn=(128.0, 8192, 32.0, 1.0, 1.4852030263919618),
                  attn_gate=True,
                  layer_kinds=window_layer_kinds(
                      ["full_attention", "sliding_attention",
                       "sliding_attention", "sliding_attention"] * 12,
                      ["dense"] + ["sparse"] * 47))


def mellum2_12b_a2_5b() -> Config:
    """Mellum2-12B-A2.5B geometry (``JetBrains/Mellum2-12B-A2.5B-Instruct``,
    ``mellum``): 28 layers on a 2304-wide state, 32 heads of 128 over 4 KV
    heads; three layers over the last 1,024 keys, rotated whole at theta
    500,000, then one over all earlier keys, rotated whole with YaRN's
    frequencies (factor 16 over 8,192) and attention factor, the full layer
    LAST in each period of four; in every layer 64 SwiGLU experts of width
    896, 8 a token, softmax over the 64, renormalised, dropless, no shared
    expert, no dense layer; untied embedding and head of 98,304 rows."""
    return Config(vocab=98304, d_model=2304, n_layers=28, n_heads=32,
                  n_kv_heads=4, head_dim=128, d_ff=896, max_seq=131072,
                  rope_theta=500000.0, norm_eps=1e-6, n_experts=64,
                  expert_top_k=8, capacity_factor=None, moe_aux_coef=0.0,
                  moe_renormalize=True, swa_window=1024,
                  swa_rope_theta=500000.0,
                  rope_yarn=(16.0, 8192, 32.0, 1.0, 1.2772588722239782),
                  layer_kinds=window_layer_kinds(
                      ["sliding_attention"] * 3 + ["full_attention"],
                      ["sparse"] * 4) * 7)


def falcon_h1_34b() -> Config:
    """Falcon-H1-34B geometry (``tiiuae/Falcon-H1-34B-Instruct``,
    ``falcon_h1``): 72 layers alike on a 5120-wide state, in each an
    attention branch (20 heads of 128 over 4 KV heads, rotated whole at theta
    1e11) and a Mamba-2 state-space branch (one projection to 9248, a
    convolution of 4 taps over 5120 channels, 32 heads of 128 in 2 groups, a
    state of 128 x 256 a head, chunks of 128, a gated norm a group) side by
    side on one normed input, then a dense SwiGLU of 21,504; the published
    constants on the embedding, the logits, every branch and the five
    sections of the projection; untied embedding and head of 261,120 rows."""
    return Config(vocab=261120, d_model=5120, n_layers=72, n_heads=20,
                  n_kv_heads=4, head_dim=128, d_ff=21504, max_seq=262144,
                  rope_theta=1e11, norm_eps=1e-5,
                  ssm_heads=32, ssm_head_dim=128, ssm_state=256, ssm_groups=2,
                  ssm_conv=4, ssm_chunk=128,
                  embed_multiplier=5.656854249492381,
                  head_multiplier=0.0078125, attn_in_multiplier=1.0,
                  key_multiplier=0.011048543456039804,
                  attn_out_multiplier=0.0375, ssm_in_multiplier=0.25,
                  ssm_multipliers=(0.3535533905932738, 0.25,
                                   0.1767766952966369, 0.5,
                                   0.3535533905932738),
                  ssm_out_multiplier=0.08838834764831845,
                  ffn_multipliers=(0.1767766952966369, 0.011160714285714284),
                  layer_kinds=(("attn+ssm", "dense"),) * 72)


def layer_runs(cfg: Config) -> Tuple[Tuple[str, str, int], ...]:
    """The stack as homogeneous runs, ``(mixer, ffn, length)`` each:
    consecutive layers of one kind.  A configuration without
    ``layer_kinds`` is one run."""
    if cfg.layer_kinds is None:
        return (("attn", "moe" if cfg.n_experts else "dense", cfg.n_layers),)
    runs = []
    for kinds in cfg.layer_kinds:
        if runs and runs[-1][:2] == kinds:
            runs[-1] = (*kinds, runs[-1][2] + 1)
        else:
            runs.append((*kinds, 1))
    return tuple(runs)


def softmax_heads(cfg: Config, mixer: str) -> int:
    """Query heads of a softmax layer of kind ``mixer``: a window layer's
    (``"swa"``) where the configuration gives them a count of their own."""
    return cfg.swa_heads if mixer == "swa" and cfg.swa_heads else cfg.n_heads


def tiny(vocab: int = 256, seq: int = 64) -> Config:
    """Test-scale config for the 8-device CPU mesh."""
    return Config(vocab=vocab, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                  d_ff=128, max_seq=seq)


def moe_tiny(vocab: int = 256, seq: int = 64, n_experts: int = 4,
             k: int = 2) -> Config:
    """Test-scale MoE config for the 8-device CPU mesh."""
    return Config(vocab=vocab, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                  d_ff=128, max_seq=seq, n_experts=n_experts, expert_top_k=k)


# ---------------------------------------------------------------------- init

def _init_run(key: jax.Array, cfg: Config, mixer: str, ffn: str, n: int,
              dtype) -> Params:
    """One run of a stack that is not homogeneous: ``n`` layers of one
    mixer and one FFN kind, every leaf led by ``n``.  An expert's weights
    are drawn from its id, so a share (``cfg.experts_held``) holds the very
    experts the whole layer would."""
    D, F = cfg.d_model, cfg.d_ff
    keys = iter(jax.random.split(key, 24))
    dense = lambda d_in, d_out: stack_dense(next(keys), n, d_in, d_out, dtype)
    ones = lambda *shape: jnp.ones((n, *shape), jnp.float32)
    normal = lambda shape, std: (jax.random.normal(
        next(keys), (n, *shape), jnp.float32) * std)
    lp = {"attn_norm": ones(D), "mlp_norm": ones(D)}
    if mixer == "kda":
        H, hd, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
        conv = lambda: normal((taps, H * hd), taps ** -0.5).astype(dtype)
        # The decay as Mamba-style layers seed it: A in [1, 16], and a bias
        # that puts softplus(dt_bias) log-uniform in [1e-3, 1e-1].
        dt = jnp.exp(jax.random.uniform(next(keys), (n, H * hd), jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        lp.update(
            wq=dense(D, H * hd), wk=dense(D, H * hd), wv=dense(D, H * hd),
            conv_q=conv(), conv_k=conv(), conv_v=conv(),
            f_down=dense(D, hd), f_up=dense(hd, H * hd),
            a_log=jnp.log(jax.random.uniform(next(keys), (n, H), jnp.float32,
                                             1.0, 16.0)),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            wb=dense(D, H), g_down=dense(D, hd), g_up=dense(hd, H * hd),
            g_bias=jnp.zeros((n, H * hd), dtype), o_norm=ones(hd),
            wo=dense(H * hd, D))
    elif mixer == "mla":
        H, r = cfg.n_heads, cfg.kv_lora_rank
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        if cfg.q_lora_rank:
            lp.update(wq_a=dense(D, cfg.q_lora_rank),
                      q_norm=ones(cfg.q_lora_rank),
                      wq_b=dense(cfg.q_lora_rank, H * qk))
        else:
            lp.update(wq=dense(D, H * qk))
        lp.update(
            wkv_a=dense(D, r + cfg.qk_rope_head_dim),
            kv_norm=ones(r),
            wkv_b=dense(r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            wo=dense(H * cfg.v_head_dim, D))
    else:
        hd, KV = cfg.head_dim, cfg.n_kv_heads
        H = softmax_heads(cfg, mixer)
        lp.update(wq=dense(D, H * hd), wk=dense(D, KV * hd),
                  wv=dense(D, KV * hd), wo=dense(H * hd, D))
        if cfg.attn_gate:
            lp.update(wg=dense(D, H))
        if mixer == "attn+ssm":
            # The state-space branch beside the attention: one projection to
            # [gate | x | B | C | dt], the convolution over [x | B | C] with
            # its bias, A, D and dt's bias a head (A uniform in [1, 16],
            # softplus(dt_bias) log-uniform in [1e-3, 1e-1], as the KDA
            # layers' decay above), the gated norm's weight, the way out.
            Hs, taps = cfg.ssm_heads, cfg.ssm_conv
            inner = Hs * cfg.ssm_head_dim
            conved = inner + 2 * cfg.ssm_groups * cfg.ssm_state
            dt = jnp.exp(jax.random.uniform(next(keys), (n, Hs), jnp.float32,
                                            np.log(1e-3), np.log(1e-1)))
            lp.update(
                ssm_in=dense(D, inner + conved + Hs),
                ssm_conv=normal((taps, conved), taps ** -0.5).astype(dtype),
                ssm_conv_bias=normal((conved,), 0.02).astype(dtype),
                ssm_a_log=jnp.log(jax.random.uniform(
                    next(keys), (n, Hs), jnp.float32, 1.0, 16.0)),
                ssm_dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                ssm_d=ones(Hs), ssm_norm=ones(inner),
                ssm_out=dense(inner, D))
    if ffn == "dense":
        W = cfg.dense_d_ff or F
        lp.update(w_gate=dense(D, W), w_up=dense(D, W), w_down=dense(W, D))
        return lp
    first, held = cfg.experts_held or (0, cfg.n_experts)

    def experts(d_in, d_out):
        key = next(keys)
        draw = lambda e: jax.random.normal(jax.random.fold_in(key, e),
                                           (n, d_in, d_out), jnp.float32)
        w = jax.vmap(draw, out_axes=1)(first + jnp.arange(held))
        return (w * np.sqrt(1.0 / d_in)).astype(dtype)

    lp.update(router=normal((D, cfg.n_experts), 0.02).astype(dtype),
              w_gate=experts(D, F), w_up=experts(D, F), w_down=experts(F, D))
    if cfg.router_bias:
        # Not zero, so that it changes choices: a twentieth of a sigmoid
        # score's range is several ranks among 256 experts.
        lp["router_bias"] = normal((cfg.n_experts,), 0.05)
    if cfg.n_shared_experts:
        S = cfg.n_shared_experts * F
        lp.update(shared_gate=dense(D, S), shared_up=dense(D, S),
                  shared_down=dense(S, D))
    return lp


def init(rng: jax.Array, cfg: Config, dtype=jnp.float32) -> Params:
    """Stacked-layer parameter pytree (leaves lead with n_layers).  With
    ``cfg.layer_kinds`` the ``"layers"`` entry is a tuple of such stacks, one
    for each run of :func:`layer_runs` (:func:`_init_run`)."""
    hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    # 9-way split exactly as v0.1: dense configs must produce identical
    # initial weights for the same seed across versions.  MoE-only keys are
    # sub-split from keys[5] below so they never perturb the dense path.
    keys = jax.random.split(rng, 9)

    def stack(key, d_in, d_out):
        return stack_dense(key, cfg.n_layers, d_in, d_out, dtype)

    def stack_experts(key, d_in, d_out):
        # (n_layers, E, d_in, d_out), fan-in scaled like _dense.
        w = jax.random.normal(
            key, (cfg.n_layers, cfg.n_experts, d_in, d_out), jnp.float32)
        return (w * np.sqrt(1.0 / d_in)).astype(dtype)

    if cfg.n_experts:
        k_router, k_down = jax.random.split(keys[5])
        ffn = {
            "router": (jax.random.normal(
                k_router, (cfg.n_layers, cfg.d_model, cfg.n_experts),
                jnp.float32) * 0.02).astype(dtype),
            "w_gate": stack_experts(keys[6], cfg.d_model, cfg.d_ff),
            "w_up": stack_experts(keys[7], cfg.d_model, cfg.d_ff),
            "w_down": stack_experts(k_down, cfg.d_ff, cfg.d_model),
        }
    else:
        ffn = {
            "w_gate": stack(keys[5], cfg.d_model, cfg.d_ff),
            "w_up": stack(keys[6], cfg.d_model, cfg.d_ff),
            "w_down": stack(keys[7], cfg.d_ff, cfg.d_model),
        }

    qk = {}
    if cfg.qk_norm:
        qk = {"q_norm": jnp.ones((cfg.n_layers, H * hd), jnp.float32),
              "k_norm": jnp.ones((cfg.n_layers, KV * hd), jnp.float32)}
    post = {}
    if cfg.sandwich_norm:
        post = {"attn_post_norm": jnp.ones((cfg.n_layers, cfg.d_model),
                                           jnp.float32),
                "mlp_post_norm": jnp.ones((cfg.n_layers, cfg.d_model),
                                          jnp.float32)}
    gate = {}
    if cfg.exit_gate:
        # A key of its own, folded in: the nine above stay what they were.
        gate = {"gate_w": _dense(jax.random.fold_in(rng, 9), cfg.d_model, 1,
                                 dtype)[:, 0],
                "gate_b": jnp.zeros((1,), dtype)}

    embed = (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model), jnp.float32)
             * 0.02).astype(dtype)
    if cfg.layer_kinds is not None:
        mtp = {}
        if cfg.mtp_layers:
            # The module's leaves: a norm for the next token's embedding and
            # one for the stack's state, the projection of the two side by
            # side, one layer of the stack's last kind (a run of one, every
            # leaf led by 1) and a final norm; the embedding and the head are
            # the model's own.  A key of its own, folded in.
            k_eh, k_layer = jax.random.split(jax.random.fold_in(rng, 10))
            unit = lambda: jnp.ones((cfg.d_model,), jnp.float32)
            mtp = {"mtp": {
                "enorm": unit(), "hnorm": unit(),
                "w_eh": _dense(k_eh, 2 * cfg.d_model, cfg.d_model, dtype),
                "layer": _init_run(k_layer, cfg, *cfg.layer_kinds[-1], 1,
                                   dtype),
                "norm": unit()}}
        return {
            "embed": embed,
            "layers": tuple(
                _init_run(jax.random.fold_in(rng, 16 + i), cfg, mixer, ffn,
                          n, dtype)
                for i, (mixer, ffn, n) in enumerate(layer_runs(cfg))),
            "norm": jnp.ones((cfg.d_model,), jnp.float32),
            "head": _dense(keys[8], cfg.d_model, cfg.vocab, dtype),
            **mtp,
        }
    return {
        "embed": embed,
        "layers": {
            "attn_norm": jnp.ones((cfg.n_layers, cfg.d_model), jnp.float32),
            "wq": stack(keys[1], cfg.d_model, H * hd),
            "wk": stack(keys[2], cfg.d_model, KV * hd),
            "wv": stack(keys[3], cfg.d_model, KV * hd),
            "wo": stack(keys[4], H * hd, cfg.d_model),
            "mlp_norm": jnp.ones((cfg.n_layers, cfg.d_model), jnp.float32),
            **qk,
            **post,
            **ffn,
        },
        "norm": jnp.ones((cfg.d_model,), jnp.float32),
        "head": _dense(keys[8], cfg.d_model, cfg.vocab, dtype),
        **gate,
    }


# ------------------------------------------------------------------- sharding

def param_specs(cfg: Config) -> Params:
    """PartitionSpec pytree: Megatron tp sharding over stacked layers; MoE
    expert weights additionally shard their expert axis over ``ep``."""
    col = P(None, None, AXIS_TP)    # (layers, d_in, sharded d_out)
    row = P(None, AXIS_TP, None)    # (layers, sharded d_in, d_out)
    if cfg.n_experts:
        ffn = {
            "router": P(None, None, None),
            "w_gate": P(None, AXIS_EP, None, AXIS_TP),
            "w_up": P(None, AXIS_EP, None, AXIS_TP),
            "w_down": P(None, AXIS_EP, AXIS_TP, None),
        }
    else:
        ffn = {"w_gate": col, "w_up": col, "w_down": row}
    # The norm runs over the whole projection, so its weight follows the
    # projection's columns and GSPMD sums the squares over tp.
    qk = ({"q_norm": P(None, AXIS_TP), "k_norm": P(None, AXIS_TP)}
          if cfg.qk_norm else {})
    post = ({"attn_post_norm": P(None, None), "mlp_post_norm": P(None, None)}
            if cfg.sandwich_norm else {})
    gate = ({"gate_w": P(None), "gate_b": P(None)} if cfg.exit_gate else {})
    if cfg.layer_kinds is not None:
        # A run's projections by columns and rows as above (the output gate's
        # columns are heads, as ``wq``'s are heads' channels), its experts
        # over ``ep`` and ``tp`` too; every other leaf (norms, convolutions,
        # the low-rank pairs, the router and its bias) whole on each device.
        sharded = {"wq": col, "wk": col, "wv": col, "wkv_b": col, "wo": row,
                   "wq_b": col, "wg": col,
                   "shared_gate": col, "shared_up": col, "shared_down": row}
        dense = {"w_gate": col, "w_up": col, "w_down": row}

        def run_specs(run):
            by_name = {**sharded, **(dense if run["w_gate"].ndim == 3 else ffn)}
            return {name: by_name.get(name, P(*[None] * a.ndim))
                    for name, a in run.items()}

        shapes = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
        # The module's layer as a run of one; its norms and ``w_eh`` whole.
        mtp = ({"mtp": {"enorm": P(None), "hnorm": P(None),
                        "w_eh": P(None, None), "norm": P(None),
                        "layer": run_specs(shapes["mtp"]["layer"])}}
               if cfg.mtp_layers else {})
        return {"embed": P(None, None),
                "layers": tuple(run_specs(run) for run in shapes["layers"]),
                "norm": P(None), "head": P(None, AXIS_TP), **mtp}
    return {
        "embed": P(None, None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": col, "wk": col, "wv": col, "wo": row,
            "mlp_norm": P(None, None),
            **qk,
            **post,
            **ffn,
        },
        "norm": P(None),
        "head": P(None, AXIS_TP),
        **gate,
    }


def shard_params(params: Params, mesh: Mesh, cfg: Config) -> Params:
    return shard_by_specs(params, mesh, param_specs(cfg))


def _ep_ranks(cfg: Config, mesh: Optional[Mesh]) -> int:
    """Ranks the sorted, dropless expert layer exchanges its units over: the
    size of the mesh's ``ep`` axis; 1 on a mesh without one and for every
    other FFN (the one-hot dispatch leaves ``ep`` to GSPMD)."""
    if mesh is None or not cfg.n_experts or cfg.capacity_factor is not None:
        return 1
    return dict(mesh.shape).get(AXIS_EP, 1)


def _batch_axes(cfg: Config, mesh: Optional[Mesh]):
    """The mesh axes a batch's rows are sharded over: ``dp``, and ``ep`` too
    where the expert layer exchanges over it (:func:`_moe_ffn_ep`: each rank
    routes its own tokens, so between the expert layers the axis is one more
    data-parallel one)."""
    return (AXIS_DP, AXIS_EP) if _ep_ranks(cfg, mesh) > 1 else AXIS_DP


def _rows_divide(cfg: Config, mesh: Mesh, n_rows: int) -> None:
    """Raise ``ValueError`` unless a batch of ``n_rows`` rows divides over
    the axes :func:`_batch_axes` names."""
    axes = [a for a in _batch_axes(cfg, mesh) if a in mesh.shape]
    if n_rows % int(np.prod([mesh.shape[a] for a in axes])):
        raise ValueError(
            f"a batch of {n_rows} rows does not divide over the mesh's "
            f"{axes} axes: the sorted expert layer shards its tokens over ep")


def batch_spec(cfg: Config, mesh: Mesh) -> P:
    """The ``PartitionSpec`` of a ``(batch, seq_len)`` array of tokens or
    targets as :func:`make_train_step` takes it on ``mesh``."""
    return _mesh_spec(P(_batch_axes(cfg, mesh), None), mesh)


# -------------------------------------------------------------------- forward

def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    norm = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (norm * w).astype(x.dtype)


def _rotate_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Adjacent channel pairs of x (B, L, H, d) turned by the angles whose
    ``cos`` and ``sin`` (L, d / 2) are given, in float32: channel 2i becomes
    ``x[2i] cos_i - x[2i+1] sin_i`` and 2i+1 ``x[2i] sin_i + x[2i+1] cos_i``.

    A channel meets its partner through a product with the (d, d) matrix that
    swaps the two of a pair and negates the second: entries 0, 1 and -1, one
    a column, so the product is exact and the values are those of slicing x
    at a stride of two.  The slicing is what it replaces.  For it the chip's
    compiler kept q and k with the SEQUENCE on the lanes and copied each
    twice on its way to the flash kernels' (heads, L, d), forward, recomputed
    and as gradients: 17.8 GB of copies a Laguna step, 7.6 with the product,
    whose 128 x 128 matrix the idle MXU takes (a roll of the lanes by one and
    a select by parity saves the same copies and costs more than they did:
    PERF.md section 6, PR 41)."""
    d = x.shape[-1]
    swap = np.zeros((d, d), np.float32)
    swap[np.arange(1, d, 2), np.arange(0, d, 2)] = -1.0    # [2i] = -x[2i+1]
    swap[np.arange(0, d, 2), np.arange(1, d, 2)] = 1.0     # [2i+1] = x[2i]
    # The batch rides as a batch dimension of the product (the matrix
    # broadcast over it): a checkpoint policy that keeps matmul outputs
    # (``"dots"``: those WITHOUT a batch dimension) would else keep this one,
    # float32 and of q's size, which no backward pass reads.
    partner = jnp.einsum(
        "blhd,bde->blhe", x,
        jnp.broadcast_to(jnp.asarray(swap, x.dtype), (x.shape[0], d, d)),
        preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST)
    cos = jnp.repeat(cos, 2, axis=-1)[None, :, None, :]
    sin = jnp.repeat(sin, 2, axis=-1)[None, :, None, :]
    return (x.astype(jnp.float32) * cos + partner * sin).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding; x: (B, L, H, D_head), positions: (L,)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (L, d/2)
    return _rotate_pairs(x, jnp.cos(angles), jnp.sin(angles))


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's ``dim // 2`` inverse frequencies (Peng et al.,
    arXiv:2309.00071, as ``transformers``' ``_compute_yarn_parameters``
    writes them, truncated): channel pair i keeps ``theta ** (-2i / dim)``
    where it turns more than ``beta_fast`` times over the ``original``
    length, takes it over ``factor`` where it turns fewer than ``beta_slow``
    times, and a linear ramp between the two pairs those counts name."""
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    turns_at = lambda n: (dim * np.log(original / (2 * np.pi * n))
                          / (2 * np.log(theta)))
    low = max(int(np.floor(turns_at(beta_fast))), 0)
    high = min(int(np.ceil(turns_at(beta_slow))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (plain * (1 - ramp) + plain / factor * ramp).astype(np.float32)


def rope_scaled(x: jax.Array, positions: jax.Array, inv_freq,
                factor: float = 1.0) -> jax.Array:
    """:func:`rope` on the first ``2 * len(inv_freq)`` channels of x (B, L,
    H, D_head) at the inverse frequencies given, cos and sin times
    ``factor``; the channels after them pass as they are."""
    r = 2 * len(inv_freq)
    angles = positions[:, None].astype(jnp.float32) * jnp.asarray(inv_freq)
    out = _rotate_pairs(x[..., :r], factor * jnp.cos(angles),
                        factor * jnp.sin(angles))
    return jnp.concatenate([out, x[..., r:]], axis=-1)


def _rotation(cfg: Config, mixer: str) -> Callable:
    """``(x, positions) -> x`` rotated as a softmax layer of kind ``mixer``
    rotates its queries and keys: a window layer every channel at
    ``swa_rope_theta``; a full layer the first ``rope_fraction`` of them at
    ``rope_theta``, with YaRN's frequencies and attention factor where the
    configuration has ``rope_yarn``."""
    if mixer == "swa":
        return lambda x, positions: rope(x, positions, cfg.swa_rope_theta)
    if cfg.rope_yarn is None and cfg.rope_fraction == 1:
        return lambda x, positions: rope(x, positions, cfg.rope_theta)
    dim = int(cfg.head_dim * cfg.rope_fraction)
    if cfg.rope_yarn is None:
        inv_freq, factor = cfg.rope_theta ** (
            -np.arange(0, dim, 2, dtype=np.float32) / dim), 1.0
    else:
        *yarn, factor = cfg.rope_yarn
        inv_freq = yarn_inv_freq(dim, cfg.rope_theta, *yarn)
    return lambda x, positions: rope_scaled(x, positions, inv_freq, factor)


_NEG_INF = -1e30   # attention mask fill, shared by training and decode paths


def _causal_attention(q, k, v, scale, window: Optional[int] = None):
    """(B, L, H, Dh) x (B, L, KV, Dh): GQA causal attention, f32 softmax;
    with a ``window`` row i sees keys ``i - window < j <= i``."""
    B, L, H, Dh = q.shape
    KV = k.shape[2]
    rep = H // KV
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    # f32 ACCUMULATION on both einsums (not a post-hoc astype, which would
    # round bf16 scores first): keeps attn="full" in agreement with the
    # flash/ring paths' f32 score/output accumulation beyond bf16 input
    # rounding.  full is the O(L^2)-memory small-model path, so the f32 PV
    # cost is not on the long-context critical path.
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.tril(jnp.ones((L, L), bool))
    if window is not None:
        mask &= ~jnp.tril(jnp.ones((L, L), bool), -window)
    s = jnp.where(mask[None, None], s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _tp_head_axis(mesh: Mesh, heads: int, kv_heads: int) -> Optional[str]:
    """``tp`` when the mesh has it and it divides both head counts, so a
    shard_map may split attention over heads; else heads stay whole."""
    tp = dict(mesh.shape).get(AXIS_TP)
    if tp and heads and kv_heads and heads % tp == 0 and kv_heads % tp == 0:
        return AXIS_TP
    return None


def _ring_attention_batched(mesh: Mesh, causal_scale,
                            heads: int = 0, kv_heads: int = 0,
                            impl: str = "ring_flash"):
    """shard_map'ed ring attention over sp, batched.  GQA is native: K/V
    enter at n_kv_heads and circulate the ring at that count (1/(H/KV) of
    the repeated-KV traffic); blocks expand them locally.

    ``impl="ring_flash"`` (default) runs every per-chunk block through the
    Pallas flash kernels with the f32 log-sum-exp carry across ring steps
    (parallel/sequence.py:ring_flash_attention_batched) — per-device memory
    O(L_local * block), the long-context production path.  ``impl="ring"``
    keeps the exact XLA-einsum blocks (the oracle; materializes
    (H, L_local, L_local) scores, short-L_local only).

    On a mesh that also has a ``tp`` axis the head dimension shards over it
    (Megatron-SP composition: tp over heads x ring over sequence) when both
    head counts divide — otherwise heads would be *replicated* over tp,
    forcing an all-gather of the tp-sharded qkv projections at the
    shard_map boundary and repeating the full attention on every tp rank.
    """
    from jax import shard_map
    from ..parallel import sequence as seq_mod

    if impl == "ring_flash":
        def body(q, k, v):
            return seq_mod.ring_flash_attention_batched(
                q, k, v, axis=AXIS_SP, causal=True, scale=causal_scale)
    elif impl == "zigzag":
        def body(q, k, v):
            return seq_mod.zigzag_ring_flash_attention_batched(
                q, k, v, axis=AXIS_SP, scale=causal_scale)
    else:
        def body(q, k, v):
            fn = lambda q1, k1, v1: seq_mod.ring_attention(
                q1, k1, v1, axis=AXIS_SP, causal=True, scale=causal_scale)
            return jax.vmap(fn)(q, k, v)

    spec = _mesh_spec(
        P(AXIS_DP, AXIS_SP, _tp_head_axis(mesh, heads, kv_heads), None), mesh)
    return shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)


def _flash_attention_sharded(mesh: Optional[Mesh], heads: int,
                             kv_heads: int, window: Optional[int] = None,
                             rows=AXIS_DP) -> Callable:
    """Causal flash attention ``(q, k, v) -> o`` for K/V at their native
    ``kv_heads``, over the last ``window`` keys where one is given (the
    kernels then run the band's blocks alone).  On a mesh the kernel runs
    inside a ``shard_map`` over
    the batch (``rows``: ``dp``, and ``ep`` where :func:`_batch_axes` says so)
    and head (``tp``) axes: the TPU compiler refuses to
    partition a Mosaic kernel itself (``NotImplementedError: Mosaic kernels
    cannot be automatically partitioned``), and each device wants only its
    own batch rows and head shard anyway — the layout the hand-sharded
    stage (:func:`_decoder_layer_tp_manual`) already runs.  K/V are split
    at ``kv_heads`` and stay at that count: the kernels' index maps name a
    group of query heads its one K/V head and ``flash_bwd`` sums dk and dv
    over the group, so nothing is repeated in HBM.  A step
    runs two kernels a layer, ``flash_fwd`` and ``flash_bwd``, under every
    remat policy: the layer's checkpoint keeps the forward's ``o`` and
    ``lse`` under ``"dots"`` and under ``"full"`` alike
    (:func:`_wrap_remat`), so ``"full"`` holds one more array of the layer
    input's size a layer application and never runs the forward kernel
    twice."""
    from jax import shard_map

    from ..ops import flash_attention

    def local(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window)

    if mesh is None or mesh.size == 1:
        return local
    spec = _mesh_spec(
        P(rows, None, _tp_head_axis(mesh, heads, kv_heads), None), mesh)

    def sharded(q, k, v):
        # Inside a pipeline stage ``pp`` is already manual: the nested
        # shard_map then takes the context mesh and only the axes left.
        manual = set(jax.sharding.get_abstract_mesh().manual_axes)
        where = (dict(axis_names=set(mesh.axis_names) - manual) if manual
                 else dict(mesh=mesh))
        return shard_map(local, in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False, **where)(q, k, v)

    return sharded


def _kda_sharded(mesh: Optional[Mesh], heads: int, eps: float) -> Callable:
    """A KDA layer between its projections (``ops.kda_mixer.kda_mixer``: the
    way in, the recurrence, the way out).  On a mesh all three run in ONE
    ``shard_map`` over the batch (``dp``) and head (``tp``) axes, as the
    flash kernel does and for its reason; :func:`_kda_block` says how the
    per-channel leaves go.  On no mesh or one device: the plain call."""
    from ..ops.kda_mixer import kda_mixer

    mixer = functools.partial(kda_mixer, eps=eps)
    if mesh is None or mesh.size == 1:
        return mixer
    from jax import shard_map

    tp = _tp_head_axis(mesh, heads, heads)
    wide = _mesh_spec(P(AXIS_DP, None, tp), mesh)
    return shard_map(
        mixer, mesh=mesh, out_specs=wide, check_vma=False,
        in_specs=(wide,) * 6 + (P(None, tp),) * 3 + (P(tp), P(tp), P()))


_RINGS = ("ring", "ring-zigzag", "ring-xla")


def _make_attn_impl(cfg: Config, attn: str, mesh: Optional[Mesh],
                    scale: float, mixer: str = "attn") -> Callable:
    """Resolve the attention mode to one callable ``(q, k, v) -> o`` with
    q (B, L, H, hd) and k/v at the native (B, L, KV, hd) — the single
    dispatch point shared by :func:`apply` and the pipeline stages.
    ``mixer`` ``"swa"``: a window layer's, at its own head count over the
    last ``cfg.swa_window`` keys."""
    H, KV = softmax_heads(cfg, mixer), cfg.n_kv_heads
    window = cfg.swa_window if mixer == "swa" else None
    if attn in _RINGS:
        if mesh is None:
            raise ValueError("attn='ring' needs a mesh with an sp axis")
        assert window is None, "refused before (_LACKS, the rings' rows)"
        # K/V enter the ring at their native n_kv_heads — the ring
        # circulates 1/(H/KV) of the bytes; blocks repeat locally.
        # Contiguous head sharding over tp keeps each rank's q heads
        # aligned with its kv heads (rank t owns q [tH/tp, (t+1)H/tp) and
        # kv [tKV/tp, (t+1)KV/tp); h // (H/KV) lands in exactly that kv
        # range).  'ring' composes the ring with the Pallas flash block
        # kernels; 'ring-zigzag' is its load-balanced layout (the caller —
        # make_loss_fn — permutes tokens/positions into zigzag order);
        # 'ring-xla' is the exact einsum-block oracle.
        impl = {"ring": "ring_flash", "ring-zigzag": "zigzag",
                "ring-xla": "ring"}[attn]
        return _ring_attention_batched(mesh, scale, H, KV, impl=impl)
    if attn == "flash":
        return _flash_attention_sharded(mesh, H, KV, window,
                                        _batch_axes(cfg, mesh))
    if attn == "full":
        return lambda q, k, v: _causal_attention(q, k, v, scale, window)
    raise ValueError(
        f"attn must be 'full', 'flash', 'ring', 'ring-zigzag', or "
        f"'ring-xla', got {attn!r}")


def _moe_group(cfg: Config, n_tokens: int) -> int:
    """Routing-group size: largest divisor of ``n_tokens`` at most
    ``cfg.moe_group_size``.  When only sliver divisors exist below the
    target (e.g. ``n_tokens = 2 * prime``), groups of ~2 tokens would
    collapse capacity to ~1, reduce the aux load-balance statistic to
    noise, and vmap thousands of tiny dispatch einsums — so fall UP to the
    smallest divisor above the target instead: a bigger group costs
    linearly more dispatch memory but stays statistically and MXU-sane,
    and token counts the caller cannot control (prime generation prompt
    lengths, odd decode batches) must never fail."""
    target = min(n_tokens, cfg.moe_group_size)
    g = target
    while n_tokens % g:
        g -= 1
    floor = min(n_tokens, max(16, cfg.moe_group_size // 8))
    if g >= floor:
        return g
    for d in range(target + 1, n_tokens + 1):
        if n_tokens % d == 0:      # n_tokens divides itself: always found
            if d > 8 * cfg.moe_group_size:
                import logging

                logging.getLogger(__name__).warning(
                    "moe routing group %d is %.0fx the configured %d "
                    "(n_tokens=%d has no mid-sized divisor); dispatch "
                    "memory grows with the group — pad the token count "
                    "if this is the training path", d,
                    d / cfg.moe_group_size, cfg.moe_group_size, n_tokens)
            return d
    return n_tokens  # unreachable


def _moe_capacity(cfg: Config, group: int) -> int:
    """Static per-expert slot count for one routing group.  Top-k experts
    are distinct, so an expert's worst-case load is ``group`` (one unit per
    token), not ``k * group``."""
    k, E = cfg.expert_top_k, cfg.n_experts
    cap = int(np.ceil(cfg.capacity_factor * k * group / E))
    return max(1, min(cap, group))


def _moe_ffn(cfg: Config, lp: Params, x: jax.Array, dropless: bool = False,
             mesh: Optional[Mesh] = None):
    """Mixture-of-experts SwiGLU FFN on normed input x (B, L, D) ->
    ``(out (B, L, D), aux-loss scalar f32)``.

    GShard-style dense dispatch/combine over fixed-size **routing groups**:
    tokens are split into groups of ~``cfg.moe_group_size`` and each group
    routes independently with capacity ``C = cf * k * G / E`` slots per
    expert — the dispatch tensor is (G·k, E, C) *per group*, so cost grows
    linearly in token count (a single global group would be O(T²)).  The
    dispatch and combine are einsums, so the whole layer is three batched
    GEMMs plus routing on the MXU.  Under pjit with expert weights sharded
    over ``ep`` (see :func:`param_specs`), GSPMD inserts the token
    all-to-alls — the same primitive parallel/moe.py's shard_map form issues
    explicitly.  Routing is top-k with choice-major capacity priority (every
    token's primary route is served before any secondary route); weights are
    renormalized over the chosen k for k > 1, raw gate prob for k = 1.  A
    unit past capacity is dropped (contributes 0 to the residual stream).
    ``dropless=True`` sets C = G (an expert can receive at most one unit
    per token since top-k picks distinct experts) — the decode path's
    guarantee that routing never depends on bucket pressure, on its
    handful of tokens.  A configuration with ``capacity_factor=None`` is
    dropless in training and prefill too, and takes
    :func:`_moe_ffn_sorted` there: at C = G this form computes E/k times
    the required expert FLOPs.  Weights are renormalised over the chosen k
    unless ``cfg.moe_renormalize`` is off; with ``cfg.moe_z_coef`` the
    aux result is the pair (load balance, router z-loss).

    The aux loss is the Switch/GShard load-balance term
    ``E * sum_e mean_prob_e * primary_fraction_e`` (= 1 at perfect balance),
    averaged over groups.
    """
    if cfg.capacity_factor is None and not dropless:
        return _moe_ffn_sorted(cfg, lp, x, mesh)
    B, L, D = x.shape
    E, k = cfg.n_experts, cfg.expert_top_k
    T = B * L
    G = _moe_group(cfg, T)
    C = G if dropless else _moe_capacity(cfg, G)
    xg = x.reshape(T // G, G, D)

    def route_group(xt):                    # (G, D) -> ((G, D), aux)
        with jax.named_scope("moe.router"):
            logits = xt.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)                 # (G, E)
            # ONE routing definition for both MoE forms: the shared top-k /
            # choice-major / capacity-queue step
            # (parallel/moe.py:route_topk).
            sel_f, w_f, onehot, slot = _route_topk(
                probs, k, cfg.moe_renormalize and k > 1)
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(jax.nn.one_hot(sel_f[:G], E, dtype=jnp.float32),
                          axis=0)
            aux = E * jnp.sum(me * ce)
            if cfg.moe_z_coef:
                aux = jnp.stack([aux, _router_z(logits)])
        with jax.named_scope("moe.dispatch"):
            # one_hot(slot, C) drops units whose queue position >= C.
            dispatch = (jax.nn.one_hot(slot, C, dtype=jnp.float32)
                        * onehot[..., None])                        # (kG, E, C)
            disp = dispatch.astype(x.dtype)

            xk = jnp.tile(xt, (k, 1))                               # (kG, D)
            buckets = jnp.einsum("tec,td->ecd", disp, xk)           # (E, C, D)
        with jax.named_scope("moe.experts"):
            hb = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buckets,
                                         lp["w_gate"]))
                  * jnp.einsum("ecd,edf->ecf", buckets, lp["w_up"]))
            out_b = jnp.einsum("ecf,efd->ecd", hb, lp["w_down"])    # (E, C, D)

        with jax.named_scope("moe.combine"):
            combine = disp * w_f[:, None, None].astype(x.dtype)
            yk = jnp.einsum("tec,ecd->td", combine, out_b)          # (kG, D)
            return jnp.sum(yk.reshape(k, G, D), axis=0), aux

    y, aux = jax.vmap(route_group)(xg)
    return y.reshape(B, L, D), jnp.mean(aux, axis=0)


def _router_z(logits: jax.Array) -> jax.Array:
    """Router z-loss, ``mean(logsumexp(logits)^2)`` (Zoph et al.,
    arXiv:2202.08906 eq. 5): keeps the router's logits small."""
    return jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_rows(x, order, inverse, k):
    """Rows of ``x`` (T, D) to their k routed units in sorted order, (k*T,
    D): unit u = t * k + j is token t's j-th choice, ``order`` lists the
    units by expert and ``inverse`` is its inverse permutation.  With
    :func:`_combine_rows` a pair, each the other's transpose, so a backward
    pass gathers where autodiff would scatter-add."""
    return x[order // k]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _combine_rows(ys, order, inverse, k):
    """Sorted units ``ys`` (k*T, D) back to tokens: the sum of each token's
    k units, (T, D), accumulated in float32."""
    yk = ys[inverse].reshape(ys.shape[0] // k, k, ys.shape[1])
    return jnp.sum(yk, axis=1, dtype=jnp.float32).astype(ys.dtype)


def _rows_bwd(transpose):
    def bwd(k, perms, g):
        zero = np.zeros(perms[0].shape, jax.dtypes.float0)
        return transpose(g, *perms, k), zero, zero
    return bwd


_dispatch_rows.defvjp(lambda x, o, i, k: (_dispatch_rows(x, o, i, k), (o, i)),
                      _rows_bwd(_combine_rows))
_combine_rows.defvjp(lambda ys, o, i, k: (_combine_rows(ys, o, i, k), (o, i)),
                     _rows_bwd(_dispatch_rows))


def _grouped_matmul(xs: jax.Array, w: jax.Array, counts: jax.Array,
                    kernel: bool) -> jax.Array:
    """``xs`` (M, K), whose rows lie in contiguous segments of ``counts[e]``
    rows for expert e, times that expert's ``w[e]`` (E, K, N) -> (M, N).
    On one device the Mosaic grouped matmul that JAX ships
    (``pallas.ops.tpu.megablox``: row tiles of 512 each owned by one expert,
    a tile that two experts share visited once for each; its VJP is two more
    such kernels), in interpret mode off the TPU; 81-87% of the v5e's peak on
    131,072 rows of 2048 x 1024 where XLA's own lowering of
    ``lax.ragged_dot`` reaches 55-68% (PERF.md section 6, PR 26).  Under GSPMD
    on several devices ``lax.ragged_dot``, which the compiler partitions
    itself and a Mosaic kernel of ours it would refuse to."""
    if not kernel:
        return lax.ragged_dot(xs, w, counts)
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    (M, K), N = xs.shape, w.shape[-1]
    tile = min(512, -(-M // 16) * 16)
    pad = -M % tile         # whole row tiles: the last expert takes the zeros
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
        counts = counts.at[-1].add(pad)
    return megablox.gmm(xs, w, counts, xs.dtype,
                        (tile, min(K, 1024), min(N, 1024)),
                        interpret=jax.default_backend() != "tpu")[:M]


def _route_tokens(cfg: Config, lp: Params, xt: jax.Array,
                  axes: Tuple[str, ...] = ()):
    """The dropless router on tokens ``xt`` (T, D): float32 softmax over all
    experts, top-k with the weights renormalised over the chosen k or not as
    the configuration says.  Returns ``(weight (T, k) f32, expert (T, k)
    int32, counts (E,) int32, aux)``: ``counts[e]`` units go to expert e and
    sum to k*T; ``aux`` is the Switch load-balance term over the whole batch
    (first choices), stacked with the z-loss where that has a weight.

    With ``cfg.router_act == "sigmoid"`` the scores are float32 sigmoids, the
    k experts those with the largest ``score + router_bias`` (the bias moves
    the choice alone: the weights are the chosen scores without it, and its
    gradient is exactly zero), renormalised as published (``w / (sum + 1e-20)
    ``), and there is no auxiliary term (``aux`` 0).  ``cfg.routed_scale``
    multiplies the weights of either router.

    Inside a ``shard_map`` whose ranks each hold an equal share of the tokens
    (:func:`_moe_ffn_ep`), ``axes`` names its axes: ``counts`` stay this
    rank's, and ``aux`` is the whole batch's, its means taken over the ranks
    too."""
    E, k = cfg.n_experts, cfg.expert_top_k
    over = lambda a: lax.pmean(a, axes) if axes else a
    logits = xt.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    if cfg.router_act == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        ranked = scores
        if cfg.router_bias:
            ranked = scores + lax.stop_gradient(lp["router_bias"])
        expert = lax.top_k(ranked, k)[1]
        weight = jnp.take_along_axis(scores, expert, axis=-1)
        if cfg.moe_renormalize and k > 1:
            weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                               + 1e-20)
        counts = jnp.sum(jax.nn.one_hot(expert, E, dtype=jnp.int32),
                         axis=(0, 1))
        return (weight * cfg.routed_scale, expert, counts,
                jnp.zeros((), jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                         # (T, E)
    weight, expert = lax.top_k(probs, k)
    if cfg.moe_renormalize and k > 1:
        weight = weight / jnp.maximum(
            jnp.sum(weight, axis=-1, keepdims=True), 1e-9)
    if cfg.routed_scale != 1.0:
        weight = weight * cfg.routed_scale
    chosen = jax.nn.one_hot(expert, E, dtype=jnp.int32)             # (T, k, E)
    counts = jnp.sum(chosen, axis=(0, 1))
    aux = E * jnp.sum(over(jnp.mean(probs, axis=0)) * over(
        jnp.mean(chosen[:, 0].astype(jnp.float32), axis=0)))
    if cfg.moe_z_coef:
        aux = jnp.stack([aux, over(_router_z(logits))])
    return weight, expert, counts, aux


def _moe_ffn_sorted(cfg: Config, lp: Params, x: jax.Array,
                    mesh: Optional[Mesh] = None):
    """The dropless mixture-of-experts FFN, x (B, L, D) -> ``(out, aux)``:
    no capacity, no routing group, every one of the k*T routed units reaches
    its expert.  Units are sorted by expert (stable, so in token order within
    an expert) and gathered into one (k*T, D) array whose contiguous segments
    each meet their own expert's weights in a grouped matmul
    (:func:`_grouped_matmul`) for gate, up and down; the hidden rows carry
    the router's weight into the down product, so the results only have to
    be summed back to their tokens by the inverse permutation, and no
    backward pass needs the down product's output.  The gate and the up
    products' outputs a backward pass does read, through the SwiGLU between
    them: each carries a ``checkpoint_name`` (``GROUPED_DOT_NAMES``), given
    after :func:`_grouped_matmul` returns so that megablox's ``gmm`` and
    ``lax.ragged_dot`` are named alike, and ``remat="dots"`` keeps them as it
    keeps any dot (:func:`_wrap_remat`): a step forms the 9 products a layer
    that it requires and none twice.  The cost is k/E of the
    one-hot form's at C = G and does not grow with E.  On one device
    (``mesh`` None or of size 1) or under GSPMD on dp and tp; on a mesh with
    an ``ep`` axis the layer is :func:`_moe_ffn_ep`, and ``aux`` is then the
    pair of it and the units the exchange delivered to each rank.

    For a chip's share of the experts (``cfg.experts_held``) the router is
    still ``n_experts`` wide and its weights are normalised over all k
    choices, but only the units whose expert is held here are computed
    (:func:`_held_experts`, as dropless as the rest), and ``out`` is the held
    experts' part of the layer's result plus the shared expert's, which every
    chip computes alike.  What the absent experts would add is left out: on
    one chip there is no exchange, and nothing stands in for one."""
    if _ep_ranks(cfg, mesh) > 1:
        return _moe_ffn_ep(cfg, lp, x, mesh)
    B, L, D = x.shape
    k = cfg.expert_top_k
    T = B * L
    xt = x.reshape(T, D)
    kernel = mesh is None or mesh.size == 1
    with jax.named_scope("moe.router"):
        weight, expert, counts, aux = _route_tokens(cfg, lp, xt)
    if cfg.experts_held:
        first, held = cfg.experts_held
        R = held_pass_rows(cfg, T)
        with jax.named_scope("moe.dispatch"):
            order = _held_order(expert.reshape(T * k), first, held, R)[0]
        y = _held_experts(k, R, kernel, xt, weight.reshape(T * k), order,
                          counts[first:first + held],
                          (lp["w_gate"], lp["w_up"], lp["w_down"]))
        return _add_shared_expert(cfg, lp, xt, y).reshape(B, L, D), aux
    with jax.named_scope("moe.dispatch"):
        order = jnp.argsort(expert.reshape(T * k), stable=True)
        inverse = jnp.argsort(order)
        xs = _dispatch_rows(xt, order, inverse, k)
        ws = _dispatch_rows(weight.reshape(T * k, 1), order, inverse, 1)
    with jax.named_scope("moe.experts"):
        gate = checkpoint_name(
            _grouped_matmul(xs, lp["w_gate"], counts, kernel),
            GROUPED_DOT_NAMES[0])
        up = checkpoint_name(
            _grouped_matmul(xs, lp["w_up"], counts, kernel),
            GROUPED_DOT_NAMES[1])
        hs = jax.nn.silu(gate) * up
        ys = _grouped_matmul((hs * ws).astype(x.dtype), lp["w_down"], counts,
                             kernel)                                # (kT, D)
    with jax.named_scope("moe.combine"):
        y = _combine_rows(ys, order, inverse, k)
    return _add_shared_expert(cfg, lp, xt, y).reshape(B, L, D), aux


def _held_order(chosen, first, held, R):
    """The units whose expert ``chosen`` (n,) is one of the ``held`` from
    ``first`` on, FIRST and by expert, in their own order within one (a stable
    sort), the others after them; padded to whole passes of ``R`` rows, so
    that no slice of a pass is moved to fit.  Returns ``(order, at)``: ``at``
    is each unit's held expert, ``held`` for a unit of an expert not held."""
    local = chosen - first
    here = (local >= 0) & (local < held)
    at = jnp.where(here, local, held)
    return jnp.pad(jnp.argsort(at, stable=True), (0, -chosen.size % R)), at


def _add_shared_expert(cfg: Config, lp: Params, xt: jax.Array, y: jax.Array):
    """``y`` plus the expert every token meets, on tokens ``xt`` (T, D): one
    SwiGLU of width ``n_shared_experts * d_ff`` with weight 1.  ``y`` itself
    for a configuration without one."""
    if not cfg.n_shared_experts:
        return y
    with jax.named_scope("moe.shared"):
        return y + ((jax.nn.silu(xt @ lp["shared_gate"])
                     * (xt @ lp["shared_up"])) @ lp["shared_down"])


# Rows of one pass of :func:`_held_experts` over the rows uniform routing
# sends the held experts (k * T * held / n_experts).  It decides time and
# memory, never the result: a layer whose held experts draw more takes another
# pass.  At seeded weights 8 of 256 experts drew 0.4% to 6.8% of a layer's
# units where uniform routing gives 3.1%, and a router trained on its held
# experts' part of the gradient alone drifts towards or away from them (one
# layer of one seed in six passed four times the share within 13 AdamW steps;
# chip runs of PR 32, PERF.md section 6): at four times the share nearly every
# layer of every step is one pass, and the pass's rows are an eighth of the
# worst case's for 8 of 256.
_HELD_PASS_OVER_SHARE = 4


def held_pass_rows(cfg: Config, n_tokens: int) -> int:
    """Rows of one pass of :func:`_held_experts` for ``n_tokens`` tokens:
    ``_HELD_PASS_OVER_SHARE`` (4) times the held experts' share under uniform
    routing, in whole tiles of 16, and never more than the units that can
    reach them (a token meets an expert once)."""
    k, (_, held) = cfg.expert_top_k, cfg.experts_held
    share = -(-k * n_tokens * held // cfg.n_experts)
    return min(min(k, held) * n_tokens,
               -(-_HELD_PASS_OVER_SHARE * share // 16) * 16)


def _held_pass(k, R, xt, wflat, order, arrived, p):
    """Pass ``p`` of :func:`_held_experts`: rows ``p * R`` to ``(p + 1) * R``
    of the held units in sorted order, gathered.  Returns ``(token, unit,
    rows, xs, ws, kept)``: each row's token and unit (``T`` and ``T * k``,
    which no gather or scatter reaches, where the row is past the units that
    arrived), the valid rows as a column, the tokens' rows of ``xt`` and the
    router's weights (0 where not valid), and each held expert's rows in
    this pass."""
    T = xt.shape[0]
    ends = jnp.cumsum(arrived)
    lo = p * R
    valid = lo + jnp.arange(R) < ends[-1]
    unit = jnp.where(valid, lax.dynamic_slice(order, (lo,), (R,)), T * k)
    token = jnp.where(valid, unit // k, T)
    xs = xt.at[token].get(mode="fill", fill_value=0)
    ws = wflat.at[unit].get(mode="fill", fill_value=0)[:, None]
    kept = jnp.clip(ends - lo, 0, R) - jnp.clip(ends - arrived - lo, 0, R)
    return token, unit, valid[:, None], xs, ws, kept


def _held_swiglu(kernel, rows, kept, xs, ws, w_gate, w_up, w_down):
    """The held experts' SwiGLUs on one pass's rows ``xs`` (R, D), the hidden
    rows carrying the router's weights ``ws`` into the down product.  Rows
    past the units that arrived belong to no expert: megablox's ``gmm``
    leaves them unwritten (whatever the buffer held), so every product's
    output is selected against the valid ``rows``, forward and, through the
    selects' transposes, backward."""
    product = lambda a, w: jnp.where(
        rows, _grouped_matmul(a, w, kept, kernel), 0)
    hs = jnp.where(rows, jax.nn.silu(product(xs, w_gate))
                   * product(xs, w_up) * ws, 0)
    return product(hs.astype(xs.dtype), w_down)


def _row_sums(xt):
    """Float32 zeros for the sums of the rows ``xt`` (N, D), a row a slab:
    (N, 1, D), what :func:`_add_rows` adds into."""
    return jnp.zeros((xt.shape[0], 1, xt.shape[1]), jnp.float32)


def _add_rows(kernel, sums, token, rows, kept):
    """``sums`` (N, 1, D) float32 with one pass's ``rows`` (R, D) added to
    their tokens' in float32: row r to ``sums[token[r]]``, a row whose token
    is N (past the units that arrived: :func:`_held_pass`) to none.  The
    loops carry their sums a row a slab, (N, 1, D): on the chip that shape
    lies row after row in memory, so a row is one DMA
    (``ops/scatter_add_rows.py``), where a row of (N, D) is D / 128 pieces of
    an (8, 128) tiling that Mosaic will not slice.

    With ``kernel`` (the condition :func:`_grouped_matmul` has) the Pallas
    kernel: it takes the rows one expert's segment at a time (``kept``, the
    pass's rows by held expert: within a segment a token appears once, and a
    token's two or three units in one pass lie in different segments, which
    it takes in turn), moves a row by two DMAs through VMEM, in place, and
    does not visit the row tiles past the rows that arrived.  XLA's own
    scatter-add took 273 to 318 ns a row of 2,304 numbers there, a tenth of
    the HBM's rate (PERF.md section 6, PR 52).  ``at[].add`` where ``tp`` is
    left to GSPMD, which would refuse to partition a kernel of ours (no
    cell), and on the chip where a row is not whole lanes of 128 (no cell)."""
    D = sums.shape[-1]
    if not kernel or (D % 128 and jax.default_backend() == "tpu"):
        return sums.at[token].add(rows.astype(jnp.float32)[:, None],
                                  mode="drop")
    from ..ops.scatter_add_rows import scatter_add_rows

    return scatter_add_rows(sums, token, rows, kept,
                            interpret=jax.default_backend() != "tpu")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _held_experts(k, R, kernel, xt, wflat, order, arrived, w):
    """The routed experts held here on tokens ``xt`` (T, D) -> (T, D): the
    sum, for each token, of its units whose expert is held, each unit the
    expert's SwiGLU times the router's weight ``wflat`` (T * k, float32).
    ``order`` lists the units, those of held experts first and by expert
    (padded to whole passes), ``arrived`` counts them by held expert, ``w``
    is ``(w_gate, w_up, w_down)`` of the held experts.

    The worst case is min(k, held) * T rows, thirty-two times what uniform
    routing sends 8 of 256 experts, and the gathers and scatter-adds of that
    many rows, nearly all of them empty, would cost several times what the
    experts do (PERF.md section 6).  So the units are taken ``R`` rows a pass
    (:func:`held_pass_rows`) in a loop of as many passes as the units that
    arrived need, one as a rule: the shapes are static, time and memory are
    those of the units that arrived, and no unit is ever dropped.  Each pass gathers its rows,
    runs the grouped matmuls (:func:`_held_swiglu`) and adds the results to
    their tokens in float32 (:func:`_add_rows`: since PR 52 a Pallas kernel
    that moves the rows that arrived by DMA, one expert's segment at a time,
    into sums the loop carries a row a slab; the rows' cotangents backward
    likewise; the gathers are XLA's).  A loop whose length the data decides has no
    transpose, so the gradient is written out: it keeps the inputs alone and
    takes the same passes, each through :func:`_held_swiglu`'s own VJP (its
    gate and up products formed again there), the weights' gradients summed
    over the passes in float32."""
    return _held_experts_fwd(k, R, kernel, xt, wflat, order, arrived, w)[0]


def _held_experts_fwd(k, R, kernel, xt, wflat, order, arrived, w):
    def one_pass(p, y):
        with jax.named_scope("moe.dispatch"):
            token, _, rows, xs, ws, kept = _held_pass(k, R, xt, wflat, order,
                                                      arrived, p)
        with jax.named_scope("moe.experts"):
            ys = _held_swiglu(kernel, rows, kept, xs, ws, *w)
        with jax.named_scope("moe.combine"):
            return _add_rows(kernel, y, token, ys, kept)

    y = lax.fori_loop(0, -(-jnp.sum(arrived) // R), one_pass,
                      _row_sums(xt))
    return y[:, 0].astype(xt.dtype), (xt, wflat, order, arrived, w)


def _held_experts_bwd(k, R, kernel, saved, dy):
    xt, wflat, order, arrived, w = saved
    f32 = lambda a: a.astype(jnp.float32)

    def one_pass(p, grads):
        dxt, dwflat, dw = grads
        with jax.named_scope("moe.dispatch"):
            token, unit, rows, xs, ws, kept = _held_pass(k, R, xt, wflat,
                                                         order, arrived, p)
        with jax.named_scope("moe.combine"):
            dys = dy.at[token].get(mode="fill", fill_value=0)
        with jax.named_scope("moe.experts"):
            dxs, dws, *dwp = jax.vjp(functools.partial(
                _held_swiglu, kernel, rows, kept), xs, ws, *w)[1](dys)
        with jax.named_scope("moe.dispatch"):
            return (_add_rows(kernel, dxt, token, dxs, kept),
                    dwflat.at[unit].add(dws[:, 0], mode="drop"),
                    tuple(a + f32(b) for a, b in zip(dw, dwp)))

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    dxt, dwflat, dw = lax.fori_loop(
        0, -(-jnp.sum(arrived) // R), one_pass,
        (_row_sums(xt), zeros(wflat), tuple(zeros(a) for a in w)))
    none = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dxt[:, 0].astype(xt.dtype), dwflat.astype(wflat.dtype),
            none(order), none(arrived),
            tuple(g.astype(a.dtype) for g, a in zip(dw, w)))


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


# The tile of a sum (K, N) that one grid step of a weight gradient's
# ``tgmm_add`` holds in :func:`_held_swiglu_bwd`, beside the row tile of the
# ``gmm``s; chosen on the chip at Mellum2's block, 8,192 rows of 2304 x 896
# (PERF.md section 6, PR 47).
_TGMM_TILES = (1152, 1152)


def _held_swiglu_bwd(kernel, rows, kept, xs, ws, w, dys, dw):
    """:func:`_held_swiglu`'s VJP on one block of :func:`_ep_experts`' rows
    or one pass of :func:`_ep_gathered`'s,
    written out, with the weights' gradients ADDED to sums it is handed:
    ``(dxs, dws, dw)`` for the block's results' cotangents ``dys`` (R, D),
    where ``dw`` comes in as the float32 sums of ``(w_gate, w_up, w_down)``'s
    gradients over the blocks before this one and leaves with this block's
    in them.

    Gate and up are formed again (the layer keeps its inputs alone), the
    hidden rows' cotangent is the grouped matmul of ``dys`` with the down
    weights transposed, the SwiGLU's and the router weight's cotangents are
    taken in float32 and rounded once, ``dxs`` is the two transposed products
    of the gate's and the up's: the five ``gmm``s autodiff runs, at its
    tiles.  The three weight gradients are ``ops.tgmm.tgmm_add``: each adds
    its groups' products into the sum of its weight in float32 inside the
    kernel, the sum aliased to the result, and visits only the experts the
    block has rows for.  Autodiff through megablox's VJP had each ``tgmm``
    write all the held experts' gradients in the compute dtype, whichever few
    the block's sorted rows belong to, and an XLA pass read them and read and
    wrote the float32 sums: 1 GB a block of Mellum2's layer, 80 blocks a step
    (PERF.md section 6, PRs 46 and 47).  Now no array of the weights' shape
    is written a block, a block's gradient is not rounded before it is
    summed, and a block with no valid row returns the sums to the bit.  The
    selects against the valid ``rows`` stand wherever ``gmm`` leaves rows
    unwritten (gate, up, the hidden rows' cotangent, ``dxs``); ``tgmm_add``
    reads the rows its groups cover and no others.

    With ``kernel`` False (``tp`` left to GSPMD: ``lax.ragged_dot``, which
    has no form that adds into an operand; no cell) the block keeps
    :func:`_held_swiglu`'s own VJP and the sums an XLA add.
    :func:`_held_experts` keeps autodiff's too: it takes one pass of one
    block as a rule, so its sum has one term and there is no pass over sums
    to save, and its three cells' compiled steps stay what they are."""
    f32 = lambda a: a.astype(jnp.float32)
    if not kernel:
        dxs, dws, *dwp = jax.vjp(functools.partial(
            _held_swiglu, kernel, rows, kept), xs, ws, *w)[1](dys)
        return dxs, dws, tuple(a + f32(b) for a, b in zip(dw, dwp))
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend

    from ..ops.tgmm import tgmm_add

    R = xs.shape[0]
    tile = min(512, -(-R // 16) * 16)
    pad = -R % tile         # whole row tiles, as _grouped_matmul pads them
    if pad:
        rows, xs, ws, dys = (jnp.pad(a, ((0, pad), (0, 0)))
                             for a in (rows, xs, ws, dys))
        kept = kept.at[-1].add(pad)
    interpret = jax.default_backend() != "tpu"
    # a product's cotangent back through its weights (E, K, N), transposed, at
    # the forward product's tiles, as megablox's VJP has it
    back = lambda da, w: jnp.where(rows, backend.gmm(
        da, w, kept, xs.dtype, (tile, *(min(n, 1024) for n in w.shape[1:])),
        transpose_rhs=True, interpret=interpret), 0)
    add = lambda a, b, sums: tgmm_add(
        a, b, kept, sums, tiling=(tile, *_TGMM_TILES), interpret=interpret)
    w_gate, w_up, w_down = w
    gate, up = (jnp.where(rows, _grouped_matmul(xs, a, kept, kernel), 0)
                for a in (w_gate, w_up))
    act = jax.nn.silu(gate) * up
    hs = jnp.where(rows, act * ws, 0).astype(xs.dtype)
    dhs = f32(back(dys, w_down))
    dws = jnp.sum(dhs * f32(act), axis=-1, keepdims=True).astype(ws.dtype)
    dact, g, sig = dhs * ws, f32(gate), jax.nn.sigmoid(f32(gate))
    dup = (dact * g * sig).astype(xs.dtype)
    dgate = (dact * f32(up) * sig * (1 + g * (1 - sig))).astype(xs.dtype)
    dxs = back(dgate, w_gate) + back(dup, w_up)
    dw = (add(xs, dgate, dw[0]), add(xs, dup, dw[1]), add(hs, dys, dw[2]))
    return dxs[:R], dws[:R], dw


def ep_pass_rows(cfg: Config, n_tokens: int, ep: int) -> int:
    """Rows a peer of the FIRST pass of :func:`_ep_experts` for a rank's
    ``n_tokens`` tokens on ``ep`` ranks: the rows uniform routing sends one
    rank's experts from one rank's tokens (k * T / ep), in whole tiles of 16,
    and never more than the units one rank's experts can be sent (a token
    meets an expert once).  The units of a pair of ranks that it does not
    hold go in overflow passes of :func:`ep_overflow_rows` rows a peer.  The
    two sizes decide time and memory, never the result: a pair of ranks with
    more units takes another overflow pass.  A pass costs what its buffers
    hold, filled or not (the all-to-all and the gathers move ep times its
    rows), so a step's time moves in whole overflow passes.  The fullest pair
    is always above the share (at seeded weights 1.11 to 1.16 of it in the
    Mellum2 cell, one overflow pass a layer), so a first pass of the share is
    full on the fullest pair and about 0.8 full on the mean one, and what is
    left is a small multiple of a quarter.
    What was read on the way here (PERF.md section 6, PRs 44 to 46, Mellum2's
    layer: 131,072 routed units a rank, 2304 wide): every pass the share,
    two a layer, moved 2.0 shares where the routing needs 1.1; every pass half
    the share, three or four a layer as the routing falls, a batch with one
    pass more 5% longer; a first pass that covers the fullest pair has to
    pass it in every layer of every step, or that step takes a second pass of
    the larger buffers."""
    k = cfg.expert_top_k
    most = min(k, cfg.n_experts // ep) * n_tokens
    return -(-min(-(-k * n_tokens // ep), most) // 16) * 16


def ep_overflow_rows(cfg: Config, n_tokens: int, ep: int) -> int:
    """Rows a peer of each pass of :func:`_ep_experts` after the first: a
    quarter of :func:`ep_pass_rows`, in whole tiles of 16 (Mellum2's layer:
    8,192 rows a peer, 512 a held expert on average, one row tile of the
    grouped matmul)."""
    return -(-ep_pass_rows(cfg, n_tokens, ep) // 64) * 16


def ep_grad_plan(cfg: Config, n_tokens: int, ep: int, kernel: bool = True,
                 itemsize: int = 2) -> Dict[str, int]:
    """The account of how :func:`_ep_experts_bwd` sums the held experts'
    weight gradients, for one rank's ``n_tokens`` tokens and one layer on
    ``ep`` ranks: the rows of a block of the experts, the blocks of the first
    pass and of each overflow pass, the weight-gradient products of a block
    that add into the float32 sums inside their kernel
    (:func:`_held_swiglu_bwd`), and the bytes a block's summing moves outside
    a kernel: none, where before each of the three products wrote every held
    expert's gradient in the compute dtype (``itemsize`` bytes) and an XLA
    pass read it and read and wrote the float32 sum, as it still does with
    ``kernel`` False.  Static, from shapes alone (a test pins it against the
    jaxpr); no metric reads it."""
    share, overflow = (ep_pass_rows(cfg, n_tokens, ep),
                       ep_overflow_rows(cfg, n_tokens, ep))
    B = math.gcd(share, overflow)
    a_sum = (cfg.n_experts // ep) * cfg.d_model * cfg.d_ff
    return {"block_rows": B,
            "first_pass_blocks": ep * share // B,
            "overflow_pass_blocks": ep * overflow // B,
            "added_in_place_a_block": 3 if kernel else 0,
            "summed_outside_bytes_a_block":
                0 if kernel else 3 * (2 * 4 + itemsize) * a_sum}


def _ep_pass(k, R, n_tokens, order, first, sent, lo):
    """One pass of :func:`_ep_experts` on the sending side: for each rank,
    rows ``lo`` to ``lo + R`` of the units that go to its experts, in sorted
    order.  Returns ``(token, unit)``, (ranks, R) each: ``n_tokens`` and
    ``n_tokens * k``, which no gather or scatter reaches, where a row is past
    the units that rank is sent."""
    at = lo + jnp.arange(R)
    valid = at < jnp.sum(sent, axis=1)[:, None]
    unit = jnp.where(valid, order.at[first[:, None] + at].get(mode="clip"),
                     n_tokens * k)
    return jnp.where(valid, unit // k, n_tokens), unit


def _ep_arrived(R, arrived, lo):
    """Rows ``lo`` to ``lo + R`` on the receiving side, from the units
    ``arrived`` (ranks, experts held) each rank sends in all: the valid rows
    of each rank's block as a column (ranks, R, 1), and each held expert's
    rows in it."""
    ends = jnp.cumsum(arrived, axis=1)
    kept = jnp.clip(ends - lo, 0, R) - jnp.clip(ends - arrived - lo, 0, R)
    return (jnp.arange(R) < jnp.sum(kept, axis=1)[:, None])[..., None], kept


def _ep_blocks(R, B, arrived, lo):
    """:func:`_ep_arrived` of a pass of ``R`` rows from row ``lo``, cut into
    blocks of ``B`` rows for the experts: ``(valid, kept)``, (ranks * R / B,
    B, 1) and (ranks * R / B, experts held), a rank's blocks one after the
    other as its rows lie."""
    valid, kept = jax.vmap(lambda at: _ep_arrived(B, arrived, at),
                           out_axes=1)(lo + B * jnp.arange(R // B))
    return valid.reshape(-1, B, 1), kept.reshape(-1, kept.shape[-1])


def _ep_passes(rows, passes, one_pass, carry):
    """``carry`` through the passes of :func:`_ep_experts`, forward or
    backward: ``one_pass(R, lo, carry)`` once at ``rows[0]`` rows a peer from
    row 0, then at ``rows[1]`` from where the pass before ended, ``passes``
    times in all.  Two loops, the first of at most one pass: the bodies of a
    program's ``while``s share the lowering of what both call (the grouped
    matmuls, eight functions for fifteen), which a ``cond``'s branch and a
    ``while``'s body do not."""
    share, overflow = rows
    carry = lax.fori_loop(0, jnp.minimum(passes, 1),
                          lambda _, carry: one_pass(share, 0, carry), carry)
    return lax.fori_loop(
        1, passes, lambda p, carry: one_pass(
            overflow, share + (p - 1) * overflow, carry), carry)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _ep_experts(k, rows, kernel, axis, xt, wflat, order, plan, w):
    """The routed experts, sharded over the mesh axis ``axis``, on this
    rank's tokens ``xt`` (T, D) -> (T, D), inside a ``shard_map``: the sum,
    for each token, of its k units, each unit its expert's SwiGLU times the
    router's weight ``wflat`` (T * k, float32), whichever rank holds the
    expert; and with it ``delivered`` (ranks,) int32, the rows of each rank
    that the passes carried here and ran.  ``order`` lists this rank's units
    by expert (so by rank: rank r holds experts r * E / ep and on), ``rows``
    is the pair of a pass's rows a peer, the first pass's and each later
    one's (:func:`ep_pass_rows`, :func:`ep_overflow_rows`), ``plan`` is
    ``parallel.moe.pass_plan`` of the units' counts at ``rows``, ``w`` is
    ``(w_gate, w_up, w_down)`` of the experts held here.

    A pass gathers its rows for each rank (tokens' rows and router weights),
    sends each block to its rank and receives one from each
    (``parallel.moe.exchange``: one all-to-all of (ranks, R, D)), runs the
    held experts on what arrived (:func:`_held_swiglu`: a source's rows lie
    by expert, ``arrived`` says how many each), sends the results back the
    same way and adds them to their tokens in float32 (XLA's ``at[].add``
    here, forward and backward: no cell runs this form, so :func:`_add_rows`'
    kernel, which would take a peer's block one expert's segment at a time,
    was not wired in and not measured).  The first pass takes
    the uniform share from every pair of ranks; what a pair has above it goes
    in overflow passes a quarter that size, as many as the fullest pair of
    ranks needs, the same number on every rank (``parallel.moe.pass_plan``;
    :func:`_ep_passes`): the shapes are static, memory is a first pass's, the
    rows moved are the share and the overflow in whole quarters, and no unit
    is dropped under any imbalance.  The experts take either pass's rows in
    blocks of the two sizes' greatest common divisor, the overflow's wherever
    a quarter of the share is whole tiles (:func:`_ep_blocks`; the row tiles
    a grouped matmul visits are the same), so a program holds ONE set of
    grouped-matmul shapes.  A loop whose length the data decides has
    no transpose, so the gradient is written out (:func:`_ep_experts_bwd`): it
    keeps the inputs alone and takes the same passes, the rows and the
    results' cotangents out, the rows' and the router weights' cotangents
    back, each block through :func:`_held_swiglu_bwd` (the VJP of
    :func:`_held_swiglu` written out), which adds the block's weight
    gradients, in float32 and inside the kernels that form them, into the sums
    the loops carry; rounded to the weights' dtype once, after the passes, and
    left on the rank that holds the experts (:func:`ep_grad_plan` is the
    account of it).

    The forward and the backward pass are each a ``jax.jit`` of their own
    (``k``, ``rows``, ``kernel`` and ``axis`` static): every expert layer of
    a stack has the same shapes, so a program traces and lowers the four
    bodies (forward and backward, the share's and the overflow's) once and
    every layer, and every replay of one under ``remat``, calls them
    (``tests/test_mellum2_passes.py``: a body is traced once a shape).  Staged
    once a layer, the second size cost 9.6 s of set-up in the Mellum2 cell,
    more than its bound (PERF.md section 6, PRs 45 and 46).  What they call
    is read when they are traced: a test that patches :func:`_ep_pass` or
    :func:`_ep_arrived` clears JAX's caches round its patch.

    ``delivered`` is counted where the rows move, not read from the plan: a
    sender counts the rows of each block that its gather filled, a receiver
    the rows of each block that its experts ran, both over the passes of
    both sizes that ran; the senders' counts follow their rows (one small
    exchange after the loop) and a block delivered the lesser of the two.
    With too few passes, or a mask on either side that leaves rows out,
    ``delivered`` falls short of the routers' counts: that is how a caller
    sees a dropped unit.

    :func:`_held_experts` is the same passes without an exchange, and the two
    share what a pass computes (:func:`_held_swiglu`, :func:`_grouped_matmul`)
    but not the loop, nor the block's backward (its one block a pass goes
    through autodiff: a sum of one term has no pass over sums to save): a
    chip's share runs on one device under no ``shard_map``, so it has no axis
    to exchange over; it leaves the units of
    the experts it lacks out where this sends every unit somewhere; it has one
    block where this has one a source; and its passes are counted from its own
    units where these are the fullest pair's over the axis.  One loop for
    both would change the compiled steps of the three accepted cells that
    hold a share (``experts_held``), which this form's arrival left to the
    byte: ROADMAP.md R6 has it as a change of its own, measured in those
    cells."""
    return _ep_experts_fwd(k, rows, kernel, axis, xt, wflat, order, plan, w)[0]


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _ep_experts_fwd(k, rows, kernel, axis, xt, wflat, order, plan, w):
    (T, D), B = xt.shape, math.gcd(*rows)
    sent, arrived, first, passes = plan[:4]

    def one_pass(R, lo, carry):
        y, filled, ran = carry
        with jax.named_scope("moe.dispatch"):
            token, unit = _ep_pass(k, R, T, order, first, sent, lo)
            xs = xt.at[token].get(mode="fill", fill_value=0)
            ws = wflat.at[unit].get(mode="fill", fill_value=0)
        xs, ws = _exchange(xs, axis), _exchange(ws, axis)
        valid, kept = _ep_blocks(R, B, arrived, lo)
        with jax.named_scope("moe.experts"):
            ys = lax.map(lambda block: _held_swiglu(kernel, *block, *w),
                         (valid, kept, xs.reshape(-1, B, D),
                          ws.reshape(-1, B, 1)))
        ys = _exchange(ys.reshape(xs.shape), axis)
        with jax.named_scope("moe.combine"):
            return (y.at[token].add(ys.astype(jnp.float32), mode="drop"),
                    filled + jnp.sum(token < T, axis=1, dtype=jnp.int32),
                    ran + jnp.sum(valid.reshape(-1, R), axis=1,
                                  dtype=jnp.int32))

    none = jnp.zeros(sent.shape[:1], jnp.int32)
    y, filled, ran = _ep_passes(
        rows, passes, one_pass, (jnp.zeros(xt.shape, jnp.float32), none, none))
    delivered = jnp.minimum(_exchange(filled, axis), ran)
    return (y.astype(xt.dtype), delivered), (xt, wflat, order, plan, w)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _ep_experts_bwd(k, rows, kernel, axis, saved, given):
    dy, _ = given
    xt, wflat, order, plan, w = saved
    (T, D), B = xt.shape, math.gcd(*rows)
    f32 = lambda a: a.astype(jnp.float32)
    sent, arrived, first, passes = plan[:4]

    def one_pass(R, lo, grads):
        dxt, dwflat, dw = grads
        with jax.named_scope("moe.dispatch"):
            token, unit = _ep_pass(k, R, T, order, first, sent, lo)
            xs = xt.at[token].get(mode="fill", fill_value=0)
            ws = wflat.at[unit].get(mode="fill", fill_value=0)
        with jax.named_scope("moe.combine"):
            dys = dy.at[token].get(mode="fill", fill_value=0)
        xs, ws, dys = (_exchange(a, axis) for a in (xs, ws, dys))

        def block(dw, given):
            valid, kept, xs, ws, dys = given
            dxs, dws, dw = _held_swiglu_bwd(kernel, valid, kept, xs, ws, w,
                                            dys, dw)
            return dw, (dxs, dws)

        with jax.named_scope("moe.experts"):
            dw, (dxs, dws) = lax.scan(
                block, dw, (*_ep_blocks(R, B, arrived, lo),
                            xs.reshape(-1, B, D), ws.reshape(-1, B, 1),
                            dys.reshape(-1, B, D)))
        dxs = _exchange(dxs.reshape(xs.shape), axis)
        dws = _exchange(dws.reshape(ws.shape), axis)
        with jax.named_scope("moe.dispatch"):
            return (dxt.at[token].add(f32(dxs), mode="drop"),
                    dwflat.at[unit].add(dws, mode="drop"), dw)

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    dxt, dwflat, dw = _ep_passes(
        rows, passes, one_pass,
        (zeros(xt), zeros(wflat), tuple(zeros(a) for a in w)))
    none = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dxt.astype(xt.dtype), dwflat.astype(wflat.dtype), none(order),
            jax.tree.map(none, plan),
            tuple(g.astype(a.dtype) for g, a in zip(dw, w)))


_ep_experts.defvjp(_ep_experts_fwd, _ep_experts_bwd)


def _ep_form(cfg: Config, ep: int) -> str:
    """What the expert layer moves over an ``ep`` axis of ``ep`` ranks
    (:func:`_moe_ffn_ep`): ``"tokens"`` where the axis is no wider than the
    choices a token (``ep <= expert_top_k``), ``"units"`` past it.  A gather
    sends a token's row to the ``ep - 1`` other ranks once; the unit exchange
    sends ``k * (ep - 1) / ep`` rows a token, and in whole passes a pair of
    ranks.  Up to ``ep`` = k the gather sends fewer rows (Mellum2's 8 choices
    over 4 ranks: 3 for 6, and the overflow passes besides); past it it sends
    rows that no expert of the rank wants, and every rank holds ``ep`` times
    its tokens.  Read from the mesh and the configuration, nothing else: one
    algorithm (dropless passes over the held experts) with the rows brought
    either way."""
    return "tokens" if ep <= cfg.expert_top_k else "units"


def ep_token_pass_rows(cfg: Config, n_tokens: int, ep: int) -> int:
    """Rows of one pass of :func:`_ep_gathered` for a rank's ``n_tokens``
    tokens on ``ep`` ranks: the rows ONE held expert draws from the
    ``ep * n_tokens`` gathered tokens under uniform routing (k * ep * T / E),
    in whole tiles of 16; Mellum2's layer: 8,192, sixteen row tiles of the
    grouped matmul, one to three experts a pass.  It decides time and memory,
    never the result: a rank takes as many passes as the rows that arrived
    fill (what arrived and at most one pass's rows more), a fuller rank more
    than the others.  On the chip twice and four times these rows a pass ran
    24% and 11% SLOWER (PERF.md section 6, PR 50): that was XLA's lowering of
    the passes' float32 scatter-adds, 285 ns a row at this pass alone and
    more at a larger one, and not the gathers (55 ns a row).  Since PR 52 a
    Pallas kernel adds a pass's rows (:func:`_add_rows`), at a cost a row
    that does not follow the pass's size (a visit is a row tile of one
    expert's segment); other sizes are untried with it (PERF.md section 6,
    PR 52)."""
    return -(-cfg.expert_top_k * ep * n_tokens // (16 * cfg.n_experts)) * 16


def ep_exchange_plan(cfg: Config, n_tokens: int, ep: int,
                     itemsize: int = 2) -> Dict[str, Any]:
    """The account of what :func:`_moe_ffn_ep` moves over ``ep`` ranks for
    one rank's ``n_tokens`` tokens and one layer: the ``form``
    (:func:`_ep_form`), the rows of d_model numbers of ``itemsize`` bytes
    that LEAVE a rank forward and backward (``rows_forward``,
    ``rows_backward``; ``bytes_*`` their bytes), the rows the held experts
    take a pass (``pass_rows``) and a grouped matmul sees (``block_rows``).

    ``"tokens"``: forward the gather of the rows and the exchange of the
    partial sums, backward the gathers of the rows and of the result's
    cotangent and the exchange of the rows' cotangents, each ``(ep - 1) * T``
    rows; no pass moves a row between ranks, so an overflow costs no row
    (``rows_*_overflow`` 0).  ``"units"``: the FIRST pass's two exchanges
    forward and three backward, ``(ep - 1) * ep_pass_rows`` rows each, and
    what each overflow pass adds (``rows_*_overflow``, at
    :func:`ep_overflow_rows`).  The choices, the router's weights and the
    counts also cross (4 bytes a unit and less): not counted.  Static, from
    shapes alone (``tests/test_ep_tokens.py`` pins it against the collectives
    of the layer's jaxpr); the passes a rank took are data:
    :func:`ep_pass_counts`.  No metric reads it."""
    T, form = n_tokens, _ep_form(cfg, ep)
    if form == "tokens":
        first = over = ep_token_pass_rows(cfg, T, ep)
        block, sent, sent_over = first, (ep - 1) * T, 0
    else:
        share, overflow = (ep_pass_rows(cfg, T, ep),
                           ep_overflow_rows(cfg, T, ep))
        first, over, block = ep * share, ep * overflow, math.gcd(share,
                                                                 overflow)
        sent, sent_over = (ep - 1) * share, (ep - 1) * overflow
    row = cfg.d_model * itemsize
    return {"form": form, "pass_rows": first, "overflow_pass_rows": over,
            "block_rows": block,
            "rows_forward": 2 * sent, "rows_backward": 3 * sent,
            "rows_forward_overflow": 2 * sent_over,
            "rows_backward_overflow": 3 * sent_over,
            "bytes_forward": 2 * sent * row, "bytes_backward": 3 * sent * row}


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _ep_gathered(k, R, kernel, axis, xt, wflat, order, arrived, w):
    """The routed experts, sharded over the mesh axis ``axis``, on this
    rank's tokens ``xt`` (T, D) -> (T, D), inside a ``shard_map``, with the
    TOKENS brought to the experts (:func:`_ep_form`): the same sum as
    :func:`_ep_experts`, each token's k units through their experts' SwiGLUs
    times the router's weights ``wflat`` (T * k, float32), and with it
    ``delivered`` (ranks,) int32, the units of each rank's tokens that ran
    here.  ``order`` lists the units of ALL the ranks' tokens (unit u of the
    gathered tokens: token u // k, rank u // (k * T)), those of the experts
    held here first and by expert, so by source rank and token within one
    (padded to whole passes), ``arrived`` counts them by held expert, ``w``
    is ``(w_gate, w_up, w_down)`` of the experts held here.

    Every rank's rows are gathered over the axis once, (ranks * T, D), and
    the router's weights with them.  Then the passes of
    :func:`_held_experts`, on what this rank has now: a pass takes ``R`` rows
    of the order (:func:`ep_token_pass_rows`), gathers them from the gathered
    tokens, runs the held experts (:func:`_held_swiglu`) and adds the results
    to this rank's PARTIAL sums (ranks * T, D) in float32 (:func:`_add_rows`:
    the Pallas scatter-add of ``ops/scatter_add_rows.py``, by expert's
    segment, in place; XLA's own took a third of the cell's step, PERF.md
    section 6, PR 52); as many passes as
    the units that arrived HERE fill, for no collective stands inside the
    loop and the ranks need not agree, so none is dropped under any
    imbalance.  The partial sums, rounded once to the rows' dtype, go home
    (``parallel.moe.exchange``: block s to rank s) and a token's partials are
    summed in float32.  Against :func:`_ep_experts`: a row leaves its rank
    ``ranks - 1`` times where its k units left ``k * (ranks - 1) / ranks``
    times in whole passes a pair of ranks, and the experts see their rows in
    ONE order by expert, a pass of the mean expert's rows meeting one to three
    experts where a per-source block met all that are held (megablox's
    ``gmm`` visits a row tile once for each expert in it).

    The gradient is written out, for a loop whose length the data decides
    has no transpose (:func:`_ep_gathered_bwd`): it keeps the inputs alone;
    the rows are gathered again and the result's cotangent as they were; the
    same passes run on the same order, each through :func:`_held_swiglu_bwd`,
    which adds the pass's weight gradients into the float32 sums the loop
    carries, inside the kernels that form them; the rows' cotangents
    (ranks * T, D), summed over a rank's units in float32 by the same
    scatter-add kernel (:func:`_add_rows`) and rounded once,
    go home by the exchange and are summed in float32 (the gather's own
    transpose would be a reduce-scatter in the rows' dtype), the router
    weights' likewise in float32.  The experts' weight gradients stay on
    their rank.

    The forward and the backward pass are each a ``jax.jit`` of their own, as
    :func:`_ep_experts`' are and for its reason: a stack's expert layers
    share one trace of each loop body, two bodies a program and ONE set of
    grouped-matmul shapes.

    ``delivered`` is counted in the passes: each row a pass ran, by the rank
    its token came from (its index over T).  With too few passes, or a mask
    that leaves rows out, it falls short of the routers' counts."""
    return _ep_gathered_fwd(k, R, kernel, axis, xt, wflat, order, arrived,
                            w)[0]


def _gathered(rows, axis):
    """Every rank's ``rows``, rank after rank, under scope
    ``moe.exchange``."""
    with jax.named_scope("moe.exchange"):
        return lax.all_gather(rows, axis, tiled=True)


def _summed_home(partials, ranks, axis):
    """``partials`` (ranks * n, ...), this rank's part of every rank's rows
    -> (n, ...) float32: block s goes to rank s and the blocks that arrive,
    every rank's part of this rank's rows, are summed in float32."""
    home = _exchange(partials.reshape(ranks, -1, *partials.shape[1:]), axis)
    with jax.named_scope("moe.combine"):
        return jnp.sum(home, axis=0, dtype=jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _ep_gathered_fwd(k, R, kernel, axis, xt, wflat, order, arrived, w):
    T = xt.shape[0]
    xg, wg = (_gathered(a, axis) for a in (xt, wflat))
    p = xg.shape[0] // T

    def one_pass(i, carry):
        y, ran = carry
        with jax.named_scope("moe.dispatch"):
            token, _, rows, xs, ws, kept = _held_pass(k, R, xg, wg, order,
                                                      arrived, i)
        with jax.named_scope("moe.experts"):
            ys = _held_swiglu(kernel, rows, kept, xs, ws, *w)
        with jax.named_scope("moe.combine"):
            return (_add_rows(kernel, y, token, ys, kept),
                    ran + jnp.sum(jax.nn.one_hot(token // T, p,
                                                 dtype=jnp.int32), axis=0))

    y, ran = lax.fori_loop(
        0, -(-jnp.sum(arrived) // R), one_pass,
        (_row_sums(xg), jnp.zeros((p,), jnp.int32)))
    y = _summed_home(y[:, 0].astype(xt.dtype), p, axis)
    return (y.astype(xt.dtype), ran), (xt, wflat, order, arrived, w)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _ep_gathered_bwd(k, R, kernel, axis, saved, given):
    dy, _ = given
    xt, wflat, order, arrived, w = saved
    T = xt.shape[0]
    xg, wg, dyg = (_gathered(a, axis) for a in (xt, wflat, dy))
    p = xg.shape[0] // T

    def one_pass(i, grads):
        dxg, dwg, dw = grads
        with jax.named_scope("moe.dispatch"):
            token, unit, rows, xs, ws, kept = _held_pass(k, R, xg, wg, order,
                                                         arrived, i)
        with jax.named_scope("moe.combine"):
            dys = dyg.at[token].get(mode="fill", fill_value=0)
        with jax.named_scope("moe.experts"):
            dxs, dws, dw = _held_swiglu_bwd(kernel, rows, kept, xs, ws, w,
                                            dys, dw)
        with jax.named_scope("moe.dispatch"):
            return (_add_rows(kernel, dxg, token, dxs, kept),
                    dwg.at[unit].add(dws[:, 0], mode="drop"), dw)

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    dxg, dwg, dw = lax.fori_loop(
        0, -(-jnp.sum(arrived) // R), one_pass,
        (_row_sums(xg), zeros(wg), tuple(zeros(a) for a in w)))
    dxt = _summed_home(dxg[:, 0].astype(xt.dtype), p, axis)
    dwflat = _summed_home(dwg, p, axis)
    none = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dxt.astype(xt.dtype), dwflat.astype(wflat.dtype), none(order),
            none(arrived), tuple(g.astype(a.dtype) for g, a in zip(dw, w)))


_ep_gathered.defvjp(_ep_gathered_fwd, _ep_gathered_bwd)


def _moe_ffn_ep(cfg: Config, lp: Params, x: jax.Array, mesh: Mesh):
    """:func:`_moe_ffn_sorted` on a mesh with an ``ep`` axis, x (B, L, D) ->
    ``(out, (aux, delivered))``.  The batch's rows are sharded over ``dp`` and
    ``ep`` (:func:`_batch_axes`) and each rank holds ``n_experts / ep``
    experts, a contiguous range, whole.  In one ``shard_map`` over the axes
    that share the tokens each rank routes its own over all the experts
    (:func:`_route_tokens`, the auxiliary terms' means over the ranks too).
    Then the rows meet the held experts, dropless passes either way, brought
    as the shapes say (:func:`_ep_form`).  On an axis no wider than the
    choices a token (``ep <= expert_top_k``; Mellum2: 4 <= 8) the TOKENS move:
    every rank's choices are gathered, the units of all the ranks' tokens
    whose expert is held here are sorted by expert, and :func:`_ep_gathered`
    gathers the rows, runs the held experts on them and sends each rank the
    partial sums of its tokens.  Past that the UNITS move: a rank sorts its
    own by expert, so by the rank that holds it, and :func:`_ep_experts`
    sends every unit to its expert and brings the result back.  No capacity,
    no routing group, none dropped in either.  The router
    and whatever else every rank holds alike enter whole, so their gradients
    leave summed over the ranks; the experts' stay where the experts are.
    The grouped matmul is the Mosaic kernel where every axis of the mesh is
    one of the ``shard_map``'s, ``lax.ragged_dot`` where ``tp`` is left to
    GSPMD.  ``delivered`` (ep, ep) int32: the units of rank s that reached
    the experts of rank r and ran there, at [r, s], counted in the passes
    themselves (of either form); a layer's sum to ``k * B * L``, or a unit
    was dropped."""
    from jax import shard_map

    _refuse(cfg, "an ep axis")
    ep, k = _ep_ranks(cfg, mesh), cfg.expert_top_k
    if cfg.n_experts % ep:
        raise ValueError(f"{cfg.n_experts} experts do not divide over "
                         f"ep={ep}")
    sp = AXIS_SP if AXIS_SP in mesh.shape else None
    shared = tuple(a for a in (AXIS_DP, AXIS_EP, sp) if a in mesh.shape)
    kernel = set(shared) == set(mesh.axis_names)
    _rows_divide(cfg, mesh, x.shape[0])
    rows = _mesh_spec(P(_batch_axes(cfg, mesh), sp, None), mesh)
    routers = {name: lp[name] for name in ("router", "router_bias")
               if name in lp}

    def local(x, routers, w):
        B, L, D = x.shape
        T = B * L
        xt = x.reshape(T, D)
        with jax.named_scope("moe.router"):
            weight, expert, units, aux = _route_tokens(cfg, routers, xt,
                                                       shared)
        if _ep_form(cfg, ep) == "tokens":
            held, R = cfg.n_experts // ep, ep_token_pass_rows(cfg, T, ep)
            chosen = _gathered(expert.reshape(T * k), AXIS_EP)
            with jax.named_scope("moe.dispatch"):
                order, at = _held_order(
                    chosen, held * lax.axis_index(AXIS_EP), held, R)
                arrived = jnp.sum(jax.nn.one_hot(at, held, dtype=jnp.int32),
                                  axis=0)
            y, delivered = _ep_gathered(k, R, kernel, AXIS_EP, xt,
                                        weight.reshape(T * k), order, arrived,
                                        w)
        else:
            with jax.named_scope("moe.dispatch"):
                order = jnp.argsort(expert.reshape(T * k), stable=True)
            sizes = ep_pass_rows(cfg, T, ep), ep_overflow_rows(cfg, T, ep)
            plan = _pass_plan(units, sizes, AXIS_EP)
            y, delivered = _ep_experts(k, sizes, kernel, AXIS_EP, xt,
                                       weight.reshape(T * k), order, plan, w)
        if len(shared) > 1:
            delivered = lax.psum(delivered, tuple(
                a for a in shared if a != AXIS_EP))
        return y.reshape(B, L, D), aux, delivered[None]

    held = P(AXIS_EP, None, None)
    y, aux, delivered = shard_map(
        local, mesh=mesh, axis_names=set(shared), check_vma=False,
        in_specs=(rows, jax.tree.map(lambda _: P(), routers), (held,) * 3),
        out_specs=(rows, P(), P(AXIS_EP, None)))(
            x, routers, (lp["w_gate"], lp["w_up"], lp["w_down"]))
    B, L, D = x.shape
    y = _add_shared_expert(cfg, lp, x.reshape(B * L, D), y.reshape(B * L, D))
    return y.reshape(B, L, D), (aux, delivered)


def _qk_norm(cfg: Config, lp: Params, q: jax.Array, k: jax.Array):
    """QK-norm: RMSNorm over the whole q and k projections (..., H*hd) and
    (..., KV*hd), before the heads are split and rotated (OLMoE,
    arXiv:2409.02060 section 3).  The identity for other configurations."""
    if not cfg.qk_norm:
        return q, k
    with jax.named_scope("attn.qk_norm"):
        return (rms_norm(q, lp["q_norm"], cfg.norm_eps),
                rms_norm(k, lp["k_norm"], cfg.norm_eps))


def _kda_block(cfg: Config, lp: Params, x: jax.Array,
               mixer: Callable) -> jax.Array:
    """The KDA mixer on the normed input x (B, L, D).

    Here, as XLA's matmuls: the q, k and v projections, the low-rank pairs
    through ``head_dim`` of the decay (``W_f``) and of the gate (``W_g``,
    with its bias), the write strength ``beta = sigmoid(x W_b)`` in float32,
    and ``W_o``.  Their outputs are rounded to the compute type, and stay the
    only copies of the layer's activations that a pass reads: nothing is cast
    to float32, padded or laid out a head at a time on its way to the mixer.

    Between them ``mixer`` (:func:`_kda_sharded`'s for the mesh;
    ``ops.kda_mixer.kda_mixer``), on (B, L, H * head_dim) arrays throughout:
    the way in (q, k and v each through a short causal convolution and a
    SiLU, q and k L2-normalised over a head's channels, q scaled by
    ``head_dim ** -0.5``; the log-decay a head and channel ``g = -exp(a_log)
    * softplus(x W_f + dt_bias)``, float32), the recurrence (``ops.kda.kda``,
    scope ``kda``), the way out (the output normed a head by ``o_norm`` and
    gated by ``sigmoid(x W_g + b_g)``).

    Which form runs is read from the head width alone.  Where a head is a
    multiple of 128 channels wide the way in and the way out are a fused
    Pallas kernel each (``kda_pre``, ``kda_post``: every array read once and
    written once a pass) with a hand-written gradient (``kda_pre_bwd``,
    ``kda_post_bwd``) whose residuals are its inputs alone, so a remat
    policy keeps nothing more for them and forms them again in the backward
    pass; at any other width the ``jax.numpy`` form
    (``ops.kda_mixer.pre_plain``, ``post_plain``), which is also the tests'
    oracle.  The recurrence chooses between its own two forms likewise.

    On a mesh every step is a channel's or a head's own and the sequence is
    whole on each device: the filters, ``dt_bias`` and the gate's bias go
    with their channels over ``tp``, ``a_log`` with its heads, ``o_norm``
    whole to every device; the rings are refused."""
    f = (x @ lp["f_down"]) @ lp["f_up"]
    beta = jax.nn.sigmoid((x @ lp["wb"]).astype(jnp.float32))
    z = ((x @ lp["g_down"]) @ lp["g_up"]) + lp["g_bias"]
    o = mixer(x @ lp["wq"], x @ lp["wk"], x @ lp["wv"], f, beta, z,
              lp["conv_q"], lp["conv_k"], lp["conv_v"], lp["a_log"],
              lp["dt_bias"], lp["o_norm"])
    return o @ lp["wo"]


def _mla_block(cfg: Config, lp: Params, x: jax.Array, attn_impl: Callable,
               positions: Optional[jax.Array] = None) -> jax.Array:
    """The latent-attention mixer on the normed input x (B, L, D), as a
    training step runs it: q projected whole or, with ``cfg.q_lora_rank``,
    through a latent with a norm of its own; the keys' and values' latent
    and the key part all heads share from one projection; the latent normed
    and expanded to each head's keys and values; softmax attention with keys
    of ``qk_nope_head_dim + qk_rope_head_dim`` and values of ``v_head_dim``
    (``attn_impl``, made for that scale); ``W_o``.  With ``cfg.mla_rope`` the
    shared key part is rotated once, as one head, and each head's last
    ``qk_rope_head_dim`` query channels with it (:func:`rope` at
    ``positions``, 0 to L - 1 where not given, all of those channels);
    without, nothing is rotated."""
    B, L, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, shared, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim)
    with jax.named_scope("mla"):
        if cfg.q_lora_rank:
            q = rms_norm(x @ lp["wq_a"], lp["q_norm"],
                         cfg.norm_eps) @ lp["wq_b"]
        else:
            q = x @ lp["wq"]
        q = q.reshape(B, L, H, nope + shared)
        latent = x @ lp["wkv_a"]
        kv = (rms_norm(latent[..., :r], lp["kv_norm"], cfg.norm_eps)
              @ lp["wkv_b"]).reshape(B, L, H, nope + vd)
        k_n = kv[..., :nope]
        k_shared = latent[..., None, r:]                    # (B, L, 1, shared)
        if cfg.mla_rope:
            if positions is None:
                positions = jnp.arange(L)
            k_shared = rope(k_shared, positions, cfg.rope_theta)
            q = jnp.concatenate(
                [q[..., :nope], rope(q[..., nope:], positions,
                                     cfg.rope_theta)], axis=-1)
        k = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_shared, (B, L, H, shared))], axis=-1)
        o = attn_impl(q, k, kv[..., nope:])
        return o.reshape(B, L, H * vd) @ lp["wo"]


def _times(x: jax.Array, m: float) -> jax.Array:
    """x times a constant of the forward pass, in float32 (a constant
    rounded to bfloat16 is off by up to 0.4%, every element alike); x itself
    where the constant is 1."""
    return x if m == 1 else (x.astype(jnp.float32) * m).astype(x.dtype)


def _ssm_block(cfg: Config, lp: Params, x: jax.Array) -> jax.Array:
    """The Mamba-2 state-space branch on the normed input x (B, L, D), scope
    ``ssm``: ``ssm_in_multiplier`` on x; one projection to [gate z | x | B |
    C | dt], its five sections times ``ssm_multipliers``; a causal depthwise
    convolution with bias over [x | B | C], then SiLU (``ssm.conv``); ``dt =
    softplus(dt + dt_bias)`` and ``A = -exp(a_log)`` a head, float32; the
    scan (``ops.ssd.ssd``, scope ``ssd``: the heads of a group share B and
    C, the skip ``D x`` in it); the gated norm a group (``ssm.norm``); the
    way out.  The branch's output multiplier is the caller's, where the two
    branches meet."""
    from ..ops import ssd

    Bt, L, _ = x.shape
    H, G, N = cfg.ssm_heads, cfg.ssm_groups, cfg.ssm_state
    inner = H * cfg.ssm_head_dim
    with jax.named_scope("ssm"):
        proj = _times(x, cfg.ssm_in_multiplier) @ lp["ssm_in"]
        if set(cfg.ssm_multipliers) != {1}:
            scale = np.repeat(np.asarray(cfg.ssm_multipliers, np.float32),
                              (inner, inner, G * N, G * N, H))
            proj = (proj.astype(jnp.float32) * scale).astype(proj.dtype)
        z, dt = proj[..., :inner], proj[..., -H:]
        with jax.named_scope("ssm.conv"):
            xbc = ssd.conv_silu(proj[..., inner:-H], lp["ssm_conv"],
                                lp["ssm_conv_bias"])
        y = ssd.ssd(
            xbc[..., :inner].reshape(Bt, L, H, cfg.ssm_head_dim),
            jax.nn.softplus(dt.astype(jnp.float32) + lp["ssm_dt_bias"]),
            -jnp.exp(lp["ssm_a_log"]),
            xbc[..., inner:inner + G * N].reshape(Bt, L, G, N),
            xbc[..., inner + G * N:].reshape(Bt, L, G, N), lp["ssm_d"],
            chunk=cfg.ssm_chunk)
        with jax.named_scope("ssm.norm"):
            y = ssd.gated_norm(y.reshape(Bt, L, inner), z, lp["ssm_norm"], G,
                               cfg.norm_eps)
        return y @ lp["ssm_out"]


def _softmax_mixer(cfg: Config, lp: Params, x: jax.Array,
                   positions: jax.Array, attn_impl: Callable,
                   mixer: str = "attn"):
    """A softmax layer's mixer on the normed input x (B, L, D), for a caller
    inside the scope ``attn``: the projections (``attn_in_multiplier`` on x,
    ``key_multiplier`` on the keys before the rotation, each 1 but in a
    two-branch layer), QK-norm, the rotation of the layer's kind, the
    attention (``swa`` round a window layer's), the gate a head, the way
    out.  Returns it with the (pre-repeat, native-KV-head) keys and
    values."""
    B, L, _ = x.shape
    hd, H, KV = cfg.head_dim, softmax_heads(cfg, mixer), cfg.n_kv_heads
    rotate = _rotation(cfg, mixer)
    x = _times(x, cfg.attn_in_multiplier)
    q, k = _qk_norm(cfg, lp, x @ lp["wq"],
                    _times(x @ lp["wk"], cfg.key_multiplier))
    q = rotate(q.reshape(B, L, H, hd), positions)
    k = rotate(k.reshape(B, L, KV, hd), positions)
    v = (x @ lp["wv"]).reshape(B, L, KV, hd)
    if mixer == "swa":
        with jax.named_scope("swa"):
            o = attn_impl(q, k, v)
    else:
        o = attn_impl(q, k, v)
    if cfg.attn_gate:
        o = _gate_heads(o, x, lp["wg"])
    return o.reshape(B, L, H * hd) @ lp["wo"], (k, v)


def _two_branches(cfg: Config, lp: Params, h: jax.Array,
                  positions: jax.Array, attn_impl: Callable):
    """What an ``"attn+ssm"`` layer's two mixers add to the residual, each
    with its output multiplier on it, float32: ``(attention's, the
    state-space branch's)``, both of ONE normed input."""
    with jax.named_scope("attn"):
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        a, _ = _softmax_mixer(cfg, lp, x, positions, attn_impl)
    s = _ssm_block(cfg, lp, x)
    return (a.astype(jnp.float32) * cfg.attn_out_multiplier,
            s.astype(jnp.float32) * cfg.ssm_out_multiplier)


def _attention_block(cfg: Config, lp: Params, h: jax.Array,
                     positions: jax.Array, attn_impl: Callable,
                     constrain: Callable = lambda x: x,
                     with_kv: bool = False, mixer: str = "attn"):
    """The attention half of a decoder block: ``h`` plus the attention of
    its pre-norm (under ``cfg.sandwich_norm`` the branch's output is normed
    too, before the add); with ``with_kv`` also the (pre-repeat,
    native-KV-head) K/V projections.  ``mixer`` is the layer's kind:
    ``"attn"`` the softmax attention written here, ``"swa"`` the same body
    at a window layer's head count and rotation (``attn_impl`` then the one
    made for its window), ``"kda"`` :func:`_kda_block` (``attn_impl`` then
    the recurrence), ``"mla"`` :func:`_mla_block` (``attn_impl`` then the one
    made for its scale), ``"attn+ssm"`` :func:`_two_branches` (``attn_impl``
    the full layers'; the state-space branch beside it needs none)."""
    # Names in the device program (docs/observability.md): ``attn`` (the
    # projections, ``attn.qk_norm``, rope, the attention itself, the output
    # projection; in it ``kda``, the chunked recurrence alone, ``mla``,
    # the whole latent mixer, ``swa``, a window layer's attention alone, or
    # ``attn.gate``, the gate on the heads' outputs), ``ssm`` BESIDE it in a
    # two-branch layer (in it ``ssm.conv``, ``ssd``, ``ssm.norm``),
    # ``moe.router``/
    # ``moe.dispatch``/
    # ``moe.experts``/``moe.combine``/``moe.shared`` or ``ffn``, ``embed``,
    # ``final_norm``, ``exit_gate``, ``head_loss``, ``optimizer``; ``mtp``,
    # outermost, round a multi-token-prediction module's copy of those.
    # Metadata only.
    if mixer in ("kda", "mla"):
        with jax.named_scope("attn"):
            x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
            o = (_kda_block(cfg, lp, x, attn_impl) if mixer == "kda"
                 else _mla_block(cfg, lp, x, attn_impl, positions))
            return h + constrain(o)
    if mixer == "attn+ssm":
        # ``ssm`` stands beside ``attn``, not in it; in it ``ssm.conv``,
        # ``ssd`` (the scan alone) and ``ssm.norm``.
        a, s = _two_branches(cfg, lp, h, positions, attn_impl)
        return h + constrain((a + s).astype(h.dtype))
    with jax.named_scope("attn"):
        x = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        o, (k, v) = _softmax_mixer(cfg, lp, x, positions, attn_impl, mixer)
        if cfg.sandwich_norm:
            o = rms_norm(o, lp["attn_post_norm"], cfg.norm_eps)
        h = h + constrain(o)
    return (h, (k, v)) if with_kv else h


@jax.named_scope("attn.gate")
def _gate_heads(o: jax.Array, x: jax.Array, wg: jax.Array) -> jax.Array:
    """The heads' outputs ``o`` (B, L, H, hd) each times its gate, ``sigmoid(x
    @ wg)`` of the normed layer input x (B, L, D), one number a head and
    token, float32 (the headwise gate of "Gated Attention for Large Language
    Models", arXiv:2505.06708)."""
    gate = jax.nn.sigmoid((x @ wg).astype(jnp.float32))
    return (o * gate[..., None]).astype(o.dtype)


def _aux_zero(cfg: Config, mesh: Optional[Mesh] = None):
    """What a layer without experts adds to the stack's aux sum: 0 (two for
    a configuration with a z-loss); where the expert layers exchange over
    ``ep`` (:func:`_moe_ffn_ep`) the pair of it and no unit delivered."""
    zero = jnp.zeros((2,) if cfg.n_experts and cfg.moe_z_coef else (),
                     jnp.float32)
    ep = _ep_ranks(cfg, mesh)
    return (zero, jnp.zeros((ep, ep), jnp.int32)) if ep > 1 else zero


def _dense_ffn(cfg: Config, lp: Params, x: jax.Array) -> jax.Array:
    """The dense SwiGLU of the normed input x, ``ffn_multipliers`` on the
    gate (inside the SiLU) and on the output."""
    on_gate, on_out = cfg.ffn_multipliers
    return _times((jax.nn.silu(_times(x @ lp["w_gate"], on_gate))
                   * (x @ lp["w_up"])) @ lp["w_down"], on_out)


def _ffn_block(cfg: Config, lp: Params, h: jax.Array,
               constrain: Callable = lambda x: x,
               mesh: Optional[Mesh] = None, ffn: Optional[str] = None):
    """The feed-forward half: ``h`` plus the SwiGLU or mixture-of-experts
    FFN of its pre-norm (normed again under ``cfg.sandwich_norm``), and the
    MoE aux term (0 for dense configs; :func:`_aux_zero`).  ``ffn`` is the
    layer's kind, ``"dense"`` or ``"moe"``; left None, what
    ``cfg.n_experts`` says."""
    x = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
    if ffn == "moe" or (ffn is None and cfg.n_experts):
        g, aux = _moe_ffn(cfg, lp, x, mesh=mesh)
    else:
        with jax.named_scope("ffn"):
            g = _dense_ffn(cfg, lp, x)
        aux = _aux_zero(cfg, mesh)
    if cfg.sandwich_norm:
        with jax.named_scope("ffn"):
            g = rms_norm(g, lp["mlp_post_norm"], cfg.norm_eps)
    return h + constrain(g), aux


def _decoder_layer(cfg: Config, lp: Params, h: jax.Array,
                   positions: jax.Array, attn_impl: Callable,
                   constrain: Callable = lambda x: x,
                   with_kv: bool = False, mesh: Optional[Mesh] = None,
                   mixer: str = "attn", ffn: Optional[str] = None):
    """One pre-norm decoder block (attention + SwiGLU-or-MoE FFN with
    residuals) — the single definition the scanned forward (:func:`apply`),
    the pipeline stages (:func:`make_pp_train_step`), and decode prefill
    run.  Returns ``(h, aux)`` where ``aux`` is the MoE load-balance term
    (0 for dense configs); with ``with_kv`` also returns the (pre-repeat,
    native-KV-head) K/V projections — the cache seed for autoregressive
    decoding.  ``mesh`` is the mesh the parameters live on, if any: the
    dropless expert layer runs its kernel on one device only.  ``mixer``
    and ``ffn`` are the layer's kinds in a stack that is not homogeneous
    (:func:`layer_runs`)."""
    h = _attention_block(cfg, lp, h, positions, attn_impl, constrain, with_kv,
                         mixer)
    if with_kv:
        h, kv = h
        return (*_ffn_block(cfg, lp, h, constrain, mesh, ffn), kv)
    return _ffn_block(cfg, lp, h, constrain, mesh, ffn)


def _chunk_nll(head, h_c, t_c):
    """One (B, C, D) chunk: its tokens' NLL (B, C), its (B, C, V) f32 logits,
    their log-sum-exp and where the targets are among them.  The target's
    logit is a masked sum (one term, so exact), which the compiler takes in
    the pass that sums the exponentials; a gather would have the chunk's
    logits written out in float32 for it."""
    logits = (h_c @ head).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = t_c[..., None] == lax.broadcasted_iota(t_c.dtype, logits.shape, 2)
    tgt = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    return lse - tgt, logits, lse, hit


def _chunk_at(arrays, idx, C):
    return [lax.dynamic_slice_in_dim(a, idx * C, C, axis=1) for a in arrays]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunked_nll(head, h, targets, weights, C):
    """Mean NLL over ``L // C`` sequence chunks, one ``h_c @ head`` a chunk.
    Under differentiation the chunk that forms the logits takes the head's
    gradients too (:func:`_chunked_nll_fwd`): three products over the
    vocabulary a chunk, where a checkpointed chunk would form its logits
    again in the backward pass and run four.

    With ``weights`` (B, L) float32 the result is ``sum(weights * nll)``, not
    the mean: the weights carry the normalisation.  A looped model
    (``cfg.ut_steps`` > 1) hands the states of all its recurrent steps as
    rows of one ``h`` and each token's exit probability over the token count
    as its weight.  The weights are an input of the chunk's forward pass, so
    its gradient products take them there and still no chunk's logits are
    formed twice; the gradient of the weights is the tokens' NLL, which the
    forward pass keeps, (B, L) float32."""
    B, L, _ = h.shape
    rows = (h, targets) if weights is None else (h, targets, weights)

    def step(acc, idx):
        h_c, t_c, *w_c = _chunk_at(rows, idx, C)
        nll = _chunk_nll(head, h_c, t_c)[0]
        return acc + jnp.sum(nll * w_c[0] if w_c else nll), None

    total, _ = lax.scan(step, jnp.zeros((), jnp.float32), jnp.arange(L // C))
    return total / (B * L) if weights is None else total


def _chunked_nll_fwd(head, h, targets, weights, C):
    B, L, _ = h.shape
    # The dtype the logits are formed in, so the dtype their cotangent has.
    dtype = jnp.result_type(h.dtype, head.dtype)
    rows = (h, targets) if weights is None else (h, targets, weights)

    def step(carry, idx):
        acc, dh, dw = carry
        h_c, t_c, *w_c = _chunk_at(rows, idx, C)
        nll, logits, lse, hit = _chunk_nll(head, h_c, t_c)
        dl = jnp.exp(logits - lse[..., None]) - hit
        dl = (dl * w_c[0][..., None] if w_c else dl / (B * L)).astype(dtype)
        dh_c = (dl @ head.T).astype(h.dtype)
        dw = dw + jnp.einsum("bcd,bcv->dv", h_c, dl).astype(dw.dtype)
        dh = lax.dynamic_update_slice_in_dim(dh, dh_c, idx * C, axis=1)
        return ((acc + jnp.sum(nll * w_c[0] if w_c else nll), dh, dw),
                nll if w_c else None)

    (total, dh, dw), nll = lax.scan(
        step, (jnp.zeros((), jnp.float32), jnp.zeros_like(h),
               jnp.zeros_like(head)), jnp.arange(L // C))
    # The residuals are the gradients themselves, (B, L, D) and (D, V), and
    # with weights the tokens' NLL: the (B, C, V) logits never leave their
    # chunk.
    if weights is None:
        return total / (B * L), (dh, dw, targets, None)
    return total, (dh, dw, targets, jnp.moveaxis(nll, 0, 1).reshape(B, L))


def _chunked_nll_bwd(C, saved, g):
    dh, dw, targets, nll = saved
    scale = lambda a: (a.astype(jnp.float32) * g).astype(a.dtype)
    return (scale(dw), scale(dh), np.zeros(targets.shape, jax.dtypes.float0),
            None if nll is None else nll * g)


_chunked_nll.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


@jax.named_scope("head_loss")
def _nll_from_hidden(head: jax.Array, h: jax.Array, targets: jax.Array,
                     loss_chunk: int,
                     weights: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token NLL from final (post-norm) hidden states — the one
    place the output head is applied, dense or sequence-chunked (the
    memory-critical path: chunking caps the live (B, C, V) f32 logits).
    With ``weights`` (B, L) float32, ``sum(weights * nll)`` over the tokens
    (:func:`_chunked_nll`)."""
    if not loss_chunk:
        logits = (h @ head).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return (-jnp.mean(picked) if weights is None
                else -jnp.sum(weights * picked))
    L, C = h.shape[1], int(loss_chunk)
    if L % C:
        raise ValueError(f"seq len {L} not divisible by loss_chunk {C}")
    return _chunked_nll(head, h, targets, weights, C)



def _traits(cfg: Config) -> Dict[str, str]:
    """What ``cfg`` is beyond one stack of identical full-attention layers
    passed once, ``{trait: what to call it, with the field values that say
    so}``: the one place each trait's predicate is written.  Every consumer of
    a configuration takes the plain stack; what it takes of these is
    ``_LACKS``'s to say, so a new kind of configuration adds a predicate here
    and its rows there."""
    called = lambda what, *fields: "{} ({})".format(
        what, ", ".join(f"{name}={getattr(cfg, name)}" for name in fields))
    found = {}
    if cfg.ut_steps > 1 or cfg.sandwich_norm or cfg.exit_gate:
        found["looped"] = called("a looped configuration", "ut_steps",
                                 "sandwich_norm", "exit_gate")
    if cfg.layer_kinds is not None:
        found["runs"] = (
            "a stack of runs of different layer kinds (KDA and "
            "latent-attention layers among them; layer_kinds of {})".format(
                ", ".join(sorted({"/".join(k) for k in cfg.layer_kinds}))))
    if cfg.q_lora_rank or cfg.mla_rope or cfg.mtp_layers:
        found["rotary_latent"] = called(
            "rotary latent attention with a query latent or a "
            "multi-token-prediction module", "q_lora_rank", "mla_rope",
            "mtp_layers")
    if (cfg.swa_window or cfg.attn_gate or cfg.rope_yarn is not None
            or cfg.rope_fraction != 1):
        found["window"] = called(
            "window layers among full ones, a gate on the attention output "
            "or a scaled or partial rotation", "swa_window", "attn_gate",
            "rope_fraction", "rope_yarn")
    if cfg.ssm_heads or {
            cfg.embed_multiplier, cfg.head_multiplier, cfg.attn_in_multiplier,
            cfg.key_multiplier, cfg.attn_out_multiplier,
            cfg.ssm_in_multiplier, cfg.ssm_out_multiplier,
            *cfg.ssm_multipliers, *cfg.ffn_multipliers} != {1}:
        found["two_branches"] = called(
            "an attention and a state-space branch side by side in a layer, "
            "or constants on the forward pass", "ssm_heads", "ssm_head_dim",
            "ssm_state", "embed_multiplier", "attn_out_multiplier",
            "ssm_out_multiplier")
    if cfg.n_experts:
        found["experts"] = called("a mixture of experts", "n_experts")
    if cfg.experts_held:
        found["held"] = called("a chip's share of the experts",
                               "experts_held")
    return found


def _rows(instead: str, **lacks: str) -> Dict[str, str]:
    """A consumer's rows of ``_LACKS``, ``{trait: what the consumer lacks for
    it; what to do instead}``, in the order the consumer checks them."""
    return {trait: f"{text}; {instead}" for trait, text in lacks.items()}


_TRAIN = "train it with make_train_step"

# Both pipeline schedules run one stage program.
_STAGE_ROWS = _rows(
    _TRAIN,
    two_branches="a hand-sharded layer with the state-space branch (its "
    "projection's sections, convolution and scan over the heads of a tp "
    "shard) and the constants on the embedding, both branches, the FFN and "
    "the logits, which the stage program does not read",
    rotary_latent="a last stage that hands the module the state before the "
    "final norm, and the embedding on the first and the last stage at once",
    window="stages whose layers differ in head count, window and rotation (a "
    "stage is one stacked scan of identical layers, its attention made once) "
    "and the gate in the hand-sharded layer",
    runs="a stage split by run (a stage is one stacked scan of identical "
    "layers)",
    experts="a carrier that threads the aux loss through the stage boundary "
    "(the carrier is a single (mb, L, D) array)",
    looped="stages that are passed ut_steps times, a norm on a branch's "
    "output and an exit gate (a stage runs its layers once)")

_RING_ROWS = _rows(
    "take attn='full' or 'flash'",
    two_branches="a state that crosses sequence shards (a shard's scan "
    "starts from its left neighbour's last state of ssm_head_dim x "
    "ssm_state a head, and its convolution from that neighbour's last taps) "
    "and the key's constant in the ring's hand-sharded layer",
    rotary_latent="a ring form of the latent layer (the one rotated key part "
    "all heads share would circulate with every head's keys) and a module "
    "whose next token and target lie past a sequence shard's edge",
    window="a ring form of the band (a sequence shard meets its left "
    "neighbours' last swa_window keys alone) and the gate and the scaled, "
    "partial rotation in the ring's hand-sharded layer",
    runs="ring kernels for more than one head width (they take one head "
    "width for q, k and v) and a recurrent state that crosses sequence shards")

# What each consumer of a configuration cannot run, and why: consumer ->
# trait (:func:`_traits`) -> what the consumer lacks for it.  A trait without
# a row the consumer runs.  :func:`_refuse` is the table's one reader; the
# consumers in ``llama_decode`` and ``llama_pipeline`` have their rows here,
# beside the model they would have to follow.
_LACKS: Dict[str, Dict[str, str]] = {
    "an ep axis": _rows(
        "hold all the experts (experts_held=None), or use a mesh without ep",
        held="the ranks the share stands for (a share is what ONE of the "
        "chips that divide a layer holds; the exchange sends a unit to the "
        "rank of its expert, and the absent experts have none)"),
    "a tp axis": _rows(
        "use a mesh without tp (dp alone)",
        two_branches="the state-space branch's heads over tp: its one "
        "projection's five sections, the convolution's channels, A, D, dt's "
        "bias and the gated norm's groups each split by head, and the scan "
        "in a shard_map beside the flash kernel's"),
    "expert_unit_counts": _rows(
        _TRAIN,
        looped="a row for each recurrent step's routers (it runs its layers "
        "once, with no norm on a branch's output)"),
    **{f"attn={attn!r}": _RING_ROWS for attn in _RINGS},
    "the decode step": _rows(
        _TRAIN,
        two_branches="a recurrent-state cache (ssm_heads x ssm_head_dim x "
        "ssm_state float32 a layer and the convolution's last ssm_conv - 1 "
        "taps) BESIDE a key-value cache in one layer "
        "(serving/kvcache.py:BlockPool accounts for one kind of block), the "
        "scan's one-token form, and the constants in the one-row path",
        looped="a cache of ut_steps x n_layers slots, a norm on a branch's "
        "output and an exit gate (it runs its layers once)",
        rotary_latent="a latent cache (the normed latent and the rotated "
        "shared key part a token), the absorbed form of the query latent's "
        "product with it, and a self-drafting step for the module",
        window="a rolling cache of swa_window positions for the window "
        "layers beside the full layers' (serving/kvcache.py:BlockPool "
        "accounts for one kind of block), the gate in the one-row path, and "
        "YaRN's positions past the original length",
        runs="a recurrent-state cache for the KDA layers (a head's d x d "
        "state and the convolutions' last taps) beside a latent cache for "
        "the others"),
    "prefill": _rows(
        _TRAIN,
        two_branches="the scan's final state and the convolution's last "
        "taps to seed a recurrent-state cache with, beside the keys and "
        "values of the same layer",
        looped="a cache of ut_steps x n_layers slots to seed, a norm on a "
        "branch's output and an exit gate (it runs its layers once)",
        rotary_latent="a latent cache to seed decoding with (the normed "
        "latent and the rotated shared key part a token) and the module's "
        "state for a first draft",
        window="a rolling cache of the last swa_window positions to seed "
        "the window layers' decoding with, beside the full layers' whole one",
        runs="a latent cache (the normed latent and the shared key part a "
        "token) and the KDA layers' final state to seed decoding with"),
    "make_generate_fn": _rows(
        _TRAIN,
        two_branches="the two caches of one layer its prefill and decode "
        "step would fill (recurrent state and last taps, keys and values)",
        looped="the cache of ut_steps x n_layers slots its prefill and "
        "decode step would fill, and an exit rule",
        rotary_latent="the latent cache its prefill and decode step would "
        "fill and a step that drafts with the module and verifies",
        window="the two caches its prefill and decode step would fill (a "
        "rolling one of swa_window positions, a whole one) and the gate in "
        "the one-row path",
        runs="the two caches its prefill and decode step would fill "
        "(recurrent state, latent)"),
    "make_pp_train_step": _STAGE_ROWS,
    "make_1f1b_train_step": _STAGE_ROWS,
}


def _refuse(cfg: Config, consumer: str) -> None:
    """Raise ``NotImplementedError`` if ``consumer`` has a row in ``_LACKS``
    for something ``cfg`` is: the first such row in the consumer's order, by
    the consumer's name, the trait with its field values and what is
    missing."""
    has = _traits(cfg)
    for trait, lacks in _LACKS[consumer].items():
        if trait in has:
            raise NotImplementedError(
                f"{consumer} has no form yet for {has[trait]}: it lacks "
                f"{lacks}")


def _mixer_impls(cfg: Config, attn: str, mesh: Optional[Mesh]):
    """{mixer kind: attention callable} for the layers of ``cfg``: the
    softmax layers' at ``head_dim ** -0.5`` (a window layer's at its own head
    count and window), the latent layers' at the scale of their whole key; a
    KDA layer's is the layer between its projections
    (:func:`_kda_sharded`)."""
    mla = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    if attn in _RINGS:
        _refuse(cfg, f"attn={attn!r}")
    softmax = 1.0 / np.sqrt(cfg.head_dim)
    full = _make_attn_impl(cfg, attn, mesh, softmax)
    if mesh is not None and dict(mesh.shape).get(AXIS_TP, 1) > 1:
        _refuse(cfg, "a tp axis")
    return {"attn": full, "attn+ssm": full,
            "swa": (_make_attn_impl(cfg, attn, mesh, softmax, "swa")
                    if cfg.swa_window else None),
            "mla": (_make_attn_impl(cfg, attn, mesh, 1.0 / np.sqrt(mla))
                    if mla else None),
            "kda": _kda_sharded(mesh, cfg.kda_heads, cfg.norm_eps)}


def _stacks(cfg: Config, params: Params):
    """The stacked layers of each run of :func:`layer_runs`, in order."""
    return (params["layers"] if cfg.layer_kinds is not None
            else (params["layers"],))


def exit_distribution(cfg: Config, params: Params, tokens: jax.Array,
                      mesh: Optional[Mesh] = None,
                      attn: str = "full") -> jax.Array:
    """(ut_steps, B, L) float32: the probability that each token of a batch
    leaves at each recurrent step, by the gate code the training step runs
    (:func:`_exit_log_probs`) on the states the forward pass gives it; it
    sums to 1 over the steps.  A counter for outside the step: its mean over
    the tokens, weighted by the step's number, is the expected exit step."""
    h = apply(cfg, params, tokens, mesh=mesh, attn=attn, return_hidden=True,
              all_steps=True)
    return jnp.exp(_exit_log_probs(params, h))


def _exit_log_probs(params: Params, h: jax.Array) -> jax.Array:
    """The log of a looped model's exit distribution from the normed states
    ``h`` (T, B, L, D) of its T recurrent steps, (T, B, L) float32: with
    ``lambda_t = sigmoid(h_t @ gate_w + gate_b)`` the chance to leave at
    step t once there, ``p_t = lambda_t * prod_{j<t} (1 - lambda_j)`` for
    t < T and the rest at T, ``p_T = prod_{j<T} (1 - lambda_j)``."""
    a = jnp.einsum("tbld,d->tbl", h, params["gate_w"],
                   preferred_element_type=jnp.float32) + params["gate_b"]
    stay = jnp.cumsum(jax.nn.log_sigmoid(-a[:-1]), axis=0)  # log S_1..S_{T-1}
    before = jnp.concatenate([jnp.zeros_like(a[:1]), stay], axis=0)
    return before + jnp.concatenate(
        [jax.nn.log_sigmoid(a[:-1]), jnp.zeros_like(a[:1])], axis=0)


def _mtp_input(cfg: Config, params: Params, h: jax.Array,
               mtp_tokens: jax.Array, constrain: Callable) -> jax.Array:
    """What a multi-token-prediction module's layer reads (DeepSeek-V3's
    report, section 2.2, depth 1): for every position the embedding of its
    NEXT token (``mtp_tokens`` (B, L): ``targets`` in the ``(tokens,
    targets)`` contract) and the stack's state ``h`` (B, L, D) BEFORE the
    final norm, each through a norm of its own (``enorm``, ``hnorm``), side
    by side in that order, projected back to D by ``w_eh`` (2D, D).  The
    embedding is the model's own, read a second time."""
    mp = params["mtp"]
    with jax.named_scope("embed"):
        e = constrain(params["embed"][mtp_tokens])
    return jnp.concatenate(
        [rms_norm(e, mp["enorm"], cfg.norm_eps),
         rms_norm(h, mp["hnorm"], cfg.norm_eps)], axis=-1) @ mp["w_eh"]


def _mtp_loss_parts(cfg: Config, params: Params, h, targets: jax.Array,
                    loss_chunk: int):
    """The two terms of the loss of a configuration with a
    multi-token-prediction module, from ``h``, the pair of normed states
    :func:`apply` hands out (B, L, D each): the main model's mean NLL, and
    ``cfg.mtp_coef`` times the module's.  The module's position i, which read
    token i + 1, is held to token i + 2, ``targets[i + 1]``, through the
    model's own head (a call of its own under ``mtp``, so the head's gradient
    is the sum of two paths).  All L rows run so that the chunks keep their
    shape; the last row, which has no such target, carries weight 0, and the
    others ``mtp_coef`` over their count."""
    B, L, _ = h[1].shape
    weights = jnp.broadcast_to(
        jnp.where(jnp.arange(L) < L - 1, cfg.mtp_coef / (B * (L - 1)), 0.0
                  ).astype(jnp.float32), (B, L))
    main = _nll_from_hidden(params["head"], h[0], targets, loss_chunk)
    with jax.named_scope("mtp"):
        return main, _nll_from_hidden(params["head"], h[1],
                                      jnp.roll(targets, -1, axis=1),
                                      loss_chunk, weights)


def mtp_loss_parts(cfg: Config, params: Params, batch, mesh=None,
                   attn: str = "full", loss_chunk: int = 0):
    """``(main NLL, the module's NLL)`` of a batch ``(tokens, targets)``,
    both unweighted means, by the code the training step runs
    (:func:`_mtp_loss_parts`): a counter for outside the step."""
    tokens, targets = batch
    h = apply(cfg, params, tokens, mesh=mesh, attn=attn, return_hidden=True,
              mtp_tokens=targets)
    main, module = _mtp_loss_parts(cfg, params, h, targets, loss_chunk)
    return main, module / cfg.mtp_coef


def _expert_layers_read(cfg: Config, params: Params, tokens: jax.Array,
                        mesh: Optional[Mesh], attn: str,
                        mtp_tokens: Optional[jax.Array],
                        read: Callable) -> jax.Array:
    """``read(lp, x, aux)`` of every layer with experts on ``tokens``, a row
    each in the stack's order: the layer's weights, the normed input its
    router sees (B, L, D) and the ``aux`` its FFN returns, by the code the
    training step runs, a layer after the other with no checkpoint.  A
    multi-token-prediction module's layer is one more row, the last, on the
    next tokens ``mtp_tokens`` it reads (:func:`_mtp_input`)."""
    if cfg.mtp_layers and mtp_tokens is None:
        raise ValueError("a configuration with a multi-token-prediction "
                         "module needs mtp_tokens, the batch's targets")
    positions = jnp.arange(tokens.shape[1])
    impls = _mixer_impls(cfg, attn, mesh)
    h, rows = params["embed"][tokens], []

    def through(h, mixer, ffn, stack):
        """A run's layers on ``h``, what is read of them added to ``rows``."""
        def layer(h, lp):
            h = _attention_block(cfg, lp, h, positions, impls[mixer],
                                 mixer=mixer)
            out, aux = _ffn_block(cfg, lp, h, mesh=mesh, ffn=ffn)
            if ffn != "moe":
                return out, None
            return out, read(lp, rms_norm(h, lp["mlp_norm"], cfg.norm_eps),
                             aux)

        h, read_of = lax.scan(layer, h, stack)
        if read_of is not None:
            rows.append(read_of)
        return h

    for (mixer, ffn, _), stack in zip(layer_runs(cfg), _stacks(cfg, params)):
        h = through(h, mixer, ffn, stack)
    if cfg.mtp_layers:
        through(_mtp_input(cfg, params, h, mtp_tokens, lambda x: x),
                *cfg.layer_kinds[-1], params["mtp"]["layer"])
    return jnp.concatenate(rows)


def expert_unit_counts(cfg: Config, params: Params, tokens: jax.Array,
                       mesh: Optional[Mesh] = None, attn: str = "full",
                       mtp_tokens: Optional[jax.Array] = None) -> jax.Array:
    """(layers with experts, n_experts) int32: how many of a batch's k*T
    routed units each expert of each layer is sent, by the router code the
    training step runs (:func:`_route_tokens`), on the activations the forward
    pass gives it.  A counter for outside the step: a row sums to k*T, and its
    largest entry over its mean says how lopsided that layer's routing is.  A
    stack of runs has a row for each layer of its ``"moe"`` runs, in order,
    over all ``n_experts`` whatever ``cfg.experts_held``: the held experts'
    columns are what the step's tiles see.  A multi-token-prediction
    module's router is one more row, the last, on the next tokens
    ``mtp_tokens`` it reads (:func:`_mtp_input`)."""
    _refuse(cfg, "expert_unit_counts")
    return _expert_layers_read(
        cfg, params, tokens, mesh, attn, mtp_tokens, lambda lp, x, _:
        _route_tokens(cfg, lp, x.reshape(-1, x.shape[-1]))[2])


def ep_pass_counts(cfg: Config, params: Params, tokens: jax.Array,
                   mesh: Mesh, attn: str = "full") -> jax.Array:
    """(layers with experts, ep) int32: the passes each rank's held experts
    took in each layer on a batch, on a mesh whose ``ep`` axis alone shares
    the tokens (:func:`_moe_ffn_ep`).  The passes are data, so this is a
    jitted read beside the step, as :func:`expert_unit_counts` is, and from
    what the passes themselves counted: a layer's ``delivered``, the units of
    each rank that ran on each rank.  Where the layer gathers tokens
    (:func:`ep_exchange_plan`'s ``form``) a rank takes the passes its own
    arrivals fill, so a fuller rank reads more than the others (Mellum2's
    layer: 16 passes of 8,192 rows are the uniform share, a rank 6% over it
    takes 17 or 18); where it exchanges units every rank takes the passes of
    the fullest PAIR of ranks, one and the overflow passes."""
    ep = _ep_ranks(cfg, mesh)
    if ep < 2 or any(mesh.shape.get(a, 1) > 1 for a in (AXIS_DP, AXIS_SP)):
        raise ValueError("ep_pass_counts reads a mesh whose ep axis alone "
                         f"shares the tokens, not {dict(mesh.shape)}")
    plan = ep_exchange_plan(cfg, tokens.size // ep, ep)
    first, over = plan["pass_rows"], plan["overflow_pass_rows"]

    def read(lp, x, aux):
        delivered = aux[1]
        if plan["form"] == "tokens":
            return -(-jnp.sum(delivered, axis=1) // first)
        most = ep * jnp.max(delivered)      # a pass takes a block a peer
        return jnp.full((ep,), jnp.minimum(most, 1)
                        - (-jnp.maximum(most - first, 0) // over))

    return _expert_layers_read(cfg, params, tokens, mesh, attn, None, read)


# The deepest stack :func:`apply` inlines; a deeper one it scans.  In a stack
# of runs of different layer kinds (:func:`layer_runs`) the depth is a run's:
# each run is inlined or scanned by its own length, and the runs follow one
# another inlined, so Kimi Linear's published 27 layers (runs of at most
# three) are 27 inlined layers, where a homogeneous 27 would be one scan: the
# price of kinds that alternate every fourth layer is the compile time of
# each layer (not measured at that depth; the benchmark's cut runs five).
# Scanning
# costs copies every step in proportion to the depth, inlining compile time
# in proportion to it.  Measured on a v5e, scanned -> inlined (chip runs of
# PR 29, PERF.md section 6): OLMoE widths, 2 layers, step 316.4 -> 301.2 ms
# and compile 24.8 -> 23.2 s, 4 layers 296.2 -> 258.1 ms and 26.8 -> 25.8 s;
# dense Llama-3-8B widths, 4 layers 400.6 -> 369.1 ms and 6.8 -> 12.5 s, 8
# layers 359.1 -> 348.3 ms and 7.0 -> 21.0 s.  Up to 4 layers the step wins
# 5-13% for at most 6 s of compile; at 8 it wins 3% for 14 s, and a dense
# layer inlined adds 1.4-1.8 s: a minute at depth 32.  A looped stack
# (``cfg.ut_steps`` > 1) is 32 layer applications at depth 8 and 4 steps, and
# the rule still reads the depth alone (chip runs of PR 30, Ouro-2.6B widths,
# 8 layers x 4 steps, 2 x 4096 tokens, AdamW; scanned -> inlined): remat
# "full" 1078.7 -> 1033.5 ms, the plan 14.39 -> 11.24 GB (a scanned pass
# leaves a stacked gradient of 0.82 GB until the four are summed) and compile
# 21.7 -> 81.4 s; three steps "full" and one "dots" 1041.5 -> 988.3 ms, 15.07
# -> 13.24 GB, 26.6 -> 84.6 s.  4-5% of a step for a minute of compile in
# every program that holds the stack: the trade declined at depth 8, so the
# bound was not changed by them.  The recurrent steps themselves are always
# inlined: a ``lax.scan`` over them with the stack closed over ran 1061.6 ms
# for 1078.7 (-1.6%) under "full", but it takes one remat policy for all its
# steps, and a policy for each step wins more (1041.5).
def branch_contributions(cfg: Config, params: Params, tokens: jax.Array,
                         mesh: Optional[Mesh] = None, attn: str = "full"):
    """What each branch of each ``"attn+ssm"`` layer adds to the residual on
    ``tokens`` (B, L), by the code the training step runs
    (:func:`_two_branches`, :func:`_dense_ffn`), a layer after the other with
    no checkpoint: ``{"attn": (n_layers, B, L, D), "ssm": ..., "ffn": ...}``
    float32, each with its multiplier on it.  A counter for outside the step:
    a branch left out or a constant dropped reads here against a reference
    as an error of its own size, where the logits bury it under the other
    branches' sum."""
    assert all(kinds == ("attn+ssm", "dense")
               for kinds in cfg.layer_kinds or ()), cfg.layer_kinds
    impl = _mixer_impls(cfg, attn, mesh)["attn+ssm"]
    positions = jnp.arange(tokens.shape[1])
    h = _times(params["embed"][tokens], cfg.embed_multiplier)
    found = {"attn": [], "ssm": [], "ffn": []}
    for stacked in _stacks(cfg, params):
        for i in range(jax.tree.leaves(stacked)[0].shape[0]):
            lp = jax.tree.map(lambda a: a[i], stacked)
            a, s = _two_branches(cfg, lp, h, positions, impl)
            h = h + (a + s).astype(h.dtype)
            g = _dense_ffn(cfg, lp, rms_norm(h, lp["mlp_norm"], cfg.norm_eps))
            h = h + g
            for name, value in (("attn", a), ("ssm", s), ("ffn", g)):
                found[name].append(value.astype(jnp.float32))
    return {name: jnp.stack(values) for name, values in found.items()}


_INLINE_MAX_LAYERS = 4


def apply(cfg: Config, params: Params, tokens: jax.Array,
          mesh: Optional[Mesh] = None, attn: str = "full",
          remat: Remat = "none", return_hidden: bool = False,
          return_aux: bool = False, layer_loop: Optional[str] = None,
          positions: Optional[jax.Array] = None,
          all_steps: bool = False,
          mtp_tokens: Optional[jax.Array] = None) -> jax.Array:
    """Forward: tokens (B, L) int32 -> logits (B, L, vocab) f32, or the
    final hidden states (B, L, D) in compute dtype when ``return_hidden``
    (the chunked-loss path applies the output head itself so the full
    ``(B, L, V)`` f32 logits never materialize).  With ``return_aux`` the
    result is ``(out, aux)`` where ``aux`` is the layer-mean MoE
    load-balance loss (0 for dense configs) — the training path for
    ``n_experts > 0`` configs adds ``cfg.moe_aux_coef * aux`` — and with
    ``cfg.moe_z_coef`` the pair (load balance, router z-loss).

    A looped configuration (``cfg.ut_steps`` = T > 1) runs the stack T times
    with the same weights and positions, the final norm after every pass, and
    returns the last step's logits or states: no step leaves early.  With
    ``all_steps`` the result carries a leading axis of the T recurrent steps,
    (T, B, L, ...): what the expected-exit loss and the comparison with a
    reference read.  ``aux`` is then the mean over all T * n_layers layer
    applications.

    With ``mtp_tokens`` (B, L), each position's next token, a configuration
    with a multi-token-prediction module (``cfg.mtp_layers``) returns the pair
    ``(main, module's)`` in ``out``'s place: the module's logits or normed
    states for the token after next (:func:`_mtp_input`).  Without it the
    module does not run and the result is the main model's alone.

    ``mesh`` enables activation sharding constraints (and is required for
    ``attn='ring'``); without it the model runs unconstrained (single-device
    or auto-sharded).  On a mesh with an ``ep`` axis a dropless configuration
    shards the batch's rows over ``ep`` as over ``dp`` (:func:`batch_spec`)
    and exchanges its routed units over the axis (:func:`_moe_ffn_ep`), and
    ``aux`` is then the pair ``(aux, delivered)``: ``delivered`` (ep, ep)
    int32, the units of rank s that reached the experts of rank r at [r, s],
    counted in the exchange's passes and summed over the stack's expert
    layers: ``k * B * L`` a layer in all, or one was dropped.

    ``remat`` is the rematerialization policy applied to each layer
    (:func:`_wrap_remat` has the list of what each keeps; no policy runs a
    flash kernel twice):
      * ``"none"``  — save all residuals (small models),
      * ``"dots"``  — save matmul outputs and the kernels' named outputs,
        recompute elementwise (the transformer default: activations per layer
        shrink ~4x),
      * ``"full"``  — save only layer boundaries and, with ``attn="flash"``,
        the kernel's output and log-sum-exp; the backward pass recomputes the
        rest of each layer's forward and never the L^2 part (longest
        contexts).
    For a looped configuration the policy holds for every layer application
    of every recurrent step, or ``remat`` is a sequence of T such names, one
    for each recurrent step's layers: the T * n_layers applications of a
    step's backward pass all keep what their policy keeps at once, so
    ``"dots"`` for as many steps as the memory holds and ``"full"`` for the
    rest is the recomputation chosen to fit.

    ``layer_loop`` is the form of the loop over the stacked layers.  Left
    ``None``, the code chooses from the depth it is given: a stack of at
    most ``_INLINE_MAX_LAYERS`` layers is inlined (``"unroll"``), a deeper
    one goes through ``lax.scan`` (``"scan"``: one compiled block, and a
    compile time that does not grow with the depth).  The scan pays in
    copies, every layer, every step: a Mosaic kernel (the grouped matmuls of
    the sorted dispatch, flash) cannot fuse the ``dynamic-slice`` of its
    layer's weights as an XLA convolution does, so each is first copied out
    of the stack, forward and again backward, and the layer's gradients and
    saved residuals are ``dynamic-update-slice``d into stacked buffers the
    loop carries.  Inlined, a layer's slice of a weight is a static slice,
    its gradient an operand of the update, its residuals plain buffers.
    Both forms compute the same function
    (``tests/test_llama.py::test_unrolled_matches_scan``), and both names
    stay accepted for that test's sake.  The form is that of one pass
    through the stack; a looped configuration's T passes are always inlined
    round it (a Python loop: T is small, each step may have a remat policy of
    its own, and each pass's states go to the head), so ``"scan"`` is T scans
    of one layer and ``"unroll"`` T * n_layers inlined layers.  The rule
    reads the depth alone, whatever T (``_INLINE_MAX_LAYERS``'s comment has
    the measurements).
    """
    B, L = tokens.shape
    if attn == "ring-zigzag" and positions is None:
        # The zigzag kernels mask as if row blocks sit in the zigzag
        # layout; contiguous rows with default positions would compute a
        # silently wrong (non-causal) pattern.  make_loss_fn does the
        # permutation; direct callers must too.
        raise ValueError(
            "attn='ring-zigzag' needs tokens permuted into the zigzag "
            "layout and the matching ``positions`` "
            "(parallel.sequence.zigzag_indices); use make_loss_fn / "
            "make_train_step, which handle the permutation")
    if positions is None:
        positions = jnp.arange(L)
    # (non-contiguous positions: the zigzag ring trains on row-permuted
    # sequences; RoPE only ever reads per-row absolute positions, so the
    # permutation rides through — make_loss_fn supplies it.)

    def constrain(x):
        if mesh is None or mesh.empty:
            return x
        # Drop axes the mesh doesn't have (e.g. sp on a pure dp x tp mesh).
        kept = _mesh_spec(P(_batch_axes(cfg, mesh), AXIS_SP, None), mesh)
        return lax.with_sharding_constraint(x, NamedSharding(mesh, kept))

    exchanged = _ep_ranks(cfg, mesh) > 1
    if exchanged:
        _rows_divide(cfg, mesh, B)
    with jax.named_scope("embed"):
        h = constrain(_times(params["embed"][tokens],
                             cfg.embed_multiplier))  # (B, L, D)
    impls = _mixer_impls(cfg, attn, mesh)

    remats = (remat,) * cfg.ut_steps if isinstance(remat, str) else tuple(remat)
    if len(remats) != cfg.ut_steps:
        raise ValueError(f"remat names {len(remats)} recurrent steps, the "
                         f"configuration has {cfg.ut_steps}")
    if layer_loop not in ("scan", "unroll", None):
        raise ValueError("layer_loop must be 'scan', 'unroll' or None")

    def run_loop(mixer, ffn, n, stacked):
        """{remat name: one pass through a run of ``n`` layers of one kind},
        the run inlined or scanned by its own length."""
        loop = layer_loop or ("unroll" if n <= _INLINE_MAX_LAYERS else "scan")

        def layer(carry, lp):
            h, aux = carry
            h, a = _decoder_layer(cfg, lp, h, positions, impls[mixer],
                                  constrain, mesh=mesh, mixer=mixer, ffn=ffn)
            return (h, jax.tree.map(jnp.add, aux, a)), None

        def run(layer, carry):
            if loop == "scan":
                return lax.scan(layer, carry, stacked)[0]
            for i in range(n):
                carry, _ = layer(carry, jax.tree.map(lambda a: a[i], stacked))
            return carry

        return {r: functools.partial(
                    run, _wrap_remat(layer, r, scanned=loop == "scan"))
                for r in dict.fromkeys(remats)}

    runs = [run_loop(mixer, ffn, n, stacked)
            for (mixer, ffn, n), stacked in zip(layer_runs(cfg),
                                                _stacks(cfg, params))]

    def ut_step(carry, r):
        """One pass through the stack: its runs one after the other.  With
        the normed state and the aux sum, the state before the norm."""
        for run in runs:
            carry = run[r](carry)
        h, aux = carry
        with jax.named_scope("final_norm"):
            return (rms_norm(h, params["norm"], cfg.norm_eps), aux), h

    carry = (h, _aux_zero(cfg, mesh))
    states = []
    for r in remats:
        carry, before_norm = ut_step(carry, r)
        states.append(carry[0])
    aux = carry[1]
    if exchanged:
        aux = (aux[0] / (cfg.n_layers * cfg.ut_steps), aux[1])
    else:
        aux = aux / (cfg.n_layers * cfg.ut_steps)
    h = jnp.stack(states) if all_steps else states[-1]
    head = lambda h: (h if return_hidden else _times(
        (h @ params["head"]).astype(jnp.float32), cfg.head_multiplier))
    out = head(h)
    if cfg.mtp_layers and mtp_tokens is not None:
        # One more layer of the stack's last kind after the stack, under the
        # stack's own remat policy, and a norm of its own; ``mtp`` outermost
        # round the names every layer has.
        with jax.named_scope("mtp"):
            x = _mtp_input(cfg, params, before_norm, mtp_tokens, constrain)
            x, _ = run_loop(*cfg.layer_kinds[-1], 1, params["mtp"]["layer"])[
                remats[-1]]((x, _aux_zero(cfg, mesh)))
            with jax.named_scope("final_norm"):
                x = rms_norm(x, params["mtp"]["norm"], cfg.norm_eps)
            out = (out, head(x))
    return (out, aux) if return_aux else out


def _expected_exit_nll(cfg: Config, params: Params, h: jax.Array,
                       targets: jax.Array, loss_chunk: int) -> jax.Array:
    """A looped model's training loss from the normed states ``h`` (T, B, L,
    D) of its T recurrent steps: the mean over the tokens of ``sum_t p_t *
    nll_t - cfg.exit_entropy_coef * H(p)``, with ``nll_t`` the next-token NLL
    of step t's logits, ``p`` the token's exit distribution
    (:func:`_exit_log_probs`) and ``H`` its entropy.  The gate learns through
    both terms, the head and the stack through the p-weighted NLL of every
    step.  The steps' states go through the head as T * B rows of one call,
    each token's weight its ``p_t`` over the token count: with a
    ``loss_chunk`` one pass over the head's gradient accumulator a chunk, not
    T (:func:`_chunked_nll`).  With T = 1 it is the plain mean NLL."""
    T, B, L, D = h.shape
    with jax.named_scope("exit_gate"):
        logp = _exit_log_probs(params, h)
        p = jnp.exp(logp)
        entropy = -jnp.mean(jnp.sum(p * logp, axis=0))
        weights = (p / (B * L)).reshape(T * B, L)
    nll = _nll_from_hidden(params["head"], h.reshape(T * B, L, D),
                           jnp.tile(targets, (T, 1)), loss_chunk, weights)
    return nll - cfg.exit_entropy_coef * entropy


def make_loss_fn(cfg: Config, mesh: Optional[Mesh] = None, attn: str = "full",
                 remat: Remat = "none", loss_chunk: int = 0,
                 layer_loop: Optional[str] = None):
    """Next-token cross-entropy: ``loss_fn(params, (tokens, targets))`` —
    the engine contract; targets = tokens shifted by the caller.

    ``loss_chunk`` > 0 computes the loss in sequence chunks of that size so
    the full ``(B, L, V)`` f32 logits never materialize — at 8B scale
    (V=128256) those logits alone are ~4 GB per 8k sequence, more than the
    layer activations; chunking caps the live buffer at ``(B, C, V)``.  Under
    differentiation each chunk takes the head's gradients in the pass that
    forms its logits (:func:`_chunked_nll`), so the peak holds there too and
    no chunk is formed twice.  ``L`` must be divisible by ``loss_chunk``.
    ``layer_loop`` as in :func:`apply`: left ``None``, the depth decides.

    A configuration with an exit gate (``cfg.exit_gate``, a looped model)
    trains on the expected-exit loss over all its recurrent steps
    (:func:`_expected_exit_nll`); without one, on the last step's NLL.  A
    configuration with a multi-token-prediction module (``cfg.mtp_layers``)
    adds ``cfg.mtp_coef`` times the module's loss (:func:`_mtp_loss_parts`):
    the embedding and the head are each read twice, and their gradients are
    the sums of both paths.
    """
    both = _loss_and_delivered(cfg, mesh, attn, remat, loss_chunk, layer_loop)

    def loss_fn(params: Params, batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
        return both(params, batch)[0]

    return loss_fn


def _loss_and_delivered(cfg: Config, mesh: Optional[Mesh], attn: str,
                        remat: Remat, loss_chunk: int,
                        layer_loop: Optional[str] = None):
    """:func:`make_loss_fn`'s loss as the pair ``(loss, delivered)``:
    ``delivered`` is what the expert layers' exchange over ``ep`` counted
    (:func:`apply`'s ``aux`` on such a mesh), None where nothing is
    exchanged."""

    def loss_fn(params: Params, batch: Tuple[jax.Array, jax.Array]):
        tokens, targets = batch
        positions = None
        if attn == "ring-zigzag":
            # Balanced causal ring: rows permute into the zigzag layout
            # (device d gets global chunks (d, 2p-1-d)); RoPE positions
            # carry the permutation, targets follow their tokens, and the
            # mean NLL is permutation-invariant — so the loss (and its
            # grads) equal the contiguous layout's exactly while every sp
            # device computes the same attention block area per ring step.
            from ..parallel import sequence as seq_mod
            from ..parallel.mesh import mesh_axis_size

            p = mesh_axis_size(mesh, AXIS_SP)
            idx = seq_mod.zigzag_indices(tokens.shape[1], p)
            tokens = tokens[:, idx]
            targets = targets[:, idx]
            positions = jnp.asarray(idx)
        h, aux = apply(
            cfg, params, tokens, mesh=mesh, attn=attn, remat=remat,
            return_hidden=True, return_aux=True, layer_loop=layer_loop,
            positions=positions, all_steps=cfg.exit_gate,   # (B, L, D)
            mtp_tokens=targets if cfg.mtp_layers else None)
        aux, delivered = aux if _ep_ranks(cfg, mesh) > 1 else (aux, None)
        if cfg.exit_gate:
            nll = _expected_exit_nll(cfg, params, h, targets, loss_chunk)
        elif cfg.mtp_layers:
            nll = sum(_mtp_loss_parts(cfg, params, h, targets, loss_chunk))
        else:
            # The logits' constant on the states that make them: the same
            # logits by linearity, and the chunked head stays the one it is.
            nll = _nll_from_hidden(params["head"],
                                   _times(h, cfg.head_multiplier), targets,
                                   loss_chunk)
        if cfg.n_experts and cfg.moe_z_coef:
            nll = nll + cfg.moe_aux_coef * aux[0] + cfg.moe_z_coef * aux[1]
        elif cfg.n_experts:
            nll = nll + cfg.moe_aux_coef * aux
        return nll, delivered

    return loss_fn



# The sorted expert layer's gate and up products (:func:`_moe_ffn_sorted`
# names them): the grouped matmul's outputs that a backward pass reads again.
GROUPED_DOT_NAMES = ("moe_gate", "moe_up")


def _wrap_remat(layer: Callable, remat: str,
                scanned: bool = False) -> Callable:
    """THE remat taxonomy ('none'/'dots'/'full'), one definition for the
    forward's layer loop and both pipeline stage builders.  One policy for
    every application of ``layer``; :func:`apply` wraps the layer once for
    each policy a looped configuration's recurrent steps name.

    No policy replays a Mosaic kernel whose output it can keep for about a
    layer input's bytes.  A kernel is no ``dot_general``, so a policy sees
    its output only by the name the output carries, and this is the one list
    of the names each policy keeps:

    * ``"dots"``: matmul outputs; the flash kernel's ``o`` and ``lse``
      (``ops.flash_attention.RESIDUAL_NAMES``); the grouped matmul's gate and
      up products (``GROUPED_DOT_NAMES``), which are dots whether megablox's
      ``gmm`` or ``lax.ragged_dot`` forms them: two arrays of k*T x d_expert a
      layer, for two forward kernels a layer not run again.
    * ``"full"``: the layer's input and the flash kernel's ``o`` and ``lse``,
      nothing else: with ``attn="flash"`` one more array the size of the
      layer's input for each layer application (``o``, B*L*H*hd; ``lse``,
      float32, is 1/64 of its bytes at a head of 128), and the one part of a
      layer whose recomputation grows with L^2 runs once.  The grouped
      matmul's products it does not keep (at OLMoE's shapes they are eight
      layer inputs) and replays.
    * both: the KDA recurrence's output, chunk-entry states and, from its
      kernels, each chunk's inverse and ``P`` (``ops.kda.KDA_RESIDUAL_NAMES``:
      L/64 states of d x d and tiles of 64 x 64 a head; one, four, one and a
      half layer inputs' bytes at Kimi Linear's widths), so the recurrence
      runs once each way and its kernels invert no tile and sum no P twice;
      and a state-space branch's scan's output and chunk-entry states
      (``ops.ssd.SSD_RESIDUAL_NAMES``: one layer input's bytes at Falcon-H1's
      widths less a fifth, and 3.2), so that scan runs once each way too.
    * ``"none"``: everything, no checkpoint.

    No other attention mode emits the flash names, no other mixer the KDA
    or the scan's ones and no other FFN the grouped ones, so ``attn="full"``, the rings, the dense SwiGLU and the
    one-hot experts compile as before.

    ``scanned`` says the wrapped layer is the body of a ``lax.scan``.  The
    checkpoint's optimization barrier (``prevent_cse``) is there so that the
    compiler cannot merge a layer's recomputation with its forward pass and
    keep everything after all; under a scan the two run in different loops
    and nothing can be merged, while the barrier still makes every kept
    array and every cotangent stand in memory as it is handed over, laid out
    again for the kernel that reads it: on Ouro-2.6B's 8 scanned layers x 4
    steps 43 ms of a 1,035 ms step, and with it ``"full"`` keeping ``o`` and
    ``lse`` shortened the step by 4.5 ms of the 33 it takes out of the flash
    kernels (PERF.md section 6, PR 31).  So a scanned layer is checkpointed
    without it, an inlined one with.  The pipeline stage builders scan their
    layers too and keep the barrier: no cell times them."""
    if remat == "none":
        return layer
    if remat not in ("dots", "full"):
        raise ValueError("remat must be 'none', 'dots', or 'full'")
    from ..ops.flash_attention import RESIDUAL_NAMES
    from ..ops.kda import KDA_RESIDUAL_NAMES
    from ..ops.ssd import SSD_RESIDUAL_NAMES

    policies = jax.checkpoint_policies
    kernels = (*RESIDUAL_NAMES, *KDA_RESIDUAL_NAMES, *SSD_RESIDUAL_NAMES)
    if remat == "full":
        policy = policies.save_only_these_names(*kernels)
    else:
        policy = policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable,
            policies.save_only_these_names(*kernels, *GROUPED_DOT_NAMES))
    return jax.checkpoint(layer, policy=policy, prevent_cse=not scanned)



# ----------------------------------------------------------------- train step

def _zero1_opt_shardings(cfg: Config, mesh: Mesh, opt_state_example,
                         specs=None):
    """ZeRO-1 / optimizer-state sharding over ``dp`` on top of the model
    layout: every optimizer leaf whose shape matches a parameter keeps that
    parameter's spec (tp — or pp x tp when ``specs=param_specs_pp(cfg)``)
    and additionally shards its first still-unsharded, divisible axis over
    ``dp`` (Adam moments at 8B are 2x the f32 params — the dominant
    optimizer memory; each dp replica then holds 1/dp of them).
    Non-parameter-shaped leaves fall back to the engine's rule
    (leading-axis dp when divisible, else replicate); scalars replicate."""
    from jax.tree_util import (tree_flatten_with_path, tree_unflatten)

    dp = dict(mesh.shape).get(AXIS_DP, 1)
    if specs is None:
        specs = param_specs(cfg)
    pshapes = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))

    def key_str(k):
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                return str(getattr(k, attr))
        return str(k)

    # Optimizer-state pytrees embed the parameter tree (Adam's mu/nu are
    # param-shaped subtrees), so match leaves by PATH SUFFIX + shape — two
    # params can share a shape with different tp layouts (wq column- vs wo
    # row-sharded), which a shape-only match would conflate.
    ppaths, _ = tree_flatten_with_path(pshapes)
    pspecs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    by_path = {}
    for (path, sh), sp in zip(ppaths, pspecs):
        keys = tuple(key_str(k) for k in path)
        by_path[keys] = (tuple(sh.shape),
                         _mesh_spec(sp, mesh, tuple(sh.shape)))

    def match(path, shape):
        keys = tuple(key_str(k) for k in path)
        for i in range(len(keys)):
            hit = by_path.get(keys[i:])
            if hit and hit[0] == shape:
                return hit[1]
        return None

    oleaves, otree = tree_flatten_with_path(opt_state_example)
    out = []
    for path, a in oleaves:
        shape = tuple(getattr(a, "shape", ()))
        sp = match(path, shape)
        if sp is not None:
            entries = list(sp) + [None] * (len(shape) - len(sp))
            if dp > 1:
                for i, (e, d) in enumerate(zip(entries, shape)):
                    if e is None and d % dp == 0 and d >= dp:
                        entries[i] = AXIS_DP
                        break
            out.append(NamedSharding(mesh, P(*entries)))
        elif dp > 1 and len(shape) >= 1 and shape[0] % dp == 0 \
                and shape[0] >= dp:
            out.append(NamedSharding(mesh, P(AXIS_DP)))
        else:
            out.append(NamedSharding(mesh, P()))
    return tree_unflatten(otree, out)


def make_train_step(cfg: Config, mesh: Mesh, lr: float = 3e-4,
                    attn: str = "full", optimizer=None,
                    remat: Remat = "none", loss_chunk: int = 0,
                    zero1: bool = False, opt_state_example=None,
                    with_delivered: bool = False):
    """One pjit'd dp x tp (x sp/ep) training step over ``mesh``:
    ``step(params, opt_state, tokens, targets) -> (params, opt_state, loss)``.
    Params tp-sharded per :func:`param_specs`; batch dp-sharded; XLA inserts
    the gradient psums over dp and the activation psums over tp.  For a
    dropless configuration on a mesh with an ``ep`` axis the batch's rows are
    sharded over ``ep`` too (:func:`batch_spec`) and the experts over it:
    between the expert layers the axis is data-parallel, so the gradients of
    what every rank holds alike (attention, routers, norms, embedding, head)
    are summed over it as over ``dp``, and an expert's stay on the rank that
    holds it; ``with_delivered`` adds a fourth result, the units the exchange
    delivered in this step ((ep, ep) int32 as :func:`apply`'s ``aux`` has
    them; None on a mesh that exchanges nothing).  ``remat``/
    ``loss_chunk`` as in :func:`apply`/:func:`make_loss_fn` — pass
    ``remat="dots"`` and a ``loss_chunk`` for 8B-scale configs.

    ``zero1=True`` (needs ``optimizer`` and an ``opt_state_example``, e.g.
    ``jax.eval_shape(optimizer.init, params)``) shards the optimizer state
    over ``dp`` on top of tp — GSPMD then reduce-scatters gradients into
    each replica's optimizer shard and all-gathers updated parameters, the
    ZeRO-1 exchange, at the same collective volume as plain allreduce.

    A selection bias (``cfg.router_bias``) is a buffer the balancing rule
    outside the gradient owns: its gradient is exactly zero and no optimizer
    steps or decays it here (the leaf ``router_bias`` leaves the step as it
    came)."""
    loss_fn = _loss_and_delivered(cfg, mesh, attn, remat, loss_chunk)
    specs = param_specs(cfg)
    # Shape-aware axis dropping so these jit shardings agree with
    # shard_params' placement on every leaf (shared rule: _common.mesh_spec).
    pshapes = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    p_shard = jax.tree.map(
        lambda sh, s: NamedSharding(mesh, _mesh_spec(s, mesh, sh.shape)),
        pshapes, specs)
    batch_sh = NamedSharding(mesh, batch_spec(cfg, mesh))
    repl = NamedSharding(mesh, P())
    if zero1:
        if optimizer is None or opt_state_example is None:
            raise ValueError("zero1 needs optimizer and opt_state_example "
                             "(e.g. jax.eval_shape(optimizer.init, params))")
        opt_sh = _zero1_opt_shardings(cfg, mesh, opt_state_example)
    else:
        opt_sh = None

    def step(params, opt_state, tokens, targets):
        (loss, delivered), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, (tokens, targets))
        before = params
        with jax.named_scope("optimizer"):
            if optimizer is not None:
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = jax.tree.map(lambda p, u: p + u, params, updates)
            else:
                params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                                      params, grads)
        if cfg.router_bias:
            params = jax.tree_util.tree_map_with_path(
                lambda path, old, new: old if getattr(
                    path[-1], "key", None) == "router_bias" else new,
                before, params)
        if with_delivered:
            return params, opt_state, loss, delivered
        return params, opt_state, loss

    return jax.jit(
        step,
        in_shardings=(p_shard, opt_sh, batch_sh, batch_sh),
        out_shardings=(p_shard, opt_sh, repl) + (repl,) * with_delivered,
        donate_argnums=(0, 1),
    )
