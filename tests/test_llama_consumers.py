"""What each consumer of a Llama-family configuration refuses, and says why
(``llama._LACKS``, read by ``llama._refuse``): one case a (configuration,
consumer) pair.  The phrases are written out here, not read back from the
table they check.  Every refusal fires before a parameter is touched, so the
configurations are toy ones and the parameters mostly absent."""

import dataclasses
import os
import re
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from torchmpi_tpu.models import llama, llama_decode, llama_pipeline
from torchmpi_tpu.parallel import make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TOY = dict(vocab=128, d_model=64, n_layers=5, n_heads=4, d_ff=32,
            dense_d_ff=96, max_seq=256, n_experts=8, expert_top_k=2,
            experts_held=(0, 2))
_KIMI, _GLM, _LAGUNA = (llama.kimi_linear_48b_a3b(), llama.glm_4_7_flash(),
                        llama.laguna_s_2_1())
_FALCON = llama.falcon_h1_34b()
LOOPED = llama.Config(vocab=256, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=4, d_ff=96, max_seq=128, ut_steps=4,
                      sandwich_norm=True, exit_gate=True)

CONFIGS = {
    # Ouro's shape: the stack passed four times, sandwich norms, an exit gate
    "looped": LOOPED,
    "sandwich": dataclasses.replace(LOOPED, ut_steps=1, exit_gate=False),
    # Kimi Linear's: runs of KDA and NoPE latent layers, nothing else
    "runs": dataclasses.replace(
        _KIMI, **_TOY, n_kv_heads=4, kda_heads=4, kda_head_dim=16,
        kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, layer_kinds=_KIMI.layer_kinds[:5]),
    # GLM-4.7-Flash's: a query latent, a rotated key part, a module
    "rotary_latent": dataclasses.replace(
        _GLM, **_TOY, n_kv_heads=4, q_lora_rank=40, kv_lora_rank=24,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
        layer_kinds=_GLM.layer_kinds[:5]),
    # Laguna-S-2.1's: window layers, head gates, YaRN on half a head
    "window": dataclasses.replace(
        _LAGUNA, **{**_TOY, "d_model": 48}, n_kv_heads=2, head_dim=16,
        swa_heads=6, swa_window=24, layer_kinds=_LAGUNA.layer_kinds[:5]),
    # Falcon-H1's: attention and a state-space branch side by side in every
    # layer, constants on the forward pass
    "two_branches": dataclasses.replace(
        _FALCON, vocab=128, d_model=48, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=96, max_seq=256, ssm_heads=4, ssm_head_dim=8,
        ssm_state=16, ssm_chunk=16, layer_kinds=_FALCON.layer_kinds[:2]),
    # the constants alone refuse too: those paths read none of them
    "multipliers": dataclasses.replace(llama.tiny(), embed_multiplier=2.0),
    "experts": llama.moe_tiny(),
    "dropless": dataclasses.replace(llama.moe_tiny(), capacity_factor=None,
                                    moe_aux_coef=0.0),
    # a chip's share of a dropless layer's experts, which stands for the
    # absent chips: what an ep axis has no form for
    "held": dataclasses.replace(llama.moe_tiny(), capacity_factor=None,
                                moe_aux_coef=0.0, experts_held=(0, 2)),
}

_PROMPT = jnp.zeros((1, 8), jnp.int32)


def _ep_mesh():
    return make_mesh({"dp": 2, "ep": 2}, devices=jax.devices()[:4])


def _tp_mesh():
    return make_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])


def _ring(attn):
    """:func:`llama.apply` on a ring: the embedding is read before the
    layers' attention is made, so that leaf is there."""
    return lambda cfg: llama.apply(
        cfg, {"embed": jnp.zeros((cfg.vocab, cfg.d_model))}, _PROMPT,
        attn=attn)


# A case's consumer -> (its name in the table, a call that reaches its check).
CONSUMERS = {
    "decode": ("the decode step", lambda cfg: llama_decode._decode_step(
        cfg, None, None, None, None)),
    "prefill": ("prefill", lambda cfg: llama_decode._prefill(
        cfg, None, None, _PROMPT)),
    "generate": ("make_generate_fn",
                 lambda cfg: llama_decode.make_generate_fn(cfg, 8, 8)),
    "gpipe": ("make_pp_train_step",
              lambda cfg: llama_pipeline.make_pp_train_step(cfg, None, 2)),
    "1f1b": ("make_1f1b_train_step",
             lambda cfg: llama_pipeline.make_1f1b_train_step(cfg, None, 2)),
    "ring": ("attn='ring'", _ring("ring")),
    "ring-xla": ("attn='ring-xla'", _ring("ring-xla")),
    "counts": ("expert_unit_counts", lambda cfg: llama.expert_unit_counts(
        cfg, None, _PROMPT)),
    "heads-on-tp": ("a tp axis", lambda cfg: llama.apply(
        cfg, {"embed": jnp.zeros((cfg.vocab, cfg.d_model))},
        jnp.zeros((2, 8), jnp.int32), mesh=_tp_mesh())),
    "experts-on-ep": ("an ep axis", lambda cfg: llama._moe_ffn(
        cfg, None, jnp.zeros((4, 8, cfg.d_model)), mesh=_ep_mesh())),
}

# (configuration, consumer) -> the phrases its refusal holds.
_GLM_FIELDS = ("q_lora_rank=40", "mtp_layers=1")
_LAGUNA_FIELDS = ("swa_window=24", "attn_gate=True")
_FALCON_FIELDS = ("ssm_heads=4", "ssm_state=16", "ssm_out_multiplier=0.088")
CASES = {
    ("two_branches", "decode"): (
        "recurrent-state cache", "BESIDE a key-value cache in one layer",
        "BlockPool accounts for one kind", *_FALCON_FIELDS),
    ("two_branches", "prefill"): ("the convolution's last taps to seed",
                                  *_FALCON_FIELDS),
    ("two_branches", "generate"): ("two caches of one layer",
                                   *_FALCON_FIELDS),
    ("two_branches", "gpipe"): ("hand-sharded layer with the state-space "
                                "branch", *_FALCON_FIELDS),
    ("two_branches", "1f1b"): ("make_1f1b_train_step", *_FALCON_FIELDS),
    ("two_branches", "ring"): ("a state that crosses sequence shards",
                               *_FALCON_FIELDS),
    ("two_branches", "ring-xla"): ("attn='ring-xla'", *_FALCON_FIELDS),
    ("two_branches", "heads-on-tp"): ("a tp axis", "heads over tp",
                                      *_FALCON_FIELDS),
    ("multipliers", "decode"): ("constants in the one-row path",
                                "embed_multiplier=2.0", "ssm_heads=0"),
    ("looped", "decode"): ("looped configuration", "the decode step"),
    ("looped", "prefill"): ("looped configuration", "prefill"),
    ("looped", "generate"): ("looped configuration", "make_generate_fn"),
    ("looped", "gpipe"): ("looped configuration", "make_pp_train_step",
                          "passed ut_steps times"),
    ("looped", "1f1b"): ("looped configuration", "make_1f1b_train_step"),
    ("looped", "counts"): ("looped configuration", "ut_steps=4",
                           "each recurrent step's routers"),
    # sandwich norms alone refuse too: those paths norm no branch's output
    ("sandwich", "prefill"): ("sandwich_norm=True", "ut_steps=1"),
    ("runs", "decode"): ("recurrent-state cache",),
    ("runs", "prefill"): ("latent cache",),
    ("runs", "generate"): ("two caches",),
    ("runs", "gpipe"): ("stage split by run",),
    ("runs", "1f1b"): ("stage split by run",),
    ("runs", "ring"): ("one head width", "attn='ring'", "kda/moe"),
    ("runs", "ring-xla"): ("one head width", "attn='ring-xla'"),
    ("rotary_latent", "decode"): ("absorbed form of the query latent",
                                  *_GLM_FIELDS),
    ("rotary_latent", "prefill"): ("latent cache to seed decoding",
                                   *_GLM_FIELDS),
    ("rotary_latent", "generate"): ("drafts with the module", *_GLM_FIELDS),
    ("rotary_latent", "gpipe"): ("state before the final norm",
                                 *_GLM_FIELDS),
    ("rotary_latent", "1f1b"): ("state before the final norm", *_GLM_FIELDS),
    ("rotary_latent", "ring"): ("ring form of the latent layer",
                                *_GLM_FIELDS),
    ("window", "decode"): ("rolling cache of swa_window positions",
                           *_LAGUNA_FIELDS),
    ("window", "prefill"): ("rolling cache of the last swa_window positions",
                            *_LAGUNA_FIELDS),
    ("window", "generate"): ("the gate in the one-row path",
                             *_LAGUNA_FIELDS),
    ("window", "gpipe"): ("head count, window and rotation",
                          *_LAGUNA_FIELDS),
    ("window", "1f1b"): ("head count, window and rotation", *_LAGUNA_FIELDS),
    ("window", "ring"): ("ring form of the band", *_LAGUNA_FIELDS),
    ("experts", "gpipe"): ("mixture of experts", "n_experts=4",
                           "aux loss through the stage boundary"),
    ("experts", "1f1b"): ("mixture of experts", "make_1f1b_train_step"),
    ("held", "experts-on-ep"): ("a chip's share of the experts",
                                "experts_held=(0, 2)", "an ep axis",
                                "the absent experts have none"),
}


@pytest.mark.parametrize("config,consumer", list(CASES),
                         ids=["-".join(case) for case in CASES])
def test_the_refusals_say_their_reason(config, consumer):
    with pytest.raises(NotImplementedError) as refused:
        CONSUMERS[consumer][1](CONFIGS[config])
    for phrase in CASES[config, consumer]:
        assert phrase in str(refused.value)


def test_every_row_of_the_table_is_reached():
    """A row whose trait no predicate yields could never fire: every trait
    the table names is one of a configuration here, and each consumer's
    cases above reach every one of its rows."""
    traits = set().union(*(llama._traits(cfg) for cfg in CONFIGS.values()))
    assert traits == {"looped", "runs", "rotary_latent", "window", "experts",
                      "held", "two_branches"}
    for consumer, rows in llama._LACKS.items():
        assert set(rows) <= traits, consumer
    same = {"sandwich": "looped", "multipliers": "two_branches"}
    reached = {(CONSUMERS[consumer][0], same.get(config, config))
               for config, consumer in CASES}
    # the rings share their rows, one name each
    wanted = {(consumer, trait) for consumer, rows in llama._LACKS.items()
              for trait in rows if consumer not in ("attn='ring-zigzag'",
                                                    "attn='ring-xla'")}
    assert wanted <= reached, wanted - reached


def test_what_is_not_refused():
    """The plain stack passes every consumer's check; experts do where the
    consumer has no row for them, dropless or not, an ``ep`` axis too."""
    for consumer in llama._LACKS:
        llama._refuse(llama.tiny(), consumer)
    for consumer in ("the decode step", "prefill", "make_generate_fn",
                     "an ep axis", "a tp axis", "expert_unit_counts",
                     "attn='ring'"):
        llama._refuse(CONFIGS["experts"], consumer)
        llama._refuse(CONFIGS["dropless"], consumer)


@pytest.mark.parametrize("consumer", ["apply-on-ep", "counts-on-ep",
                                      "loss-on-ep"])
def test_a_dropless_configuration_runs_on_an_ep_axis(consumer):
    """What ``apply`` and ``expert_unit_counts`` refused until the sorted
    dispatch had a form over ``ep``, they run: on dp x ep the logits, the
    routed units an expert and the loss with its auxiliary term (the means
    over all the ranks' tokens) are one device's."""
    cfg = dataclasses.replace(CONFIGS["dropless"], moe_aux_coef=0.01,
                              moe_z_coef=1e-3)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    mesh = _ep_mesh()
    sharded = llama.shard_params(params, mesh, cfg)
    run = {
        "apply-on-ep": lambda p, mesh: llama.apply(cfg, p, tokens, mesh=mesh),
        "counts-on-ep": lambda p, mesh: llama.expert_unit_counts(
            cfg, p, tokens, mesh=mesh),
        "loss-on-ep": lambda p, mesh: llama.make_loss_fn(cfg, mesh)(
            p, (tokens, jnp.roll(tokens, -1, 1))),
    }[consumer]
    got = jax.jit(lambda p: run(p, mesh))(sharded)
    want = jax.jit(lambda p: run(p, None))(params)
    if consumer == "counts-on-ep":
        assert (got == want).all() and int(got.sum()) == 2 * 2 * tokens.size
    else:
        assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5)


def _names_the_benchmark_takes():
    """Every ``llama.<name>`` in the files under ``benchmark/``, in code or
    in a docstring that points at the program."""
    names = set()
    for where, _, files in os.walk(os.path.join(ROOT, "benchmark")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(where, name)) as f:
                    names.update(re.findall(r"\bllama\.([A-Za-z_]\w*)",
                                            f.read()))
    return names - {"py"}


def test_the_training_path_stands_alone():
    """In a fresh interpreter ``models.llama`` imports neither of the modules
    built on it nor ``serving``, and holds every name ``benchmark/`` takes
    from it."""
    names = sorted(_names_the_benchmark_takes())
    assert {"Config", "apply", "make_train_step", "_wrap_remat"} <= set(names)
    script = (
        "import sys\n"
        "import torchmpi_tpu.models.llama as llama\n"
        "above = [m for m in sys.modules if m in (\n"
        "    'torchmpi_tpu.models.llama_decode',\n"
        "    'torchmpi_tpu.models.llama_pipeline')\n"
        "    or m.startswith('torchmpi_tpu.serving')]\n"
        "assert not above, above\n"
        f"missing = [n for n in {names!r} if not hasattr(llama, n)]\n"
        "assert not missing, missing\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}
    done = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
