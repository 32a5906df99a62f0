"""Model zoo: MNIST MLP/CNN, ResNet, Llama-style transformer, ViT."""

from . import cnn  # noqa: F401
from . import llama  # noqa: F401
from . import mlp  # noqa: F401
from . import resnet  # noqa: F401
from . import vit  # noqa: F401

#: Every name the step programs write with ``jax.named_scope`` (the models',
#: ``ops/``, ``parallel/moe.py``, the engine's): what ``utils/profiler.py``
#: joins a device capture by, innermost in a path, so in no order here.
SCOPES = (
    "embed", "attn", "attn.qk_norm", "attn.gate", "swa", "mla", "kda", "ssm",
    "ssm.conv", "ssm.norm", "ssd", "ffn", "moe.router", "moe.dispatch",
    "moe.exchange", "moe.experts", "moe.combine", "moe.shared", "final_norm",
    "exit_gate", "head_loss", "mtp", "stem", "conv", "bn", "residual", "pool",
    "fc_loss", "grad_sync", "optimizer")
