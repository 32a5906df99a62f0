"""Hand-written TPU kernels (Pallas) for hot ops.

The reference's only hand kernel is the CUDA reduce kernel saturating HBM
bandwidth for the ring allreduce (reference: lib/detail/reduce_kernel.cu:26-138);
XLA subsumes that on TPU.  The hot op worth hand-tiling here is attention —
the MXU/VMEM blocking of flash attention feeds both the single-chip path and
the per-step block compute of ring attention (parallel/sequence.py).
``ops/kda.py`` is the other mixer's hot op, the chunked gated delta rule of
KDA linear-attention layers, forward and a hand-written backward, in two
Pallas kernels where a head fills whole lanes (``from torchmpi_tpu.ops import
kda``; the function is ``kda.kda``), and ``ops/kda_mixer.py`` the layer's
passes round it (``kda_mixer.kda_mixer``: short convolutions, SiLU, norms,
decay and gate, a fused kernel each way in and out).  ``ops/tgmm.py`` is the
grouped matmul of a weight gradient that adds into float32 sums it is given
(``tgmm.tgmm_add``: megablox's ``tgmm`` with the empty groups not visited),
which the ``ep`` path's backward pass sums the experts' gradients with.
``ops/scatter_add_rows.py`` adds a pass's rows to the float32 sums of the
tokens they name, one expert's segment at a time, by DMA through VMEM and in
place (``scatter_add_rows.scatter_add_rows``), where the expert layer's
passes ran XLA's scatter-add.
``ops/ssd.py`` is Mamba-2's state-space scan in its chunked form, forward and
a hand-written backward with a float32 state, as XLA products (``ssd.ssd``),
and the passes round it (``ssd.conv_silu``, ``ssd.gated_norm``); the layer
that runs it imports it, this package does not.
"""

from .flash_attention import flash_attention  # noqa: F401
