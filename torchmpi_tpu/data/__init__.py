"""Streaming input data plane — the first-class input subsystem.

Round 5 (no longer reproducible) measured host->device staging at
+2944.75 ms/step for 39 MB/batch against a 45.5 ms compute step: the
headline throughput only held because the bench kept data resident on
device.  The reference
hides exactly this class of host work inside the backward pass (async
prefetch hooks pipelined into ``onBackwardCriterion``, PAPER.md:16,34);
this package is the TPU-native analogue — background staging overlapped
with the running compiled step, grown out of the ``utils/data.py``
skeleton into a hardened subsystem:

* :mod:`~torchmpi_tpu.data.staging` — ``Staged`` + ``stage_rank_major``,
  the single host->device placement contract (moved here from
  ``utils/data.py``, which re-exports them).
* :mod:`~torchmpi_tpu.data.host` — ``HostStage``: bounded multi-worker
  host-side production with deterministic order, exception propagation,
  and leak-free abandonment.
* :mod:`~torchmpi_tpu.data.device` — ``DeviceStage``: background
  ``jax.device_put`` with the step's ``NamedSharding``, ``depth``
  in-flight device buffers, reusable host cast buffers, and the
  per-batch ``staged_bytes`` / wait-time feed into the obs registry.
* :mod:`~torchmpi_tpu.data.pipeline` — ``DataPipeline`` composition and
  ``engine_wrap``, the engine's knob-gated input adapter
  (``data_pipeline: off|on|auto``).

Dataset loading (``load_mnist``, ``synthetic_mnist``) and the epoch
sharder (``ShardedIterator``) stay in ``utils/data.py`` — they are data
*sources*; this package is the plane that moves their batches.
See docs/data.md.
"""

from .device import DeviceStage, StageStats
from .host import HostStage, HostStageIterator
from .pipeline import DataPipeline, engine_wrap
from .staging import HostScratchPool, Staged, stage_rank_major

#: compatibility aliases: the seed names, now hardened (see docs/data.md).
ThreadedIterator = HostStage
DevicePrefetchIterator = DeviceStage

__all__ = [
    "DataPipeline",
    "DevicePrefetchIterator",
    "DeviceStage",
    "HostScratchPool",
    "HostStage",
    "HostStageIterator",
    "StageStats",
    "Staged",
    "ThreadedIterator",
    "engine_wrap",
    "stage_rank_major",
]
