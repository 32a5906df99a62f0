"""Device stage: background host->device staging that overlaps the step.

The seed's ``DevicePrefetchIterator`` staged on the CONSUMER thread —
``jax.device_put`` is async so the *transfer* overlapped compute, but
the host-side reshape/cast ran inside the training loop's thread,
exactly the blocked window round 5 (no longer reproducible) measured at
+2944.75 ms/step for 39 MB/batch.  :class:`DeviceStage` moves the whole staging
call onto a producer thread: the reshape, the cast (through a reusable
:class:`~torchmpi_tpu.data.staging.HostScratchPool` buffer), and the
``device_put`` dispatch with the step's ``NamedSharding`` all run in the
background while the compiled step executes, keeping up to ``depth``
staged batches in flight (the TPU-native form of the reference's
async-prefetch-hidden-in-backward idiom, PAPER.md:16,34).

Yields ``(Staged, Staged)`` pairs; the x-side ``Staged`` carries
``wait_s`` — how long the consumer actually blocked waiting for the
pair — which the engine's overlap gauge reads instead of charging its
``engine.stage`` handoff span.

Lifecycle hardening matches :mod:`~torchmpi_tpu.data.host`: producer
exceptions surface on the consumer, an abandoned iterator releases its
thread promptly, and the bounded queue means a slow consumer holds at
most ``depth + 2`` staged batches (queue + producer hand + consumer
hand) of device memory.

Observability: when the live feed is on (``obs.serve.metrics_feed``),
every consumed batch publishes ``tmpi_data_staged_bytes_total``,
``tmpi_data_stage_seconds`` and the ``tmpi_data_input_overlap_fraction``
gauge through :func:`obs.serve.publish_input`; the same numbers
accumulate unconditionally in :class:`StageStats` (plain Python ints and
floats — reading them costs nothing per step), which ``bench.py``'s
non-resident mode reads for the BENCH artifact.
"""

from __future__ import annotations

import queue as _queue
import threading
import time
from typing import Any, Optional

from .host import _DONE, _bounded_get, _bounded_put
from .staging import HostScratchPool, Staged, stage_rank_major

__all__ = ["DeviceStage", "StageStats"]


def _produce(source, sharding, cast, scratch, q: _queue.Queue,
             stop: threading.Event) -> None:
    """Producer thread body — module-level over the shared primitives on
    purpose (a bound-method target would pin the iterator alive through
    its own thread and abandonment could never release it; see
    :mod:`~torchmpi_tpu.data.host`)."""
    try:
        for batch in source:
            xb, yb = batch
            t0 = time.monotonic()
            sx = stage_rank_major(xb, sharding, cast=cast, scratch=scratch)
            sy = stage_rank_major(yb, sharding)
            stage_s = time.monotonic() - t0
            nbytes = int(sx.array.nbytes) + int(sy.array.nbytes)
            if not _bounded_put(q, stop, (sx, sy, nbytes, stage_s)):
                return
            if stop.is_set():
                return
    except BaseException as e:  # noqa: BLE001 — forwarded to consumer
        _bounded_put(q, stop, e)
        return
    _bounded_put(q, stop, _DONE)


class StageStats:
    """Per-iteration staging totals (one instance per ``iter()`` pass;
    the owning :class:`DeviceStage` keeps the latest as ``.stats``)."""

    def __init__(self) -> None:
        self.batches = 0
        self.staged_bytes = 0
        self.stage_s = 0.0      # producer time inside stage_rank_major
        self.wait_s = 0.0       # consumer block time in __next__
        self.interval_s = 0.0   # consumer wall time spanned by fetches

    def overlap_fraction(self) -> float:
        """Fraction of the consumer's inter-fetch wall time the input
        plane did NOT block it — 1.0 is a perfectly hidden input plane."""
        if self.interval_s <= 0.0:
            return 1.0
        return max(0.0, min(1.0, 1.0 - self.wait_s / self.interval_s))

    def snapshot(self) -> dict:
        return {
            "batches": self.batches,
            "staged_bytes": self.staged_bytes,
            "staged_bytes_per_batch": (
                self.staged_bytes // self.batches if self.batches else 0),
            "stage_s": round(self.stage_s, 6),
            "wait_s": round(self.wait_s, 6),
            "interval_s": round(self.interval_s, 6),
            "overlap_fraction": round(self.overlap_fraction(), 4),
        }


class DeviceStage:
    """Wraps a rank-major batch iterator, staging batches onto the device
    mesh from a background thread, ``depth`` batches ahead of compute.

    ``cast`` optionally converts the input images (e.g. to bfloat16) on
    the host before transfer, halving PCIe traffic for the bf16 path.
    ``reuse_host_buffers`` routes the cast through a
    :class:`HostScratchPool` (safe only where ``device_put`` copies; the
    pipeline disables it on the CPU backend, where host memory may be
    aliased).  ``publish`` (default: the live-feed gate) controls the
    per-batch registry feed.
    """

    def __init__(self, it, mesh, axis: Optional[str] = None, depth: int = 2,
                 cast=None, reuse_host_buffers: bool = False,
                 publish: Optional[bool] = None):
        from jax.sharding import NamedSharding, PartitionSpec

        if axis is None:
            from ..runtime.communicator import RANK_AXIS as axis

        self.it = it
        self.sharding = NamedSharding(mesh, PartitionSpec(axis))
        self.depth = max(1, int(depth))
        self.cast = cast
        self.reuse_host_buffers = bool(reuse_host_buffers)
        self.publish = publish
        self.stats = StageStats()

    def __len__(self):
        return len(self.it)

    def __iter__(self) -> "DeviceStageIterator":
        self.stats = StageStats()
        return DeviceStageIterator(self)


class DeviceStageIterator:
    """One epoch's live staging iterator (same lifecycle contract as
    :class:`~torchmpi_tpu.data.host.HostStageIterator`)."""

    def __init__(self, stage: DeviceStage):
        self._stage = stage
        self._stats = stage.stats
        self._stop = threading.Event()
        # maxsize=depth staged pairs queued; with the pair in the
        # producer's hand and the one the consumer holds, in-flight
        # device buffers are bounded at depth + 2.
        self._q: _queue.Queue = _queue.Queue(maxsize=stage.depth)
        self._exhausted = False
        self._last_fetch: Optional[float] = None
        scratch = (HostScratchPool(stage.depth + 2)
                   if (stage.reuse_host_buffers and stage.cast is not None)
                   else None)
        self._thread = threading.Thread(
            target=_produce,
            args=(stage.it, stage.sharding, stage.cast, scratch, self._q,
                  self._stop),
            daemon=True, name="tmpi-data-device")
        self._thread.start()

    # -------------------------------------------------------- consumer

    def __iter__(self) -> "DeviceStageIterator":
        return self

    def __next__(self):
        if self._exhausted or self._stop.is_set():
            raise StopIteration
        t0 = time.monotonic()
        item = _bounded_get(self._q, self._stop, self._thread)
        now = time.monotonic()
        if item is _DONE:
            self._exhausted = True
            self.close()
            raise StopIteration
        if isinstance(item, BaseException):
            self._exhausted = True
            self.close()
            raise item
        sx, sy, nbytes, stage_s = item
        wait_s = now - t0
        stats = self._stats
        stats.batches += 1
        stats.staged_bytes += nbytes
        stats.stage_s += stage_s
        stats.wait_s += wait_s
        if self._last_fetch is not None:
            stats.interval_s += now - self._last_fetch
        else:
            # First fetch: the pipeline had the whole warmup to work in;
            # count only the measured wait so a cold start doesn't read
            # as free overlap.
            stats.interval_s += wait_s
        self._last_fetch = now
        publish = self._stage.publish
        if publish is None:
            from ..obs import serve as _serve
            publish = _serve.metrics_feed()
        if publish:
            from ..obs import serve as _serve
            _serve.publish_input(
                staged_bytes=nbytes, stage_s=stage_s,
                overlap_fraction=stats.overlap_fraction())
        return (Staged(sx.array, wait_s=wait_s), sy)

    def close(self) -> None:
        self._stop.set()
        if self._thread is not threading.current_thread():
            self._thread.join(timeout=5)

    def __del__(self):  # pragma: no cover - exercised via the leak test
        try:
            self._stop.set()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass

    def __enter__(self) -> "DeviceStageIterator":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()
