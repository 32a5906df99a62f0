"""Training meters (reference: torchnet's AverageValueMeter / ClassErrorMeter
used in every example, e.g. examples/mnist/mnist_allreduce.lua:36-38)."""

from __future__ import annotations

import math
from typing import Optional, Sequence

import numpy as np


class AverageValueMeter:
    """Running mean/std of scalar values.

    Accepts device scalars (jax arrays) with ZERO device work in the hot
    loop: ``add`` only appends the handle, and the sums materialise in one
    batched fold at read time.  Per-step device arithmetic here would both
    serialize host and device and — on dispatch-latency-bound paths (any
    low-latency step loop) — cost milliseconds per step in tiny kernel
    launches (measured +3.9 ms/step on the v5e bench in round 3, before
    this deferral; the reason the reference brackets its timers away from
    the step loop).
    """

    def __init__(self) -> None:
        self.reset()

    def reset(self) -> None:
        self.n = 0
        self.sum = 0.0          # host floats after each fold
        self.sum_sq = 0.0
        self._pending = []      # [(device scalar, weight)] awaiting the fold

    # Fold cadence bound: keeps the live device-handle list (and the
    # eventual batched device_get) bounded on long epochs where nothing
    # reads the meter.  The newest _KEEP_HOT entries stay deferred so the
    # drain only touches scalars whose steps finished long ago — the hot
    # loop never blocks on in-flight work.
    _MAX_PENDING = 512
    _KEEP_HOT = 8

    def add(self, value, n: int = 1) -> None:
        if hasattr(value, "astype"):
            # Defer: no device ops in the hot loop (fold happens at read).
            self._pending.append((value, n))
            self.n += n
            if len(self._pending) >= self._MAX_PENDING:
                hot = self._pending[-self._KEEP_HOT:]
                self._pending = self._pending[:-self._KEEP_HOT]
                self._fold()
                self._pending = hot
            return
        self.sum = self.sum + value * n
        self.sum_sq = self.sum_sq + value * value * n
        self.n += n

    def _fold(self) -> None:
        if not self._pending:
            return
        import jax

        # device_get, NOT a jnp computation: launching a fresh multi-device
        # XLA program from a metrics read can interleave with in-flight
        # training dispatches and wedge the CPU backend's collective
        # rendezvous (8 device threads on few cores).  Pipelined transfers
        # have no rendezvous.  Widening to f64 host-side keeps the running
        # sum absorbing ~2.0-sized losses regardless of the wire dtype.
        vals = np.asarray(
            jax.device_get([v for v, _ in self._pending]), dtype=np.float64)
        ws = np.asarray([n for _, n in self._pending], np.float64)
        self.sum = self.sum + float((vals * ws).sum())
        self.sum_sq = self.sum_sq + float((vals * vals * ws).sum())
        self._pending = []

    def value(self):
        if self.n == 0:
            return float("nan"), float("nan")
        self._fold()
        mean = self.sum / self.n
        var = max(self.sum_sq / self.n - mean * mean, 0.0)
        return mean, math.sqrt(var)

    @property
    def mean(self) -> float:
        return self.value()[0]


class ClassErrorMeter:
    """Top-k classification error in percent."""

    def __init__(self, topk: Sequence[int] = (1,)) -> None:
        self.topk = tuple(topk)
        self.reset()

    def reset(self) -> None:
        self.n = 0
        self.errors = {k: 0 for k in self.topk}

    def add(self, logits: np.ndarray, targets: np.ndarray) -> None:
        logits = np.asarray(logits)
        targets = np.asarray(targets).reshape(-1)
        n = targets.shape[0]
        order = np.argsort(-logits.reshape(n, -1), axis=1)
        for k in self.topk:
            hit = (order[:, :k] == targets[:, None]).any(axis=1)
            self.errors[k] += int(n - hit.sum())
        self.n += n

    def value(self, k: Optional[int] = None) -> float:
        if k is None:
            k = self.topk[0]
        if self.n == 0:
            return float("nan")
        return 100.0 * self.errors[k] / self.n
