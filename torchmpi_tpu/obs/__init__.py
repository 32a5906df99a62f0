"""Unified observability subsystem (tracing + metrics + export).

TorchMPI's operability story stopped at nvprof step-window brackets and
stderr warnings (SURVEY §5.1); the chaos PR left the host planes' raw
C-ABI counters (``tmpi_ps_retry_count`` ...) as disconnected peepholes
with no timeline.  This package is the timeline — the Horovod-timeline /
TAU-style tracing discipline (PAPERS.md: Sergeev & Del Balso 2018;
Shende & Malony 2006) for the whole stack:

* :mod:`.tracer`  — thread-safe Python span tracer with contextvar
  correlation ids.  An engine step, the host collective it dispatched,
  and the native frames that carried it share ONE id.
* :mod:`.native`  — the Python side of the native trace rings in
  ``_native/hostcomm.cpp`` / ``_native/ps.cpp`` (``tmpi_*_trace_drain``
  and friends): knob plumbing (``obs_*``), bulk drain into numpy
  structured arrays, op/phase name tables.
* :mod:`.metrics` — counters/gauges/histograms registry that auto-scrapes
  the existing C-ABI counters and exports Prometheus text + JSON.
* :mod:`.export`  — merges native events, Python spans and the
  ``jax.profiler`` xplane capture's device timeline into one Chrome/Perfetto
  trace JSON; ``merge_ranks`` joins N per-rank obsdump bundles onto one
  clock-aligned timeline with cross-rank flow arrows; computes the
  span-join and flow-join rates.
* :mod:`.clocksync` — ping-pong clock alignment over the hostcomm plane
  (midpoint estimator, min-RTT round wins): per-rank
  ``(offset_ns, uncertainty_ns)`` as a ``ClockMap``, optionally applied
  at the stamp source (tracer + native rings).
* :mod:`.aggregate` — per-rank ``obsdump-<rank>.json`` bundles (on
  demand and at shutdown) and the straggler/skew detector over aligned
  collective start events.
* :mod:`.flight` — the failure flight recorder: bounded post-mortem
  bundles dumped when ``runtime/failure.py`` or the PS failover paths
  trip (``obs_flight`` knobs).
* :mod:`.numerics` — the training-health plane: in-step sentinel
  statistics fused into the compiled step (``numerics_mode`` knob), the
  cross-rank parameter-fingerprint auditor (blake2b digests allgathered
  over the hostcomm plane, binary drill-down to the first divergent
  leaf + outlier rank), the ``diverged`` /healthz state, and the
  ``tmpi_step_flops``/``tmpi_mfu_estimate`` compute-efficiency gauges.
* :mod:`.serve` — the LIVE plane: a per-rank HTTP endpoint (stdlib
  ``http.server`` daemon thread, loopback by default; ``obs_http*``
  knobs) serving ``/metrics`` (live Prometheus), ``/healthz`` (the
  healthy/degraded/stalled/draining state machine), ``/spans``,
  ``/journal``, ``/history`` and ``POST /flight``; started/stopped by
  ``runtime/lifecycle.py``.
* :mod:`.journal` — the persistent per-rank event journal (JSONL
  segments, rotation + shared retention, crash-safe appends;
  ``journal_*`` knobs): every discrete state change the planes above
  compute — health transitions, elastic restores, PS failovers,
  autotune cache verdicts, numerics audits, chaos injections — lands as
  one replayable line (docs/history.md).
* :mod:`.history` — the bounded on-disk metrics history: a background
  sampler over ``Registry.collect()`` into downsampling tier rings with
  ``rate``/``drift`` trend queries (``history_*`` knobs) — the sensor a
  step-rate trend column, an autoscaler policy, or a continuous-tuning
  controller polls.
* :mod:`.alerts` — the declarative alerting & SLO plane: rules
  (threshold / absence / rate / drift / movement / share / mark-age)
  over the metrics history with the pending→firing→resolved lifecycle,
  a default pack encoding the stack's known failure signatures,
  phase-attributed firings (``tmpi_step_phase_seconds``), journal +
  flight + ``/healthz`` integration, ``GET /alerts`` + ``tmpi-trace
  alerts`` (``alert_*`` knobs; docs/alerts.md).
* :mod:`.rca` — the automated postmortem behind ``tmpi-trace why``:
  journals + flight bundles + history merged onto one timeline, walked
  by a weighted causality rulebook into a ranked root-cause verdict
  with the evidence chain.
* :mod:`.cluster` — the aggregator over those endpoints: bounded-timeout
  federation (a dead rank reads ``unreachable``, never hangs the sweep),
  the job-level health verdict + live straggler attribution, one merged
  ``/metrics`` federation document, and the ``tmpi-trace top`` table.
* CLI ``python -m torchmpi_tpu.obs`` / ``tmpi-trace`` — snapshot, merge,
  merge-ranks, dump, report, top, serve, journal, why, and the
  instrumented drills producing the ``OBS_r06.json`` /
  ``OBS2_r07.json`` / ``OBSLIVE_r09.json`` / ``NUMERICS_r12.json`` /
  ``RCA_r13.json`` artifacts.

Everything is gated by the ``obs_*`` knobs (``runtime/config.py``;
registry rows in docs/config.md).  With ``obs_trace`` off — the default —
tracing costs one relaxed atomic branch per native emit site and one
shared no-op context per Python span site.
"""

from __future__ import annotations

from . import aggregate, alerts, clocksync, cluster, export  # noqa: F401
from . import flight, history, journal, rca  # noqa: F401
from . import metrics, native, numerics, serve, tracer  # noqa: F401
from .clocksync import ClockMap  # noqa: F401
from .export import chrome_trace, merge_ranks, span_join_rate  # noqa: F401
from .metrics import registry  # noqa: F401
from .native import apply_config, drain_events  # noqa: F401
from .tracer import current_correlation, enabled, span  # noqa: F401
