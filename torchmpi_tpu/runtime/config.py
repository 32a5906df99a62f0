"""Tunable runtime constants — the TPU-native equivalent of the reference's
mutable-global flag system (reference: lib/constants.cpp:129-352, lib/constants.h:21-80).

The reference exposes every performance knob as a C++ mutable global with an
``extern "C"`` get/set pair and a (never-enabled) ``immutableConstants`` freeze
guard (reference: resources.cpp:83-85).  Here the same taxonomy lives in one
typed registry: algorithm switches (hierarchical vs flat, staged vs direct,
cartesian vs tree), small-message cutoffs, buffer geometry, pool sizes.

Unlike the reference we actually honour the freeze: :func:`freeze` makes every
subsequent :func:`set` raise, which matters on TPU because knobs that feed
compiled programs (bucket bytes, chunk counts) must not change once a step has
been traced and cached.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any, Callable, Dict, Optional


def _env(name: str, default: Any, cast: Callable[[str], Any]) -> Any:
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return cast(raw)
    except (TypeError, ValueError):
        return default


def _env_bool(name: str, default: bool) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() in ("1", "true", "yes", "on")


@dataclasses.dataclass
class Constants:
    """All runtime knobs, mirroring the reference's taxonomy.

    Names keep the reference's meaning; values keep its defaults where the
    default still makes sense on TPU (reference: lib/constants.cpp:129-155).
    """

    # --- algorithm switches (reference: constants.cpp:129-141) ---
    # (The reference's kUseStagedCollectives — staged-via-pinned-host vs
    # direct GDR inter-node transfers — has no TPU analogue to switch:
    # PJRT owns device<->host staging and XLA owns DCN transfer shape, so
    # the knob is intentionally absent rather than present-but-unread.)
    # Hierarchical (intra-slice ICI x inter-host DCN) vs flat collectives.
    use_hierarchical_collectives: bool = True
    # Cartesian (regular 2-D mesh) vs tree (uneven groups) communicator splits.
    use_cartesian_communicators: bool = True
    use_tree_communicators: bool = False
    # Prefer the custom Pallas ring collectives over XLA's where available
    # (the reference's "custom p2p rings over the vendor library" switch,
    # README.md:106; off by default — XLA's rings are the vendor fast path).
    use_pallas_collectives: bool = False

    # --- small-message cutoffs (ELEMENT counts, like the reference's
    # nElement switch): below these, latency-optimised paths win
    # (reference: constants.cpp:142-147; allreduce 1<<16).  "cpu" = the
    # host/DCN plane (hostcomm rings: single-piece transfers below the
    # cutoff); "gpu" = the device plane (selector: the pallas ring falls
    # back to the fused-XLA path below the cutoff,
    # reference collectives_cuda.cpp:641-648).  The reference's separate
    # bcast cutoffs chose stock-MPI vs p2p transports; with one transport
    # per plane here, broadcast is governed by bcast_size_tree_based alone.
    small_allreduce_size_cpu: int = 1 << 16
    small_allreduce_size_gpu: int = 1 << 16
    # At or below this, host-plane broadcast moves as a single piece (the
    # latency path standing in for the reference's tree mode); above it,
    # buffer-size chunked pipeline (reference: constants.cpp:148-149, 1<<22).
    bcast_size_tree_based: int = 1 << 22

    # --- buffer geometry for chunked/ring paths: these two feed the pallas
    # ring kernels (sub-chunk pipelining, staging slot count); the _cpu pair
    # below feeds the hostcomm rings' transfer piece size
    # (reference: constants.cpp:150-152; min 1<<17, max 1<<20, 3 buffers) ---
    min_buffer_size: int = 1 << 17
    max_buffer_size: int = 1 << 20
    # Host-plane (hostcomm TCP ring) piece sizes, separate from the device
    # knobs above the way the reference splits CPU/GPU buffer constants:
    # the planes have different optima.  Defaults from the round-4 measured
    # sweep (benchmarks/hostcomm_bench.py, 4 real processes on loopback):
    # 256 KiB pieces beat 1 MiB by ~1.8x at 4-16 MB payloads (pipelined
    # reduce overlaps the receive), and beat 64 KiB except under heavy
    # host contention — BASELINE.md round-4 table.
    min_buffer_size_cpu: int = 1 << 17
    max_buffer_size_cpu: int = 1 << 18
    num_buffers_per_collective: int = 3
    # Cap on staging slots per ring collective
    # (reference: resources.h kMaxNumBuffersPerCollectiveGPU = 16).
    max_num_buffers_per_collective_tpu: int = 16

    # --- async machinery (reference: constants.cpp:152-155).  The
    # reference's collective offload pool is subsumed by JAX async dispatch
    # (no thread pool to size); the PS pool survives in ps.cpp ---
    num_async_collectives_in_flight: int = 1 << 20
    parameterserver_offload_pool_size: int = 4

    # Engine dispatch-depth bound: the compiled train loop and both eval
    # loops keep at most this many steps in flight, blocking on the OLDEST
    # step's loss when the window fills (eager *training* needs no bound —
    # its per-step gradient sync already blocks).  0 = auto = 8: the
    # multi-device CPU backend needs a bound (its collective rendezvous can
    # be starved into its fatal stuck-detector by unbounded host run-ahead
    # — observed on a 1-core host with 8 virtual devices) and on a v5e chip
    # a window of 8 costs nothing (engine/sgdengine.py _bound_inflight has
    # the PR 21 numbers).  Negative = unbounded.
    engine_max_inflight_steps: int = 0

    # How the engine's eager_async mode drains its async bucket
    # allreduces (nn.async_):
    #   "ready"   — drain AT THE OPTIMIZER BOUNDARY: as each bucket's
    #               collective completes, that bucket's parameters update
    #               immediately while later buckets are still in flight
    #               (the reference's registerAsyncMPIBackward pipeline,
    #               nn.lua:112-213; PyTorch DDP's bucketed overlap).  The
    #               engine's overlap-fraction gauge then measures REAL
    #               overlap: only actual wait time counts as blocked.
    #   "barrier" — the old discipline: wait every handle after backward,
    #               then update (kept as the A/B baseline the BENCH
    #               artifact's overlap section compares against).
    # Numerically identical either way (same per-leaf update on the same
    # reduced values; pinned by tests/test_autotune.py).
    engine_async_drain: str = "ready"

    # --- streaming input data plane (torchmpi_tpu/data/: host stage ->
    # device stage -> engine; all reads funnel through
    # data/pipeline.py:knob_defaults — see docs/data.md) ---
    # Engine input adapter mode (engine_wrap, compiled mode only):
    #   "off"  — the seed staging path bit-for-bit: the engine stages
    #            every batch synchronously inside the step (a +2944
    #            ms/step cliff on host batches in round 5, no longer
    #            reproducible).
    #   "on"   — every train()/test() iterator that is not already a
    #            pipeline is wrapped in DataPipeline.
    #   "auto" — (default) like "on", but a materialized list of
    #            pre-staged Staged pairs (device-resident data; nothing
    #            to overlap) passes through untouched.
    data_pipeline: str = _env("TORCHMPI_TPU_DATA_PIPELINE", "auto", str)
    # Staged batches the device stage keeps in flight beyond the one the
    # consumer holds (bounded queue = backpressure: a slow consumer holds
    # at most depth + 2 batches of device memory).
    data_prefetch_depth: int = _env("TORCHMPI_TPU_DATA_PREFETCH_DEPTH",
                                    2, int)
    # Host-stage transform worker threads (0 = single producer, no pool).
    # Only meaningful with a per-batch transform; order stays
    # deterministic at any worker count (sequence-number reordering).
    data_host_workers: int = 0
    # Bound (batches) on the host stage's output queue; total host-stage
    # in-flight memory is data_host_depth + data_host_workers batches.
    data_host_depth: int = 4
    # Reuse host-side cast buffers (HostScratchPool) instead of
    # allocating per batch; forced off on the CPU backend, where
    # device_put may alias host memory (docs/data.md "Buffer reuse").
    data_reuse_host_buffers: bool = True

    # --- collective wire dtypes (the device-plane counterpart of the
    # hostcomm/PS wire-dtype taxonomy: bf16/f16/i8 wires on the host planes,
    # hostcomm.py:29-49 / ps.cpp Dtype enum) ---
    # Wire dtype for the gradient/activation psums inside MANUAL shard_map
    # regions (Megatron f/g markers, the manual-tp 1F1B stage's collectives,
    # the tp-sharded CE backward, the 1F1B gradient aggregation psums):
    #   "auto"     — bf16 on the TPU backend, f32 elsewhere.  XLA-CPU's
    #                AllReducePromotion pass crashes on bf16 all-reduce
    #                inside partial-manual regions, while the TPU pipeline
    #                compiles them clean — proven by AOT compilation against
    #                named TPU topologies (runtime/topology.py,
    #                TOPOLOGY_r06.json), which is what gates this knob.
    #   "bfloat16" — force bf16 wires (half the f32 bytes per collective).
    #   "float32"  — force f32 wires (full partial-sum accuracy; the old
    #                unconditional behaviour).
    manual_wire_dtype: str = _env("TORCHMPI_TPU_MANUAL_WIRE_DTYPE",
                                  "auto", str)

    # --- gradient bucketing (new, TPU-specific: fuse per-parameter tensors
    # into flat buckets so allreduce rides ICI at full bandwidth;
    # the reference allreduces per-parameter tensors, nn.lua:49-56) ---
    gradient_bucket_bytes: int = 32 * 1024 * 1024
    # Async backward syncs gradients every N steps; intermediate steps
    # update with local gradients (reference: nn.lua syncGradientFrequency,
    # nn.lua:112-213).
    sync_gradient_frequency: int = 1

    # --- measured collective autotuner (collectives/autotune.py; the
    # reference's per-tensor collectiveSelector choice made measured —
    # see docs/autotune.md) ---
    # Selector dispatch mode:
    #   "off"    — (default) the static preference table, bit-for-bit the
    #              pre-autotune behaviour; resolve() costs one extra
    #              config read and nothing else.
    #   "cache"  — payload-keyed resolutions consult the persisted winner
    #              cache (validated against the topology fingerprint; a
    #              stale cache is NEVER applied).
    #   "online" — cache winners, with each candidate's measured ms
    #              replaced by its production mean from the
    #              tmpi_collective_seconds histograms once enough samples
    #              exist — long-running jobs converge on live traffic.
    autotune_mode: str = _env("TORCHMPI_TPU_AUTOTUNE_MODE", "off", str)
    # Winner-cache file ("" = ~/.cache/torchmpi_tpu/autotune.json).
    autotune_cache_path: str = _env("TORCHMPI_TPU_AUTOTUNE_CACHE_PATH",
                                    "", str)
    # Interleaved best-of trials per cell in the explicit pass (each trial
    # times every candidate once; a candidate keeps its best block).
    autotune_trials: int = 3
    # Warmup calls per candidate before its first timed block.
    autotune_warmup: int = 1
    # Timed reps per block; 0 = auto from a ~4 MiB payload-byte budget
    # (floor 2, cap 16 — the hostcomm_bench budget discipline).
    autotune_reps: int = 0
    # Minimum histogram samples before an "online" decision trusts a
    # production mean over the pass's measured ms for a candidate.
    autotune_online_min_samples: int = 20

    # (The reference's PS tag constants — kSentinelTag instance*tag
    # disambiguation, resources.h:61-73 — are subsumed by the framed-TCP
    # header carrying the instance id explicitly; no knob to keep.)

    # --- diagnostics ---
    # Progress-warning interval on host-plane collective waits: a peer
    # making no progress for this long prints a deadlock warning and the
    # wait continues ("this looks like a deadlock!", reference
    # resources.cpp:124-133 — a diagnostic, not an abort).
    deadlock_timeout_seconds: float = 10.0
    verbose: int = _env("TORCHMPI_TPU_VERBOSE", 0, int)

    # --- host-plane hardening (hostcomm TCP rings, _native/hostcomm.cpp) ---
    # Hard no-progress deadline per blocking ring wait, in ms.  0 keeps the
    # reference's warn-forever semantics (the spin-with-timeout detector
    # above); > 0 aborts the collective and surfaces a typed
    # HostcommTimeout to Python with rank/op/bytes-progressed context, so
    # run_elastic can ride a sick network instead of hanging on it.
    hc_io_deadline_ms: int = _env("TORCHMPI_TPU_HC_IO_DEADLINE_MS", 0, int)
    # CRC32 trailer on every hostcomm data frame, verified on receive
    # (HostcommCorruption on mismatch).  Off by default so benches can
    # measure its cost against the seed fast path.
    hc_frame_crc: bool = _env_bool("TORCHMPI_TPU_HC_FRAME_CRC", False)

    # --- parameter-server client resilience (_native/ps.cpp) ---
    # Max request attempts per PS operation (connect + send + reply); the
    # seed behaviour was a single reconnect (2 attempts).  Retries honour
    # the idempotency split: a send-side failure always retries, a lost
    # reply only for idempotent ops (pull/create/free/ping — never a
    # rule=add push).
    ps_retry_max: int = 4
    # Exponential backoff between attempts: base * 2^attempt plus jitter,
    # capped at the max.
    ps_retry_backoff_ms: int = 50
    ps_retry_backoff_max_ms: int = 2000
    # Per-request socket deadline (SO_RCVTIMEO/SO_SNDTIMEO) in ms; 0 waits
    # forever (seed semantics).  An expired deadline counts in
    # tmpi_ps_timeout_count and fails the attempt (retried per the
    # idempotency rules above).
    ps_request_deadline_ms: int = 0
    # CRC32 trailers on PS frames (push payloads verified server-side with
    # a retriable NACK — the rule has NOT run, so re-sending is safe even
    # for rule=add; pull replies verified client-side).  Mismatches count
    # in tmpi_ps_crc_failure_count.
    ps_frame_crc: bool = False

    # --- parameter-server durability + crash-restart failover
    # (_native/ps.cpp snapshot engine; parameterserver/__init__.py failover;
    # see docs/parameterserver.md "Durability & crash-restart failover") ---
    # Server-side durable snapshot directory ("" = durability off).  When
    # set, init_cluster restores the newest snapshot that VALIDATES (CRC
    # trailer + bounds, torn files skipped) and starts the cadence writer;
    # snapshots are fsync'd and atomically renamed like checkpoints.
    ps_snapshot_dir: str = _env("TORCHMPI_TPU_PS_SNAPSHOT_DIR", "", str)
    # Cadence of the background snapshot writer in ms (0 = on-demand
    # tmpi_ps_snapshot only).  Effective immediately for running servers.
    ps_snapshot_interval_ms: int = 0
    # Epoch fence for non-idempotent pushes: pushes carry the server epoch
    # learned at registration; a server restarted from a snapshot serves a
    # NEW epoch and NACKs stale pushes (rule never runs), and the client's
    # failover re-seeds the shard via an idempotent `copy` of its local
    # shadow before replaying — `add` pushes land exactly once across a
    # server SIGKILL.  Off = the seed behaviour (replay blindly; a push
    # whose apply survived into the snapshot double-counts).
    ps_epoch_fence: bool = True
    # Client failover budget after an exhausted request-retry budget or an
    # epoch-fence NACK: reconnect pings (0 = failover off, failures raise
    # PSTransportError immediately) and the base backoff between them
    # (exponential, capped at ~2s) — sized to span a supervisor restart.
    ps_failover_max: int = 8
    ps_failover_backoff_ms: int = 250

    # --- parameter-server replication & shard placement (the N-server
    # group; placement ring in parameterserver/placement.py, forwarding +
    # drain/handoff in _native/ps.cpp; see docs/parameterserver.md
    # "Replication & shard placement") ---
    # Master switch.  Off (default): the seed contract exactly — shard k
    # lives on endpoints[k], no backups, no placement ring on any path.
    # On: shard keys place onto servers via deterministic consistent
    # hashing, each shard gets a backup server the primary forwards
    # applied pushes to, a dead primary is PROMOTED away from (the backup
    # becomes the owner), and live handoff can drain a server mid-run.
    ps_replication: bool = False
    # Virtual points per server slot on the placement ring; more = flatter
    # shard balance, slower ring (re)build.  Must be identical on every
    # client of a cluster (all derive the same map from membership alone).
    ps_placement_vnodes: int = 128
    # Reconnect attempts to an unresponsive primary before promoting its
    # backup (replicated mode only; non-replicated failover keeps the full
    # ps_failover_max budget).  Small on purpose: with a warm backup the
    # cheap move is promotion, not waiting out a supervisor restart.
    ps_promote_reconnect_max: int = 1
    # Promotion-storm suppression window (milliseconds; 0 = off, the
    # pre-scale behavior).  When many primaries die at once (a spot-
    # preemption wave), every client would otherwise promote each dead
    # slot back-to-back, bumping the placement epoch and re-seeding moved
    # shards once PER SLOT.  With the window on, a client's first
    # promotion pays a random jitter in [0, window) — de-phasing N
    # clients that observed the same wave — and FURTHER promotions inside
    # the window coalesce into the same placement epoch (one bump, one
    # drain fence per storm), counted in tmpi_promote_coalesced_total.
    ps_promote_jitter_ms: int = _env(
        "TORCHMPI_TPU_PS_PROMOTE_JITTER_MS", 0, int)
    # Bound (frames) on each server's pending-forward queue to its
    # backups; overflow drops the OLDEST frame, counted in
    # tmpi_ps_forward_error_count (repaired by re-seed at promotion).
    ps_forward_queue_max: int = 1024

    # --- observability (torchmpi_tpu/obs: span tracer, native trace rings,
    # metrics registry; see docs/observability.md).  Off by default so the
    # fast path is untouched: with obs_trace False every native emit site
    # is one relaxed atomic load + branch and the Python span() call
    # returns a shared no-op context ---
    # Master switch: native phase-event rings in hostcomm.cpp/ps.cpp
    # (pushed by obs/native.apply_config) AND the Python span tracer.
    obs_trace: bool = _env_bool("TORCHMPI_TPU_OBS_TRACE", False)
    # Capacity (events) of each native trace ring; drop-oldest on overflow,
    # losses counted in tmpi_{hc,ps}_trace_dropped.
    obs_trace_ring_capacity: int = _env(
        "TORCHMPI_TPU_OBS_TRACE_RING_CAPACITY", 4096, int)
    # Capacity (spans) of the Python tracer's finished-span buffer; same
    # drop-oldest discipline, losses counted in the tracer's dropped().
    obs_span_capacity: int = _env(
        "TORCHMPI_TPU_OBS_SPAN_CAPACITY", 4096, int)
    # --- cluster observability plane (obs/clocksync.py alignment,
    # obs/aggregate.py obsdump bundles + straggler detector,
    # obs/flight.py failure flight recorder; see docs/observability.md
    # "Cluster tracing & flight recorder") ---
    # Ping-pong rounds per peer in the clock-alignment exchange; the
    # min-RTT round's midpoint estimate wins, so more rounds tighten the
    # published per-rank uncertainty at the cost of a few extra
    # sendreceives at alignment time.
    obs_clocksync_rounds: int = _env(
        "TORCHMPI_TPU_OBS_CLOCKSYNC_ROUNDS", 8, int)
    # Bounded-sample clock alignment (0 = off: measure every peer, the
    # pre-scale behavior).  At hundreds of ranks the all-peers exchange
    # costs O(N * rounds) serial sendreceives on rank 0; with k > 0 only
    # k deterministically-chosen peers are measured per align() and the
    # rest inherit the sampled median offset with a widened uncertainty
    # (the spread of the sampled offsets) — honest about what was not
    # measured.  Every rank derives the same sample, so the exchange
    # stays a collective.
    obs_clocksync_sample_peers: int = _env(
        "TORCHMPI_TPU_OBS_CLOCKSYNC_SAMPLE_PEERS", 0, int)
    # Directory each rank writes its self-describing obsdump-<rank>.json
    # bundle into at runtime shutdown ("" = no shutdown dump); bundles
    # merge offline via `tmpi-trace merge-ranks` / obs.export.merge_ranks.
    # On-demand dumps (`tmpi-trace dump`, obs.aggregate.write_obsdump)
    # take an explicit directory and ignore this knob.
    obs_dump_dir: str = _env("TORCHMPI_TPU_OBS_DUMP_DIR", "", str)
    # Failure flight recorder (obs/flight.py): when on, the failure paths
    # (elastic restore, watchdog expiry before EXIT_STALLED, PS failover/
    # promotion) snapshot the last spans + drained native ring tails +
    # metrics into a post-mortem bundle on disk.  Off by default — the
    # recorder itself is passive, but a dump drains the trace rings.
    obs_flight: bool = _env_bool("TORCHMPI_TPU_OBS_FLIGHT", False)
    # Directory for flight bundles ("" = current working directory).
    obs_flight_dir: str = _env("TORCHMPI_TPU_OBS_FLIGHT_DIR", "", str)
    # Retention bound on flight bundles per directory (oldest pruned): a
    # failover storm must not fill the disk with forensic dumps.
    obs_flight_keep: int = _env("TORCHMPI_TPU_OBS_FLIGHT_KEEP", 8, int)
    # --- live telemetry & health plane (obs/serve.py per-rank HTTP
    # endpoint + obs/cluster.py aggregator; see docs/observability.md
    # "Live endpoints & health") ---
    # Serve GET /metrics (live Prometheus), GET /healthz (health state
    # machine), GET /spans and POST /flight on a daemon thread for this
    # process; started by runtime/lifecycle.start (and scripts/ps_server
    # --obs-http-port).  Off by default: no socket, no thread.
    obs_http: bool = _env_bool("TORCHMPI_TPU_OBS_HTTP", False)
    # Listen port for the endpoint; 0 picks an ephemeral port (read it
    # back via obs.serve.url()).  Multi-rank hosts give each rank its own
    # port (e.g. base + rank via the env var per worker).
    obs_http_port: int = _env("TORCHMPI_TPU_OBS_HTTP_PORT", 0, int)
    # Bind address.  Loopback by default ON PURPOSE: the endpoint exposes
    # runtime internals with no auth; widen to a routable address only
    # behind a trusted network or a scraping proxy.
    obs_http_bind: str = _env("TORCHMPI_TPU_OBS_HTTP_BIND",
                              "127.0.0.1", str)
    # Fan-in of the hierarchical federation tree (obs/cluster.py,
    # scripts/elastic_launch.py ScaleSensor): endpoints shard into groups
    # of about this many per aggregator, sweeps run at most this many
    # concurrent probes, and unreachable ranks summarize per shard
    # instead of N individual verdicts.  Sized so a 256-rank sweep is
    # ~16 shards x ~16 serial probes — bounded wall-clock AND bounded
    # threads, where the flat per-rank fan-out was neither.
    obs_federation_fanout: int = _env(
        "TORCHMPI_TPU_OBS_FEDERATION_FANOUT", 16, int)

    # --- job history plane: persistent event journal (obs/journal.py;
    # all reads funnel through journal.journal_config — see
    # docs/history.md).  Off by default: emit() is one config read ---
    # Master switch: append-only JSONL event journal of discrete state
    # changes (health transitions, elastic restores, watchdog expiries,
    # PS failover/promotion/handoff, autotune cache verdicts, numerics
    # audits, chaos fault injections, supervisor actions).
    journal_enabled: bool = _env_bool("TORCHMPI_TPU_JOURNAL_ENABLED", False)
    # Directory for journal segments ("" = current working directory).
    journal_dir: str = _env("TORCHMPI_TPU_JOURNAL_DIR", "", str)
    # Rotate the active segment once it exceeds this many bytes.
    journal_segment_bytes: int = _env(
        "TORCHMPI_TPU_JOURNAL_SEGMENT_BYTES", 1 << 20, int)
    # Retention bound: newest segments kept PER RANK (oldest pruned — a
    # failover storm must not fill the disk; same discipline as
    # obs_flight_keep, one shared pruning helper).
    journal_keep: int = _env("TORCHMPI_TPU_JOURNAL_KEEP", 8, int)
    # fsync after every appended line (crash-safe to the last event at
    # the cost of one fsync per state change; off = flush-only, crash-
    # safe to the last OS writeback, torn tails skipped by readers).
    journal_fsync: bool = _env_bool("TORCHMPI_TPU_JOURNAL_FSYNC", False)

    # --- job history plane: on-disk metrics history (obs/history.py
    # background sampler over Registry.collect; all reads funnel through
    # history.history_config — see docs/history.md) ---
    # Master switch for the background sampler (started by
    # runtime/lifecycle.start when on; off = no thread, no samples).
    history_enabled: bool = _env_bool("TORCHMPI_TPU_HISTORY_ENABLED", False)
    # Seconds between registry snapshots in the finest tier.
    history_interval_s: float = _env(
        "TORCHMPI_TPU_HISTORY_INTERVAL_S", 1.0, float)
    # Directory the sampler persists history-<rank>.json into ("" =
    # in-memory rings only; tmpi-trace why then reads the live /history
    # route instead of disk).
    history_dir: str = _env("TORCHMPI_TPU_HISTORY_DIR", "", str)
    # Samples per tier ring (every tier holds this many rows; tier k
    # covers history_tier_len * history_downsample^k * interval seconds).
    history_tier_len: int = _env("TORCHMPI_TPU_HISTORY_TIER_LEN", 512, int)
    # Downsampling factor between tiers (e.g. 1 s samples -> 30 s means
    # -> 15 min means with the defaults); also the number of fine rows
    # aggregated into one coarse row.
    history_downsample: int = _env("TORCHMPI_TPU_HISTORY_DOWNSAMPLE",
                                   30, int)

    # --- declarative alerting & SLO plane (obs/alerts.py rules engine
    # evaluated on the history sampler's cadence; all reads funnel
    # through alerts.alerts_config — see docs/alerts.md) ---
    # Master switch.  Off = one config read: no rules are compiled, the
    # sampler hook stays None, /alerts answers enabled=false.  Needs
    # history_enabled (the rules read the metrics history).
    alert_enabled: bool = _env_bool("TORCHMPI_TPU_ALERT_ENABLED", False)
    # Ship the default rule pack (the stack's known failure signatures:
    # nonfinite movement, numerics divergence, step-rate sag, overlap
    # collapse, PS storm, journal drop-loss, straggler skew share,
    # autotune byte-mix drift, watchdog-near-expiry).  Off = only
    # alert_rules_path rules run.
    alert_default_pack: bool = _env_bool(
        "TORCHMPI_TPU_ALERT_DEFAULT_PACK", True)
    # JSON file of author-supplied rule specs ("" = none); a rule whose
    # name collides with a default-pack rule replaces it.
    alert_rules_path: str = _env("TORCHMPI_TPU_ALERT_RULES_PATH", "", str)
    # Sampler ticks between rule evaluations (1 = every sample; raise it
    # to amortize a large rule set on a fast sampler).
    alert_eval_every: int = _env("TORCHMPI_TPU_ALERT_EVAL_EVERY", 1, int)
    # Default for: hold duration (seconds a predicate must stay true
    # before pending becomes firing) for rules that do not set for_s —
    # one noisy sample can never page.
    alert_for_s: float = _env("TORCHMPI_TPU_ALERT_FOR_S", 3.0, float)
    # Dump a flight-recorder bundle when a CRITICAL rule fires (still
    # gated by obs_flight — this only decides whether the alert plane
    # asks).
    alert_flight: bool = _env_bool("TORCHMPI_TPU_ALERT_FLIGHT", True)

    # --- training-health & numerics observability (obs/numerics.py:
    # in-step sentinels + cross-rank consistency auditor; all reads
    # funnel through numerics.numerics_config() — see docs/numerics.md) ---
    # Numerics plane mode:
    #   "off"      — (default) the compiled step is bit-for-bit the
    #                pre-numerics step: no extra step outputs, no device
    #                reads, one config read at compile-key time (pinned
    #                by tests/test_numerics.py).
    #   "sentinel" — cheap fused in-graph statistics ride the compiled
    #                step (per-bucket gradient L2 norms, global nonfinite
    #                count, update/param ratio) and publish per step as
    #                tmpi_numerics_* gauges/histograms via
    #                obs/serve.publish_step.
    #   "audit"    — sentinel plus the cross-rank parameter-fingerprint
    #                auditor every numerics_audit_interval steps (an
    #                Auditor on engine.step_boundaries allgathers blake2b
    #                digests over the hostcomm plane and binary-searches
    #                the leaf tree on mismatch).
    numerics_mode: str = _env("TORCHMPI_TPU_NUMERICS_MODE", "off", str)
    # Steps between cross-rank digest audits in audit mode (the audit
    # costs one parameter-tree hash + a handful of 16-byte allgathers).
    numerics_audit_interval: int = _env(
        "TORCHMPI_TPU_NUMERICS_AUDIT_INTERVAL", 100, int)
    # Bound (records) on the in-memory per-step sentinel history ring —
    # the recent-numerics evidence the flight recorder snapshots into
    # divergence bundles.
    numerics_history: int = _env("TORCHMPI_TPU_NUMERICS_HISTORY", 64, int)

    # --- transport chaos (runtime/chaos.py: seeded in-process TCP fault
    # proxy between ring neighbours / PS client<->server; wired by endpoint
    # rewriting, so nothing on the fast path reads these when disabled) ---
    chaos_enabled: bool = False
    chaos_seed: int = 0
    # Added latency per forwarded chunk (plus uniform jitter).
    chaos_delay_ms: float = 0.0
    chaos_jitter_ms: float = 0.0
    # Throughput cap in bytes/second; 0 = unlimited.
    chaos_bandwidth_bytes_per_s: int = 0
    # Per-forwarded-chunk probabilities of flipping one byte, RST-closing
    # the connection, or black-holing it (stop forwarding, keep it open —
    # the hang the hc_io_deadline_ms deadline exists to catch).
    chaos_corrupt_prob: float = 0.0
    chaos_reset_prob: float = 0.0
    chaos_blackhole_prob: float = 0.0

    # --- elastic resize (runtime/resize.py: membership-epoch state
    # machine — propose -> quiesce -> commit/abort; all reads funnel
    # through resize.resize_config() — see docs/resize.md) ---
    # Arms the resize request queue (and the live endpoint's POST /resize
    # route): with this off, enqueue_request raises — membership must not
    # be mutable from an unarmed surface.
    resize_enabled: bool = _env_bool("TORCHMPI_TPU_RESIZE_ENABLED", False)
    # Socket deadline (ms) on every out-of-band resize wait: the state
    # ship to a joiner, the joiner's verdict wait, the restart-rejoin
    # state pull.  A joiner that cannot be shipped inside the deadline
    # aborts the proposal cleanly (the old ring never stopped).
    resize_io_deadline_ms: int = _env(
        "TORCHMPI_TPU_RESIZE_IO_DEADLINE_MS", 10000, int)
    # Step boundaries between proposal polls (each poll is one ~24-byte
    # broadcast on the ring); 1 = every boundary.  Must be identical on
    # every rank — the poll is a collective.
    resize_poll_interval_steps: int = _env(
        "TORCHMPI_TPU_RESIZE_POLL_INTERVAL_STEPS", 1, int)

    # --- autoscaler policy (the in-process defaults behind
    # scripts/elastic_launch.py --autoscale and scripts/scale_drill.py;
    # read via resize.scale_config() — see docs/resize.md) ---
    # Step-rate drift (recent/baseline, obs/history.drift) at or below
    # which a sweep votes scale-UP (sustained backlog: the job is
    # slowing against its own trailing baseline).
    scale_up_drift: float = _env("TORCHMPI_TPU_SCALE_UP_DRIFT", 0.85, float)
    # Consecutive scale-up votes before a grow request fires.
    scale_up_sweeps: int = _env("TORCHMPI_TPU_SCALE_UP_SWEEPS", 3, int)
    # Share of the job's total straggler-attributed skew
    # (tmpi_rank_skew_attributed_seconds) one rank must hold for a sweep
    # to name it an eviction candidate.
    scale_evict_share: float = _env(
        "TORCHMPI_TPU_SCALE_EVICT_SHARE", 0.5, float)
    # Consecutive sweeps naming the SAME rank before it is evicted —
    # detection (PR 7's straggler detector) converted into action.
    scale_evict_sweeps: int = _env(
        "TORCHMPI_TPU_SCALE_EVICT_SWEEPS", 3, int)

    # --- retune controller (collectives/retune.py: the alert->knob action
    # loop — a firing perf alert triggers an off-hot-path re-bench and a
    # measured knob flip, the same detect->decide->act pattern the
    # autoscaler proved for membership; all reads funnel through
    # retune.retune_config() — see docs/autotune.md "Retune controller") ---
    # Arms the controller: with this off, nothing joins
    # engine.step_boundaries and the step boundary costs nothing.
    retune_enabled: bool = _env_bool("TORCHMPI_TPU_RETUNE_ENABLED", False)
    # Step boundaries between controller polls; 1 = every boundary.  Each
    # poll is a few dict reads — the alert plane already did the watching.
    retune_poll_interval_steps: int = _env(
        "TORCHMPI_TPU_RETUNE_POLL_INTERVAL_STEPS", 1, int)
    # A trigger rule must stay firing this long before a probe launches —
    # the controller's OWN debounce on top of the alert plane's for_s (two
    # independent debounces, one knob flip; the autoscaler discipline).
    retune_debounce_s: float = _env(
        "TORCHMPI_TPU_RETUNE_DEBOUNCE_S", 5.0, float)
    # Quiet window after an apply (or a no-op decision) before the next
    # probe may launch — a flapping alert must not thrash the knobs.
    retune_cooldown_s: float = _env(
        "TORCHMPI_TPU_RETUNE_COOLDOWN_S", 60.0, float)
    # Post-apply observation window: a regression detected inside it
    # reverts the flips to their pre-apply values.
    retune_revert_window_s: float = _env(
        "TORCHMPI_TPU_RETUNE_REVERT_WINDOW_S", 30.0, float)
    # Step-rate ratio (post-apply rate / pre-probe baseline rate) at or
    # below which the post-retune window counts as REGRESSED and the
    # flips revert — the retune must not make a sagging job worse.
    retune_revert_drift: float = _env(
        "TORCHMPI_TPU_RETUNE_REVERT_DRIFT", 0.9, float)
    # tmpi_autotune_mix_drift level (fraction of live collective traffic
    # in (op, bytes-bucket) cells the winner cache never measured) the
    # default-pack autotune_mix_drift alert fires at.
    retune_mix_threshold: float = _env(
        "TORCHMPI_TPU_RETUNE_MIX_THRESHOLD", 0.5, float)
    # Minimum live histogram samples before the mix-drift gauge publishes
    # a nonzero value (the mix of nothing is noise, not drift).
    retune_mix_min_samples: int = _env(
        "TORCHMPI_TPU_RETUNE_MIX_MIN_SAMPLES", 20, int)

    # --- inference serving plane (torchmpi_tpu/serving/: continuous-
    # batching request engine, paged KV pool, request frontend, replica
    # router; all reads funnel through serving.serve_config() — see
    # docs/serving.md) ---
    # Tokens per KV-cache block: the paged pool's allocation unit.  A
    # request leases ceil(len/block_size) blocks; smaller blocks waste
    # less tail capacity but grow the per-request block lists.
    serve_block_size: int = _env("TORCHMPI_TPU_SERVE_BLOCK_SIZE", 16, int)
    # Total KV blocks in the pool — the replica's whole token budget
    # (block_size * kv_blocks positions shared across every live
    # request).  Admission is gated on headroom against this.
    serve_kv_blocks: int = _env("TORCHMPI_TPU_SERVE_KV_BLOCKS", 256, int)
    # Decode slots per iteration: the max number of requests batched into
    # one compiled decode step.  Requests join/leave between iterations
    # (continuous batching) — this bounds the batch, not the queue.
    serve_max_batch: int = _env("TORCHMPI_TPU_SERVE_MAX_BATCH", 8, int)
    # Admitted-but-not-yet-scheduled queue bound.  A request arriving at
    # a full queue gets a typed admission rejection (HTTP 503
    # reason=queue_full) instead of unbounded buffering — backpressure.
    serve_max_queue: int = _env("TORCHMPI_TPU_SERVE_MAX_QUEUE", 64, int)
    # Per-request deadline (ms) when the client sends none.  Past it the
    # request is shed wherever it is — queued, prefilling, or mid-decode
    # — with a typed reason=deadline response, and its blocks are freed.
    serve_default_deadline_ms: int = _env(
        "TORCHMPI_TPU_SERVE_DEADLINE_MS", 10000, int)
    # Cap on tokens generated per request; a client asking for more is
    # clamped, not rejected (the KV lease is sized from this cap).
    serve_max_new_tokens: int = _env(
        "TORCHMPI_TPU_SERVE_MAX_NEW_TOKENS", 32, int)
    # Fraction of the KV pool that must be FREE for admission to accept
    # a new request — the KV-headroom gate.  Below it new work is shed
    # (reason=kv_pressure) so in-flight decodes can finish growing.
    serve_admission_headroom: float = _env(
        "TORCHMPI_TPU_SERVE_ADMISSION_HEADROOM", 0.05, float)
    # Model runner behind the engine: "stub" (deterministic tokens,
    # optional simulated per-token latency — load/chaos drills) or
    # "llama" (the real compiled prefill/decode split over models/llama).
    serve_runner: str = _env("TORCHMPI_TPU_SERVE_RUNNER", "stub", str)
    # Simulated per-token compute seconds for the stub runner (0 = as
    # fast as Python goes).  Lets one box emulate realistic decode
    # latency for thousand-client load legs.
    serve_stub_token_s: float = _env(
        "TORCHMPI_TPU_SERVE_STUB_TOKEN_S", 0.0, float)
    # Max seconds begin_drain/shutdown waits for in-flight requests to
    # finish before shedding the stragglers — bounds the router's
    # handoff window during a roll-restart.
    serve_drain_timeout_s: float = _env(
        "TORCHMPI_TPU_SERVE_DRAIN_TIMEOUT_S", 5.0, float)


_constants = Constants()
_frozen = False
_lock = threading.Lock()

_FIELDS = {f.name for f in dataclasses.fields(Constants)}


def get(name: str) -> Any:
    """Read a knob (reference: torchmpi_get_* pairs, constants.cpp:161-352)."""
    if name not in _FIELDS:
        raise KeyError(f"unknown constant {name!r}")
    return getattr(_constants, name)


def set(name: str, value: Any) -> None:  # noqa: A001 - mirrors reference API
    """Write a knob (reference: torchmpi_set_* pairs, constants.cpp:161-352).

    Raises if :func:`freeze` has been called — the reference's
    ``immutableConstants`` guard, actually enforced here.
    """
    if name not in _FIELDS:
        raise KeyError(f"unknown constant {name!r}")
    with _lock:
        if _frozen:
            raise RuntimeError(
                f"constants are frozen; cannot set {name!r} "
                "(collectives have already been compiled against them)"
            )
        setattr(_constants, name, value)


def freeze() -> None:
    """Make all constants immutable (reference: immutableConstants, resources.cpp:83-85)."""
    global _frozen
    with _lock:
        _frozen = True


def frozen() -> bool:
    return _frozen


def snapshot() -> Dict[str, Any]:
    """All knobs as a dict, for logging / reproducibility."""
    return dataclasses.asdict(_constants)


def reset(**overrides: Any) -> None:
    """Restore defaults (test helper); optionally apply overrides."""
    global _constants, _frozen
    with _lock:
        _constants = Constants()
        _frozen = False
        for k, v in overrides.items():
            if k not in _FIELDS:
                raise KeyError(f"unknown constant {k!r}")
            setattr(_constants, k, v)


class constants:
    """Attribute-style access: ``config.constants.min_buffer_size``."""

    def __getattr__(self, name: str) -> Any:
        # AttributeError, not KeyError: hasattr()/copy/pickle/IPython all
        # probe attributes and only swallow AttributeError — a KeyError
        # here turns benign introspection of the facade into a crash.
        if name not in _FIELDS:
            raise AttributeError(f"unknown constant {name!r}")
        return get(name)

    def __setattr__(self, name: str, value: Any) -> None:
        set(name, value)


constants = constants()
