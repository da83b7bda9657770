"""Typed configuration for the framework.

The reference configures everything through env vars scattered across
Dockerfiles and docker-compose service blocks with no validation layer
(reference docker-compose.yml:23-25,188-192; model_builder_image/Dockerfile:8-13).
Here a single dataclass holds every knob, reads the environment once, and is
importable everywhere — the "typed pydantic-style settings" upgrade called for
in SURVEY.md §7 without taking a pydantic dependency.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, fields
from typing import Optional


def _env(name: str, default, cast=None):
    raw = os.environ.get(name)
    if raw is None:
        return default
    if cast is None:
        cast = type(default) if default is not None else str
    if cast is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return cast(raw)


@dataclass
class Settings:
    """All framework knobs, env-overridable with the ``LO_TPU_`` prefix."""

    # --- storage -----------------------------------------------------------
    #: On-disk root for persisted datasets (parquet + metadata.json). The
    #: catalog always keeps hot data in host RAM; this is the durability tier
    #: replacing the reference's MongoDB volumes (docker-compose.yml:335-340).
    store_root: str = field(
        default_factory=lambda: _env("LO_TPU_STORE_ROOT", "/tmp/lo_tpu_store")
    )
    #: Persist datasets to disk on every commit (finished-flip).
    persist: bool = field(default_factory=lambda: _env("LO_TPU_PERSIST", True, bool))
    #: Soft cap (MiB) on column data resident in host RAM *per dataset*;
    #: 0 = unlimited. Over budget, chunks flush to immutable parquet chunk
    #: files and are evicted — the out-of-core tier replacing the
    #: reference's disk-backed Mongo collections (database.py:133-216).
    ram_budget_mb: int = field(
        default_factory=lambda: _env("LO_TPU_RAM_BUDGET_MB", 0)
    )
    #: Force the shard-local streamed design-matrix path for every build
    #: (ops/preprocess.ChunkedDesign). Default off: builds stream
    #: automatically when a dataset is over its RAM budget; this knob
    #: forces it for testing / for pods whose datasets fit in RAM but
    #: whose operators still want per-process residency divided by
    #: process count.
    stream_design: bool = field(
        default_factory=lambda: _env("LO_TPU_STREAM_DESIGN", False, bool)
    )
    #: Optional second directory mirroring every committed dataset (chunk
    #: files + journal + metadata). Standing in for the reference's Mongo
    #: primary/secondary replica set (docker-compose.yml:27-91): if the
    #: primary store_root is lost, load_all() restores from the replica.
    replica_root: str = field(
        default_factory=lambda: _env("LO_TPU_REPLICA_ROOT", "")
    )
    #: Comma-separated ``host:port`` list of peer replica servers
    #: (catalog/replicate.py). Each committed journal prefix is pushed to
    #: every peer by an async single-slot committer; `_repair_chunk` adds
    #: a CRC-verified remote fetch rung so reads heal whole-host loss
    #: through the same ChunkCorrupt path as local bit-rot. Empty (the
    #: default) keeps replica_root-only behavior byte-for-byte unchanged.
    replica_peers: str = field(
        default_factory=lambda: _env("LO_TPU_REPLICA_PEERS", "")
    )
    #: Port for this host's ReplicaServer (the receive side of the
    #: replication plane). 0 (default) does not start one — set it on
    #: every host that should hold peers' replicas. Bound on
    #: LO_TPU_HOST.
    replica_port: int = field(
        default_factory=lambda: _env("LO_TPU_REPLICA_PORT", 0)
    )
    #: Socket timeout, seconds, for every replication frame exchange
    #: (push, fetch, probe). A dead peer costs at most this long per
    #: attempt before the push is recorded as failed and the dataset
    #: counted under-replicated.
    replica_timeout_s: float = field(
        default_factory=lambda: _env("LO_TPU_REPLICA_TIMEOUT_S", 10.0)
    )
    #: Minimum seconds between re-push attempts for an under-replicated
    #: dataset. Failed pushes leave the dataset on the push queue's
    #: retry list; each /metrics scrape (or replication_snapshot call)
    #: re-queues datasets whose last attempt is older than this.
    replica_push_retry_s: float = field(
        default_factory=lambda: _env("LO_TPU_REPLICA_PUSH_RETRY_S", 2.0)
    )
    #: Chunks read ahead of the consumer by the prefetching read pipeline
    #: (catalog/readpipe.py): while a streaming consumer (iter_chunks /
    #: snapshot scans) computes on chunk i, a background worker pool
    #: reads + CRC-verifies + decodes chunks i+1..i+K. 0 disables
    #: prefetch entirely — the strictly synchronous read path is kept as
    #: the parity oracle (docs/performance.md).
    prefetch_chunks: int = field(
        default_factory=lambda: _env("LO_TPU_PREFETCH_CHUNKS", 2)
    )
    #: Byte budget for the host-RAM LRU chunk cache shared across
    #: passes/datasets: decoded chunk reads are kept keyed by
    #: (chunk file, journal CRC, field selection) so the second scan of a
    #: streamed-fit pipeline and repeated histogram/projection calls hit
    #: warm memory instead of re-reading disk. 0 disables caching.
    chunk_cache_bytes: int = field(
        default_factory=lambda: _env("LO_TPU_CHUNK_CACHE_BYTES", 256 << 20)
    )
    #: Run a full checksum scrub (DatasetStore.scrub) as part of
    #: load_all's recovery scan: every journaled chunk file is re-read
    #: and verified against its journal CRC32, repairing from the
    #: replica on mismatch. Off by default — it reads every chunk at
    #: startup; the lazy first-read verification covers the default
    #: path, and POST /catalog/scrub runs the same pass on demand.
    scrub_on_load: bool = field(
        default_factory=lambda: _env("LO_TPU_SCRUB_ON_LOAD", False, bool)
    )

    # --- ingestion ---------------------------------------------------------
    #: CSV ingest chunk size (rows) for the streaming loader. Replaces the
    #: reference's 3-thread/queue(1000) row-at-a-time pipeline
    #: (database_api_image/database.py:133-216) with columnar chunks.
    #: 256k rows ≈ 10-20 MB blocks — big enough that per-chunk overheads
    #: (journal record, file open, arrow framing) vanish in the noise.
    ingest_chunk_rows: int = field(
        default_factory=lambda: _env("LO_TPU_INGEST_CHUNK_ROWS", 262144)
    )
    #: HTTP timeout for CSV downloads, seconds.
    download_timeout: float = field(
        default_factory=lambda: _env("LO_TPU_DOWNLOAD_TIMEOUT", 60.0)
    )
    #: Use the native C++ CSV parser when its shared library is built.
    use_native_csv: bool = field(
        default_factory=lambda: _env("LO_TPU_USE_NATIVE_CSV", True, bool)
    )
    #: Parser threads for streaming ingest. Row-aligned byte blocks parse
    #: concurrently (the native parser releases the GIL for the whole
    #: call); chunks still commit in source order. 0 = automatic:
    #: os.cpu_count() clamped to [4, 8] (a few threads pay even on one
    #: core by overlapping the committer's IO waits; beyond 8 the
    #: in-order committer is the bottleneck).
    ingest_parse_threads: int = field(
        default_factory=lambda: _env("LO_TPU_INGEST_PARSE_THREADS", 0)
    )
    #: Commit (journal-fsync + metadata write) cadence for streaming
    #: ingest, in bytes of parsed chunk data; chunks batch up to this many
    #: bytes per store.save. 0 = commit every chunk (max durability).
    ingest_commit_bytes: int = field(
        default_factory=lambda: _env("LO_TPU_INGEST_COMMIT_BYTES", 64 << 20)
    )
    #: Range-partitioned ingest: split the source byte range into this
    #: many per-host partitions fetched/parsed/journaled concurrently
    #: (catalog/ingest.py). 0 or 1 = today's single-stream path,
    #: byte-for-byte. Only applies when the source advertises its length
    #: (HEAD Content-Length, or file size); unsized sources fall back to
    #: the serial path.
    ingest_partitions: int = field(
        default_factory=lambda: _env("LO_TPU_INGEST_PARTITIONS", 0)
    )
    #: Minimum partition size in bytes: sources smaller than
    #: 2 * this never split (a second ranged connection costs more than
    #: it overlaps on small files).
    ingest_partition_min_bytes: int = field(
        default_factory=lambda: _env("LO_TPU_INGEST_PARTITION_MIN_BYTES",
                                     4 << 20)
    )

    # --- kernels -----------------------------------------------------------
    #: Use hand-written Pallas kernels for hot inner loops (t-SNE repulsion;
    #: ops/pallas_kernels.py). Off-TPU they run in interpreter mode, so the
    #: flag is safe everywhere; disable to force the pure-XLA fallbacks.
    use_pallas: bool = field(
        default_factory=lambda: _env("LO_TPU_USE_PALLAS", True, bool)
    )
    #: Route the tree families' (dt/rf/gb) histogram, routing and descent
    #: hot loops through the fused Pallas binned-histogram kernels
    #: (ops/pallas_kernels.py tree_*). ``0`` selects the pure-XLA blocked
    #: contraction path, kept as the bit-parity oracle
    #: (docs/performance.md §tree kernels). Subordinate to ``use_pallas``;
    #: off-TPU the kernels run in interpreter mode so the same code path
    #: is exercised by the CPU-mesh tests.
    tree_kernel: bool = field(
        default_factory=lambda: _env("LO_TPU_TREE_KERNEL", True, bool)
    )

    # --- mesh / parallelism ------------------------------------------------
    #: Mesh axis names. "data" shards rows (the reference's Spark partitioning
    #: axis, SURVEY.md §2 parallelism #1); "model" shards features/params.
    data_axis: str = "data"
    model_axis: str = "model"
    #: Optional forced mesh shape "D,M" or "D,M,S" (data × model × seq);
    #: empty = all local devices on the data axis.
    mesh_shape: str = field(default_factory=lambda: _env("LO_TPU_MESH_SHAPE", ""))

    # --- serving -----------------------------------------------------------
    #: Single service port. The reference runs 7 Flask apps on ports
    #: 5000-5006 (client __init__.py:56-333); here one server hosts all
    #: routers; per-service ports are emulated by path prefixes.
    port: int = field(default_factory=lambda: _env("LO_TPU_PORT", 5000))
    host: str = field(default_factory=lambda: _env("LO_TPU_HOST", "127.0.0.1"))
    #: Page-size cap for dataset reads; reference hard-caps at 20
    #: (database_api_image/server.py:28,69-70).
    read_limit_cap: int = field(default_factory=lambda: _env("LO_TPU_READ_CAP", 20))
    #: Per-connection socket timeout (seconds) on the HTTP server. A
    #: handler thread reading a request body blocks on the client's
    #: socket; without a timeout a hung/dead client that sent a
    #: Content-Length it never delivers wedges that thread forever.
    #: 0 disables (not recommended outside tests).
    http_timeout_s: float = field(
        default_factory=lambda: _env("LO_TPU_HTTP_TIMEOUT_S", 30.0)
    )
    #: Directory where viz services write PNGs (reference volumes
    #: tsne:/images, pca:/images, docker-compose.yml:289-290).
    image_root: str = field(
        default_factory=lambda: _env("LO_TPU_IMAGE_ROOT", "/tmp/lo_tpu_images")
    )
    #: HTTP accept processes. ``1`` (the default) keeps today's
    #: single-process topology byte-for-byte: the device-owning process
    #: serves HTTP itself through the threaded stdlib server. ``N > 1``
    #: binds N lightweight front-end worker processes to the SAME
    #: host:port via ``SO_REUSEPORT`` (the kernel spreads accepted
    #: connections across them, sidestepping the GIL), each running an
    #: async ``selectors`` request loop and forwarding predict rows /
    #: proxied requests to the device-owning process over the
    #: length-prefixed row channel (serving/rowchannel.py,
    #: serving/frontend.py — docs/serving.md §front end).
    http_workers: int = field(
        default_factory=lambda: _env("LO_TPU_HTTP_WORKERS", 1)
    )
    #: Handler threads the device-owning process runs for row-channel
    #: frames from front-end workers — bounds how many forwarded
    #: requests execute concurrently inside the primary (the analogue
    #: of the threaded server's one-thread-per-connection, made
    #: explicit). Only meaningful when ``http_workers > 1``.
    frontend_channel_threads: int = field(
        default_factory=lambda: _env("LO_TPU_FRONTEND_CHANNEL_THREADS", 16)
    )

    # --- online inference (serving/batcher.py, models/aot.py) --------------
    #: Largest coalesced micro-batch (rows) per device dispatch of the
    #: online predict tier — also the top of the AOT padding-bucket
    #: ladder (1/8/64/…/max), so raising it adds compiled programs per
    #: model. Requests carrying more rows than this are rejected 406;
    #: the client SDK splits client-side (Model.predict_online).
    serve_max_batch: int = field(
        default_factory=lambda: _env("LO_TPU_SERVE_MAX_BATCH", 256)
    )
    #: Bound (rows) on each model's predict queue. A request that would
    #: push the queue past this answers 503 + Retry-After — backpressure
    #: the stock client's jittered backoff already honors. 0 disables
    #: the online tier entirely (every /predict answers 503).
    serve_queue_depth: int = field(
        default_factory=lambda: _env("LO_TPU_SERVE_QUEUE_DEPTH", 1024)
    )
    #: Optional coalescing linger (milliseconds): after picking up the
    #: first waiting request, the dispatcher may wait this long for more
    #: rows before dispatching a non-full batch. Default 0 — dispatch
    #: immediately: continuous batching coalesces on its own because the
    #: queue refills while the device runs the previous batch, and a
    #: linger just adds its full length to every batch's latency
    #: whenever traffic can't fill ``serve_max_batch`` within it
    #: (measured: a 2 ms linger cost a 24-worker closed loop ~10x
    #: throughput). Raise it only for sparse open-loop traffic where
    #: trading p50 for occupancy is explicitly wanted.
    serve_max_wait_ms: float = field(
        default_factory=lambda: _env("LO_TPU_SERVE_MAX_WAIT_MS", 0.0)
    )
    #: How long a queued request may wait for its batch result before
    #: answering 503 (dispatcher wedged / overloaded) — bounds handler
    #: threads the same way http_timeout_s bounds the socket.
    serve_timeout_s: float = field(
        default_factory=lambda: _env("LO_TPU_SERVE_TIMEOUT_S", 30.0)
    )
    #: Default end-to-end deadline budget (milliseconds) applied to a
    #: predict request that carries no ``X-Deadline-Ms`` header. 0 = no
    #: implicit deadline (requests wait out ``serve_timeout_s``). A
    #: request whose budget expires — at admission (predicted queue wait
    #: exceeds the remaining budget) or in queue — answers a terminal
    #: 504, and its rows are never dispatched to the device.
    serve_deadline_default_ms: float = field(
        default_factory=lambda: _env("LO_TPU_SERVE_DEADLINE_DEFAULT_MS", 0.0)
    )
    #: Upper clamp (milliseconds) on client-supplied deadline budgets —
    #: a confused client must not park a handler thread for an hour.
    #: 0 disables deadline handling entirely (headers are ignored).
    serve_deadline_cap_ms: float = field(
        default_factory=lambda: _env("LO_TPU_SERVE_DEADLINE_CAP_MS",
                                     600000.0)
    )
    #: Device replicas of the online predict plane: each replica is a
    #: full AOT bucket ladder compiled for (and params resident on) its
    #: own local device, with its own dispatcher thread + bounded queue;
    #: a router sends each request to the replica with the lowest
    #: predicted queue wait. ``1`` (the default) preserves the
    #: single-device topology byte-for-byte (``jax.local_devices()[0]``,
    #: one dispatcher per model — exactly the pre-replication tier);
    #: ``0`` means ALL local devices; ``N`` clamps to the locally
    #: available device count. Quarantine, self-healing, drain and chaos
    #: failpoints are all per-replica — a crashed replica degrades
    #: capacity, not availability.
    serve_replicas: int = field(
        default_factory=lambda: _env("LO_TPU_SERVE_REPLICAS", 1)
    )
    #: Consecutive dispatcher-thread crashes (exceptions escaping the
    #: dispatch loop, not per-request model errors) before a model is
    #: QUARANTINED: its predicts answer a terminal 503 naming the
    #: quarantine instead of endlessly crash-looping, and the
    #: ``serving_quarantined`` alert fires. A successful dispatch resets
    #: the streak; DELETE or re-save (invalidate) lifts the quarantine.
    #: With ``serve_replicas > 1`` the threshold applies PER REPLICA —
    #: one poisoned replica quarantines alone while siblings keep
    #: serving.
    serve_quarantine_crashes: int = field(
        default_factory=lambda: _env("LO_TPU_SERVE_QUARANTINE_CRASHES", 3)
    )
    #: First supervised-restart backoff (seconds) after a dispatcher
    #: crash; doubles per consecutive crash, capped at 5 s so teardown
    #: joins stay bounded.
    serve_restart_backoff_s: float = field(
        default_factory=lambda: _env("LO_TPU_SERVE_RESTART_BACKOFF_S", 0.2)
    )
    #: Graceful-drain window (seconds): on SIGTERM (or a programmatic
    #: ``App.drain``) the server stops admitting new work (503 +
    #: Retry-After + ``Connection: close``), lets in-flight predicts and
    #: queued jobs finish for up to this long, then stops. The
    #: supervisor's planned-restart path (SIGHUP) grants children this
    #: window before escalating to SIGKILL.
    drain_timeout_s: float = field(
        default_factory=lambda: _env("LO_TPU_DRAIN_TIMEOUT_S", 30.0)
    )

    # --- training ----------------------------------------------------------
    #: Max concurrently running model fits (reference: 5 classifiers through
    #: a ThreadPoolExecutor + Spark FAIR pool, model_builder.py:95,160-176).
    max_concurrent_fits: int = field(
        default_factory=lambda: _env("LO_TPU_MAX_CONCURRENT_FITS", 5)
    )
    #: Allow user-supplied preprocessing code via exec(). The reference does
    #: this unconditionally (model_builder.py:145-150); here it is opt-in and
    #: off by default — the declarative preprocessing API is the default path.
    allow_exec_preprocessing: bool = field(
        default_factory=lambda: _env("LO_TPU_ALLOW_EXEC", False, bool)
    )
    #: Resource jail for exec preprocessing (ops/exec_jail.py): wall-clock
    #: timeout, CPU seconds, and address-space cap for the child process.
    #: 0 disables the respective limit.
    exec_timeout_seconds: float = field(
        default_factory=lambda: _env("LO_TPU_EXEC_TIMEOUT_S", 300.0)
    )
    exec_cpu_seconds: int = field(
        default_factory=lambda: _env("LO_TPU_EXEC_CPU_S", 300)
    )
    exec_memory_mb: int = field(
        default_factory=lambda: _env("LO_TPU_EXEC_MEM_MB", 4096)
    )
    #: Checkpoint fitted models (orbax) into store_root/_models so they can
    #: be listed and re-used for prediction. The reference discards models
    #: after use (model_builder.py:227-248) — this is the §5 upgrade.
    persist_models: bool = field(
        default_factory=lambda: _env("LO_TPU_PERSIST_MODELS", True, bool)
    )
    #: Mid-fit checkpoint cadence (utils/fitckpt.py): persist per-family
    #: fit progress under ``<store_root>/_fitckpt`` every this many
    #: natural units — gb boost rounds, mlp training iterations, and (at
    #: every vmapped tree-batch boundary) rf trees — plus the streamed
    #: design fit's accumulator state at pass boundaries. A retried job
    #: (supervisor restart, watchdog kill, explicit re-POST) resumes
    #: from the newest valid checkpoint and produces BIT-IDENTICAL final
    #: params/metrics to an uninterrupted fit. ``0`` (the default)
    #: disables checkpointing entirely and keeps today's single-program
    #: fit path as the oracle (docs/fault_tolerance.md §8).
    fit_ckpt_rounds: int = field(
        default_factory=lambda: _env("LO_TPU_FIT_CKPT_ROUNDS", 0)
    )
    #: Successive-halving rungs for a hyperparameter sweep (models/
    #: tune.py): the sweep's total unit budget (boost rounds / adam
    #: iterations / tree batches) is cut into this many segments; after
    #: each, every candidate's k-fold scores are taken and the bottom
    #: half of the surviving configs is dropped (masks zeroed — the
    #: survivors' arithmetic is untouched). ``1`` disables halving (one
    #: rung, everyone runs to completion).
    tune_rungs: int = field(
        default_factory=lambda: _env("LO_TPU_TUNE_RUNGS", 3)
    )
    #: Cross-validation folds for tune sweeps: fold membership is an
    #: index mask over the ONE resident design matrix (row i belongs to
    #: fold ``i % folds``), never a data copy. ``1`` disables CV — each
    #: candidate trains on all rows and is scored on them too.
    tune_folds: int = field(
        default_factory=lambda: _env("LO_TPU_TUNE_FOLDS", 3)
    )
    #: HBM budget (MB) for sizing a tune population wave: the largest
    #: candidate count whose modeled per-member footprint (models/
    #: tune.py ``_per_member_bytes``, raised to the family's recorded
    #: ``peak_hbm_bytes`` watermark when one exists) fits this budget
    #: runs as ONE vmapped device program; extra candidates spill into
    #: sequential waves (counted on ``/metrics``). ``0`` = unlimited
    #: (one wave, trusting the device).
    tune_hbm_budget_mb: int = field(
        default_factory=lambda: _env("LO_TPU_TUNE_HBM_BUDGET_MB", 0)
    )
    #: Hard cap on candidates per vmapped wave regardless of the HBM
    #: model — bounds compile-time shape growth for very large sweeps.
    tune_max_population: int = field(
        default_factory=lambda: _env("LO_TPU_TUNE_MAX_POPULATION", 64)
    )

    # --- job-tier fault domain (jobs.py watchdog) ---------------------------
    #: Per-job liveness deadline (seconds): a managed job whose BODY has
    #: started and then makes no PROGRESS for this long — progress marks
    #: (``jobs.heartbeat``) fire at boost-round / tree-batch /
    #: fitting-pass / dispatch boundaries — is failed by the watchdog
    #: thread with the retryable ``interrupted: watchdog`` prefix, the
    #: pod is poisoned so the supervisor restarts it under a new mesh
    #: epoch, and a flight-recorder bundle freezes the evidence. Bounds
    #: the one phase nothing else bounds: a hung device program after
    #: SPMD 'go'. Marks land at PROGRAM boundaries (a running device
    #: program is opaque), so size this above the longest single fit
    #: program plus cold compile — docs/fault_tolerance.md §8 has the
    #: granularity table. ``0`` (the default) disables the watchdog.
    job_deadline_s: float = field(
        default_factory=lambda: _env("LO_TPU_JOB_DEADLINE_S", 0.0)
    )

    # --- elastic recovery (supervisor.py) ----------------------------------
    #: Automatic re-runs per job whose outputs failed from INFRASTRUCTURE
    #: (``pod failure:`` watchdog flags, ``interrupted:`` restart marks) —
    #: the analogue of Spark re-running lost tasks on recovered executors.
    #: On startup, process 0 rescans the store and resubmits such jobs
    #: until each has been retried this many times. 0 disables retry.
    job_retries: int = field(
        default_factory=lambda: _env("LO_TPU_JOB_RETRIES", 1)
    )
    #: Pod restarts the supervisor will attempt before declaring the pod
    #: failed (reason then served via its fallback /cluster responder) —
    #: the bounded analogue of the reference's restart_policy:on-failure.
    restart_budget: int = field(
        default_factory=lambda: _env("LO_TPU_RESTART_BUDGET", 5)
    )
    #: First restart delay, seconds; doubles per restart (exponential
    #: backoff) up to ``restart_backoff_max_s``.
    restart_backoff_s: float = field(
        default_factory=lambda: _env("LO_TPU_RESTART_BACKOFF_S", 1.0)
    )
    restart_backoff_max_s: float = field(
        default_factory=lambda: _env("LO_TPU_RESTART_BACKOFF_MAX_S", 30.0)
    )
    #: Cadence of the supervisor's /cluster health poll, seconds — catches
    #: degradations where no supervised process died (e.g. a remote host's
    #: worker vanished and the watchdog poisoned this pod).
    health_interval_s: float = field(
        default_factory=lambda: _env("LO_TPU_HEALTH_INTERVAL_S", 2.0)
    )
    #: Restart-budget decay window (seconds): after this much CONTINUOUS
    #: healthy pod uptime the supervisor resets its consumed restart
    #: count to zero, so budget spent on an incident from hours ago no
    #: longer dooms tonight's single blip (budget exhaustion used to be
    #: permanent). A pod that keeps flapping faster than this window
    #: still exhausts its budget exactly as before. ``0`` disables decay.
    restart_healthy_s: float = field(
        default_factory=lambda: _env("LO_TPU_RESTART_HEALTHY_S", 300.0)
    )

    # --- observability -----------------------------------------------------
    #: Capacity (spans) of the in-process trace ring buffer
    #: (utils/tracing.py). Old spans evict FIFO past this, so a long-lived
    #: server holds a bounded window of recent traces. 0 disables span
    #: retention entirely (trace ids still mint and propagate).
    trace_buffer_spans: int = field(
        default_factory=lambda: _env("LO_TPU_TRACE_BUFFER_SPANS", 4096)
    )
    #: Probability (0.0-1.0) that a new trace records spans. 1.0 traces
    #: every request/job; 0.0 disables recording (ids still propagate).
    trace_sample: float = field(
        default_factory=lambda: _env("LO_TPU_TRACE_SAMPLE", 1.0)
    )
    #: Log line format for the structured logger (utils/structlog.py):
    #: "text" (human-readable, trace ids appended) or "json" (one JSON
    #: doc per line, trace/span ids as fields).
    log_format: str = field(
        default_factory=lambda: _env("LO_TPU_LOG_FORMAT", "text")
    )
    #: Log level for the framework's ``lo_tpu`` logger tree.
    log_level: str = field(
        default_factory=lambda: _env("LO_TPU_LOG_LEVEL", "INFO")
    )

    # --- telemetry history (utils/timeseries.py) ----------------------------
    #: Cadence (seconds) of the background telemetry sampler: the server
    #: snapshots its own ``/metrics`` document this often into the
    #: history ring, whether or not anything scrapes it — retained
    #: telemetry, not scrape luck, is what post-hoc debugging reads.
    #: ``0`` disables the sampler thread and records one sample per
    #: registry read instead (tests drive history deterministically this
    #: way); negative disables history entirely.
    telemetry_sample_s: float = field(
        default_factory=lambda: _env("LO_TPU_TELEMETRY_SAMPLE_S", 5.0)
    )
    #: In-memory history ring capacity (samples). 720 × the 5 s default
    #: cadence ≈ one hour of full-resolution history served from RAM.
    telemetry_ring_samples: int = field(
        default_factory=lambda: _env("LO_TPU_TELEMETRY_RING_SAMPLES", 720)
    )
    #: Samples per on-disk segment: every this many samples the ring
    #: rotates a delta-encoded segment file to
    #: ``<store_root>/_telemetry/`` so history survives restarts.
    telemetry_segment_samples: int = field(
        default_factory=lambda: _env("LO_TPU_TELEMETRY_SEGMENT_SAMPLES",
                                     120)
    )
    #: Newest on-disk segments kept; older ones are unlinked at each
    #: rotation (bounded retention — telemetry must never eat the disk
    #: the ``disk_free_low`` alert guards).
    telemetry_retention_segments: int = field(
        default_factory=lambda: _env(
            "LO_TPU_TELEMETRY_RETENTION_SEGMENTS", 48)
    )

    # --- flight recorder (utils/flightrec.py) -------------------------------
    #: Newest flight-recorder bundles kept under
    #: ``<store_root>/_flightrec/``; older bundles are pruned at each
    #: dump. ``0`` disables the recorder entirely.
    flightrec_keep: int = field(
        default_factory=lambda: _env("LO_TPU_FLIGHTREC_KEEP", 8)
    )
    #: Minimum seconds between AUTOMATIC bundle dumps (alert firing,
    #: healthz flip, quarantine, supervisor incident): a flapping
    #: condition records its first transition, not one bundle per flap.
    #: Manual ``POST /debug/flightrec`` ignores this.
    flightrec_min_interval_s: float = field(
        default_factory=lambda: _env("LO_TPU_FLIGHTREC_MIN_INTERVAL_S",
                                     30.0)
    )
    #: Seconds of telemetry history captured into each bundle's
    #: ``history.json`` — the "surrounding window" an operator replays.
    flightrec_window_s: float = field(
        default_factory=lambda: _env("LO_TPU_FLIGHTREC_WINDOW_S", 600.0)
    )

    # --- resource & capacity plane (utils/resources.py, utils/alerts.py) ---
    #: Evaluation-window length (seconds) of the declarative alert engine:
    #: rule conditions are (re)checked at most once per window, driven by
    #: /metrics, /alerts, /healthz and status-page reads — the Prometheus
    #: scrape-window model. 0 evaluates on every read (tests).
    alert_window_s: float = field(
        default_factory=lambda: _env("LO_TPU_ALERT_WINDOW_S", 15.0)
    )
    #: Consecutive bad windows before a threshold rule (serving p99,
    #: queue rejection rate) transitions to FIRING — the fire-side
    #: hysteresis that keeps one jittery window from paging anyone.
    #: Event rules (pod degraded, disk watermark, corruption/worker-error
    #: increments) fire on a single window regardless.
    alert_for_windows: int = field(
        default_factory=lambda: _env("LO_TPU_ALERT_FOR_WINDOWS", 2)
    )
    #: Consecutive clean windows before a firing alert resolves — the
    #: resolve-side hysteresis (a flapping condition stays visibly FIRING
    #: instead of strobing).
    alert_clear_windows: int = field(
        default_factory=lambda: _env("LO_TPU_ALERT_CLEAR_WINDOWS", 2)
    )
    #: Serving-latency SLO: the online predict tier's recent-window p99
    #: (milliseconds, per model — worst model counts) above this for
    #: ``alert_for_windows`` windows fires ``serving_p99_slo``. 0 disables
    #: the rule.
    slo_p99_ms: float = field(
        default_factory=lambda: _env("LO_TPU_SLO_P99_MS", 500.0)
    )
    #: Queue-rejection-rate SLO: rejected / offered requests per window
    #: at or above this ratio fires ``serving_reject_rate`` (sustained
    #: backpressure — capacity, not a blip). 0 disables the rule.
    slo_reject_rate: float = field(
        default_factory=lambda: _env("LO_TPU_SLO_REJECT_RATE", 0.05)
    )
    #: Deadline-miss-rate SLO: deadline-expired / offered predict
    #: requests per window above this ratio fires
    #: ``serving_deadline_exceeded_rate`` — callers are giving up on a
    #: sustained fraction of answers, so the device is burning time the
    #: clients no longer want. 0 disables the rule.
    slo_deadline_rate: float = field(
        default_factory=lambda: _env("LO_TPU_SLO_DEADLINE_RATE", 0.05)
    )
    #: Fast burn-rate window (seconds) for the serving SLO rules when a
    #: telemetry history store is attached (serving_p99_slo,
    #: serving_reject_rate, serving_deadline_exceeded_rate): the rule
    #: fires only while the condition is STILL bad over this recent
    #: window. 0 keeps the legacy single-window evaluation.
    slo_burn_fast_s: float = field(
        default_factory=lambda: _env("LO_TPU_SLO_BURN_FAST_S", 300.0)
    )
    #: Slow burn-rate window (seconds): the error budget is judged over
    #: this span, so a brief spike that consumed almost none of it stops
    #: paging, and a slow burn that consumes it keeps paging. 0 keeps
    #: the legacy single-window evaluation.
    slo_burn_slow_s: float = field(
        default_factory=lambda: _env("LO_TPU_SLO_BURN_SLOW_S", 3600.0)
    )
    #: Error budget: the fraction of an evaluation window that may be
    #: out-of-SLO before its burn rate reads 1.0 (the firing line).
    slo_burn_budget: float = field(
        default_factory=lambda: _env("LO_TPU_SLO_BURN_BUDGET", 0.02)
    )
    #: Disk-headroom watermark (MiB) for the chunk store's filesystem:
    #: free bytes under it fires ``disk_free_low`` and degrades
    #: ``GET /healthz`` — ingest/journal writes are about to start
    #: failing. 0 disables the check.
    disk_free_watermark_mb: int = field(
        default_factory=lambda: _env("LO_TPU_DISK_FREE_WATERMARK_MB", 512)
    )
    #: Allow ``POST /debug/profile`` to capture an on-demand
    #: ``jax.profiler`` trace (N seconds, written under
    #: ``<store_root>/_profiles``). Off by default: profiling costs real
    #: overhead and writes operator-readable traces to disk, so it is an
    #: explicit opt-in, never ambient.
    debug_profile: bool = field(
        default_factory=lambda: _env("LO_TPU_DEBUG_PROFILE", False, bool)
    )

    def replace(self, **kw) -> "Settings":
        new = Settings()
        for f in fields(self):
            setattr(new, f.name, kw.get(f.name, getattr(self, f.name)))
        return new


#: Process-global settings instance. Tests construct their own.
settings = Settings()


# --- dynamic environment accessors ------------------------------------------
# Knobs that cannot be Settings fields because they change within a
# process's lifetime (the supervisor bumps LO_TPU_MESH_EPOCH and
# LO_TPU_RESTART_COUNT per pod restart, and the poison/health scope must
# follow the env, not an import-time snapshot) or are read before any
# Settings instance exists (failpoint arming at import). They still live
# HERE: every LO_TPU_* read in the codebase is either a Settings field
# above or an accessor below, so one file answers "what knobs exist" —
# enforced by lolint's env-discipline rule (docs/static_analysis.md),
# which also cross-checks that each knob named in this file appears in
# docs/configuration.md.


def restart_count() -> int:
    """This incarnation's supervisor restart ordinal
    (``LO_TPU_RESTART_COUNT``, set by supervisor.py for each supervised
    child; 0 = first launch). Served on ``/cluster`` as ``restarts``."""
    try:
        return int(os.environ.get("LO_TPU_RESTART_COUNT", "0") or 0)
    except ValueError:
        return 0


def mesh_epoch() -> int:
    """The pod's mesh generation (``LO_TPU_MESH_EPOCH``) — bumped by the
    supervisor on every restart so the SPMD job channel can reject
    workers from a previous incarnation (parallel/spmd.py). Read per
    call, never cached: the epoch-scoped pod poison follows the env."""
    try:
        return int(os.environ.get("LO_TPU_MESH_EPOCH", "0") or 0)
    except ValueError:
        return 0


def coordinator_address(default: Optional[str] = None) -> Optional[str]:
    """``host:port`` of process 0's jax.distributed coordination service
    (``LO_TPU_COORDINATOR``); also locates the SPMD job channel
    (coordinator host, port + 1). None/default = single-host."""
    return os.environ.get("LO_TPU_COORDINATOR") or default


def job_port(default: int) -> int:
    """Explicit SPMD job-channel port (``LO_TPU_JOB_PORT``); defaults to
    the coordinator port + 1 computed by the caller. A malformed value
    raises immediately: silently falling back would have coordinator and
    workers listening on different ports, surfacing as an opaque
    handshake timeout instead of a config error."""
    raw = os.environ.get("LO_TPU_JOB_PORT")
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(
            f"LO_TPU_JOB_PORT must be an integer, got {raw!r}") from None


def num_processes() -> Optional[int]:
    """Pod process count for jax.distributed init
    (``LO_TPU_NUM_PROCESSES``); None = unset (single-host)."""
    raw = os.environ.get("LO_TPU_NUM_PROCESSES")
    return int(raw) if raw else None


def process_id() -> Optional[int]:
    """This process's pod rank for jax.distributed init
    (``LO_TPU_PROCESS_ID``); None = unset (single-host)."""
    raw = os.environ.get("LO_TPU_PROCESS_ID")
    return int(raw) if raw is not None and raw != "" else None


def failpoint_spec() -> str:
    """The deterministic fault-injection arming spec
    (``LO_TPU_FAILPOINTS=site=mode[:nth],...``), read at
    utils/failpoints.py import — before any Settings exists."""
    return os.environ.get("LO_TPU_FAILPOINTS", "")


def shard_host() -> Optional[int]:
    """Explicit placement identity of this host for shard-map planning
    (``LO_TPU_SHARD_HOST``): which ingest-partition owner's chunks count
    as host-local when ``mesh.shard_chunked`` classifies its feed. None =
    unset — multi-process pods use the jax process index, single-process
    sims model the pod topology (parallel/spmd.local_host_id)."""
    raw = os.environ.get("LO_TPU_SHARD_HOST")
    return int(raw) if raw is not None and raw != "" else None
