"""Mesh runtime — the compute tier replacing the reference's Spark cluster.

The reference scales by adding Spark workers to a standalone cluster
(`docker service scale microservice_sparkworker=N`, reference
docs/usage.md:21-33) and partitions DataFrames across them (800 shuffle
partitions, model_builder.py:80). The TPU-native equivalent is a
``jax.sharding.Mesh`` over the attached devices with named axes:

- ``data`` — rows of a dataset are sharded across this axis (the analogue of
  Spark's RDD partitioning; SURVEY.md §2 parallelism #1). All trainers and
  analytics reductions psum over it, which XLA lowers to ICI all-reduces.
- ``model`` — parameters/features shard across this axis for wide models
  (no Spark analogue; the TPU-idiomatic hook SURVEY.md §2 calls for).

Arrays move host→device exactly once per job via ``shard_rows`` (row-sharded
``jax.device_put``); every subsequent op runs device-side. Multi-host:
``jax.distributed`` bootstrap lives in ``parallel/distributed.py``; this
module only sees the global device list, so the same code drives 1 chip or a
pod slice.
"""

from __future__ import annotations

import threading
import weakref
from typing import Optional, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learningorchestra_tpu.config import Settings, settings as global_settings

DATA_AXIS = "data"
MODEL_AXIS = "model"
#: Sequence/context-parallel axis: long sequences shard their length across
#: it and attention runs as a ring over ICI (parallel/ring_attention.py).
SEQ_AXIS = "seq"


def local_mesh(cfg: Optional[Settings] = None,
               devices=None) -> Mesh:
    """Build the (data, model, seq) mesh over the given (default: all)
    devices.

    Default layout puts every device on the data axis — the reference's
    pure-data-parallel Spark layout. ``cfg.mesh_shape = "D,M"`` or
    ``"D,M,S"`` forces the layout (e.g. "2,2,2" on 8 devices for
    data×model×seq sharding; the seq axis defaults to 1).
    """
    cfg = cfg or global_settings
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if cfg.mesh_shape:
        dims = [int(x) for x in cfg.mesh_shape.split(",")]
        if len(dims) not in (2, 3):
            raise ValueError(
                f"mesh_shape {cfg.mesh_shape!r} must be 'D,M' or 'D,M,S'")
        if len(dims) == 2:
            dims.append(1)                      # no seq axis requested
        d, m, s = dims
        if d * m * s != n:
            raise ValueError(
                f"mesh_shape {cfg.mesh_shape} != device count {n}")
    else:
        d, m, s = n, 1, 1
    arr = mesh_utils.create_device_mesh((d, m, s), devices=devices)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS, SEQ_AXIS))


def pad_rows(arr: np.ndarray, multiple: int) -> Tuple[np.ndarray, int]:
    """Pad axis-0 to a multiple (static shapes for XLA); returns (padded, n).

    Padding rows are zeros; compute masks them via ``row < n`` so results are
    exact — the device-side analogue of the reference filtering out its
    metadata row before compute (projection.py:105-110).
    """
    n = arr.shape[0]
    pad = (-n) % multiple
    if pad:
        arr = np.concatenate(
            [arr, np.zeros((pad,) + arr.shape[1:], dtype=arr.dtype)], axis=0)
    return arr, n


def shard_rows(mesh: Mesh, arr: np.ndarray) -> Tuple[jax.Array, int]:
    """Place a host array on the mesh sharded along rows (data axis).

    Returns the device array (rows padded to the data-axis size) and the
    true row count for masking.

    Multi-process: ``jax.device_put`` of a host array only addresses local
    devices, so the global array is assembled per-process from a callback —
    each process materializes exactly the row blocks its addressable shards
    own (every process holds the same host array, rebuilt from the shared
    store; SURVEY.md §2's Mongo-as-shared-data-plane role).
    """
    arr = np.asarray(arr)
    n_shards = mesh.shape[DATA_AXIS]
    padded, n = pad_rows(arr, n_shards)
    spec = P(DATA_AXIS, *([None] * (arr.ndim - 1)))
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() > 1:
        out = jax.make_array_from_callback(
            padded.shape, sharding, lambda idx: padded[idx])
    else:
        out = jax.device_put(padded, sharding)
    return out, n


def _plan_placement(ranges, n_rows: int, shard_map) -> None:
    """Classify each addressable shard's row range against the dataset's
    ingest shard map (owner host → contiguous row range, recorded by the
    range-partitioned ingest in catalog/ingest.py): rows whose owning
    host is the host that will read them count local, the rest remote —
    readpipe's ``lo_shard_local_reads_total`` / ``_remote_reads_total``,
    whose local fraction is THE placement health signal. An aligned feed
    (devices in partition order over a partition-aligned dataset) plans
    ~1.0 local, with only boundary tails remote; those tails still read
    correctly through the replicate.fetch_chunk repair path.

    On a real multi-process pod every range here is addressed by THIS
    host (``spmd.local_host_id``) — as it is under an explicit
    ``LO_TPU_SHARD_HOST``. A single-process sim addresses every device,
    so it models the pod topology instead: consecutive devices per host,
    range k of D read by host k*H//D."""
    if not shard_map:
        return
    parts = shard_map.get("partitions") or []
    hosts = max(1, int(shard_map.get("hosts") or 1))
    if not parts:
        return
    from learningorchestra_tpu import config as _config
    from learningorchestra_tpu.catalog import readpipe
    from learningorchestra_tpu.parallel import spmd

    pinned = _config.shard_host() is not None or jax.process_count() > 1
    n_ranges = max(1, len(ranges))
    local_total = 0
    remote_total = 0
    for k, (start, stop) in enumerate(ranges):
        start, stop = int(start), min(int(stop), n_rows)
        if stop <= start:
            continue
        reader = (spmd.local_host_id() if pinned
                  else (k * hosts) // n_ranges)
        local = 0
        for p in parts:
            if int(p.get("host", -1)) != reader:
                continue
            r0 = int(p.get("row_start", 0))
            r1 = r0 + int(p.get("rows", 0))
            local += max(0, min(stop, r1) - max(start, r0))
        local_total += local
        remote_total += (stop - start) - local
    if local_total:
        readpipe.bump_shard("local_reads", local_total)
    if remote_total:
        readpipe.bump_shard("remote_reads", remote_total)


def shard_chunked(mesh: Mesh, design,
                  prefetch: Optional[int] = None) -> Tuple[jax.Array, int]:
    """Row-shard a LAZY design matrix (ops/preprocess.ChunkedDesign
    protocol: ``.shape``/``.dtype``/``.rows(start, stop)``) without ever
    materializing it fully on the host.

    ``jax.make_array_from_callback`` asks for each addressable shard's
    index; the callback materializes exactly that row range from the chunk
    store. On a pod each process therefore reads only its OWN shards —
    host-RAM cost divides by process count instead of multiplying
    (VERDICT r4 #1; the reference's executors likewise hold only their
    partitions, model_builder.py:200). Tail padding rows are zeros, masked
    by ``row < n`` downstream exactly like ``shard_rows``.

    Device feeding is DOUBLE-BUFFERED (the streamed-fit data path's
    host→device overlap): the addressable shard ranges are known up
    front, so a readpipe worker materializes shard i+1's rows from the
    chunk store while ``device_put`` of shard i runs on the caller
    thread. At most two shards are ever resident beyond what the device
    holds — per-process host memory stays O(shard), not O(dataset).
    ``prefetch=0`` (or a single addressable shard) degenerates to the
    strictly serial read→put loop, the parity oracle; a range jax
    requests that was not read ahead (defensive — callback order is
    expected to follow the addressable-device order) materializes
    inline."""
    n = int(design.shape[0])
    n_shards = mesh.shape[DATA_AXIS]
    padded_n = n + (-n) % n_shards
    tail = tuple(int(s) for s in design.shape[1:])
    sharding = NamedSharding(mesh, P(DATA_AXIS, *([None] * len(tail))))
    dtype = np.dtype(getattr(design, "dtype", np.float32))

    def read_range(start: int, stop: int) -> np.ndarray:
        parts = []
        if start < n:
            parts.append(np.ascontiguousarray(
                np.asarray(design.rows(start, min(stop, n)), dtype)))
        pad = stop - max(start, n)
        if pad > 0:
            parts.append(np.zeros((pad,) + tail, dtype))
        return parts[0] if len(parts) == 1 else np.concatenate(parts, 0)

    def norm(idx) -> Tuple[int, int]:
        rs = idx[0]
        return (rs.start or 0,
                padded_n if rs.stop is None else rs.stop)

    from learningorchestra_tpu.catalog import readpipe

    # Deduped addressable shard ranges in device order (devices on a >1
    # model/seq axis replicate a row range; read it once).
    order: list = []
    seen = set()
    for idx in sharding.addressable_devices_indices_map(
            (padded_n,) + tail).values():
        key = norm(idx)
        if key not in seen:
            seen.add(key)
            order.append(key)
    _plan_placement(order, n, getattr(design, "shard_map", None))
    depth = min(2, readpipe.prefetch_depth(prefetch))
    if depth <= 0 or len(order) <= 1:
        out = jax.make_array_from_callback(
            (padded_n,) + tail, sharding,
            lambda idx: read_range(*norm(idx)))
        return out, n

    pool = readpipe.pool()
    state_lock = threading.Lock()
    pending = list(order)            # ranges not yet submitted
    futures: dict = {}               # (start, stop) -> Future

    def submit_ahead() -> None:
        with state_lock:
            while pending and len(futures) < depth:
                key = pending.pop(0)
                futures[key] = pool.submit(read_range, *key)

    submit_ahead()

    def cb(idx):
        key = norm(idx)
        with state_lock:
            fut = futures.pop(key, None)
        submit_ahead()           # keep the next read in flight while we
        if fut is None:          # (possibly) block on this one
            return read_range(*key)
        if not fut.done():
            readpipe.bump("prefetch_stalls")
        try:
            return fut.result()
        except BaseException:
            readpipe.bump("worker_errors")
            raise

    try:
        out = jax.make_array_from_callback((padded_n,) + tail, sharding, cb)
    finally:
        with state_lock:
            leftover = list(futures.values())
            futures.clear()
            pending.clear()
        for fut in leftover:
            fut.cancel()
        for fut in leftover:
            if not fut.cancelled():
                try:
                    fut.result()
                except BaseException:  # noqa: BLE001 — result discarded
                    pass
    return out, n


def replicate(mesh: Mesh, x) -> jax.Array:
    """Replicate a value across every mesh device (fully-replicated spec)."""
    x = np.asarray(x)
    sharding = NamedSharding(mesh, P())
    if jax.process_count() > 1:
        return jax.make_array_from_callback(
            x.shape, sharding, lambda idx: x[idx])
    return jax.device_put(x, sharding)


def host_rows(x: jax.Array) -> np.ndarray:
    """Device array → host numpy, valid under multi-process.

    Row-sharded outputs are not fully addressable when the mesh spans
    processes; ``process_allgather`` (a collective — every process must
    call it, which the SPMD dispatch protocol guarantees) gathers the
    global value. Single-process is a plain copy."""
    if jax.process_count() > 1 and not x.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)


class MeshRuntime:
    """Process-wide mesh holder (built lazily on first compute job).

    The reference builds one SparkSession per request and tears it down
    (model_builder.py:70-95,177); devices are persistent here, so the mesh is
    built once and shared by every job in the server process.

    ``shard_rows`` memoizes host→device transfers per host array: a
    5-classifier build shards the same design matrix five times (and the
    host→device link makes each gigabyte-scale transfer a dominant cost), so the sharded device array is cached keyed by the host
    array's identity and dropped when the host array is garbage-collected.
    Callers must treat arrays handed to ``shard_rows`` as immutable; the
    cache *enforces* this by marking cached owner-arrays read-only (a later
    in-place write raises instead of silently computing on stale device
    data). Views are sharded uncached.
    """

    def __init__(self, cfg: Optional[Settings] = None):
        self.cfg = cfg or global_settings
        # RLock: cache-eviction finalizers can fire from gc inside a
        # lock-holding allocation; a plain Lock would self-deadlock.
        self._lock = threading.RLock()
        self._mesh: Optional[Mesh] = None
        self._transfer_cache: dict = {}

    @property
    def mesh(self) -> Mesh:
        with self._lock:
            if self._mesh is None:
                self._mesh = local_mesh(self.cfg)
            return self._mesh

    def shard_rows(self, arr: np.ndarray) -> Tuple[jax.Array, int]:
        # Structural SPMD guard: on a multi-process pod, host→device entry
        # is only legal inside a dispatched job scope (parallel/spmd.py) —
        # every mesh op funnels through here or replicate, so nothing can
        # "forget" to dispatch and wedge the pod mid-collective.
        from learningorchestra_tpu.parallel import spmd

        spmd.check_mesh_entry("shard_rows")
        if hasattr(arr, "rows") and not isinstance(arr, np.ndarray):
            # Lazy design matrix (ChunkedDesign protocol): device shards
            # materialize from per-shard range reads; cache by identity
            # like host arrays (a 5-classifier build shards the same
            # design five times). Designs pin their row snapshot at
            # construction, so identity-keyed caching is sound.
            key = ("design", id(arr))
            with self._lock:
                hit = self._transfer_cache.get(key)
            if hit is not None:
                return hit
            out = shard_chunked(self.mesh, arr,
                                prefetch=self.cfg.prefetch_chunks)
            with self._lock:
                self._transfer_cache[key] = out

                def _evict_d(cache=self._transfer_cache, key=key,
                             lock=self._lock):
                    with lock:
                        cache.pop(key, None)

                weakref.finalize(arr, _evict_d)
            return out
        if not isinstance(arr, np.ndarray):
            return shard_rows(self.mesh, arr)
        key = (id(arr), arr.shape, str(arr.dtype))
        with self._lock:
            hit = self._transfer_cache.get(key)
        if hit is not None:
            return hit
        # Enforce the immutability contract instead of just documenting it:
        # freeze the host array on first caching so an in-place mutation
        # (which would silently serve stale device data) raises at the
        # mutation site. Views never enter the cache — freezing a view
        # leaves its base writable, so mutation through the base would
        # still serve stale device data silently.
        if arr.base is not None or not arr.flags.owndata:
            return shard_rows(self.mesh, arr)
        arr.flags.writeable = False
        out = shard_rows(self.mesh, arr)
        with self._lock:
            self._transfer_cache[key] = out

            def _evict(cache=self._transfer_cache, key=key, lock=self._lock):
                with lock:
                    cache.pop(key, None)

            # Drop the device copy when the host array dies (also guards
            # against a recycled id() pointing at the stale entry).
            weakref.finalize(arr, _evict)
        return out

    def replicate(self, x) -> jax.Array:
        from learningorchestra_tpu.parallel import spmd

        spmd.check_mesh_entry("replicate")
        return replicate(self.mesh, x)


_runtime: Optional[MeshRuntime] = None
_runtime_lock = threading.Lock()


def get_runtime(cfg: Optional[Settings] = None) -> MeshRuntime:
    global _runtime
    with _runtime_lock:
        if _runtime is None:
            _runtime = MeshRuntime(cfg)
        return _runtime
