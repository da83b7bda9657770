"""Ahead-of-time-compiled online predict programs (the serving tier's
device side).

The batch predict path (`ModelBuilder.predict`) re-traces and re-jits per
dataset shape and pays a host→mesh shard per call — fine for minutes-long
dataset jobs, fatal for request/response serving where the whole latency
budget is milliseconds. Here every trained model gets a small set of
predict programs compiled ONCE at model load, bucketed by padded batch
size (1/8/64/…/max_batch), so a micro-batch of any size ≤ max_batch
dispatches a pre-compiled XLA executable with zero trace/compile work on
the hot path — the same static-shape discipline the fit programs use,
applied to serving.

Design points:

- **AOT, not lazy jit**: ``jax.jit(...).lower(params, x_spec).compile()``
  at load time. The first request never eats a compile; a model's whole
  bucket ladder is built before it serves.
- **Bucketed padding**: requests coalesce into batches padded up to the
  next bucket. Few buckets keep compile count bounded; padding rows are
  zeros and sliced off the output (per-row programs mask nothing —
  every family's predict is row-local, so pad rows cannot perturb real
  rows).
- **Per-device replicas, not mesh shards**: micro-batches (≤ a few
  hundred rows) cannot amortize a mesh shard, and single-device
  programs carry no collectives — so the online tier is safe
  per-process even on a multi-process pod (no SPMD dispatch scope
  needed; contrast ``MeshRuntime.shard_rows``). ``serve_replicas``
  (``LO_TPU_SERVE_REPLICAS``) replicates the whole bucket ladder
  across N local devices instead: params ``device_put`` to each
  replica's device, one compiled ladder per device, every replica
  bit-identical by the row-wise-evaluation argument below. The default
  (1) preserves the single-device topology byte-for-byte; 0 means all
  local devices.
- **No donated inputs**: the (b, d) batch buffer can never hold the
  (b, classes) output, so donating it bought nothing — on the TPU every
  bucket compile only warned "Some donated buffers were not usable"
  (first chip run, PR 22) — and the TPU-only branch is gone.
- **Versioned cache**: programs are keyed (model name, version, bucket)
  where version is the manifest file's (mtime_ns, size). Re-saving a
  model under the same name (incremental refit, ROADMAP item 4) or
  deleting it invalidates automatically on the next entry lookup.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from learningorchestra_tpu.config import Settings, settings as global_settings
from learningorchestra_tpu.models.persistence import ModelRegistry
from learningorchestra_tpu.models.registry import ONLINE_KINDS
from learningorchestra_tpu.utils import failpoints, resources

#: Chaos seam before a model's bucket-ladder load+compile — raise-mode
#: proves a failed cold load surfaces as the request's error (never a
#: half-cached entry), slow/hang-mode that compile stalls block only the
#: loading model's requests (per-name lock, docs/fault_tolerance.md §7).
FP_PRE_COMPILE = failpoints.declare("serving.aot.pre_compile")


def resolve_replicas(cfg: Settings) -> int:
    """How many device replicas the online predict plane runs
    (``serve_replicas``): 1 — the default — is today's single-device
    topology, kept byte-for-byte; 0 means one replica per local device;
    any other N clamps to the locally addressable device count (never
    the global pod list — other processes' devices are not addressable
    from here)."""
    n = int(cfg.serve_replicas)
    if n == 1:
        return 1
    import jax

    avail = max(1, len(jax.local_devices()))
    return avail if n <= 0 else min(n, avail)


def predict_buckets(max_batch: int) -> Tuple[int, ...]:
    """The padded-batch-size ladder: powers of 8 up to ``max_batch``,
    which is always itself a bucket (1, 8, 64, 256 for the default 256).
    Geometric spacing bounds both the compile count (log_8) and the
    worst-case padding waste (<8x, and real micro-batches cluster near
    the coalesced size anyway)."""
    max_batch = max(1, int(max_batch))
    out: List[int] = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 8
    out.append(max_batch)
    return tuple(out)


def bucket_for(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _numeric_column(field: str, values: List[Any]) -> np.ndarray:
    """Column-ize one numeric field of inline rows (None → NaN so fitted
    fillna stats apply). Strings are rejected rather than silently
    fitted a fresh vocab: the model has no encoding for this field, and
    letting ``apply_steps`` invent one would both answer garbage and
    write into the SHARED fitted state from a request thread."""
    try:
        return np.array([np.nan if v is None else float(v)
                         for v in values], dtype=np.float64)
    except (TypeError, ValueError):
        raise ValueError(
            f"field {field!r} is numeric for this model; got "
            "non-numeric values") from None


def design_from_rows(rows: Sequence[Any], pp: Dict[str, Any]) -> np.ndarray:
    """Inline JSON feature rows → the model's design matrix, with its
    train-time preprocessing state applied.

    Two row forms:

    - list of objects ``{field: value}`` — raw source fields; the fitted
      pipeline (label-encode vocabs, fillna statistics, standardize
      stats) applies exactly as ``ModelBuilder.predict`` applies it to a
      stored dataset. A field the fitted vocab knows is forced to the
      object dtype (so numbers sent for a train-time string column still
      hit the vocab), everything else is numeric.
    - list of lists — already-assembled design rows in
      ``feature_fields`` order (the zero-copy fast path for callers that
      preprocess client-side).
    - a 2-D ``np.ndarray`` — rows already decoded from a binary columnar
      request body (serving/rowchannel.py): same width/finiteness
      validation as list rows with ZERO per-row decode — the buffer the
      socket delivered is the design matrix.
    """
    from learningorchestra_tpu.ops.preprocess import apply_steps

    if isinstance(rows, np.ndarray):
        feature_fields = list(pp["feature_fields"])
        if rows.ndim != 2 or rows.shape[0] == 0:
            raise ValueError(
                "columnar rows must be a non-empty 2-D matrix")
        if rows.shape[1] != len(feature_fields):
            raise ValueError(
                f"columnar rows must be shaped (n, {len(feature_fields)}) "
                f"matching feature_fields {feature_fields}")
        X = np.asarray(rows, dtype=np.float32)
        return _finite_design(np.ascontiguousarray(X), feature_fields)
    if not isinstance(rows, (list, tuple)) or not rows:
        raise ValueError("rows must be a non-empty JSON array")
    feature_fields = list(pp["feature_fields"])
    if not isinstance(rows[0], dict):
        try:
            X = np.asarray(rows, dtype=np.float32)
        except (TypeError, ValueError):
            # Non-numeric elements (dicts mixed into list rows, strings,
            # nested objects) must 406 like every other malformed body,
            # not surface numpy's TypeError as a 500.
            raise ValueError(
                "list rows must contain only numeric values") from None
        if X.ndim != 2 or X.shape[1] != len(feature_fields):
            raise ValueError(
                f"list rows must be shaped (n, {len(feature_fields)}) "
                f"matching feature_fields {feature_fields}")
        return _finite_design(np.ascontiguousarray(X), feature_fields)

    if not all(isinstance(r, dict) for r in rows):
        raise ValueError("rows must be all objects or all lists")
    # Empty steps means the default pipeline — ``design_matrix`` defaults
    # it internally, so persisted manifests carry [] and the fitted state
    # keys ("0:label_encode", …) only line up once we default the same
    # way.
    from learningorchestra_tpu.ops.preprocess import _DEFAULT_STEPS

    steps = pp["steps"] or list(_DEFAULT_STEPS)
    # The fitted state is shared READ-ONLY across concurrent requests —
    # no per-request copy (a deepcopy of a 100k-entry vocab would
    # dominate single-row predicts). Safe because the column coercion
    # below guarantees apply_steps never has a statistic to fit: fields
    # the fitted vocabs know arrive as object/string columns, every
    # other field arrives numeric-or-406, and every fitted step carries
    # its state key, so all step branches reduce to pure application.
    state = pp["state"]
    vocab_fields = set()
    for key, val in state.items():
        if ":label_encode" in str(key) and isinstance(val, dict):
            vocab_fields.update(val.keys())
    fields: List[str] = []
    for r in rows:
        for f in r:
            if f not in fields:
                fields.append(f)
    label = pp.get("label")
    # Only the columns the design needs: feature fields plus any field
    # the fitted vocabs encode. Extra payload fields (a Name column, a
    # request id) are ignored, matching the batch path's tolerance of
    # non-feature columns — rejecting them would 406 every client that
    # sends its full raw record.
    needed = set(feature_fields) | vocab_fields
    cols: Dict[str, np.ndarray] = {}
    for f in fields:
        if f == label or f not in needed:
            continue                      # label / non-feature payload
        values = [r.get(f) for r in rows]
        if f in vocab_fields:
            # Train-time string column: route through the fitted vocab
            # (unknown values encode to len(vocab), same as the batch
            # path's apply-to-test semantics).
            cols[f] = np.array(
                [None if v is None else str(v) for v in values],
                dtype=object)
        else:
            cols[f] = _numeric_column(f, values)
    out, _ = apply_steps(cols, steps, state)
    missing = [f for f in feature_fields if f not in out]
    if missing:
        raise ValueError(
            f"rows missing model feature fields: {missing}")
    return _finite_design(np.stack(
        [np.asarray(out[f], np.float32) for f in feature_fields], axis=1),
        feature_fields)


def _finite_design(X: np.ndarray, feature_fields: List[str]) -> np.ndarray:
    """Reject rows whose design values are non-finite AFTER the fitted
    pipeline ran — e.g. a null sent for a field that had no missing
    values at train time, so no fill statistic was ever fitted. The
    batch path would silently propagate the NaN into NaN probabilities
    (caught live during verification); online serving answers an
    explicit 406 naming the field instead."""
    finite = np.isfinite(X)
    if not finite.all():
        bad = ~finite
        bad_rows = np.where(bad.any(axis=1))[0]
        bad_fields = [feature_fields[j]
                      for j in np.where(bad.any(axis=0))[0]]
        raise ValueError(
            f"rows {bad_rows[:5].tolist()} have non-finite features "
            f"after preprocessing (fields {bad_fields}); the model was "
            "fitted with no fill statistic for them — send finite "
            "values or refit with NaNs present")
    return X


class AotModel:
    """One loaded trained model + its compiled bucket ladder.

    Compilation happens once, in ``__init__`` (model load) — never on
    the request path. ``predict`` pads a host batch up to its bucket,
    runs the compiled executable on the serving device, and slices the
    padding back off.
    """

    def __init__(self, name: str, version: Tuple[int, int],
                 manifest: Dict[str, Any], model,
                 buckets: Sequence[int], replicas: int = 1):
        import jax
        import jax.numpy as jnp

        if manifest["kind"] not in ONLINE_KINDS:
            raise ValueError(
                f"model kind {manifest['kind']!r} is not servable online "
                f"(supported: {list(ONLINE_KINDS)})")
        pp = manifest.get("preprocess")
        if pp is None:
            raise ValueError(
                f"model {name} was exec-preprocessed; it carries no "
                "reproducible preprocessing state to apply to request rows")
        self.name = name
        self.version = version
        self.manifest = manifest
        self.preprocess = pp
        self.kind = manifest["kind"]
        self.buckets = tuple(buckets)
        self.n_features = len(pp["feature_fields"])
        #: Swap-epoch token stamped by the cache on insert: strictly
        #: increasing per model name across rebuilds, so any response
        #: evaluated through this entry is attributable to exactly one
        #: version-swap generation (the mesh-epoch discipline applied to
        #: the registry version token). 0 until the cache stamps it.
        self.swap_epoch = 0
        # local_devices, not devices: after jax.distributed init the
        # global list leads with the coordinator's devices, which are
        # non-addressable from other pod processes — each process must
        # pin its online tier to devices it owns. Replica i is pinned to
        # local device i; replicas beyond the local device count would
        # double up on a device for zero parallelism, so they clamp.
        local = jax.local_devices()
        self.n_replicas = max(1, min(int(replicas), len(local)))
        self._devices = local[:self.n_replicas]
        #: Host bytes of one params pytree, and the total replicated
        #: device footprint (× n_replicas) — the AOT cache snapshot's
        #: bytes accounting, next to compile_s.
        self.params_bytes_per_replica = int(sum(
            np.asarray(leaf).nbytes
            for leaf in jax.tree.leaves(model.params)))
        self.params_bytes = self.params_bytes_per_replica * self.n_replicas
        self._params_r = [jax.device_put(model.params, d)
                          for d in self._devices]
        self._device = self._devices[0]
        self._params = self._params_r[0]
        fn = model.predict_proba_fn

        def rowwise(p, x):
            # One dispatch per BATCH, but rows evaluate one at a time
            # inside the program (on-device lax.map over (1, d) slices).
            # This is deliberate: XLA's batched reductions round
            # shape-dependently (measured on CPU: rf diverges between a
            # (3,d) and a padded (8,d) batch, mlp between (1,d) and
            # (3,d)), so a batched matmul would make a row's probability
            # depend on which bucket its batch coalesced into. Row-wise
            # evaluation pins the per-row compute shape to (1, d) —
            # bit-identical across every bucket AND to the batch
            # predict path's per-row oracle — and micro-batches this
            # size are dispatch-overhead-bound, not FLOP-bound, so the
            # batching win (one dispatch, measured 30-77x over per-row
            # dispatch) is untouched.
            return jax.lax.map(lambda r: fn(p, r[None, :])[0], x)

        jitted = jax.jit(rowwise)
        x_specs = {
            b: jax.ShapeDtypeStruct((b, self.n_features), jnp.float32)
            for b in self.buckets}
        # The whole bucket-ladder set is a compile site the resource
        # plane accounts: wall time of the build (all replicas) plus the
        # XLA backend-compile seconds the monitoring listener attributes
        # to this window (lo_compile_* on /metrics;
        # docs/observability.md). Lowering against each replica's
        # committed params pins each ladder to that replica's device —
        # explicit placement, so dispatchers on different replicas never
        # contend for one device.
        resources.ensure_listener()
        c0 = resources.compile_seconds()
        t0 = time.monotonic()
        self._programs_r = [
            {b: jitted.lower(params, x_specs[b]).compile()
             for b in self.buckets}
            for params in self._params_r]
        self._programs = self._programs_r[0]
        #: Wall seconds this model's ladder(s) took to build, and the
        #: XLA backend-compile share of it — surfaced per load on the
        #: AOT cache snapshot so a hot-swap's recompile cost is
        #: attributable.
        self.compile_wall_s = round(time.monotonic() - t0, 6)
        self.compile_s = round(resources.compile_seconds() - c0, 6)

    def predict_padded(self, X: np.ndarray, replica: int = 0) -> np.ndarray:
        """One device dispatch for a host batch of ≤ max-bucket rows:
        pad → compiled executable → host probs sliced to the true count.
        This is the ONLY device entry of the online tier; replica
        ``replica``'s dispatcher thread owns that replica's device
        (replica 0 — the default — is the single-device topology)."""
        import jax

        n = len(X)
        bucket = bucket_for(n, self.buckets)
        if n < bucket:
            X = np.concatenate(
                [X, np.zeros((bucket - n, self.n_features), np.float32)],
                axis=0)
        x_dev = jax.device_put(np.ascontiguousarray(X, np.float32),
                               self._devices[replica])
        return np.asarray(self._programs_r[replica][bucket](
            self._params_r[replica], x_dev))[:n]

    def predict(self, X: np.ndarray, replica: int = 0) -> np.ndarray:
        """Probabilities for any host batch on the given replica's
        device; rows beyond the largest bucket run as successive
        max-bucket dispatches. Bit-identical across replicas: the
        row-wise program pins per-row numerics to a (1, d) compute
        shape, and every replica compiles the identical program from
        the identical params bytes."""
        max_b = self.buckets[-1]
        if len(X) <= max_b:
            return self.predict_padded(X, replica)
        return np.concatenate(
            [self.predict_padded(X[i:i + max_b], replica)
             for i in range(0, len(X), max_b)], axis=0)


class AotCache:
    """Persistent in-process cache of compiled predict programs, keyed
    (model name, version, bucket) — version is the manifest file's
    (mtime_ns, size), so a re-save under the same name recompiles and a
    delete raises ``ModelNotFound`` on the next lookup."""

    def __init__(self, registry: ModelRegistry,
                 cfg: Optional[Settings] = None):
        self.registry = registry
        self.cfg = cfg or global_settings
        self.buckets = predict_buckets(self.cfg.serve_max_batch)
        #: Device replicas every entry's ladder is compiled for —
        #: resolved ONCE so every model in this cache has the same
        #: replica topology (the router and the dispatcher set in
        #: serving/batcher.py are sized off the same number).
        self.replicas = resolve_replicas(self.cfg)
        self._lock = threading.Lock()
        self._models: Dict[str, AotModel] = {}
        self._name_locks: Dict[str, threading.Lock] = {}
        #: Per-name swap epoch: bumped each time a (re)built entry is
        #: inserted, stamped onto the entry. Because ONE AotModel holds
        #: ALL replicas' params+ladders and the name maps to exactly one
        #: entry, every replica of a model always serves the same
        #: version — the epoch is the observable token proving which
        #: swap generation a response came from.
        self._epochs: Dict[str, int] = {}
        self._compiles = 0
        self._evictions = 0
        self._hits = 0
        self._compile_s = 0.0

    def entry(self, name: str) -> AotModel:
        """The loaded+compiled model, (re)built when absent or stale.
        The manifest stat per lookup (``ModelRegistry.version``) is the
        staleness probe — ~µs, paid once per request, and what lets a
        hot-swapped model serve its new version without a restart.

        Loading + compiling runs under a PER-NAME lock, never the
        global one: a cold load or hot-swap of one model (seconds of
        XLA compiles for the whole bucket ladder) must not
        head-of-line-block every other model's handlers and
        dispatchers."""
        version = self.registry.version(name)
        with self._lock:
            ent = self._models.get(name)
            if ent is not None and ent.version == version:
                self._hits += 1
                hit = True
            else:
                hit = False
                name_lock = self._name_locks.setdefault(
                    name, threading.Lock())
        if hit:
            # Counted outside the cache lock: a compile-cache hit per
            # served request is the hit leg of lo_compile_* — the miss
            # leg (real backend compiles) comes from the monitoring
            # listener (utils/resources.py).
            resources.note_cache_hit()
            return ent
        with name_lock:
            # Re-read the token under the name lock: a save() completing
            # while we waited means load() below returns the NEW content
            # — tagging it with the pre-wait token would force a full
            # redundant bucket-ladder recompile on the next request.
            version = self.registry.version(name)
            with self._lock:                 # another thread built it?
                ent = self._models.get(name)
                if ent is not None and ent.version == version:
                    return ent
                stale = ent is not None
            # Double-read the token AROUND the load and retry until it
            # is stable: version() is lock-free while load() waits out
            # any in-flight save() on the registry lock, so a lone
            # pre-load read can pair a pre-save token with post-save
            # content — mistagged cache ⇒ the next request's probe
            # mismatches and re-pays the whole seconds-long bucket
            # ladder. Tokens are strictly increasing across saves (no
            # ABA), so token-before == token-after proves the loaded
            # snapshot corresponds to that token; a retry costs one
            # checkpoint restore, never a compile.
            failpoints.fire(FP_PRE_COMPILE)
            while True:
                manifest, model = self.registry.load(name)
                after = self.registry.version(name)
                if after == version:
                    break
                version = after
            ent = AotModel(name, version, manifest, model, self.buckets,
                           replicas=self.replicas)
            # Deleted while we compiled? Re-probe before caching: the
            # bucket-ladder compile takes seconds, and inserting after a
            # DELETE's invalidate() would pin device params for a model
            # that can never serve (and overstate models_loaded) until
            # restart. ModelNotFound propagates as the request's 404.
            # The residual insert-vs-invalidate window is µs, vs the
            # seconds-long window this closes.
            self.registry.version(name)
            with self._lock:
                if stale:
                    self._evictions += 1
                # Stamp the swap epoch under the same lock that makes
                # the entry visible: readers that observe the new entry
                # observe its (strictly increasing) epoch atomically, so
                # no two responses from one epoch can span a version
                # swap.
                ent.swap_epoch = self._epochs.get(name, 0) + 1
                self._epochs[name] = ent.swap_epoch
                self._models[name] = ent
                self._compiles += len(self.buckets) * ent.n_replicas
                self._compile_s = round(
                    self._compile_s + ent.compile_s, 6)
            return ent

    def invalidate(self, name: Optional[str] = None) -> None:
        with self._lock:
            if name is None:
                self._evictions += len(self._models)
                self._models.clear()
            elif self._models.pop(name, None) is not None:
                self._evictions += 1

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"models_loaded": len(self._models),
                    "programs_compiled": self._compiles,
                    "compile_s": round(self._compile_s, 6),
                    "hits": self._hits,
                    "evictions": self._evictions,
                    "buckets": list(self.buckets),
                    "replicas": self.replicas,
                    # Replicated-params device footprint of everything
                    # currently loaded — the bytes side of the
                    # compile_s accounting (ISSUE 16 satellite).
                    "params_bytes": sum(
                        m.params_bytes for m in self._models.values()),
                    # Completed hot-swaps: epoch 1 is the cold load, so
                    # each name contributes (epoch - 1) swaps.
                    "swaps": sum(
                        e - 1 for e in self._epochs.values())}
