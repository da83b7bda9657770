"""Histogram service op: per-field value counts over the mesh.

The reference's histogram microservice runs a Mongo aggregation
``[{"$group": {"_id": "$field", "count": {"$sum": 1}}}]`` per requested field
and stores the result as a new collection (reference histogram.py:49-74).

TPU-native design: for integer/categorical columns the count is a one-hot
bincount computed *on the mesh* — each data-axis shard scatter-adds its local
rows into a bin vector, then a ``psum`` over the data axis reduces partial
counts; XLA lowers that psum to an ICI all-reduce, making this op the
framework's allreduce exemplar (SURVEY.md §7 stage 3). Float/string columns
fall back to a vectorized host ``np.unique`` (still thousands of times
fewer operations than a per-document Mongo pipeline).

Result dataset shape matches the reference: one row per field, carrying the
value→count mapping, with lineage ``parent_filename`` set.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from learningorchestra_tpu.catalog.store import (
    DatasetStore, column_value_counts)
from learningorchestra_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, MeshRuntime

#: Columns with more distinct integer levels than this go to the host path —
#: a bin vector past this size stops being a cheap VPU scatter target.
MAX_DEVICE_BINS = 1 << 16


#: Elements allowed in one (blk × bins) one-hot transient (~128 M bools).
_BINCOUNT_BLOCK_ELEMS = 1 << 27
#: Widest histogram the one-hot reduction path handles; beyond it the
#: transient row blocks get too skinny to amortize and scatter-add wins.
_ONEHOT_MAX_BINS = 4096


@partial(jax.jit, static_argnames=("num_bins", "mesh"))
def _mesh_bincount(codes: jax.Array, n_valid: jax.Array, *,
                   num_bins: int, mesh) -> jax.Array:
    """Exact bincount of row-sharded int codes; psum over the data axis."""

    def shard_fn(codes_shard, n_valid):
        shard_len = codes_shard.shape[0]
        start = jax.lax.axis_index(DATA_AXIS) * shard_len
        valid = (start + jnp.arange(shard_len)) < n_valid
        # Padding rows land in an overflow bin that is dropped after reduce.
        seg = jnp.where(valid, codes_shard, num_bins)
        width = num_bins + 1
        if width > _ONEHOT_MAX_BINS:
            local = jnp.zeros(width, jnp.int32).at[seg].add(1)
            return jax.lax.psum(local, DATA_AXIS)
        # Blocked one-hot reduction instead of scatter-add: TPU
        # scatter-adds serialize per element (measured ~11 s at 50M rows),
        # while a (blk, bins) compare + column-sum is a dense VPU pass.
        # The budget divides by the LANE-PADDED width (trailing dims < 128
        # still occupy 128 lanes), else narrow histograms get multi-GB
        # transients.
        blk = max(512, min(shard_len,
                           _BINCOUNT_BLOCK_ELEMS // max(width, 128)))
        nbk = -(-shard_len // blk)
        pad = nbk * blk - shard_len
        if pad:
            # Padding rows land in the overflow bin, dropped with it below.
            seg = jnp.pad(seg, (0, pad), constant_values=num_bins)

        def body(acc, i):
            s = jax.lax.dynamic_slice_in_dim(seg, i * blk, blk)
            oh = s[:, None] == jnp.arange(width, dtype=s.dtype)[None, :]
            return acc + oh.sum(axis=0, dtype=jnp.int32), None

        local, _ = jax.lax.scan(body, jnp.zeros(width, jnp.int32),
                                jnp.arange(nbk))
        return jax.lax.psum(local, DATA_AXIS)

    counts = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS), P()),
        out_specs=P(),
        check_vma=False,
    )(codes, n_valid)
    return counts[:num_bins]


def field_counts(runtime: MeshRuntime, col: np.ndarray) -> Dict:
    """Value→count dict for one column, device path when it pays off.

    The device/host decision depends only on the column's dtype and value
    range, so identical chunk data yields identical decisions on every
    process of a pod — the property the SPMD histogram dispatch relies on.
    """
    if len(col) == 0:
        return {}
    if col.dtype.kind in "iu":
        lo, hi = int(col.min()), int(col.max())
        num_bins = hi - lo + 1
        if 0 < num_bins <= MAX_DEVICE_BINS:
            n_dev = int(np.prod(list(runtime.mesh.shape.values())))
            if n_dev == 1:
                # One device: there is nothing to reduce across, so the
                # host counts the chunk it already holds instead of
                # paying a host↔device round trip per chunk (not
                # measured on today's chip — ROADMAP S8). Same exact
                # counts; the decision depends only on the global mesh,
                # so it is identical on every pod process.
                counts = np.bincount((col - lo).astype(np.int64),
                                     minlength=num_bins)
                return {int(lo + i): int(c)
                        for i, c in enumerate(counts) if c}
            codes = (col - lo).astype(np.int32)
            sharded, n = runtime.shard_rows(codes)
            counts = np.asarray(_mesh_bincount(
                sharded, runtime.replicate(np.int32(n)),
                num_bins=num_bins, mesh=runtime.mesh))
            return {int(lo + i): int(c) for i, c in enumerate(counts) if c}
    # host fallback: floats, strings, huge integer ranges
    return column_value_counts(col)


def merge_counts(total: Dict, part: Dict) -> None:
    """Accumulate one chunk's value→count map into the running total."""
    for k, v in part.items():
        total[k] = total.get(k, 0) + v


def histogram_totals(runtime: MeshRuntime, parent_ds, fields: List[str],
                     max_chunks: Optional[int] = None) -> Dict[str, Dict]:
    """Per-field value→count maps, streamed one chunk at a time.

    This is the device-op sequence shared verbatim by process 0 and SPMD
    workers (parallel/spmd.py ``prep_histogram_job``): per chunk, per
    field, one ``field_counts`` call whose device/host decision depends
    only on the chunk's data. With ``max_chunks`` pinned to a journaled
    snapshot, every process iterates identical chunk boundaries in
    identical order, so the collective programs line up.

    ``iter_chunks`` streams through the prefetching read pipeline: while
    this loop counts chunk i (host bincount, or device scatter+psum with
    its blocking result gather), workers read + CRC-verify + decode
    chunks i+1..i+K — so on the device path the host→device transfer and
    collective of block i overlap the fetch of block i+1. SPMD-safe:
    prefetch workers do pure host I/O (no device ops), and chunk order is
    deterministic regardless of depth, so every pod process still runs
    the identical collective sequence. Repeated histograms of the same
    parent hit the shared chunk cache instead of disk.
    """
    totals: Dict[str, Dict] = {f: {} for f in fields}
    for cols in parent_ds.iter_chunks(list(fields), max_chunks=max_chunks):
        for f in fields:
            merge_counts(totals[f], field_counts(runtime, cols[f]))
    return totals


def create_histogram(store: DatasetStore, runtime: MeshRuntime,
                     parent: str, name: str, fields: List[str],
                     existing: bool = False) -> None:
    """Build the histogram dataset (sync core; run under JobManager).

    Streams the parent one chunk at a time (``iter_chunks``) and merges
    per-chunk counts, so datasets larger than host RAM histogram without
    ever being fully materialized — matching the reference's disk-backed
    Mongo aggregation (histogram.py:49-74) at out-of-core scale.

    Multi-process pods dispatch the job to every worker first (the full
    scalable-tier behavior of the reference, where histogram-scale work
    also ran against shared storage): the spec pins the parent's journaled
    chunk count so all processes stream the same snapshot.

    ``existing=True`` means the API layer already created the output dataset
    (metadata-first protocol); otherwise it is created here.
    """
    from learningorchestra_tpu.parallel import spmd

    parent_ds = store.get(parent)
    missing = [f for f in fields if f not in parent_ds.metadata.fields]
    if missing:
        raise ValueError(f"fields not in dataset: {missing}")
    ds = store.get(name) if existing else store.create(name, parent=parent)
    pin: Dict[str, int] = {}

    def make_spec():
        # Evaluated after dispatch_job's save: the journaled chunk count
        # is the snapshot every process streams.
        pin["n_chunks"] = len(parent_ds.journal_files())
        return {"op": "histogram", "parent": parent,
                "fields": list(fields), "n_chunks": pin["n_chunks"]}

    with spmd.dispatch_job(store, (parent,), make_spec, outputs=(name,)):
        totals = histogram_totals(runtime, parent_ds, fields,
                                  max_chunks=pin.get("n_chunks"))
    ds.append_rows([{"field": f, "counts": totals[f]} for f in fields])
    store.finish(name)
