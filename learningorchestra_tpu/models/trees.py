"""Tree-ensemble trainers: "dt", "rf", "gb" — histogram-split trees in XLA.

The reference fits ``pyspark.ml`` DecisionTreeClassifier,
RandomForestClassifier and GBTClassifier as distributed Spark jobs
(reference model_builder.py:153-155). Spark's tree algorithm is itself
histogram-based (maxBins feature quantization + per-node sufficient
statistics aggregated across executors) — which is exactly the shape that
maps onto a TPU, so this module re-designs it as a fixed-shape XLA program
(SURVEY.md §7 "hard part (a)"):

- Features are quantized once to ``n_bins`` quantile bins (Spark's maxBins).
- A tree is grown *level-wise*: every node at a level computes a
  (node, feature, bin, stat) histogram with one scatter-add over the rows,
  split quality for every candidate comes from a cumulative sum over bins,
  and the best split is an argmax — no data-dependent control flow, so the
  whole build jit-compiles with static shapes.
- Rows stay sharded across the mesh data axis for the entire build inside a
  single ``shard_map``: each shard scatter-adds its local rows, one
  ``lax.psum`` per level reduces histograms over ICI (the analogue of
  Spark's per-level executor aggregation), and node decisions are computed
  identically on every shard.
- One generic builder serves all three families: classification trees carry
  per-class weight stats (gini criterion); boosted trees carry
  gradient/hessian stats (Newton gain, XGBoost-hist style).

Defaults match Spark 2.4's: maxDepth=5, maxBins=32, numTrees=20 (rf),
maxIter=20 + stepSize=0.1 (gb), and "gb" is binary-only exactly as Spark's
GBTClassifier is.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from learningorchestra_tpu.models.base import TrainedModel, as_design
from learningorchestra_tpu.ops import pallas_kernels
from learningorchestra_tpu.parallel import spmd
from learningorchestra_tpu.parallel.mesh import DATA_AXIS, MeshRuntime

NEG = -1e30


def _use_tree_kernel(runtime: Optional[MeshRuntime] = None) -> bool:
    """Whether tree fits route their hot loops through the fused Pallas
    kernels (ops/pallas_kernels.py): the two config flags and nothing
    else. ``LO_TPU_TREE_KERNEL=0`` selects the pure-XLA contraction path
    — kept as the bit-parity oracle (docs/performance.md); the master
    ``LO_TPU_USE_PALLAS`` switch disables every Pallas kernel at once.
    With the flags on, a kernel the backend's compiler refuses raises
    out of the fit and fails the job — there is no fallback to the
    oracle. Off-TPU the kernels run in interpreter mode, so the default
    exercises the same code path on the CPU mesh."""
    if runtime is not None:
        cfg = runtime.cfg
    else:
        from learningorchestra_tpu.config import settings as cfg
    return bool(cfg.use_pallas and cfg.tree_kernel)


def _hist_dtype():
    """Histogram matmul operand dtype: bf16 on TPU (halves the dominant
    one-hot HBM traffic; MXU accumulates in f32 via
    preferred_element_type), f32 elsewhere (the CPU dot thunk lacks
    BF16×BF16→F32)."""
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32


def _sel_col(Bblk: jax.Array, f_idx: jax.Array) -> jax.Array:
    """Per-row feature select ``B[i, f_i]`` as a dense compare+sum.

    ``take_along_axis`` lowers to a per-row gather, which serializes on
    TPU — profiled at 2.7 ms per 262k-row block, it dominated tree fits
    (~17 s of a 27 s gb fit across routing+descent). The (blk, d) one-hot
    masked sum is a single fused VPU pass."""
    d = Bblk.shape[1]
    oh = f_idx[:, None] == jnp.arange(d, dtype=f_idx.dtype)[None, :]
    return jnp.where(oh, Bblk.astype(jnp.int32), 0).sum(axis=1)


def _sel_table(table: jax.Array, idx: jax.Array) -> jax.Array:
    """Per-row small-table lookup ``table[idx]`` as a dense compare+sum
    (same gather-avoidance rationale as ``_sel_col``; tables here are the
    ≤2^(depth+1) per-node arrays)."""
    M = table.shape[0]
    oh = idx[:, None] == jnp.arange(M, dtype=idx.dtype)[None, :]
    t = table.astype(jnp.int32) if table.dtype == jnp.bool_ else table
    out = jnp.where(oh, t[None, :], 0).sum(axis=1)
    return out.astype(jnp.bool_) if table.dtype == jnp.bool_ else out


def _sel_table_blocked(table: jax.Array, idx: jax.Array) -> jax.Array:
    """Blocked ``table[idx]`` over a full row-length index array (e.g. the
    per-round leaf-value broadcast in boosting): the (n, M) one-hot
    transient stays one block wide instead of gigabytes."""
    n = idx.shape[0]
    blk, nbk, n_pad = _block_shape(n)
    if n_pad != n:
        idx = jnp.pad(idx, (0, n_pad - n))

    def body(acc, i):
        ib = jax.lax.dynamic_slice_in_dim(idx, i * blk, blk)
        return jax.lax.dynamic_update_slice_in_dim(
            acc, _sel_table(table, ib), i * blk, axis=0), None

    out, _ = jax.lax.scan(
        body, jnp.zeros((n_pad,), table.dtype), jnp.arange(nbk))
    return out[:n]


def _sel_rows_blocked(table: jax.Array, idx: jax.Array) -> jax.Array:
    """Blocked ``table[idx]`` for a 2-D (M, S) table: per block, a
    (blk, M) one-hot @ (M, S) dot — exact in f32, transients stay one
    block wide (an unblocked one-hot for a 2M-row predict chunk × 20
    vmapped trees would be gigabytes of lane-padded HBM)."""
    n = idx.shape[0]
    M, S = table.shape
    blk, nbk, n_pad = _block_shape(n)
    if n_pad != n:
        idx = jnp.pad(idx, (0, n_pad - n))

    def body(acc, i):
        ib = jax.lax.dynamic_slice_in_dim(idx, i * blk, blk)
        oh = (ib[:, None] == jnp.arange(M, dtype=ib.dtype)[None, :]
              ).astype(table.dtype)
        return jax.lax.dynamic_update_slice_in_dim(
            acc, oh @ table, i * blk, axis=0), None

    out, _ = jax.lax.scan(
        body, jnp.zeros((n_pad, S), table.dtype), jnp.arange(nbk))
    return out[:n]


# ---------------------------------------------------------------------------
# Quantization (Spark's maxBins analogue)
# ---------------------------------------------------------------------------

def quantile_edges(X: np.ndarray, n_bins: int,
                   sample: int = 200_000) -> np.ndarray:
    """Per-feature bin edges from quantiles of a row sample. (d, n_bins-1)."""
    n = len(X)
    if n > sample:
        idx = np.random.default_rng(0).choice(n, sample, replace=False)
        Xs = X[idx]
    else:
        Xs = X
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    edges = np.quantile(Xs, qs, axis=0).T.astype(np.float32)  # (d, n_bins-1)
    return np.ascontiguousarray(edges)


def validate_n_bins(n_bins: int) -> None:
    """Single guard for the uint8 bin-code representation ``bin_features``
    produces — every tree entry point (edge prep, dt/rf, gb) funnels
    through it."""
    if n_bins > 256:
        raise ValueError("n_bins is capped at 256 (uint8 bin codes)")


@jax.jit
def bin_features(X: jax.Array, edges: jax.Array) -> jax.Array:
    """float features → uint8 bin codes: code = #edges strictly below x.

    One fused compare+sum over the (n, d, n_bins-1) broadcast instead of
    per-feature ``searchsorted`` (which lowers to gather-heavy binary
    search on TPU); the compare form is a single VPU pass, XLA fuses the
    broadcast away, and — crucially — it has no cross-row op, so a
    row-sharded ``X`` yields a row-sharded result with no resharding.

    uint8 keeps the resident bin matrix 4× smaller than int32 (and TPU
    lane padding makes (n, d<128) arrays pay for 128 lanes regardless, so
    narrow dtypes are the only lever); n_bins is capped at 256.
    """
    return (X[:, :, None] > edges[None, :, :]).sum(
        axis=-1, dtype=jnp.int32).astype(jnp.uint8)  # (n, d)


# ---------------------------------------------------------------------------
# Generic level-wise histogram tree builder (runs inside shard_map)
# ---------------------------------------------------------------------------

#: Rows per histogram/routing block. Level-wise stats accumulate in a
#: lax.scan over row blocks so nothing (n, d, S)-shaped ever materializes —
#: at HIGGS scale (11M × 28) that tensor would be gigabytes *before* TPU
#: lane padding inflates trailing small dims to 128 lanes (a (n·d, 2) f32
#: scatter operand allocates 64× its logical size).
_ROW_BLOCK = 1 << 18
#: f32 elements allowed for the per-block (blk, d·n_bins) one-hot operand of
#: the histogram contraction (~128 MB) — bounds transient HBM per block.
_ONEHOT_BUDGET = 32 * 1024 * 1024


def _block_shape(n, onehot_cols=0):
    blk = _ROW_BLOCK
    if onehot_cols:
        cap = max(512, _ONEHOT_BUDGET // onehot_cols)
        blk = min(blk, 1 << (cap.bit_length() - 1))
    blk = min(blk, n)
    nbk = -(-n // blk)
    return blk, nbk, nbk * blk


def _hist_level_xla(B, stats_T, rel, active, *, n_nodes, n_bins, blk):
    """One level's local (node, feature, bin, stat) histogram via the
    blocked MXU-contraction emulation — the ``LO_TPU_TREE_KERNEL=0``
    oracle path.

    The histogram is ONE MXU contraction per block — not scatters (TPU
    scatter-adds serialize) and not a per-feature matmul loop (n_bins=32
    lane-pads to 128, NL·S is sublane-starved, and the d-way unroll
    bloats compile time). The (feature, bin) one-hot packs into a single
    (blk, d·n_bins) operand so every feature rides the same matmul: A
    packs node-masked per-row stats (NL·S, blk); one
    (NL·S, blk) @ (blk, d·n_bins) product per block. Blocks are carved
    with dynamic_slice inside the scan body (index scan) rather than
    scanning over a stacked (nbk, blk, ...) operand: XLA:TPU compiles
    scans over multi-hundred-MB stacked inputs ~30x slower (measured
    23.5s vs 0.8s for a trivial body at 11 x 1M rows). The one-hot
    operands materialize in HBM per block — the traffic the Pallas
    kernel path exists to eliminate.
    """
    n_pad, d = B.shape
    S = stats_T.shape[0]
    nbk = n_pad // blk
    bins_u8 = jnp.arange(n_bins, dtype=jnp.uint8)[None, None, :]

    def hist_block(hist, i):
        Bblk = jax.lax.dynamic_slice_in_dim(B, i * blk, blk)
        relblk = jax.lax.dynamic_slice_in_dim(rel, i * blk, blk)
        ablk = jax.lax.dynamic_slice_in_dim(active, i * blk, blk)
        sblk = jax.lax.dynamic_slice_in_dim(
            stats_T, i * blk, blk, axis=1)               # (S, blk)
        node_oh = ((relblk[:, None] == jnp.arange(n_nodes)[None, :])
                   & ablk[:, None])                      # (blk, NL)
        # bf16 operands (on TPU) halve the dominant HBM traffic (the
        # (blk, d·n_bins) one-hot materialization); products of {0,1}
        # one-hots with bf16-rounded stats are exact, and partial
        # sums accumulate in f32 via preferred_element_type.
        hdt = _hist_dtype()
        A = (node_oh[:, :, None].astype(hdt)
             * sblk.T.astype(hdt)[:, None, :])           # (blk, NL, S)
        At = A.reshape(blk, n_nodes * S).T               # (NL·S, blk)
        oh = (Bblk[:, :, None] == bins_u8).astype(hdt)
        return hist + jax.lax.dot(
            At, oh.reshape(blk, d * n_bins),
            preferred_element_type=jnp.float32), None

    hist, _ = jax.lax.scan(
        hist_block, jnp.zeros((n_nodes * S, d * n_bins), jnp.float32),
        jnp.arange(nbk))
    # (NL·S, d·nb) → (NL, d, bins, S)
    return hist.reshape(n_nodes, S, d, n_bins).transpose(0, 2, 3, 1)


def _route_level_xla(B, rel, active, assign, best_f, best_t, split, *,
                     blk):
    """One level's routing pass (oracle path): rows of split nodes go to
    their children, leaf rows keep their node. Blocked for the same
    lane-padding reason as the histogram."""
    nbk = B.shape[0] // blk

    def route_block(asg, i):
        Bblk = jax.lax.dynamic_slice_in_dim(B, i * blk, blk)
        relblk = jax.lax.dynamic_slice_in_dim(rel, i * blk, blk)
        ablk = jax.lax.dynamic_slice_in_dim(active, i * blk, blk)
        asgblk = jax.lax.dynamic_slice_in_dim(asg, i * blk, blk)
        rf = _sel_table(best_f, relblk)
        rt = _sel_table(best_t, relblk)
        rs = _sel_table(split, relblk) & ablk
        gr = _sel_col(Bblk, rf) > rt
        new = jnp.where(rs, 2 * asgblk + 1 + gr.astype(jnp.int32),
                        asgblk)
        return jax.lax.dynamic_update_slice_in_dim(
            asg, new, i * blk, axis=0), None

    asg, _ = jax.lax.scan(route_block, assign, jnp.arange(nbk))
    return asg


def _leaf_stats_xla(assign, stats_T, *, n_nodes, blk):
    """Local per-leaf sufficient statistics (oracle path) — the same
    matmul-histogram trick over the final assignment. (S, M)."""
    S = stats_T.shape[0]
    nbk = assign.shape[0] // blk

    def leaf_block(acc, i):
        asgblk = jax.lax.dynamic_slice_in_dim(assign, i * blk, blk)
        sblk = jax.lax.dynamic_slice_in_dim(stats_T, i * blk, blk, axis=1)
        hdt = _hist_dtype()
        oh = (asgblk[:, None] == jnp.arange(n_nodes)[None, :]).astype(hdt)
        return acc + jax.lax.dot(sblk.astype(hdt), oh,
                                 preferred_element_type=jnp.float32), None

    leaf, _ = jax.lax.scan(
        leaf_block, jnp.zeros((S, n_nodes), jnp.float32), jnp.arange(nbk))
    return leaf


def _build_tree(B, stats_T, feat_gain_mask, *, max_depth, n_bins,
                gain_fn, weight_fn, min_child_weight, min_gain,
                use_kernel=False, bin_gain_mask=None, level_allow=None):
    """Grow one tree. All shapes static; call inside shard_map.

    B: (n, d) uint8 bin codes (local shard rows).
    stats_T: (S, n) float32 per-row sufficient statistics, TRANSPOSED so
        the long row axis sits in TPU lanes (zero columns for masked
        rows — padding/bootstrap-excluded rows simply carry zero weight).
    feat_gain_mask: (d,) float32 — 0 allows a feature, NEG forbids it
        (random-forest per-tree feature subsampling).
    gain_fn(left, total) -> gain over trailing stat dim; higher is better.
    weight_fn(stat_sums) -> scalar node weight for min_child_weight.
    use_kernel: route the histogram/routing/leaf passes through the
        fused Pallas kernels (ops/pallas_kernels.py) instead of the
        blocked XLA contraction oracle. Must be static (it selects the
        compiled program); split decisions and per-level psums are
        identical either way.
    bin_gain_mask: optional (n_bins,) float32 traced mask — 0 allows a
        split threshold, NEG forbids it. The hyperparameter-population
        path (models/tune.py) builds at the population's STATIC maximum
        n_bins and forbids thresholds ≥ a member's own n_bins - 1, which
        reproduces that member's standalone split set exactly (its high
        bins hold zero mass, so allowed gains are bit-identical).
    level_allow: optional (max_depth,) traced mask — False forbids
        splitting any node at that level. Same population trick for
        per-member max_depth under a static maximum: forbidden levels
        leave nodes as leaves, so node ids [0, 2^(member_depth+1)-1)
        match a standalone build at the member's own depth.

    Returns (feat (M,), thr (M,), is_internal (M,), leaf_stats (M, S)) with
    M = 2^(max_depth+1) - 1 nodes; children of i at 2i+1 / 2i+2.
    """
    n, d = B.shape
    S = stats_T.shape[0]
    M = 2 ** (max_depth + 1) - 1
    if use_kernel:
        # Kernel row tiles are VMEM-sized; everything else about the
        # level loop (and the per-level psum) is shared with the oracle.
        blk = pallas_kernels.tree_tile(d, n_bins)
        nbk = -(-n // blk)
        n_pad = nbk * blk
    else:
        blk, nbk, n_pad = _block_shape(n, d * n_bins)
    if n_pad != n:
        B = jnp.pad(B, ((0, n_pad - n), (0, 0)))
        stats_T = jnp.pad(stats_T, ((0, 0), (0, n_pad - n)))
    hdt = _hist_dtype()
    # The kernels stream every per-row operand with rows in lanes — the
    # bin matrix too, transposed once per tree outside the level loop.
    BT = B.T if use_kernel else None

    #: Fixed per-level node width: the deepest processed level has
    #: 2^(max_depth-1) nodes, and every level runs at that width so the
    #: whole level loop is ONE lax.scan body (a per-level Python unroll
    #: re-traces 5 distinct level shapes and blew gb's compile time to
    #: minutes). Slots past a level's real node count carry all-zero
    #: stats — their gain is NEG so they never split — and their
    #: node-id writes spill into exactly the id range later levels
    #: rewrite (binary-heap layout: level l writes [2^l-1, 2^l-1+NL),
    #: and every id ≥ 2^(l+1)-1 is level-(l+1)+ territory).
    NL = 2 ** max(max_depth - 1, 0)

    def level_step(carry, xs):
        l, lvl_ok = xs
        feat, thr, is_internal, assign = carry
        offset = jnp.left_shift(1, l) - 1            # 2^l - 1
        nl = offset + 1                              # 2^l real nodes
        rel = assign - offset
        active = (rel >= 0) & (rel < nl)
        rel = jnp.where(active, rel, 0)

        if use_kernel:
            hist = pallas_kernels.tree_histogram(
                BT, stats_T, rel, active, n_nodes=NL, n_bins=n_bins,
                tile=blk, operand_dtype=hdt)
        else:
            hist = _hist_level_xla(B, stats_T, rel, active, n_nodes=NL,
                                   n_bins=n_bins, blk=blk)
        hist = jax.lax.psum(hist, DATA_AXIS)                 # ICI reduce

        left = jnp.cumsum(hist, axis=2)                          # ≤ bin t
        total = left[:, :, -1:, :]                               # (NL,d,1,S)
        gain = gain_fn(left, total)                              # (NL,d,nb)
        # A split at the last bin sends everything left — forbid it.
        gain = gain.at[:, :, -1].set(NEG)
        lw = weight_fn(left)
        rw = weight_fn(total) - lw
        ok = (lw >= min_child_weight) & (rw >= min_child_weight)
        gain = jnp.where(ok, gain, NEG) + feat_gain_mask[None, :, None]
        if bin_gain_mask is not None:
            gain = gain + bin_gain_mask[None, None, :]

        flat = gain.reshape(NL, d * n_bins)
        best = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
        best_f = (best // n_bins).astype(jnp.int32)
        best_t = (best % n_bins).astype(jnp.int32)
        split = (best_gain > min_gain) & lvl_ok

        node_ids = offset + jnp.arange(NL)
        feat = feat.at[node_ids].set(jnp.where(split, best_f, 0))
        thr = thr.at[node_ids].set(jnp.where(split, best_t, 0))
        is_internal = is_internal.at[node_ids].set(split)

        if use_kernel:
            asg = pallas_kernels.tree_route_level(
                BT, rel, active, assign, best_f, best_t, split, tile=blk)
        else:
            asg = _route_level_xla(B, rel, active, assign, best_f,
                                   best_t, split, blk=blk)
        return (feat, thr, is_internal, asg), None

    if level_allow is None:
        level_allow = jnp.ones((max_depth,), bool)
    (feat, thr, is_internal, assign), _ = jax.lax.scan(
        level_step,
        (jnp.zeros((M,), jnp.int32), jnp.zeros((M,), jnp.int32),
         jnp.zeros((M,), bool), jnp.zeros((n_pad,), jnp.int32)),
        (jnp.arange(max_depth), level_allow))

    # Leaf sufficient statistics over ALL nodes (every row sits at a leaf;
    # padded columns carry zero stats).
    if use_kernel:
        leaf = pallas_kernels.tree_leaf_stats(
            assign, stats_T, n_nodes=M, tile=blk, operand_dtype=hdt)
    else:
        leaf = _leaf_stats_xla(assign, stats_T, n_nodes=M, blk=blk)
    leaf = jax.lax.psum(leaf.T, DATA_AXIS)                   # (M, S)
    return feat, thr, is_internal, leaf


def _descend(B, feat, thr, is_internal, max_depth, use_kernel=False):
    """Blocked routing of binned rows to their leaf node id.

    ``use_kernel`` routes through the fused Pallas descent kernel; the
    result is bit-identical either way (integer arithmetic throughout),
    so predict paths may flip it per batch shape — batches below the
    kernel row tile (e.g. the serving tier's row-wise AOT programs) stay
    on the oracle, where tile padding would dominate."""
    n, d = B.shape
    if use_kernel and n >= pallas_kernels.TREE_ROUTE_TILE:
        return pallas_kernels.tree_descend(B.T, feat, thr, is_internal,
                                           max_depth=max_depth)
    blk, nbk, n_pad = _block_shape(n)
    if n_pad != n:
        B = jnp.pad(B, ((0, n_pad - n), (0, 0)))

    def desc_block(acc, i):
        Bblk = jax.lax.dynamic_slice_in_dim(B, i * blk, blk)
        a = jnp.zeros((blk,), jnp.int32)
        for _ in range(max_depth):
            f = _sel_table(feat, a)
            t = _sel_table(thr, a)
            internal = _sel_table(is_internal, a)
            go_right = _sel_col(Bblk, f) > t
            a = jnp.where(internal, 2 * a + 1 + go_right.astype(jnp.int32),
                          a)
        return jax.lax.dynamic_update_slice_in_dim(acc, a, i * blk,
                                                   axis=0), None

    a, _ = jax.lax.scan(desc_block, jnp.zeros((n_pad,), jnp.int32),
                        jnp.arange(nbk))
    return a[:n]


def _predict_program(body):
    """The family's ``(params, X, *, max_depth) -> probs`` predict
    function from its row-local ``body``: one jitted program, run under
    ``shard_map`` over the design's own mesh when the concrete ``X``
    handed in is row-sharded across several devices. XLA partitions a
    plain jitted predict by itself, but it cannot partition a Mosaic
    kernel ("Mosaic kernels cannot be automatically partitioned" — the
    first four-chip run, PR 22; interpret mode on the CPU mesh never
    shows it). Every op in ``body`` is row-local and the tree params are
    replicated, so per-shard evaluation is the same arithmetic row for
    row. A traced ``X`` (the AOT row-wise serving programs) or a
    one-device design takes the plain program."""

    @partial(jax.jit, static_argnames=("max_depth", "mesh"))
    def program(params, X, *, max_depth, mesh):
        fn = partial(body, max_depth=max_depth)
        if mesh is None:
            return fn(params, X)
        return jax.shard_map(
            fn, mesh=mesh, in_specs=(P(), P(DATA_AXIS)),
            out_specs=P(DATA_AXIS), check_vma=False)(params, X)

    def predict(params, X, *, max_depth):
        sharding = None if isinstance(X, jax.core.Tracer) else getattr(
            X, "sharding", None)
        spans = (isinstance(sharding, NamedSharding)
                 and len(sharding.device_set) > 1)
        return program(params, X, max_depth=max_depth,
                       mesh=sharding.mesh if spans else None)

    predict.program = program    # tests compile it for a described mesh
    return predict


# ---------------------------------------------------------------------------
# Criteria
# ---------------------------------------------------------------------------

def _gini_gain(left, total):
    """Weighted gini impurity decrease; stats are per-class weights."""
    right = total - left
    lw = left.sum(-1)
    rw = right.sum(-1)
    tw = total.sum(-1)

    def gini_w(counts, w):
        # w * gini = w - sum(c^2)/w
        return w - (counts ** 2).sum(-1) / jnp.maximum(w, 1e-12)

    parent = gini_w(total, tw)
    child = gini_w(left, lw) + gini_w(right, rw)
    return (parent - child) / jnp.maximum(tw, 1e-12)


def _make_newton_gain(lam: float):
    """XGBoost-style gain on [grad, hess] stats."""

    def gain(left, total):
        right = total - left
        gl, hl = left[..., 0], left[..., 1]
        gr, hr = right[..., 0], right[..., 1]
        g, h = total[..., 0], total[..., 1]
        return (gl ** 2 / (hl + lam) + gr ** 2 / (hr + lam)
                - g ** 2 / (h + lam))

    return gain


# ---------------------------------------------------------------------------
# dt / rf  (classification trees, gini)
# ---------------------------------------------------------------------------

def _forest_batch_shape(n_trees: int):
    """(trees per vmapped batch, batch count). Batch = the largest
    divisor of n_trees ≤ 8, falling back to padded batches of 8 when
    n_trees has no usable divisor (the discarded pad trees cost < one
    batch). Shared by the oracle and the checkpoint-segmented path so
    per-batch shapes — and therefore values — cannot diverge."""
    tb = max((t for t in range(1, min(8, n_trees) + 1)
              if n_trees % t == 0), default=1)
    if tb < 4 and n_trees > 8:
        tb = 8
    nb = -(-n_trees // tb)
    return tb, nb


def _one_tree_fn(B, y, valid, *, num_classes, n_trees, max_depth, n_bins,
                 mtry, min_child_weight, use_kernel):
    """The per-tree builder (bootstrap + feature subsample + level-wise
    build), shared verbatim by the oracle's lax.map and the
    checkpoint-segmented per-batch program. Runs inside shard_map."""
    d = B.shape[1]
    # Per-class weights TRANSPOSED to (C, n): the long row axis must
    # sit in TPU lanes (an (n, C<128) layout pays for 128 lanes).
    classes = jnp.arange(num_classes, dtype=y.dtype)[:, None]
    base_stats = ((y[None, :] == classes).astype(jnp.float32)
                  * valid[None, :])

    def one_tree(key):
        kb, kf = jax.random.split(key)
        if n_trees == 1:
            stats = base_stats
            fmask = jnp.zeros((d,), jnp.float32)
        else:
            # Poisson(1) bootstrap weights; identical draw on every
            # shard would correlate rows, so fold in the shard index.
            kb = jax.random.fold_in(kb, jax.lax.axis_index(DATA_AXIS))
            w = jax.random.poisson(kb, 1.0, (B.shape[0],)).astype(
                jnp.float32)
            stats = base_stats * w[None, :]
            # mtry features allowed per tree (same mask on all shards).
            perm = jax.random.permutation(kf, d)
            allowed = jnp.zeros((d,), bool).at[perm[:mtry]].set(True)
            fmask = jnp.where(allowed, 0.0, NEG)
        feat, thr, internal, leaf = _build_tree(
            B, stats, fmask, max_depth=max_depth, n_bins=n_bins,
            gain_fn=_gini_gain, weight_fn=lambda s: s.sum(-1),
            min_child_weight=min_child_weight, min_gain=1e-9,
            use_kernel=use_kernel)
        return feat, thr, internal, leaf

    return one_tree


@partial(jax.jit,
         static_argnames=("num_classes", "max_depth", "n_bins", "n_trees",
                          "mesh", "mtry", "use_kernel"))
def _fit_forest(B, y, valid, key, *, num_classes, max_depth, n_bins,
                n_trees, mesh, mtry, min_child_weight=1.0,
                use_kernel=False):
    """dt (n_trees=1, no bagging) and rf (bootstrap + feature subsampling)."""

    def shard_fn(B, y, valid, key):
        one_tree = _one_tree_fn(
            B, y, valid, num_classes=num_classes, n_trees=n_trees,
            max_depth=max_depth, n_bins=n_bins, mtry=mtry,
            min_child_weight=min_child_weight, use_kernel=use_kernel)
        # Trees build in vmapped batches: a batch's (NL·S, blk) histogram
        # operands stack into one (tb·NL·S, blk) @ (blk, d·n_bins) MXU
        # contraction per row block. On the oracle path that is what
        # vmap makes of the contraction; on the kernel path the
        # histogram call's own batching rule (ops/pallas_kernels.py
        # `_hist_call`) does it, because the batch shares its bin matrix:
        # one one-hot per row tile for the tb trees, 4.3× over a pass
        # per tree at 11M rows (PERF.md, PR 28). The outer sequential
        # map bounds live per-tree row state (stats/weights/assign are
        # O(tb·n), not O(n_trees·n), so n_trees=100 still fits HBM).
        tb, nb = _forest_batch_shape(n_trees)
        keys = jax.random.split(key, nb * tb)
        outs = jax.lax.map(jax.vmap(one_tree),
                           keys.reshape(nb, tb, *keys.shape[1:]))
        return jax.tree.map(
            lambda a: a.reshape(nb * tb, *a.shape[2:])[:n_trees], outs)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=P(), check_vma=False,
    )(B, y, valid, key)


@partial(jax.jit,
         static_argnames=("num_classes", "max_depth", "n_bins", "n_trees",
                          "mesh", "mtry", "use_kernel"))
def _fit_forest_batch(B, y, valid, keys_b, *, num_classes, max_depth,
                      n_bins, n_trees, mesh, mtry, min_child_weight=1.0,
                      use_kernel=False):
    """ONE vmapped tree batch of the forest — the checkpoint-segmented
    complement to ``_fit_forest``'s internal lax.map: the same vmapped
    ``one_tree`` body over an explicit key slice, so batch b's trees are
    bit-identical to the oracle's iteration b (``n_trees`` stays the
    FULL forest size — it selects the bagging branch, not the batch
    width). Only engaged when ``LO_TPU_FIT_CKPT_ROUNDS > 0``."""

    def shard_fn(B, y, valid, keys_b):
        one_tree = _one_tree_fn(
            B, y, valid, num_classes=num_classes, n_trees=n_trees,
            max_depth=max_depth, n_bins=n_bins, mtry=mtry,
            min_child_weight=min_child_weight, use_kernel=use_kernel)
        return jax.vmap(one_tree)(keys_b)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=P(), check_vma=False,
    )(B, y, valid, keys_b)


# ---------------------------------------------------------------------------
# Config-population programs (models/tune.py)
#
# The hyperparameter-search tier vmaps a POPULATION of same-family
# configs over the member axis — the tree-batch vmap one level up. All
# static shapes are the population's maxima (max_depth, n_bins,
# n_trees); a member's smaller depth/bin-count is enforced by the
# traced ``level_allow``/``bin_gain_mask`` arguments of ``_build_tree``,
# which reproduce the member's standalone split set exactly. Per-member
# row weights carry validity × k-fold membership (and drop to zero when
# successive halving kills the member), so folds are index masks over
# the ONE resident design — never data copies. The Pallas kernel path
# stays off here (the oracle contraction is the documented bit-parity
# reference); were it switched on, the histogram call's batching rule
# would stack a member's trees and put the members, each with a bin
# matrix of its own, on a grid axis.
# ---------------------------------------------------------------------------

@jax.jit
def _bin_features_pop(X, edges_pop):
    """Per-member bin codes from per-member (inf-padded) edge stacks:
    (n, d) × (Pm, d, n_bins_max - 1) → (Pm, n, d) uint8. Padding edges
    with +inf yields codes bit-identical to binning with the member's
    own (shorter) edge list."""
    return jax.vmap(lambda e: bin_features(X, e))(edges_pop)


@partial(jax.jit,
         static_argnames=("num_classes", "max_depth", "n_bins", "n_trees",
                          "mesh"))
def _fit_forest_pop_batch(B_pop, y, w_pop, bin_mask, level_allow,
                          mtry_vec, keys_b, *, num_classes, max_depth,
                          n_bins, n_trees, mesh):
    """One vmapped tree batch for a POPULATION of dt/rf configs.

    Mirrors ``_fit_forest_batch`` with a member axis on top: per member
    its own bin matrix, row weights (validity × fold × alive), bin/level
    masks and mtry. ``n_trees`` is the population-shared forest size (it
    selects the bagging branch and the key count, exactly as in the
    serial oracle, so per-member trees are bit-identical to that
    member's standalone fit)."""

    def shard_fn(B_pop, y, w_pop, bin_mask, level_allow, mtry_vec,
                 keys_b):
        d = B_pop.shape[2]
        classes = jnp.arange(num_classes, dtype=y.dtype)[:, None]

        def one_member(B, w, bmask, lallow, mtry_m, keys):
            base_stats = ((y[None, :] == classes).astype(jnp.float32)
                          * w[None, :])

            def one_tree(key):
                kb, kf = jax.random.split(key)
                if n_trees == 1:
                    stats = base_stats
                    fmask = jnp.zeros((d,), jnp.float32)
                else:
                    kb = jax.random.fold_in(
                        kb, jax.lax.axis_index(DATA_AXIS))
                    wb = jax.random.poisson(
                        kb, 1.0, (B.shape[0],)).astype(jnp.float32)
                    stats = base_stats * wb[None, :]
                    # First-mtry-of-perm mask via the inverse permutation
                    # (rank < mtry) — the traced-mtry form of the
                    # oracle's static ``perm[:mtry]`` scatter; the
                    # resulting feature set is identical.
                    perm = jax.random.permutation(kf, d)
                    allowed = jnp.argsort(perm) < mtry_m
                    fmask = jnp.where(allowed, 0.0, NEG)
                return _build_tree(
                    B, stats, fmask, max_depth=max_depth, n_bins=n_bins,
                    gain_fn=_gini_gain, weight_fn=lambda s: s.sum(-1),
                    min_child_weight=1.0, min_gain=1e-9,
                    use_kernel=False, bin_gain_mask=bmask,
                    level_allow=lallow)

            return jax.vmap(one_tree)(keys)

        return jax.vmap(one_member)(B_pop, w_pop, bin_mask, level_allow,
                                    mtry_vec, keys_b)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(None, DATA_AXIS), P(DATA_AXIS), P(None, DATA_AXIS),
                  P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(B_pop, y, w_pop, bin_mask, level_allow, mtry_vec, keys_b)


@partial(jax.jit, static_argnames=("max_depth", "mesh"))
def _forest_pop_scores(B_pop, y, ew_pop, feat, thr, internal, leaf, *,
                       max_depth, mesh):
    """Per-member forest accuracy on per-member (eval-fold) row weights.

    Tree arrays arrive at the FULL (Pm, n_trees, ...) shape with
    all-zero slots for not-yet-built trees (their leaf counts are zero,
    contributing zero probability mass), so every halving rung scores
    through this one compiled program."""

    def shard_fn(B_pop, y, ew_pop, feat, thr, internal, leaf):
        def one_member(B, ew, f, t, it, lf):
            def tree_proba(f1, t1, it1, lf1):
                assign = _descend(B, f1, t1, it1, max_depth)
                counts = _sel_rows_blocked(lf1, assign)
                return counts / jnp.maximum(
                    counts.sum(-1, keepdims=True), 1e-12)

            probs = jax.vmap(tree_proba)(f, t, it, lf).mean(axis=0)
            pred = jnp.argmax(probs, axis=1).astype(y.dtype)
            hit = jax.lax.psum(
                ((pred == y).astype(jnp.float32) * ew).sum(), DATA_AXIS)
            tot = jax.lax.psum(ew.sum(), DATA_AXIS)
            return hit / jnp.maximum(tot, 1.0)

        return jax.vmap(one_member)(B_pop, ew_pop, feat, thr, internal,
                                    leaf)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(None, DATA_AXIS), P(DATA_AXIS), P(None, DATA_AXIS),
                  P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(B_pop, y, ew_pop, feat, thr, internal, leaf)


@partial(jax.jit,
         static_argnames=("max_depth", "n_bins", "n_rounds", "mesh"))
def _fit_gbt_pop_seg(B_pop, y, w_pop, margin0, step_sizes, round_active,
                     bin_mask, level_allow, *, max_depth, n_bins,
                     n_rounds, mesh):
    """One SEGMENT of boost rounds for a POPULATION of gb configs.

    ``round_active`` is (Pm, n_rounds) ∈ {0, 1}: a zero round leaves the
    member's margin untouched and zeroes the round's leaf values (so the
    stacked trees stay inert in prediction) — this is how per-member
    ``n_rounds`` under the static maximum and halving-dropped members
    are expressed. Per-member ``step_sizes`` ride as traced scalars, the
    boost-round arithmetic is the serial oracle's (lam = 1.0)."""

    def shard_fn(B_pop, y, w_pop, margin0, step_sizes, round_active,
                 bin_mask, level_allow):
        gain_fn = _make_newton_gain(1.0)
        yf = y.astype(jnp.float32)

        def one_member(B, w, margin, step_size, ractive, bmask, lallow):
            def boost_round(margin, act):
                p = jax.nn.sigmoid(margin)
                g = (p - yf) * w
                h = jnp.maximum(p * (1 - p), 1e-6) * w
                stats = jnp.stack([g, h], axis=0)
                feat, thr, internal, leaf = _build_tree(
                    B, stats, jnp.zeros((B.shape[1],), jnp.float32),
                    max_depth=max_depth, n_bins=n_bins, gain_fn=gain_fn,
                    weight_fn=lambda s: s[..., 1],
                    min_child_weight=1e-3, min_gain=1e-9,
                    use_kernel=False, bin_gain_mask=bmask,
                    level_allow=lallow)
                leaf_val = (-leaf[:, 0] / (leaf[:, 1] + 1.0)) * act
                assign = _descend(B, feat, thr, internal, max_depth)
                margin = margin + step_size * _sel_table_blocked(
                    leaf_val, assign)
                return margin, (feat, thr, internal, leaf_val)

            margin, trees_out = jax.lax.scan(boost_round, margin,
                                             ractive)
            return trees_out, margin

        return jax.vmap(one_member)(B_pop, w_pop, margin0, step_sizes,
                                    round_active, bin_mask, level_allow)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(None, DATA_AXIS), P(DATA_AXIS), P(None, DATA_AXIS),
                  P(None, DATA_AXIS), P(), P(), P(), P()),
        out_specs=(P(), P(None, DATA_AXIS)), check_vma=False,
    )(B_pop, y, w_pop, margin0, step_sizes, round_active, bin_mask,
      level_allow)


@partial(jax.jit, static_argnames=("max_depth", "mesh"))
def _gbt_pop_replay_margin(B_pop, feat, thr, internal, leaf_val,
                           step_sizes, *, max_depth, mesh):
    """Per-member margin replay from checkpointed population trees — the
    resume path's analogue of ``_gbt_replay_margin``. Leaf values were
    stored already round-activity-scaled, so the replayed fold is the
    training scan's own sequence bit-for-bit."""

    def shard_fn(B_pop, feat, thr, internal, leaf_val, step_sizes):
        def one_member(B, f, t, it, lv, ss):
            def one(margin, tree):
                f1, t1, it1, lv1 = tree
                assign = _descend(B, f1, t1, it1, max_depth)
                return margin + ss * _sel_table_blocked(lv1, assign), None

            margin, _ = jax.lax.scan(
                one, jnp.zeros(B.shape[0], jnp.float32), (f, t, it, lv))
            return margin

        return jax.vmap(one_member)(B_pop, feat, thr, internal, leaf_val,
                                    step_sizes)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(None, DATA_AXIS), P(), P(), P(), P(), P()),
        out_specs=P(None, DATA_AXIS), check_vma=False,
    )(B_pop, feat, thr, internal, leaf_val, step_sizes)


@partial(jax.jit, static_argnames=("max_depth", "mesh"))
def _gbt_pop_scores(B_pop, y, ew_pop, feat, thr, internal, leaf_val,
                    step_sizes, *, max_depth, mesh):
    """Per-member binary-gb accuracy on eval-fold weights. Unbuilt/inert
    rounds carry zero leaf values, so the fixed (Pm, R_max, ...) shape
    scores every rung through one compiled program."""

    def shard_fn(B_pop, y, ew_pop, feat, thr, internal, leaf_val,
                 step_sizes):
        def one_member(B, ew, f, t, it, lv, ss):
            def tree_margin(f1, t1, it1, lv1):
                return _sel_table_blocked(
                    lv1, _descend(B, f1, t1, it1, max_depth))

            margin = ss * jax.vmap(tree_margin)(f, t, it, lv).sum(axis=0)
            pred = (jax.nn.sigmoid(margin) > 0.5).astype(y.dtype)
            hit = jax.lax.psum(
                ((pred == y).astype(jnp.float32) * ew).sum(), DATA_AXIS)
            tot = jax.lax.psum(ew.sum(), DATA_AXIS)
            return hit / jnp.maximum(tot, 1.0)

        return jax.vmap(one_member)(B_pop, ew_pop, feat, thr, internal,
                                    leaf_val, step_sizes)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(None, DATA_AXIS), P(DATA_AXIS), P(None, DATA_AXIS),
                  P(), P(), P(), P(), P()),
        out_specs=P(), check_vma=False,
    )(B_pop, y, ew_pop, feat, thr, internal, leaf_val, step_sizes)


def _edge_prep(X, n_bins: int = 32, **_ignored) -> dict:
    """Host-side prep shared by every tree family: per-feature quantile
    bin edges from a row sample. Exposed as the trainers' ``host_prep``
    hook so the pipelined builder can run this (chunk-store reads for
    lazy designs, host quantiles) OUTSIDE the device phase — overlapping
    another family's device compute. Deterministic (seeded sampler), so
    pod workers recomputing it inside their trainer calls produce
    bit-identical edges. Lazy designs never exist fully on the host: the
    sample comes from strided range reads (quantile sketches over samples
    are the norm for histogram GBTs — the full-matrix path itself
    subsamples to 200k)."""
    validate_n_bins(n_bins)
    X = as_design(X)
    return {"edges": quantile_edges(
        X if isinstance(X, np.ndarray) else X.sample_rows(200_000), n_bins)}


def _run_forest_checkpointed(runtime, ckpt, B_dev, y_dev, valid_dev,
                             seed, *, num_classes, max_depth, n_bins,
                             n_trees, mtry, use_kernel):
    """Batch-at-a-time forest build with a checkpoint at every vmapped
    tree-batch boundary. Keys, batch shapes and the per-tree body are
    the oracle's, so the stacked result is bit-identical to one
    ``_fit_forest`` call; a resume skips the completed batches."""
    from learningorchestra_tpu import jobs
    from learningorchestra_tpu.utils import fitckpt

    mesh = runtime.mesh
    tb, nb = _forest_batch_shape(n_trees)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed), nb * tb))
    names = ("feat", "thr", "internal", "leaf")
    done_b = 0
    host: dict = {}
    loaded = ckpt.load()
    if loaded is not None:
        trees_done, arrays, meta = loaded
        if trees_done % tb == 0 and 0 < trees_done <= nb * tb and all(
                k in arrays for k in names):
            done_b = trees_done // tb
            host = {k: arrays[k] for k in names}
            fitckpt.count_resume()
            jobs.record_job_resume(ckpt.family, {
                "trees": int(trees_done), "of": int(n_trees),
                "mesh_epoch": meta.get("mesh_epoch")})
        else:
            ckpt.clear()
    for b in range(done_b, nb):
        outs = _fit_forest_batch(
            B_dev, y_dev, valid_dev,
            jnp.asarray(keys[b * tb:(b + 1) * tb]),
            num_classes=num_classes, max_depth=max_depth, n_bins=n_bins,
            n_trees=n_trees, mesh=mesh, mtry=mtry, use_kernel=use_kernel)
        seg = {k: np.asarray(a) for k, a in zip(names, outs)}
        host = ({k: np.concatenate([host[k], seg[k]]) for k in names}
                if host else seg)
        jobs.heartbeat()
        if b + 1 < nb:
            ckpt.save((b + 1) * tb, host)
    return tuple(jnp.asarray(host[k][:n_trees]) for k in names)


def _fit_cls_trees(kind, runtime, X, y, num_classes, seed, *, n_trees,
                   max_depth, n_bins, mtry=None, edges=None, ckpt=None):
    validate_n_bins(n_bins)

    X = as_design(X)
    if edges is None:
        edges = _edge_prep(X, n_bins)["edges"]
    # Shard the raw design matrix (one cached host→device transfer shared
    # with every other family in a multi-classifier build) and bin ON
    # DEVICE: binning is row-local, so the uint8 codes come out row-sharded
    # with no host round-trip of the bin matrix.
    X_dev, n = runtime.shard_rows(X)
    B_dev = bin_features(X_dev, runtime.replicate(edges))
    y_dev, _ = runtime.shard_rows(np.asarray(y, np.int32))
    padded_len = len(X) + (-len(X)) % runtime.mesh.shape[DATA_AXIS]
    valid_dev, _ = runtime.shard_rows(
        (np.arange(padded_len) < n).astype(np.float32))
    d = X.shape[1]
    mtry = mtry or max(1, int(np.sqrt(d)))
    use_kernel = _use_tree_kernel(runtime)
    if (ckpt is not None and ckpt.enabled
            and _forest_batch_shape(n_trees)[1] > 1):
        feat, thr, internal, leaf = _run_forest_checkpointed(
            runtime, ckpt, B_dev, y_dev, valid_dev, seed,
            num_classes=num_classes, max_depth=max_depth, n_bins=n_bins,
            n_trees=n_trees, mtry=mtry, use_kernel=use_kernel)
    else:
        feat, thr, internal, leaf = _fit_forest(
            B_dev, y_dev, valid_dev, jax.random.PRNGKey(seed),
            num_classes=num_classes, max_depth=max_depth, n_bins=n_bins,
            n_trees=n_trees, mesh=runtime.mesh, mtry=mtry,
            use_kernel=use_kernel)
    params = {"edges": jnp.asarray(edges), "feat": feat, "thr": thr,
              "internal": internal, "leaf": leaf}
    return TrainedModel(
        kind=kind, params=params,
        predict_proba_fn=partial(_forest_proba_static, max_depth=max_depth),
        num_classes=num_classes,
        hparams={"n_trees": n_trees, "max_depth": max_depth,
                 "n_bins": n_bins})


@_predict_program
def _forest_proba_static(params, X, *, max_depth):
    B = bin_features(X, params["edges"])
    # Trace-time kernel selection is safe here: descent is integer
    # arithmetic, so probabilities are bit-identical on either path (the
    # AOT row-wise predict programs stay on the oracle via the batch-size
    # gate in _descend).
    use_kernel = _use_tree_kernel()

    def tree_proba(f, t, it, lf):
        assign = _descend(B, f, t, it, max_depth, use_kernel=use_kernel)
        counts = _sel_rows_blocked(lf, assign)
        return counts / jnp.maximum(counts.sum(-1, keepdims=True), 1e-12)

    probs = jax.vmap(tree_proba)(params["feat"], params["thr"],
                                 params["internal"], params["leaf"])
    return probs.mean(axis=0)


def fit_dt(runtime: MeshRuntime, X, y, num_classes, seed=0, *,
           max_depth: int = 5, n_bins: int = 32,
           edges=None, ckpt=None) -> TrainedModel:
    return _fit_cls_trees("dt", runtime, X, y, num_classes, seed,
                          n_trees=1, max_depth=max_depth, n_bins=n_bins,
                          edges=edges, ckpt=ckpt)


def fit_rf(runtime: MeshRuntime, X, y, num_classes, seed=0, *,
           n_trees: int = 20, max_depth: int = 5,
           n_bins: int = 32, mtry: Optional[int] = None,
           edges=None, ckpt=None) -> TrainedModel:
    return _fit_cls_trees("rf", runtime, X, y, num_classes, seed,
                          n_trees=n_trees, max_depth=max_depth,
                          n_bins=n_bins, mtry=mtry, edges=edges,
                          ckpt=ckpt)


fit_dt.host_prep = _edge_prep
fit_rf.host_prep = _edge_prep


# ---------------------------------------------------------------------------
# gb  (gradient-boosted trees, binary, logistic loss — as Spark's GBT)
# ---------------------------------------------------------------------------

def _boost_round_fn(B, yf, valid, *, max_depth, n_bins, step_size, lam,
                    use_kernel):
    """The per-round boosting body, shared verbatim by the oracle scan
    (``_fit_gbt``) and the checkpoint-segmented scan (``_fit_gbt_seg``)
    so the two paths cannot drift numerically."""
    gain_fn = _make_newton_gain(lam)

    def boost_round(margin, _):
        p = jax.nn.sigmoid(margin)
        g = (p - yf) * valid          # d loss / d margin
        h = jnp.maximum(p * (1 - p), 1e-6) * valid
        stats = jnp.stack([g, h], axis=0)          # (2, n) — lanes = n
        feat, thr, internal, leaf = _build_tree(
            B, stats, jnp.zeros((B.shape[1],), jnp.float32),
            max_depth=max_depth, n_bins=n_bins, gain_fn=gain_fn,
            weight_fn=lambda s: s[..., 1],
            min_child_weight=1e-3, min_gain=1e-9,
            use_kernel=use_kernel)
        leaf_val = -leaf[:, 0] / (leaf[:, 1] + lam)       # (M,)
        assign = _descend(B, feat, thr, internal, max_depth,
                          use_kernel=use_kernel)
        margin = margin + step_size * _sel_table_blocked(leaf_val,
                                                         assign)
        return margin, (feat, thr, internal, leaf_val)

    return boost_round


@partial(jax.jit,
         static_argnames=("max_depth", "n_bins", "n_rounds", "mesh",
                          "use_kernel"))
def _fit_gbt(B, y, valid, *, max_depth, n_bins, n_rounds, mesh,
             step_size=0.1, lam=1.0, use_kernel=False):
    def shard_fn(B, y, valid):
        yf = y.astype(jnp.float32)
        margin = jnp.zeros(B.shape[0], jnp.float32)
        boost_round = _boost_round_fn(
            B, yf, valid, max_depth=max_depth, n_bins=n_bins,
            step_size=step_size, lam=lam, use_kernel=use_kernel)
        _, trees = jax.lax.scan(boost_round, margin, None,
                                length=n_rounds)
        return trees

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(), check_vma=False,
    )(B, y, valid)


@partial(jax.jit,
         static_argnames=("max_depth", "n_bins", "n_rounds", "mesh",
                          "use_kernel"))
def _fit_gbt_seg(B, y, valid, margin0, *, max_depth, n_bins, n_rounds,
                 mesh, step_size=0.1, lam=1.0, use_kernel=False):
    """One SEGMENT of boost rounds for the checkpointed gb path: takes
    the carried margin in (row-sharded), returns it back out next to the
    segment's trees — so a fit interrupted between segments resumes from
    the persisted trees with bit-identical arithmetic (the round body is
    the oracle's, shared via ``_boost_round_fn``). Only engaged when
    ``LO_TPU_FIT_CKPT_ROUNDS > 0``; the single-scan oracle above stays
    today's path otherwise."""
    def shard_fn(B, y, valid, margin0):
        yf = y.astype(jnp.float32)
        boost_round = _boost_round_fn(
            B, yf, valid, max_depth=max_depth, n_bins=n_bins,
            step_size=step_size, lam=lam, use_kernel=use_kernel)
        margin, trees = jax.lax.scan(boost_round, margin0, None,
                                     length=n_rounds)
        return trees, margin

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS)),
        out_specs=(P(), P(DATA_AXIS)), check_vma=False,
    )(B, y, valid, margin0)


@partial(jax.jit, static_argnames=("max_depth", "mesh", "use_kernel"))
def _gbt_replay_margin(B, feat, thr, internal, leaf_val, step_size, *,
                       max_depth, mesh, use_kernel=False):
    """Rebuild the boosting margin from checkpointed trees by replaying
    each round's margin update — the same sequential
    ``margin += step_size * leaf_val[descend(B)]`` fold the training
    scan performs, in the same order, so the resumed margin is
    bit-identical to the interrupted fit's carry (descent is integer
    arithmetic; the f32 accumulation order is preserved). Cost is the
    cheap descent/lookup part of each completed round — the histogram
    builds, which dominate a round, are never re-executed."""
    def shard_fn(B, feat, thr, internal, leaf_val, step_size):
        def one(margin, tree):
            f, t, it, lv = tree
            assign = _descend(B, f, t, it, max_depth,
                              use_kernel=use_kernel)
            return margin + step_size * _sel_table_blocked(lv, assign), \
                None

        margin, _ = jax.lax.scan(
            one, jnp.zeros(B.shape[0], jnp.float32),
            (feat, thr, internal, leaf_val))
        return margin

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(DATA_AXIS), P(), P(), P(), P(), P()),
        out_specs=P(DATA_AXIS), check_vma=False,
    )(B, feat, thr, internal, leaf_val, step_size)


@_predict_program
def _gbt_proba_static(params, X, *, max_depth):
    B = bin_features(X, params["edges"])
    use_kernel = _use_tree_kernel()

    def tree_margin(f, t, it, lv):
        return _sel_table_blocked(lv, _descend(B, f, t, it, max_depth,
                                               use_kernel=use_kernel))

    margins = jax.vmap(tree_margin)(params["feat"], params["thr"],
                                    params["internal"], params["leaf_val"])
    margin = params["step_size"] * margins.sum(axis=0)
    p1 = jax.nn.sigmoid(margin)
    return jnp.stack([1 - p1, p1], axis=1)


@_predict_program
def _gbt_ovr_proba_static(params, X, *, max_depth):
    """Multiclass gb probabilities: per-class booster margins (leading
    class axis on every tree param), class scores p_k = σ(margin_k),
    normalized — standard one-vs-rest calibration."""
    B = bin_features(X, params["edges"])
    use_kernel = _use_tree_kernel()

    def class_margin(feat, thr, internal, leaf_val):
        def tree_margin(f, t, it, lv):
            return _sel_table_blocked(lv, _descend(B, f, t, it, max_depth,
                                                   use_kernel=use_kernel))

        return jax.vmap(tree_margin)(feat, thr, internal,
                                     leaf_val).sum(axis=0)

    margins = jax.vmap(class_margin)(
        params["feat"], params["thr"], params["internal"],
        params["leaf_val"])                              # (C, n)
    p = jax.nn.sigmoid(params["step_size"] * margins).T  # (n, C)
    return p / jnp.maximum(p.sum(axis=1, keepdims=True), 1e-12)


def _run_gbt_checkpointed(runtime, ckpt, B_dev, y_dev, valid_dev, *,
                          max_depth, n_bins, n_rounds, step_size,
                          use_kernel):
    """Segment-at-a-time gb build with a checkpoint every
    ``ckpt.every`` boost rounds. The carried margin stays on device
    between segments (row-sharded); on resume it is REPLAYED from the
    checkpointed trees — the same sequential fold the training scan
    performs, so the continued fit is bit-identical to an uninterrupted
    one. Returns the stacked per-round tree params."""
    from learningorchestra_tpu import jobs
    from learningorchestra_tpu.utils import fitckpt

    mesh = runtime.mesh
    names = ("feat", "thr", "internal", "leaf_val")
    done = 0
    host: dict = {}
    margin = None
    loaded = ckpt.load()
    if loaded is not None:
        rounds_done, arrays, meta = loaded
        if 0 < rounds_done <= n_rounds and all(k in arrays
                                               for k in names):
            done = rounds_done
            host = {k: arrays[k] for k in names}
            margin = _gbt_replay_margin(
                B_dev, jnp.asarray(host["feat"]),
                jnp.asarray(host["thr"]), jnp.asarray(host["internal"]),
                jnp.asarray(host["leaf_val"]), step_size,
                max_depth=max_depth, mesh=mesh, use_kernel=use_kernel)
            fitckpt.count_resume()
            jobs.record_job_resume(ckpt.family, {
                "rounds": int(done), "of": int(n_rounds),
                "mesh_epoch": meta.get("mesh_epoch")})
        else:
            ckpt.clear()
    if margin is None:
        margin, _ = runtime.shard_rows(
            np.zeros(int(B_dev.shape[0]), np.float32))
    every = max(1, int(ckpt.every))
    while done < n_rounds:
        k = min(every, n_rounds - done)
        trees, margin = _fit_gbt_seg(
            B_dev, y_dev, valid_dev, margin, max_depth=max_depth,
            n_bins=n_bins, n_rounds=k, mesh=mesh, step_size=step_size,
            use_kernel=use_kernel)
        seg = {kk: np.asarray(a) for kk, a in zip(names, trees)}
        host = ({kk: np.concatenate([host[kk], seg[kk]])
                 for kk in names} if host else seg)
        done += k
        jobs.heartbeat()
        if done < n_rounds:
            ckpt.save(done, host)
    return tuple(jnp.asarray(host[kk]) for kk in names)


def fit_gb(runtime: MeshRuntime, X, y, num_classes, seed=0, *,
           n_rounds: int = 20, max_depth: int = 5, n_bins: int = 32,
           step_size: float = 0.1, edges=None, ckpt=None) -> TrainedModel:
    """Gradient-boosted trees. Binary is the reference-parity path (one
    booster, exactly Spark 2.4's GBTClassifier). ``num_classes > 2``
    goes BEYOND the reference (whose GBTClassifier refuses multiclass):
    one-vs-rest over the same binary builder — booster k fits labels
    ``y == k`` with identical bins/rounds, margins stack on a leading
    class axis, and probabilities are normalized sigmoid scores
    (``_gbt_ovr_proba_static``). Each booster's margin is bit-identical
    to a standalone binary fit on the same rest-labeled split (parity
    pinned in tests/test_models.py)."""
    validate_n_bins(n_bins)

    X = as_design(X)
    if edges is None:
        edges = _edge_prep(X, n_bins)["edges"]
    # Same device-side binning as _fit_cls_trees: shard X (cached), bin
    # row-locally on device, no host round-trip of the bin matrix.
    X_dev, n = runtime.shard_rows(X)
    B_dev = bin_features(X_dev, runtime.replicate(edges))
    padded_len = len(X) + (-len(X)) % runtime.mesh.shape[DATA_AXIS]
    valid_dev, _ = runtime.shard_rows(
        (np.arange(padded_len) < n).astype(np.float32))
    hparams = {"n_rounds": n_rounds, "max_depth": max_depth,
               "n_bins": n_bins, "step_size": step_size}
    use_kernel = _use_tree_kernel(runtime)
    if num_classes == 2:
        y_dev, _ = runtime.shard_rows(np.asarray(y, np.int32))
        if ckpt is not None and ckpt.enabled and n_rounds > 1:
            feat, thr, internal, leaf_val = _run_gbt_checkpointed(
                runtime, ckpt, B_dev, y_dev, valid_dev,
                max_depth=max_depth, n_bins=n_bins, n_rounds=n_rounds,
                step_size=step_size, use_kernel=use_kernel)
        else:
            feat, thr, internal, leaf_val = _fit_gbt(
                B_dev, y_dev, valid_dev, max_depth=max_depth,
                n_bins=n_bins, n_rounds=n_rounds, mesh=runtime.mesh,
                step_size=step_size, use_kernel=use_kernel)
        params = {"edges": jnp.asarray(edges), "feat": feat, "thr": thr,
                  "internal": internal, "leaf_val": leaf_val,
                  "step_size": jnp.float32(step_size)}
        return TrainedModel(
            kind="gb", params=params,
            predict_proba_fn=partial(_gbt_proba_static,
                                     max_depth=max_depth),
            num_classes=2, hparams=hparams)
    # One-vs-rest: C boosters over the SAME binned matrix (one transfer,
    # one binning program — only the 0/1 labels change per booster).
    # Mid-fit checkpointing stays off here (per-booster streams would
    # need per-class keys); the binary reference-parity path is the one
    # HIGGS-scale fits take.
    y_np = np.asarray(y, np.int32)
    per_class = []
    for k in range(num_classes):
        yk_dev, _ = runtime.shard_rows((y_np == k).astype(np.int32))
        per_class.append(_fit_gbt(
            B_dev, yk_dev, valid_dev, max_depth=max_depth, n_bins=n_bins,
            n_rounds=n_rounds, mesh=runtime.mesh, step_size=step_size,
            use_kernel=use_kernel))
        # Boosters enqueue back-to-back; fence the multi-process CPU rig
        # (no-op on TPU — stream order already aligns the collectives).
        spmd.serialize_collectives(per_class[-1])
    feat, thr, internal, leaf_val = (
        jnp.stack([pc[i] for pc in per_class]) for i in range(4))
    params = {"edges": jnp.asarray(edges), "feat": feat, "thr": thr,
              "internal": internal, "leaf_val": leaf_val,
              "step_size": jnp.float32(step_size)}
    return TrainedModel(
        kind="gb", params=params,
        predict_proba_fn=partial(_gbt_ovr_proba_static,
                                 max_depth=max_depth),
        num_classes=num_classes,
        hparams=dict(hparams, ovr_classes=num_classes))


fit_gb.host_prep = _edge_prep
