"""Pallas TPU kernels for the framework's hottest inner loops.

The reference's native horsepower lived in the external Spark JVM
(SURVEY.md §2); here the native tier is hand-written TPU kernels for the
ops XLA alone schedules sub-optimally. Residents:

- **t-SNE exact repulsion** — the O(n²) loop executed every one of ~750
  descent iterations (viz/tsne.py), dominating embed wall-clock at
  MNIST-60k scale. The kernel keeps the whole (row-tile × col-tile)
  block pipeline — distance, Student-t weight, masking, the three
  reductions — in VMEM, with zero HBM traffic beyond streaming the
  (n, 1) coordinate vectors and accumulating (n, 1) force outputs.

- **Binned-histogram tree fitting** (models/trees.py, gated by
  `LO_TPU_TREE_KERNEL`) — the two hot inner loops of level-wise tree
  growth. `tree_histogram` / `tree_leaf_stats` accumulate the
  (node, feature, bin, stat) sufficient statistics per row tile with the
  one-hot operands of the histogram contraction built *inside* VMEM —
  the pure-XLA path materializes a ~97%-zeros (block, d·n_bins) one-hot
  in HBM per row block per level, and that traffic dominates tree fits.
  `tree_route_level` / `tree_descend` fuse the per-row node-table
  lookups (the compare-sum gather emulations) and child-assignment
  update into one VPU pass per row tile. The XLA contraction path is
  kept as the bit-parity oracle (docs/performance.md).
  A pass of the histogram kernel costs what its one-hot costs — built
  on the VPU and loaded into the MXU per row tile — however many stats
  rows then stream past it, so a batch of trees that shares its bin
  matrix (rf's `vmap(one_tree)`) is ONE pass: the call's batching rule
  (`_hist_call`, a `jax.custom_batching.custom_vmap`) reads which
  operands carry the batch axis and stacks the trees on the matmul's
  rows (`tree_hist_stacked`, M = T·NG·S) where the codes are shared, and
  leaves the batch a grid axis of the one-tree kernel where every
  member has codes of its own (the leaf statistics, a population).

- **Attention over chosen keys** (models/transformer.py
  `_chosen_attention`: the `tx` family's grouped-query causal attention,
  with or without the sparse-attention indexer's selection) — a block of
  `C` queries against the row's `T` keys. The plain body writes the
  (heads, C, T) float32 score block to HBM and reads it back for the
  softmax and again for the probability-times-value product, forward and
  backward (134 MB a block at 32 heads x 128 x 8,192). `chosen_attention`
  is three kernels under one `jax.custom_vjp` that walk the row a key
  block at a time: per key-value head, VMEM holds the group's stacked
  queries (R·C, D), the current (key block, D) keys and values, the
  (C, key block) int8 mask tile, one (R·C, key block) float32 score tile
  with its exponentials in the MXU's operand type, and the running
  maximum, sum and (R·C, D) accumulator of the online softmax
  (`chosen_attn_fwd`); the head-summed probabilities the indexer's
  alignment loss reads take a second walk once the log-sum-exp is final,
  the (C, key block) sum staying in VMEM while the heads pass
  (`chosen_attn_probs`); the backward recomputes scores and
  probabilities from the log-sum-exp, keeps the `dq` (R·C, D)
  accumulator in VMEM and writes each key block's `dk`, `dv` once
  (`chosen_attn_bwd`). Key blocks that start past the block's last
  query are neither computed nor fetched: the last needed block rides
  scalar prefetch and the index maps clamp to it. The key block (512,
  256 or 128 keys) is the largest whose working set at the group's R·C
  rows fits half a v5e core's VMEM (`chosen_attn_key_block`), and that
  working set is the scoped VMEM each call asks for; a group too tall
  for the smallest runs the plain body.

- **The delta rule's chunk transform** (models/transformer.py
  `_chunk_transform`: `T = (I + A)^-1` of the unit-lower-triangular
  systems of a block of the gated delta-rule mixer, 60 of 64x64 a
  256-token block at Olmo-Hybrid's widths). XLA's triangular solve
  inverts them in a serial custom call, 400 us a block and pass on the
  v5e; the plain block recursion is exact but two dozen small fusions.
  `delta_transform` puts the systems in lanes (row, column, system) and
  runs blocked forward substitution on the VPU in one call: groups of 8
  rows, a group finished among its own rows and then taken out of every
  later row, rows read only as far as they are non-zero.

- **The expert layer's grouped products** (models/transformer.py
  `_expert_loop`: the routed pairs of a held-experts layer, sorted by
  expert). `grouped_matmul` runs each row through its group's weights
  a tile of `GROUP_TILE` rows at a time, and `grouped_matmul_t` sums a
  group's rows' outer products into its weight gradient: megablox's
  `gmm` / `tgmm` schedule (`jax.experimental.pallas.ops.tpu.megablox`),
  whose grid is the number of tiles the groups cover, counted at run
  time, so rows past the count cost nothing. Here the contraction is
  one block (a group's weights stay in VMEM over its tiles), the
  products and the weight gradient are float32 whatever the operands,
  and the outputs are typed for `shard_map`.

On non-TPU backends every `pallas_call` runs in interpreter mode, so the
same code path is unit-tested on the CPU mesh (tests/conftest.py) and
cross-checked against the pure-XLA reference implementation.

Each `pallas_call` carries a `name=` (`tsne_repulsion`, `tree_hist`,
`tree_hist_stacked`, `tree_route`, `tree_descend`, `chosen_attn_fwd`,
`chosen_attn_probs`, `chosen_attn_bwd`, `delta_transform`, `grouped_mm`,
`grouped_mm_t`): it becomes the
innermost name scope, XLA names the custom-call instruction after it
(`%tree_hist.3 = ... custom_call_target="tpu_custom_call"`), and that
instruction text is the event's name on a device profile's `XLA Ops` line — which is all the
benchmark's per-kernel metrics read (perfbench/layer_metrics).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Row/col tile for the repulsion grid. 512×512 f32 blocks are 1 MB —
#: a handful fit VMEM alongside the coordinate vectors; big enough that
#: the (8, 128) f32 sublane×lane tiling is fully utilized.
TILE = 512


def _interpret() -> bool:
    """Interpreter mode off-TPU so kernels run (and are tested) anywhere."""
    return jax.default_backend() != "tpu"


def _repulsion_kernel(off_ref, xr_ref, yr_ref, vr_ref, xc_ref, yc_ref,
                      vc_ref, z_ref, fx_ref, fy_ref):
    """One (row-tile i, col-tile j) cell of the pairwise Student-t grid.

    Refs: off is the (1, 1) SMEM global row offset of the query block
    (row-sharded multi-chip t-SNE passes each shard's range; 0 for the
    full embedding); xr/yr/vr are (TILE, 1) row-block coordinate/valid
    columns; xc/yc/vc are (1, TILE) col-block rows. Outputs: fx/fy
    accumulate the repulsive force numerator per row block (revisited
    across j, so the block stays resident in VMEM while the column tiles
    stream past); z is the (1, 1) SMEM running sum of all q_ij (the
    normalizer Z).
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    tile = xr_ref.shape[0]

    dx = xr_ref[:] - xc_ref[:]                      # (tile, tile)
    dy = yr_ref[:] - yc_ref[:]
    q = 1.0 / (1.0 + dx * dx + dy * dy)

    # Mask invalid (padding) rows/cols and the self-pair diagonal
    # (row ids are global via the shard offset; col ids are global).
    rid = (off_ref[0] + i * tile
           + jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0))
    cid = j * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    q = q * (vr_ref[:] * vc_ref[:]) * (rid != cid).astype(jnp.float32)

    q2 = q * q
    s = jnp.sum(q2, axis=1, keepdims=True)          # (TILE, 1)
    fx = xr_ref[:] * s - jnp.sum(q2 * xc_ref[:], axis=1, keepdims=True)
    fy = yr_ref[:] * s - jnp.sum(q2 * yc_ref[:], axis=1, keepdims=True)
    zp = jnp.sum(q)

    @pl.when(j == 0)
    def _init_row():
        fx_ref[:] = fx
        fy_ref[:] = fy

    @pl.when(j != 0)
    def _acc_row():
        fx_ref[:] += fx
        fy_ref[:] += fy

    @pl.when((i == 0) & (j == 0))
    def _init_z():
        z_ref[0, 0] = zp

    @pl.when((i != 0) | (j != 0))
    def _acc_z():
        z_ref[0, 0] += zp


def tsne_repulsion_rows(Yq: jax.Array, validq: jax.Array, Y: jax.Array,
                        valid: jax.Array, offset, *, tile: int = TILE):
    """Repulsion for the query row block ``Yq`` (global rows
    [offset, offset+len(Yq))) against every column of ``Y`` — the
    per-shard unit of the row-sharded multi-chip embed (viz/tsne.py).
    Returns (Z_partial, F (len(Yq), 2)); summing Z partials over shards
    reproduces ``tsne_repulsion``'s Z exactly.
    """
    nq = Yq.shape[0]
    n = Y.shape[0]
    assert nq % tile == 0 and n % tile == 0, (nq, n, tile)
    off = jnp.asarray(offset, jnp.int32).reshape(1)
    xr = Yq[:, 0:1]
    yr = Yq[:, 1:2]
    vr = validq[:, None]
    xc = Y[:, 0][None, :]
    yc = Y[:, 1][None, :]
    vc = valid[None, :]

    grid = (nq // tile, n // tile)
    # The offset rides scalar prefetch (SMEM); index maps therefore take
    # the scalar ref as a trailing argument.
    row_spec = pl.BlockSpec((tile, 1), lambda i, j, off: (i, 0))
    col_spec = pl.BlockSpec((1, tile), lambda i, j, off: (0, j))
    out_row_spec = pl.BlockSpec((tile, 1), lambda i, j, off: (i, 0))
    z_spec = pl.BlockSpec(memory_space=pltpu.SMEM)

    z, fx, fy = pl.pallas_call(
        _repulsion_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[row_spec, row_spec, row_spec,
                      col_spec, col_spec, col_spec],
            out_specs=[z_spec, out_row_spec, out_row_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((nq, 1), jnp.float32),
            jax.ShapeDtypeStruct((nq, 1), jnp.float32),
        ],
        interpret=_interpret(),
        name="tsne_repulsion",
    )(off, xr, yr, vr, xc, yc, vc)
    return z[0, 0], jnp.concatenate([fx, fy], axis=1)


@partial(jax.jit, static_argnames=("tile",))
def tsne_repulsion(Y: jax.Array, valid: jax.Array, *, tile: int = TILE):
    """Exact t-SNE repulsion over all pairs of a 2-D embedding.

    Y: (n, 2) float32, n a multiple of ``tile`` (padding masked by
    ``valid``). Returns (Z, F): the scalar partition-function sum
    Σ_{i≠j} q_ij and the (n, 2) force numerator Σ_j q²_ij (y_i − y_j) —
    identical semantics to the pure-XLA ``rep_block`` scan in viz/tsne.py.
    """
    return tsne_repulsion_rows(Y, valid, Y, valid, 0, tile=tile)


# ---------------------------------------------------------------------------
# Binned-histogram tree-fitting kernels (models/trees.py hot loops)
# ---------------------------------------------------------------------------

#: Bounds the row tile (``tree_tile``): the bytes a whole (tile, d·n_bins)
#: f32 bin one-hot would take. The kernel holds no such block — it
#: builds one 128-column group's (128, tile) compare mask at a time and
#: the MXU takes the mask itself as its weights — so this is not what
#: VMEM holds (that is the step's operand blocks, double-buffered, and
#: the accumulator under ``_TREE_ACC_BYTES``). It stays because the tile
#: is the length of every dot's contraction, hence the order in which
#: gb's real-valued statistics are summed: another tile is another
#: last bit.
_TREE_ONEHOT_BYTES = 4 << 20
#: VMEM byte budget for the resident (node·stat, d·n_bins) histogram
#: accumulator block; larger accumulators split over a node-group grid
#: dimension (each group re-streams the row tiles).
_TREE_ACC_BYTES = 2 << 20
#: VMEM byte budget for one grid step's operand blocks of the histogram
#: kernel (bin codes, stats, node ids; the pipeline holds two steps).
_TREE_STEP_BYTES = 1 << 20
#: Row tile for the routing/descent kernels (pure VPU, tiny per-row
#: state) and the minimum prediction batch that engages ``tree_descend``
#: (below it, padding overhead beats the fusion win — e.g. the online
#: serving tier's row-wise AOT programs stay on the XLA oracle).
TREE_ROUTE_TILE = 512
#: Lane width of a TPU vector register: the histogram kernel builds its
#: one-hot and accumulates one such column group at a time.
_LANES = 128


def tree_tile(d: int, n_bins: int) -> int:
    """Histogram-kernel row tile — the rows one dot contracts over: the
    largest power of two ≤ 1024 under ``_TREE_ONEHOT_BYTES``. Floor 128
    keeps the f32/bf16 sublane tiling utilized even at d·n_bins extremes
    (d=128 × n_bins=256 → 128-row tiles)."""
    tile = 1024
    while tile > 128 and tile * max(d * n_bins, 1) * 4 > _TREE_ONEHOT_BYTES:
        tile //= 2
    return tile


def _tree_node_groups(n_nodes: int, n_stats: int, d: int,
                      n_bins: int) -> int:
    """Nodes per grid group so the resident accumulator block stays under
    budget; n_nodes is a power of two, so halving always divides."""
    ng = max(n_nodes, 1)
    while ng > 1 and ng * n_stats * d * n_bins * 4 > _TREE_ACC_BYTES:
        ng //= 2
    return ng


def _tiles_per_step(tile_bytes: int, n_tiles: int) -> int:
    """Row tiles one grid step of the histogram kernel takes (an in-kernel
    loop over them): up to eight, as many as keep the step's operand
    blocks under ``_TREE_STEP_BYTES``. A 1,024-row step is shorter than
    the latency of the three DMAs that fetch the next one, and waits for
    them at every step: 8.70 ms a pass at 11M × 28, 32 bins, against
    7.92 ms at eight tiles a step (chip run, PR 33). A loop and not an
    unrolled body: every process start traces and lowers the body again,
    persistent compile cache or not."""
    return max(1, min(8, _TREE_STEP_BYTES // tile_bytes, n_tiles))


def _pad_lanes(arr: jax.Array, n_pad: int, value=0) -> jax.Array:
    """Pad the trailing (row) axis out to ``n_pad``."""
    n = arr.shape[-1]
    if n == n_pad:
        return arr
    return jnp.pad(arr, ((0, 0),) * (arr.ndim - 1) + ((0, n_pad - n),),
                   constant_values=value)


def _row_vec(v: jax.Array, n_pad: int, value=0) -> jax.Array:
    """A per-row (n,) vector as the (1, n_pad) int32 row the kernels
    stream — a batch's (T, n) as (T, n_pad): rows sit in lanes, so the
    array is dense in HBM — an (n, 1) column is lane-padded 128×
    (measured from the v5e compile's memory_analysis: 11 GB of
    temporaries for a 1.1M-row rf batch)."""
    return _pad_lanes(v.astype(jnp.int32).reshape(-1, v.shape[-1]), n_pad,
                      value)


def _group_runs(lo: int, n_bins: int, d: int):
    """Static plan of the 128-column histogram group that starts at
    column ``lo``: its sublanes cut into runs that each lie in ONE
    feature's columns, as (feature, first sublane, bin at that sublane,
    sublanes); feature None past the last real column ``d·n_bins``."""
    runs, c = [], lo
    while c < lo + _LANES:
        f = c // n_bins
        end = min((f + 1) * n_bins, lo + _LANES)
        runs.append((f if f < d else None, c - lo, c - f * n_bins, end - c))
        c = end
    return runs


def _tree_hist_kernel(codes_ref, stats_ref, rel_ref, out_ref, *, n_bins,
                      n_trees, operand_dtype, tile, n_tiles):
    """One (node-group g, step t) cell of the histogram grid, for the
    ``n_trees`` trees that share the bin codes: the step's row tiles
    (``_tiles_per_step``), ``tile`` rows each, in the table's order.

    Scatter-adds each row tile's sufficient statistics into the
    VMEM-resident (T·NG·S, d·n_bins) accumulator block: the node-masked
    stats operand and the bin one-hot are built in VMEM and consumed by
    the MXU — never written to HBM. The accumulator block is indexed by
    g only, so it stays resident while the row tiles stream past (t is
    the innermost grid dimension). One dot per tile and group, whatever
    the step holds: the tile is the contraction, so the sums and their
    order are those of a one-tile step.

    Every value is 2-D with the tile's rows in lanes, as the operands
    arrive — Mosaic refuses the rank-3 broadcasts/reshapes the XLA
    oracle's formulation uses. A tree's stats operand's row
    ``node·S + s`` is selected from its (1, tile) node-id row, and the
    trees' (NG·S, tile) operands stack along the matmul's rows; the
    one-hot is built transposed, one 128-column group of the histogram
    at a time, ONCE for all the trees. It never exists as a value: what
    the kernel builds is the group's (128, tile) compare mask, which
    the TPU's compiler packs to the operand's width and pushes into the
    MXU as the weights themselves (1.0 under the mask), a 128×128 block
    per 128 rows of the tile. A pass is paced by the mask-writing
    instructions (two a bundle on the v5e), so the body spends one
    compare a vreg where the shape allows it: when ``n_bins`` is a
    multiple of the 8 sublanes of a vreg, every feature's run of a
    group's sublanes (``_group_runs``) starts and ends on a vreg, so
    the runs' sublane-broadcast code rows — each shifted so that a hit
    reads "equals my sublane's index" — concatenate for free and meet
    ONE iota in one compare, whatever the number of features in the
    group and wherever a feature straddles two groups. Any other
    ``n_bins`` (the 63 leaves of the leaf statistics, 2 bins) takes a
    compare per feature of the group, OR-ed: ``col`` is each (feature,
    row)'s global column ``f·n_bins + code``. The form is chosen from
    the static shape alone. Operands mirror the XLA oracle's dtype
    (bf16 on TPU, f32 elsewhere); {0,1} one-hot products are exact and
    every dot accumulates in f32 along the tile, per output row — so a
    tree's rows read the same whether it rides alone or stacked, and the
    same under either form of the mask.
    """
    g = pl.program_id(0)
    t = pl.program_id(1)
    d, step_rows = codes_ref.shape
    per_step = step_rows // tile
    S = stats_ref.shape[-2]
    NGS = out_ref.shape[0] // n_trees
    Wp = out_ref.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (NGS, tile), 0)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_LANES, tile), 0)
    one_compare = n_bins % 8 == 0

    @pl.when(t == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    def one_tile(c, carry):
        rows = pl.ds(pl.multiple_of(c * tile, tile), tile)
        # Inactive/padded rows carry rel = -1 and rows of other node
        # groups fall outside [0, NGS): neither matches any accumulator
        # row.
        parts = []
        for k in range(n_trees):
            first = (rel_ref[k:k + 1, rows] - g * (NGS // S)) * S  # (1, tile)
            At = jnp.zeros((NGS, tile), jnp.float32)
            for s in range(S):
                stat = (stats_ref[s:s + 1, rows]
                        if len(stats_ref.shape) == 2
                        else stats_ref[k, s:s + 1, rows])
                At = jnp.where(row == first + s, stat, At)
            parts.append(At)
        At = parts[0] if n_trees == 1 else jnp.concatenate(parts, axis=0)
        At = At.astype(operand_dtype)                    # (T·NGS, tile)

        codes = codes_ref[:, rows].astype(jnp.int32)          # (d, tile)
        if not one_compare:
            col = jnp.where(
                codes < n_bins,
                codes + n_bins * jax.lax.broadcasted_iota(
                    jnp.int32, (d, tile), 0),
                -1)
        for lo in range(0, Wp, _LANES):
            runs = _group_runs(lo, n_bins, d)
            if one_compare:
                # Sublane a + i of a run is bin b + i of its feature: a
                # hit where code + (a - b) equals the sublane's own
                # index. A code ≥ n_bins would need a sublane past its
                # run's end.
                want = [jnp.full((nr, tile), -1, jnp.int32) if f is None
                        else jnp.broadcast_to(codes[f:f + 1, :] + (a - b),
                                              (nr, tile))
                        for f, a, b, nr in runs]
                hit = (want[0] if len(want) == 1
                       else jnp.concatenate(want, axis=0)) == sub
            else:
                hit = None
                for f in (f for f, *_ in runs if f is not None):
                    m = col[f:f + 1, :] == sub + lo
                    hit = m if hit is None else hit | m
            ohT = jnp.where(hit, 1.0, 0.0).astype(operand_dtype)
            out_ref[:, lo:lo + _LANES] += jax.lax.dot_general(
                At, ohT, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        return carry

    # The last step ends with the table, not with its block.
    jax.lax.fori_loop(0, jnp.minimum(per_step, n_tiles - t * per_step),
                      one_tile, 0)


def _hist_pallas(codes_T, stats_T, rel, active, *, n_nodes, n_bins, tile,
                 operand_dtype):
    """The histogram ``pallas_call``. One tree: stats_T (S, n), rel and
    active (n,), returns (n_nodes·S, d·n_bins) f32 — the call a single
    tree has always made (``tree_hist``). T trees that share the bin
    matrix codes_T (d, n): stats_T (T, S, n), rel and active (T, n),
    returns (T, n_nodes·S, d·n_bins) — the trees ride the same one-hot
    as further matmul rows (``tree_hist_stacked``). Each takes its
    operands in the shape the caller holds them: a reshape of an
    11M-row array is a copy in HBM (a lone tree's (1, S, n)) or minutes
    of compile (a batch flattened to (T·S, n))."""
    d, n = codes_T.shape
    stacked = stats_T.ndim == 3
    T = stats_T.shape[0] if stacked else 1
    S = stats_T.shape[-2]
    n_pad = -(-n // tile) * tile
    # Padded rows carry zero stats (callers pad stats with zeros) and an
    # inactive node id, so their contribution is an exact 0.
    rel = _row_vec(jnp.where(active, rel, -1), n_pad, -1)
    NG = _tree_node_groups(n_nodes, T * S, d, n_bins)
    G = n_nodes // NG
    # The accumulator's lane width rounds up to whole 128-lane groups so
    # every in-kernel store is aligned; the group axis leads so a block
    # always spans the array's full trailing dims, whatever T·NG·S is.
    Wp = -(-d * n_bins // _LANES) * _LANES
    n_tiles = n_pad // tile
    # A row's operand bytes: d codes, and per tree S f32 stats + a node id.
    step = tile * _tiles_per_step(
        tile * (d * codes_T.dtype.itemsize + 4 * T * (S + 1)), n_tiles)
    out = pl.pallas_call(
        partial(_tree_hist_kernel, n_bins=n_bins, n_trees=T,
                operand_dtype=operand_dtype, tile=tile, n_tiles=n_tiles),
        grid=(G, -(-n_pad // step)),
        in_specs=[
            pl.BlockSpec((d, step), lambda g, t: (0, t)),
            (pl.BlockSpec((T, S, step), lambda g, t: (0, 0, t)) if stacked
             else pl.BlockSpec((S, step), lambda g, t: (0, t))),
            pl.BlockSpec((T, step), lambda g, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((None, T * NG * S, Wp),
                               lambda g, t: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((G, T * NG * S, Wp), jnp.float32),
        interpret=_interpret(),
        name="tree_hist_stacked" if stacked else "tree_hist",
    )(_pad_lanes(codes_T, n_pad), _pad_lanes(stats_T, n_pad), rel)
    if not stacked:
        return out.reshape(n_nodes * S, Wp)[:, :d * n_bins]
    # Block g holds tree k's nodes [g·NG, (g+1)·NG) at rows k·NG·S…
    out = out.reshape(G, T, NG * S, Wp).transpose(1, 0, 2, 3)
    return out.reshape(T, n_nodes * S, Wp)[:, :, :d * n_bins]


def _trees_per_call(n_nodes: int, n_stats: int, d: int, n_bins: int) -> int:
    """How many trees of a batch one stacked call takes: as many as the
    accumulator budget holds at the node group ONE tree gets — a wider
    stack at a narrower node group would re-stream the rows once per
    further group and could cost more passes than tree-at-a-time."""
    ng = _tree_node_groups(n_nodes, n_stats, d, n_bins)
    return max(1, _TREE_ACC_BYTES // (ng * n_stats * d * n_bins * 4))


def _hist_call(codes_T, stats_T, rel, active, *, n_nodes, n_bins, tile,
               operand_dtype):
    """Shared call for tree_histogram / tree_leaf_stats: one tree's flat
    (n_nodes·S, d·n_bins) f32 histogram, with the batching rule that
    decides — from which operands carry the batch axis — what a ``vmap``
    over it (rf's tree batch) compiles to."""
    plain = partial(_hist_pallas, n_nodes=n_nodes, n_bins=n_bins, tile=tile,
                    operand_dtype=operand_dtype)
    call = jax.custom_batching.custom_vmap(plain)

    @call.def_vmap
    def _rule(axis_size, in_batched, codes_T, stats_T, rel, active):
        d, S = codes_T.shape[-2], stats_T.shape[-2]
        step = 1 if in_batched[0] else min(
            axis_size, _trees_per_call(n_nodes, S, d, n_bins))
        if step == 1:
            # Nothing to share — every member has bin codes of its own
            # (the leaf statistics, whose "codes" are the tree's
            # assignment; a population's members) — or no room for a
            # second tree's accumulator: the batch is a grid axis.
            axes = [0 if b else None for b in in_batched]
            return jax.vmap(plain, in_axes=axes)(
                codes_T, stats_T, rel, active), True
        # One bin matrix under the whole batch: its one-hot is built once
        # a row tile and the members' stats rows stack on the matmul.
        stats_T, rel, active = (
            a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
            for a, b in zip((stats_T, rel, active), in_batched[1:]))
        outs = [plain(codes_T, stats_T[lo:lo + step], rel[lo:lo + step],
                      active[lo:lo + step])
                for lo in range(0, axis_size, step)]
        return (outs[0] if len(outs) == 1 else jnp.concatenate(outs)), True

    return call(codes_T, stats_T, rel, active)


def tree_histogram(codes_T, stats_T, rel, active, *, n_nodes, n_bins,
                   tile, operand_dtype=jnp.float32):
    """Per-level (node, feature, bin, stat) histogram over the local
    shard rows — the fused replacement for models/trees.py's
    ``hist_block`` contraction scan.

    codes_T: (d, n) uint8 bin codes, TRANSPOSED so rows sit in lanes
    like every other per-row operand; stats_T: (S, n) f32 per-row
    stats; rel: (n,) int32 node id relative to the level offset;
    active: (n,) bool. Returns (n_nodes, d, n_bins, S) f32 — exactly the
    oracle's reshape/transpose of the contraction.
    """
    S = stats_T.shape[0]
    d = codes_T.shape[0]
    out = _hist_call(codes_T, stats_T, rel, active, n_nodes=n_nodes,
                     n_bins=n_bins, tile=tile, operand_dtype=operand_dtype)
    return out.reshape(n_nodes, S, d, n_bins).transpose(0, 2, 3, 1)


def tree_leaf_stats(assign, stats_T, *, n_nodes, tile,
                    operand_dtype=jnp.float32):
    """Per-leaf sufficient statistics — ``leaf_block`` is structurally
    the histogram kernel with one synthetic feature whose "bin code" is
    the row's node assignment and a single always-active node group.
    Returns (S, n_nodes) f32 (callers transpose + psum)."""
    n = assign.shape[0]
    return _hist_call(assign.astype(jnp.int32).reshape(1, n), stats_T,
                      jnp.zeros((n,), jnp.int32), jnp.ones((n,), bool),
                      n_nodes=1, n_bins=n_nodes, tile=tile,
                      operand_dtype=operand_dtype)    # (S, n_nodes)


def _sel_small(table_col, oh):
    """In-VMEM ``table[idx]`` via the one-hot mask ``oh`` (M, tile) over
    the (M, 1) int32 table column — the kernel-side analogue of
    models/trees.py `_sel_table`. Returns the (1, tile) looked-up row."""
    return jnp.sum(jnp.where(oh, table_col, 0), axis=0, keepdims=True)


def _node_tables(*cols) -> jax.Array:
    """Per-node arrays packed as the (M, len(cols)) int32 block the
    routing/descent kernels keep resident in VMEM."""
    return jnp.stack([c.astype(jnp.int32) for c in cols], axis=1)


def _tree_route_kernel(codes_ref, rel_ref, asg_ref, tbl_ref, out_ref):
    """One row tile of the per-level routing pass: node-table lookups
    (feature, threshold, did-split) and the child-assignment update,
    fused into a single VPU pass over lane-dense (·, tile) values. tbl
    packs [best_f, best_t, split] as (NL, 3) int32; inactive rows carry
    rel = -1, match no node and so keep their assignment."""
    d, tile = codes_ref.shape
    NL = tbl_ref.shape[0]
    node_oh = rel_ref[:] == jax.lax.broadcasted_iota(
        jnp.int32, (NL, tile), 0)                          # (NL, tile)
    rf = _sel_small(tbl_ref[:, 0:1], node_oh)              # (1, tile)
    rt = _sel_small(tbl_ref[:, 1:2], node_oh)
    rs = _sel_small(tbl_ref[:, 2:3], node_oh) != 0
    feat_oh = rf == jax.lax.broadcasted_iota(jnp.int32, (d, tile), 0)
    val = jnp.sum(jnp.where(feat_oh, codes_ref[:].astype(jnp.int32), 0),
                  axis=0, keepdims=True)
    go_right = (val > rt).astype(jnp.int32)
    asg = asg_ref[:]
    out_ref[:] = jnp.where(rs, 2 * asg + 1 + go_right, asg)


def tree_route_level(codes_T, rel, active, assign, best_f, best_t, split,
                     *, tile):
    """Route split-node rows to their children for one level — the fused
    replacement for ``route_block``. Returns the new (n,) int32 node
    assignment (leaf rows keep theirs)."""
    d, n = codes_T.shape
    NL = best_f.shape[0]
    n_pad = -(-n // tile) * tile
    row_spec = pl.BlockSpec((1, tile), lambda t: (0, t))
    out = pl.pallas_call(
        _tree_route_kernel,
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec((d, tile), lambda t: (0, t)),
            row_spec,
            row_spec,
            pl.BlockSpec((NL, 3), lambda t: (0, 0)),
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=_interpret(),
        name="tree_route",
    )(_pad_lanes(codes_T, n_pad),
      _row_vec(jnp.where(active, rel, -1), n_pad, -1),
      _row_vec(assign, n_pad), _node_tables(best_f, best_t, split))
    return out[0, :n]


def _tree_descend_kernel(codes_ref, tbl_ref, out_ref, *, max_depth):
    """One row tile of full-tree descent: all ``max_depth`` levels of
    node-table lookups run over the VMEM-resident tile in one pass. tbl
    packs [feat, thr, internal] as (M, 3) int32."""
    d, tile = codes_ref.shape
    M = tbl_ref.shape[0]
    codes = codes_ref[:].astype(jnp.int32)
    feat_iota = jax.lax.broadcasted_iota(jnp.int32, (d, tile), 0)
    node_iota = jax.lax.broadcasted_iota(jnp.int32, (M, tile), 0)
    a = jnp.zeros((1, tile), jnp.int32)
    for _ in range(max_depth):
        node_oh = a == node_iota
        f = _sel_small(tbl_ref[:, 0:1], node_oh)
        t = _sel_small(tbl_ref[:, 1:2], node_oh)
        internal = _sel_small(tbl_ref[:, 2:3], node_oh) != 0
        val = jnp.sum(jnp.where(f == feat_iota, codes, 0), axis=0,
                      keepdims=True)
        a = jnp.where(internal, 2 * a + 1 + (val > t).astype(jnp.int32), a)
    out_ref[:] = a


def tree_descend(codes_T, feat, thr, internal, *, max_depth,
                 tile=TREE_ROUTE_TILE):
    """Leaf assignment for binned rows — the fused replacement for
    models/trees.py ``_descend``'s blocked per-level select loops.
    Returns (n,) int32 leaf node ids (bit-identical to the oracle: all
    arithmetic is integer)."""
    d, n = codes_T.shape
    M = feat.shape[0]
    n_pad = -(-n // tile) * tile
    out = pl.pallas_call(
        partial(_tree_descend_kernel, max_depth=max_depth),
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec((d, tile), lambda t: (0, t)),
            pl.BlockSpec((M, 3), lambda t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=_interpret(),
        name="tree_descend",
    )(_pad_lanes(codes_T, n_pad), _node_tables(feat, thr, internal))
    return out[0, :n]


# ---------------------------------------------------------------------------
# Attention over the chosen keys (models/transformer.py:_chosen_attention)
# ---------------------------------------------------------------------------

#: Key blocks the attention kernels may walk, largest first: a larger
#: block leaves fewer grid steps to skip past the causal edge.
_ATTN_KEY_BLOCKS = (512, 256, 128)
#: What a rematerialised caller of ``chosen_attention`` may keep of a
#: query block's forward for its backward (``jax.checkpoint_policies
#: .save_only_these_names``): the block's output and its log-sum-exp.
ATTN_RESIDUALS = ("chosen_attn_o", "chosen_attn_lse")
#: Scoped VMEM the attention kernels may ask for: half the 128 MiB of a
#: v5e core, the smallest VMEM of the chips this runs on.
_ATTN_VMEM_BUDGET = 64 << 20


def _attn_vmem_bytes(RC: int, C: int, D: int, tk: int) -> int:
    """VMEM the backward kernel, the largest of the three, is given for
    ``RC`` stacked query rows and a key block of ``tk``: every operand
    and result block twice (the pipeline's two buffers), the (R·C, 1)
    log-sum-exp and delta columns padded to a lane tile, the four
    (R·C, tk) scratch tiles (scores and ``do·v`` float32, ``p`` and
    ``ds`` as MXU operands) and the operands' bfloat16 copies. An upper
    bound: the compiler for the described v5e takes about half (8 MiB of
    these 13.9 in the benchmark's cell, 53 of 94.8 at R·C 8,192 and 512
    keys)."""
    rows = RC * (2 * 3 * D * 4         # q, do, dq
                 + 2 * 2 * _LANES * 4  # lse, delta
                 + 2 * D * 2           # q, do as operands
                 + tk * (4 + 4 + 2 + 2))
    keys = tk * (2 * 4 * D * 4         # k, v, dk, dv
                 + 2 * D * 2           # k, v as operands
                 + 2 * C)              # the int8 mask tile
    return rows + keys


def chosen_attn_key_block(T: int, C: int, D: int, R: int) -> int:
    """The key block the attention kernels walk for ``C`` queries of a
    ``T``-token row at head width ``D`` with ``R`` query heads a
    key-value head, or 0 where the plain body runs: heads are a lane
    tile wide, a query block is whole int8 mask tiles (32 sublanes), and
    the key block is the largest that divides the row and whose working
    set (``_attn_vmem_bytes``) fits ``_ATTN_VMEM_BUDGET``: 512 to
    R·C 4,096 at heads of 128, 128 to 8,192, none beyond."""
    if D % _LANES or C % 32 or T % C:
        return 0
    return next((b for b in _ATTN_KEY_BLOCKS if T % b == 0
                 and _attn_vmem_bytes(R * C, C, D, b) <= _ATTN_VMEM_BUDGET), 0)


def mxu_operand_dtype():
    """What the MXU takes of a float32 product at default precision on
    the chip; off it the plain body's products are float32 too. The
    attention kernels and the grouped products round their operands to
    it."""
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32


def _nt_dot(a, b):
    """``a @ b.T`` with float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn_dot(a, b):
    """``a.T @ b`` with float32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _across(x, width: int):
    """A (rows, 128) value whose lanes all hold the row's number, as
    (rows, width), ``width`` a multiple of 128."""
    reps = width // _LANES
    return x if reps == 1 else jnp.concatenate([x] * reps, axis=1)


def _bias_tile(mask_ref):
    """(C, block) float32: 0 where the key was chosen, -inf elsewhere."""
    return jnp.where(mask_ref[:].astype(jnp.int32) != 0, 0.0, -jnp.inf)


def _chosen_fwd_kernel(last_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                       lse_ref, m_sc, l_sc, acc_sc, s_sc, p_sc, *, scale, dt):
    """One (key-value head g, key block kb) cell of a query block's
    forward: the online softmax of flash attention over the keys the
    mask keeps. The group's R query heads are stacked on the matmul's
    rows (q_ref (R·C, D)); the running maximum, sum and the (R·C, D)
    accumulator stay in VMEM while the key blocks stream past (kb is
    the innermost grid dimension), and a (R·C, block) score tile never
    leaves it. Maximum and sum are kept across a vreg's 128 lanes, so
    they meet scores and accumulator without a lane broadcast (14%
    fewer bundles a tile than as columns). The softmax takes the tile a head (C rows) at a time, as
    straight-line code: the compiler's schedule for the described v5e
    overlaps one head's lane reductions with the next one's arithmetic
    (18% fewer bundles a tile than a loop over the heads; 47% in the
    second walk). ``last_ref[0]`` is the last key block that holds a key
    at or before the block's last query: the blocks past it are not
    computed, and the index maps fetch nothing for them. A query with
    no chosen key in a block (or in none so far: its maximum is still
    -inf) gets exactly 0 from it."""
    kb = pl.program_id(1)
    C = mask_ref.shape[0]
    reps = q_ref.shape[0] // C

    @pl.when(kb == 0)
    def _init():
        m_sc[:] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[:] = jnp.zeros_like(l_sc)
        acc_sc[:] = jnp.zeros_like(acc_sc)

    @pl.when(kb <= last_ref[0])
    def _step():
        s_sc[:] = _nt_dot(q_ref[:].astype(dt), k_ref[:].astype(dt)) * scale
        bias = _bias_tile(mask_ref)

        for r in range(reps):
            rows = slice(r * C, (r + 1) * C)
            s = s_sc[rows, :] + bias
            m_prev = m_sc[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            alpha = jnp.exp(m_prev - m_safe)
            p = jnp.exp(s - _across(m_safe, s.shape[1]))
            l_sc[rows, :] = alpha * l_sc[rows, :] + jnp.sum(
                p, axis=1, keepdims=True)
            acc_sc[rows, :] = _across(alpha, acc_sc.shape[1]) * acc_sc[rows, :]
            m_sc[rows, :] = m_new
            p_sc[rows, :] = p.astype(dt)
        acc_sc[:] += jnp.dot(p_sc[:], v_ref[:].astype(dt),
                             preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _finish():
        o_ref[:] = acc_sc[:] / _across(l_sc[:], acc_sc.shape[1])
        lse_ref[:] = (m_sc[:] + jnp.log(l_sc[:]))[:, :1]


def _chosen_probs_kernel(last_ref, q_ref, k_ref, mask_ref, lse_ref, out_ref,
                         s_sc, *, scale, dt):
    """One (key block kb, key-value head g) cell of the second walk: the
    probabilities ``exp(s - lse)`` of the block's keys, summed over this
    shard's heads into the (C, block) result, which stays in VMEM while
    the heads pass (g is the innermost grid dimension)."""
    kb, g = pl.program_id(0), pl.program_id(1)
    C = mask_ref.shape[0]
    reps = q_ref.shape[0] // C

    @pl.when(g == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(kb <= last_ref[0])
    def _step():
        s_sc[:] = _nt_dot(q_ref[:].astype(dt), k_ref[:].astype(dt)) * scale
        bias = _bias_tile(mask_ref)

        total = jnp.zeros_like(out_ref)
        for r in range(reps):
            rows = slice(r * C, (r + 1) * C)
            total += jnp.exp(s_sc[rows, :] + bias - lse_ref[rows, :])
        out_ref[:] += total


def _chosen_bwd_kernel(last_ref, q_ref, do_ref, lse_ref, delta_ref, k_ref,
                       v_ref, mask_ref, dq_ref, dk_ref, dv_ref, s_sc, dp_sc,
                       p_sc, ds_sc, *, scale, dt):
    """One (key-value head g, key block kb) cell of a query block's
    backward: scores and probabilities recomputed from the log-sum-exp,
    ``ds = p * (do·v - delta) * scale``; ``dq`` (R·C, D) accumulates in
    VMEM over the key blocks, the block's ``dk`` and ``dv`` (block, D)
    are written once: zeros past the causal edge."""
    kb = pl.program_id(1)
    C = mask_ref.shape[0]
    reps = q_ref.shape[0] // C

    @pl.when(kb == 0)
    def _init():
        dq_ref[:] = jnp.zeros_like(dq_ref)

    @pl.when(kb > last_ref[0])
    def _skip():
        dk_ref[:] = jnp.zeros_like(dk_ref)
        dv_ref[:] = jnp.zeros_like(dv_ref)

    @pl.when(kb <= last_ref[0])
    def _step():
        q, do = q_ref[:].astype(dt), do_ref[:].astype(dt)
        k, v = k_ref[:].astype(dt), v_ref[:].astype(dt)
        s_sc[:] = _nt_dot(q, k) * scale
        dp_sc[:] = _nt_dot(do, v)
        bias = _bias_tile(mask_ref)

        for r in range(reps):
            rows = slice(r * C, (r + 1) * C)
            p = jnp.exp(s_sc[rows, :] + bias - lse_ref[rows, :])
            ds = p * (dp_sc[rows, :] - delta_ref[rows, :]) * scale
            p_sc[rows, :] = p.astype(dt)
            ds_sc[rows, :] = ds.astype(dt)
        dv_ref[:] = _tn_dot(p_sc[:], do)
        dk_ref[:] = _tn_dot(ds_sc[:], q)
        dq_ref[:] += jnp.dot(ds_sc[:], k, preferred_element_type=jnp.float32)


def _attn_call(kernel, name, last, args, *, grid, in_specs, out_specs,
               out_shape, scratch, vmem):
    """A ``pallas_call`` of the attention kernels: ``last`` (1,) int32,
    the last key block to compute, rides scalar prefetch, so the index
    maps clamp the key block to it and a skipped step re-names the block
    already in VMEM: no DMA. ``vmem``: the scoped VMEM asked for.
    Results vary over the mesh axes ``args[0]`` (the queries) varies
    over."""
    vma = jax.typeof(args[0]).vma
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)
                   for shape in out_shape],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=_interpret(),
        name=name,
    )(last, *args)


def _attn_specs(RC, D, C, tk, heads_first: bool):
    """``(per_head(width), keys, mask)`` block specs for a grid of
    (heads, key blocks) or, not ``heads_first``, (key blocks, heads)."""
    def at(fn):
        if heads_first:
            return lambda g, kb, last: fn(g, jnp.minimum(kb, last[0]))
        return lambda kb, g, last: fn(g, jnp.minimum(kb, last[0]))

    def per_head(width):
        return pl.BlockSpec((None, RC, width), at(lambda g, kb: (g, 0, 0)))

    return (per_head, pl.BlockSpec((tk, D), at(lambda g, kb: (kb, g))),
            pl.BlockSpec((C, tk), at(lambda g, kb: (0, kb))))


def _attn_dims(qg, mask):
    """``(G, R·C, D, C, T, key block, VMEM to ask for)`` of one query
    block's operands."""
    G, RC, D = qg.shape
    C, T = mask.shape
    tk = chosen_attn_key_block(T, C, D, RC // C)
    return G, RC, D, C, T, tk, _attn_vmem_bytes(RC, C, D, tk)


def _chosen_attn_forward(qg, k2, v2, mask, last):
    """``(o (G, R·C, D), lse (G, R·C, 1))`` of one query block."""
    G, RC, D, C, T, tk, vmem = _attn_dims(qg, mask)
    dt = mxu_operand_dtype()
    per_head, keys, msk = _attn_specs(RC, D, C, tk, True)
    return _attn_call(
        partial(_chosen_fwd_kernel, scale=D ** -0.5, dt=dt),
        "chosen_attn_fwd", last, (qg, k2, v2, mask),
        grid=(G, T // tk), in_specs=[per_head(D), keys, keys, msk],
        out_specs=[per_head(D), per_head(1)],
        out_shape=[(G, RC, D), (G, RC, 1)],
        scratch=[pltpu.VMEM((RC, _LANES), jnp.float32),
                 pltpu.VMEM((RC, _LANES), jnp.float32),
                 pltpu.VMEM((RC, D), jnp.float32),
                 pltpu.VMEM((RC, tk), jnp.float32),
                 pltpu.VMEM((RC, tk), dt)],
        vmem=vmem)


def _chosen_attn_probs(qg, k2, mask, lse, last):
    """``Σ_heads p`` (C, T) of one query block, from the log-sum-exp."""
    G, RC, D, C, T, tk, vmem = _attn_dims(qg, mask)
    per_head, keys, msk = _attn_specs(RC, D, C, tk, False)
    (probs,) = _attn_call(
        partial(_chosen_probs_kernel, scale=D ** -0.5,
                dt=mxu_operand_dtype()),
        "chosen_attn_probs", last, (qg, k2, mask, lse),
        grid=(T // tk, G), in_specs=[per_head(D), keys, msk, per_head(1)],
        out_specs=[pl.BlockSpec((C, tk), lambda kb, g, last: (0, kb))],
        out_shape=[(C, T)],
        scratch=[pltpu.VMEM((RC, tk), jnp.float32)], vmem=vmem)
    return probs


def _chosen_attn_backward(qg, k2, v2, mask, last, lse, delta, do):
    """``(dq (G, R·C, D), dk, dv (T, G·D))`` of one query block."""
    G, RC, D, C, T, tk, vmem = _attn_dims(qg, mask)
    dt = mxu_operand_dtype()
    per_head, keys, msk = _attn_specs(RC, D, C, tk, True)
    grads = pl.BlockSpec((tk, D), lambda g, kb, last: (kb, g))
    return _attn_call(
        partial(_chosen_bwd_kernel, scale=D ** -0.5, dt=dt),
        "chosen_attn_bwd", last, (qg, do, lse, delta, k2, v2, mask),
        grid=(G, T // tk),
        in_specs=[per_head(D), per_head(D), per_head(1), per_head(1),
                  keys, keys, msk],
        out_specs=[per_head(D), grads, grads],
        out_shape=[(G, RC, D), (T, G * D), (T, G * D)],
        scratch=[pltpu.VMEM((RC, tk), jnp.float32),
                 pltpu.VMEM((RC, tk), jnp.float32),
                 pltpu.VMEM((RC, tk), dt),
                 pltpu.VMEM((RC, tk), dt)],
        vmem=vmem)


def _chosen_attn_fwd(qg, k2, v2, mask, last):
    o, lse = _chosen_attn_forward(qg, k2, v2, mask, last)
    # Named, so a rematerialised caller may keep them (ATTN_RESIDUALS)
    # and not run the forward kernel again before the backward. The
    # log-sum-exp is kept lane-dense: a (.., 1) column pads to 128 lanes
    # in HBM.
    o = checkpoint_name(o, ATTN_RESIDUALS[0])
    lse = checkpoint_name(lse[..., 0], ATTN_RESIDUALS[1])
    return ((o, _chosen_attn_probs(qg, k2, mask, lse[..., None], last)),
            (qg, k2, v2, mask, last, o, lse))


@jax.custom_vjp
def _chosen_attn(qg, k2, v2, mask, last):
    """``(o (G, R·C, D), Σ_heads p (C, T))`` of one query block."""
    return _chosen_attn_fwd(qg, k2, v2, mask, last)[0]


def _chosen_attn_bwd(res, cts):
    qg, k2, v2, mask, last, o, lse = res
    do = cts[0]        # the probabilities are read detached: no cotangent
    delta = (o * do).sum(-1, keepdims=True)
    dq, dk, dv = _chosen_attn_backward(qg, k2, v2, mask, last,
                                       lse[..., None], delta, do)
    return dq, dk, dv, None, None


_chosen_attn.defvjp(_chosen_attn_fwd, _chosen_attn_bwd)


def chosen_attention(q, k, v, chosen, block):
    """Causal grouped-query attention of one block of ``C`` queries over
    the keys ``chosen`` keeps — the fused replacement for the score /
    softmax / probability-times-value lines of
    ``models/transformer.py:_chosen_attention``'s plain body, for shapes
    ``chosen_attn_key_block`` admits.

    q (C, H, D); k, v (T, G·D), float32, the key-value heads side by
    side in lanes (a (T, G, D) array pads G to whole sublane tiles in
    HBM, and flattening it is a copy: the caller flattens once a row,
    not once a block); chosen (C, T) bool, which holds the causal mask
    (every query keeps at least one key); ``block`` the query block's
    index in the row (int32 scalar): key blocks that start past query
    ``(block + 1)·C - 1`` are skipped. Returns ``(o (C, H, D), Σ_heads p
    (C, T))`` in float32; differentiable in q, k and v (the
    probabilities are for use under ``stop_gradient``). Operands are
    rounded to bfloat16 only as the MXU takes them, as the default
    precision does; scores, maxima, sums, the log-sum-exp and every
    accumulator are float32."""
    C, H, D = q.shape
    T, G = k.shape[0], k.shape[1] // D
    R = H // G
    tk = chosen_attn_key_block(T, C, D, R)
    last = (((block + 1) * C - 1) // tk).astype(jnp.int32).reshape(1)
    qg = q.reshape(C, G, R, D).transpose(1, 2, 0, 3).reshape(G, R * C, D)
    o, probs = _chosen_attn(qg, k, v, chosen.astype(jnp.int8), last)
    return (o.reshape(G, R, C, D).transpose(2, 0, 1, 3).reshape(C, H, D),
            probs)


# --- the delta rule's chunk transform ---------------------------------------

#: Longest chunk ``delta_transform`` takes: a (C, C, 128) float32 block
#: in and one out, each double-buffered, are 8 MiB at C 64, inside the
#: 16 MiB of scoped VMEM a call has without asking.
_DELTA_MAX_CHUNK = 64


def delta_transform_fits(C: int, on_mesh: bool = False) -> bool:
    """Whether ``delta_transform`` takes chunks of ``C`` tokens: whole
    sublane groups of 8 rows, at most ``_DELTA_MAX_CHUNK``. Off the TPU
    the kernel runs in interpret mode, which cannot type a broadcast of
    values that vary over mesh axes: an operand ``on_mesh`` (inside
    ``shard_map``) is the plain form's there."""
    return (C % 8 == 0 and 8 <= C <= _DELTA_MAX_CHUNK
            and not (on_mesh and _interpret()))


def _delta_transform_kernel(a_ref, t_ref):
    """``T = (I + A)^-1`` of 128 unit-lower-triangular systems at once,
    one system a lane: ``a_ref``, ``t_ref`` (C, C, 128), row, column,
    system. Blocked forward substitution over groups of 8 rows, all of
    it float32 on the VPU: ``T`` starts as ``I``; a group's rows are
    finished among themselves (row ``i`` less ``A[i, j] * row_j`` for
    the group's earlier ``j``), then taken out of every later row. Row
    ``j`` of ``T`` is zero past column ``j``, so a group's rows are
    read and updated only as far as the group's last column. Entries of
    ``A`` on or above the diagonal are never read."""
    C, _, L = a_ref.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C, L), 1)
    t_ref[...] = (row == col).astype(jnp.float32)

    def less(i, first, count, width, live=None):
        """Row ``i`` less ``A[i, j] * row_j`` for the ``count`` rows from
        ``first`` (static), the first ``live`` of them where given (a
        traced count), over the first ``width`` sublane groups."""
        acc = [t_ref[i, 8 * p:8 * p + 8, :] for p in range(width)]
        for n, j in enumerate(range(first, first + count)):
            a = a_ref[i, j:j + 1, :]
            if live is not None:
                a = jnp.where(n < live, a, 0.0)
            a = jnp.broadcast_to(a, (8, L))
            for p in range(width):
                acc[p] = acc[p] - a * t_ref[j, 8 * p:8 * p + 8, :]
        for p in range(width):
            t_ref[i, 8 * p:8 * p + 8, :] = acc[p]

    for g in range(C // 8):
        def own(r, carry, g=g):        # rows 1..7 of the group, in turn
            less(8 * g + r, 8 * g, 7, g + 1, live=r)
            return carry

        def later(i, carry, g=g):
            less(i, 8 * g, 8, g + 1)
            return carry

        jax.lax.fori_loop(1, 8, own, 0)
        jax.lax.fori_loop(8 * (g + 1), C, later, 0)


def delta_transform(A):
    """``(I + A)^-1`` of strictly lower-triangular ``A`` (..., C, C),
    float32, for chunk lengths ``delta_transform_fits`` admits: the
    systems (a 256-token block of Olmo-Hybrid's mixer has 60 of 64x64)
    ride the lanes of ONE kernel call, which replaces the fifteen
    substitution steps, four batched products and the slicing between
    them that the plain recursion (``models/transformer.py:
    _block_inverse``, this kernel's oracle) costs XLA a block and pass.
    Not differentiable here: the caller holds the ``custom_vjp``."""
    lead, C = A.shape[:-2], A.shape[-1]
    N = A.size // (C * C)
    Np = -(-N // _LANES) * _LANES
    a = jnp.pad(jnp.moveaxis(A.reshape(N, C, C), 0, -1),
                [(0, 0), (0, 0), (0, Np - N)])
    spec = pl.BlockSpec((C, C, _LANES), lambda n: (0, 0, n))
    t = pl.pallas_call(
        _delta_transform_kernel,
        grid=(Np // _LANES,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((C, C, Np), jnp.float32,
                                       vma=jax.typeof(A).vma),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
        name="delta_transform",
    )(a)
    return jnp.moveaxis(t[:, :, :N], -1, 0).reshape(lead + (C, C))


# --- the expert layer's grouped products ------------------------------------

#: Rows a tile of the grouped products holds. A group's rows are computed
#: a tile at a time; a tile that two groups share is visited once by each,
#: each visit storing only its own group's rows.
GROUP_TILE = 128

#: Scoped VMEM a grouped product asks for, and the part of it its blocks
#: (double-buffered operands and output, the accumulator) may take.
_GROUP_VMEM = 48 * 2**20
_GROUP_BLOCKS = 40 * 2**20


def grouped_fits(on_mesh: bool = False) -> bool:
    """Whether the expert layer's routed products run as grouped
    products. Off the TPU, inside ``shard_map``, the dense form runs:
    there it is the oracle the mesh tests hold the layer to (the
    kernels would run in interpret mode; tests/test_tx_moe_grouped.py
    forces them on a mesh against it)."""
    return not (on_mesh and _interpret())


def _widths(dim: int) -> list:
    """Block widths along a lane dimension of ``dim``: the whole of it,
    then the multiples of 128 that divide it, widest first."""
    return [dim] + [w for w in range(dim - dim % _LANES, 0, -_LANES)
                    if w != dim and dim % w == 0]


def _varying(*arrays) -> frozenset:
    """The mesh axes any of the arrays varies over (inside
    ``shard_map``; empty outside it)."""
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))


def _group_schedule(sizes, m: int, tm: int, visit_empty: bool):
    """Megablox's tile schedule for rows sorted by group (``jax.
    experimental.pallas.ops.tpu.megablox.gmm.make_group_metadata``, in
    fewer operations): grid step ``i`` works on row tile ``tile[i]``
    for group ``group[i]``; a group's steps run from the tile its first
    row lies in to the tile its last row lies in, so a tile two groups
    share is visited once by each, consecutively. ``visit_empty``: an
    empty group gets one step too (the weight gradient zeroes it).
    Returns ``((offsets (G + 1,), group, tile), steps)``: ``steps``
    sizes the grid at run time, and tiles past the groups' rows are
    not visited."""
    G = sizes.shape[0]
    end = jnp.cumsum(sizes)
    first = (end - sizes) // tm
    tiles = jnp.where(sizes > 0, (end + tm - 1) // tm - first,
                      int(visit_empty))
    done = jnp.cumsum(tiles)                       # steps through group g
    i = jnp.arange(m // tm + G - 1)
    group = jnp.minimum((done[None, :] <= i[:, None]).sum(1), G - 1)
    tile = jnp.clip(first[group] + i - (done[group] - tiles[group]), 0,
                    m // tm - 1)
    offsets = jnp.concatenate([jnp.zeros(1, end.dtype), end])
    return (offsets.astype(jnp.int32), group.astype(jnp.int32),
            tile.astype(jnp.int32)), done[-1]


def grouped_tile_rows(sizes, tm: int = 0):
    """Rows of the tiles ``grouped_matmul`` visits for groups of
    ``sizes`` (..., G) laid one after another (``_group_schedule``):
    over the rows themselves, the padding and straddle overhead of the
    schedule. ``tm`` 0: ``GROUP_TILE``."""
    tm = tm or GROUP_TILE
    end = jnp.cumsum(sizes, -1)
    tiles = (end + tm - 1) // tm - (end - sizes) // tm
    return (jnp.where(sizes > 0, tiles, 0) * tm).sum(-1)


def _gmm_kernel(offsets, gids, mids, x_ref, w_ref, out_ref, *, tm,
                transpose):
    i = pl.program_id(1)
    g = gids[i]
    dims = (((1,), (1,)) if transpose else ((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(x_ref[...], w_ref[...], dims,
                              preferred_element_type=jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0) + mids[i] * tm
    keep = (row >= offsets[g]) & (row < offsets[g + 1])
    out_ref[...] = jnp.where(keep, acc, out_ref[...])


def grouped_matmul(x, w, sizes, *, transpose: bool = False):
    """``out[r] = x[r] @ w[group of r]`` (``w[g].T`` with ``transpose``)
    for rows sorted by group, ``sizes`` (G,) int32 rows a group, in
    order from row 0; x (m, c) and w (G, c, n) (or (G, n, c)) in
    ``mxu_operand_dtype``, ``m`` a multiple of ``GROUP_TILE``; float32
    products. Rows past the groups' are neither read nor written: what
    they hold is undefined. The whole contraction is one block, so a
    group's weight block stays in VMEM over the group's tiles (blocks
    sized for bfloat16 operands, double-buffered)."""
    tm = GROUP_TILE
    m, c = x.shape
    n = w.shape[1] if transpose else w.shape[2]
    fixed = 2 * tm * c * 2
    tn = next((t for t in _widths(n)
               if fixed + 2 * c * t * 2 + 3 * tm * t * 4 <= _GROUP_BLOCKS),
              None)
    if tn is None:
        raise ValueError(f"a grouped product of width {c} x {n} does not "
                         "fit VMEM")
    meta, steps = _group_schedule(sizes, m, tm, visit_empty=False)
    if transpose:
        w_spec = pl.BlockSpec((None, tn, c),
                              lambda j, i, off, gid, mid: (gid[i], j, 0))
    else:
        w_spec = pl.BlockSpec((None, c, tn),
                              lambda j, i, off, gid, mid: (gid[i], 0, j))
    return pl.pallas_call(
        partial(_gmm_kernel, tm=tm, transpose=transpose),
        out_shape=jax.ShapeDtypeStruct(
            (m, n), jnp.float32, vma=_varying(x, w, sizes)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), steps),
            in_specs=[pl.BlockSpec((tm, c),
                                   lambda j, i, off, gid, mid: (mid[i], 0)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, off, gid, mid: (mid[i], j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_GROUP_VMEM),
        interpret=_interpret(),
        name="grouped_mm",
    )(*meta, x, w)


def _tgmm_kernel(offsets, gids, mids, x_ref, g_ref, out_ref, acc_ref, *,
                 tm):
    i = pl.program_id(2)
    last = pl.num_programs(2) - 1
    grp = gids[i]
    start, end = offsets[grp], offsets[grp + 1]

    @pl.when((i == 0) | (gids[jnp.maximum(i - 1, 0)] != grp))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(end > start)
    def _accumulate():
        def rows(ref):
            r = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 0) + mids[i] * tm
            keep = (r >= start) & (r < end)
            return jnp.where(keep, ref[...].astype(jnp.float32),
                             0.0).astype(ref.dtype)
        acc_ref[...] += jax.lax.dot(rows(x_ref).swapaxes(0, 1), rows(g_ref),
                                    preferred_element_type=jnp.float32)

    @pl.when((i == last) | (gids[jnp.minimum(i + 1, last)] != grp))
    def _store():
        out_ref[...] = acc_ref[...]


def grouped_matmul_t(x, g, sizes):
    """``out[e] = x[rows of e].T @ g[rows of e]`` (G, k, n) float32, for
    rows sorted by group as ``grouped_matmul`` takes them; x (m, k), g
    (m, n) in ``mxu_operand_dtype``. A group with no rows gets zeros; rows past the
    groups' are not read."""
    tm = GROUP_TILE
    m, k = x.shape
    n = g.shape[1]
    G = sizes.shape[0]
    fits = [(tk, tn) for tk in _widths(k) for tn in _widths(n)
            if 2 * tm * (tk + tn) * 2 + 3 * tk * tn * 4 <= _GROUP_BLOCKS]
    if not fits:
        raise ValueError(f"a grouped product of width {k} x {n} does not "
                         "fit VMEM")
    tk, tn = max(fits, key=lambda t: t[0] * t[1])
    meta, steps = _group_schedule(sizes, m, tm, visit_empty=True)
    return pl.pallas_call(
        partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct(
            (G, k, n), jnp.float32, vma=_varying(x, g, sizes)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pl.cdiv(n, tn), pl.cdiv(k, tk), steps),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda j, l, i, off, gid, mid: (mid[i], l)),
                      pl.BlockSpec((tm, tn),
                                   lambda j, l, i, off, gid, mid: (mid[i], j))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda j, l, i, off, gid, mid: (gid[i], l, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_GROUP_VMEM),
        interpret=_interpret(),
        name="grouped_mm_t",
    )(*meta, x, g)
