"""Kernel/oracle parity for the fused Pallas tree kernels.

The tree families (dt/rf/gb) route their histogram, routing and descent
hot loops through ops/pallas_kernels.py when ``LO_TPU_TREE_KERNEL`` is
on (the default); the pure-XLA blocked contraction path is kept as the
oracle. Off-TPU the kernels run in interpreter mode, so this whole suite
executes on the tier-1 CPU mesh (8 simulated devices — every fit here is
multi-shard, so the per-level psum reduction is exercised by default).

Parity guarantee pinned here (docs/performance.md):

- dt/rf: bit-identical ``(feat, thr, internal, leaf)`` on ANY shape —
  classification stats are small integers, whose f32 sums are exact
  under any summation order, so different row tilings cannot move a bit.
- gb: bit-identical while a shard's rows fit one kernel row tile (the
  kernel then performs the same single contraction as the oracle, plus
  exact-zero padding rows). Beyond one tile the kernel and oracle sum
  real-valued grad/hess stats in different groupings; last-bit histogram
  differences can legitimately flip argmax split ties, so cross-path
  equality is statistical (accuracy parity), not bitwise.
"""

from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from learningorchestra_tpu.config import Settings  # noqa: E402
from learningorchestra_tpu.models import trees  # noqa: E402
from learningorchestra_tpu.models.registry import get_trainer  # noqa: E402
from learningorchestra_tpu.ops import pallas_kernels as pk  # noqa: E402
from learningorchestra_tpu.parallel.mesh import (  # noqa: E402
    DATA_AXIS, MeshRuntime)

PARAM_KEYS = {"dt": ("feat", "thr", "internal", "leaf"),
              "rf": ("feat", "thr", "internal", "leaf"),
              "gb": ("feat", "thr", "internal", "leaf_val")}


def _runtime(tree_kernel: bool) -> MeshRuntime:
    cfg = Settings()
    cfg.persist = False
    cfg.tree_kernel = tree_kernel
    return MeshRuntime(cfg)


def _blobs(n, d=6, classes=2, seed=0, sep=2.0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(classes, d)) * sep
    y = rng.integers(0, classes, size=n)
    X = (centers[y] + rng.normal(size=(n, d))).astype(np.float32)
    return X, y.astype(np.int32)


def _fit_pair(kind, n, d=6, seed=0, **hp):
    X, y = _blobs(n, d=d, seed=seed)
    mk = get_trainer(kind)(_runtime(True), X, y, 2, **hp)
    mo = get_trainer(kind)(_runtime(False), X, y, 2, **hp)
    return mk, mo, X, y


def _assert_params_bitexact(kind, mk, mo):
    for key in PARAM_KEYS[kind]:
        a = np.asarray(mk.params[key])
        b = np.asarray(mo.params[key])
        np.testing.assert_array_equal(a, b, err_msg=f"{kind}.{key}")


def test_kernel_oracle_parity_smoke():
    """Tier-1 pin: bit-identical fitted params kernel-vs-oracle for all
    three families at an odd row count (wrappers pad the ragged tile
    tail), on the 8-device mesh (per-level psum included)."""
    for kind, n in (("dt", 777), ("rf", 500), ("gb", 700)):
        mk, mo, _, _ = _fit_pair(kind, n, max_depth=3,
                                 **({"n_rounds": 3} if kind == "gb"
                                    else {"n_trees": 4} if kind == "rf"
                                    else {}))
        _assert_params_bitexact(kind, mk, mo)


def test_descend_kernel_parity():
    """The fused descent kernel is bit-identical to the oracle on
    batches above the kernel gate (integer arithmetic end to end) —
    which is what lets the predict statics flip paths per batch shape
    without perturbing a single probability."""
    rng = np.random.default_rng(2)
    n, d, max_depth = pk.TREE_ROUTE_TILE + 37, 6, 5
    M = 2 ** (max_depth + 1) - 1
    B = jnp.asarray(rng.integers(0, 32, (n, d)).astype(np.uint8))
    feat = jnp.asarray(rng.integers(0, d, M).astype(np.int32))
    thr = jnp.asarray(rng.integers(0, 32, M).astype(np.int32))
    internal = jnp.asarray(rng.random(M) < 0.7)
    a_k = np.asarray(pk.tree_descend(B.T, feat, thr, internal,
                                     max_depth=max_depth))
    a_o = np.asarray(trees._descend(B, feat, thr, internal, max_depth,
                                    use_kernel=False))
    assert a_k.shape == (n,)
    np.testing.assert_array_equal(a_k, a_o)


def test_tree_kernel_disabled_via_use_pallas():
    """The master LO_TPU_USE_PALLAS switch also disables the tree
    kernels (and the oracle fit still works)."""
    cfg = Settings()
    cfg.persist = False
    cfg.use_pallas = False
    cfg.tree_kernel = True
    assert trees._use_tree_kernel(MeshRuntime(cfg)) is False


def test_refused_kernel_fails_the_fit(monkeypatch):
    """No quiet fallback: with the flags on (the default), a kernel the
    compiler refuses raises out of the fit — no oracle-path model comes
    back in its place."""
    class Refused(Exception):
        pass

    def refuse(*_a, **_k):
        raise Refused("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(pk, "tree_histogram", refuse)
    rt = _runtime(True)
    assert trees._use_tree_kernel(rt) is True
    X, y = _blobs(257, d=5, seed=11)       # a shape no other test jits
    for kind in ("dt", "gb"):
        with pytest.raises(Refused):
            get_trainer(kind)(rt, X, y, 2, max_depth=2)
    # The explicit oracle path is untouched by the broken kernel.
    assert get_trainer("dt")(_runtime(False), X, y, 2,
                             max_depth=2).params["feat"].shape == (1, 7)


def test_n_bins_validator_shared():
    """The uint8 cap guard is one validator used by every entry point."""
    rt = _runtime(True)
    X, y = _blobs(64)
    with pytest.raises(ValueError, match="capped at 256"):
        trees.validate_n_bins(512)
    for fit in (trees.fit_dt, trees.fit_gb):
        with pytest.raises(ValueError, match="capped at 256"):
            fit(rt, X, y, 2, n_bins=512)
    with pytest.raises(ValueError, match="capped at 256"):
        trees._edge_prep(X, n_bins=512)


def test_per_level_psum_parity_multi_shard():
    """The per-level histogram reduction is unchanged by the kernel
    path: one level-0 histogram computed inside shard_map on the
    8-device mesh, reduced with the same single psum, is bit-identical
    kernel-vs-oracle (integer stats — exact under any tiling)."""
    n, d, nb, NL, S = 2048, 5, 16, 4, 3
    rng = np.random.default_rng(0)
    B = rng.integers(0, nb, (n, d)).astype(np.uint8)
    stats = rng.integers(0, 3, (S, n)).astype(np.float32)
    rel = rng.integers(0, NL, n).astype(np.int32)
    act = rng.random(n) < 0.9
    mesh = jax.make_mesh((jax.device_count(),), (DATA_AXIS,))

    def run(kernel):
        def fn(B, sT, rel, act):
            if kernel:
                h = pk.tree_histogram(B.T, sT, rel, act, n_nodes=NL,
                                      n_bins=nb, tile=pk.tree_tile(d, nb))
            else:
                blk, _, n_pad = trees._block_shape(B.shape[0], d * nb)
                assert n_pad == B.shape[0]
                h = trees._hist_level_xla(B, sT, rel, act, n_nodes=NL,
                                          n_bins=nb, blk=blk)
            return jax.lax.psum(h, DATA_AXIS)

        return np.asarray(jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(DATA_AXIS), P(None, DATA_AXIS), P(DATA_AXIS),
                      P(DATA_AXIS)),
            out_specs=P(), check_vma=False,
        ))(B, stats, rel, act))

    hk, ho = run(True), run(False)
    assert hk.shape == (NL, d, nb, S)
    np.testing.assert_array_equal(hk, ho)
    # And the reduction really aggregated every shard's rows (each
    # active row lands in exactly one bin per feature; stats are
    # integers so the f32 total is exact).
    assert hk.sum() == d * float((stats.sum(0) * act).sum())


def _hist_operands(T, d, n_bins, n, NL, S, seed=0):
    """A tree batch's histogram operands: one bin matrix, per-tree
    integer stats (f32 sums exact under any grouping), node ids, and an
    active mask that leaves some rows out."""
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, n_bins, (d, n)).astype(np.uint8)),
            jnp.asarray(rng.integers(0, 4, (T, S, n)).astype(np.float32)),
            jnp.asarray(rng.integers(0, NL, (T, n)).astype(np.int32)),
            jnp.asarray(rng.random((T, n)) < 0.8))


def _pallas_calls(jaxpr):
    """(name, grid, result shape) of every pallas_call in a jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield (eqn.params["name"], eqn.params["grid_mapping"].grid,
                   eqn.outvars[0].aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("T", [2, 5, 8])
@pytest.mark.parametrize("d,n_bins", [(3, 2), (28, 32), (28, 256)])
def test_stacked_histogram_equals_per_tree(T, d, n_bins):
    """The batching rule's stacked call (bin matrix shared: the trees
    are matmul rows under one one-hot) gives every tree the histogram
    the plain call gives it alone, and the grid form (every member its
    own codes) does too — n not a multiple of the tile, some rows
    inactive."""
    NL, S = 4, 2
    tile = pk.tree_tile(d, n_bins)
    n = 2 * tile + 77
    codes, stats, rel, act = _hist_operands(T, d, n_bins, n, NL, S)
    hist = jax.jit(partial(pk.tree_histogram, n_nodes=NL, n_bins=n_bins,
                           tile=tile))
    trees_share = jax.jit(jax.vmap(hist, in_axes=(None, 0, 0, 0)))

    def alone(st):
        return np.stack([np.asarray(hist(codes, st[k], rel[k], act[k]))
                         for k in range(T)])

    want = alone(stats)
    np.testing.assert_array_equal(
        np.asarray(trees_share(codes, stats, rel, act)), want)
    grid = jax.jit(jax.vmap(hist))(
        jnp.broadcast_to(codes, (T,) + codes.shape), stats, rel, act)
    np.testing.assert_array_equal(np.asarray(grid), want)
    # Real-valued stats (gb's): same operands, same contraction order
    # along the tile, so stacked rows read what the lone tree reads.
    fstats = stats * 0.37 + 0.011
    np.testing.assert_allclose(
        np.asarray(trees_share(codes, fstats, rel, act)), alone(fstats),
        rtol=1e-6, atol=1e-6)


def _parent_histogram(codes_T, stats_T, rel, active, *, n_nodes, n_bins,
                      tile, operand_dtype):
    """The oracle of the kernel body's own schedule: PR 32's formulation
    of one tree's call, tile by tile in plain jnp — per 128-column group
    a compare per feature of the group against the global column ids,
    OR-ed, selected to {0,1} in the operand's dtype, and the same dot on
    the same shapes in the same order. Returns the flat
    (n_nodes·S, d·n_bins) f32 block."""
    d, n = codes_T.shape
    S = stats_T.shape[0]
    n_pad = -(-n // tile) * tile
    codes = jnp.pad(codes_T.astype(jnp.int32), ((0, 0), (0, n_pad - n)))
    stats = jnp.pad(stats_T, ((0, 0), (0, n_pad - n)))
    rel = jnp.pad(jnp.where(active, rel, -1).astype(jnp.int32)[None, :],
                  ((0, 0), (0, n_pad - n)), constant_values=-1)
    M = n_nodes * S
    Wp = -(-d * n_bins // 128) * 128
    row = jax.lax.broadcasted_iota(jnp.int32, (M, tile), 0)
    group = jax.lax.broadcasted_iota(jnp.int32, (128, tile), 0)
    out = jnp.zeros((M, Wp), jnp.float32)
    for t in range(n_pad // tile):
        sl = slice(t * tile, (t + 1) * tile)
        At = jnp.zeros((M, tile), jnp.float32)
        for s in range(S):
            At = jnp.where(row == rel[:, sl] * S + s, stats[s:s + 1, sl], At)
        At = At.astype(operand_dtype)
        col = jnp.where(
            codes[:, sl] < n_bins,
            codes[:, sl] + n_bins * jax.lax.broadcasted_iota(
                jnp.int32, (d, tile), 0),
            -1)
        for lo in range(0, Wp, 128):
            cols = group + lo
            hit = None
            for f in range(lo // n_bins,
                           min(d - 1, (lo + 127) // n_bins) + 1):
                m = col[f:f + 1, :] == cols
                hit = m if hit is None else hit | m
            ohT = jnp.where(hit, 1.0, 0.0).astype(operand_dtype)
            out = out.at[:, lo:lo + 128].add(jax.lax.dot_general(
                At, ohT, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))
    return out[:, :d * n_bins]


@pytest.mark.parametrize("operand_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("T", [1, 5])
@pytest.mark.parametrize("d,n_bins", [(3, 2), (6, 32), (28, 32), (28, 256),
                                      (5, 48)])
def test_histogram_body_equals_the_parents_formulation(d, n_bins, T,
                                                       operand_dtype):
    """Whichever form of the bin one-hot the static shape selects (one
    compare a 128-column group where ``n_bins`` is a multiple of 8 — with
    four features a group, one feature over two groups, or, at 48 bins,
    features that straddle groups and a width that is no multiple of
    128 — and a compare per feature at 2 bins), the histogram is the
    parent's bit for bit: real-valued stats, n no multiple of the tile
    (five tiles, so a grid step of four ends past the table), inactive
    rows, codes ≥ ``n_bins``; one tree and five stacked."""
    NL, S = 4, 2
    tile = pk.tree_tile(d, n_bins)
    n = 4 * tile + 77
    rng = np.random.default_rng(d * n_bins + T)
    codes = jnp.asarray(
        rng.integers(0, min(n_bins + 3, 256), (d, n)).astype(np.uint8))
    stats = jnp.asarray(rng.normal(size=(T, S, n)).astype(np.float32))
    rel = jnp.asarray(rng.integers(0, NL, (T, n)).astype(np.int32))
    act = jnp.asarray(rng.random((T, n)) < 0.8)
    kw = dict(n_nodes=NL, n_bins=n_bins, tile=tile,
              operand_dtype=operand_dtype)
    hist = jax.jit(partial(pk.tree_histogram, **kw))
    if T == 1:
        got = hist(codes, stats[0], rel[0], act[0])[None]
    else:
        got = jax.vmap(hist, in_axes=(None, 0, 0, 0))(codes, stats, rel, act)
    want = np.stack([
        np.asarray(_parent_histogram(codes, stats[k], rel[k], act[k], **kw))
        .reshape(NL, S, d, n_bins).transpose(0, 2, 3, 1) for k in range(T)])
    assert (n_bins == 256) or bool((np.asarray(codes) >= n_bins).any())
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("operand_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_leaf_stats_body_equals_the_parents_formulation(operand_dtype):
    """The leaf statistics' shape: one synthetic feature whose int32
    "codes" are node ids, 63 "bins" (no multiple of 8: the per-feature
    compare), one lane group, one node."""
    M, S = 63, 2
    tile = pk.tree_tile(28, 32)
    n = 2 * tile + 77
    rng = np.random.default_rng(63)
    assign = jnp.asarray(rng.integers(0, M, (n,)).astype(np.int32))
    stats = jnp.asarray(rng.normal(size=(S, n)).astype(np.float32))
    got = pk.tree_leaf_stats(assign, stats, n_nodes=M, tile=tile,
                             operand_dtype=operand_dtype)
    want = _parent_histogram(
        assign[None, :], stats, jnp.zeros((n,), jnp.int32),
        jnp.ones((n,), bool), n_nodes=1, n_bins=M, tile=tile,
        operand_dtype=operand_dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batching_rule_branches():
    """Which kernel a ``vmap`` over the histogram call compiles to is
    read off the operands' own batch flags: codes shared → ONE call
    whose matmul rows are M = T·NG·S; codes batched → the plain kernel
    under a grid axis; member × tree nesting → stacked inside, grid
    outside; no vmap → the plain call, untouched."""
    T, Bm, d, n_bins, NL, S, n = 5, 3, 28, 32, 4, 2, 300
    tile = pk.tree_tile(d, n_bins)
    tiles, Wp = -(-n // tile), d * n_bins
    codes, stats, rel, act = _hist_operands(T, d, n_bins, n, NL, S)
    hist = partial(pk.tree_histogram, n_nodes=NL, n_bins=n_bins, tile=tile)
    trees_share = jax.vmap(hist, in_axes=(None, 0, 0, 0))

    def calls(fn, *args):
        return list(_pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr))

    assert calls(hist, codes, stats[0], rel[0], act[0]) == [
        ("tree_hist", (1, tiles), (1, NL * S, Wp))]
    assert calls(trees_share, codes, stats, rel, act) == [
        ("tree_hist_stacked", (1, tiles), (1, T * NL * S, Wp))]
    # stats alone batched (node ids shared): still one stacked call.
    assert calls(jax.vmap(hist, in_axes=(None, 0, None, None)),
                 codes, stats, rel[0], act[0]) == [
        ("tree_hist_stacked", (1, tiles), (1, T * NL * S, Wp))]
    assert calls(jax.vmap(hist), jnp.broadcast_to(codes, (T, d, n)),
                 stats, rel, act) == [
        ("tree_hist", (T, 1, tiles), (T, 1, NL * S, Wp))]
    # The leaf statistics' "codes" are the tree's own assignment.
    leaf = partial(pk.tree_leaf_stats, n_nodes=7, tile=tile)
    assert calls(jax.vmap(leaf), rel, stats) == [
        ("tree_hist", (T, 1, tiles), (T, 1, S, 128))]
    # A population: members bring their own bin matrix, their trees
    # share it.
    pop = [jnp.broadcast_to(a, (Bm,) + a.shape)
           for a in (codes, stats, rel, act)]
    assert calls(jax.vmap(trees_share), *pop) == [
        ("tree_hist_stacked", (Bm, 1, tiles), (Bm, 1, T * NL * S, Wp))]
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(trees_share)(*pop)[1]),
        np.asarray(trees_share(codes, stats, rel, act)))


def test_stack_width_follows_the_accumulator_budget(monkeypatch):
    """No room for every tree's accumulator at the node group one tree
    gets → as many trees a call as do fit (5 → 2 + 2 + 1), and with room
    for one only, the per-tree grid form — from the shapes alone."""
    T, d, n_bins, NL, S, n = 5, 28, 32, 4, 2, 300
    tile = pk.tree_tile(d, n_bins)
    codes, stats, rel, act = _hist_operands(T, d, n_bins, n, NL, S)
    hist = partial(pk.tree_histogram, n_nodes=NL, n_bins=n_bins, tile=tile)
    want = np.asarray(jax.vmap(hist, in_axes=(None, 0, 0, 0))(
        codes, stats, rel, act))
    one_tree = NL * S * d * n_bins * 4
    for budget, rows in ((2 * one_tree, [2 * NL * S, 2 * NL * S, NL * S]),
                         (one_tree, [NL * S]),
                         (one_tree // 2, [NL * S // 2])):
        monkeypatch.setattr(pk, "_TREE_ACC_BYTES", budget)
        fn = jax.vmap(hist, in_axes=(None, 0, 0, 0))
        got = list(_pallas_calls(
            jax.make_jaxpr(fn)(codes, stats, rel, act).jaxpr))
        assert [c[2][-2] for c in got] == rows, (budget, got)
        # below two trees' worth the batch is the plain kernel's grid axis
        assert [c[0] for c in got] == (
            ["tree_hist_stacked"] * 3 if len(rows) == 3 else ["tree_hist"])
        assert all(np.prod(c[2][-2:]) * 4 <= budget for c in got)
        np.testing.assert_array_equal(
            np.asarray(fn(codes, stats, rel, act)), want)


def _binned_shards(n, seed, n_bins=32):
    """(runtime, bin codes, labels, validity) on the 8-device mesh, as
    ``_fit_cls_trees`` hands them to the jitted fit programs."""
    X, y = _blobs(n, d=6, seed=seed)
    rt = _runtime(True)
    X_dev, n_real = rt.shard_rows(X)
    B = trees.bin_features(X_dev, rt.replicate(
        trees.quantile_edges(X, n_bins)))
    y_dev, _ = rt.shard_rows(y)
    valid, _ = rt.shard_rows(
        (np.arange(X_dev.shape[0]) < n_real).astype(np.float32))
    return rt, B, y_dev, valid


@pytest.mark.parametrize("d,n_bins", [(3, 2), (6, 32), (28, 256)])
def test_rf_stacked_fit_matches_oracle(d, n_bins):
    """An rf fit on the kernel path — its batch of trees now stacked on
    the histogram kernel's matmul rows — grows the oracle's trees, at
    the sweep's shapes and a ragged row count."""
    mk, mo, _, _ = _fit_pair("rf", 300, d=d, n_bins=n_bins, n_trees=4,
                             max_depth=3)
    _assert_params_bitexact("rf", mk, mo)


def test_forest_batch_program_stacks_and_matches_fit_forest():
    """The checkpoint-segmented per-batch program takes the stacked
    kernel as ``_fit_forest`` does, and its trees are ``_fit_forest``'s
    batch for batch."""
    rt, B, y_dev, valid = _binned_shards(900, seed=3)
    kw = dict(num_classes=2, max_depth=3, n_bins=32, n_trees=10,
              mesh=rt.mesh, mtry=2, use_kernel=True)
    key = jax.random.PRNGKey(5)
    whole = trees._fit_forest(B, y_dev, valid, key, **kw)
    tb, nb = trees._forest_batch_shape(10)
    assert (tb, nb) == (5, 2)
    keys = jax.random.split(key, nb * tb)
    for b in range(nb):
        part = trees._fit_forest_batch(B, y_dev, valid,
                                       keys[b * tb:(b + 1) * tb], **kw)
        for got, ref in zip(part, whole):
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(ref)[b * tb:(b + 1) * tb])
    for fn, k in ((trees._fit_forest, key), (trees._fit_forest_batch,
                                             keys[:tb])):
        names = [c[0] for c in _pallas_calls(
            jax.make_jaxpr(partial(fn, **kw))(B, y_dev, valid, k).jaxpr)]
        # one stacked histogram a level; the leaf pass on the grid form
        assert names.count("tree_hist_stacked") == 1
        assert names.count("tree_hist") == 1


@pytest.mark.parametrize("kind", ["dt", "gb"])
def test_single_tree_families_keep_their_histogram_call(kind):
    """dt and gb offer one tree at a time: no batch axis reaches the
    rule, and the histogram call in their programs is the plain one."""
    rt, B, y_dev, valid = _binned_shards(400, seed=4)
    if kind == "dt":
        fn = partial(trees._fit_forest, num_classes=2, max_depth=3,
                     n_bins=32, n_trees=1, mesh=rt.mesh, mtry=6,
                     use_kernel=True)
        args = (B, y_dev, valid, jax.random.PRNGKey(0))
    else:
        fn = partial(trees._fit_gbt, max_depth=3, n_bins=32, n_rounds=2,
                     mesh=rt.mesh, use_kernel=True)
        args = (B, y_dev, valid)
    hists = [c for c in _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
             if "tree_hist" in c[0]]
    tile = pk.tree_tile(6, 32)
    assert hists and all(
        c[0] == "tree_hist" and c[1] == (1, -(-50 // tile))
        for c in hists), hists


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["dt", "rf"])
@pytest.mark.parametrize("n", [300, 3001])
@pytest.mark.parametrize("d,n_bins", [(3, 2), (6, 32), (28, 256)])
def test_kernel_parity_sweep_classification(kind, n, d, n_bins):
    """Heavy odd-shape sweep (slow lane): n not a multiple of the
    kernel tile, d below the 128 lane width, n_bins at both extremes of
    the uint8 range. Classification stats are integers, so bit-parity
    holds at ANY tiling — including the multi-tile n=3001 cases."""
    hp = {"n_trees": 4, "max_depth": 3} if kind == "rf" else \
        {"max_depth": 3}
    mk, mo, _, _ = _fit_pair(kind, n, d=d, n_bins=n_bins, **hp)
    _assert_params_bitexact(kind, mk, mo)


@pytest.mark.slow
@pytest.mark.parametrize("n_bins", [2, 32, 256])
def test_kernel_parity_sweep_gb_single_tile(n_bins):
    """gb bit-parity in the single-tile regime (odd n below the kernel
    row tile): the kernel performs the same contraction as the oracle
    plus exact-zero padding rows, so multi-round float stats still
    reduce identically."""
    d = 6
    # One tile per shard: the 8-way mesh splits rows before the kernel
    # tiles them, so any n ≤ tile per shard stays single-tile; odd n
    # exercises the ragged padded tail.
    n = pk.tree_tile(d, n_bins) - 47
    mk, mo, _, _ = _fit_pair("gb", n, d=d, n_bins=n_bins, n_rounds=3,
                             max_depth=3)
    _assert_params_bitexact("gb", mk, mo)


@pytest.mark.slow
def test_gb_multi_tile_statistical_parity():
    """Beyond one row tile gb's float grad/hess histograms sum in
    different groupings, so trees may legitimately differ on argmax
    ties — pin statistical equivalence instead: held-out accuracy
    within ±0.01 of the oracle fit."""
    n = 3001
    X, y = _blobs(n + 600, seed=7)
    rt_k, rt_o = _runtime(True), _runtime(False)
    mk = get_trainer("gb")(rt_k, X[:n], y[:n], 2)
    mo = get_trainer("gb")(rt_o, X[:n], y[:n], 2)
    acc_k = float((mk.predict(rt_k, X[n:]) == y[n:]).mean())
    acc_o = float((mo.predict(rt_o, X[n:]) == y[n:]).mean())
    assert abs(acc_k - acc_o) <= 0.01, (acc_k, acc_o)
