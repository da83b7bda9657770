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


def test_tree_bench_smoke(monkeypatch):
    """The bench harness's tree-phase microbenchmark runs end to end on
    the CPU mesh (LO_BENCH_TREE_ROWS smoke regime) and reports both
    paths per phase."""
    import bench

    monkeypatch.setattr(bench, "N_TREE", 2048)
    doc = bench.tree_bench()
    assert doc["rows"] == 2048
    assert set(doc["speedup"]) == {"hist", "route", "descend"}
    for path in ("kernel", "xla"):
        assert all(doc[path][k] > 0 for k in
                   ("hist_ms", "route_ms", "descend_ms"))


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
