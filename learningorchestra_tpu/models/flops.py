"""Analytic per-family FLOP counts — the numerator of the bench's MFU.

A speed-up over a single-core sklearn stand-in never establishes that
the chip is well used — nothing distinguishes 40% MFU from 4%. These
formulas count the *algorithmically required* floating-point
work of each trainer's device program (the dominant contraction terms,
from the same shapes the modules document), so

    mfu = flops / (device_s * peak_flops)

is a falsifiable utilization figure next to wall-clock. Counts are
analytic rather than XLA cost-model dumps on purpose: they price the
algorithm, not whatever the compiler materialized, so a bloated lowering
shows up as LOW mfu instead of inflating the numerator to hide itself.

Conventions: one multiply-add = 2 flops; one-hot compare/select passes
count 1 flop per element (they occupy the VPU exactly like an add);
terms an order of magnitude below the leading contraction are dropped.
Shapes/blocking mirror models/logistic.py, models/trees.py,
models/naive_bayes.py — the line references below.

Tree families carry TWO cost models since the fused Pallas kernel path
landed (LO_TPU_TREE_KERNEL, models/trees.py):

- The **oracle path** genuinely executes the dense one-hot contraction,
  so its flops price that emulation (the MXU work the device performs).
- The **kernel path** prices the *algorithm* — a binned scatter-add is
  one accumulate per (row, feature, stat) per level — NOT the dense
  contraction the kernel still uses internally to feed the MXU. The
  contraction term is ~NL·n_bins (≈512× at the defaults) the
  algorithmic accumulate — ~97% multiplications by zero — and pricing
  it would inflate the end-to-end kernel-path numerator ~50× (the bin
  compares and gain terms are shared by both paths): congratulating the
  kernel for doing useless work fast is exactly the "bloated lowering
  hides itself" failure mode above.
  Kernel-path tree fits are therefore memory-bound by design and their
  honest utilization figure is ``bw_util`` — modeled HBM bytes
  (``fit_bytes``) over device time against peak HBM bandwidth — with
  mfu reported alongside as the (low) MXU-work fraction.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from learningorchestra_tpu import config

#: Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``:
#: dense-matmul FLOP/s at bf16 (the dtype the dominant contractions here
#: actually use: trees' histogram matmuls and lr's Newton accumulation
#: run bf16 operands with f32 accumulation) and HBM bytes/s (the
#: denominator of ``bw_util`` for memory-bound programs — kernel-path
#: tree fits). A device that is not in the table has no peak: its
#: ``mfu``/``bw_util`` read None ("not measured"), never another chip's.
#: Source for "TPU v5 lite": Google Cloud documentation, "TPU v5e"
#: (197 TFLOP/s bf16, 819 GB/s HBM per chip).
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "bw": 819e9},
}


def device_peak(which: str,
                device_kind: Optional[str] = None) -> Optional[float]:
    """Peak ``"flops"`` or ``"bw"`` of ``device_kind`` (default: this
    process's first device). The ``LO_TPU_PEAK_FLOPS`` /
    ``LO_TPU_PEAK_BW`` overrides (config.py) win; otherwise the table
    above; None for a device in neither."""
    override = (config.peak_flops() if which == "flops"
                else config.peak_bw())
    if override:
        return override
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return DEVICE_PEAKS.get(device_kind, {}).get(which)


def _tree_kernel_default() -> bool:
    """Whether the fit programs route through the Pallas tree kernels —
    models/trees.py `_use_tree_kernel` (the two config flags), imported
    here so jax stays out of this module's import."""
    from learningorchestra_tpu.models import trees

    return trees._use_tree_kernel()


def _tree_build_flops(n: float, d: float, n_bins: float, max_depth: float,
                      n_stats: float, kernel: bool = False) -> float:
    """One level-wise histogram tree (models/trees.py _build_tree).

    Oracle path — per level, per row block: the (NL·S, blk) @
    (blk, d·n_bins) histogram contraction (trees.py _hist_level_xla)
    dominates at 2·n·NL·S·d·n_bins; building the bin one-hot costs
    n·d·n_bins compares and the node-masked stats operand n·NL·S.
    Routing (_sel_col/_sel_table one-hot passes) adds ~n·(2d + 3·NL)
    per level. NL is the fixed per-level node width 2^(max_depth-1).
    Leaf stats add one (S, n) @ (n, M) contraction.

    Kernel path — algorithmic cost only (see module docstring): one
    accumulate per (row, feature, stat) per level (2·n·d·S), the
    n·d·n_bins bin compares, ~5·n routing ops per level, the
    ~6·NL·d·n_bins·S gain evaluation, and n·S leaf accumulates.
    """
    NL = 2 ** max(int(max_depth) - 1, 0)
    M = 2 ** (int(max_depth) + 1) - 1
    if kernel:
        per_level = (2.0 * n * d * n_stats            # binned scatter-add
                     + n * d * n_bins                 # bin one-hot
                     + 6.0 * NL * d * n_bins * n_stats  # split gains
                     + 5.0 * n)                       # routing
        return max_depth * per_level + 2.0 * n * n_stats
    per_level = (2.0 * n * NL * n_stats * d * n_bins   # histogram matmul
                 + n * d * n_bins                      # bin one-hot
                 + n * NL * n_stats                    # stats operand
                 + n * (2.0 * d + 3.0 * NL))           # routing selects
    return max_depth * per_level + 2.0 * n * n_stats * M


def _tree_build_bytes(n: float, d: float, n_bins: float, max_depth: float,
                      n_stats: float, kernel: bool = False) -> float:
    """Modeled HBM traffic of one tree build (the roofline numerator for
    the memory-bound kernel path).

    Kernel path — per level the histogram pass streams the uint8 bin
    matrix (n·d), the f32 stats (4·n·S) and the int32 rel/active columns
    (~8·n); the routing pass re-streams the bin matrix and
    reads+writes assignment (~12·n). Accumulator blocks live in VMEM.
    Leaf pass: stats + assignment once.

    Oracle path adds the materialized contraction operands per level:
    the (blk, d·n_bins) bin one-hot and the (blk, NL·S) node-masked
    stats, each written then read (2× each way) at the operand dtype
    (bf16 on TPU — modeled at 2 bytes).
    """
    hist_level = n * (d + 4.0 * n_stats + 8.0)
    route_level = n * (d + 12.0)
    leaf = n * (4.0 * n_stats + 4.0)
    total = max_depth * (hist_level + route_level) + leaf
    if not kernel:
        NL = 2 ** max(int(max_depth) - 1, 0)
        onehot = 2.0 * 2.0 * n * (d * n_bins + NL * n_stats)
        total += max_depth * onehot + 2.0 * 2.0 * n * (
            2 ** (int(max_depth) + 1) - 1)
    return total


def _binning_flops(n: float, d: float, n_bins: float) -> float:
    """bin_features: fused (n, d, n_bins-1) compare+sum (trees.py:139)."""
    return n * d * (n_bins - 1)


def _descend_flops(n: float, d: float, max_depth: float) -> float:
    """Blocked leaf routing: per depth step, _sel_table×3 (M-wide) +
    _sel_col (d-wide) one-hot passes (trees.py:329-351)."""
    M = 2 ** (int(max_depth) + 1) - 1
    return max_depth * n * (d + 3.0 * M)


def fit_flops(kind: str, n: int, d: int, num_classes: int,
              hparams: Optional[Dict[str, Any]] = None,
              tree_kernel: Optional[bool] = None) -> float:
    """Analytic FLOPs of one family's *fit* device program on (n, d)
    rows. ``hparams`` are the request's overrides; defaults mirror the
    trainer signatures (Spark-2.4 parity defaults). ``tree_kernel``
    selects the tree families' cost model (module docstring); None
    reads the active configuration."""
    hp = dict(hparams or {})
    if kind in ("dt", "rf", "gb") and tree_kernel is None:
        tree_kernel = _tree_kernel_default()
    n, d, C = float(n), float(d), float(max(num_classes, 2))
    if kind == "lr":
        solver = hp.get("solver", "auto")
        d1 = d + 1
        if solver == "auto":
            solver = "newton" if C * d1 <= 256 else "adam"
        if solver == "newton":
            # Per Newton step (logistic.py:138-168): logits 2·n·d1·C, the
            # A-operand n·C·d1, T2 = AᵀA at 2·n·(C·d1)², T1's C blocked
            # d1×d1 contractions at 2·n·C·d1², gradient 2·n·d1·C; plus
            # the (C·d1)³ solve (replicated, negligible at n≫d).
            iters = min(float(hp.get("iters", 300)), 20.0)
            per = (2.0 * n * (C * d1) ** 2 + 2.0 * n * C * d1 ** 2
                   + 5.0 * n * C * d1)
            stats = 4.0 * n * d            # _device_stats two-pass
            return iters * per + stats
        iters = float(hp.get("iters", 300))
        # Adam full-batch value_and_grad ≈ 3× the forward 2·n·d·C matmul.
        return iters * 6.0 * n * d * C + 4.0 * n * d
    if kind == "nb":
        # One pass (naive_bayes.py:50-65): center matmul 2·n·d, the two
        # (C, n) @ (n, d) moment contractions 4·n·C·d, one-hot n·C.
        return 4.0 * n * C * d + 3.0 * n * d + n * C
    if kind in ("dt", "rf"):
        n_trees = float(hp.get("n_trees", 1 if kind == "dt" else 20))
        max_depth = float(hp.get("max_depth", 5))
        n_bins = float(hp.get("n_bins", 32))
        return (_binning_flops(n, d, n_bins)
                + n_trees * _tree_build_flops(n, d, n_bins, max_depth,
                                              n_stats=C,
                                              kernel=bool(tree_kernel)))
    if kind == "gb":
        n_rounds = float(hp.get("n_rounds", 20))
        max_depth = float(hp.get("max_depth", 5))
        n_bins = float(hp.get("n_bins", 32))
        boosters = C if C > 2 else 1.0     # one-vs-rest above binary
        # Per round: grad/hess stats ~6·n, one tree build (S=2 stats),
        # leaf-value descent + margin update (~_descend + n·M select).
        M = 2 ** (int(max_depth) + 1) - 1
        per_round = (_tree_build_flops(n, d, n_bins, max_depth,
                                       n_stats=2.0,
                                       kernel=bool(tree_kernel))
                     + _descend_flops(n, d, max_depth) + n * M + 6.0 * n)
        return boosters * (n_rounds * per_round) + _binning_flops(n, d,
                                                                  n_bins)
    if kind == "mlp":
        hidden = float(hp.get("hidden", 64))
        iters = float(hp.get("iters", 200))
        return iters * 6.0 * n * hidden * (d + C)
    return 0.0


def predict_flops(kind: str, n: int, d: int, num_classes: int,
                  hparams: Optional[Dict[str, Any]] = None) -> float:
    """Analytic FLOPs of one family's probability pass on (n, d) rows."""
    hp = dict(hparams or {})
    n, d, C = float(n), float(d), float(max(num_classes, 2))
    if kind == "lr":
        return 2.0 * n * d * C + 3.0 * n * d
    if kind == "nb":
        # Two (n, d) @ (d, C) matmuls (naive_bayes.py:84).
        return 4.0 * n * d * C + 3.0 * n * d
    if kind in ("dt", "rf", "gb"):
        n_bins = float(hp.get("n_bins", 32))
        max_depth = float(hp.get("max_depth", 5))
        if kind == "gb":
            trees = float(hp.get("n_rounds", 20)) * (C if C > 2 else 1.0)
            leaf_cols = 1.0
        else:
            trees = float(hp.get("n_trees", 1 if kind == "dt" else 20))
            leaf_cols = C
        M = 2 ** (int(max_depth) + 1) - 1
        return (_binning_flops(n, d, n_bins)
                + trees * (_descend_flops(n, d, max_depth)
                           + 2.0 * n * M * leaf_cols))
    if kind == "mlp":
        hidden = float(hp.get("hidden", 64))
        return 2.0 * n * hidden * (d + C)
    return 0.0


def build_flops(kind: str, n_train: int, n_test: int, d: int,
                num_classes: int,
                hparams: Optional[Dict[str, Any]] = None,
                tree_kernel: Optional[bool] = None) -> float:
    """Fit + probability pass — the device program one family contributes
    to a model build (models/builder.py fit device phase)."""
    return (fit_flops(kind, n_train, d, num_classes, hparams,
                      tree_kernel=tree_kernel)
            + predict_flops(kind, n_test, d, num_classes, hparams))


def mfu(flops: float, device_s: float,
        peak_flops: float = 0.0) -> Optional[float]:
    """Achieved fraction of peak: flops / (device_s · peak). None when
    the span is degenerate (failed fit, unmeasured) or the device has no
    published peak (``device_peak``)."""
    peak = peak_flops or device_peak("flops")
    if peak is None or device_s <= 0.0 or peak <= 0.0 or flops <= 0.0:
        return None
    return flops / (device_s * peak)


def fit_bytes(kind: str, n: int, d: int, num_classes: int,
              hparams: Optional[Dict[str, Any]] = None,
              tree_kernel: Optional[bool] = None) -> Optional[float]:
    """Modeled HBM bytes moved by one family's fit device program — the
    roofline numerator for memory-bound programs. Currently modeled for
    the tree families only (the ones the Pallas kernel path turned
    memory-bound); None elsewhere."""
    if kind not in ("dt", "rf", "gb"):
        return None
    hp = dict(hparams or {})
    if tree_kernel is None:
        tree_kernel = _tree_kernel_default()
    n, d, C = float(n), float(d), float(max(num_classes, 2))
    max_depth = float(hp.get("max_depth", 5))
    n_bins = float(hp.get("n_bins", 32))
    binning = 5.0 * n * d                      # read f32, write uint8
    if kind in ("dt", "rf"):
        n_trees = float(hp.get("n_trees", 1 if kind == "dt" else 20))
        return binning + n_trees * _tree_build_bytes(
            n, d, n_bins, max_depth, n_stats=C, kernel=bool(tree_kernel))
    n_rounds = float(hp.get("n_rounds", 20))
    boosters = C if C > 2 else 1.0
    # Per round: the tree build, full-tree descent (bin matrix + assign),
    # and the margin/grad/hess elementwise passes (~5 f32 row vectors).
    per_round = (_tree_build_bytes(n, d, n_bins, max_depth, n_stats=2.0,
                                   kernel=bool(tree_kernel))
                 + n * (d + 4.0) + 20.0 * n)
    return binning + boosters * n_rounds * per_round


def bw_util(bytes_moved: Optional[float], device_s: float,
            peak_bw: float = 0.0) -> Optional[float]:
    """Achieved fraction of peak HBM bandwidth: bytes / (device_s ·
    peak). The utilization figure that matters for memory-bound programs
    (kernel-path tree fits); None when unmodeled, degenerate, or the
    device has no published peak (``device_peak``)."""
    peak = peak_bw or device_peak("bw")
    if peak is None or bytes_moved is None or device_s <= 0.0 \
            or peak <= 0.0 or bytes_moved <= 0.0:
        return None
    return bytes_moved / (device_s * peak)
