"""Operations and bytes the algorithms need, from a cell's shapes.

The benchmark's own copy of the program's analytic counts
(``learningorchestra_tpu/models/flops.py``, kernel-path model): they
price the algorithm, not what a compiler or a kernel happens to execute,
so an implementation that does useless work fast reads LOW, and the same
work reads the same whatever later implements it. One multiply-add is 2
operations; a compare or select is 1.

``shapes(config)`` gives the sizes; every function takes them as numbers.
"""

from __future__ import annotations


def shapes(config: dict) -> dict:
    data, fam = config["data"], config["families"]
    return {"n": data["n_train"], "n_test": data["n_test"],
            "d": data["n_features"], "classes": 2, "families": fam}


# -- tree families ------------------------------------------------------------

def tree_count(families: dict, kinds) -> dict:
    """Trees built by each tree family of the sweep."""
    out = {}
    for k in kinds:
        if k == "dt":
            out[k] = 1
        elif k == "rf":
            out[k] = families[k]["n_trees"]
        elif k == "gb":
            out[k] = families[k]["n_rounds"]
    return out


def tree_level_bytes(n: float, d: float, n_stats: float) -> float:
    """Bytes one level of one tree has to move: every row's ``d`` bin
    codes (uint8), its ``n_stats`` float32 statistics, and its node id
    (one byte read, four written back by the routing)."""
    return n * (d + 4.0 * n_stats + 5.0)


def tree_level_ops(n: float, d: float, n_bins: float, n_stats: float,
                   depth: float) -> float:
    """Operations of one level: one accumulate per (row, feature, stat),
    the bin compares, the split gains of the widest level, the routing."""
    nl = 2 ** max(int(depth) - 1, 0)
    return (2.0 * n * d * n_stats + n * d * n_bins
            + 6.0 * nl * d * n_bins * n_stats + 5.0 * n)


def tree_build_bytes(n, d, depth, n_stats, trees) -> float:
    leaf = n * (4.0 * n_stats + 1.0)
    return trees * (depth * tree_level_bytes(n, d, n_stats) + leaf)


def tree_build_ops(n, d, n_bins, depth, n_stats, trees) -> float:
    return trees * (depth * tree_level_ops(n, d, n_bins, n_stats, depth)
                    + 2.0 * n * n_stats)


def sweep_tree_work(sh: dict, kinds) -> dict:
    """``{"bytes", "ops"}`` of one sweep's tree building (the part the
    tree kernels do): every tree family's trees, level by level."""
    n, d = float(sh["n"]), float(sh["d"])
    total = {"bytes": 0.0, "ops": 0.0}
    for k, trees in tree_count(sh["families"], kinds).items():
        hp = sh["families"][k]
        total["bytes"] += tree_build_bytes(n, d, hp["max_depth"], 2.0, trees)
        total["ops"] += tree_build_ops(n, d, hp["n_bins"], hp["max_depth"],
                                       2.0, trees)
    return total


def least_seconds(work: dict, peaks: dict) -> tuple:
    """``(seconds, "bytes" | "ops")``: the roofline's least time and
    which of the two bounds it."""
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = work["ops"] / peaks["bf16_flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "ops")


# -- whole sweep ----------------------------------------------------------------

def _binning_ops(n, d, n_bins):
    return n * d * (n_bins - 1)


def _descend_ops(n, d, depth):
    return depth * n * (d + 3.0 * (2 ** (int(depth) + 1) - 1))


def fit_ops(kind: str, sh: dict) -> float:
    n, d, C = float(sh["n"]), float(sh["d"]), float(sh["classes"])
    hp = sh["families"][kind]
    if kind == "lr":
        d1 = d + 1
        if C * d1 <= hp["newton_max_cd"]:
            per = (2.0 * n * (C * d1) ** 2 + 2.0 * n * C * d1 ** 2
                   + 5.0 * n * C * d1)
            return hp["newton_steps"] * per + 4.0 * n * d
        return hp["adam_steps"] * 6.0 * n * d * C + 4.0 * n * d
    if kind == "nb":
        return 4.0 * n * C * d + 3.0 * n * d + n * C
    depth, n_bins = hp["max_depth"], hp["n_bins"]
    if kind in ("dt", "rf"):
        trees = 1 if kind == "dt" else hp["n_trees"]
        return _binning_ops(n, d, n_bins) + tree_build_ops(
            n, d, n_bins, depth, C, trees)
    if kind == "gb":
        M = 2 ** (int(depth) + 1) - 1
        per_round = (tree_build_ops(n, d, n_bins, depth, 2.0, 1)
                     + _descend_ops(n, d, depth) + n * M + 6.0 * n)
        return hp["n_rounds"] * per_round + _binning_ops(n, d, n_bins)
    raise ValueError(f"no operation count for family {kind!r}")


def predict_ops(kind: str, sh: dict) -> float:
    n, d, C = float(sh["n_test"]), float(sh["d"]), float(sh["classes"])
    hp = sh["families"][kind]
    if kind == "lr":
        return 2.0 * n * d * C + 3.0 * n * d
    if kind == "nb":
        return 4.0 * n * d * C + 3.0 * n * d
    depth, n_bins = hp["max_depth"], hp["n_bins"]
    M = 2 ** (int(depth) + 1) - 1
    if kind == "gb":
        trees, cols = hp["n_rounds"], 1.0
    else:
        trees, cols = (1 if kind == "dt" else hp["n_trees"]), C
    return _binning_ops(n, d, n_bins) + trees * (
        _descend_ops(n, d, depth) + 2.0 * n * M * cols)


def sweep_ops(sh: dict, kinds) -> float:
    """Operations of one whole sweep: every family's fit and its
    probability pass over the test table."""
    return sum(fit_ops(k, sh) + predict_ops(k, sh) for k in kinds)
