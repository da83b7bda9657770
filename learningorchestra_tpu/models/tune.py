"""Device-resident hyperparameter search — vmapped config populations.

The reference (and Spark's CrossValidator generally) fits one cluster
job per (config, fold) candidate: every candidate pays its own dispatch
round, its own data scan, and its own compile. Here a POPULATION of
same-family configs becomes ONE device program:

- **vmapped configs**: the trainers expose population fit paths
  (trees/logistic/mlp ``*_pop_*``) that vmap a member axis over the
  per-family fit body. Static shapes are the population's maxima
  (max_depth, n_bins, n_rounds, iterations, hidden width); a member's
  smaller hyperparameter rides as a traced mask (``bin_gain_mask`` /
  ``level_allow`` / round-activity / step-gating / width zero-padding)
  constructed so the member's arithmetic is IDENTICAL to its standalone
  fit — per-config results are bit-identical to serial fits for
  dt/rf/lr/mlp (gb: accuracy parity, the PR-7 standard), pinned in
  tests/test_tune.py.
- **masked k-fold CV**: fold membership is the index predicate
  ``row % folds == fold`` evaluated into per-member row-weight masks
  over the ONE resident (n, d) design — never a data copy. A sweep of
  16 configs × 3 folds is 48 members of one vmapped program.
- **successive halving on checkpoint rungs**: the family's natural
  segment boundaries (PR 14's fitckpt units — boost rounds, tree
  batches, adam iterations) are the rungs. After each rung every
  candidate's fold scores are taken by one fixed-shape scoring program
  (unbuilt trees/rounds carry zero mass, so every rung reuses the same
  compile), the bottom half of surviving configs is dropped by zeroing
  masks — survivors' arithmetic is untouched — and the population state
  is checkpointed, so a crashed sweep resumes to identical survivors
  and scores.
- **profile-guided population sizing**: per-member HBM footprint is
  modeled analytically and raised to the family's recorded
  ``peak_hbm_bytes`` watermark (utils/resources.py);
  the largest candidate count that fits ``LO_TPU_TUNE_HBM_BUDGET_MB``
  runs as one wave, extras spill into sequential waves (counted on
  ``/metrics`` as ``lo_tune_hbm_spill_waves_total``).
"""

from __future__ import annotations

import math
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from learningorchestra_tpu.models import logistic, mlp, trees
from learningorchestra_tpu.models.base import as_design
from learningorchestra_tpu.models.registry import validate_hparams
from learningorchestra_tpu.parallel.mesh import (
    DATA_AXIS, MODEL_AXIS, MeshRuntime)
from learningorchestra_tpu.utils import tracing
from learningorchestra_tpu.utils.structlog import get_logger

log = get_logger("tune")

#: Families with a population fit path. nb is a closed-form single pass
#: (nothing to halve) and tx's sequence programs are out of scope.
POP_FAMILIES = ("dt", "rf", "gb", "lr", "mlp")

#: Wave stride for the fitckpt progress integer: progress =
#: wave * stride + units_done_in_wave stays monotone as long as no wave
#: exceeds a million units (rounds/iterations) — far beyond any real
#: sweep.
_WAVE_STRIDE = 1_000_000

# -- /metrics counters (the ``tune`` section; jobs._fault pattern) -----------

_counter_lock = threading.Lock()
_counters = {
    "populations_fitted": 0,     # vmapped waves run to completion
    "candidates_evaluated": 0,   # configs that received a final score
    "rungs_completed": 0,        # segment+score rounds across all waves
    "halving_drops": 0,          # configs dropped before their budget
    "hbm_spill_waves": 0,        # extra waves forced by the HBM budget
    "sweeps_resumed": 0,         # sweeps continued from a checkpoint
}


def _bump(key: str, by: int = 1) -> None:
    with _counter_lock:
        _counters[key] += by


def counters_snapshot() -> Dict[str, int]:
    with _counter_lock:
        return dict(_counters)


# -- validation ---------------------------------------------------------------

def validate_population(family: str, configs: Sequence[Dict[str, Any]],
                        num_classes: Optional[int] = None) -> None:
    """Reject sweeps the population programs cannot run bit-faithfully.

    Beyond per-config hparam validation (unknown names / out-of-range
    values → the serving tier's 406), population members must agree on
    the axes that change PRNG key derivation or program structure:
    ``jax.random.split(key, n)`` values depend on ``n``, so rf members
    must share ``n_trees`` (a member with fewer trees would draw
    different bootstrap keys than its standalone fit); lr members must
    resolve to one solver (newton and adam are different programs); gb's
    population path is the binary reference-parity booster."""
    if family not in POP_FAMILIES:
        raise ValueError(
            f"classifier {family!r} has no population tune path; "
            f"choose from {sorted(POP_FAMILIES)}")
    if not configs or not isinstance(configs, (list, tuple)):
        raise ValueError("tune needs a non-empty list of configs")
    for c in configs:
        validate_hparams(family, c)
    if family == "rf":
        if len({int(c.get("n_trees", 20)) for c in configs}) != 1:
            raise ValueError(
                "rf tune populations must share n_trees: the bootstrap "
                "key split depends on the tree count, so mixed forest "
                "sizes cannot be bit-faithful to standalone fits — "
                "sweep n_trees across separate tune calls")
    if family == "lr":
        if len({_resolve_solver(c, num_classes) for c in configs}) != 1:
            raise ValueError(
                "lr tune populations must resolve to one solver "
                "(newton and adam are different device programs); pin "
                "'solver' explicitly or split the sweep")
    if family == "gb" and num_classes is not None and num_classes != 2:
        raise ValueError(
            "gb tune populations support the binary reference-parity "
            "booster only (num_classes == 2)")


def _resolve_solver(config: Dict[str, Any],
                    num_classes: Optional[int]) -> str:
    solver = str(config.get("solver", "auto"))
    if solver != "auto":
        return solver
    if num_classes is None:
        return "auto"
    # The serial fit's auto rule (models/logistic.py): d is unknown at
    # validation time, so auto resolves per sweep in the driver; here we
    # only need config-level agreement, which "auto" for all satisfies.
    return "auto"


# -- population sizing --------------------------------------------------------

def _per_member_bytes(family: str, n: int, d: int,
                      num_classes: int) -> float:
    """Analytic resident-HBM model for ONE population member: the
    member's share of the vmapped working set (bin matrices, row masks,
    margins, activation transients). Deliberately coarse — it is raised
    to the family's recorded whole-fit watermark below, and the budget
    knob exists for operators to clamp it anyway."""
    C = float(max(num_classes, 2))
    nf = float(n)
    masks = 8.0 * nf                       # train + eval f32 row weights
    if family in ("dt", "rf"):
        return masks + nf * d + 4.0 * nf * (C + 3.0)
    if family == "gb":
        return masks + nf * d + 24.0 * nf
    if family == "lr":
        return masks + 4.0 * nf * C
    # mlp: hidden activations (bf16) + logits; width is bounded by the
    # population max but unknown here — assume the serial default.
    return masks + 2.0 * nf * 256.0 + 4.0 * nf * C


def plan_waves(family: str, configs: Sequence[Dict[str, Any]], *, n: int,
               d: int, num_classes: int, folds: int,
               cfg) -> List[List[int]]:
    """Split config indices into sequential population waves.

    Wave width = the largest count whose modeled footprint
    (``_per_member_bytes`` raised to the family's recorded
    ``peak_hbm_bytes`` watermark, × folds members per config) fits
    ``LO_TPU_TUNE_HBM_BUDGET_MB``, capped by
    ``LO_TPU_TUNE_MAX_POPULATION`` members. Budget 0 = one wave."""
    from learningorchestra_tpu.utils import resources

    cap = max(1, int(cfg.tune_max_population) // max(folds, 1))
    budget = float(cfg.tune_hbm_budget_mb) * (1 << 20)
    if budget > 0:
        per = _per_member_bytes(family, n, d, num_classes)
        wm = resources.family_watermarks().get(family, {})
        per = max(per, float(wm.get("peak_hbm_bytes", 0)))
        fit = int(budget // max(per * max(folds, 1), 1.0))
        width = max(1, min(cap, fit))
    else:
        width = cap
    idxs = list(range(len(configs)))
    waves = [idxs[i:i + width] for i in range(0, len(idxs), width)]
    if len(waves) > 1 and budget > 0:
        _bump("hbm_spill_waves", len(waves) - 1)
    return waves


# -- fold masks ---------------------------------------------------------------

def _fold_masks(n: int, padded: int, folds: int
                ) -> Tuple[List[int], np.ndarray, np.ndarray]:
    """(fold_ids, train_masks (F, padded), eval_masks (F, padded)) as
    f32 row weights over the padded global row index. Fold membership is
    ``row % folds == fid``; fid = -1 (folds <= 1) trains AND scores on
    every valid row."""
    idx = np.arange(padded)
    valid = (idx < n).astype(np.float32)
    if folds <= 1:
        return [-1], valid[None, :], valid[None, :]
    fids = list(range(folds))
    ev = np.stack([valid * (idx % folds == f) for f in fids]
                  ).astype(np.float32)
    tr = valid[None, :] - ev
    return fids, tr, ev


def _put_members(mesh, arr: np.ndarray):
    """Place a (members, rows) host array member-replicated /
    row-sharded — the layout every population program's shard_map
    expects for per-member row weights."""
    return jax.device_put(
        np.asarray(arr), NamedSharding(mesh, P(None, DATA_AXIS)))


def runtime_replicate(mesh, x):
    """Fully-replicated device placement for population-axis vectors."""
    return jax.device_put(np.asarray(x), NamedSharding(mesh, P()))


# -- family drivers -----------------------------------------------------------
#
# A driver owns one wave's device state. Interface:
#   total_units()            — the wave's unit budget (max over members)
#   run_segment(k)           — advance every live member k units
#   scores()                 — per-MEMBER eval-fold accuracy, (Pm,) np
#   set_alive(alive_configs) — (n_cfg,) 0/1; zeroes dropped members' masks
#   ckpt_arrays()            — host arrays for fitckpt.save
#   restore(units, arrays)   — rebuild device state mid-wave
#
# Members are (config, fold) pairs flattened config-major: member
# m = ci * folds + fi.


class _ForestDriver:
    """dt / rf: units are vmapped tree batches (the serial checkpointed
    path's boundaries); trees accumulate host-side per batch exactly
    like ``_run_forest_checkpointed``."""

    def __init__(self, family, runtime, X, y, num_classes, configs,
                 fold_ids, tr_masks, ev_masks):
        mesh = runtime.mesh
        self.mesh = mesh
        self.num_classes = num_classes
        self.configs = configs
        self.nf = len(fold_ids)
        d = X.shape[1]
        depths = [int(c.get("max_depth", 5)) for c in configs]
        nbins = [int(c.get("n_bins", 32)) for c in configs]
        self.max_depth = max(depths)
        self.n_bins = max(nbins)
        if family == "dt":
            self.n_trees = 1
            mtries = [1] * len(configs)
        else:
            self.n_trees = int(configs[0].get("n_trees", 20))
            mtries = [int(c.get("mtry") or max(1, int(np.sqrt(d))))
                      for c in configs]
        self.tb, self.nb = trees._forest_batch_shape(self.n_trees)
        self.M = 2 ** (self.max_depth + 1) - 1

        # Per-config edges at the config's own n_bins, padded to the
        # population max with +inf (x > inf is never true, so the padded
        # codes are bit-identical to binning with the shorter list).
        sample = X if isinstance(X, np.ndarray) else X.sample_rows(200_000)
        cfg_edges = []
        for c, nb_c in zip(configs, nbins):
            e = np.full((d, self.n_bins - 1), np.inf, np.float32)
            if nb_c > 1:
                e[:, :nb_c - 1] = trees.quantile_edges(sample, nb_c)
            cfg_edges.append(e)
        # Per-config bin/level masks and keys, expanded config-major to
        # members. NEG forbids thresholds ≥ a member's n_bins - 1 and
        # levels ≥ its max_depth (see trees._build_tree).
        NEG = trees.NEG
        bmask = np.zeros((len(configs), self.n_bins), np.float32)
        lallow = np.zeros((len(configs), self.max_depth), bool)
        keys = []
        for i, (c, nb_c, dep) in enumerate(zip(configs, nbins, depths)):
            bmask[i, max(nb_c - 1, 0):] = NEG
            lallow[i, :dep] = True
            keys.append(np.asarray(jax.random.split(
                jax.random.PRNGKey(int(c.get("seed", 0))),
                self.nb * self.tb)))

        rep = lambda a: np.repeat(np.asarray(a), self.nf, axis=0)
        self.edges_dev = runtime.replicate(rep(np.stack(cfg_edges)))
        self.bin_mask = runtime.replicate(rep(bmask))
        self.level_allow = runtime.replicate(rep(lallow))
        self.mtry_vec = runtime.replicate(
            rep(np.asarray(mtries, np.int32)))
        self.keys = rep(np.stack(keys))          # (Pm, nb*tb, 2) host
        X_dev, self.n = runtime.shard_rows(as_design(X))
        self.B_pop = trees._bin_features_pop(X_dev, self.edges_dev)
        self.y_dev, _ = runtime.shard_rows(np.asarray(y, np.int32))
        self.w_base = _put_members(mesh, tr_masks)
        self.ew_dev = _put_members(mesh, ev_masks)
        self.alive_dev = runtime.replicate(
            np.ones(len(configs) * self.nf, np.float32))
        self.done_b = 0
        self.host: Dict[str, np.ndarray] = {}
        self._names = ("feat", "thr", "internal", "leaf")

    def total_units(self) -> int:
        return self.nb

    def run_segment(self, k: int) -> None:
        w_pop = self.w_base * self.alive_dev[:, None]
        for b in range(self.done_b, self.done_b + k):
            outs = trees._fit_forest_pop_batch(
                self.B_pop, self.y_dev, w_pop, self.bin_mask,
                self.level_allow, self.mtry_vec,
                runtime_replicate(
                    self.mesh,
                    self.keys[:, b * self.tb:(b + 1) * self.tb]),
                num_classes=self.num_classes, max_depth=self.max_depth,
                n_bins=self.n_bins, n_trees=self.n_trees, mesh=self.mesh)
            seg = {kk: np.asarray(a)
                   for kk, a in zip(self._names, outs)}
            self.host = ({kk: np.concatenate([self.host[kk], seg[kk]],
                                             axis=1)
                          for kk in self._names} if self.host else seg)
        self.done_b += k

    def _padded_trees(self):
        Pm = len(self.configs) * self.nf
        full = {
            "feat": np.zeros((Pm, self.n_trees, self.M), np.int32),
            "thr": np.zeros((Pm, self.n_trees, self.M), np.int32),
            "internal": np.zeros((Pm, self.n_trees, self.M), bool),
            "leaf": np.zeros((Pm, self.n_trees, self.M,
                              self.num_classes), np.float32),
        }
        if self.host:
            built = min(self.host["feat"].shape[1], self.n_trees)
            for kk in self._names:
                full[kk][:, :built] = self.host[kk][:, :built]
        return full

    def scores(self) -> np.ndarray:
        full = self._padded_trees()
        return np.asarray(trees._forest_pop_scores(
            self.B_pop, self.y_dev, self.ew_dev,
            jnp.asarray(full["feat"]), jnp.asarray(full["thr"]),
            jnp.asarray(full["internal"]), jnp.asarray(full["leaf"]),
            max_depth=self.max_depth, mesh=self.mesh))

    def set_alive(self, alive_configs: np.ndarray) -> None:
        self.alive_dev = runtime_replicate(
            self.mesh, np.repeat(alive_configs.astype(np.float32),
                                 self.nf))

    def ckpt_arrays(self) -> Dict[str, np.ndarray]:
        return dict(self.host)

    def restore(self, units: int, arrays: Dict[str, np.ndarray]) -> None:
        self.host = {kk: arrays[kk] for kk in self._names}
        self.done_b = units


class _GbDriver:
    """gb: units are boost rounds; the margin carries on device between
    segments and is REPLAYED from the stored (activity-scaled) leaf
    values on resume, like the serial checkpointed path."""

    def __init__(self, runtime, X, y, num_classes, configs, fold_ids,
                 tr_masks, ev_masks):
        mesh = runtime.mesh
        self.mesh = mesh
        self.configs = configs
        self.nf = len(fold_ids)
        d = X.shape[1]
        depths = [int(c.get("max_depth", 5)) for c in configs]
        nbins = [int(c.get("n_bins", 32)) for c in configs]
        rounds = [int(c.get("n_rounds", 20)) for c in configs]
        self.max_depth = max(depths)
        self.n_bins = max(nbins)
        self.r_max = max(rounds)
        self.M = 2 ** (self.max_depth + 1) - 1

        sample = X if isinstance(X, np.ndarray) else X.sample_rows(200_000)
        cfg_edges = []
        for c, nb_c in zip(configs, nbins):
            e = np.full((d, self.n_bins - 1), np.inf, np.float32)
            if nb_c > 1:
                e[:, :nb_c - 1] = trees.quantile_edges(sample, nb_c)
            cfg_edges.append(e)
        NEG = trees.NEG
        bmask = np.zeros((len(configs), self.n_bins), np.float32)
        lallow = np.zeros((len(configs), self.max_depth), bool)
        for i, (nb_c, dep) in enumerate(zip(nbins, depths)):
            bmask[i, max(nb_c - 1, 0):] = NEG
            lallow[i, :dep] = True

        rep = lambda a: np.repeat(np.asarray(a), self.nf, axis=0)
        self.edges_dev = runtime.replicate(rep(np.stack(cfg_edges)))
        self.bin_mask = runtime.replicate(rep(bmask))
        self.level_allow = runtime.replicate(rep(lallow))
        self.step_sizes = runtime.replicate(rep(np.asarray(
            [float(c.get("step_size", 0.1)) for c in configs],
            np.float32)))
        self.rounds_m = rep(np.asarray(rounds, np.int32))
        X_dev, self.n = runtime.shard_rows(as_design(X))
        self.B_pop = trees._bin_features_pop(X_dev, self.edges_dev)
        self.y_dev, _ = runtime.shard_rows(np.asarray(y, np.int32))
        self.w_base = _put_members(mesh, tr_masks)
        self.ew_dev = _put_members(mesh, ev_masks)
        Pm, padded = tr_masks.shape
        self.margin = _put_members(mesh, np.zeros((Pm, padded),
                                                  np.float32))
        self.alive = np.ones(len(configs) * self.nf, np.float32)
        self.done = 0
        self.host: Dict[str, np.ndarray] = {}
        self._names = ("feat", "thr", "internal", "leaf_val")

    def total_units(self) -> int:
        return self.r_max

    def run_segment(self, k: int) -> None:
        ractive = (((self.done + np.arange(k))[None, :]
                    < self.rounds_m[:, None])
                   & (self.alive[:, None] > 0)).astype(np.float32)
        w_pop = self.w_base * jnp.asarray(self.alive)[:, None]
        outs, self.margin = trees._fit_gbt_pop_seg(
            self.B_pop, self.y_dev, w_pop, self.margin, self.step_sizes,
            runtime_replicate(self.mesh, ractive), self.bin_mask,
            self.level_allow, max_depth=self.max_depth,
            n_bins=self.n_bins, n_rounds=k, mesh=self.mesh)
        seg = {kk: np.asarray(a) for kk, a in zip(self._names, outs)}
        self.host = ({kk: np.concatenate([self.host[kk], seg[kk]],
                                         axis=1)
                      for kk in self._names} if self.host else seg)
        self.done += k

    def _padded_trees(self):
        Pm = self.w_base.shape[0]
        full = {
            "feat": np.zeros((Pm, self.r_max, self.M), np.int32),
            "thr": np.zeros((Pm, self.r_max, self.M), np.int32),
            "internal": np.zeros((Pm, self.r_max, self.M), bool),
            "leaf_val": np.zeros((Pm, self.r_max, self.M), np.float32),
        }
        if self.host:
            built = min(self.host["feat"].shape[1], self.r_max)
            for kk in self._names:
                full[kk][:, :built] = self.host[kk][:, :built]
        return full

    def scores(self) -> np.ndarray:
        full = self._padded_trees()
        return np.asarray(trees._gbt_pop_scores(
            self.B_pop, self.y_dev, self.ew_dev,
            jnp.asarray(full["feat"]), jnp.asarray(full["thr"]),
            jnp.asarray(full["internal"]),
            jnp.asarray(full["leaf_val"]), self.step_sizes,
            max_depth=self.max_depth, mesh=self.mesh))

    def set_alive(self, alive_configs: np.ndarray) -> None:
        self.alive = np.repeat(alive_configs.astype(np.float32), self.nf)

    def ckpt_arrays(self) -> Dict[str, np.ndarray]:
        return dict(self.host)

    def restore(self, units: int, arrays: Dict[str, np.ndarray]) -> None:
        self.host = {kk: arrays[kk] for kk in self._names}
        self.done = units
        self.margin = trees._gbt_pop_replay_margin(
            self.B_pop, jnp.asarray(self.host["feat"]),
            jnp.asarray(self.host["thr"]),
            jnp.asarray(self.host["internal"]),
            jnp.asarray(self.host["leaf_val"]), self.step_sizes,
            max_depth=self.max_depth, mesh=self.mesh)


class _LrDriver:
    """lr: units are solver iterations (newton capped at 20 like the
    serial auto rule); per-member lr/l2 ride as traced scalars."""

    def __init__(self, runtime, X, y, num_classes, configs, fold_ids,
                 tr_masks, ev_masks):
        mesh = runtime.mesh
        self.mesh = mesh
        self.num_classes = num_classes
        self.configs = configs
        self.nf = len(fold_ids)
        self.d = X.shape[1]
        solvers = set()
        for c in configs:
            s = str(c.get("solver", "auto"))
            if s == "auto":
                s = ("newton" if num_classes * (self.d + 1)
                     <= logistic._NEWTON_MAX_CD else "adam")
            solvers.add(s)
        if len(solvers) != 1:
            raise ValueError(
                "lr tune populations must resolve to one solver; got "
                f"{sorted(solvers)}")
        self.solver = solvers.pop()
        iters = [int(c.get("iters", 300)) for c in configs]
        if self.solver == "newton":
            iters = [min(i, 20) for i in iters]
        self.it_max = max(iters)

        rep = lambda a: np.repeat(np.asarray(a), self.nf, axis=0)
        self.iters_vec = runtime.replicate(rep(np.asarray(iters,
                                                          np.int32)))
        self.lrs = runtime.replicate(rep(np.asarray(
            [float(c.get("lr", 0.1)) for c in configs], np.float32)))
        self.l2s = runtime.replicate(rep(np.asarray(
            [float(c.get("l2", 1e-4)) for c in configs], np.float32)))
        self.X_dev, self.n = runtime.shard_rows(as_design(X))
        self.y_dev, _ = runtime.shard_rows(np.asarray(y, np.int32))
        self.mu, self.sigma = logistic._device_stats(
            self.X_dev, runtime.replicate(np.int32(self.n)), mesh=mesh)
        self.masks = _put_members(mesh, tr_masks)
        self.ew_dev = _put_members(mesh, ev_masks)
        self.alive = runtime.replicate(
            np.ones(len(configs) * self.nf, np.float32))
        self.done = 0
        if self.solver == "adam":
            seeds = rep(np.asarray(
                [int(c.get("seed", 0)) for c in configs], np.int32))
            self.params, self.opt_state = logistic._pop_lr_init(
                jnp.asarray(seeds), self.mu, self.sigma, d=self.d,
                num_classes=num_classes)
        else:
            Pm = len(configs) * self.nf
            self.Wz = runtime.replicate(np.zeros(
                (Pm, self.d + 1, num_classes), np.float32))

    def total_units(self) -> int:
        return self.it_max

    def run_segment(self, k: int) -> None:
        t0 = np.int32(self.done)
        if self.solver == "adam":
            self.params, self.opt_state, _ = logistic._fit_pop_adam(
                self.params, self.opt_state, self.X_dev, self.y_dev,
                self.masks, self.mu, self.sigma, self.lrs, self.l2s,
                self.iters_vec, self.alive, t0, iters=k)
        else:
            self.Wz = logistic._fit_pop_newton(
                self.X_dev, self.y_dev, self.masks, self.mu, self.sigma,
                self.l2s, self.iters_vec, self.alive, self.Wz, t0,
                num_classes=self.num_classes, iters=k, mesh=self.mesh)
        self.done += k

    def _Wb(self):
        if self.solver == "adam":
            return self.params["W"], self.params["b"]
        return self.Wz[:, :self.d, :], self.Wz[:, self.d, :]

    def scores(self) -> np.ndarray:
        W, b = self._Wb()
        return np.asarray(logistic._pop_lr_scores(
            W, b, self.mu, self.sigma, self.X_dev, self.y_dev,
            self.ew_dev, mesh=self.mesh))

    def set_alive(self, alive_configs: np.ndarray) -> None:
        self.alive = runtime_replicate(
            self.mesh, np.repeat(alive_configs.astype(np.float32),
                                 self.nf))

    def ckpt_arrays(self) -> Dict[str, np.ndarray]:
        if self.solver == "newton":
            return {"Wz": np.asarray(self.Wz)}
        out = {f"p.{k}": np.asarray(v) for k, v in self.params.items()}
        leaves = jax.tree_util.tree_leaves(self.opt_state)
        out.update({f"o.{i}": np.asarray(v)
                    for i, v in enumerate(leaves)})
        return out

    def restore(self, units: int, arrays: Dict[str, np.ndarray]) -> None:
        self.done = units
        if self.solver == "newton":
            self.Wz = runtime_replicate(self.mesh, arrays["Wz"])
            return
        self.params = {k[2:]: jnp.asarray(v) for k, v in arrays.items()
                       if k.startswith("p.")}
        tdef = jax.tree_util.tree_structure(self.opt_state)
        nleaves = len(jax.tree_util.tree_leaves(self.opt_state))
        self.opt_state = jax.tree_util.tree_unflatten(
            tdef, [jnp.asarray(arrays[f"o.{i}"])
                   for i in range(nleaves)])


class _MlpDriver:
    """mlp: units are adam iterations; member widths are zero-padded to
    the population max after each member initializes at its OWN rounded
    width (the draw depends on the shape)."""

    def __init__(self, runtime, X, y, num_classes, configs, fold_ids,
                 tr_masks, ev_masks):
        mesh = runtime.mesh
        self.mesh = mesh
        self.configs = configs
        self.nf = len(fold_ids)
        d = X.shape[1]
        iters = [int(c.get("iters", 300)) for c in configs]
        self.it_max = max(iters)
        X = as_design(X)
        self.X_dev, self.n = runtime.shard_rows(X)
        if isinstance(X, np.ndarray):
            mu = X.mean(axis=0).astype(np.float32)
            sigma = np.where(X.std(axis=0) < 1e-7, 1.0,
                             X.std(axis=0)).astype(np.float32)
        else:
            mu, sigma = logistic._device_stats(
                self.X_dev, runtime.replicate(np.int32(self.n)),
                mesh=mesh)
            mu, sigma = np.asarray(mu), np.asarray(sigma)
        rep = lambda a: np.repeat(np.asarray(a), self.nf, axis=0)
        self.params, self.opt_state, self.rounded = mlp._pop_mlp_init(
            rep([int(c.get("seed", 0)) for c in configs]),
            rep([int(c.get("hidden", 256)) for c in configs]),
            d, num_classes, mu, sigma,
            model_mult=mesh.shape[MODEL_AXIS])
        self.iters_vec = runtime.replicate(rep(np.asarray(iters,
                                                          np.int32)))
        self.lrs = runtime.replicate(rep(np.asarray(
            [float(c.get("lr", 1e-2)) for c in configs], np.float32)))
        self.l2s = runtime.replicate(rep(np.asarray(
            [float(c.get("l2", 1e-4)) for c in configs], np.float32)))
        self.y_dev, _ = runtime.shard_rows(np.asarray(y, np.int32))
        self.masks = _put_members(mesh, tr_masks)
        self.ew_dev = _put_members(mesh, ev_masks)
        self.alive = runtime.replicate(
            np.ones(len(configs) * self.nf, np.float32))
        self.done = 0

    def total_units(self) -> int:
        return self.it_max

    def run_segment(self, k: int) -> None:
        self.params, self.opt_state, _ = mlp._run_pop(
            self.params, self.opt_state, self.X_dev, self.y_dev,
            self.masks, self.lrs, self.l2s, self.iters_vec, self.alive,
            np.int32(self.done), iters=k)
        self.done += k

    def scores(self) -> np.ndarray:
        return np.asarray(mlp._pop_mlp_scores(
            self.params, self.X_dev, self.y_dev, self.ew_dev))

    def set_alive(self, alive_configs: np.ndarray) -> None:
        self.alive = runtime_replicate(
            self.mesh, np.repeat(alive_configs.astype(np.float32),
                                 self.nf))

    def ckpt_arrays(self) -> Dict[str, np.ndarray]:
        out = {f"p.{k}": np.asarray(v) for k, v in self.params.items()}
        leaves = jax.tree_util.tree_leaves(self.opt_state)
        out.update({f"o.{i}": np.asarray(v)
                    for i, v in enumerate(leaves)})
        return out

    def restore(self, units: int, arrays: Dict[str, np.ndarray]) -> None:
        self.done = units
        self.params = {k[2:]: jnp.asarray(v) for k, v in arrays.items()
                       if k.startswith("p.")}
        tdef = jax.tree_util.tree_structure(self.opt_state)
        nleaves = len(jax.tree_util.tree_leaves(self.opt_state))
        self.opt_state = jax.tree_util.tree_unflatten(
            tdef, [jnp.asarray(arrays[f"o.{i}"])
                   for i in range(nleaves)])


_DRIVERS = {"dt": _ForestDriver, "rf": _ForestDriver, "gb": _GbDriver,
            "lr": _LrDriver, "mlp": _MlpDriver}


def _make_driver(family, runtime, X, y, num_classes, configs, fold_ids,
                 tr_masks, ev_masks):
    cls = _DRIVERS[family]
    if cls is _ForestDriver:
        return cls(family, runtime, X, y, num_classes, configs,
                   fold_ids, tr_masks, ev_masks)
    return cls(runtime, X, y, num_classes, configs, fold_ids, tr_masks,
               ev_masks)


# -- the sweep ----------------------------------------------------------------

def sweep(runtime: MeshRuntime, X, y, num_classes: int, family: str,
          configs: Sequence[Dict[str, Any]], *, cfg,
          folds: Optional[int] = None, rungs: Optional[int] = None,
          ckpt=None) -> Dict[str, Any]:
    """Run one device-resident sweep; returns the leaderboard document.

    ``ckpt`` is an optional fitckpt context: population state persists
    at every rung boundary, and an interrupted sweep resumes to
    IDENTICAL survivors and scores (the per-family segment arithmetic is
    bit-stable under segmentation, and the alive set / rung history ride
    in the checkpoint meta)."""
    from learningorchestra_tpu import jobs

    validate_population(family, configs, num_classes)
    configs = [dict(c) for c in configs]
    folds = int(cfg.tune_folds if folds is None else folds)
    rungs = int(cfg.tune_rungs if rungs is None else rungs)
    if folds < 1 or folds > 64:
        raise ValueError("tune folds must be in [1, 64]")
    if rungs < 1:
        raise ValueError("tune rungs must be >= 1")
    if jax.process_count() > 1:
        raise ValueError(
            "tune sweeps run single-process: the member-axis mask "
            "placement is not multi-host addressable yet")

    X = as_design(X)
    if not isinstance(X, np.ndarray):
        raise ValueError(
            "tune sweeps need a resident design matrix; materialize the "
            "dataset (streamed designs are fit-only)")
    n = int(len(X))
    padded = n + (-n) % runtime.mesh.shape[DATA_AXIS]
    fold_ids, tr_all, ev_all = _fold_masks(n, padded, folds)
    nf = len(fold_ids)
    d = int(X.shape[1])
    waves = plan_waves(family, configs, n=n, d=d,
                       num_classes=num_classes, folds=nf, cfg=cfg)

    # Resume bookkeeping: the fitckpt meta carries the wave index, the
    # alive set, the rung history and finished waves' results — enough
    # to rebuild the exact orchestration state around the restored
    # device arrays.
    resume = ckpt.load() if ckpt is not None and ckpt.enabled else None
    completed: List[Dict[str, Any]] = []
    resume_wave = -1
    resume_state = None
    if resume is not None:
        progress, arrays, meta = resume
        if meta.get("family") == family and meta.get("waves") == len(
                waves) and meta.get("folds") == folds:
            resume_wave = int(meta.get("wave", 0))
            completed = list(meta.get("completed", []))
            resume_state = (int(progress) % _WAVE_STRIDE, arrays, meta)
            _bump("sweeps_resumed")
            from learningorchestra_tpu.utils import fitckpt

            fitckpt.count_resume()
            jobs.record_job_resume(f"tune_{family}", {
                "wave": resume_wave, "units": resume_state[0]})
        else:
            ckpt.clear()

    results: List[Dict[str, Any]] = list(completed)
    for w, wave_idx in enumerate(waves):
        if w < resume_wave:
            continue          # finished wave — its results rode the meta
        wave_cfgs = [configs[i] for i in wave_idx]
        nc = len(wave_cfgs)
        tr = np.tile(tr_all, (nc, 1))
        ev = np.tile(ev_all, (nc, 1))
        driver = _make_driver(family, runtime, X, y, num_classes,
                              wave_cfgs, fold_ids, tr, ev)
        units = driver.total_units()
        R = max(1, min(rungs, units))
        seg = -(-units // R)
        alive = np.ones(nc, np.float64)
        survived = np.zeros(nc, np.int64)
        fold_scores = np.zeros((nc, nf), np.float64)
        done = 0
        rung_i = 0
        fit_s = 0.0
        if w == resume_wave and resume_state is not None:
            done, arrays, meta = resume_state
            if 0 < done < units:
                driver.restore(done, arrays)
                alive = np.asarray(meta.get("alive",
                                            alive.tolist()), np.float64)
                survived = np.asarray(
                    meta.get("survived", survived.tolist()), np.int64)
                fold_scores = np.asarray(
                    meta.get("fold_scores", fold_scores.tolist()),
                    np.float64)
                rung_i = int(meta.get("rung", 0))
                fit_s = float(meta.get("fit_s", 0.0))
                driver.set_alive(alive)
            else:
                ckpt.clear()
        while done < units:
            k = min(seg, units - done)
            with tracing.span("tune.rung", family=family, wave=w,
                              rung=rung_i, alive=int(alive.sum())):
                t0 = time.monotonic()
                driver.run_segment(k)
                member_scores = driver.scores()
                fit_s += time.monotonic() - t0
            done += k
            rung_i += 1
            _bump("rungs_completed")
            ms = np.asarray(member_scores, np.float64).reshape(nc, nf)
            live = alive > 0
            fold_scores[live] = ms[live]
            survived[live] = rung_i
            if done < units and R > 1 and live.sum() > 1:
                means = fold_scores.mean(axis=1)
                keep = math.ceil(int(live.sum()) / 2)
                # Rank live configs by mean score, ties to the lower
                # index (deterministic across resumes).
                order = sorted(np.flatnonzero(live),
                               key=lambda i: (-means[i], i))
                dropped = order[keep:]
                if dropped:
                    alive[dropped] = 0.0
                    driver.set_alive(alive)
                    _bump("halving_drops", len(dropped))
            jobs.heartbeat()
            if done < units and ckpt is not None and ckpt.enabled:
                ckpt.save(
                    w * _WAVE_STRIDE + done, driver.ckpt_arrays(),
                    meta={"family": family, "wave": w,
                          "waves": len(waves), "folds": folds,
                          "rung": rung_i, "fit_s": fit_s,
                          "alive": alive.tolist(),
                          "survived": survived.tolist(),
                          "fold_scores": fold_scores.tolist(),
                          "completed": results})
        means = fold_scores.mean(axis=1)
        for i, ci in enumerate(wave_idx):
            results.append({
                "config": configs[ci],
                "fold_scores": [round(float(s), 6)
                                for s in fold_scores[i]],
                "mean_score": round(float(means[i]), 6),
                "fit_seconds": round(fit_s, 3),
                "rungs_survived": int(survived[i]),
                "alive": bool(alive[i]),
                "wave": w,
            })
        _bump("populations_fitted")
        _bump("candidates_evaluated", nc)
        # The next wave's resume anchor: this wave is complete, so its
        # results ride the meta and device state restarts fresh.
        if w + 1 < len(waves) and ckpt is not None and ckpt.enabled:
            ckpt.save((w + 1) * _WAVE_STRIDE, {"anchor": np.zeros(1)},
                      meta={"family": family, "wave": w + 1,
                            "waves": len(waves), "folds": folds,
                            "completed": results})
    if ckpt is not None and ckpt.enabled:
        ckpt.clear()

    finishers = [r for r in results if r["alive"]] or results
    winner = max(finishers, key=lambda r: r["mean_score"])
    board = {
        "family": family, "folds": folds, "rungs": rungs,
        "waves": len(waves), "halving": rungs > 1,
        "results": sorted(results, key=lambda r: -r["mean_score"]),
        "winner": winner,
    }
    log.info("tune %s: %d configs x %d folds in %d wave(s); winner "
             "mean_score=%.4f", family, len(configs), folds, len(waves),
             winner["mean_score"])
    return board
