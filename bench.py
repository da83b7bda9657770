"""Benchmark harness — prints ONE JSON line for the driver.

Headline metric (BASELINE.md north star): wall-clock of the model_builder
5-classifier sweep (lr/dt/rf/gb/nb) fitting HIGGS-11M (11,000,000 x 28
float32, binary label) through the full service path — catalog dataset →
design matrix → sharded fits on the mesh → metrics → prediction datasets
for a 100k evaluation split.

Workload: benchmarks/workload.py — a generative HIGGS-like task
calibrated so the sklearn reference families reproduce the published
HIGGS difficulty ordering (trees beat linear: lr≈nb < dt < rf < gb;
Baldi et al. 2014 territory), replacing the round-3 linearly-separable
generator that inverted it. The per-family accuracy gates below encode
that ordering, so a fast-but-broken fit cannot game the wall-clock.

Baseline: the reference's Spark 2.4.7 stack is not runnable here and it
publishes no HIGGS numbers, so the Spark-CPU stand-in is sklearn with the
same hyperparameters (depth-5 trees, 20 trees/rounds, histogram GBT —
favoring the baseline) measured on this machine at 1.1M rows ON THE SAME
WORKLOAD and extrapolated linearly (conservative for trees):
104.98 CPU-seconds at 1.1M → 1049.8 s at 11M (benchmarks/baseline_cpu.py,
recorded in BASELINE.md). ``vs_baseline`` = baseline_seconds /
our_seconds. The north-star target is ≥10x (BASELINE.json).

Steady-state timing: one warmup sweep populates XLA's compilation cache
(also persisted to disk so repeated bench runs stay warm), then three
measured sweeps run and the median is reported — matching how the
long-lived server process actually behaves (the reference's published
41.87 s NaiveBayes fit likewise excludes Spark cluster startup).

Instrumentation: before the measured sweeps, one SERIALIZED sweep (max_concurrent_fits=1, so device spans are
uncontended) records per-family ``device_s`` — dispatch through blocked
completion, the split that separates host jitter from device
compute — and ``mfu`` = analytic family FLOPs / (device_s · the device's
published peak) (learningorchestra_tpu/models/flops.py keeps the table,
keyed by ``device_kind``; LO_TPU_PEAK_FLOPS overrides it; a device in
neither is an error). The measured sweeps then run PIPELINED
(max_concurrent_fits=2: host prep/finishing overlaps device compute
while the device working set stays bounded — 5-way concurrency thrashed
HBM, measured 363 s vs 106 s sequential); ``overlap`` reports the
headline wall-clock against the sum of the same sweep's per-family fit
times (which exclude scheduler waits by construction), making the
pipeline win directly falsifiable.

Tracing (ISSUE 9): the measured sweeps run under an active trace at
full sampling — what a traced production job pays — and a mirrored,
interleaved set runs with ``LO_TPU_TRACE_SAMPLE=0`` semantics;
``tracing_overhead`` records both medians, the percentage delta, and a
``pass_2pct`` verdict against the < 2% acceptance bar, so an
instrumentation-cost regression shows up in the trajectory like any
compute regression. The verdict is recorded rather than asserted: at
sub-scale smoke sizes rig jitter exceeds 2% in either direction and a
flapping hard gate would mask real regressions.

Resources (ISSUE 10): the ``resources`` block records per-family
``peak_hbm_bytes`` + ``compile_s`` watermarks from the serialized
instrumented sweep (utils/resources.py — the same accounting every job
profile now carries) and the cold-vs-warm compile split: XLA compile
seconds paid by the warmup sweep vs the residue across all six measured
sweeps, the amortization a steady-state server banks.

Tree families (PR 7): fits route through the fused Pallas
binned-histogram kernels by default (``tree_kernel`` in the output
records the active path); their cost model switches with the path
(flops.py module docstring — the kernel path is memory-bound, so
``bw_util`` against peak HBM bandwidth is recorded next to ``mfu``),
and ``tree_bench`` times the histogram/routing/descent phases on both
paths separately (LO_BENCH_TREE_ROWS scales or skips it).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks.workload import higgs_like_columns  # noqa: E402

#: sklearn 5-family sweep, same hyperparameters and same workload, CPU
#: process-time at 1.1M rows x10 (benchmarks/baseline_cpu.py; BASELINE.md).
CPU_BASELINE_11M_S = 1049.8

#: Overridable for smoke-testing the harness itself off-TPU (the driver
#: runs the defaults — the headline stays HIGGS-11M).
N_TRAIN = int(os.environ.get("LO_BENCH_TRAIN_ROWS", 11_000_000))
N_TEST = int(os.environ.get("LO_BENCH_TEST_ROWS", 100_000))
#: Rows for the chunk-store scan-throughput microbenchmark (PR 5:
#: prefetching read pipeline + chunk cache); 0 skips it.
N_SCAN = int(os.environ.get("LO_BENCH_SCAN_ROWS", 4_000_000))
#: Rows for the tree-kernel phase microbenchmark (PR 7: fused Pallas
#: binned-histogram kernels) — times the histogram and routing/descent
#: phases separately on the kernel and XLA-oracle paths, so the record
#: shows where the tree-family speedup lands; 0 skips it.
N_TREE = int(os.environ.get("LO_BENCH_TREE_ROWS", 4_000_000))
#: Rows for the peer-replication microbenchmark (PR 17: cross-host data
#: fault domain) — push throughput to an in-process peer plus a remote
#: chunk-repair latency smoke; 0 skips it.
N_REPLICA = int(os.environ.get("LO_BENCH_REPLICA_ROWS", 2_000_000))
#: Rows / population size for the hyperparameter-search A/B (PR 18:
#: device-resident tune): a population-of-N vmapped sweep vs the same N
#: configs fitted AND scored serially, per family, with compile counts.
#: The default row count deliberately sits in the compile-dominated
#: regime — a 16-config grid over static-shape knobs recompiles the
#: serial arm per distinct shape, which is the cost the population
#: program amortizes on every backend. 0 skips it.
N_TUNE_ROWS = int(os.environ.get("LO_BENCH_TUNE_ROWS", 4_000))
N_TUNE_CONFIGS = int(os.environ.get("LO_BENCH_TUNE_CONFIGS", 16))


def scan_bench() -> dict:
    """Scan-throughput microbenchmark over a SPILLED dataset (all chunks
    on disk, loaded lazily): rows/s for the synchronous oracle
    (prefetch=0, cache off), the prefetching pipeline cold, and the
    warm chunk cache; plus the streamed-fit pass counters showing the
    default 3-step pipeline's physical reads at ~1 scan.

    "Cold" means the process-level chunk cache is cold; the OS page
    cache is whatever it is (same for every variant — the deltas are
    what matter)."""
    import shutil
    import tempfile
    import numpy as np

    from learningorchestra_tpu.catalog import readpipe
    from learningorchestra_tpu.catalog.store import DatasetStore
    from learningorchestra_tpu.config import Settings
    from learningorchestra_tpu.ops import preprocess

    n = N_SCAN
    if n <= 0:
        return {}
    tmp = tempfile.mkdtemp(prefix="lo_scan_bench_")
    try:
        cfg = Settings()
        cfg.store_root = tmp
        cfg.persist = True
        store = DatasetStore(cfg)
        ds = store.create("scanb")
        rng = np.random.default_rng(0)
        chunk = 262_144
        for off in range(0, n, chunk):
            k = min(chunk, n - off)
            ds.append_columns({
                "x1": rng.normal(size=k), "x2": rng.normal(size=k),
                "x3": rng.normal(size=k),
                "y": rng.integers(0, 2, k)})
        store.finish("scanb")
        store2 = DatasetStore(cfg)
        ds2 = store2.load("scanb")
        fields = ["x1", "x2", "x3", "y"]

        def one_scan(prefetch) -> float:
            t0 = time.time()
            acc = 0.0
            for cols in ds2.iter_chunks(fields, prefetch=prefetch):
                # A light per-chunk reduction stands in for consumer
                # compute — what prefetch overlaps the reads against.
                acc += float(cols["x1"].sum())
            assert acc == acc
            return time.time() - t0

        readpipe.reset()
        readpipe.set_cache_budget(0)
        sync_s = one_scan(0)                 # synchronous oracle, uncached
        prefetch_cold_s = one_scan(None)     # pipeline, still uncached
        readpipe.set_cache_budget(None)
        cold_s = one_scan(None)              # populates the cache
        warm_s = one_scan(None)              # served from host RAM
        counters = readpipe.snapshot()

        prof = {}
        readpipe.reset()
        preprocess.design_matrix_streamed(
            ds2, "y", [{"op": "label_encode"},
                       {"op": "fillna", "strategy": "mean"},
                       {"op": "standardize"}], profile=prof)
        readpipe.reset()
        readpipe.set_cache_budget(None)
        return {
            "rows": n,
            "chunks": len(ds2.journal_files()),
            "sync_rows_s": round(n / sync_s),
            "prefetch_cold_rows_s": round(n / prefetch_cold_s),
            "cold_rows_s": round(n / cold_s),
            "warm_rows_s": round(n / warm_s),
            "warm_vs_cold": round(cold_s / warm_s, 2),
            "prefetch_vs_sync": round(sync_s / prefetch_cold_s, 2),
            "prefetch_stalls": counters["prefetch_stalls"],
            "streamed_fit": {k: prof[k] for k in
                             ("fit_passes", "fit_cache_hits",
                              "fit_cache_misses") if k in prof},
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def replication_bench() -> dict:
    """Peer-replication microbenchmark (fault_tolerance.md §9): full-sync
    push throughput of a committed dataset to an in-process replica
    peer (the re-replicate leg of the host-loss runbook), and the
    latency of one remote chunk repair through the ladder's peer rung.

    Loopback sockets, so the figures bound protocol + CRC + fsync cost,
    not the network — the deltas across commits are what matter."""
    import shutil
    import tempfile
    import numpy as np

    from learningorchestra_tpu.catalog.replicate import ReplicaServer
    from learningorchestra_tpu.catalog.store import DatasetStore
    from learningorchestra_tpu.config import Settings

    n = N_REPLICA
    if n <= 0:
        return {}
    tmp = tempfile.mkdtemp(prefix="lo_replica_bench_")
    peer = ReplicaServer(root=os.path.join(tmp, "peer"), port=0)
    try:
        cfg = Settings()
        cfg.store_root = os.path.join(tmp, "store")
        cfg.persist = True
        seed_store = DatasetStore(cfg)          # build WITHOUT peers:
        ds = seed_store.create("repb")          # pushes don't skew the
        rng = np.random.default_rng(0)          # ingest timing
        chunk = 262_144
        for off in range(0, n, chunk):
            k = min(chunk, n - off)
            ds.append_columns({
                "x1": rng.normal(size=k), "x2": rng.normal(size=k),
                "y": rng.integers(0, 2, k)})
            seed_store.save("repb")
        seed_store.finish("repb")

        cfg.replica_peers = peer.addr
        store = DatasetStore(cfg)
        t0 = time.time()
        store.load_all()                        # recovery re-queues all
        drained = store.replication_drain(timeout_s=600.0)
        push_s = time.time() - t0
        snap = store.replication_snapshot()
        assert drained and snap["max_lag_bytes"] == 0, snap
        push_bytes = snap["counters"]["push_bytes"]
        store.stop_replication()

        # remote repair latency: one chunk lost, healed via the peer
        chunks_dir = os.path.join(cfg.store_root, "repb", "chunks")
        victim = sorted(os.listdir(chunks_dir))[0]
        vbytes = os.path.getsize(os.path.join(chunks_dir, victim))
        os.remove(os.path.join(chunks_dir, victim))
        store2 = DatasetStore(cfg)
        store2.load("repb")
        t0 = time.time()
        report = store2.scrub("repb")
        repair_s = time.time() - t0
        assert report["ok"] and report["missing"] == 1, report
        store2.stop_replication()
        return {
            "rows": n,
            "chunks": snap["counters"]["pushes"],
            "push_mb": round(push_bytes / 1e6, 1),
            "push_rps": round(n / push_s),
            "push_mb_s": round(push_bytes / 1e6 / push_s, 1),
            "repair_chunk_mb": round(vbytes / 1e6, 2),
            "repair_duration_ms": round(repair_s * 1000.0, 1),
        }
    finally:
        peer.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def tree_bench() -> dict:
    """Phase-level microbenchmark of the tree-fit hot loops: one level's
    histogram accumulation, one level's routing pass, and a full-tree
    descent, timed separately on the fused Pallas kernel path and the
    XLA contraction oracle (LO_TPU_TREE_KERNEL=0 equivalent) over the
    same HIGGS-shaped inputs — so the record shows *where* the
    tree-family speedup lands, not just the end-to-end fit_s delta."""
    import numpy as np

    if N_TREE <= 0:
        return {}
    import jax
    from functools import partial

    from learningorchestra_tpu.models import trees
    from learningorchestra_tpu.ops import pallas_kernels as pk

    n, d, n_bins, max_depth, S = N_TREE, 28, 32, 5, 2
    NL = 2 ** (max_depth - 1)
    M = 2 ** (max_depth + 1) - 1
    rng = np.random.default_rng(0)
    codes = rng.integers(0, n_bins, (n, d), dtype=np.uint8).astype(np.uint8)
    stats = rng.random((S, n), dtype=np.float32)
    rel = rng.integers(0, NL, n).astype(np.int32)
    active = np.ones(n, bool)
    assign = (rel + NL - 1).astype(np.int32)
    best_f = rng.integers(0, d, NL).astype(np.int32)
    best_t = rng.integers(0, n_bins, NL).astype(np.int32)
    split = np.ones(NL, bool)
    feat = rng.integers(0, d, M).astype(np.int32)
    thr = rng.integers(0, n_bins, M).astype(np.int32)
    internal = (np.arange(M) < M // 2)

    tile = pk.tree_tile(d, n_bins)
    blk, nbk, n_pad = trees._block_shape(n, d * n_bins)

    def padded(a, k, axis0=True):
        pad = [(0, 0)] * a.ndim
        pad[0 if axis0 else a.ndim - 1] = (0, k - a.shape[0 if axis0 else -1])
        return np.pad(a, pad)

    n_pad_k = -(-n // tile) * tile
    hdt = trees._hist_dtype()
    # The kernels take the bin matrix transposed (rows in lanes); the
    # fits transpose it once per tree, outside what is timed here.
    variants = dict(
        kernel=dict(
            n_pad=n_pad_k, codes=lambda a: np.ascontiguousarray(a.T),
            hist=jax.jit(partial(pk.tree_histogram, n_nodes=NL,
                                 n_bins=n_bins, tile=tile,
                                 operand_dtype=hdt)),
            route=jax.jit(partial(pk.tree_route_level, tile=tile)),
            descend=jax.jit(partial(pk.tree_descend, max_depth=max_depth)),
        ),
        xla=dict(
            n_pad=n_pad, codes=lambda a: a,
            hist=jax.jit(partial(trees._hist_level_xla, n_nodes=NL,
                                 n_bins=n_bins, blk=blk)),
            route=jax.jit(partial(trees._route_level_xla, blk=blk)),
            descend=jax.jit(partial(trees._descend, max_depth=max_depth)),
        ))

    def best_of(f, *args, reps=3):
        jax.tree.map(lambda a: a.block_until_ready(), f(*args))  # compile
        times = []
        for _ in range(reps):
            t0 = time.time()
            out = f(*args)
            jax.tree.map(lambda a: a.block_until_ready(), out)
            times.append(time.time() - t0)
        return min(times)

    doc = {"rows": n, "d": d, "n_bins": n_bins, "tile": tile,
           "oracle_block": blk}
    for name, v in variants.items():
        np_ = v["n_pad"]
        B_p, B_d = v["codes"](padded(codes, np_)), v["codes"](codes)
        stats_p = padded(stats, np_, axis0=False)
        rel_p, act_p, asg_p = (padded(rel, np_), padded(active, np_),
                               padded(assign, np_))
        doc[name] = {
            "hist_ms": round(1e3 * best_of(
                v["hist"], B_p, stats_p, rel_p, act_p), 3),
            "route_ms": round(1e3 * best_of(
                v["route"], B_p, rel_p, act_p, asg_p, best_f, best_t,
                split), 3),
            "descend_ms": round(1e3 * best_of(
                v["descend"], B_d, feat, thr, internal), 3),
        }
    doc["speedup"] = {
        k.replace("_ms", ""): round(doc["xla"][k] / doc["kernel"][k], 2)
        for k in ("hist_ms", "route_ms", "descend_ms")
        if doc["kernel"][k] > 0}
    return doc


def _tune_config_grid(family: str, pop: int) -> list:
    """``pop`` same-family configs varying the knobs a real sweep varies
    — deliberately INCLUDING static-shape ones (depth, bins, rounds,
    width, iteration counts): serially those recompile per distinct
    value, while the population program masks them into one compile, so
    the A/B measures exactly the amortization the tune plane sells."""
    if family == "dt":
        return [{"max_depth": 2 + (i % 4),
                 "n_bins": (8, 16, 32)[i % 3]} for i in range(pop)]
    if family == "lr":
        return [{"solver": "adam", "iters": 40 + 10 * (i % 6),
                 "lr": round(0.02 * 1.3 ** (i % 8), 6),
                 "l2": (1e-4, 1e-3)[i % 2]} for i in range(pop)]
    if family == "gb":
        return [{"max_depth": 3 + (i % 3), "n_rounds": 8 + 2 * (i % 5),
                 "step_size": (0.05, 0.1, 0.2)[i % 3],
                 "n_bins": 16} for i in range(pop)]
    if family == "mlp":
        return [{"hidden": (32, 64, 96, 128)[i % 4],
                 "iters": 20 + 5 * (i % 2),
                 "lr": (0.005, 0.01, 0.02)[i % 3]} for i in range(pop)]
    raise ValueError(family)


def tune_bench(runtime=None, families=("dt", "lr", "gb", "mlp")) -> dict:
    """Hyperparameter-search A/B (PR 18): a population of
    ``N_TUNE_CONFIGS`` same-family configs fitted as ONE vmapped device
    sweep (models/tune.py, folds=1, rungs=1 — halving off so both arms
    do identical work) against the same configs fitted serially through
    the builder's trainer entry points. Records wall-clock, speedup and
    BACKEND COMPILE COUNTS per family: the population arm compiles a
    handful of one-time programs (segment driver + scorer + their
    helpers) where the serial arm re-compiles per distinct static
    shape — and an identical second sweep measures the MARGINAL
    per-wave cost (``compiles_per_wave``), expected 0 and bounded 2.

    The ``gate`` block arms at the full population of 16 (the smoke
    sizes tier-1 runs are compile-dominated noise) and requires the
    worst family's speedup ≥ 3x and per-wave marginal compiles ≤ 2."""
    import numpy as np

    n, pop = N_TUNE_ROWS, N_TUNE_CONFIGS
    if n <= 0 or pop <= 0:
        return {}
    import jax

    from learningorchestra_tpu.config import Settings
    from learningorchestra_tpu.models import tune as tune_mod
    from learningorchestra_tpu.models.registry import get_trainer
    from learningorchestra_tpu.parallel.mesh import MeshRuntime
    from learningorchestra_tpu.utils import resources as res_mod

    cfg = Settings()
    if runtime is None:
        runtime = MeshRuntime(cfg)
    res_mod.ensure_listener()
    rng = np.random.default_rng(7)
    d = 12
    X = rng.normal(size=(n, d)).astype(np.float32)
    y = (X[:, :4].sum(axis=1) + 0.5 * rng.normal(size=n) > 0
         ).astype(np.int32)

    doc: dict = {"rows": n, "population": pop}
    speedups = []
    for family in families:
        configs = _tune_config_grid(family, pop)
        # Serial arm FIRST, doing what a real serial sweep does: fit AND
        # score every candidate (the population arm's rung scoring is
        # inside its wall below). Ordering matters for the compile
        # ledger: shared one-time prep programs (per-width param init,
        # quantile edges) land on whichever arm runs first, so serial-
        # first leaves the population arm's compile count at its true
        # marginal cost — the segment driver + the scorer.
        trainer = get_trainer(family)
        prep = getattr(trainer, "host_prep", None)
        c0 = res_mod.compile_snapshot()["compiles"]
        t0 = time.time()
        serial_best = 0.0
        for hp in configs:
            extra = prep(X, **hp) if prep is not None else {}
            model = trainer(runtime, X, y, 2, **dict(hp, **extra))
            probs = model.predict_proba(runtime, X)
            acc = float((probs.argmax(axis=1) == y).mean())
            serial_best = max(serial_best, acc)
        serial_wall = time.time() - t0
        compiles_serial = res_mod.compile_snapshot()["compiles"] - c0

        c0 = res_mod.compile_snapshot()["compiles"]
        t0 = time.time()
        board = tune_mod.sweep(runtime, X, y, 2, family, configs,
                               cfg=cfg, folds=1, rungs=1)
        pop_wall = time.time() - t0
        compiles_pop = res_mod.compile_snapshot()["compiles"] - c0

        # Per-wave marginal compile cost — the acceptance claim. The
        # first sweep's ledger above includes the one-time driver +
        # scorer programs; every further wave of the same shapes reuses
        # them, so an identical second sweep measures what wave 2..N of
        # a real multi-wave sweep pays: expected 0, bounded <= 2.
        c0 = res_mod.compile_snapshot()["compiles"]
        tune_mod.sweep(runtime, X, y, 2, family, configs,
                       cfg=cfg, folds=1, rungs=1)
        compiles_per_wave = res_mod.compile_snapshot()["compiles"] - c0

        speedup = serial_wall / pop_wall if pop_wall > 0 else 0.0
        speedups.append(speedup)
        doc[family] = {
            "pop_wall_s": round(pop_wall, 3),
            "serial_wall_s": round(serial_wall, 3),
            "speedup": round(speedup, 2),
            "compiles_pop": compiles_pop,
            "compiles_per_wave": compiles_per_wave,
            "compiles_serial": compiles_serial,
            "waves": board["waves"],
            "winner_mean_score": board["winner"]["mean_score"],
        }
    # Armed only at the full 16-config population (the driver default):
    # tier-1 smoke runs at toy sizes where compile noise dominates both
    # arms and a hard floor would flap.
    armed = pop >= 16 and n >= 2_000
    max_marginal = max(doc[f]["compiles_per_wave"] for f in families)
    doc["gate"] = {"speedup_floor": 3.0, "armed": armed,
                   "min_speedup": round(min(speedups), 2),
                   "max_compiles_per_wave": max_marginal,
                   "pass": bool(min(speedups) >= 3.0
                                and max_marginal <= 2)}
    if armed:
        assert doc["gate"]["pass"], f"tune speedup gate failed: {doc}"
    return doc


#: Per-family held-out accuracy gates. Floors catch broken fits; the
#: orderings (every tree family must beat lr) pin the published HIGGS
#: difficulty structure the workload was calibrated to.
ACC_FLOOR = {"lr": 0.62, "nb": 0.62, "dt": 0.66, "rf": 0.70, "gb": 0.75}


def main() -> None:
    from learningorchestra_tpu.parallel import distributed

    # A device benchmark: every number below is a statement about the
    # chip, so it runs there or not at all — and names the device.
    device = distributed.device_info()
    if device["platform"] != "tpu":
        raise SystemExit(f"bench.py measures the TPU; found {device}")
    distributed.place_compile_cache()  # repeat bench runs stay warm

    from learningorchestra_tpu.catalog.store import DatasetStore
    from learningorchestra_tpu.config import Settings
    from learningorchestra_tpu.models.builder import ModelBuilder
    from learningorchestra_tpu.parallel.mesh import MeshRuntime

    from learningorchestra_tpu.models import flops as flops_mod
    from learningorchestra_tpu.models import trees as trees_mod

    peak_flops = flops_mod.device_peak("flops", device["kind"])
    peak_bw = flops_mod.device_peak("bw", device["kind"])
    if peak_flops is None or peak_bw is None:
        raise SystemExit(
            f"no published peaks for device kind {device['kind']!r} "
            "(models/flops.py DEVICE_PEAKS; LO_TPU_PEAK_FLOPS/"
            "LO_TPU_PEAK_BW override)")

    scan = scan_bench()
    tree = tree_bench()
    replication = replication_bench()
    #: Which tree-fit path the sweep below runs (the config flags) —
    #: selects the matching flops/bytes cost model.
    tree_kernel = trees_mod._use_tree_kernel()

    cfg = Settings()
    cfg.persist = False
    cfg.persist_models = False
    store = DatasetStore(cfg)
    runtime = MeshRuntime(cfg)
    store.create("bench_train", columns=higgs_like_columns(N_TRAIN, 0),
                 finished=True)
    store.create("bench_test", columns=higgs_like_columns(N_TEST, 1),
                 finished=True)
    mb = ModelBuilder(store, runtime, cfg)
    classifiers = ["lr", "dt", "rf", "gb", "nb"]
    n_features = 28

    # Hyperparameter-search A/B on the same mesh, BEFORE the headline
    # warmup (its programs are disjoint from the sweep's, so ordering
    # only affects which section pays process-global JAX init).
    tune = tune_bench(runtime)

    # Resource accounting (ISSUE 10): the compile-seconds deltas around
    # the warmup vs the measured sweeps quantify cold-vs-warm compile
    # amortization — the cost a long-lived server pays once and a
    # per-job cold process pays every time.
    from learningorchestra_tpu.utils import resources as res_mod

    res_mod.ensure_listener()
    compile_t0 = res_mod.compile_seconds()

    # warmup (compile + host->device transfer)
    cfg.max_concurrent_fits = 2
    mb.build("bench_train", "bench_test", "warm", classifiers, "label")
    cold_compile_s = res_mod.compile_seconds() - compile_t0

    def check_gates(fam):
        # Accuracy gates: floors per family, and the HIGGS ordering
        # (trees beat linear) on every sweep.
        for kind, floor in ACC_FLOOR.items():
            assert fam[kind]["accuracy"] > floor, (kind, fam)
        for tree in ("dt", "rf", "gb"):
            assert fam[tree]["accuracy"] > fam["lr"]["accuracy"], fam

    def sweep_doc(reports):
        bad = [r.kind for r in reports if "error" in r.metrics]
        assert not bad, f"failed fits: {bad}"
        return {r.kind: {
            "fit_s": round(r.fit_time, 3),
            "device_s": round(r.metrics.get("device_s", 0.0), 3),
            "accuracy": round(r.metrics.get("accuracy", 0.0), 4),
        } for r in reports}

    # Instrumented SERIALIZED sweep: one family in its device phase at a
    # time, so each device_s span is uncontended — the per-family device
    # occupancy MFU divides against, and the per-family resource
    # watermarks (peak_hbm_bytes, residual compile_s) are attributable.
    res_mod.reset_watermarks()
    cfg.max_concurrent_fits = 1
    serial = sweep_doc(mb.build("bench_train", "bench_test", "profiled",
                                classifiers, "label"))
    check_gates(serial)
    family_watermarks = res_mod.family_watermarks()
    families = {}
    for kind, doc in serial.items():
        fl = flops_mod.build_flops(kind, N_TRAIN, N_TEST, n_features, 2,
                                   tree_kernel=tree_kernel)
        m = flops_mod.mfu(fl, doc["device_s"], peak_flops)
        families[kind] = dict(doc, flops=fl,
                              mfu=round(m, 6) if m is not None else None)
        # Tree families are memory-bound on the kernel path (flops.py
        # module docstring): record the roofline figure that matters.
        by = flops_mod.fit_bytes(kind, N_TRAIN, n_features, 2,
                                 tree_kernel=tree_kernel)
        bw = flops_mod.bw_util(by, doc["device_s"], peak_bw)
        if bw is not None:
            families[kind].update(hbm_bytes=by, bw_util=round(bw, 6))
    serial_sum_fit_s = sum(doc["fit_s"] for doc in serial.values())

    # Median of 3 measured PIPELINED sweeps: a single sample would bake
    # host run-to-run jitter into the record. Each sweep runs under an active trace at full sampling
    # — what a traced production job pays — and a second set of 3 runs
    # with LO_TPU_TRACE_SAMPLE=0 semantics, so the record carries the
    # measured tracing overhead (ISSUE 9 gate: < 2% on the smoke sweep)
    # and the trajectory catches an instrumentation-cost regression the
    # same way it catches a compute one.
    from learningorchestra_tpu.utils import tracing

    cfg.max_concurrent_fits = 2

    def one_sweep(name: str, sample: float):
        tracing.set_sample(sample)
        try:
            t0 = time.time()
            with tracing.trace(f"bench.sweep.{name}"):
                reports = mb.build("bench_train", "bench_test",
                                   f"bench_{name}", classifiers, "label")
            return time.time() - t0, sweep_doc(reports)
        finally:
            tracing.set_sample(None)

    # INTERLEAVED pairs (traced, untraced) so slow machine-state drift
    # lands on both arms instead of biasing whichever ran last.
    warm_compile_t0 = res_mod.compile_seconds()
    times, sweeps, off_times, off_sweeps = [], [], [], []
    for i in range(3):
        t, s = one_sweep(f"t{i}", 1.0)               # traced (the default)
        times.append(t)
        sweeps.append(s)
        t, s = one_sweep(f"u{i}", 0.0)               # sampling off
        off_times.append(t)
        off_sweeps.append(s)
    elapsed = sorted(times)[1]
    median_sweep = sweeps[times.index(elapsed)]
    untraced_s = sorted(off_times)[1]
    overhead_pct = (elapsed - untraced_s) / untraced_s * 100
    tracing_overhead = {
        "traced_median_s": round(elapsed, 4),
        "untraced_median_s": round(untraced_s, 4),
        "overhead_pct": round(overhead_pct, 3),
        # The ISSUE 9 acceptance verdict, recorded explicitly so the
        # trajectory (and a reviewer) reads pass/fail without redoing
        # the arithmetic. Not a hard exit: at sub-scale smoke sizes
        # rig jitter routinely exceeds 2% in either direction, and a
        # flapping bench would mask real regressions — the driver/
        # reviewer judges the flag against the run's scale.
        "pass_2pct": bool(overhead_pct < 2.0),
    }
    # Six measured sweeps after warmup: residual compile here is what a
    # steady-state server re-pays (ideally ~0 — amortization evidence).
    warm_compile_s = res_mod.compile_seconds() - warm_compile_t0
    resources_block = {
        "cold_compile_s": round(cold_compile_s, 3),
        "warm_compile_s_6_sweeps": round(warm_compile_s, 3),
        "compile": res_mod.compile_snapshot(),
        "host": res_mod.host_snapshot(),
        "device_source": res_mod.device_snapshot().get("source"),
        # Per-family watermarks from the serialized instrumented sweep
        # (same provenance as device_s/mfu): peak device bytes at each
        # family's phases and any compile residue it still paid.
        "families": family_watermarks,
    }
    for fam in sweeps + off_sweeps:
        check_gates(fam)
    # Per-family fit times exclude scheduler waits by construction
    # (models/builder.py fit_device), so their sum estimates the
    # serialized sweep and wall-clock below it demonstrates overlap.
    overlap_sum = sum(doc["fit_s"] for doc in median_sweep.values())
    accs = {k: v["accuracy"] for k, v in families.items()}
    print(json.dumps({
        "metric": "model_builder 5-classifier sweep wall-clock "
                  "(HIGGS-11M, steady-state, pipelined; accs "
                  + ",".join(f"{k}={v}" for k, v in sorted(accs.items()))
                  + ")",
        "value": round(elapsed, 4),
        "unit": "seconds",
        "vs_baseline": round(CPU_BASELINE_11M_S / elapsed, 2),
        "families": families,
        "sweep_times_s": [round(t, 3) for t in times],
        "overlap": {
            "wall_s": round(elapsed, 3),
            "sum_fit_s": round(overlap_sum, 3),
            "saved_s": round(overlap_sum - elapsed, 3),
            "serialized_sweep_sum_fit_s": round(serial_sum_fit_s, 3),
        },
        "tracing_overhead": tracing_overhead,
        "resources": resources_block,
        "device": device,
        "peak_flops": peak_flops,
        "peak_bw": peak_bw,
        "tree_kernel": tree_kernel,
        "scan_bench": scan,
        "tree_bench": tree,
        "replication_bench": replication,
        "tune_bench": tune,
    }))


if __name__ == "__main__":
    main()
