"""At-scale engine benchmarks on the TPU (BASELINE.md configs).

Synthetic datasets shaped like the baseline workloads (no egress in the
bench environment):

- ``higgs``: 5-classifier sweep on HIGGS-shape data (11M × 28 floats,
  binary label) — the north-star config (≥10× Spark-CPU on a v5e-8).
- ``tsne``: MNIST-60k-shape embed (60000 × 784) — reports the kNN+
  calibration front-end time and steady-state seconds/iteration of the
  Pallas repulsion kernel, plus the projected full-embed time.
- ``pca``: HIGGS-shape 2-component embedding.
- ``analytics``: histogram (mesh bincount) + projection on 50M rows.

Usage: python benchmarks/bench_scale.py [higgs|tsne|pca|analytics|all]
Prints one JSON line per measurement.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _emit(name, seconds, **extra):
    print(json.dumps({"bench": name, "seconds": round(seconds, 3), **extra}),
          flush=True)


def _higgs_like(n, d=28, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    w = rng.normal(size=d).astype(np.float32)
    y = ((X @ w + 0.5 * rng.normal(size=n)) > 0).astype(np.int32)
    return X, y


def bench_higgs(runtime, n=11_000_000):
    from learningorchestra_tpu.models.registry import get_trainer

    X, y = _higgs_like(n)
    for kind in ("lr", "nb", "dt", "gb", "rf"):
        trainer = get_trainer(kind)
        # warmup on a slice to populate the jit cache with these shapes?
        # shapes differ per dataset size, so compile cost is part of a
        # cold fit; report warm fit separately via a second run.
        t0 = time.time()
        model = trainer(runtime, X, y, 2)
        cold = time.time() - t0
        t0 = time.time()
        model = trainer(runtime, X, y, 2, seed=1)
        warm = time.time() - t0
        probs = model.predict_proba(runtime, X[:1_000_000])
        acc = float((np.argmax(probs, 1) == y[:1_000_000]).mean())
        _emit(f"higgs11m.fit.{kind}", warm, cold_s=round(cold, 3),
              acc_1m=round(acc, 4), rows=n)


def _manifold_mix(n, d, rng, n_cls=10):
    """MNIST-60k stand-in: each class a curved 10-D manifold embedded in
    d dims. (The earlier 10-gaussian-blob stand-in was degenerate for a
    viz benchmark — 60k points collapsing onto 10 dots, with kNN hub
    in-degrees in the thousands at the blob cores; class manifolds have
    the moderate hubness real image data shows.)"""
    t = rng.normal(size=(n, 10)).astype(np.float32)
    cls = rng.integers(0, n_cls, n)
    X = np.zeros((n, d), np.float32)
    for c in range(n_cls):
        m = cls == c
        A = rng.normal(size=(10, d)).astype(np.float32) * 0.8
        B = rng.normal(size=(10, d)).astype(np.float32) * 0.4
        off = rng.normal(size=d).astype(np.float32) * 3.0
        X[m] = t[m] @ A + np.tanh(t[m]) @ B + off
    return X + rng.normal(size=(n, d)).astype(np.float32) * 0.2


def bench_tsne(runtime, n=60_000, d=784):
    import jax.numpy as jnp

    from learningorchestra_tpu.ops import pallas_kernels
    from learningorchestra_tpu.viz import tsne as tz
    from learningorchestra_tpu.viz.pca import pca_embed

    rng = np.random.default_rng(0)
    X = _manifold_mix(n, d, rng)

    # The headline: the FULL embed as the service runs it (PCA-50 front
    # end + kNN + calibration + edge table + 750 descent iterations).
    t0 = time.time()
    emb = tz.tsne_embed(runtime, X, perplexity=30.0, iters=750,
                        exaggeration_iters=250)
    _emit("tsne60k.full_embed", time.time() - t0, shape=list(emb.shape))

    t0 = time.time()
    Xp = pca_embed(runtime, X, k=50)
    _emit("tsne60k.pca50", time.time() - t0)

    tile = 1024
    Xpad, n_valid = tz._pad_rows(Xp, tile)
    k = 90
    t0 = time.time()
    d2k, idx = tz._knn(jnp.asarray(Xpad), k=k, tile=tile)
    d2k.block_until_ready()
    _emit("tsne60k.knn", time.time() - t0, k=k)
    t0 = time.time()
    P = tz._calibrate(d2k[:n_valid], jnp.float32(30.0))
    P.block_until_ready()
    _emit("tsne60k.calibrate", time.time() - t0)

    # steady-state descent iteration (Pallas repulsion, scatter-free
    # attraction over the host-built edge table)
    t0 = time.time()
    table = tz._edge_table(np.asarray(idx)[:n_valid],
                           np.asarray(P), len(Xpad), n_valid)
    _emit("tsne60k.edge_table", time.time() - t0,
          table_cols=int(table[0].shape[1]),
          overflow_edges=int(table[2].shape[0]))
    sym_idx, sym_w, ov_src, ov_dst, ov_w = (jnp.asarray(a) for a in table)
    Y = jnp.asarray(rng.normal(scale=1e-4, size=(len(Xpad), 2)), jnp.float32)
    vel = jnp.zeros_like(Y)
    gains = jnp.ones_like(Y)
    nv = jnp.float32(n_valid)
    args = (sym_idx, sym_w, ov_src, ov_dst, ov_w, nv, jnp.float32(12.0),
            jnp.float32(1250.0), jnp.float32(0.5))
    Y, vel, gains = tz._step(Y, vel, gains, *args, tile=tile,
                             use_pallas=True)  # compile
    Y.block_until_ready()
    iters = 20
    t0 = time.time()
    for _ in range(iters):
        Y, vel, gains = tz._step(Y, vel, gains, *args, tile=tile,
                                 use_pallas=True)
    Y.block_until_ready()
    per_iter = (time.time() - t0) / iters
    _emit("tsne60k.step_pallas", per_iter,
          projected_750_iters_s=round(per_iter * 750, 1))
    # XLA-scan fallback for comparison
    Y, vel, gains = tz._step(Y, vel, gains, *args, tile=tile,
                             use_pallas=False)
    Y.block_until_ready()
    t0 = time.time()
    for _ in range(iters):
        Y, vel, gains = tz._step(Y, vel, gains, *args, tile=tile,
                                 use_pallas=False)
    Y.block_until_ready()
    _emit("tsne60k.step_xla_scan", (time.time() - t0) / iters)


def bench_pca(runtime, n=11_000_000, d=28):
    from learningorchestra_tpu.viz.pca import pca_embed

    X, _ = _higgs_like(n, d)
    t0 = time.time()
    emb = pca_embed(runtime, X, k=2)
    cold = time.time() - t0
    t0 = time.time()
    emb = pca_embed(runtime, X, k=2)
    _emit("higgs11m.pca2", time.time() - t0, cold_s=round(cold, 3),
          shape=list(emb.shape))


def bench_analytics(runtime, n=50_000_000):
    from learningorchestra_tpu.ops.histogram import field_counts

    rng = np.random.default_rng(0)
    col = rng.integers(0, 1000, n).astype(np.int64)
    t0 = time.time()
    counts = field_counts(runtime, col)
    cold = time.time() - t0
    t0 = time.time()
    counts = field_counts(runtime, col)
    _emit("analytics.histogram_50m", time.time() - t0,
          cold_s=round(cold, 3), bins=len(counts))


def main():
    from learningorchestra_tpu.config import Settings
    from learningorchestra_tpu.parallel import distributed
    from learningorchestra_tpu.parallel.mesh import MeshRuntime

    # Device benchmarks: they run on the chip or not at all, and every
    # run names the device its numbers belong to.
    device = distributed.device_info()
    if device["platform"] != "tpu":
        raise SystemExit(f"bench_scale.py measures the TPU; found {device}")
    distributed.place_compile_cache()  # steady-state numbers, like bench.py

    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    cfg = Settings()
    cfg.persist = False
    runtime = MeshRuntime(cfg)
    print(json.dumps({"device": device}), flush=True)
    if which in ("higgs", "all"):
        bench_higgs(runtime)
    if which in ("tsne", "all"):
        bench_tsne(runtime)
    if which in ("pca", "all"):
        bench_pca(runtime)
    if which in ("analytics", "all"):
        bench_analytics(runtime)


if __name__ == "__main__":
    main()
