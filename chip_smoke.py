"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once on one TPU chip, through the entry points a
user calls: the REST server (started in THIS process on a free localhost
port — one process holds the chip, and no child that needs JAX is
started) driven over HTTP with the client SDK. The data is the
HIGGS-like workload of ``higgs_like_xy`` below (28 float32 features +
binary label, seeded) written to CSV under a scratch directory and
ingested by ``file://`` URL; nothing needs the network.

Phases, each timed and printed as one JSON line:

1. ingest     POST /files of the train / test / viz CSVs
2. catalog    one projection, one dtype coercion, one histogram
3. sweep      POST /models lr/dt/rf/gb/nb at the Spark-parity defaults,
              tree kernels on; the ``ACC_FLOOR`` accuracy floors; cold and warm
4. online     one model into the AOT plane, 32 predict_online requests,
              answers equal the batch predictions of the same rows
5. viz        PCA and t-SNE plots over REST; the repulsion kernel against
              its XLA reference
   (then phase 3 once more at 11,000,000 rows — the repo's headline
   size — placed straight into the store, as perfbench does)
6. shutdown   drain, stop, join

``--chips 4`` runs, instead of all that, only the mesh comparison: the
sweep on a one-device mesh and on the (4,1,1) mesh in one process
(library entry points, no server), and the row-sharded t-SNE repulsion
and descent against the single-device ones.

Any failed phase makes the exit code non-zero. Without a TPU nothing
runs. The LAST line of stdout is exactly
``{"ok": ..., "device": {"platform": ..., "kind": ..., "count": ...}}``;
everything else is printed before it, and logs go to stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

#: HIGGS's 10% sample, and the evaluation split.
N_TRAIN = 1_100_000
N_TEST = 100_000
#: The headline size (perfbench's ``higgs-11m``), placed straight into
#: the store (no CSV).
N_HEADLINE = 11_000_000
#: Rows of the PCA / t-SNE plots (16 repulsion tiles of 512).
N_VIZ = 8192
TSNE_ITERS = 250
CLASSIFIERS = ["lr", "dt", "rf", "gb", "nb"]
D = 28
FEATURES = [f"f{i}" for i in range(D)]
#: Statistical-parity bound between two fits of one family that sum in
#: different orders (tests/test_tree_kernel.py uses the same ±0.01).
ACC_PARITY = 0.01
#: Per-family held-out accuracy floors on the workload below: they catch
#: a broken fit, not a slow one.
ACC_FLOOR = {"lr": 0.62, "nb": 0.62, "dt": 0.66, "rf": 0.70, "gb": 0.75}

# The HIGGS-like workload. Each family gets its own signal, classes
# balanced 50/50, so that shallow-tree ensembles beat linear models as on
# the real HIGGS (Baldi et al. 2014):
# - three *mean-shift* features (±delta): the linear food lr and nb eat;
# - five *bimodal* features: class 1 draws from a two-mode mixture whose
#   mean AND variance match class 0's N(0,1), so lr and gaussian-nb are
#   blind to them while axis-aligned tree splits separate the modes;
# - four *correlation-sign pairs*: (a, b) jointly gaussian with rho =
#   +0.55 for class 1 and -0.55 for class 0; both marginals are N(0,1),
#   so only feature interactions (ensembled trees) can learn them;
# - the remaining features are pure N(0,1) noise, as distractors.
_DELTA = 0.24          # mean-shift half-gap (linear signal strength)
_MODE = 0.95           # bimodal mode offset; mode sd keeps variance at 1
_RHO = 0.55            # correlation magnitude of the sign pairs
_SHIFT_FEATURES = (10, 11, 12)
_BIMODAL_FEATURES = range(13, 18)
_PAIR_FEATURES = tuple((20 + 2 * j, 21 + 2 * j) for j in range(4))


def higgs_like_xy(n: int, seed: int):
    """(X float32 [n, 28], y int32 [n]) with the calibrated class
    structure above."""
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, n).astype(np.int32)
    X = rng.normal(size=(n, D)).astype(np.float32)
    mode_sd = float(np.sqrt(1.0 - _MODE * _MODE))
    for f in _BIMODAL_FEATURES:
        sign = rng.integers(0, 2, n) * 2 - 1
        bim = (_MODE * sign + mode_sd * rng.normal(size=n)).astype(
            np.float32)
        X[:, f] = np.where(y == 1, bim, X[:, f])
    resid = float(np.sqrt(1.0 - _RHO * _RHO))
    for a, b in _PAIR_FEATURES:
        z = rng.normal(size=n).astype(np.float32)
        e = rng.normal(size=n).astype(np.float32)
        r = np.where(y == 1, _RHO, -_RHO).astype(np.float32)
        X[:, a] = z
        X[:, b] = r * z + np.float32(resid) * e
    for f in _SHIFT_FEATURES:
        X[:, f] += np.where(y == 1, _DELTA, -_DELTA).astype(np.float32)
    return X, y


def higgs_like_columns(n: int, seed: int) -> dict:
    """The same workload as catalog columns."""
    X, y = higgs_like_xy(n, seed)
    cols = {f"f{i}": X[:, i] for i in range(D)}
    cols["label"] = y.astype(np.int64)
    return cols


def final_line(ok: bool, device: dict) -> dict:
    """The object the last line of stdout carries: exactly ``ok`` and
    ``device``, and in ``device`` exactly ``platform``, ``kind``,
    ``count``. The driver reads these keys; no other may appear."""
    return {"ok": bool(ok),
            "device": {"platform": device.get("platform"),
                       "kind": device.get("kind"),
                       "count": device.get("count", 0)}}


def emit(phase: str, t0: float, **fields) -> None:
    print(json.dumps({"phase": phase,
                      "seconds": round(time.time() - t0, 3), **fields}),
          flush=True)


def build_native_parser() -> str:
    """``make -C native`` — the .so is git-ignored, so it exists only if
    built here from the committed source. Returns which parser ingest
    will run; without a compiler that is pandas, said so, not hidden."""
    if shutil.which("make") is None or shutil.which(
            os.environ.get("CXX", "g++")) is None:
        return "pandas (no compiler here to build native/csv_parser.cpp)"
    subprocess.run(["make", "-C", os.path.join(ROOT, "native")],
                   check=True, stdout=sys.stderr, stderr=sys.stderr)
    from learningorchestra_tpu.catalog import native

    if not native.available():
        raise RuntimeError("native/libcsv_parser.so was built but does "
                           "not load")
    return "native"


def write_csv(path: str, n: int, seed: int):
    """One HIGGS-like CSV; returns its (X float32, y) for later checks.
    Float32 values are written in their shortest round-trip form."""
    import pyarrow as pa
    import pyarrow.csv as pacsv

    cols = higgs_like_columns(n, seed)
    pacsv.write_csv(pa.table(cols), path)
    return np.stack([cols[f] for f in FEATURES], axis=1), cols["label"]


def check_sweep(db, prefix: str) -> dict:
    """Every ``<prefix>_<c>`` metadata doc carries f1/accuracy/fit_time
    and clears the accuracy floor for its family."""
    out = {}
    for kind in CLASSIFIERS:
        doc = db.read_file(f"{prefix}_{kind}", limit=1)[0]
        if not doc.get("finished") or doc.get("error"):
            raise RuntimeError(f"{prefix}_{kind} did not finish: {doc}")
        for key in ("f1", "accuracy", "fit_time"):
            if not isinstance(doc.get(key), (int, float)):
                raise RuntimeError(f"{prefix}_{kind} lacks {key}: {doc}")
        if not doc["accuracy"] > ACC_FLOOR[kind]:
            raise RuntimeError(
                f"{kind} accuracy {doc['accuracy']} under the floor "
                f"{ACC_FLOOR[kind]}")
        out[kind] = {"accuracy": round(doc["accuracy"], 4),
                     "f1": round(doc["f1"], 4),
                     "fit_time": round(doc["fit_time"], 3)}
    return out


def timed_sweep(model, db, obs, train: str, prefix: str) -> dict:
    """One synchronous POST /models; wall-clock, and the XLA compile
    seconds and persistent-cache hits utils/resources.py counted in its
    window (the ``compile`` section of GET /metrics)."""
    c0, t0 = obs.metrics()["compile"], time.time()
    model.create_model(train, "test", prefix, CLASSIFIERS, "label")
    wall = time.time() - t0
    c1 = obs.metrics()["compile"]
    return {"wall_s": round(wall, 3),
            "compile_s": round(c1["compile_s"] - c0["compile_s"], 3),
            "compiles": c1["compiles"] - c0["compiles"],
            "persistent_cache_hits": (c1["persistent_cache_hits"]
                                      - c0["persistent_cache_hits"]),
            "families": check_sweep(db, prefix)}


def repulsion_check(n: int, seed: int) -> dict:
    """The compiled ``tsne_repulsion`` kernel against its references.

    First tests/test_pallas.py's own case — 256 rows, tile 128, a masked
    padding tail, a float64 NumPy evaluation, that test's tolerances —
    run on the chip. Then one call at the plot's size against the XLA
    scan path viz/tsne.py falls back to. That reference's matmul runs at
    full f32 precision (at the TPU's default, bf16 passes, its
    ``|a|²+|b|²-2ab`` distance cancels catastrophically), and both sides
    sum 8,192 float32 terms per force, so F is held to the test's 1e-5
    relative to the force scale, not as an absolute."""
    import jax
    import jax.numpy as jnp

    from learningorchestra_tpu.ops import pallas_kernels
    from learningorchestra_tpu.viz import tsne

    rng = np.random.default_rng(seed)
    Y = rng.normal(size=(256, 2)).astype(np.float32)
    valid = (np.arange(256) < 201).astype(np.float32)
    Z, F = pallas_kernels.tsne_repulsion(jnp.asarray(Y), jnp.asarray(valid),
                                         tile=128)
    Y64 = Y.astype(np.float64)
    q = 1.0 / (1.0 + ((Y64[:, None, :] - Y64[None, :, :]) ** 2).sum(-1))
    q *= valid[:, None] * valid[None, :] * (1.0 - np.eye(256))
    if not np.isclose(float(Z), q.sum(), rtol=1e-5):
        raise RuntimeError(f"tsne_repulsion Z {Z} vs NumPy {q.sum()}")
    np.testing.assert_allclose(
        np.asarray(F), Y64 * (q * q).sum(1, keepdims=True) - (q * q) @ Y64,
        rtol=1e-4, atol=1e-5)

    Y = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    valid = (jnp.arange(n) < n - 37).astype(jnp.float32)
    Z, F = pallas_kernels.tsne_repulsion(Y, valid)
    with jax.default_matmul_precision("highest"):
        Zr, Fr = jax.jit(
            lambda Y, v: tsne._rep_rows_scan(Y, v, Y, v, 0,
                                             tile=pallas_kernels.TILE)
        )(Y, valid)
    Z, F, Zr, Fr = (np.asarray(a) for a in (Z, F, Zr, Fr))
    scale = float(np.abs(Fr).max())
    err = float(np.abs(F - Fr).max())
    if not (np.isfinite(F).all() and np.isclose(Z, Zr, rtol=1e-5)
            and err <= 1e-5 * scale):
        raise RuntimeError(f"tsne_repulsion vs XLA reference: Z {Z} / "
                           f"{Zr}, max |dF| {err} at force scale {scale}")
    return {"rows": n, "Z": float(Z), "max_abs_dF": err,
            "force_scale": scale}


def run_one_chip(seed: int) -> None:
    from learningorchestra_tpu.client import (
        Context, DatabaseApi, DataTypeHandler, Histogram, Model,
        Observability, Pca, Projection, Tsne)
    from learningorchestra_tpu.config import Settings
    from learningorchestra_tpu.parallel import distributed
    from learningorchestra_tpu.serving.app import App
    from learningorchestra_tpu.utils import structlog

    structlog.configure()
    cache = distributed.place_compile_cache()
    t0 = time.time()
    parser = build_native_parser()
    scratch = tempfile.mkdtemp(prefix="lo_chip_smoke_")
    app = server = None
    try:
        sets = {}
        for name, n, s in (("train", N_TRAIN, seed), ("test", N_TEST,
                                                      seed + 1),
                           ("viz", N_VIZ, seed + 2)):
            sets[name] = write_csv(os.path.join(scratch, f"{name}.csv"),
                                   n, s)
        emit("setup", t0, parser=parser, compile_cache=(
            cache or os.environ["JAX_COMPILATION_CACHE_DIR"]),
            csv_mb=round(sum(os.path.getsize(os.path.join(scratch, f))
                             for f in os.listdir(scratch)) / 1e6, 1))

        # The server, as serving/__main__.py:main builds it — App + the
        # stdlib HTTP server — on a free port, in this process.
        cfg = Settings()
        cfg.host, cfg.port = "127.0.0.1", 0
        cfg.store_root = os.path.join(scratch, "store")
        cfg.image_root = os.path.join(scratch, "images")
        if int(cfg.http_workers) > 1:
            raise RuntimeError("LO_TPU_HTTP_WORKERS must stay unset: a "
                               "front-end child would inherit stdout")
        app = App(cfg, recover=False)
        server = app.serve(background=True)
        ctx = Context(f"http://127.0.0.1:{server.port}", poll_seconds=0.2,
                      timeout=900.0, request_timeout=900.0)
        db, model, obs = DatabaseApi(ctx), Model(ctx), Observability(ctx)

        # 1. ingest
        t0 = time.time()
        for name in sets:
            db.create_file(name, "file://" + os.path.join(
                scratch, f"{name}.csv"))
        for name, (X, _) in sets.items():
            db.waiter.wait(name)
            # Every row arrived and the last one is the one written.
            (last,) = db.read_file(name, skip=len(X), limit=2)
            if not np.array_equal(
                    np.asarray([last[f] for f in FEATURES], np.float32),
                    X[-1]):
                raise RuntimeError(f"{name}: last row changed in ingest")
        emit("ingest", t0, rows={k: len(v[0]) for k, v in sets.items()},
             features=len(FEATURES), parser=parser)

        # 2. catalog ops, on the full train set
        t0 = time.time()
        Projection(ctx).create_projection(
            "train", "train_pr", FEATURES[:8] + ["label"])
        DataTypeHandler(ctx).change_file_type("train_pr",
                                              {"label": "string"})
        Histogram(ctx).create_histogram("train_pr", "train_hist",
                                        ["label"])
        (hist,) = db.read_file("train_hist", skip=1, limit=2)
        y = sets["train"][1]
        want = {"0": int((y == 0).sum()), "1": int((y == 1).sum())}
        if hist["counts"] != want:
            raise RuntimeError(f"label histogram {hist} != {want}")
        emit("catalog", t0, projection_fields=9, coerced="label→string",
             histogram=want)

        # 3. the five-family sweep, twice
        t0 = time.time()
        cold = timed_sweep(model, db, obs, "train", "pred")
        warm = timed_sweep(model, db, obs, "train", "pred2")
        emit("sweep", t0, rows=N_TRAIN, cold=cold, warm=warm,
             tree_kernel=bool(cfg.use_pallas and cfg.tree_kernel))

        # 4. online predict against the batch predictions
        t0 = time.time()
        rng = np.random.default_rng(seed)
        sizes = [1, 64] + [int(s) for s in rng.integers(1, 65, 30)]
        lo = 0
        for i, size in enumerate(sizes):
            batch = []                    # the batch job's rows + answers
            while len(batch) < size:      # (the read cap is 20 rows)
                batch += db.read_file("pred_gb", skip=1 + lo + len(batch),
                                      limit=min(20, size - len(batch)))
            out = model.predict_online(
                "pred_gb", [[r[f] for f in FEATURES] for r in batch],
                max_batch=64)
            if out["predictions"] != [r["prediction"] for r in batch]:
                raise RuntimeError(f"online request {i} (rows {lo}.."
                                   f"{lo + size}) != batch predictions")
            np.testing.assert_allclose(
                out["probabilities"], [r["probability"] for r in batch],
                rtol=1e-5, atol=1e-6)
            lo += size
        emit("online", t0, model="pred_gb", requests=len(sizes), rows=lo)

        # 5. viz
        t0 = time.time()
        Projection(ctx).create_projection("viz", "viz_pr",
                                          FEATURES + ["label"])
        png = {}
        for client, kw in ((Pca(ctx), {}),
                           (Tsne(ctx), {"iters": TSNE_ITERS})):
            t1 = time.time()
            client.create_image_plot("smoke", "viz_pr", label_name="label",
                                     **kw)
            body = client.read_image_plot("smoke")
            if body[:8] != b"\x89PNG\r\n\x1a\n":
                raise RuntimeError(f"{client.method}: not a PNG")
            png[client.method] = {"bytes": len(body),
                                  "seconds": round(time.time() - t1, 3)}
        emit("viz", t0, rows=N_VIZ, png=png,
             repulsion=repulsion_check(N_VIZ, seed))

        # The headline size, straight into the store (the one step no
        # user's call reaches: there is no 3 GB CSV).
        t0 = time.time()
        app.store.create("train11m", columns=higgs_like_columns(
            N_HEADLINE, seed), finished=True)
        made = round(time.time() - t0, 3)
        emit("sweep_headline", t0, rows=N_HEADLINE, datagen_s=made,
             cold=timed_sweep(model, db, obs, "train11m", "pred11m"),
             warm=timed_sweep(model, db, obs, "train11m", "pred11m2"))
    finally:
        # 6. shutdown: drain accepted work, stop the server, join.
        t0 = time.time()
        unclean = []
        if server is not None:
            if not app.drain():
                unclean.append("drain timed out")
            server.stop()
            unclean += [t.name for t in threading.enumerate()
                        if t.name == "lo-http"]
            emit("shutdown", t0, unclean=unclean)
        shutil.rmtree(scratch, ignore_errors=True)
    if unclean:
        raise RuntimeError(f"the server did not shut down cleanly: "
                           f"{unclean}")


def mesh_sweep(store, cfg, devices, tag: str) -> dict:
    """The phase-3 sweep through the library entry points on a mesh
    over ``devices``: cold and warm wall-clock, per-family accuracy
    (floors checked) and the warm sweep's test-set predictions."""
    from learningorchestra_tpu.models.builder import ModelBuilder
    from learningorchestra_tpu.parallel.mesh import MeshRuntime, local_mesh

    runtime = MeshRuntime(cfg)
    runtime._mesh = local_mesh(cfg, devices=devices)
    builder = ModelBuilder(store, runtime, cfg)
    walls = {}
    for rep in ("cold", "warm"):
        t0 = time.time()
        reports = builder.build("train", "test", f"{tag}_{rep}",
                                CLASSIFIERS, "label")
        walls[rep] = round(time.time() - t0, 3)
    acc = {}
    for r in reports:
        if "error" in r.metrics or not (r.metrics["accuracy"]
                                        > ACC_FLOOR[r.kind]):
            raise RuntimeError(f"{tag} {r.kind}: {r.metrics}")
        acc[r.kind] = float(r.metrics["accuracy"])
    preds = {k: np.asarray(store.get(f"{tag}_warm_{k}").column(
        "prediction")) for k in CLASSIFIERS}
    return {"runtime": runtime, "walls": walls, "accuracy": acc,
            "predictions": preds}


def run_four_chips(seed: int) -> None:
    """Only what exists across chips, and what it is compared with: the
    sweep on a one-device mesh against the (4,1,1) mesh, and the
    row-sharded t-SNE repulsion and descent against single-device."""
    import jax
    import jax.numpy as jnp

    from learningorchestra_tpu.catalog.store import DatasetStore
    from learningorchestra_tpu.config import Settings
    from learningorchestra_tpu.parallel import distributed
    from learningorchestra_tpu.utils import resources, structlog
    from learningorchestra_tpu.viz import tsne

    structlog.configure()
    distributed.place_compile_cache()
    cfg = Settings()
    cfg.persist = cfg.persist_models = False
    store = DatasetStore(cfg)
    t0 = time.time()
    store.create("train", columns=higgs_like_columns(N_TRAIN, seed),
                 finished=True)
    store.create("test", columns=higgs_like_columns(N_TEST, seed + 1),
                 finished=True)
    emit("setup", t0, rows={"train": N_TRAIN, "test": N_TEST})

    t0 = time.time()
    one = mesh_sweep(store, cfg, jax.devices()[:1], "one")
    four = mesh_sweep(store, cfg, jax.devices(), "four")
    # dt's statistics are integer counts — exact under any sharding, so
    # its trees and predictions are identical. The other families sum
    # floats in another order (rf also draws its bootstrap per shard):
    # statistical parity, at the bound the kernel/oracle tests use.
    if not np.array_equal(one["predictions"]["dt"],
                          four["predictions"]["dt"]):
        raise RuntimeError("dt predictions differ between 1 and 4 chips")
    agree = {}
    for k in CLASSIFIERS:
        agree[k] = round(float((one["predictions"][k]
                                == four["predictions"][k]).mean()), 5)
        if abs(one["accuracy"][k] - four["accuracy"][k]) > ACC_PARITY:
            raise RuntimeError(f"{k}: accuracy {one['accuracy'][k]} on "
                               f"one chip, {four['accuracy'][k]} on four")
    # The sharded design matrix really spans the four chips.
    x, _ = four["runtime"].shard_rows(higgs_like_xy(N_TEST, seed + 1)[0])
    spans = len(x.sharding.device_set)
    snap = resources.device_snapshot()
    in_use = [d.get("bytes_in_use", 0) for d in snap["devices"]]
    if (spans != 4 or snap["source"] != "memory_stats" or len(in_use) != 4
            or not all(b > 0 for b in in_use)):
        raise RuntimeError(f"design spans {spans} devices; {snap}")
    emit("mesh_sweep", t0, rows=N_TRAIN,
         one_chip={"walls": one["walls"], "accuracy": one["accuracy"]},
         four_chips={"walls": four["walls"], "accuracy": four["accuracy"]},
         prediction_agreement=agree, design_devices=spans,
         bytes_in_use=in_use)

    # One repulsion evaluation, row-sharded (tsne_repulsion_rows under
    # shard_map) against single-device, at tests/test_viz.py's bounds;
    # then the whole descent both ways.
    t0 = time.time()
    mesh = four["runtime"].mesh
    rng = np.random.default_rng(seed)
    Y = rng.normal(size=(N_VIZ, 2)).astype(np.float32)
    valid = (np.arange(N_VIZ) < N_VIZ - 37).astype(np.float32)
    Z1, F1 = tsne._repulsion(jnp.asarray(Y), jnp.asarray(valid), tile=512,
                             use_pallas=True, mesh=None)
    Z4, F4 = jax.jit(lambda Y, v: tsne._repulsion(
        Y, v, tile=512, use_pallas=True, mesh=mesh))(
        four["runtime"].replicate(Y), four["runtime"].replicate(valid))
    if not np.isclose(float(Z1), float(Z4), rtol=1e-5):
        raise RuntimeError(f"sharded repulsion Z {Z4} vs {Z1}")
    np.testing.assert_allclose(np.asarray(F1), np.asarray(F4), rtol=1e-4,
                               atol=1e-6)
    X, _ = higgs_like_xy(N_VIZ, seed + 2)
    walls = {}
    for name, res in (("one_chip", one), ("four_chips", four)):
        t1 = time.time()
        emb = tsne.tsne_embed(res["runtime"], X, iters=TSNE_ITERS,
                              seed=seed)
        walls[name] = round(time.time() - t1, 3)
        if emb.shape != (N_VIZ, 2) or not np.isfinite(emb).all():
            raise RuntimeError(f"t-SNE descent on {name}: bad embedding")
    emit("mesh_tsne", t0, rows=N_VIZ, iters=TSNE_ITERS, Z=float(Z4),
         max_abs_dF=float(np.abs(np.asarray(F1) - np.asarray(F4)).max()),
         descent_walls=walls)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh comparison on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device: dict = {}
    ok = False
    try:
        import jax

        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        if device["platform"] != "tpu":
            raise RuntimeError(f"no TPU: JAX found {device}")
        if args.chips == 4 and device["count"] != 4:
            raise RuntimeError(f"--chips 4 needs four chips: {device}")
        (run_four_chips if args.chips == 4 else run_one_chip)(args.seed)
        ok = True
    except BaseException as e:  # noqa: BLE001 — report, then fail
        import traceback

        traceback.print_exc()
        print(json.dumps({"phase": "failed", "error": repr(e)[:500]}),
              flush=True)
    sys.stderr.flush()
    print(json.dumps(final_line(ok, device)), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
