"""Device-resident hyperparameter search (models/tune.py) + /tune route.

Acceptance bars from the PR issue:

1. **parity** — a vmapped population of N configs is BIT-IDENTICAL
   per-config to N serial fits for dt/rf/lr/mlp (gb: accuracy-parity,
   the PR 7 statistical-equivalence standard), including across
   HBM-budget wave splits;
2. **halving** — successive halving drops losers at rung boundaries and
   the winner's final score still matches its serial full fit (the
   survivor runs its complete unit budget, segmented);
3. **resume** — a sweep interrupted at a halving-rung checkpoint
   (armed ``fit.ckpt.pre_rename`` failpoint) resumes to IDENTICAL
   survivors and scores as the uninterrupted oracle;
4. **surface** — POST /tune end to end (sync leaderboard, async poll,
   winner promotion to the registry), 406s that NAME the bad hparam on
   both /tune and /models, and the ``lo_tune_*`` /metrics series.

Full 16-config population chaos (budget-forced waves + crash + resume)
is slow-marked; tier-1 keeps the small populations.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from learningorchestra_tpu.config import Settings
from learningorchestra_tpu.models import tune
from learningorchestra_tpu.models.registry import get_trainer
from learningorchestra_tpu.parallel.mesh import MeshRuntime
from learningorchestra_tpu.utils import failpoints, fitckpt


@pytest.fixture(scope="module")
def runtime():
    return MeshRuntime(Settings())


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


def _blobs(n=240, d=6, classes=2, seed=0, sep=2.0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(classes, d)) * sep
    y = rng.integers(0, classes, size=n)
    X = centers[y] + rng.normal(size=(n, d))
    return X.astype(np.float32), y.astype(np.int32)


def _serial_score(runtime, family, config, X, y, num_classes):
    """One standalone fit + self-accuracy — what the sweep's folds=1
    fold (-1: train AND score every valid row) must reproduce."""
    trainer = get_trainer(family)
    prep = getattr(trainer, "host_prep", None)
    extra = prep(X, **config) if prep is not None else {}
    model = trainer(runtime, X, y, num_classes, **dict(config, **extra))
    preds = np.argmax(np.asarray(model.predict_proba(runtime, X)), axis=1)
    return round(float((preds == y).mean()), 6)


def _by_config(board, config):
    for r in board["results"]:
        if r["config"] == config:
            return r
    raise AssertionError(f"config {config} missing from board")


def _config_grid(family: str, pop: int) -> list:
    """``pop`` same-family configs varying the knobs a real sweep varies,
    static-shape ones (depth, bins, iteration counts) included: serial
    fits recompile per distinct value, the population program masks them
    into one compile."""
    if family == "dt":
        return [{"max_depth": 2 + (i % 4),
                 "n_bins": (8, 16, 32)[i % 3]} for i in range(pop)]
    if family == "lr":
        return [{"solver": "adam", "iters": 40 + 10 * (i % 6),
                 "lr": round(0.02 * 1.3 ** (i % 8), 6),
                 "l2": (1e-4, 1e-3)[i % 2]} for i in range(pop)]
    raise ValueError(family)


def _mk_cfg(tmp_path=None, **knobs):
    cfg = Settings()
    if tmp_path is not None:
        cfg.store_root = str(tmp_path / "store")
        cfg.persist = True
    for k, v in knobs.items():
        setattr(cfg, k, v)
    return cfg


# -- unit layer ---------------------------------------------------------------

def test_fold_masks_partition_valid_rows():
    fids, tr, ev = tune._fold_masks(10, 16, 3)
    assert fids == [0, 1, 2] and tr.shape == ev.shape == (3, 16)
    valid = (np.arange(16) < 10).astype(np.float32)
    # Each fold's train/eval split partitions exactly the valid rows,
    # and the eval folds partition them across folds (each valid row
    # scores in exactly one fold; padding rows in none).
    np.testing.assert_array_equal(tr + ev, np.tile(valid, (3, 1)))
    np.testing.assert_array_equal(ev.sum(axis=0), valid)
    assert set(np.unique(tr)) <= {0.0, 1.0}


def test_fold_masks_single_fold_trains_and_scores_everything():
    fids, tr, ev = tune._fold_masks(5, 8, 1)
    valid = (np.arange(8) < 5).astype(np.float32)
    assert fids == [-1]
    np.testing.assert_array_equal(tr[0], valid)
    np.testing.assert_array_equal(ev[0], valid)


@pytest.mark.parametrize("family,configs,msg", [
    ("nb", [{}], "no population tune path"),
    ("dt", [], "non-empty list"),
    ("dt", [{"bogus": 1}], "bogus"),
    ("dt", [{"n_bins": 500}], "n_bins"),
    ("rf", [{"n_trees": 4}, {"n_trees": 8}], "share n_trees"),
    ("lr", [{"solver": "newton"}, {"solver": "adam"}], "one solver"),
])
def test_validate_population_rejections(family, configs, msg):
    with pytest.raises(ValueError, match=msg):
        tune.validate_population(family, configs)


def test_validate_population_gb_binary_only():
    with pytest.raises(ValueError, match="binary"):
        tune.validate_population("gb", [{"n_rounds": 4}], num_classes=3)
    tune.validate_population("gb", [{"n_rounds": 4}], num_classes=2)


def test_plan_waves_budget_spill_covers_every_config_once():
    # A 1 MiB budget against a million-row design forces width 1: five
    # sequential waves, each config exactly once, spill counter bumped.
    before = tune.counters_snapshot()["hbm_spill_waves"]
    cfg = _mk_cfg(tune_hbm_budget_mb=1)
    cfgs = [{"max_depth": k} for k in range(2, 7)]
    waves = tune.plan_waves("dt", cfgs, n=1_000_000, d=8, num_classes=2,
                            folds=1, cfg=cfg)
    assert len(waves) > 1
    flat = [i for w in waves for i in w]
    assert sorted(flat) == list(range(5)) == flat  # order-preserving
    assert tune.counters_snapshot()["hbm_spill_waves"] > before


def test_plan_waves_population_cap_divides_by_folds():
    # cap = max_population // folds: 4 // 2 -> waves of two configs.
    cfg = _mk_cfg(tune_max_population=4)
    waves = tune.plan_waves("lr", [{} for _ in range(5)], n=100, d=4,
                            num_classes=2, folds=2, cfg=cfg)
    assert [len(w) for w in waves] == [2, 2, 1]
    # Budget 0 with a roomy cap: a single wave.
    cfg = _mk_cfg()
    waves = tune.plan_waves("lr", [{} for _ in range(5)], n=100, d=4,
                            num_classes=2, folds=2, cfg=cfg)
    assert [len(w) for w in waves] == [5]


# -- population-vs-serial parity (the tentpole's correctness bar) -------------

PARITY_CASES = [
    ("dt", [{"max_depth": 2, "n_bins": 8}, {"max_depth": 4, "n_bins": 16},
            {"max_depth": 3, "n_bins": 32}]),
    ("rf", [{"n_trees": 8, "max_depth": 3, "n_bins": 16},
            {"n_trees": 8, "max_depth": 5, "n_bins": 8}]),
    ("lr", [{"solver": "adam", "iters": 30, "lr": 0.05},
            {"solver": "adam", "iters": 30, "lr": 0.1, "l2": 1e-3}]),
    ("lr", [{"solver": "newton", "iters": 8},
            {"solver": "newton", "iters": 12, "l2": 1e-2}]),
    ("mlp", [{"hidden": 32, "iters": 20, "lr": 0.01},
             {"hidden": 64, "iters": 24, "lr": 0.005}]),
]


@pytest.mark.parametrize(
    "family,configs", PARITY_CASES,
    ids=["dt", "rf", "lr-adam", "lr-newton", "mlp"])
def test_population_bit_identical_to_serial(runtime, family, configs):
    """folds=1/rungs=1: each population member's score equals its
    standalone fit's self-accuracy EXACTLY — one flipped prediction
    moves accuracy by 1/n >> the 1e-6 rounding, so score equality is
    prediction equality."""
    X, y = _blobs(seed=3)
    board = tune.sweep(runtime, X, y, 2, family, configs, cfg=Settings(),
                       folds=1, rungs=1)
    assert board["waves"] == 1 and not board["halving"]
    for c in configs:
        r = _by_config(board, c)
        assert r["fold_scores"] == [_serial_score(runtime, family, c,
                                                  X, y, 2)], c
        assert r["alive"] and r["mean_score"] == r["fold_scores"][0]


def test_population_parity_multiclass_dt(runtime):
    X, y = _blobs(n=300, classes=3, seed=5, sep=3.0)
    configs = [{"max_depth": 3, "n_bins": 16}, {"max_depth": 5, "n_bins": 8}]
    board = tune.sweep(runtime, X, y, 3, "dt", configs, cfg=Settings(),
                       folds=1, rungs=1)
    for c in configs:
        assert _by_config(board, c)["fold_scores"] == [
            _serial_score(runtime, "dt", c, X, y, 3)], c


def test_population_parity_gb_accuracy(runtime):
    """gb is the PR 7 statistical-equivalence standard: the population
    booster's per-config self-accuracy tracks the serial fit within a
    couple of row-flips (empirically exact on this data)."""
    X, y = _blobs(seed=7)
    configs = [{"n_rounds": 6, "max_depth": 3},
               {"n_rounds": 8, "max_depth": 2, "step_size": 0.1}]
    board = tune.sweep(runtime, X, y, 2, "gb", configs, cfg=Settings(),
                       folds=1, rungs=1)
    for c in configs:
        got = _by_config(board, c)["fold_scores"][0]
        want = _serial_score(runtime, "gb", c, X, y, 2)
        assert abs(got - want) <= 0.02, (c, got, want)


def test_population_parity_across_budget_waves(runtime):
    """A capped population spills into sequential waves — per-config
    results must not depend on which wave a config landed in."""
    X, y = _blobs(seed=11)
    configs = [{"max_depth": k, "n_bins": 16} for k in (2, 3, 4, 5)]
    cfg = _mk_cfg(tune_max_population=2)  # waves of 2
    board = tune.sweep(runtime, X, y, 2, "dt", configs, cfg=cfg,
                       folds=1, rungs=1)
    assert board["waves"] == 2
    assert {r["wave"] for r in board["results"]} == {0, 1}
    for c in configs:
        assert _by_config(board, c)["fold_scores"] == [
            _serial_score(runtime, "dt", c, X, y, 2)], c


# -- k-fold CV ----------------------------------------------------------------

def test_kfold_scores_and_mean(runtime):
    X, y = _blobs(n=300, seed=13)
    configs = [{"max_depth": 3, "n_bins": 16}, {"max_depth": 5, "n_bins": 16}]
    board = tune.sweep(runtime, X, y, 2, "dt", configs, cfg=Settings(),
                       folds=3, rungs=1)
    assert board["folds"] == 3
    for r in board["results"]:
        assert len(r["fold_scores"]) == 3
        assert all(0.0 <= s <= 1.0 for s in r["fold_scores"])
        assert abs(np.mean(r["fold_scores"]) - r["mean_score"]) < 2e-6
    # Held-out scoring on separable blobs still beats chance by a lot.
    assert board["winner"]["mean_score"] > 0.8


def test_sweep_input_validation(runtime):
    X, y = _blobs(n=60)
    with pytest.raises(ValueError, match="folds"):
        tune.sweep(runtime, X, y, 2, "dt", [{"max_depth": 2}],
                   cfg=Settings(), folds=0, rungs=1)
    with pytest.raises(ValueError, match="rungs"):
        tune.sweep(runtime, X, y, 2, "dt", [{"max_depth": 2}],
                   cfg=Settings(), folds=1, rungs=0)


# -- successive halving -------------------------------------------------------

def test_halving_drops_losers_and_keeps_winner(runtime):
    before = tune.counters_snapshot()
    X, y = _blobs(n=300, seed=17)
    configs = [{"solver": "adam", "iters": 48, "lr": r}
               for r in (0.001, 0.01, 0.05, 0.2)]
    board = tune.sweep(runtime, X, y, 2, "lr", configs, cfg=Settings(),
                       folds=1, rungs=3)
    after = tune.counters_snapshot()
    assert board["halving"]
    alive = [r for r in board["results"] if r["alive"]]
    # 4 -> 2 -> 1 across the two interior rung boundaries.
    assert len(alive) == 1
    assert board["winner"] is alive[0]
    assert board["winner"]["rungs_survived"] == 3
    # Dropped configs keep the (frozen) score of their last live rung.
    survived = sorted(r["rungs_survived"] for r in board["results"])
    assert survived == [1, 1, 2, 3]
    assert after["halving_drops"] - before["halving_drops"] == 3
    assert after["rungs_completed"] - before["rungs_completed"] == 3
    assert after["candidates_evaluated"] - before["candidates_evaluated"] == 4


def test_halving_winner_matches_serial_full_fit(runtime):
    """The survivor runs its whole unit budget in rung segments; the
    segmentation must be invisible — its final score is bit-identical
    to the one-shot serial fit of the same config."""
    X, y = _blobs(n=300, seed=19)
    configs = [{"solver": "adam", "iters": 48, "lr": r}
               for r in (0.005, 0.02, 0.08, 0.3)]
    board = tune.sweep(runtime, X, y, 2, "lr", configs, cfg=Settings(),
                       folds=1, rungs=3)
    w = board["winner"]
    assert w["fold_scores"] == [_serial_score(runtime, "lr", w["config"],
                                              X, y, 2)]


# -- crash-at-rung-boundary resume -------------------------------------------

def _strip_timing(board):
    doc = json.loads(json.dumps(board))  # deep copy, JSON-able by contract
    for r in doc["results"] + [doc["winner"]]:
        r.pop("fit_seconds")
    return doc


def test_interrupted_sweep_resumes_to_identical_board(runtime, tmp_path):
    """Crash on the SECOND rung checkpoint commit (the first is durable),
    re-run the same sweep: it resumes from rung 1 — alive set, rung
    history and scores restored — and finishes with a board identical
    to the uninterrupted oracle's, minus wall-clock."""
    X, y = _blobs(n=300, seed=23)
    configs = [{"solver": "adam", "iters": 48, "lr": r}
               for r in (0.003, 0.01, 0.06, 0.25)]
    oracle = tune.sweep(runtime, X, y, 2, "lr", configs, cfg=Settings(),
                        folds=1, rungs=3)

    cfg = _mk_cfg(tmp_path)
    mk_ctx = lambda: fitckpt.context(
        cfg, dataset="blobs", family="tune_lr",
        config={"configs": configs, "folds": 1, "rungs": 3},
        snapshot="rows=300", every=1)
    failpoints.configure("fit.ckpt.pre_rename=raise:2")
    with pytest.raises(failpoints.FailpointError):
        tune.sweep(runtime, X, y, 2, "lr", configs, cfg=cfg,
                   folds=1, rungs=3, ckpt=mk_ctx())
    failpoints.reset()

    before = tune.counters_snapshot()["sweeps_resumed"]
    fck_before = fitckpt.counters_snapshot()["resumes"]
    board = tune.sweep(runtime, X, y, 2, "lr", configs, cfg=cfg,
                       folds=1, rungs=3, ckpt=mk_ctx())
    assert tune.counters_snapshot()["sweeps_resumed"] == before + 1
    assert fitckpt.counters_snapshot()["resumes"] == fck_before + 1
    assert _strip_timing(board) == _strip_timing(oracle)
    # The finished sweep cleared its checkpoints.
    assert fitckpt.disk_snapshot(cfg)["files"] == 0


def test_stale_checkpoint_is_discarded_not_trusted(runtime, tmp_path):
    """A checkpoint whose orchestration shape (folds) no longer matches
    is cleared and the sweep runs fresh — never resumed into the wrong
    fold geometry."""
    X, y = _blobs(n=240, seed=29)
    configs = [{"solver": "adam", "iters": 30, "lr": r}
               for r in (0.01, 0.1)]
    cfg = _mk_cfg(tmp_path)
    ctx = fitckpt.context(cfg, dataset="b", family="tune_lr",
                          config={"v": 1}, snapshot="rows=240", every=1)
    failpoints.configure("fit.ckpt.pre_rename=raise:2")
    with pytest.raises(failpoints.FailpointError):
        tune.sweep(runtime, X, y, 2, "lr", configs, cfg=cfg,
                   folds=1, rungs=3, ckpt=ctx)
    failpoints.reset()
    before = tune.counters_snapshot()["sweeps_resumed"]
    ctx2 = fitckpt.context(cfg, dataset="b", family="tune_lr",
                           config={"v": 1}, snapshot="rows=240", every=1)
    board = tune.sweep(runtime, X, y, 2, "lr", configs, cfg=cfg,
                       folds=2, rungs=3, ckpt=ctx2)
    assert tune.counters_snapshot()["sweeps_resumed"] == before
    assert board["folds"] == 2


# -- slow chaos: full population, budget waves, crash + resume ---------------

@pytest.mark.slow
def test_full_population_halving_chaos(runtime, tmp_path):
    """16-config population forced into HBM-budget waves, interrupted at
    a mid-wave halving rung, resumed: identical survivors and scores to
    the uninterrupted oracle under the SAME budget."""
    X, y = _blobs(n=400, seed=31)
    configs = _config_grid("lr", 16)
    cfg = _mk_cfg(tmp_path, tune_max_population=12)  # 12 // 2 folds -> waves
    oracle = tune.sweep(runtime, X, y, 2, "lr", configs, cfg=cfg,
                        folds=2, rungs=3)
    assert oracle["waves"] > 1

    mk_ctx = lambda: fitckpt.context(
        cfg, dataset="chaos", family="tune_lr",
        config={"configs": configs}, snapshot="rows=400", every=1)
    failpoints.configure("fit.ckpt.pre_rename=raise:3")
    with pytest.raises(failpoints.FailpointError):
        tune.sweep(runtime, X, y, 2, "lr", configs, cfg=cfg,
                   folds=2, rungs=3, ckpt=mk_ctx())
    failpoints.reset()
    board = tune.sweep(runtime, X, y, 2, "lr", configs, cfg=cfg,
                       folds=2, rungs=3, ckpt=mk_ctx())
    assert _strip_timing(board) == _strip_timing(oracle)
    assert [r["alive"] for r in board["results"]] == \
        [r["alive"] for r in oracle["results"]]


# -- REST surface -------------------------------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from learningorchestra_tpu.serving.app import App

    tmp = tmp_path_factory.mktemp("tune_serve")
    cfg = Settings()
    cfg.store_root = str(tmp / "store")
    cfg.image_root = str(tmp / "images")
    cfg.port = 0
    cfg.persist = True
    app = App(cfg, recover=False)
    server = app.serve(background=True)
    from learningorchestra_tpu.client import Context, DatabaseApi

    ctx = Context(f"http://127.0.0.1:{server.port}", poll_seconds=0.1,
                  timeout=120)
    csv = tmp / "t.csv"
    rows = ["Pclass,Sex,Age,Fare,Survived"]
    rng = np.random.default_rng(0)
    for _ in range(160):
        sex = rng.choice(["male", "female"])
        surv = int(rng.random() < (0.75 if sex == "female" else 0.2))
        rows.append(f"{rng.integers(1, 4)},{sex},{rng.integers(1, 70)},"
                    f"{round(float(rng.lognormal(2.5, 1.0)), 2)},{surv}")
    csv.write_text("\n".join(rows) + "\n")
    DatabaseApi(ctx).create_file("tune_train", str(csv), wait=True)
    yield ctx, server.port
    server.stop()


def _post(port, path, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        method="POST", headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_tune_route_sync_promotes_winner(served):
    from learningorchestra_tpu.client import DatabaseApi, Model

    ctx, port = served
    m = Model(ctx)
    out = m.tune("tune_train", "tuned_dt", "dt",
                 [{"max_depth": 2, "n_bins": 8},
                  {"max_depth": 4, "n_bins": 16}],
                 "Survived", folds=2, rungs=2, promote=True)
    board = out["result"]
    assert board["family"] == "dt" and len(board["results"]) == 2
    assert board["promoted"] == "tuned_dt", board.get("promote_error")
    # Leaderboard persisted on the dataset's metadata document.
    meta = DatabaseApi(ctx).read_file("tuned_dt", limit=1)[0]
    assert meta["finished"] is True
    assert meta["tune"]["winner"]["config"] == board["winner"]["config"]
    # The promoted winner serves online predictions.
    pred = m.predict_online("tuned_dt", [[3, 1, 22, 7.25]])
    assert len(pred["predictions"]) == 1


def test_tune_route_async(served):
    from learningorchestra_tpu.client import DatabaseApi, Model

    ctx, port = served
    m = Model(ctx)
    m.tune("tune_train", "tuned_lr", "lr",
           [{"iters": 30, "lr": 0.05}, {"iters": 30, "lr": 0.2}],
           "Survived", folds=2, rungs=1, sync=False)
    meta = DatabaseApi(ctx).read_file("tuned_lr", limit=1)[0]
    assert meta["finished"] is True and meta["tune"]["family"] == "lr"


@pytest.mark.parametrize("configs,needle", [
    ([{"max_depth": 4, "bogus": 1}], "bogus"),       # unknown name
    ([{"n_bins": 500}], "n_bins"),                   # out of range
], ids=["unknown-key", "out-of-range"])
def test_tune_route_406_names_bad_hparam(served, configs, needle):
    _, port = served
    code, body = _post(port, "/tune", {
        "training_filename": "tune_train", "tune_filename": "rejected",
        "classificator": "dt", "configs": configs, "label": "Survived"})
    assert code == 406 and needle in json.dumps(body), (code, body)


def test_tune_route_rejects_family_without_pop_path(served):
    _, port = served
    code, body = _post(port, "/tune", {
        "training_filename": "tune_train", "tune_filename": "rejected2",
        "classificator": "nb", "configs": [{}], "label": "Survived"})
    assert code == 406 and "population" in json.dumps(body)


def test_tune_route_missing_dataset_404(served):
    _, port = served
    code, _ = _post(port, "/tune", {
        "training_filename": "nope", "tune_filename": "rejected3",
        "classificator": "dt", "configs": [{"max_depth": 2}],
        "label": "Survived"})
    assert code == 404


@pytest.mark.parametrize("hparams,needle", [
    ({"lr": {"learning_rate": 0.1}}, "learning_rate"),  # unknown name
    ({"gb": {"n_bins": 500}}, "n_bins"),                # out of range
], ids=["unknown-key", "out-of-range"])
def test_models_route_406_names_bad_hparam(served, hparams, needle):
    _, port = served
    code, body = _post(port, "/models", {
        "training_filename": "tune_train", "test_filename": "tune_train",
        "prediction_filename": "rejected_pred",
        "classificators_list": list(hparams), "label": "Survived",
        "hparams": hparams})
    assert code == 406 and needle in json.dumps(body), (code, body)


def test_metrics_expose_tune_section(served):
    _, port = served
    # Self-seed one sweep so the counters are non-zero regardless of
    # which other tests ran first.
    code, _ = _post(port, "/tune", {
        "training_filename": "tune_train", "tune_filename": "tuned_metrics",
        "classificator": "dt",
        "configs": [{"max_depth": 2, "n_bins": 8},
                    {"max_depth": 3, "n_bins": 8}],
        "label": "Survived", "folds": 1, "rungs": 1})
    assert code == 201
    doc = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics").read())
    assert doc["tune"]["populations_fitted"] >= 1
    assert doc["tune"]["candidates_evaluated"] >= 2
    txt = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics?format=prometheus"
    ).read().decode()
    for series in ("lo_tune_populations_fitted", "lo_tune_candidates_evaluated",
                   "lo_tune_rungs_completed", "lo_tune_halving_drops",
                   "lo_tune_hbm_spill_waves", "lo_tune_sweeps_resumed"):
        assert series in txt, series


# -- compile reuse -------------------------------------------------------------

def test_identical_second_sweep_compiles_nothing(runtime):
    """A wave of shapes already seen pays no compile: an identical second
    sweep leaves the process compile counter where it was, and its winner
    is still the best of the serial fits."""
    from learningorchestra_tpu.utils import resources

    X, y = _blobs(n=400, seed=7)
    configs = _config_grid("dt", 4)
    first = tune.sweep(runtime, X, y, 2, "dt", configs, cfg=Settings(),
                       folds=1, rungs=1)
    c0 = resources.compile_snapshot()["compiles"]
    board = tune.sweep(runtime, X, y, 2, "dt", configs, cfg=Settings(),
                       folds=1, rungs=1)
    assert resources.compile_snapshot()["compiles"] == c0
    assert ([(r["config"], r["fold_scores"]) for r in board["results"]]
            == [(r["config"], r["fold_scores"]) for r in first["results"]])
    assert board["winner"]["mean_score"] == max(
        _serial_score(runtime, "dt", c, X, y, 2) for c in configs)
