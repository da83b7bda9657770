"""ModelBuilder end-to-end tests on a Titanic-like dataset (the reference's
de-facto smoke test, SURVEY.md §4)."""

import numpy as np
import pytest

from learningorchestra_tpu.config import Settings
from learningorchestra_tpu.models.builder import ModelBuilder
from learningorchestra_tpu.ops.preprocess import apply_steps, design_matrix
from learningorchestra_tpu.parallel.mesh import MeshRuntime


@pytest.fixture(scope="module")
def runtime():
    return MeshRuntime(Settings())


def _titanic_like(store, name, n=400, seed=0):
    rng = np.random.default_rng(seed)
    pclass = rng.integers(1, 4, n)
    sex = rng.choice(["male", "female"], n)
    age = rng.normal(30, 12, n)
    age[rng.random(n) < 0.15] = np.nan  # missing ages like the real set
    fare = rng.lognormal(2.5, 1.0, n)
    logit = 1.5 * (sex == "female") - 0.5 * pclass + 0.01 * fare - 0.3
    surv = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.int64)
    store.create(name, columns={
        "Pclass": pclass.astype(np.int64),
        "Sex": np.array(sex, dtype=object),
        "Age": age, "Fare": fare, "Survived": surv}, finished=True)


def test_design_matrix_default_pipeline(store):
    _titanic_like(store, "train")
    ds = store.get("train")
    X, y, fields, state = design_matrix(ds, "Survived")
    assert X.shape == (400, 4)
    assert not np.isnan(X).any()          # mean-fill applied
    assert set(fields) == {"Pclass", "Sex", "Age", "Fare"}
    assert y.dtype == np.int32
    # same pipeline on "test" reuses fitted state (vocab + fill values)
    _titanic_like(store, "test", n=100, seed=1)
    X2, y2, _, _ = design_matrix(store.get("test"), "Survived",
                                 state=state, feature_fields=fields)
    assert X2.shape == (100, 4) and not np.isnan(X2).any()


def test_apply_steps_select_drop_standardize():
    cols = {"a": np.arange(10, dtype=np.float64),
            "b": np.arange(10, dtype=np.float64) * 3,
            "s": np.array(["x", "y"] * 5, dtype=object)}
    out, state = apply_steps(cols, [
        {"op": "drop", "fields": ["b"]},
        {"op": "label_encode", "fields": ["s"]},
        {"op": "standardize"}])
    assert set(out) == {"a", "s"}
    assert abs(out["a"].mean()) < 1e-9
    # test-time application reuses train stats
    out2, _ = apply_steps(cols, [
        {"op": "drop", "fields": ["b"]},
        {"op": "label_encode", "fields": ["s"]},
        {"op": "standardize"}], state=state)
    np.testing.assert_allclose(out2["a"], out["a"])


def test_build_five_classifiers(store, runtime, cfg):
    _titanic_like(store, "train")
    _titanic_like(store, "test", n=120, seed=2)
    mb = ModelBuilder(store, runtime, cfg)
    classifiers = ["lr", "dt", "rf", "gb", "nb"]
    mb.validate("train", "test", classifiers, "pred")
    reports = mb.build("train", "test", "pred", classifiers, "Survived")
    assert len(reports) == 5
    for r in reports:
        assert r.fit_time > 0
        assert r.metrics.get("accuracy", 0) > 0.6, r
        ds = store.get(f"pred_{r.kind}")
        doc = ds.metadata.to_doc()
        assert doc["finished"] is True
        assert doc["parent_filename"] == "test"
        assert 0 < doc["f1"] <= 1 and 0 < doc["accuracy"] <= 1
        assert doc["fit_time"] > 0
        # prediction rows: test columns + prediction + probability list
        row = ds.rows(np.arange(1))[0]
        assert "prediction" in row and "probability" in row
        assert len(row["probability"]) == 2
        assert ds.num_rows == 120


def test_build_validation_errors(store, runtime, cfg):
    _titanic_like(store, "train")
    mb = ModelBuilder(store, runtime, cfg)
    with pytest.raises(KeyError):
        mb.validate("train", "missing", ["lr"], "p")
    with pytest.raises(ValueError, match="invalid classifier"):
        mb.validate("train", "train", ["svm"], "p")


def test_build_failed_classifier_marks_dataset(store, runtime, cfg):
    """A classifier failing deterministically (gb with n_bins past the
    uint8 cap) must fail its dataset but not the others. (gb on a
    3-class label used to be the failure exemplar here; it is now a
    supported one-vs-rest fit — tests/test_models.py.)"""
    rng = np.random.default_rng(0)
    for name in ("tr3", "te3"):
        store.create(name, columns={
            "x": rng.normal(size=100), "y2": rng.normal(size=100),
            "lab": rng.integers(0, 3, 100).astype(np.int64)}, finished=True)
    mb = ModelBuilder(store, runtime, cfg)
    reports = mb.build("tr3", "te3", "p3", ["gb", "nb"], "lab",
                       hparams={"gb": {"n_bins": 512}})
    by_kind = {r.kind: r for r in reports}
    assert "error" in by_kind["gb"].metrics
    assert store.get("p3_gb").metadata.error is not None
    assert store.get("p3_nb").metadata.finished is True
    assert store.get("p3_nb").metadata.error is None


def test_build_multiclass_includes_gb(store, runtime, cfg):
    """gb on a 3-class label is a real fit now (one-vs-rest over the
    binary booster) — better than chance, pollable, normalized probs."""
    rng = np.random.default_rng(1)
    n = 600
    x = rng.normal(size=n)
    y2 = rng.normal(size=n)
    lab = (x + 0.3 * rng.normal(size=n) > 0.5).astype(np.int64) \
        + (x + 0.3 * rng.normal(size=n) > -0.5).astype(np.int64)
    for name, sl in (("m3tr", slice(0, 500)), ("m3te", slice(500, None))):
        store.create(name, columns={"x": x[sl], "y2": y2[sl],
                                    "lab": lab[sl]}, finished=True)
    mb = ModelBuilder(store, runtime, cfg)
    reports = mb.build("m3tr", "m3te", "m3p", ["gb"], "lab",
                       hparams={"gb": {"n_rounds": 5, "max_depth": 3}})
    assert "error" not in reports[0].metrics, reports[0].metrics
    assert reports[0].metrics["accuracy"] > 0.55
    out = store.get("m3p_gb")
    assert out.metadata.finished is True
    row = out.rows(np.arange(1))[0]
    assert len(row["probability"]) == 3
    assert abs(sum(row["probability"]) - 1.0) < 1e-5


def test_pipelined_build_matches_direct_sequential_fits(store, runtime, cfg):
    """Determinism of the pipelined scheduler: the overlapped build's
    prediction probabilities are identical to fitting each family
    directly, sequentially, on the same design matrix (same seeds, same
    programs — the scheduler must change WHEN things run, never what)."""
    from learningorchestra_tpu.models.registry import get_trainer

    _titanic_like(store, "ov_tr")
    _titanic_like(store, "ov_te", n=100, seed=7)
    cfg.max_concurrent_fits = 2
    mb = ModelBuilder(store, runtime, cfg)
    classifiers = ["lr", "nb", "dt"]
    reports = mb.build("ov_tr", "ov_te", "ovp", classifiers, "Survived")
    assert all("error" not in r.metrics for r in reports), reports
    assert all(r.metrics.get("device_s", 0) > 0 for r in reports)

    X, y, ff, state = design_matrix(store.get("ov_tr"), "Survived")
    Xt, yt, _, _ = design_matrix(store.get("ov_te"), "Survived",
                                 state=state, feature_fields=ff)
    for c in classifiers:
        model = get_trainer(c)(runtime, np.asarray(X, np.float32), y, 2)
        want = model.predict_proba(runtime, np.asarray(Xt, np.float32))
        got = np.stack(store.get(f"ovp_{c}").read_rows(
            ["probability"], 0, 100)["probability"])
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7,
                                   err_msg=c)


@pytest.mark.parametrize("streamed", [False, True],
                         ids=["resident", "streamed"])
@pytest.mark.parametrize("num_classes", [2, 10])
def test_probability_column_equals_the_per_row_loops(
        store, runtime, cfg, monkeypatch, num_classes, streamed):
    """The probability column is built without a loop over the rows and
    is what the loop built: a 1-D object array of Python lists of
    Python floats, in both branches of ``_save_predictions``."""
    from learningorchestra_tpu.models.base import FitReport

    _titanic_like(store, "pc_te", n=50, seed=5)
    cfg.stream_design = streamed
    mb = ModelBuilder(store, runtime, cfg)
    out = store.create("pc_out", parent="pc_te")
    probs = np.random.default_rng(num_classes).random(
        (50, num_classes)).astype(np.float32)
    want = [[float(x) for x in row] for row in probs]   # the per-row loop

    handed, real = [], out.append_columns
    monkeypatch.setattr(
        out, "append_columns",
        lambda cols: (handed.append(cols["probability"]), real(cols))[1])
    mb._save_predictions("pc_out", store.get("pc_te"),
                         np.argmax(probs, axis=1), probs,
                         FitReport(kind="nb", fit_time=0.0),
                         phase="fit.nb.finish")
    (col,) = handed
    assert col.dtype == object and col.shape == (50,)
    assert all(type(row) is list and all(type(x) is float for x in row)
               for row in col)
    assert list(col) == want
    stored = store.get("pc_out")
    assert stored.metadata.finished
    assert list(stored.read_rows(["probability"], 0, 50)["probability"]) \
        == want


def test_exec_preprocess_gated(store, runtime, cfg):
    _titanic_like(store, "train")
    _titanic_like(store, "test", n=50, seed=3)
    mb = ModelBuilder(store, runtime, cfg)
    with pytest.raises(PermissionError):
        mb.build("train", "test", "pe", ["nb"], "Survived",
                 preprocessor_code="features_training = 1")


def test_exec_preprocess_enabled(store, runtime, cfg):
    cfg.allow_exec_preprocessing = True
    _titanic_like(store, "train")
    _titanic_like(store, "test", n=50, seed=3)
    mb = ModelBuilder(store, runtime, cfg)
    code = """
import numpy as np
def prep(df):
    X = df[["Pclass", "Fare"]].to_numpy(dtype="float32")
    X = np.nan_to_num(X)
    return X
features_training = prep(training_df)
labels_training = training_df["Survived"].to_numpy()
features_testing = prep(testing_df)
labels_testing = testing_df["Survived"].to_numpy()
"""
    reports = mb.build("train", "test", "pe", ["nb"], "Survived",
                       preprocessor_code=code)
    assert reports[0].metrics["accuracy"] > 0.4


def test_fillna_fits_on_train_only():
    """The fill statistic comes from the fitting pass even when the fitted
    column had no NaN — test-set NaNs must use the TRAIN mean."""
    train = {"a": np.array([1.0, 2.0, 3.0])}          # no NaN at fit time
    test = {"a": np.array([np.nan, 10.0, np.nan])}
    steps = [{"op": "fillna", "strategy": "mean"}]
    _, state = apply_steps(train, steps)
    out, _ = apply_steps(test, steps, state=state)
    np.testing.assert_allclose(out["a"], [2.0, 10.0, 2.0])


# -- the save staged while the probability pass runs (ISSUE 39) --------------

TX_HP = {"tx": {"train_steps": 2, "batch": 16, "d_model": 16, "d_ff": 32,
                "n_heads": 2}}


def _tokens(store, name, n, seed, T=8, vocab=8):
    rng = np.random.default_rng(seed)
    cols = {f"t{j}": rng.integers(0, vocab, n).astype(np.int64)
            for j in range(T)}
    cols["label"] = (cols["t0"] < vocab // 2).astype(np.int64)
    store.create(name, columns=cols, finished=True)


@pytest.fixture()
def staging_build(store, runtime, cfg, monkeypatch):
    """A tx + nb build with model persistence on, where a tree of 4 KiB
    or more (the tiny tx model's 18 KB, not nb's 136 bytes) is written
    flat, and so staged."""
    from learningorchestra_tpu.models import persistence

    monkeypatch.setattr(persistence, "FLAT_BYTES", 4 << 10)
    cfg.persist_models = True
    _tokens(store, "sg_train", 64, 0)
    _tokens(store, "sg_test", 16, 1)
    return ModelBuilder(store, runtime, cfg)


def test_large_tree_is_staged_during_the_probability_pass(staging_build):
    """The tx model's leaves start for the disk before the probability
    pass ends, and ``fit.tx.finish.model`` says so; nb's small tree
    saves as before. Both models are saved whole."""
    import os

    from learningorchestra_tpu.utils import tracing

    mb = staging_build
    with tracing.trace("root", sampled=True) as root:
        reports = mb.build("sg_train", "sg_test", "sg", ["tx", "nb"],
                           "label", hparams=TX_HP)
    assert all("error" not in r.metrics and "persist_error" not in r.metrics
               for r in reports), [r.metrics for r in reports]
    by_name = {}
    for s in tracing.spans_for(root.trace_id):
        by_name.setdefault(s["name"], []).append(s)
    (tx_save,) = by_name["fit.tx.finish.model"]
    assert tx_save["attrs"]["save_staged"] is True
    assert tx_save["attrs"]["save_ahead_s"] > 0
    (stage,) = by_name["fit.tx.finish.model.stage"]
    (device,) = by_name["fit.tx.device"]
    (fit,) = by_name["fit.tx"]
    assert stage["parent_id"] == fit["span_id"]
    assert stage["start"] < device["start"] + device["duration_ms"] / 1e3
    assert all(s["parent_id"] == stage["span_id"]
               for p in ("fetch", "write", "sync")
               for s in by_name[f"fit.tx.finish.model.{p}"])
    (nb_save,) = by_name["fit.nb.finish.model"]
    assert nb_save["attrs"] == {"save_staged": False, "save_ahead_s": 0.0}
    assert "fit.nb.finish.model.stage" not in by_name
    assert sorted(m["name"] for m in mb.registry.list()) == ["sg_nb", "sg_tx"]
    man, model = mb.registry.load("sg_tx")
    assert man["metrics"]["accuracy"] == next(
        r.metrics["accuracy"] for r in reports if r.kind == "tx")
    assert sorted(os.listdir(mb.registry.root)) == ["sg_nb", "sg_tx"]


def test_failed_probability_pass_leaves_no_model_and_no_staging(
        staging_build, monkeypatch):
    """A probability pass that raises after the staging started fails
    the family as before: no persisted model, no staging directory;
    the other family is untouched."""
    import os

    from learningorchestra_tpu.models import base

    real = base.TrainedModel.predict_proba

    def broken(self, runtime, X):
        if self.kind == "tx":
            raise RuntimeError("predict pass lost the device")
        return real(self, runtime, X)

    monkeypatch.setattr(base.TrainedModel, "predict_proba", broken)
    mb = staging_build
    reports = {r.kind: r for r in mb.build(
        "sg_train", "sg_test", "sf", ["tx", "nb"], "label", hparams=TX_HP)}
    assert "predict pass lost the device" in reports["tx"].metrics["error"]
    assert mb.store.get("sf_tx").metadata.error is not None
    assert not mb.registry.exists("sf_tx")
    assert mb.registry.exists("sf_nb")
    assert sorted(os.listdir(mb.registry.root)) == ["sf_nb"]
