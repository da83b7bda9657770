"""Trainer numerics + ModelBuilder tests on the 8-device CPU mesh.

Parity strategy per SURVEY.md §4: every family must beat a sanity floor on a
separable synthetic task, and lr/nb/dt/rf are cross-checked against sklearn
on the same data (the reference's only published metrics are Titanic
F1≈0.703 / acc≈0.703 for nb — our floors are set well above chance and near
sklearn's result)."""

import numpy as np
import pytest

from learningorchestra_tpu.config import Settings
from learningorchestra_tpu.models.metrics import classification_metrics
from learningorchestra_tpu.models.registry import CLASSIFIERS, get_trainer
from learningorchestra_tpu.parallel.mesh import MeshRuntime


@pytest.fixture(scope="module")
def runtime():
    return MeshRuntime(Settings())


def _blobs(n=600, d=6, classes=2, seed=0, sep=2.0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(classes, d)) * sep
    y = rng.integers(0, classes, size=n)
    X = centers[y] + rng.normal(size=(n, d))
    return X.astype(np.float32), y.astype(np.int32)


def _split(X, y, frac=0.25):
    n_test = int(len(X) * frac)
    return X[n_test:], y[n_test:], X[:n_test], y[:n_test]


def _acc(runtime, model, X, y):
    preds = model.predict(runtime, X)
    return float((preds == y).mean())


# "tx" is excluded: it consumes token sequences, not continuous feature
# vectors — casting gaussian blobs to ints is out-of-domain for it. Its
# end-to-end coverage (REST, dp×tp×sp mesh) lives in test_sequence.py.
@pytest.mark.parametrize("kind", sorted(set(CLASSIFIERS) - {"tx"}))
def test_trainer_beats_floor_binary(runtime, kind):
    X, y = _blobs(n=600, classes=2)
    Xtr, ytr, Xte, yte = _split(X, y)
    model = get_trainer(kind)(runtime, Xtr, ytr, 2)
    assert _acc(runtime, model, Xte, yte) > 0.9, kind


@pytest.mark.parametrize("kind", ["lr", "nb", "dt", "rf", "mlp"])
def test_trainer_multiclass(runtime, kind):
    X, y = _blobs(n=900, classes=3, sep=3.0)
    Xtr, ytr, Xte, yte = _split(X, y)
    model = get_trainer(kind)(runtime, Xtr, ytr, 3)
    assert _acc(runtime, model, Xte, yte) > 0.85, kind


def test_gb_multiclass_one_vs_rest_parity(runtime):
    """Multiclass gb (beyond the reference — Spark 2.4's GBTClassifier is
    binary-only) is one-vs-rest over the existing binary builder: booster
    k's probabilities must equal a standalone binary gb fit on ``y == k``
    with the same bins, and the multiclass output is their normalized
    sigmoid scores."""
    X, y = _blobs(n=240, classes=3, seed=4)
    Xtr, ytr, Xte, yte = _split(X, y)
    hp = dict(n_rounds=4, max_depth=3)
    model = get_trainer("gb")(runtime, Xtr, ytr, 3, **hp)
    probs = model.predict_proba(runtime, Xte)
    assert probs.shape == (len(Xte), 3)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-5)
    assert model.hparams["ovr_classes"] == 3
    assert _acc(runtime, model, Xte, yte) > 0.8

    # Booster-k parity: identical edges (shared binning) and identical
    # per-class sigmoid scores as the standalone binary fit on y == k.
    from learningorchestra_tpu.models import trees

    edges = trees._edge_prep(Xtr)["edges"]
    binary_scores = []
    for k in range(3):
        mk = get_trainer("gb")(runtime, Xtr,
                               (ytr == k).astype(np.int32), 2, **hp)
        np.testing.assert_array_equal(
            np.asarray(mk.params["edges"]), np.asarray(edges))
        binary_scores.append(mk.predict_proba(runtime, Xte)[:, 1])
    scores = np.stack(binary_scores, axis=1)
    want = scores / np.maximum(scores.sum(axis=1, keepdims=True), 1e-12)
    np.testing.assert_allclose(probs, want, rtol=1e-5, atol=1e-6)


def test_gb_multiclass_persistence_roundtrip(runtime, tmp_path):
    """A one-vs-rest gb checkpoint re-serves through the registry (the
    ovr predictor is selected from the persisted hparams)."""
    from learningorchestra_tpu.models.persistence import ModelRegistry

    cfg = Settings()
    cfg.store_root = str(tmp_path)
    X, y = _blobs(n=150, classes=3, seed=5)
    model = get_trainer("gb")(runtime, X, y, 3, n_rounds=3, max_depth=3)
    reg = ModelRegistry(cfg)
    reg.save("gb3", model, metrics={}, preprocess=None)
    _, loaded = reg.load("gb3")
    np.testing.assert_allclose(loaded.predict_proba(runtime, X),
                               model.predict_proba(runtime, X),
                               rtol=1e-6, atol=1e-7)


def test_unknown_classifier():
    with pytest.raises(ValueError, match="invalid classifier"):
        get_trainer("xgboost")


def test_nb_multinomial_matches_sklearn(runtime):
    """The reference-parity multinomial event model must match sklearn's
    MultinomialNB probabilities on count data and refuse signed input."""
    from sklearn.naive_bayes import MultinomialNB

    rng = np.random.default_rng(3)
    n, d, C = 600, 12, 3
    y = rng.integers(0, C, n)
    rates = rng.uniform(0.5, 6.0, size=(C, d))
    X = rng.poisson(rates[y]).astype(np.float32)

    tr = get_trainer("nb")
    model = tr(runtime, X, y, C, event_model="multinomial", smoothing=1.0)
    probs = model.predict_proba(runtime, X)

    # Spark (the parity target) Laplace-smooths the class prior too:
    # pi_c = (n_c + lambda) / (n + C*lambda). sklearn leaves the prior
    # unsmoothed, so hand it the Spark prior to compare like for like.
    counts = np.bincount(y, minlength=C).astype(np.float64)
    spark_prior = (counts + 1.0) / (counts.sum() + C)
    sk = MultinomialNB(alpha=1.0, class_prior=spark_prior).fit(X, y)
    np.testing.assert_allclose(probs, sk.predict_proba(X),
                               rtol=2e-4, atol=2e-5)

    with pytest.raises(ValueError, match="non-negative"):
        tr(runtime, X - 5.0, y, C, event_model="multinomial")

    # Persistence restores the right predictor for the variant.
    from learningorchestra_tpu.models import naive_bayes
    from learningorchestra_tpu.models.registry import predictor_for
    assert (predictor_for("nb", model.hparams)
            is naive_bayes._predict_multinomial)
    assert (predictor_for("nb", {"smoothing": 1e-3})
            is naive_bayes._predict_proba)


def test_lr_device_stats_avoid_cancellation(runtime):
    """Regression: standardization stats computed on-device must use the
    two-pass form — E[x²]−E[x]² in f32 collapses for |mean| ≫ std (e.g.
    a year column), which would silently feed the solver unstandardized
    features."""
    from learningorchestra_tpu.models import logistic

    rng = np.random.default_rng(0)
    n = 4096
    X = np.stack([rng.normal(2.0e4, 1.0, n),       # year/price-like
                  rng.normal(0.0, 3.0, n)], axis=1).astype(np.float32)
    X_dev, nn = runtime.shard_rows(X)
    mu, sigma = logistic._device_stats(
        X_dev, runtime.replicate(np.int32(nn)), mesh=runtime.mesh)
    np.testing.assert_allclose(np.asarray(mu), X.mean(0), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sigma), X.std(0), rtol=2e-2)


def test_lr_matches_sklearn(runtime):
    from sklearn.linear_model import LogisticRegression

    X, y = _blobs(n=800, classes=2, sep=1.2)
    Xtr, ytr, Xte, yte = _split(X, y)
    ours = get_trainer("lr")(runtime, Xtr, ytr, 2)
    sk = LogisticRegression(max_iter=1000).fit(Xtr, ytr)
    ours_acc = _acc(runtime, ours, Xte, yte)
    sk_acc = float((sk.predict(Xte) == yte).mean())
    assert ours_acc >= sk_acc - 0.03


def test_nb_matches_sklearn(runtime):
    from sklearn.naive_bayes import GaussianNB

    X, y = _blobs(n=800, classes=2, sep=1.2)
    Xtr, ytr, Xte, yte = _split(X, y)
    ours = get_trainer("nb")(runtime, Xtr, ytr, 2)
    sk = GaussianNB().fit(Xtr, ytr)
    assert _acc(runtime, ours, Xte, yte) >= \
        float((sk.predict(Xte) == yte).mean()) - 0.03


def test_dt_matches_sklearn(runtime):
    from sklearn.tree import DecisionTreeClassifier

    X, y = _blobs(n=800, classes=2, sep=1.0, seed=3)
    Xtr, ytr, Xte, yte = _split(X, y)
    ours = get_trainer("dt")(runtime, Xtr, ytr, 2)
    sk = DecisionTreeClassifier(max_depth=5).fit(Xtr, ytr)
    assert _acc(runtime, ours, Xte, yte) >= \
        float((sk.predict(Xte) == yte).mean()) - 0.05


def test_rf_matches_sklearn(runtime):
    from sklearn.ensemble import RandomForestClassifier

    X, y = _blobs(n=800, classes=2, sep=1.0, seed=5)
    Xtr, ytr, Xte, yte = _split(X, y)
    ours = get_trainer("rf")(runtime, Xtr, ytr, 2)
    sk = RandomForestClassifier(n_estimators=20, max_depth=5,
                                random_state=0).fit(Xtr, ytr)
    assert _acc(runtime, ours, Xte, yte) >= \
        float((sk.predict(Xte) == yte).mean()) - 0.05


def test_metrics_weighted_f1_matches_sklearn():
    from sklearn.metrics import accuracy_score, f1_score

    rng = np.random.default_rng(0)
    y = rng.integers(0, 3, 200)
    p = rng.integers(0, 3, 200)
    m = classification_metrics(y, p, 3)
    assert m["accuracy"] == pytest.approx(accuracy_score(y, p))
    assert m["f1"] == pytest.approx(
        f1_score(y, p, average="weighted"), abs=1e-6)


def _scatter_metrics(y_true, y_pred, num_classes):
    """The reference: the confusion matrix as one float32 scatter-add on
    the device (what the program ran before ISSUE 31), then f1 and
    accuracy by their definitions."""
    import jax.numpy as jnp

    idx = jnp.asarray(y_true, jnp.int32) * num_classes \
        + jnp.asarray(y_pred, jnp.int32)
    cm = np.asarray(jnp.zeros(num_classes * num_classes, jnp.float32)
                    .at[idx].add(1.0)).reshape(num_classes, num_classes)
    support, tp, pred_pos = cm.sum(axis=1), np.diag(cm), cm.sum(axis=0)
    precision = np.where(pred_pos > 0, tp / np.maximum(pred_pos, 1), 0.0)
    recall = np.where(support > 0, tp / np.maximum(support, 1), 0.0)
    denom = precision + recall
    f1 = np.where(denom > 0,
                  2 * precision * recall / np.maximum(denom, 1e-12), 0.0)
    total = max(support.sum(), 1)
    return {"f1": float((f1 * support).sum() / total),
            "accuracy": float(tp.sum() / total)}


def _labels(num_classes, n, true_of=None, pred_of=None, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.choice(true_of or num_classes, n).astype(np.int32),
            rng.choice(pred_of or num_classes, n).astype(np.int64))


@pytest.mark.parametrize("num_classes,y,p", [
    (2, *_labels(2, 200)),
    (3, *_labels(3, 200)),
    (10, *_labels(10, 500)),
    (3, *_labels(3, 200, true_of=[0, 1])),       # class 2 has no support
    (3, *_labels(3, 200, pred_of=[0, 2])),       # class 1 is never predicted
    (2, *_labels(2, 1)),
], ids=["2", "3", "10", "no-support", "never-predicted", "one-row"])
def test_metrics_equal_the_scatter_reference_with_no_device_call(
        num_classes, y, p):
    """Scoring is host work: equal to the device scatter it replaced,
    digit for digit, with no transfer and no program compiled."""
    import jax

    from learningorchestra_tpu.utils import resources

    want = _scatter_metrics(y, p, num_classes)
    resources.ensure_listener()
    compiles = resources.compile_snapshot()["compiles"]
    with jax.transfer_guard("disallow"):
        got = classification_metrics(y, p, num_classes)
    assert resources.compile_snapshot()["compiles"] == compiles
    assert got == want


def test_probabilities_sum_to_one(runtime):
    X, y = _blobs(n=300, classes=2)
    for kind in ("lr", "nb", "gb", "rf"):
        model = get_trainer(kind)(runtime, X, y, 2)
        probs = model.predict_proba(runtime, X[:50])
        assert probs.shape == (50, 2)
        np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-3)
