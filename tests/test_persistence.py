"""Model persistence (orbax) + re-serving + metrics observability."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from learningorchestra_tpu.models.builder import ModelBuilder  # noqa: E402
from learningorchestra_tpu.models.persistence import (  # noqa: E402
    ModelNotFound, ModelRegistry)
from learningorchestra_tpu.parallel.mesh import MeshRuntime  # noqa: E402


def _toy_columns(n, seed):
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    sex = rng.choice(["a", "b"], n).astype(object)
    y = ((x1 + (sex == "b") * 1.5 + rng.normal(0, 0.3, n)) > 0.7).astype(
        np.int64)
    return {"x1": x1, "x2": x2, "sex": sex, "label": y}


@pytest.fixture()
def built(store, cfg):
    runtime = MeshRuntime(cfg)
    cfg.persist_models = True
    store.create("pt_train", columns=_toy_columns(400, 0), finished=True)
    store.create("pt_test", columns=_toy_columns(100, 1), finished=True)
    mb = ModelBuilder(store, runtime, cfg)
    reports = mb.build("pt_train", "pt_test", "ptm", ["lr", "dt"], "label")
    return mb, reports


def test_roundtrip_predictions_identical(built, store):
    """A restored model must reproduce the exact predictions the live
    model wrote, including the train-time preprocessing state."""
    mb, reports = built
    assert {r.kind for r in reports} == {"lr", "dt"}
    assert all(r.metrics["accuracy"] > 0.7 for r in reports)

    names = [m["name"] for m in mb.registry.list()]
    assert sorted(names) == ["ptm_dt", "ptm_lr"]
    man = mb.registry.manifest("ptm_lr")
    assert man["kind"] == "lr" and man["preprocess"]["label"] == "label"

    mb.predict("ptm_lr", "pt_test", "served_lr")
    live = [r["prediction"] for r in
            store.read("served_lr", skip=1, limit=20)]
    orig = [r["prediction"] for r in store.read("ptm_lr", skip=1, limit=20)]
    assert live == orig
    assert store.get("served_lr").metadata.finished


def test_forest_predictor_rebuilds_from_hparams(built, store):
    """dt/rf/gb predictors carry static args (max_depth) in hparams; a
    fresh registry instance (new process) must rebuild them."""
    mb, _ = built
    reg2 = ModelRegistry(mb.cfg)
    man, model = reg2.load("ptm_dt")
    cols = _toy_columns(50, 2)
    X = np.stack([cols["x1"], cols["x2"],
                  (cols["sex"] == "b").astype(np.float64)], axis=1)
    probs = model.predict_proba(mb.runtime, X.astype(np.float32))
    assert probs.shape == (50, 2)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, rtol=1e-5)


def test_delete_and_missing(built):
    mb, _ = built
    mb.registry.delete("ptm_dt")
    assert not mb.registry.exists("ptm_dt")
    with pytest.raises(ModelNotFound):
        mb.registry.load("ptm_dt")


def test_exec_models_refuse_dataset_predict(store, cfg):
    runtime = MeshRuntime(cfg)
    cfg.persist_models = True
    cfg.allow_exec_preprocessing = True
    store.create("pe_train", columns=_toy_columns(200, 3), finished=True)
    store.create("pe_test", columns=_toy_columns(50, 4), finished=True)
    mb = ModelBuilder(store, runtime, cfg)
    code = (
        "import numpy as np\n"
        "features_training = np.stack([training_df['x1'],"
        " training_df['x2']], 1)\n"
        "labels_training = training_df['label'].to_numpy()\n"
        "features_testing = np.stack([testing_df['x1'],"
        " testing_df['x2']], 1)\n"
        "labels_testing = testing_df['label'].to_numpy()\n")
    mb.build("pe_train", "pe_test", "pem", ["lr"], "label",
             preprocessor_code=code)
    with pytest.raises(ValueError, match="exec-preprocessed"):
        mb.predict("pem_lr", "pe_test", "pe_out")


def test_op_timer_records_fits(built):
    from learningorchestra_tpu.utils.profiling import op_timer

    snap = op_timer.snapshot()
    assert snap["fit.lr"]["count"] >= 1
    assert snap["fit.lr"]["total_s"] > 0


def test_interrupted_hot_swap_recovers_on_init(built):
    """A crash between save()'s two swap renames (live dir parked at
    .old.<name>, new version still staged at .tmp.<name>) must not lose
    the durably-saved model: a fresh registry promotes the parked
    version back and clears the staging dirs (review finding)."""
    import os
    import shutil

    mb, _ = built
    reg = mb.registry
    d = os.path.join(reg.root, "ptm_lr")
    old = os.path.join(reg.root, ".old.ptm_lr")
    tmp = os.path.join(reg.root, ".tmp.ptm_lr")
    want = reg.manifest("ptm_lr")
    # Simulate the mid-swap crash state.
    shutil.copytree(d, tmp)
    os.rename(d, old)
    assert not os.path.isdir(d)

    reg2 = ModelRegistry(mb.cfg)
    assert reg2.exists("ptm_lr")
    assert reg2.manifest("ptm_lr") == want
    assert not os.path.isdir(old) and not os.path.isdir(tmp)
    man, model = reg2.load("ptm_lr")        # checkpoint restores cleanly
    assert man["kind"] == "lr"
    # Completed-swap stray: .old left behind AFTER the new version went
    # live must be cleaned, not promoted over it.
    shutil.copytree(os.path.join(reg2.root, "ptm_dt"),
                    os.path.join(reg2.root, ".old.ptm_dt"))
    reg3 = ModelRegistry(mb.cfg)
    assert reg3.manifest("ptm_dt") == reg2.manifest("ptm_dt")
    assert not os.path.isdir(os.path.join(reg3.root, ".old.ptm_dt"))


def test_models_wait_only_for_their_own_name(built, monkeypatch):
    """One lock per model name (ISSUE 31): while a re-save of model A is
    held inside its orbax write, save / load / version / manifest of
    model B return; a load of A waits the save out and then reads one
    whole version — the new manifest with the new params."""
    import threading

    import orbax.checkpoint as ocp

    mb, _ = built
    reg = mb.registry
    man_a, model_a = reg.load("ptm_lr")
    _, model_b = reg.load("ptm_dt")
    old_version = reg.version("ptm_lr")
    old_leaves = jax.tree.leaves(model_a.params)

    entered, release = threading.Event(), threading.Event()

    class Held(ocp.PyTreeCheckpointer):
        def save(self, directory, *args, **kwargs):
            if ".tmp.ptm_lr" in str(directory):
                entered.set()
                assert release.wait(60)
            return super().save(directory, *args, **kwargs)

    monkeypatch.setattr(ocp, "PyTreeCheckpointer", Held)

    def in_thread(fn, *args, **kwargs):
        box = []
        t = threading.Thread(
            target=lambda: box.append(fn(*args, **kwargs)), daemon=True)
        t.start()
        return t, box

    model_a.params = jax.tree.map(lambda x: np.asarray(x) + 1.0,
                                  model_a.params)
    saver, _ = in_thread(reg.save, "ptm_lr", model_a,
                         metrics={"version": 2},
                         preprocess=man_a["preprocess"])
    try:
        assert entered.wait(60)
        # Model B: every entry point returns while A's save is held.
        for fn, args in ((reg.save, ("ptm_dt", model_b)),
                         (reg.load, ("ptm_dt",)),
                         (reg.version, ("ptm_dt",)),
                         (reg.manifest, ("ptm_dt",))):
            t, box = in_thread(fn, *args)
            t.join(60)
            assert box, f"{fn.__name__} of ptm_dt waited for ptm_lr's save"
        # Model A, meanwhile: the lock-free reads still see the old
        # version (never missing), and a load waits.
        assert reg.version("ptm_lr") == old_version
        assert reg.manifest("ptm_lr")["metrics"] == man_a["metrics"]
        loader, loaded = in_thread(reg.load, "ptm_lr")
        loader.join(0.5)
        assert loader.is_alive() and not loaded
    finally:
        release.set()
    saver.join(60)
    loader.join(60)
    assert not saver.is_alive() and not loader.is_alive()
    (man, model), = loaded
    assert man["metrics"] == {"version": 2}
    for new, old in zip(jax.tree.leaves(model.params), old_leaves):
        np.testing.assert_array_equal(new, np.asarray(old) + 1.0)
    assert reg.version("ptm_lr") > old_version
