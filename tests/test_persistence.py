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


# -- the save's spans (ISSUE 38) ---------------------------------------------

class _NumpyWatch:
    """``numpy`` as ``persistence`` sees it, with each ``asarray`` (the
    wait for a leaf) noted in ``events``."""

    def __init__(self, events):
        self._events = events

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kwargs):
        self._events.append(("wait",))
        return np.asarray(a, *args, **kwargs)


@pytest.fixture()
def save_events(monkeypatch):
    """Small flat saves (every tree flat, leaves of 2 KiB or more synced
    one by one); the device-to-host copies started, the waits, and each
    sync call with the file's size at that moment, in order."""
    import os

    import jax.numpy as jnp

    from learningorchestra_tpu.models import persistence

    monkeypatch.setattr(persistence, "FLAT_BYTES", 1)
    monkeypatch.setattr(persistence, "_SYNC_BYTES", 2048)
    events = []
    monkeypatch.setattr(persistence, "np", _NumpyWatch(events))
    array_cls = type(jnp.zeros(1))
    copy = array_cls.copy_to_host_async

    def noted_copy(self):
        events.append(("copy",))
        return copy(self)

    monkeypatch.setattr(array_cls, "copy_to_host_async", noted_copy)
    for call in ("fdatasync", "fsync"):
        def noted_sync(fd, _call=call, _real=getattr(os, call)):
            events.append((_call, os.fstat(fd).st_size))
            return _real(fd)

        monkeypatch.setattr(os, call, noted_sync)
    return events


def _flat_model():
    import jax.numpy as jnp

    from learningorchestra_tpu.models.base import TrainedModel

    # Two leaves at or over the sync size (4,096 and 2,048 bytes), two
    # under it, one of them already on the host.
    params = {"a": {"w": jnp.arange(1024, dtype=jnp.float32).reshape(32, 32),
                    "b": jnp.ones(8, jnp.float32)},
              "c": jnp.full((16, 64), 2, jnp.bfloat16),
              "d": np.arange(6, dtype=np.int32)}
    return TrainedModel(kind="tx", params=params, predict_proba_fn=None,
                        num_classes=2)


def test_flat_save_spans_nest_under_the_callers_phase(cfg, save_events):
    """Under a trace, one ``.fetch`` and one ``.write`` a leaf and one
    ``.sync`` a sync call, all inside the caller's span; the save itself
    unchanged: the same files byte for byte, the same syncs in the same
    order, every copy started before the first wait. With no trace the
    same save records nothing."""
    import os

    from learningorchestra_tpu.models import persistence
    from learningorchestra_tpu.utils import tracing

    reg = ModelRegistry(cfg)
    phase = "fit.tx.finish.model"
    with tracing.trace("root", sampled=True) as root, tracing.span(phase):
        reg.save("sp_traced", _flat_model(), phase=phase)
    traced = list(save_events)
    save_events.clear()
    recorded = tracing.counters_snapshot()["spans_recorded"]
    reg.save("sp_plain", _flat_model(), phase=phase)
    assert tracing.counters_snapshot()["spans_recorded"] == recorded
    assert save_events == traced

    syncs = [e for e in traced if e[0] in ("fdatasync", "fsync")]
    assert [e[0] for e in syncs] == ["fdatasync", "fdatasync", "fsync"]
    waits = [i for i, e in enumerate(traced) if e == ("wait",)]
    copies = [i for i, e in enumerate(traced) if e == ("copy",)]
    assert len(copies) == 3 and len(waits) == 4 and max(copies) < min(waits)

    by_name = {}
    for s in tracing.spans_for(root.trace_id):
        by_name.setdefault(s["name"], []).append(s)
    (parent,) = by_name[phase]
    parts = {p: by_name.get(f"{phase}.{p}", [])
             for p in ("fetch", "write", "sync")}
    assert (len(parts["fetch"]), len(parts["write"]),
            len(parts["sync"])) == (4, 4, len(syncs))
    children = [s for group in parts.values() for s in group]
    assert all(s["parent_id"] == parent["span_id"] for s in children)
    # Docs round each span to the microsecond.
    assert sum(s["duration_ms"] for s in children) <= \
        parent["duration_ms"] + 1e-3 * len(children)

    for f in ("params.bin", "params.json"):
        with open(os.path.join(reg.root, "sp_traced", f), "rb") as a, \
                open(os.path.join(reg.root, "sp_plain", f), "rb") as b:
            assert a.read() == b.read(), f
    back = persistence._read_flat(os.path.join(reg.root, "sp_traced"))
    assert np.array_equal(back["a"]["w"], np.arange(
        1024, dtype=np.float32).reshape(32, 32))


def test_checkpoint_layer_save_is_one_fetch_and_one_write(cfg):
    """A tree under ``FLAT_BYTES`` goes through the checkpoint layer:
    one ``.fetch`` around bringing it to the host, one ``.write`` around
    the checkpoint's save (its own syncs inside), no ``.sync``."""
    import jax.numpy as jnp

    from learningorchestra_tpu.models.base import TrainedModel
    from learningorchestra_tpu.utils import tracing

    model = TrainedModel(kind="nb", params={"m": jnp.ones((3, 2)),
                                            "v": jnp.zeros(3)},
                         predict_proba_fn=None, num_classes=2)
    reg = ModelRegistry(cfg)
    phase = "fit.nb.finish.model"
    with tracing.trace("root", sampled=True) as root, tracing.span(phase):
        reg.save("sp_orbax", model, phase=phase)
    spans = tracing.spans_for(root.trace_id)
    (parent,) = [s for s in spans if s["name"] == phase]
    children = [s for s in spans if s["name"].startswith(phase + ".")]
    assert sorted(s["name"] for s in children) == [
        phase + ".fetch", phase + ".write"]
    assert all(s["parent_id"] == parent["span_id"] for s in children)


# -- a save staged ahead of its commit (ISSUE 39) -----------------------------

def _entries(reg):
    import os

    return sorted(os.listdir(reg.root)) if os.path.isdir(reg.root) else []


def _files(reg, name):
    import json
    import os

    d = os.path.join(reg.root, name)
    out = {}
    for f in ("params.bin", "params.json"):
        with open(os.path.join(d, f), "rb") as fh:
            out[f] = fh.read()
    with open(os.path.join(d, "manifest.json")) as fh:
        man = json.load(fh)
    man.pop("time_created")
    man.pop("name")
    return out, man


@pytest.fixture()
def flat_small(monkeypatch):
    """Every tree flat, leaves of 2 KiB or more synced one by one."""
    from learningorchestra_tpu.models import persistence

    monkeypatch.setattr(persistence, "FLAT_BYTES", 1)
    monkeypatch.setattr(persistence, "_SYNC_BYTES", 2048)


def test_staged_save_writes_what_the_one_call_save_writes(cfg, save_events):
    """Stage, then commit: ``params.bin``, ``params.json`` and the
    manifest are the one-call save's byte for byte, with the same syncs
    in the same order; the staging directory is gone."""
    reg = ModelRegistry(cfg)
    meta = {"metrics": {"accuracy": 0.5}, "preprocess": {"label": "y"}}
    reg.save("st_plain", _flat_model(), phase="p", **meta)
    plain_events = list(save_events)
    save_events.clear()
    staged = reg.stage("st_staged", _flat_model(), phase="p")
    assert staged is not None
    reg.save("st_staged", _flat_model(), phase="p", staged=staged, **meta)
    # The commit's model was never read: the leaves are the staging's.
    assert save_events == plain_events
    assert _files(reg, "st_staged") == _files(reg, "st_plain")
    assert _entries(reg) == ["st_plain", "st_staged"]
    assert reg.manifest("st_staged")["metrics"] == {"accuracy": 0.5}


def test_discarded_stage_leaves_no_model_and_no_staging(cfg, flat_small):
    reg = ModelRegistry(cfg)
    staged = reg.stage("st_gone", _flat_model())
    staged.discard()
    assert _entries(reg) == []
    assert not reg.exists("st_gone")
    staged.discard()                  # twice: nothing left to remove


def test_small_tree_is_not_staged(cfg):
    """A tree under ``FLAT_BYTES`` takes the checkpoint layer's path in
    ``save`` and is never staged."""
    reg = ModelRegistry(cfg)
    assert reg.stage("st_small", _flat_model()) is None
    assert _entries(reg) == []


def test_failed_stage_raises_at_save_and_keeps_the_live_version(
        cfg, flat_small, monkeypatch):
    """A writer that fails (here after writing ``params.bin``) is raised
    by ``save(staged=...)``; the name's previous version stays live and
    whole, and no staging is left."""
    from learningorchestra_tpu.models import persistence

    reg = ModelRegistry(cfg)
    reg.save("st_live", _flat_model(), metrics={"version": 1})
    before = _files(reg, "st_live")
    real = persistence._write_flat

    def full_disk(d, leaves, phase):
        real(d, leaves, phase)
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(persistence, "_write_flat", full_disk)
    bigger = _flat_model()
    bigger.params["d"] = np.arange(60, dtype=np.int32)
    staged = reg.stage("st_live", bigger)
    with pytest.raises(OSError, match="No space left"):
        reg.save("st_live", bigger, metrics={"version": 2}, staged=staged)
    assert _files(reg, "st_live") == before
    assert reg.manifest("st_live")["metrics"] == {"version": 1}
    assert _entries(reg) == ["st_live"]


def test_two_stages_of_one_name_write_apart(cfg, flat_small, monkeypatch):
    """Two stagings of one name at once (their writers meet inside the
    write) each keep their own files; each commit swaps in its own."""
    import os
    import threading

    from learningorchestra_tpu.models import persistence

    both = threading.Barrier(2, timeout=60)
    real = persistence._write_flat

    def meet(d, leaves, phase):
        both.wait()
        real(d, leaves, phase)

    monkeypatch.setattr(persistence, "_write_flat", meet)
    reg = ModelRegistry(cfg)
    one, two = _flat_model(), _flat_model()
    two.params["d"] = np.arange(6, dtype=np.int32) + 100
    s1 = reg.stage("st_same", one)
    s2 = reg.stage("st_same", two)
    s1.join()
    s2.join()
    assert s1.dir != s2.dir
    assert all(os.path.basename(d).startswith(".tmp.st_same.")
               for d in (s1.dir, s2.dir))
    live = os.path.join(reg.root, "st_same")
    reg.save("st_same", two, staged=s2)
    assert np.array_equal(persistence._read_flat(live)["d"],
                          np.arange(6) + 100)
    reg.save("st_same", one, staged=s1)
    assert np.array_equal(persistence._read_flat(live)["d"], np.arange(6))
    assert _entries(reg) == ["st_same"]


def test_staged_parts_keep_their_names_under_the_stage_span(cfg, flat_small):
    """The writer's spans: ``<phase>.stage`` under the context that
    staged, holding ``<phase>.fetch`` / ``.write`` / ``.sync`` under
    their PR 38 names; the commit's ``<phase>`` span holds none."""
    from learningorchestra_tpu.utils import tracing

    reg = ModelRegistry(cfg)
    phase = "fit.tx.finish.model"
    with tracing.trace("root", sampled=True) as root:
        with tracing.span("fit.tx") as fit:
            staged = reg.stage("st_spans", _flat_model(), phase=phase)
            staged.join()
            assert staged.ahead_s() > 0
            with tracing.span(phase):
                reg.save("st_spans", _flat_model(), phase=phase,
                         staged=staged)
    by_name = {}
    for s in tracing.spans_for(root.trace_id):
        by_name.setdefault(s["name"], []).append(s)
    (stage,) = by_name[phase + ".stage"]
    (commit,) = by_name[phase]
    assert stage["parent_id"] == fit.span_id
    assert commit["parent_id"] == fit.span_id
    parts = [s for p in ("fetch", "write", "sync")
             for s in by_name[f"{phase}.{p}"]]
    assert len(by_name[phase + ".fetch"]) == 4
    assert len(by_name[phase + ".sync"]) == 3
    assert all(s["parent_id"] == stage["span_id"] for s in parts)
