"""The comparison that decides ``correct``, shown to fail.

1. The control: the plain reference one precision down (tables rounded to
   bfloat16 for the tree families, to float8 for lr / nb), put in the
   program's place, reads gaps far over the limits that sound runs keep.
   On the chip it was run at the cell's own size (PERF.md has the
   readings); here at a size a test run can hold.
2. A whole run of the harness on the CPU at a tiny size (the look for a
   chip skipped, everything else as on the chip: the server, the client,
   the window, the read-back, the reference, the comparison) comes out
   ``correct``; with the timed path broken underneath it comes out not:
   once with half of the training rows left out of every fit, once with
   an answer altered where it is produced.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY = os.path.join(REPO, "tests", "perfbench", "tiny")


def test_lower_precision_control_reads_over_every_limit():
    from perfbench import cells, datagen, reference

    cell = cells.load_cell("tiny.sweep", TINY)
    fam = cell["config"]["families"]
    XT, y = datagen.make_table(4096, 28, 11)
    XT_test, _ = datagen.make_table(1024, 28, 12)
    kinds = ["lr", "dt", "gb", "nb"]          # rf: same code as dt
    stated = cell["config"]["precision"]["reference"]
    ref = reference.fit_predict(XT, y, XT_test, fam, kinds, stated)
    again = reference.fit_predict(XT, y, XT_test, fam, kinds, stated)
    ctl = reference.fit_predict(XT, y, XT_test, fam, kinds,
                                cell["config"]["precision"]["control"])
    for k in kinds:
        assert np.array_equal(ref[k], again[k])       # same seed, same answer
        gap = np.abs(ctl[k] - ref[k])
        reads = {f"gap.{k}": float(gap.mean()),
                 f"off.{k}": float((gap > cell["tolerance"][k]).mean())}
        held = [n for n in reads if n in cell["limits"]]
        assert held and all(reads[n] > 3 * cell["limits"][n] for n in held), \
            reads


@pytest.fixture()
def one_chip(monkeypatch):
    """The cells run on one chip; the test session has eight CPU devices
    (``conftest.py``). Give the server's mesh one of them, as the chip's
    machine would (rf draws its bootstrap per shard)."""
    import jax

    from learningorchestra_tpu.parallel import mesh

    real = mesh.local_mesh
    monkeypatch.setattr(
        mesh, "local_mesh",
        lambda cfg=None, devices=None: real(cfg, devices=jax.devices()[:1]))


def _run_tiny(capsys, seed):
    from perfbench import cells, run

    device = ({"platform": "cpu", "kind": "cpu", "count": 1},
              cells.load_peaks()["TPU v5 lite"])
    rc = run.main(["--workload", "tiny.sweep", "--seed", str(seed),
                   "--seconds", "0.5", "--trace", "0"], root=TINY,
                  device=device)
    out, err = capsys.readouterr()
    assert rc == 0
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "observed", "checks"]
    # the numbers compared are also the last lines of standard error
    tail = err.strip().splitlines()[-len(last["checks"]):]
    assert all(line.startswith("check ") for line in tail)
    return last


def _failing(last):
    return sorted(k for k, c in last["checks"].items()
                  if not c["value"] <= c["limit"])


def test_sound_tiny_run_is_correct(capsys, one_chip):
    last = _run_tiny(capsys, 3000000019)
    assert last["correct"] is True, last["checks"]
    assert last["attempted"] == 5 and last["failed"] == 0
    assert set(last["metrics"]) == {"sweep_s", "setup_s"}
    assert all(m["value"] > 0 for m in last["metrics"].values())


def test_half_of_the_rows_left_out_is_not_correct(capsys, monkeypatch,
                                                  one_chip):
    from learningorchestra_tpu.models import builder

    real = builder.get_trainer

    def half(kind):
        trainer = real(kind)

        def fit(runtime, X, y, num_classes, **kw):
            kw.pop("edges", None)          # prepared from all the rows
            return trainer(runtime, X[:len(X) // 2], y[:len(y) // 2],
                           num_classes, **kw)

        return fit

    monkeypatch.setattr(builder, "get_trainer", half)
    last = _run_tiny(capsys, 3000000019)
    assert last["correct"] is False
    assert {"off.dt", "off.gb", "gap.lr"} <= set(_failing(last))


def test_an_altered_answer_is_not_correct(capsys, monkeypatch, one_chip):
    from learningorchestra_tpu.models import builder

    real = builder.ModelBuilder._save_predictions

    def altered(self, name, test_ds, preds, probs, report):
        probs = np.array(probs)
        probs[::10] = probs[::10, ::-1]            # every tenth row swapped
        return real(self, name, test_ds, np.argmax(probs, axis=1), probs,
                    report)

    monkeypatch.setattr(builder.ModelBuilder, "_save_predictions", altered)
    last = _run_tiny(capsys, 3000000019)
    assert last["correct"] is False
    assert {"off.gb", "off.rf", "gap.nb"} <= set(_failing(last))
