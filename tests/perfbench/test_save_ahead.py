"""The staged save's metric in Keye's cell (ISSUE 39): it resolves
through the cell by name and reads the mean of the ``save_ahead_s``
attribute of ``fit.tx.finish.model``, and nothing from a program whose
span lacks it."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import cells  # noqa: E402

CELL = "keye-vl-2.0-30b-a3b.txfit"


def sp(name, **attrs):
    doc = {"name": name, "duration_ms": 1000.0}
    if attrs:
        doc["attrs"] = attrs
    return doc


def _metric():
    cell = cells.load_cell(CELL, REPO)
    (m,) = [m for m in cell["per_layer"] if m["name"] == "save_ahead_s.txfit"]
    return m


def test_save_ahead_resolves_in_keyes_cell():
    m = _metric()
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
            m["workloads"]) == ("s", "higher", "program_counter", "builder",
                                "sweep_s", [CELL])
    assert m["spec"]["reader"] == "span_attr"
    assert m["spec"]["attr"] == "save_ahead_s"


def test_save_ahead_reads_the_attributes_mean():
    spec = _metric()["spec"]
    read = cells.reader_module(spec["reader"]).read
    spans = [[sp("fit.tx.finish.model", save_staged=True, save_ahead_s=2.0),
              sp("fit.tx.finish.model.stage"),
              sp("fit.tx.finish.model.write"),
              sp("fit.nb.finish.model", save_staged=False,
                 save_ahead_s=0.0)],
             [sp("fit.tx.finish.model", save_staged=True, save_ahead_s=1.5)]]
    assert read(spec, {"spans": spans}) == pytest.approx(1.75)


def test_save_ahead_reads_nothing_from_a_program_without_it():
    spec = _metric()["spec"]
    read = cells.reader_module(spec["reader"]).read
    parent = [[sp("fit.tx.finish.model"), sp("fit.tx.finish.model.write")]]
    assert read(spec, {"spans": parent}) is None
