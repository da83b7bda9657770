"""The save's three metrics in Keye's cell (ISSUE 38): each resolves
through the cell by name and reads its own part of
``fit.tx.finish.model`` (``.fetch`` / ``.write`` / ``.sync``) from the
request's spans, and nothing from a program without them."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import cells  # noqa: E402

CELL = "keye-vl-2.0-30b-a3b.txfit"
PARTS = ("fetch", "write", "sync")


def sp(name, ms):
    return {"name": name, "duration_ms": ms}


@pytest.mark.parametrize("part", PARTS)
def test_save_metric_resolves_in_keyes_cell_and_reads_its_part(part):
    cell = cells.load_cell(CELL, REPO)
    (m,) = [m for m in cell["per_layer"]
            if m["name"] == f"save_{part}_s.txfit"]
    assert (m["unit"], m["source"], m["layer"], m["moves"],
            m["workloads"]) == ("s", "program_span", "builder", "sweep_s",
                                [CELL])
    spec = m["spec"]
    assert spec["reader"] == "span_sum"
    others = [p for p in PARTS if p != part]
    spans = [[sp("fit.tx.finish.model", 4000.0),
              sp(f"fit.tx.finish.model.{part}", 1000.0),
              sp(f"fit.tx.finish.model.{part}", 500.0),
              sp(f"fit.tx.finish.model.{others[0]}", 2000.0),
              sp(f"fit.tx.finish.model.{part}.more", 9000.0)],
             [sp(f"fit.tx.finish.model.{part}", 250.0),
              sp(f"fit.gb.finish.model.{part}", 9000.0)]]
    read = cells.reader_module(spec["reader"]).read
    assert read(spec, {"spans": spans}) == pytest.approx(0.875)
    # The parent's program has one span around the save: nothing read.
    parent = [[sp("fit.tx.finish.model", 4000.0)]]
    assert read(spec, {"spans": parent}) is None
