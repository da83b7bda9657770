"""The benchmark harness, as far as the CPU can hold it: every name in
``BENCHMARK.json`` resolves to its file and back, the trace reduction and
the cost model compute what a hand computes, the last line has the
contract's keys, the run refuses anything but a known TPU, and the sweep
traffic's loop counts what it should. Seconds in all; no chip, no server."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import cells, compare, costs, trace_reduce  # noqa: E402
from perfbench.traffic import sweep  # noqa: E402

def _json(*parts):
    with open(os.path.join(*parts), encoding="utf-8") as fh:
        return json.load(fh)


BENCH = cells.load_benchmark(REPO)
FIXTURE = os.path.join(REPO, "perfbench", "fixtures", "dt_fit.xplane.pb")


# -- every entry resolves by name ----------------------------------------------

@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_its_files_and_back(name):
    cell = cells.load_cell(name, REPO)
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert cell["config_name"] == entry["config"]
    assert cell["traffic"]["kind"] == entry["traffic"]
    assert cell["chips"] == entry["chips"] in (1, 4)
    assert callable(cells.traffic_module(entry["traffic"]).run)
    # every number the comparison can produce has a limit in the cell's file
    for family in cell["traffic"]["classifiers"]:
        assert {f"gap.{family}", f"off.{family}"} & set(cell["limits"])
        assert family in cell["config"]["families"]
        assert cell["tolerance"][family] > 0
    assert set(cell["config"]["precision"]) == {"stated", "reference",
                                                "control"}
    assert {"unfinished", "rows_wrong"} <= set(cell["limits"])
    # the cell reports setup_s, one more end-to-end metric and a layer metric
    names = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2 and cell["per_layer"]


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file_is_under_paths_and_used(conf):
    assert any(conf["file"].startswith(p + "/") for p in BENCH["paths"])
    doc = _json(REPO, conf["file"])
    assert doc["source"] == conf["source"] and len(conf["source"]) <= 200
    assert any(w["config"] == conf["name"] for w in BENCH["workloads"])
    shapes = costs.shapes(doc)
    assert shapes["n"] > shapes["n_test"] > 0 and shapes["d"] > 0
    assert conf["reduced"] == []


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_layer_metric_has_a_file_a_reader_and_a_target(metric):
    base = cells.bench_dir(REPO, BENCH)
    spec = _json(base, "layer_metrics", metric["name"] + ".json")
    assert callable(cells.reader_module(spec["reader"]).read)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert metric["moves"] in e2e
    cell_names = {w["name"] for w in BENCH["workloads"]}
    for w in metric["workloads"]:
        assert w in cell_names
        assert w in e2e[metric["moves"]].get("workloads", cell_names)
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}


def _named():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[key]:
            yield key, e


@pytest.mark.parametrize("key,entry", list(_named()),
                         ids=lambda v: v["name"] if isinstance(v, dict) else v)
def test_names_and_units_use_only_the_allowed_characters(key, entry):
    assert cells.NAME.match(entry["name"])
    if key == "workloads":
        assert cells.NAME.match(entry["config"])
        assert cells.NAME.match(entry["traffic"])
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    if key in ("end_to_end", "per_layer"):
        assert cells.UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    if key == "end_to_end":
        assert 0.01 <= entry["bound"] <= 0.1
        assert entry["source"] in ("host_clock", "device_trace")


def test_benchmark_json_top_level_and_no_cell_name_in_the_harness():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1
               for m in BENCH["end_to_end"])
    # run.py and cells.py find everything by name: they hold no cell's,
    # configuration's or metric's name
    for mod in ("run.py", "cells.py"):
        with open(os.path.join(REPO, "perfbench", mod)) as fh:
            src = fh.read()
        for key in ("configs", "workloads", "per_layer"):
            for e in BENCH[key]:
                assert e["name"] not in src, (mod, e["name"])


# -- the trace reduction ----------------------------------------------------------

EV = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("a.1", 30.0, 5.0), ("c", 31.0, 2.0)]


@pytest.mark.parametrize("events,lo,hi,busy,gap_list", [
    (EV, 0.0, 40.0, 20.0, [(15.0, 15.0), (35.0, 5.0)]),      # overlap + nested
    (EV, 8.0, 32.0, 9.0, [(15.0, 15.0)]),                    # clipped window
    ([], 0.0, 40.0, 0.0, [(0.0, 40.0)]),                     # empty plane
    ([("x", 10.0, 5.0)], 0.0, 20.0, 5.0, [(0.0, 10.0), (15.0, 5.0)]),
])
def test_trace_union_and_gaps_by_hand(events, lo, hi, busy, gap_list):
    inside = trace_reduce.clip(events, lo, hi)
    assert trace_reduce.busy_ns(inside) == busy
    assert sorted(trace_reduce.gaps(events, lo, hi)) == sorted(gap_list)


def test_trace_patterns_top_ops_and_gap_labels():
    assert [e[0] for e in trace_reduce.matching(EV, ["^a"])] == ["a", "a.1"]
    assert trace_reduce.top_ops(EV, k=2) == [["a", 15e-9], ["b", 10e-9]]
    notes = [("perfbench.sweep.0", 0.0, 20.0)]
    got = trace_reduce.label_gaps(trace_reduce.gaps(EV, 0.0, 40.0), notes)
    assert got == [["host:unattributed", 15e-9], ["host:unattributed", 5e-9]]
    got = trace_reduce.label_gaps([(15.0, 4.0)], notes)
    assert got == [["perfbench.sweep.0", 4e-9]]


def test_recorded_trace_reduces_to_kernel_time_and_idle_share():
    """A dt fit of 65,536 rows traced on the TPU v5e (PR 26): the device
    plane, its ops line and the Pallas kernels' names are found as the
    harness expects, and the readers give sane numbers from it."""
    profile = trace_reduce.load(FIXTURE)
    ops = trace_reduce.device_ops(profile)
    assert list(ops) == ["/device:TPU:0"] and len(ops["/device:TPU:0"]) > 50
    notes = trace_reduce.host_annotations(profile, "perfbench.sweep.")
    assert [n[0] for n in notes] == ["perfbench.sweep.0"]
    lo, hi = notes[0][1], notes[0][1] + notes[0][2]
    events = trace_reduce.clip(ops["/device:TPU:0"], lo, hi)
    spec = _json(REPO, "perfbench", "layer_metrics",
                 "tree_kernel_s.sweep.json")
    ctx = {"ops": events, "n_sweeps": 1, "window_ns": hi - lo}
    kernel_s = cells.reader_module("trace_ops_sum").read(spec, ctx)
    busy_s = trace_reduce.busy_ns(events) / 1e9
    assert 0.0 < kernel_s <= busy_s <= (hi - lo) / 1e9
    idle = cells.reader_module("device_idle").read({}, ctx)
    assert 0.0 < idle < 100.0
    # one dt fit and its predict: 5 levels x (histogram + route), the leaf
    # statistics and the descent, each a Mosaic custom call
    assert len(trace_reduce.matching(events, spec["ops"])) == 12
    top = trace_reduce.top_ops(events, k=3)
    assert top[0][0] == "%closed_call custom-call f32[1,32,896]"
    assert all(" while " not in name for name, _ in top)


def test_readers_return_nothing_when_there_is_nothing_to_read():
    ctx = {"ops": [], "spans": [], "n_sweeps": 1, "window_ns": 0.0}
    for reader, spec in (("span_sum", {"spans": ["x"]}),
                         ("trace_ops_sum", {"ops": ["tree_"]}),
                         ("tree_roofline", {"ops": ["tree_"]}),
                         ("step_mfu", {}), ("device_idle", {})):
        assert cells.reader_module(reader).read(spec, ctx) is None


def test_span_reader_sums_matching_spans_per_request():
    spans = [[{"name": "fit.dt.finish", "duration_ms": 500.0},
              {"name": "fit.gb.finish", "duration_ms": 250.0},
              {"name": "fit.gb.host_prep", "duration_ms": 9.0}],
             [{"name": "fit.dt.finish", "duration_ms": 750.0}]]
    got = cells.reader_module("span_sum").read(
        {"spans": [r"fit\.[a-z]+\.finish"]}, {"spans": spans})
    assert got == pytest.approx(0.75)


# -- the cost model, by hand ----------------------------------------------------

FAMILIES = _json(REPO, BENCH["configs"][0]["file"])["families"]
KINDS = ["lr", "dt", "rf", "gb", "nb"]


@pytest.mark.parametrize("n,d,level_bytes,sweep_gb", [
    # one level: n * (d + 4*2 + 5); a sweep: 41 trees * (5 levels + leaf 9n)
    (11_000_000, 28, 451_000_000, 96.514),
    (400_384, 2000, 805_972_992, 165.37),
])
def test_tree_bytes_at_both_shapes(n, d, level_bytes, sweep_gb):
    assert costs.tree_level_bytes(n, d, 2) == level_bytes
    sh = {"n": n, "n_test": 100_000, "d": d, "classes": 2,
          "families": FAMILIES}
    work = costs.sweep_tree_work(sh, KINDS)
    assert work["bytes"] == 41 * (5 * level_bytes + 9 * n)
    assert work["bytes"] / 1e9 == pytest.approx(sweep_gb, rel=1e-4)
    peaks = cells.load_peaks()["TPU v5 lite"]
    seconds, bound = costs.least_seconds(work, peaks)
    assert bound == "bytes"
    assert seconds == pytest.approx(work["bytes"] / 819e9)


def test_operation_counts_by_hand():
    n, d = 1000.0, 10.0
    # one level at depth 5, 32 bins, 2 stats: 2ndS + nd*bins + 6*16*d*bins*S + 5n
    assert costs.tree_level_ops(n, d, 32, 2, 5) == (
        40_000 + 320_000 + 61_440 + 5_000)
    sh = {"n": 1000, "n_test": 100, "d": 10, "classes": 2,
          "families": FAMILIES}
    assert costs.fit_ops("nb", sh) == 4 * 1000 * 2 * 10 + 3 * 1000 * 10 + 2000
    assert costs.predict_ops("lr", sh) == 2 * 100 * 10 * 2 + 3 * 100 * 10
    newton = 20 * (2 * n * 22 ** 2 + 2 * n * 2 * 11 ** 2 + 5 * n * 2 * 11) \
        + 4 * n * d
    assert costs.fit_ops("lr", sh) == newton
    wide = dict(sh, d=2000)
    assert costs.fit_ops("lr", wide) == 300 * 6 * n * 2000 * 2 + 4 * n * 2000
    assert costs.sweep_ops(sh, KINDS) == sum(
        costs.fit_ops(k, sh) + costs.predict_ops(k, sh) for k in KINDS)


# -- the last line and the refusals ---------------------------------------------

def test_last_line_has_exactly_the_contracts_keys():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    checks = {"gap.dt": {"value": 0.0, "limit": 1e-3}}
    doc = cells.last_line(True, 25, 0, {"setup_s": {"value": 1.0,
                                                    "unit": "s"}},
                          device, checks)
    assert list(doc) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    traced = cells.last_line(False, 5, 1, {}, device, checks,
                             breakdown={"device_ops": [], "idle_gaps": []})
    assert list(traced) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "checks"]
    assert json.loads(json.dumps(traced))["correct"] is False
    seen = cells.last_line(True, 5, 0, {}, device, checks,
                           observed={"gap.lr": 0.006})
    assert list(seen)[-2:] == ["observed", "checks"]


def test_unknown_device_kind_is_an_error_not_a_default():
    assert cells.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(cells.BenchError, match="not in peaks.json"):
        cells.device_peaks("TPU v9 imaginary")
    with pytest.raises(cells.BenchError, match="no workload"):
        cells.load_cell("no-such.cell", REPO)


def test_a_checkout_without_the_program_is_refused(monkeypatch):
    import importlib.util

    from perfbench import server

    server.require_program()                 # here the program is present
    monkeypatch.setattr(importlib.util, "find_spec", lambda name: None)
    with pytest.raises(cells.BenchError, match="not in this checkout"):
        server.require_program()


def test_run_refuses_to_run_without_a_tpu(tmp_path):
    """Under JAX_PLATFORMS=cpu: a non-zero exit code and no result."""
    first = BENCH["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "perfbench", "run.py"),
         "--workload", first, "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=REPO)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
    assert os.listdir(tmp_path) == []


# -- the sweep traffic's loop, against stubs ------------------------------------

class Clock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


@pytest.mark.parametrize("cost,seconds,max_sends,want", [
    (8.0, 40.0, None, 5),      # 5 x 8 s reach 40 s: no sixth is sent
    (8.3, 40.0, None, 5),      # the one in flight at 40 s is finished
    (7.9, 40.0, None, 6),
    (8.0, 40.0, 1, 1),         # a traced run's sweeps
    (50.0, 40.0, None, 1),
])
def test_closed_loop_sends_back_to_back_until_the_seconds_pass(
        cost, seconds, max_sends, want):
    clock, sent = Clock(), []

    def send(i):
        sent.append(i)
        clock.now += cost

    times = sweep.closed_loop(send, seconds, clock, max_sends)
    assert sent == list(range(want)) and len(times) == want
    assert times[0][0] == 100.0
    assert times[-1][1] - times[0][0] == pytest.approx(want * cost)
    assert all(b[0] == a[1] for a, b in zip(times, times[1:]))


def test_outcomes_count_unfinished_failed_and_missing_datasets():
    docs = {"s0_lr": {"finished": True}, "s0_dt": {"finished": False},
            "s0_rf": {"finished": True, "error": "boom"}, "s0_gb": None,
            "s0_nb": {"finished": True, "error": None}}
    assert sweep.outcomes(docs) == (5, 3)


def test_sample_pages_come_from_the_seed_and_stay_inside_the_table():
    a = sweep.sample_pages(3000000019, 100_000, 2, ["dt", "gb"], 40)
    assert a == sweep.sample_pages(3000000019, 100_000, 2, ["dt", "gb"], 40)
    assert a != sweep.sample_pages(7, 100_000, 2, ["dt", "gb"], 40)
    assert set(a) == {(0, "dt"), (0, "gb"), (1, "dt"), (1, "gb")}
    for firsts in a.values():
        assert len(set(firsts)) == 40
        assert all(f % 20 == 0 and 0 <= f <= 100_000 - 20 for f in firsts)


# -- the comparison ------------------------------------------------------------

def _rows(p1, XT, y, alter=None):
    import numpy as np

    rows = []
    for r in range(XT.shape[1]):
        doc = {f"f{i}": float(XT[i, r]) for i in range(XT.shape[0])}
        doc.update(label=int(y[r]), probability=[1 - p1[r], p1[r]],
                   prediction=int(np.argmax([1 - p1[r], p1[r]])))
        if alter:
            alter(r, doc)
        rows.append((r, doc))
    return rows


LIMITS = {"unfinished": 0, "rows_wrong": 0, "gap.dt": 1e-3, "off.dt": 0.1}


@pytest.mark.parametrize("case,want_ok,failing", [
    ("sound", True, None),
    ("probability_off", False, "gap.dt"),
    ("some_rows_far_off", False, "off.dt"),
    ("prediction_not_argmax", False, "rows_wrong"),
    ("feature_changed", False, "rows_wrong"),
    ("nan_probability", False, "rows_wrong"),
    ("dataset_unfinished", False, "unfinished"),
    ("nothing_read", False, "gap.dt+off.dt"),
    ("far_off_rows_below_a_tie", True, None),
    ("every_row_below_a_tie", False, "gap.dt+off.dt"),
])
def test_comparison_fails_what_it_should(case, want_ok, failing):
    import numpy as np

    rng = np.random.default_rng(0)
    XT = rng.normal(size=(3, 50)).astype(np.float32)
    y = rng.integers(0, 2, 50)
    ref = {"dt": rng.uniform(0.1, 0.9, 50).astype(np.float32)}
    served = ref["dt"].astype(float).copy()
    alter, unfinished = None, 0
    if case == "probability_off":
        served[::2] += 0.003
    elif case == "some_rows_far_off":
        served[::5] += 0.0045          # a fifth of the rows beyond 0.004
    elif case == "prediction_not_argmax":
        def alter(r, doc):
            if r == 7:
                doc["prediction"] = 1 - doc["prediction"]
    elif case == "feature_changed":
        def alter(r, doc):
            if r == 3:
                doc["f1"] += 1.0
    elif case == "nan_probability":
        def alter(r, doc):
            if r == 9:
                doc["probability"] = [float("nan"), float("nan")]
    elif case == "dataset_unfinished":
        unfinished = 1
    elif case == "far_off_rows_below_a_tie":
        served[::5] += 0.3             # the rows the reference is unsure of
        ref["dt.unsure"] = np.arange(50) % 5 == 0
    elif case == "every_row_below_a_tie":
        ref["dt.unsure"] = np.ones(50, bool)
    samples = {"dt": [] if case == "nothing_read"
               else _rows(served, XT, y, alter)}
    ok, checks, observed = compare.compare(samples, unfinished, ref, XT, y,
                                           LIMITS, {"dt": 0.004})
    assert set(observed) - {"unsure.dt"} == {"gap.dt", "off.dt"}
    if case == "far_off_rows_below_a_tie":
        assert observed["unsure.dt"] == pytest.approx(0.2)
    assert ok is want_ok
    bad = [k for k, c in checks.items() if not c["value"] <= c["limit"]]
    assert bad == (failing.split("+") if failing else [])


# -- the tables and the reference's ties ---------------------------------------

def test_tables_come_from_the_seed_and_every_column_has_signal():
    import numpy as np

    from perfbench import datagen

    XT, y = datagen.make_table(40_000, 28, 3000000019, floor=0.02)
    again, _ = datagen.make_table(40_000, 28, 3000000019, floor=0.02,
                                  threads=2)
    assert np.array_equal(XT, again)              # whatever the threads
    other, _ = datagen.make_table(40_000, 28, 7, floor=0.02)
    assert not np.array_equal(XT, other)
    plain, _ = datagen.make_table(40_000, 28, 3000000019)
    shift = (XT - plain)[:, y == 1].mean(axis=1)
    want = [0.0 if f in datagen.SHIFT else 0.02 * (1 + f / 27)
            for f in range(28)]
    assert np.allclose(shift, want, atol=1e-4)    # 0.02 .. 0.04, each its own


def test_reference_marks_the_rows_below_a_tied_split():
    """Two columns that are copies tie exactly for every split: below
    such a node float32 cannot say which tree is right, and the
    comparison leaves those rows out."""
    import numpy as np

    from perfbench import datagen, reference

    fam = {"dt": {"max_depth": 2, "n_bins": 8, "edge_sample": 10_000}}
    XT, y = datagen.make_table(4096, 28, 5, floor=0.02)
    XT_test, _ = datagen.make_table(256, 28, 6, floor=0.02)
    out = reference.fit_predict(XT, y, XT_test, fam, ["dt"])
    assert out["dt.unsure"].dtype == bool and not out["dt.unsure"].any()
    XT[:], XT_test[:] = XT[13], XT_test[13]
    out = reference.fit_predict(XT, y, XT_test, fam, ["dt"])
    assert out["dt.unsure"].all()
