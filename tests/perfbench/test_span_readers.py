"""The readers PR 27 adds (``span_wall``, ``span_minus``) on spans made
by hand, the two kernel patterns on event texts as the chip's compiler
writes them, and every metric file's reader resolved by name."""

import glob
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import cells  # noqa: E402

METRIC_FILES = sorted(glob.glob(os.path.join(
    REPO, "perfbench", "layer_metrics", "*.json")))


def _spec(metric):
    with open(os.path.join(REPO, "perfbench", "layer_metrics",
                           metric + ".json"), encoding="utf-8") as fh:
        return json.load(fh)


def sp(name, start, seconds):
    return {"name": name, "start": start, "duration_ms": seconds * 1e3}


def read(reader, params, spans=(), ops=(), n_sweeps=1):
    ctx = {"spans": list(spans), "ops": list(ops), "n_sweeps": n_sweeps,
           "window_ns": 0.0}
    return cells.reader_module(reader).read(params, ctx)


FINISH = {"spans": [r"fit\.[a-z]+\.finish"]}


@pytest.mark.parametrize("requests,want", [
    # two overlapping spans count once: [10, 13] and [12, 16] cover 6 s
    ([[sp("fit.gb.finish", 10.0, 3.0), sp("fit.rf.finish", 12.0, 4.0)]],
     6.0),
    # apart, they add; a sub-phase and another phase are not matched
    ([[sp("fit.gb.finish", 10.0, 1.0), sp("fit.nb.finish", 20.0, 0.5),
       sp("fit.gb.finish.rows", 10.0, 0.9), sp("fit.gb.host_prep", 0.0, 9.0)]],
     1.5),
    # one inside the other is the outer one
    ([[sp("fit.gb.finish", 10.0, 5.0), sp("fit.dt.finish", 11.0, 1.0)]], 5.0),
    # per traced request: (6 + 2) / 2
    ([[sp("fit.gb.finish", 10.0, 3.0), sp("fit.rf.finish", 12.0, 4.0)],
      [sp("fit.gb.finish", 30.0, 2.0)]], 4.0),
])
def test_span_wall_is_the_union_of_the_intervals(requests, want):
    assert read("span_wall", FINISH, requests) == pytest.approx(want)
    # and never more than the sum span_sum reads
    assert want <= read("span_sum", FINISH, requests) + 1e-9


@pytest.mark.parametrize("requests", [
    [], [[]], [[sp("fit.gb.host_prep", 0.0, 1.0), sp("build", 0.0, 2.0)]]])
def test_span_wall_reads_nothing_where_nothing_matches(requests):
    assert read("span_wall", FINISH, requests) is None


REST = {"spans": [r"http\.handle"], "minus": ["build"]}


def test_span_minus_takes_the_inner_span_out():
    one = [sp("http.handle", 0.0, 8.70), sp("build", 0.01, 8.68),
           sp("design.build", 0.01, 0.001), sp("rebuild", 0.0, 5.0)]
    two = [sp("http.handle", 9.0, 8.50), sp("build", 9.01, 8.46)]
    assert read("span_minus", REST, [one]) == pytest.approx(0.02)
    assert read("span_minus", REST, [one, two]) == pytest.approx(0.03)


@pytest.mark.parametrize("spans", [
    [sp("http.handle", 0.0, 8.7)],               # the parent: no build span
    [sp("build", 0.0, 8.6)],
    []])
def test_span_minus_reads_nothing_with_either_side_missing(spans):
    assert read("span_minus", REST, [spans]) is None


def call(name, shape, operands="u8[28,65536]{1,0:T(8,128)(4,1)} %x.1"):
    return (f"%{name} = {shape} custom-call({operands}), "
            'custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={u8[28,65536]{1,0}}, "
            "frontend_attributes={kernel_metadata={}}")


OPS = [
    (call("tree_hist.3", "f32[1,32,896]{2,1,0:T(8,128)S(1)}"), 0.0, 4e9),
    (call("vmap_tree_hist_.1", "f32[5,1,32,896]{3,2,1,0:T(8,128)}"),
     4e9, 2e9),
    (call("tree_route.7", "s32[1,11000832]{1,0:T(1,128)}"), 6e9, 1e9),
    (call("vmap_tree_route_.2", "s32[5,1,11000832]{2,1,0:T(1,128)}"),
     7e9, 0.5e9),
    # the descent reads the routing's result: an operand is no name
    (call("tree_descend.9", "s32[1,11000320]{1,0:T(1,128)}",
          "s32[1,11000832]{1,0} %tree_route.7, s32[63,3]{1,0} %tree_hist.3"),
     8e9, 0.25e9),
    # a fusion that inherited the scope's name is not the kernel
    ("%tree_hist.4 = f32[32,896]{1,0:T(8,128)} fusion(f32[1,32,896]{2,1,0} "
     "%tree_hist.3), kind=kLoop, calls=%fused_computation.1", 9e9, 8e9),
    # what the parent's trace calls every kernel
    (call("closed_call.65", "f32[1,32,896]{2,1,0:T(8,128)S(1)}"), 17e9, 16e9),
]


@pytest.mark.parametrize("metric,want", [
    ("hist_kernel_s.sweep", 6.0), ("route_kernel_s.sweep", 1.5),
    ("tree_kernel_s.sweep", 6.0 + 1.5 + 0.25 + 16.0)])
def test_kernel_patterns_read_the_instructions_own_name(metric, want):
    spec = _spec(metric)
    assert read(spec["reader"], spec, ops=OPS) == pytest.approx(want)
    # two traced sweeps: per sweep
    assert read(spec["reader"], spec, ops=OPS, n_sweeps=2) == \
        pytest.approx(want / 2)


@pytest.mark.parametrize("metric", ["hist_kernel_s.sweep",
                                    "route_kernel_s.sweep"])
def test_kernel_patterns_read_nothing_on_a_trace_without_names(metric):
    """The parent's program names no kernel: the metric is left out of
    its line, it does not read 0."""
    spec = _spec(metric)
    assert read(spec["reader"], spec, ops=[OPS[-1]]) is None


@pytest.mark.parametrize("path", METRIC_FILES, ids=os.path.basename)
def test_every_metric_file_names_a_reader_that_exists(path):
    with open(path, encoding="utf-8") as fh:
        spec = json.load(fh)
    assert callable(cells.reader_module(spec["reader"]).read)
    assert isinstance(spec["what"], str) and spec["what"]
    name = os.path.basename(path)[:-len(".json")]
    assert any(m["name"] == name
               for m in cells.load_benchmark(REPO)["per_layer"]), name
