"""``finish_tail_s.sweep`` (PR 31): the ``build\\.tail`` pattern on spans
made by hand (it must not take ``build``, and ``rest_overhead_s.sweep``
must not take ``build.tail`` for the ``build`` it subtracts), and a
whole traced run of the tiny copy on the CPU, given the metric's entry
and file, reporting it. Kept outside ``tests/perfbench/``: that
directory is the accepted benchmark's, and a PR that claims a gain adds
data to it only.
"""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench import cells  # noqa: E402

TINY = os.path.join(REPO, "tests", "perfbench", "tiny")
METRIC = "finish_tail_s.sweep"


def _spec(metric):
    with open(os.path.join(REPO, "perfbench", "layer_metrics",
                           metric + ".json"), encoding="utf-8") as fh:
        return json.load(fh)


def sp(name, start, seconds):
    return {"name": name, "start": start, "duration_ms": seconds * 1e3}


def read(metric, requests):
    spec = _spec(metric)
    ctx = {"spans": list(requests), "ops": [], "n_sweeps": len(requests),
           "window_ns": 0.0}
    return cells.reader_module(spec["reader"]).read(spec, ctx)


ONE = [sp("http.handle", 0.0, 6.00), sp("build", 0.01, 5.98),
       sp("build.tail", 5.49, 0.50), sp("fit.dt.finish", 5.49, 0.49),
       sp("rebuild.tail", 0.0, 3.0), sp("build.tail.more", 0.0, 3.0)]
TWO = [sp("http.handle", 7.0, 5.90), sp("build", 7.01, 5.88),
       sp("build.tail", 12.19, 0.70)]


@pytest.mark.parametrize("requests,tail,rest", [
    ([ONE], 0.50, 0.02),
    ([ONE, TWO], 0.60, 0.02),                    # per traced request
    # the parent: no such span, so the metric is left out of its line
    ([[s for s in ONE if s["name"] != "build.tail"]], None, 0.02),
    ([], None, None),
])
def test_tail_pattern_reads_the_tail_and_leaves_build_alone(requests, tail,
                                                            rest):
    got = read(METRIC, requests)
    assert got is None if tail is None else got == pytest.approx(tail)
    got = read("rest_overhead_s.sweep", requests)
    assert got is None if rest is None else got == pytest.approx(rest)


def test_benchmark_entry_of_the_tail_metric():
    (entry,) = [m for m in cells.load_benchmark(REPO)["per_layer"]
                if m["name"] == METRIC]
    assert entry == {"name": METRIC, "unit": "s", "better": "lower",
                     "source": "program_span", "layer": "builder",
                     "moves": "sweep_s", "workloads": ["higgs-11m.sweep"]}


def test_traced_tiny_run_reports_the_tail(capsys, monkeypatch, tmp_path):
    """The tiny copy with the metric's entry and file beside it (the
    copy under ``tests/perfbench/tiny`` is the accepted benchmark's and
    stays as it is), traced, on the CPU: the program's ``build.tail``
    span reaches the result line through ``span_sum``."""
    import jax

    from learningorchestra_tpu.parallel import mesh
    from perfbench import run

    root = str(tmp_path / "tiny")
    shutil.copytree(TINY, root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as fh:
        bench = json.load(fh)
    (entry,) = [m for m in cells.load_benchmark(REPO)["per_layer"]
                if m["name"] == METRIC]
    bench["per_layer"].append(dict(entry, workloads=["tiny.sweep"]))
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh)
    shutil.copy(os.path.join(REPO, "perfbench", "layer_metrics",
                             METRIC + ".json"),
                os.path.join(root, "bench", "layer_metrics"))

    real = mesh.local_mesh                   # one chip, as the cell has
    monkeypatch.setattr(
        mesh, "local_mesh",
        lambda cfg=None, devices=None: real(cfg, devices=jax.devices()[:1]))
    device = ({"platform": "cpu", "kind": "cpu", "count": 1},
              cells.load_peaks()["TPU v5 lite"])
    rc = run.main(["--workload", "tiny.sweep", "--seed", "3100000031",
                   "--seconds", "0.5", "--trace", "1"], root=root,
                  device=device)
    out, _err = capsys.readouterr()
    assert rc == 0
    last = json.loads(out.strip().splitlines()[-1])
    assert last["correct"] is True, last["checks"]
    tail = last["metrics"][METRIC]
    assert tail["unit"] == "s"
    # Part of the families' finishing, never more than all of it.
    assert 0 < tail["value"] <= last["metrics"]["host_finish_s.sweep"]["value"]
