"""tools/benchdiff: schema normalization across all shipped BENCH
shapes, direction-aware tolerance gating, the injected-regression
acceptance (a >=20% p99 regression must exit non-zero), and the CLI."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.benchdiff import diff, direction, main, normalize  # noqa: E402


def test_normalize_bare_metric_doc():
    flat = normalize({"value": 3.5, "unit": "x", "smoke": False,
                      "closed_loop": {"p99_ms": 10.0, "errors": 0},
                      "open_loop": [{"rate_rps": 50, "p99_ms": 12.5}]})
    assert flat["value"] == 3.5
    assert flat["smoke"] == 0.0
    assert flat["closed_loop.p99_ms"] == 10.0
    assert flat["open_loop.0.p99_ms"] == 12.5
    assert "unit" not in flat                      # strings drop out


def test_normalize_driver_wrapper_unwraps_parsed():
    flat = normalize({"n": 5, "cmd": "python bench.py", "rc": 0,
                      "tail": "...",
                      "parsed": {"value": 18.1,
                                 "families": {"lr": {"fit_s": 0.7}}}})
    assert flat["rc"] == 0.0                       # a failing run gates
    assert flat["value"] == 18.1
    assert flat["families.lr.fit_s"] == 0.7
    assert "n" not in flat and "cmd" not in flat


def test_normalize_real_shipped_files(tmp_path):
    # The driver-wrapper shape is written here: the repo ships no bench
    # record of that shape any more.
    wrapper = tmp_path / "BENCH_wrapper.json"
    wrapper.write_text(json.dumps({
        "n": 1, "cmd": "python bench.py", "rc": 0,
        "tail": "WARNING: platform chatter\n{\"value\": 1.5}\n",
        "parsed": {"value": 1.5, "unit": "seconds",
                   "families": {"gb": {"fit_s": 0.5, "accuracy": 0.76}},
                   "sweep_times_s": [1.4, 1.5, 1.6]}}), encoding="utf-8")
    for path in (os.path.join(REPO, "BENCH_serving.json"), str(wrapper),
                 os.path.join(REPO, "MULTICHIP_r01.json")):
        with open(path, encoding="utf-8") as f:
            flat = normalize(json.load(f))
        assert flat, path
        assert all(isinstance(v, float) for v in flat.values())


def test_direction_inference():
    assert direction("closed_loop.p99_ms") == "up"
    assert direction("closed_loop.wall_s") == "up"
    assert direction("serving_metrics.errors") == "up"
    assert direction("closed_loop.rps") == "down"
    assert direction("value") == "down"            # speedup figure
    assert direction("serving_metrics.aot.buckets.0") is None


def test_diff_gates_on_injected_p99_regression():
    """Acceptance: a 25% p99 regression (>= the 20% line the CI gate
    pins) fails; within-tolerance drift and improvements pass."""
    base = {"closed_loop.p99_ms": 100.0, "closed_loop.rps": 800.0}
    bad = {"closed_loop.p99_ms": 125.0, "closed_loop.rps": 800.0}
    report = diff(base, bad, default_tolerance=0.2)
    assert not report["ok"]
    (reg,) = report["regressions"]
    assert reg["metric"] == "closed_loop.p99_ms"
    assert diff(base, {"closed_loop.p99_ms": 115.0,
                       "closed_loop.rps": 900.0},
                default_tolerance=0.2)["ok"]
    # Throughput collapse gates in the other direction.
    assert not diff(base, {"closed_loop.p99_ms": 100.0,
                           "closed_loop.rps": 500.0},
                    default_tolerance=0.2)["ok"]


def test_diff_per_metric_tolerance_and_require_equal():
    base = {"a.p99_ms": 100.0, "errors": 0.0}
    cand = {"a.p99_ms": 140.0, "errors": 1.0}
    # Wide glob tolerance forgives the p99; pinned errors still fail.
    report = diff(base, cand, tolerances=[("*.p99_ms", 0.5)],
                  require_equal=["errors"])
    assert [r["metric"] for r in report["regressions"]] == ["errors"]
    assert report["regressions"][0]["why"] == "pinned equal-or-better"


def test_diff_tolerates_schema_growth():
    report = diff({"a.p99_ms": 10.0}, {"a.p99_ms": 10.0,
                                       "new.p99_ms": 5.0})
    assert report["ok"]
    assert report["only_candidate"] == ["new.p99_ms"]


def test_cli_exit_codes(tmp_path, capsys):
    base = tmp_path / "base.json"
    reg = tmp_path / "reg.json"
    base.write_text(json.dumps({"closed_loop": {"p99_ms": 100.0,
                                                "errors": 0}}))
    reg.write_text(json.dumps({"closed_loop": {"p99_ms": 130.0,
                                               "errors": 0}}))
    assert main([str(base), str(base)]) == 0
    assert main([str(base), str(reg), "--default-tolerance", "0.2"]) == 1
    out = capsys.readouterr().out
    assert "REGRESSION closed_loop.p99_ms" in out
    assert main([str(base), str(reg), "--default-tolerance", "0.2",
                 "--json"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["ok"] is False and doc["regressions"]
    with pytest.raises(SystemExit):
        main([str(base), str(reg), "--tolerance", "nonsense"])
