"""Everything the harness knows about a cell, found by name.

``BENCHMARK.json`` names cells, configurations and metrics; each has a
file of its own under ``perfbench/``, and nothing here (or in ``run.py``)
holds one's name:

    workloads/<cell>.json        config, traffic kind + parameters, limits
    configs/<config>.json        sizes, data recipe, hyperparameters
    traffic/<kind>.py            the generator for that kind of traffic
    layer_metrics/<metric>.json  reader + its parameters
    readers/<reader>.py          ``read(params, ctx) -> float | None``
    peaks.json                   published peaks by ``device_kind``

A later PR adds a cell, a configuration or a metric by adding files and
an entry in ``BENCHMARK.json``. ``root`` is the directory that holds
``BENCHMARK.json``; the tests point it at a tiny copy.
"""

from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class BenchError(RuntimeError):
    """The run cannot be made: no result line is printed."""


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_benchmark(root: str = REPO) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def bench_dir(root: str, bench: dict) -> str:
    """The directory of ``paths`` that holds the harness's data files."""
    for p in bench["paths"]:
        if os.path.isdir(os.path.join(root, p, "workloads")):
            return os.path.join(root, p)
    raise BenchError(f"no directory of {bench['paths']} holds workloads/")


def load_cell(name: str, root: str = REPO) -> dict:
    """The cell ``name`` with its configuration, its metrics and their
    reader files resolved. Raises ``BenchError`` for anything missing."""
    bench = load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    base = bench_dir(root, bench)
    work = _load(os.path.join(base, "workloads", name + ".json"))
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == entry["config"])
    config = _load(os.path.join(root, conf_entry["file"]))
    if work["config"] != entry["config"] or \
            work["traffic"]["kind"] != entry["traffic"]:
        raise BenchError(f"{name}: the workload file and BENCHMARK.json "
                         "disagree on config or traffic")

    def of_cell(metric):
        return name in metric.get("workloads", [name])

    layer = []
    for m in bench["per_layer"]:
        if of_cell(m):
            spec = _load(os.path.join(base, "layer_metrics",
                                      m["name"] + ".json"))
            layer.append(dict(m, spec=spec))
    return {
        "name": name, "chips": entry["chips"], "config_name": entry["config"],
        "config": config, "traffic": work["traffic"],
        "limits": work["limits"], "tolerance": work.get("tolerance", {}),
        "base": base, "root": root,
        "end_to_end": [m for m in bench["end_to_end"] if of_cell(m)],
        "per_layer": layer,
    }


def traffic_module(kind: str):
    if not NAME.match(kind):
        raise BenchError(f"bad traffic kind {kind!r}")
    return importlib.import_module(f"perfbench.traffic.{kind}")


def reader_module(reader: str):
    if not NAME.match(reader):
        raise BenchError(f"bad reader {reader!r}")
    return importlib.import_module(f"perfbench.readers.{reader}")


def load_peaks(base: str = HERE) -> dict:
    return _load(os.path.join(base, "peaks.json"))["devices"]


def device_peaks(device_kind: str, base: str = HERE) -> dict:
    peaks = load_peaks(base)
    if device_kind not in peaks:
        raise BenchError(f"device kind {device_kind!r} is not in peaks.json "
                         f"({sorted(peaks)}): no peak, no run")
    return peaks[device_kind]


def layer_metrics(cell: dict, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader found something:
    ``{name: {"value", "unit"}}``. A reader that returns None is left
    out; it never stands as 0."""
    out = {}
    for m in cell["per_layer"]:
        value = reader_module(m["spec"]["reader"]).read(m["spec"], ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def last_line(correct: bool, attempted: int, failed: int, metrics: dict,
              device: dict, checks: dict, breakdown: dict | None = None,
              observed: dict | None = None) -> dict:
    """The object a run's last line of standard output carries: the
    contract's keys, ``breakdown`` in a traced run, ``observed`` (the
    comparison's statistics that are read but not held; the driver
    ignores the key) and last the numbers compared, each beside its
    limit."""
    doc = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        doc["breakdown"] = breakdown
    if observed:
        doc["observed"] = observed
    doc["checks"] = checks
    return doc
