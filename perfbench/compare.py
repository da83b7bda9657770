"""The comparison that decides ``correct``.

Input: rows a client read back from the prediction datasets of the timed
sweeps (a sample of pages drawn from the seed, from every sweep), the
test table they must carry, and the plain reference's probabilities.
Output: the numbers compared, each beside its limit, and whether all hold.

- ``rows_wrong``: sampled rows whose feature values or label differ from
  the test table's row at that position, whose ``prediction`` is not the
  argmax of its ``probability``, or whose probabilities are not finite.
  Exact: limit 0.
- ``gap.<family>``: mean over that family's sampled rows of
  ``|p1 read back - p1 of the reference|``.
- ``off.<family>``: share of that family's sampled rows whose p1 lies
  further than ``tolerance[family]`` from the reference's: a row routed
  to another leaf is off, a leaf table read at lower precision is not.
  Rows the reference marks ``<family>.unsure`` (below a split that two
  candidates tie for in float32) are left out; ``unsure.<family>``
  under ``observed`` is their share.
  A family is held to whichever of the two its cell's ``limits`` name.
  Each limit lies between what sound runs read and what the control
  (the reference one precision down) reads; both are in PERF.md.
- ``unfinished``: prediction datasets that are missing, unfinished or
  carry an error (these are also the run's ``failed``). Limit 0.
"""

from __future__ import annotations

import math

import numpy as np


def row_is_wrong(doc: dict, XT_test: np.ndarray, y_test: np.ndarray,
                 r: int) -> bool:
    d = XT_test.shape[0]
    try:
        feats = np.asarray([doc[f"f{i}"] for i in range(d)], np.float32)
        probs = [float(p) for p in doc["probability"]]
        pred = int(doc["prediction"])
        label = int(doc["label"])
    except (KeyError, TypeError, ValueError):
        return True
    if not np.array_equal(feats, XT_test[:, r]) or label != int(y_test[r]):
        return True
    if not all(math.isfinite(p) for p in probs):
        return True
    return pred != int(np.argmax(probs))


def compare(samples: dict, unfinished: int, ref: dict, XT_test, y_test,
            limits: dict, tolerance: dict) -> tuple:
    """``samples``: ``{family: [(row index, doc), ...]}``. Returns
    ``(correct, checks, observed)``: ``checks = {name: {"value",
    "limit"}}`` are the numbers held; ``observed`` has both statistics of
    every family, held or not. A family with no sampled row, or a number
    that is not finite, fails."""
    checks = {"unfinished": {"value": float(unfinished),
                             "limit": float(limits["unfinished"])}}
    wrong, observed = 0, {}
    for family, rows in samples.items():
        gaps = []
        unsure = ref.get(f"{family}.unsure")
        for r, doc in rows:
            if row_is_wrong(doc, XT_test, y_test, r):
                wrong += 1
            elif unsure is None or not unsure[r]:
                gaps.append(abs(float(doc["probability"][1])
                                - float(ref[family][r])))
        if unsure is not None and rows:
            observed[f"unsure.{family}"] = float(
                np.mean([bool(unsure[r]) for r, _ in rows]))
        stats = {"gap": float(np.mean(gaps)) if gaps else float("nan"),
                 "off": float(np.mean(np.asarray(gaps) > tolerance[family]))
                 if gaps else float("nan")}
        for stat, value in stats.items():
            name = f"{stat}.{family}"
            observed[name] = value
            if name in limits:
                checks[name] = {"value": value, "limit": float(limits[name])}
    checks["rows_wrong"] = {"value": float(wrong),
                            "limit": float(limits["rows_wrong"])}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    return correct, checks, observed
