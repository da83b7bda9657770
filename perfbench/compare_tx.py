"""The comparison that decides ``correct`` in a ``txfit`` cell.

Nothing chaotic is held. A fit of a routed model from random weights
diverges from any second implementation within tens of steps (one
flipped top-k choice changes a token's path), so no free-running loss
after step 2 is compared. What is, all of it from what the timed fits
themselves produced and stored with their metrics:

- ``unfinished``, ``rows_wrong``, ``dropped_tokens``: exact, limit 0.
  A row is wrong where its token columns or label differ from the test
  table's row, its probabilities are not finite, or its prediction is
  not their argmax; a fit that stored no step report counts as a row.
- ``loss0_gap``: step 0's loss parts (next-token loss, index loss)
  against the reference's on the same first batch and the same seeded
  weights; the larger relative gap of the two.
- ``grad_gap.<group>``: step 0's gradient norm per group (attention,
  indexer, router, experts, embedding, head), relative gap.
- ``loss_gap.1``, ``loss_gap.2``: the loss parts of steps 1 and 2
  against the reference taking the same Adam steps itself.
- ``off.tx``: the share of the test rows read back whose class
  probabilities lie further than ``tolerance["tx"]`` (largest absolute
  difference over the classes) from the reference's own forward pass on
  the weights that fit persisted.

Every fit of the window is compared; the largest gap stands. Each limit
lies between what sound runs read and what the control (the reference
one precision down) reads; both are in PERF.md.
"""

from __future__ import annotations

import math

import numpy as np

PARTS = ("loss_main", "loss_index")


def rel_gap(a: float, b: float) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-12)


def step_gaps(steps: list, ref_steps: list) -> dict:
    """The gaps of one fit's first steps against the reference's:
    ``steps[i]`` / ``ref_steps[i]`` = ``{"loss_main", "loss_index",
    "grad_norm": {group: norm}}``."""
    out = {"loss0_gap": max(rel_gap(steps[0][p], ref_steps[0][p])
                            for p in PARTS)}
    for g, norm in ref_steps[0]["grad_norm"].items():
        out[f"grad_gap.{g}"] = rel_gap(steps[0]["grad_norm"][g], norm)
    for i in range(1, len(ref_steps)):
        out[f"loss_gap.{i}"] = max(rel_gap(steps[i][p], ref_steps[i][p])
                                   for p in PARTS)
    return out


def steps_of(meta: dict, n: int):
    """The first ``n`` step reports out of a prediction dataset's
    metadata, or None where the fit stored none."""
    try:
        return [{"loss_main": meta["loss_main"][i],
                 "loss_index": meta["loss_index"][i],
                 "grad_norm": {g: v[i] for g, v in meta["grad_norm"].items()}}
                for i in range(n)]
    except (KeyError, IndexError, TypeError):
        return None


def row_is_wrong(doc: dict, tokens: np.ndarray, label: int,
                 fields: list) -> bool:
    try:
        got = np.asarray([doc[f] for f in fields], np.int64)
        probs = [float(p) for p in doc["probability"]]
        pred, lab = int(doc["prediction"]), int(doc["label"])
    except (KeyError, TypeError, ValueError):
        return True
    if not np.array_equal(got, tokens) or lab != int(label):
        return True
    if not all(math.isfinite(p) for p in probs):
        return True
    return pred != int(np.argmax(probs))


def compare(fits: list, unfinished: int, ref_steps: list, ref_probs,
            test_tokens, test_labels, fields: list, limits: dict,
            tolerance: dict) -> tuple:
    """``fits``: one ``{"meta": metadata doc, "rows": [(row index,
    doc)], "probs_of": whether ref_probs is of this fit's weights}`` per
    timed fit. Returns ``(correct, checks, observed)``."""
    values = {"unfinished": float(unfinished), "rows_wrong": 0.0,
              "dropped_tokens": 0.0}
    observed: dict = {}
    gaps: dict = {}
    for fit in fits:
        meta = fit["meta"] or {}
        steps = steps_of(meta, len(ref_steps))
        if steps is None:
            values["rows_wrong"] += 1
        else:
            for k, v in step_gaps(steps, ref_steps).items():
                gaps[k] = max(gaps.get(k, 0.0), v)
        values["dropped_tokens"] += float(meta.get("dropped_tokens", 0) or 0)
        diffs = []
        for r, doc in fit["rows"]:
            if row_is_wrong(doc, test_tokens[r], test_labels[r], fields):
                values["rows_wrong"] += 1
            elif fit["probs_of"]:
                diffs.append(float(np.max(np.abs(
                    np.asarray(doc["probability"], np.float64)
                    - ref_probs[r]))))
        if fit["probs_of"]:
            observed["gap.tx"] = float(np.mean(diffs)) if diffs \
                else float("nan")
            observed["gap_max.tx"] = float(np.max(diffs)) if diffs \
                else float("nan")
            gaps["off.tx"] = float(np.mean(
                np.asarray(diffs) > tolerance["tx"])) if diffs \
                else float("nan")
    observed.update(gaps)
    values.update({k: v for k, v in gaps.items() if k in limits})
    for name in limits:
        values.setdefault(name, float("nan"))     # held but never read
    checks = {k: {"value": v, "limit": float(limits[k])}
              for k, v in values.items() if k in limits}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    return correct, checks, observed
