"""Evaluation metrics, on the host.

The reference evaluates each fitted model with two Spark
``MulticlassClassificationEvaluator`` jobs — metricName "f1" (weighted by
class support) and "accuracy" (reference model_builder.py:206-225). Both are
reproduced here from a single confusion matrix counted on the host: the
labels and predictions are host arrays already, and a device program
would queue behind every kernel the other families of a sweep have
enqueued — a family's finishing waits for nothing but its own results.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def classification_metrics(y_true: np.ndarray, y_pred: np.ndarray,
                           num_classes: int) -> Dict[str, float]:
    """accuracy + support-weighted F1 (pyspark's default "f1")."""
    idx = (np.asarray(y_true, np.int64) * num_classes
           + np.asarray(y_pred, np.int64))
    # Counted in integers, then float32: the arithmetic below is the
    # one the device's float32 confusion matrix went through, so f1 and
    # accuracy keep their digits.
    cm = np.bincount(idx, minlength=num_classes * num_classes).reshape(
        num_classes, num_classes).astype(np.float32)
    support = cm.sum(axis=1)
    tp = np.diag(cm)
    pred_pos = cm.sum(axis=0)
    precision = np.where(pred_pos > 0, tp / np.maximum(pred_pos, 1), 0.0)
    recall = np.where(support > 0, tp / np.maximum(support, 1), 0.0)
    denom = precision + recall
    f1 = np.where(denom > 0, 2 * precision * recall / np.maximum(denom, 1e-12),
                  0.0)
    total = support.sum()
    weighted_f1 = float((f1 * support).sum() / max(total, 1))
    accuracy = float(tp.sum() / max(total, 1))
    return {"f1": weighted_f1, "accuracy": accuracy}
