"""Classifier registry — the reference's switcher, extended.

The reference maps ``{"lr", "dt", "rf", "gb", "nb"}`` to pyspark.ml
classifiers (reference model_builder.py:152-158) and returns 409 for unknown
names (ModelBuilderRequestValidator, model_builder.py:284-292). Same five
names here, plus the TPU-native extensions: "mlp" (dp×tp perceptron) and
"tx" (the dp×tp×sp transformer with ring attention, models/sequence.py).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

from learningorchestra_tpu.models import (
    logistic, mlp, naive_bayes, sequence, trees)

CLASSIFIERS: Dict[str, Callable] = {
    "lr": logistic.fit,
    "dt": trees.fit_dt,
    "rf": trees.fit_rf,
    "gb": trees.fit_gb,
    "nb": naive_bayes.fit,
    "mlp": mlp.fit,
    "tx": sequence.fit,
}

#: Families the ONLINE predict tier (models/aot.py, serving/batcher.py)
#: serves: every continuous-feature family. "tx" is excluded — it
#: consumes token sequences, so inline JSON feature rows are
#: out-of-domain for it (its serving story is the batch predictions
#: route). That holds with its ``arch`` block too: a row of a published
#: language model is thousands of token columns and its forward pass is
#: seconds of chip time, not a micro-batch.
ONLINE_KINDS = ("lr", "nb", "dt", "rf", "gb", "mlp")


def _int_range(lo: int, hi: int) -> Tuple[Callable, str]:
    return (lambda v: isinstance(v, int) and not isinstance(v, bool)
            and lo <= v <= hi, f"an integer in [{lo}, {hi}]")


def _positive() -> Tuple[Callable, str]:
    return (lambda v: isinstance(v, (int, float))
            and not isinstance(v, bool) and v > 0, "a number > 0")


def _nonneg() -> Tuple[Callable, str]:
    return (lambda v: isinstance(v, (int, float))
            and not isinstance(v, bool) and v >= 0, "a number >= 0")


def _choice(*opts: str) -> Tuple[Callable, str]:
    return (lambda v: v in opts, f"one of {sorted(opts)}")


def _boolean() -> Tuple[Callable, str]:
    return (lambda v: isinstance(v, bool), "a boolean")


#: Per-family user-settable hyperparameters with their legal ranges —
#: the single validation table behind the 406s on ``POST /models`` and
#: ``POST /tune``. Keys the builder injects itself (``edges``, ``ckpt``)
#: are deliberately absent: a request naming them is rejected as
#: unknown instead of silently colliding with the injected values. The
#: tree-depth/bin caps mirror the builders' structural limits (uint8
#: bin codes; 2^(depth+1)-1 node arrays).
_SEED = _int_range(0, 2 ** 31 - 1)
HPARAM_SPECS: Dict[str, Dict[str, Tuple[Callable, str]]] = {
    "lr": {"seed": _SEED, "iters": _int_range(1, 1_000_000),
           "lr": _positive(), "l2": _nonneg(),
           "solver": _choice("auto", "newton", "adam")},
    "dt": {"seed": _SEED, "max_depth": _int_range(1, 12),
           "n_bins": _int_range(2, 256)},
    "rf": {"seed": _SEED, "max_depth": _int_range(1, 12),
           "n_bins": _int_range(2, 256), "n_trees": _int_range(1, 1024),
           "mtry": _int_range(1, 65536)},
    "gb": {"seed": _SEED, "max_depth": _int_range(1, 12),
           "n_bins": _int_range(2, 256), "n_rounds": _int_range(1, 4096),
           "step_size": _positive()},
    "nb": {"seed": _SEED, "smoothing": _positive(),
           "event_model": _choice("gaussian", "multinomial")},
    "mlp": {"seed": _SEED, "hidden": _int_range(1, 65536),
            "iters": _int_range(1, 1_000_000), "lr": _positive(),
            "l2": _nonneg()},
    "tx": {"seed": _SEED, "d_model": _int_range(8, 4096),
           "n_heads": _int_range(1, 64), "n_layers": _int_range(1, 64),
           "d_ff": _int_range(8, 16384), "vocab": _int_range(0, 2 ** 22),
           "train_steps": _int_range(1, 1_000_000),
           "batch": _int_range(1, 1 << 22), "lr": _positive(),
           "causal": _boolean(), "remat": _boolean(),
           # The architecture block (models/transformer.py's options): a
           # nested table, validated key by key and persisted whole in
           # the model's hparams so predictor_for rebuilds the model.
           "arch": {
               "rms_norm": _boolean(), "norm_eps": _positive(),
               "n_kv_heads": _int_range(1, 64),
               "head_dim": _int_range(2, 512), "rope_theta": _positive(),
               "qk_norm": _boolean(), "indexer_heads": _int_range(1, 64),
               "indexer_head_dim": _int_range(2, 512),
               "indexer_topk": _int_range(1, 1 << 20),
               "q_chunk": _int_range(1, 1 << 16),
               "n_experts": _int_range(1, 4096),
               "experts_per_token": _int_range(1, 64),
               "expert_width": _int_range(1, 65536),
               "experts_first": _int_range(0, 4095),
               "experts_held": _int_range(1, 4096),
               "norm_topk_prob": _boolean(), "lm_head": _boolean(),
               "init_std": _positive(),
               # A period of layer kinds (F full attention, L gated
               # delta-rule linear attention, M Mamba-2, E experts
               # alone), the linear and the Mamba-2 mixer.
               "layer_pattern": (
                   lambda v: isinstance(v, str) and 0 < len(v) <= 64
                   and not set(v) - set("FLME"),
                   "a string of F, L, M and E, at most 64 long"),
               "linear_heads": _int_range(1, 256),
               "linear_key_dim": _int_range(1, 1024),
               "linear_value_dim": _int_range(1, 1024),
               "linear_conv": _int_range(1, 16),
               "linear_neg_eigval": _boolean(),
               "linear_chunk": _int_range(1, 1024),
               "gated_width": _int_range(1, 1 << 18),
               "post_norm": _boolean(), "qk_norm_whole": _boolean(),
               "no_positions": _boolean(),
               "heads_held": _int_range(1, 64),
               "ssm_heads": _int_range(1, 1024),
               "ssm_head_dim": _int_range(1, 1024),
               "ssm_state": _int_range(1, 1024),
               "ssm_groups": _int_range(1, 1024),
               "ssm_conv": _int_range(1, 16),
               "ssm_chunk": _int_range(1, 4096),
               # The expert layer's router, scaling and expert forms.
               "router_sigmoid": _boolean(), "routed_scale": _positive(),
               "relu2_experts": _boolean(),
               "shared_width": _int_range(1, 1 << 18)}},
}


def validate_hparams(classifier: str, hparams: Any) -> None:
    """Reject unknown hyperparameter names and out-of-range values with a
    ValueError NAMING the offending key (the serving tier maps it to a
    406) — instead of the TypeError-500 a bad ``**kwargs`` splat would
    raise from deep inside a trainer."""
    get_trainer(classifier)  # unknown classifier: its own ValueError
    if hparams in (None, {}):
        return
    if not isinstance(hparams, dict):
        raise ValueError(
            f"hparams for classifier {classifier!r} must be an object of "
            f"name->value, got {type(hparams).__name__}")
    _validate_table(classifier, HPARAM_SPECS[classifier], hparams, "")


def _validate_table(classifier: str, spec: Dict, table: Dict,
                    prefix: str) -> None:
    for key, value in table.items():
        name = prefix + key
        if key not in spec:
            raise ValueError(
                f"unknown hparam {name!r} for classifier {classifier!r}; "
                f"known: {sorted(prefix + k for k in spec)}")
        if isinstance(spec[key], dict):
            if not isinstance(value, dict):
                raise ValueError(
                    f"hparam {name!r} for classifier {classifier!r} must "
                    f"be an object of name->value, got "
                    f"{type(value).__name__}")
            _validate_table(classifier, spec[key], value, name + ".")
            continue
        check, expect = spec[key]
        if not check(value):
            raise ValueError(
                f"hparam {name!r} for classifier {classifier!r} is out of "
                f"range: expected {expect}, got {value!r}")


def get_trainer(name: str) -> Callable:
    try:
        return CLASSIFIERS[name]
    except KeyError:
        raise ValueError(
            f"invalid classifier {name!r}; choose from "
            f"{sorted(CLASSIFIERS)}") from None


def predictor_for(kind: str, hparams: Dict) -> Callable:
    """Rebuild the (params, X) -> probs function for a persisted model.

    Every family's predictor is a module function parameterized only by
    static hparams, so a checkpoint of (kind, hparams, params) fully
    reconstructs a servable model (models/persistence.py)."""
    from functools import partial

    from learningorchestra_tpu.models import trees

    if kind in ("dt", "rf"):
        return partial(trees._forest_proba_static,
                       max_depth=int(hparams["max_depth"]))
    if kind == "gb":
        # ovr_classes marks a one-vs-rest multiclass booster stack
        # (leading class axis on the tree params); absent = the binary
        # reference-parity model.
        fn = (trees._gbt_ovr_proba_static if hparams.get("ovr_classes")
              else trees._gbt_proba_static)
        return partial(fn, max_depth=int(hparams["max_depth"]))
    if kind == "lr":
        return logistic._predict_proba
    if kind == "nb":
        return (naive_bayes._predict_multinomial
                if hparams.get("event_model") == "multinomial"
                else naive_bayes._predict_proba)
    if kind == "mlp":
        return mlp._predict_proba
    if kind == "tx":
        return sequence.predictor(hparams)
    raise ValueError(f"no predictor for classifier kind {kind!r}")
