"""Sequence-classifier trainer ("tx") — the transformer as a product
surface.

Round 3 left the transformer/ring-attention tier (models/transformer.py)
tested and benched but unreachable from the REST API (VERDICT r3 §5: "a
capability without a user"). This adapter registers it in the classifier
registry next to {lr,dt,rf,gb,nb,mlp}: a stored dataset whose feature
columns are token ids trains through POST /models with
``classificators_list: ["tx"]``, persists via orbax, and re-serves
through /trained-models like every other family.

The train step is the full 3-axis SPMD program (data × model × seq):
batch rows shard over ``data``, attention heads / FFN hidden over
``model`` (Megatron-style), and sequence length over ``seq`` with exact
ring attention (parallel/ring_attention.py) — the REST surface is a thin
adapter over exactly the machinery ``dryrun_multichip`` compiles for
pods.

With an ``arch`` block in its hyperparameters the same family is one of
today's language-model blocks (RMSNorm, RoPE, grouped-query attention,
the sparse-attention indexer, routed experts of which this holder was
told its share, a layer pattern with gated delta-rule linear-attention
layers or Mamba-2 layers and expert layers of their own, attention heads
of which this holder was told its share, a next-token loss with the
label-token readout: models/transformer.py). What it is held to then is
the benchmark's plain references (``perfbench/reference_tx.py``,
``perfbench/reference_hybrid.py``, ``perfbench/reference_ssm.py``); the
small block without ``arch`` has no published model to match.

The step loop touches the host once a fit: the token table is placed on
the device, each step draws its batch there from the seed, the steps are
enqueued back to back, and every step's losses, gradient norms and
counters are fetched together at the end.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from learningorchestra_tpu.models.base import TrainedModel
from learningorchestra_tpu.models.transformer import (
    MESH_AXES, NO_AXES, TxConfig, attention_path, delta_path,
    forward_reference, has_options, make_fit_programs, moe_path, ssm_path)
from learningorchestra_tpu.parallel.mesh import (
    DATA_AXIS, MODEL_AXIS, SEQ_AXIS, MeshRuntime)
from learningorchestra_tpu.utils import tracing

#: A fit's routing and selection readings: span attributes of
#: ``fit.tx.steps`` and, of the last fit, counters on ``GET /metrics``.
_READINGS = ("keys_kept_mean", "queries_short_share", "absent_share",
             "moe_imbalance", "moe_tile_rows_share", "state_absmax")

_counters_lock = threading.Lock()
_counters: Dict[str, Any] = {"fits": 0, "steps": 0, "tokens": 0,
                             "dropped_tokens": 0}


def counters_snapshot() -> Dict[str, Any]:
    """The family's counters for ``GET /metrics``: totals since the
    start, and the last fit's routing and selection readings."""
    with _counters_lock:
        return dict(_counters)


@functools.lru_cache(maxsize=8)
def _fit_programs(cfg: TxConfig, mesh, lr: float, batch: int):
    """One pair of fit programs per configuration, mesh, rate and batch:
    a second fit of the same request traces and compiles nothing."""
    return make_fit_programs(cfg, mesh, optax.adam(lr), batch)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _fit_metrics(reports: list, cfg: TxConfig, tokens_per_step: int) -> dict:
    """What a fit keeps of its steps (stored with the model's metrics):
    each step's loss parts and gradient norms, and the steps' counters."""
    steps = len(reports)
    out = {"steps": steps, "tokens": steps * tokens_per_step,
           "loss": [float(r["loss_main"] + r["loss_index"]) for r in reports],
           "loss_main": [float(r["loss_main"]) for r in reports],
           "grad_norm": {g: [float(r["grad_norm"][g]) for r in reports]
                         for g in reports[0]["grad_norm"]}}
    queries = float(steps * tokens_per_step * cfg.n_full)
    if cfg.indexer_heads or cfg.lm_head:   # 0.0 where there is no indexer
        out["loss_index"] = [float(r["loss_index"]) for r in reports]
    if "state_absmax" in reports[0]:
        # The largest |S| a linear or Mamba-2 layer's state held at the
        # end of a chunk, over the fit: says the recurrence stayed bounded.
        out["state_absmax"] = max(float(r["state_absmax"]) for r in reports)
    if cfg.n_kv_heads and cfg.causal:
        out["keys_kept_mean"] = sum(
            float(r["keys_kept"]) for r in reports) / queries
        out["queries_short_share"] = sum(
            float(r["queries_short"]) for r in reports) / queries
    if cfg.n_experts:
        moe = np.sum([np.asarray(r["moe"], np.float64) for r in reports], 0)
        per_expert = np.sum([np.asarray(r["experts"], np.float64)
                             for r in reports], 0)
        out["absent_share"] = float(moe[1] / max(moe[0], 1.0))
        out["dropped_tokens"] = int(round(moe[2]))
        out["expert_tokens"] = [int(c) for c in per_expert]
        out["moe_imbalance"] = float(
            per_expert.max() / max(per_expert.mean(), 1e-9))
        tiles = np.sum([np.asarray(r["moe_tiles"], np.float64)
                        for r in reports], 0)
        if tiles[1] > 0:      # the grouped products ran: their overhead
            out["moe_tile_rows_share"] = float(tiles[0] / tiles[1])
    return out


def fit(runtime: MeshRuntime, X: np.ndarray, y: np.ndarray,
        num_classes: int, seed: int = 0, *, d_model: int = 64,
        n_heads: int = 4, n_layers: int = 2, d_ff: int = 128,
        vocab: int = 0, train_steps: int = 300, batch: int = 1024,
        lr: float = 1e-3, causal: bool = False,
        remat: bool = False,
        arch: Optional[Dict[str, Any]] = None) -> TrainedModel:
    """Token-column design matrix → fitted transformer classifier.

    The feature columns ARE the sequence: column j holds token id at
    position j (the design matrix arrives float32; values cast back to
    int). ``vocab=0`` infers the vocabulary from the data. ``arch``
    switches the block's architecture options on (each key the
    ``TxConfig`` field of that name; ``registry.HPARAM_SPECS`` lists them); its
    sizes are a published model's and are never rounded to fit a mesh.
    """
    mesh = runtime.mesh
    arch = dict(arch or {})
    tokens_all = np.maximum(np.asarray(X, np.float32), 0.0).astype(np.int32)
    n, T = tokens_all.shape
    if n == 0 or T == 0:
        raise ValueError("tx needs at least one row and one token column")
    if not vocab:
        vocab = int(tokens_all.max()) + 1
    vocab = max(int(vocab), 2)
    tokens_all = np.minimum(tokens_all, vocab - 1)

    # Round every sharded dimension up to its mesh axis: T to the seq
    # axis (pad token 0), heads/FFN to the model axis, batch to the data
    # axis — the same program then runs on one chip or a full dp×tp×sp
    # pod mesh.
    S = mesh.shape[SEQ_AXIS]
    Dax = mesh.shape[DATA_AXIS]
    M = mesh.shape[MODEL_AXIS]
    T_pad = _round_up(T, S)
    if T_pad > T:
        if arch.get("lm_head"):
            raise ValueError(f"{T} token columns do not divide over a seq "
                             f"axis of {S}, and a next-token loss cannot "
                             "pad them")
        tokens_all = np.pad(tokens_all, ((0, 0), (0, T_pad - T)))
    if not arch:
        n_heads = _round_up(max(n_heads, 1), M)
        d_model = _round_up(max(d_model, n_heads), n_heads)
    d_ff = _round_up(max(d_ff, 1), M)
    batch = min(_round_up(batch, Dax), _round_up(n, Dax))

    cfg = TxConfig(vocab=vocab, d_model=d_model, n_heads=n_heads,
                   n_layers=n_layers, d_ff=d_ff, n_classes=num_classes,
                   max_len=T_pad, causal=causal, remat=remat, **arch)
    if arch:      # a published size is never rounded to fit a mesh
        for key, size in (("n_heads (held)", cfg.heads),
                          ("n_kv_heads (held)", cfg.kv_heads),
                          ("linear_heads (held)", cfg.lin_heads),
                          ("experts_held", cfg.held),
                          ("gated_width", cfg.gated_width),
                          ("shared_width", cfg.shared_width),
                          ("ssm_groups (M layers)",
                           cfg.ssm_groups * ("M" in cfg.pattern))):
            if size % M:
                raise ValueError(f"{key} {size} does not divide over a "
                                 f"model axis of {M}")
    init, step = _fit_programs(cfg, mesh, float(lr), batch)
    key = jax.random.PRNGKey(seed)
    with tracing.span("fit.tx.init"):
        table = runtime.replicate(tokens_all)
        labels = runtime.replicate(np.asarray(y, np.int32))
        state = jax.block_until_ready(init(key))

    # XLA's CPU backend can abort/deadlock when collective programs
    # pipeline deeply (shared thunk pool — see viz/tsne.py's identical
    # mitigation), so the simulated-mesh rig serializes steps; TPU keeps
    # the async dispatch queue.
    sync_steps = jax.default_backend() == "cpu"
    batch_key = jax.random.fold_in(key, 1 << 20)
    attrs: Dict[str, Any] = {"steps": int(train_steps),
                             "tokens": int(train_steps) * batch * T_pad,
                             **attention_path(
                                 cfg, MESH_AXES if S > 1 else
                                 MESH_AXES._replace(seq=None), T_pad // S)}
    if cfg.pattern:
        attrs.update(layer_pattern=cfg.pattern, heads_held=cfg.heads,
                     **delta_path(cfg, MESH_AXES), **ssm_path(cfg))
    attrs.update(moe_path(cfg, MESH_AXES))
    if "L" in cfg.pattern:
        attrs["linear_chunk"] = cfg.linear_chunk
    with tracing.span("fit.tx.steps", attrs):
        reports = []
        for _ in range(int(train_steps)):
            state, report = step(state, batch_key, table, labels)
            reports.append(report)
            if sync_steps:
                jax.block_until_ready(report)
        reports = jax.device_get(reports)    # the one wait of the fit
        metrics = _fit_metrics(reports, cfg, batch * T_pad)
        attrs.update(dropped_tokens=metrics.get("dropped_tokens", 0),
                     loss_first=metrics["loss"][0],
                     loss_last=metrics["loss"][-1],
                     **{k: metrics[k] for k in _READINGS if k in metrics})
    with _counters_lock:
        _counters["fits"] += 1
        _counters["steps"] += metrics["steps"]
        _counters["tokens"] += metrics["tokens"]
        _counters["dropped_tokens"] += metrics.get("dropped_tokens", 0)
        _counters.update({k: metrics[k] for k in _READINGS + ("expert_tokens",)
                          if k in metrics})

    # Replicate the fitted params: predict then runs the unsharded
    # forward under plain data parallelism on any topology, and
    # checkpointing stays a process-local numpy write (persistence.py).
    params = jax.device_put(state[0], NamedSharding(mesh, P()))
    hp = {"vocab": vocab, "d_model": d_model, "n_heads": n_heads,
          "n_layers": n_layers, "d_ff": d_ff, "n_classes": num_classes,
          "max_len": T_pad, "causal": causal, "train_steps": train_steps,
          "lr": lr, "seed": seed, "batch": batch}
    if arch:
        hp["arch"] = arch
    return TrainedModel(kind="tx", params=params,
                        predict_proba_fn=predictor(hp),
                        num_classes=num_classes, hparams=hp,
                        fit_metrics=metrics)


def predictor(hparams: dict):
    """(params, X_dev) → probs for a (possibly restored) tx model."""
    cfg = TxConfig(vocab=int(hparams["vocab"]),
                   d_model=int(hparams["d_model"]),
                   n_heads=int(hparams["n_heads"]),
                   n_layers=int(hparams["n_layers"]),
                   d_ff=int(hparams["d_ff"]),
                   n_classes=int(hparams["n_classes"]),
                   max_len=int(hparams["max_len"]),
                   causal=bool(hparams.get("causal", False)),
                   **(hparams.get("arch") or {}))
    proba = _proba_program(cfg)

    def timed(params, X):
        with tracing.span("fit.tx.predict", rows=int(X.shape[0]),
                          **attention_path(cfg, NO_AXES, cfg.max_len),
                          **delta_path(cfg, NO_AXES), **ssm_path(cfg),
                          **moe_path(cfg, NO_AXES)):
            return jax.block_until_ready(proba(params, X))

    return timed


@functools.lru_cache(maxsize=8)
def _proba_program(cfg: TxConfig):
    @jax.jit
    def proba(params, X):
        tokens = jnp.clip(X.astype(jnp.int32), 0, cfg.vocab - 1)
        pad = cfg.max_len - tokens.shape[1]
        if pad < 0 or (pad and cfg.lm_head):
            raise ValueError(
                f"dataset has {tokens.shape[1]} token columns but the "
                f"model was trained with max_len {cfg.max_len}")
        if pad:
            tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
        if not has_options(cfg):
            logits = forward_reference(params, tokens, cfg=cfg)
        else:
            # A block of rows at a time: a published-width forward of
            # every test row at once does not fit beside the weights.
            b = max(1, 8192 // cfg.max_len)
            if cfg.n_experts:       # (the others' programs stay as they were)
                logits = _in_batches(
                    lambda rows: forward_reference(params, rows, cfg=cfg),
                    tokens, b)
            else:
                logits = jax.lax.map(
                    lambda row: forward_reference(params, row[None],
                                                  cfg=cfg)[0],
                    tokens, batch_size=b)
        return jax.nn.softmax(logits, axis=-1)

    return proba


def _in_batches(fn, x, b: int):
    """``fn`` over ``x``'s rows ``b`` at a time, each block one batch
    (``lax.map`` with ``batch_size`` would ``vmap`` single rows). The
    expert layer skips the windows a batch's assignments leave empty;
    under ``vmap`` that test becomes a select that runs every window."""
    n = x.shape[0]
    full = n - n % b
    parts = []
    if full:
        y = jax.lax.map(fn, x[:full].reshape((full // b, b) + x.shape[1:]))
        parts.append(y.reshape((full,) + y.shape[2:]))
    if n % b:
        parts.append(fn(x[full:]))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]
