"""The plain reference for the ``tx`` family's published language-model
block (Keye-VL-2.0-30B-A3B's text model: the configuration file holds
its ``config.json`` keys and this file reads the sizes from them).

Independent of ``learningorchestra_tpu``: nothing is imported from it.
The equations, per layer, on a row of ``T`` positions, causal, with
``h = RMSNorm(x)``:

- attention: ``q = RoPE(RMSNorm_head(W_q h))``, ``k = RoPE(RMSNorm_head(
  W_k h))``, ``v = W_v h``; each key/value head serves ``H / G`` query
  heads; rotate-half RoPE over the whole head, base ``rope_theta``;
- indexer (``sa_config``): ``qI = RoPE(W_qI h)``, ``kI = RoPE(LayerNorm(
  W_kI h))``, ``w = W_w h``; ``I[t,s] = sum_j w[t,j] relu(qI[t,j].kI[s])
  / sqrt(Di) / sqrt(Hi)`` for ``s <= t``; ``S_t``: the ``topk`` positions
  with the largest ``I[t,s]`` (all while ``t < topk``; ties at the
  k-th value all kept);
- ``o[t] = sum_{s in S_t} softmax_{S_t}(q[t].k[s] / sqrt(D)) v[s]``,
  ``x += W_o o``;
- experts: ``g = softmax(W_r RMSNorm(x))`` over all ``num_experts``, the
  ``num_experts_per_tok`` largest renormalised to sum 1; ``x += sum over
  the chosen experts THAT ARE HELD of g_e W_down^e(silu(W_gate^e h')
  * W_up^e h')``: the share (``num_local_experts`` from
  ``experts_first``) is given, what the absent experts would add is left
  out, as in the deployment's one chip;
- loss ``L = L_LM + L_I``: mean next-token cross-entropy over every
  position (the target of a row's last position is its label token, id =
  class), logits over the ``vocab_size`` rows held; ``L_I = sum over
  layers of mean_t KL(p_t || softmax_{S_t} I[t,:])`` with ``p_t`` the
  main attention's probabilities over ``S_t`` summed over heads and
  L1-normalised; ``p_t`` and the indexer's input ``h`` detached.

Weights from the seed by the stated recipe (``init_weights``), batches
by the stated recipe (``batch_rows``), Adam written out here. Everything
float32 with every product at ``highest`` precision; blocks of queries
and ``jax.checkpoint`` only so that a row of 8,192 positions fits. The
control rounds weights and operands one type further down than the
configuration states (``precision.control``).
"""

from __future__ import annotations

import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

#: Leaf -> group of the per-group gradient norms.
GROUPS = {
    "embed": "embedding", "head_w": "head", "lnf_g": "head",
    "ln1_g": "attention", "wq": "attention", "wk": "attention",
    "wv": "attention", "wo": "attention", "q_norm": "attention",
    "k_norm": "attention",
    "ix_wq": "indexer", "ix_wk": "indexer", "ix_kn_g": "indexer",
    "ix_kn_b": "indexer", "ix_ww": "indexer",
    "router": "router",
    "ln2_g": "experts", "we_gate": "experts", "we_up": "experts",
    "we_down": "experts",
}
Q_BLOCK = 256        # queries per block of the attention
POS_BLOCK = 2048     # positions per block of the head's logits


def sizes(conf: dict) -> dict:
    """The sizes this file needs, from the configuration's own keys."""
    sa = conf["sa_config"]
    return {"L": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "H": conf["num_attention_heads"],
            "G": conf["num_key_value_heads"], "D": conf["head_dim"],
            "Hi": sa["indexer_num_heads"], "Di": sa["indexer_head_dim"],
            "topk": sa["topk"], "E": conf["num_experts"],
            "K": conf["num_experts_per_tok"],
            "held": conf["num_local_experts"],
            "first": conf.get("experts_first", 0),
            "f": conf["moe_intermediate_size"], "V": conf["vocab_size"],
            "eps": conf["rms_norm_eps"], "theta": float(conf["rope_theta"]),
            "renorm": bool(conf["norm_topk_prob"]),
            "std": conf["init"]["std"]}


def leaf_shapes(z: dict) -> dict:
    """``{path: (shape, kind)}``; layer leaves carry the layer axis first."""
    L, d, H, G, D = z["L"], z["d"], z["H"], z["G"], z["D"]
    Hi, Di, f = z["Hi"], z["Di"], z["f"]
    lay = {"ln1_g": ((L, d), "ones"), "ln2_g": ((L, d), "ones"),
           "wq": ((L, d, H, D), "normal"), "wk": ((L, d, G, D), "normal"),
           "wv": ((L, d, G, D), "normal"), "wo": ((L, H, D, d), "normal"),
           "q_norm": ((L, D), "ones"), "k_norm": ((L, D), "ones"),
           "ix_wq": ((L, d, Hi, Di), "normal"), "ix_wk": ((L, d, Di), "normal"),
           "ix_kn_g": ((L, Di), "ones"), "ix_kn_b": ((L, Di), "zeros"),
           "ix_ww": ((L, d, Hi), "normal"),
           "router": ((L, d, z["E"]), "normal"),
           "we_gate": ((L, z["held"], d, f), "normal"),
           "we_up": ((L, z["held"], d, f), "normal"),
           "we_down": ((L, z["held"], f, d), "normal")}
    out = {"embed": ((z["V"], d), "normal"), "lnf_g": ((d,), "ones"),
           "head_w": ((d, z["V"]), "normal")}
    out.update({f"layers.{k}": v for k, v in lay.items()})
    return out


def init_weights(conf: dict, seed: int) -> dict:
    """The configuration's init recipe: top-level leaves in sorted order,
    then the layer leaves in sorted order, numbered from 0; leaf ``i`` is
    ``normal(fold_in(PRNGKey(seed), i), shape, float32) * std``; norm
    gains are ones and the one bias zeros. ``{path: array}``."""
    z = sizes(conf)
    shapes = leaf_shapes(z)
    top = sorted(p for p in shapes if not p.startswith("layers."))
    order = top + sorted(p for p in shapes if p.startswith("layers."))
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, path in enumerate(order):
        shape, kind = shapes[path]
        if kind == "normal":
            out[path] = jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * z["std"]
        else:
            out[path] = (jnp.ones if kind == "ones" else jnp.zeros)(
                shape, jnp.float32)
    return out


def batch_rows(seed: int, step: int, batch: int, n_rows: int) -> np.ndarray:
    """The rows of step ``step``'s batch, by the configuration's recipe:
    ``randint(fold_in(fold_in(PRNGKey(seed), 2**20), step), (batch,), 0,
    n_rows)``."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1 << 20)
    return np.asarray(jax.random.randint(
        jax.random.fold_in(key, step), (batch,), 0, n_rows))


def load_saved(model_dir: str) -> dict:
    """The weights a fit persisted, read from its files: ``params.json``
    lists each leaf's path, dtype, shape and byte offset in
    ``params.bin``. ``{path: numpy array}``."""
    with open(os.path.join(model_dir, "params.json"), encoding="utf-8") as fh:
        index = json.load(fh)
    out = {}
    with open(os.path.join(model_dir, "params.bin"), "rb") as fh:
        for leaf in index["leaves"]:
            fh.seek(leaf["offset"])
            count = int(np.prod(leaf["shape"], dtype=np.int64))
            out[leaf["path"]] = np.fromfile(
                fh, dtype=leaf["dtype"], count=count).reshape(leaf["shape"])
    return out


# --- the equations ----------------------------------------------------------

def _down(x, dtype: str):
    """``x`` rounded to ``dtype`` and back: what computing that operand
    in the lower type loses."""
    if dtype == "float32":
        return x
    return x.astype(jnp.dtype(dtype)).astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (T, heads, D): rotate-half over the whole head."""
    T, _, D = x.shape
    half = D // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(x, W, z, prec):
    """One row's attention half: ``(x + W_o o, index loss summed over the
    row's queries)``."""
    T = x.shape[0]
    op, att = prec["operands"], prec["attention_operands"]
    h = _down(_rms(x, W["ln1_g"], z["eps"]), op)
    q = _rope(_rms(jnp.einsum("td,dhe->the", h, W["wq"]), W["q_norm"],
                   z["eps"]), z["theta"])
    k = _rope(_rms(jnp.einsum("td,dge->tge", h, W["wk"]), W["k_norm"],
                   z["eps"]), z["theta"])
    v = jnp.einsum("td,dge->tge", h, W["wv"])
    hi = jax.lax.stop_gradient(h)
    qi = _rope(jnp.einsum("td,dje->tje", hi, W["ix_wq"]), z["theta"])
    ki = jnp.einsum("td,de->te", hi, W["ix_wk"])
    mu = ki.mean(-1, keepdims=True)
    ki = (ki - mu) / jnp.sqrt(((ki - mu) ** 2).mean(-1, keepdims=True)
                              + z["eps"]) * W["ix_kn_g"] + W["ix_kn_b"]
    ki = _rope(ki[:, None, :], z["theta"])[:, 0, :]
    wi = jnp.einsum("td,dj->tj", hi, W["ix_ww"])
    q, k, v, qi, ki = (_down(a, att) for a in (q, k, v, qi, ki))
    rep = z["H"] // z["G"]
    kk, vv = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    n_keep = min(z["topk"], T)
    block = next(c for c in range(min(Q_BLOCK, T), 0, -1) if T % c == 0)

    def queries(start):
        t = start + jnp.arange(block)
        allowed = jnp.arange(T)[None, :] <= t[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        qib = jax.lax.dynamic_slice_in_dim(qi, start, block, 0)
        wb = jax.lax.dynamic_slice_in_dim(wi, start, block, 0)
        dots = jnp.maximum(jnp.einsum("tje,se->tjs", qib, ki), 0.0)
        score = jnp.einsum("tjs,tj->ts", dots, wb) / np.sqrt(z["Di"]) \
            / np.sqrt(z["Hi"])
        ranked = jnp.where(allowed, jax.lax.stop_gradient(score), -jnp.inf)
        kth = jax.lax.top_k(ranked, n_keep)[0][:, -1]
        chosen = allowed & (ranked >= kth[:, None])
        logits = jnp.einsum("the,she->hts", qb, kk) / np.sqrt(z["D"])
        p = jax.nn.softmax(jnp.where(chosen[None], logits, -jnp.inf), -1)
        o = jnp.einsum("hts,she->the", _down(p, att), vv)
        target = jax.lax.stop_gradient(p).sum(0)
        target = target / target.sum(-1, keepdims=True)
        logq = jax.nn.log_softmax(jnp.where(chosen, score, -jnp.inf), -1)
        kl = jnp.sum(jnp.where(
            target > 0, target * (jnp.log(jnp.where(target > 0, target, 1.0))
                                  - jnp.where(chosen, logq, 0.0)), 0.0))
        return o, kl

    o, kl = jax.lax.map(jax.checkpoint(queries),
                        jnp.arange(0, T, block))
    o = o.reshape(T, z["H"], z["D"])
    return x + jnp.einsum("the,hed->td", _down(o, op), W["wo"]), kl.sum()


def _experts(x, W, z, prec, whole: bool = False):
    """One row's expert half: ``x + the held experts' part`` (with
    ``whole``, every expert is held: ``W`` then carries all of them)."""
    h = _rms(x, W["ln2_g"], z["eps"])
    g = jax.nn.softmax(jnp.einsum("td,de->te", h, W["router"]), -1)
    top_g, top_e = jax.lax.top_k(g, z["K"])
    if z["renorm"]:
        top_g = top_g / top_g.sum(-1, keepdims=True)
    hd = _down(h, prec["operands"])
    first = 0 if whole else z["first"]

    def one(acc, args):
        e, wg, wu, wd = args
        gate = jnp.sum(jnp.where(top_e == first + e, top_g, 0.0), -1)
        y = jax.nn.silu(hd @ wg) * (hd @ wu)
        return acc + gate[:, None] * (_down(y, prec["operands"]) @ wd), None

    n_here = W["we_gate"].shape[0]
    part, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(n_here), W["we_gate"], W["we_up"], W["we_down"]))
    return x + part


def _trunk(w, tokens, z, prec):
    """(B, T) tokens -> ``(x (B, T, d) before the final norm, index loss
    summed over rows, layers and queries)``."""
    W = {p: _down(a, prec["weights"]) for p, a in w.items()
         if not p.startswith("layers.")}
    layers = {p[len("layers."):]: a for p, a in w.items()
              if p.startswith("layers.")}

    @jax.checkpoint
    def layer(x, lw):
        # rounded a layer at a time: the control holds no second copy
        # of every layer's weights
        lw = {k: _down(a, prec["weights"]) for k, a in lw.items()}

        def row(xr):
            xr, kl = _attention(xr, lw, z, prec)
            return _experts(xr, lw, z, prec), kl
        x, kl = jax.lax.map(row, x)
        return x, kl.sum()

    x, kl = jax.lax.scan(layer, W["embed"][tokens], layers)
    return x, kl.sum(), W


def loss_parts(w, tokens, labels, z, prec):
    """``(L_LM, L_I)`` of a batch: tokens (B, T) int32, labels (B,)."""
    x, kl, W = _trunk(w, tokens, z, prec)
    B, T = tokens.shape
    targets = jnp.concatenate([tokens[:, 1:], labels[:, None]], 1)
    h = _down(_rms(x, W["lnf_g"], z["eps"]), prec["operands"])
    n = B * T
    block = next(c for c in range(min(POS_BLOCK, n), 0, -1) if n % c == 0)

    @jax.checkpoint
    def positions(args):
        hb, tb = args
        logp = jax.nn.log_softmax(hb @ W["head_w"], -1)
        return -jnp.take_along_axis(logp, tb[:, None], 1).sum()

    ce = jax.lax.map(positions, (h.reshape(n // block, block, -1),
                                 targets.reshape(n // block, block)))
    return ce.sum() / n, kl / n


def group_norms(grads: dict) -> dict:
    sq: dict = {}
    for path, g in grads.items():
        grp = GROUPS[path.split(".")[-1]]
        sq[grp] = sq.get(grp, 0.0) + jnp.sum(g * g)
    return {k: jnp.sqrt(v) for k, v in sq.items()}


def _precision(prec: dict | None) -> dict:
    return dict({"weights": "float32", "operands": "float32",
                 "attention_operands": "float32"}, **(prec or {}))


def adam_steps(conf: dict, w: dict, batches: list, lr: float,
               prec: dict | None = None) -> list:
    """Take ``len(batches)`` Adam steps (b1 0.9, b2 0.999, eps 1e-8 outside
    the root, bias-corrected, no decay) from ``w`` on the given ``(tokens,
    labels)`` batches. Per step, BEFORE its update: ``{"loss_main",
    "loss_index", "grad_norm": {group: norm}}`` as floats."""
    z, prec = sizes(conf), _precision(prec)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, m, v, t, tokens, labels):
        def total(w):
            lm, li = loss_parts(w, tokens, labels, z, prec)
            return lm + li, (lm, li)
        (_, (lm, li)), g = jax.value_and_grad(total, has_aux=True)(w)
        t = t + 1
        m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        w = jax.tree.map(
            lambda p, a, b: p - lr * (a / (1 - 0.9 ** t))
            / (jnp.sqrt(b / (1 - 0.999 ** t)) + 1e-8), w, m, v)
        return w, m, v, t, {"loss_main": lm, "loss_index": li,
                            "grad_norm": group_norms(g)}

    with jax.default_matmul_precision("highest"):
        w = {p: jnp.array(a, jnp.float32) for p, a in w.items()}
        m = jax.tree.map(jnp.zeros_like, w)
        v = jax.tree.map(jnp.zeros_like, w)
        t = jnp.zeros((), jnp.float32)
        out = []
        for tokens, labels in batches:
            w, m, v, t, rep = step(w, m, v, t, jnp.asarray(tokens, jnp.int32),
                                   jnp.asarray(labels, jnp.int32))
            out.append(jax.tree.map(float, jax.device_get(rep)))
    return out


def class_probs(conf: dict, w: dict, tokens, n_classes: int,
                prec: dict | None = None) -> np.ndarray:
    """The softmax of each row's last-position logits over the label
    tokens ``0 .. n_classes-1``: (rows, n_classes) float32."""
    z, prec = sizes(conf), _precision(prec)

    @jax.jit
    def one(w, row):
        x, _, W = _trunk(w, row[None], z, prec)
        last = _down(_rms(x[0, -1], W["lnf_g"], z["eps"]), prec["operands"])
        return jax.nn.softmax(last @ W["head_w"][:, :n_classes])

    with jax.default_matmul_precision("highest"):
        w = {p: jnp.asarray(a, jnp.float32) for p, a in w.items()}
        return np.stack([np.asarray(one(w, jnp.asarray(r, jnp.int32)))
                         for r in np.asarray(tokens)])


def layer_whole(conf: dict, lw: dict, x, prec: dict | None = None):
    """ONE layer with every expert held (``lw["we_*"]`` carry all
    ``num_experts``), on one row ``x`` (T, d): the uncut layer the tests
    add the shares up to. Returns ``(after attention, after experts)``."""
    z, prec = sizes(conf), _precision(prec)
    with jax.default_matmul_precision("highest"):
        mid, _ = _attention(jnp.asarray(x), lw, z, prec)
        return mid, _experts(mid, lw, z, prec, whole=True)


def head_logits(conf: dict, w: dict, tokens, prec: dict | None = None):
    """Every position's logits over the rows of the head given: (B, T, V)."""
    z, prec = sizes(conf), _precision(prec)
    with jax.default_matmul_precision("highest"):
        x, _, W = _trunk(w, jnp.asarray(tokens, jnp.int32), z, prec)
        return _rms(x, W["lnf_g"], z["eps"]) @ W["head_w"]
