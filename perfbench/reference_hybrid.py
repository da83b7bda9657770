"""The plain reference for the ``tx`` family's hybrid block (Olmo-Hybrid-7B:
the configuration file holds its ``config.json`` keys and this file reads
the sizes from them).

Independent of ``learningorchestra_tpu``: nothing is imported from it.
From ``reference_tx.py`` come only the parts that are no model's: the
batch recipe and the reader of a saved model's files. The equations, on a row of ``T`` positions, ``x`` (T, d):

- block (OLMo 2 / OLMo 3's reordered norm): ``h = x + RMSNorm(Mixer(x))``,
  ``y = h + RMSNorm(MLP(h))``, ``MLP(h) = (silu(h Wg) * (h Wu)) Wd``; a
  final RMSNorm before the head;
- full layer (``layer_types[i] == "full_attention"``): ``q = RMSNorm_all(
  x Wq)``, ``k = RMSNorm_all(x Wk)`` (one RMSNorm over the whole
  projection), ``v = x Wv``; no rotary and no learned position; causal
  softmax attention per head, scale ``D^-0.5``; ``out = o Wo``;
- linear layer (Gated DeltaNet, arXiv:2412.06464; per head a (dk, dv)
  state ``S``, ``S_0 = 0``): ``[q', k', v'] = x [Wq, Wk, Wv]``, ``z = x
  Wz``, ``b = x Wb``, ``a = x Wa``; ``c_t = silu(sum_j w_j u_{t-K+1+j})``
  on every channel ``u`` of ``[q', k', v']`` (depthwise, no bias, zeros
  before the row's first token); ``q_t = l2norm(q_t) dk^-0.5``, ``k_t =
  l2norm(k_t)`` (eps 1e-6 inside the root); ``beta_t = 2 sigmoid(b_t)``
  (the 2 is ``linear_allow_neg_eigval``); ``g_t = -exp(A_log) softplus(
  a_t + dt_bias)``, ``alpha_t = exp(g_t)``; ``S_t = alpha_t S_{t-1} +
  k_t (beta_t (v_t - (alpha_t S_{t-1})^T k_t))^T``, ``o_t = S_t^T q_t``;
  ``y_t = RMSNorm_dv(o_t) * silu(z_t)`` (a weight of length dv shared by
  the heads), ``out = concat_heads(y) Wo``. The recurrence is run as
  written, TOKEN BY TOKEN (``lax.scan`` over ``T``);
- loss: mean next-token cross-entropy over every position (the target of
  a row's last position is its label token, id = class), logits over the
  ``vocab_size`` rows held. There is no second loss: ``loss_index`` is 0.

Departures from the published description, each because ``config.json``
gives sizes and not equations (the configuration file lists them under
``assumed``): the block order and the whole-projection QK-norm are OLMo
2/3's; no rotary because ``rope_parameters.rope_theta`` is null; the
linear layer's equations are Gated DeltaNet's as Qwen3-Next's module in
Hugging Face ``transformers`` writes them, whose keys the config uses.
The heads and vocabulary rows given are the share the file states; what
the absent heads would add (to ``o Wo`` and to the QK-norm's mean of
squares) is left out, as on the deployment's one chip.

Weights from the seed by the stated recipe (``init_weights``), batches by
``reference_tx.batch_rows``, Adam written out here. Everything float32
with every product at ``highest`` precision; blocks and
``jax.checkpoint`` only so that a row of 8,192 positions and its
backward fit. The control rounds weights and operands one type further
down than the configuration states (``precision.control``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference_tx import batch_rows, load_saved  # noqa: F401

#: Leaf -> group of the per-group gradient norms.
GROUPS = {
    "embed": "embedding", "head_w": "head", "lnf_g": "head",
    "ln1_g": "attention", "wq": "attention", "wk": "attention",
    "wv": "attention", "wo": "attention", "q_norm": "attention",
    "k_norm": "attention",
    "ln2_g": "mlp", "w_gate": "mlp", "w_up": "mlp", "w_down": "mlp",
    **{"la_" + k: "linear_attention" for k in (
        "ln_g", "wq", "wk", "wv", "wz", "wb", "wa", "cq", "ck", "cv",
        "a_log", "dt_bias", "gn_g", "wo")},
}
Q_BLOCK = 256        # queries per block of the full attention
POS_BLOCK = 2048     # positions per block of the MLP and the head's logits
SCAN_BLOCK = 64      # tokens of the recurrence per checkpointed block
KIND = {"full_attention": "F", "linear_attention": "L"}


def sizes(conf: dict) -> dict:
    """The sizes this file needs, from the configuration's own keys."""
    types = [KIND[t] for t in conf["layer_types"]]
    L = conf["num_hidden_layers"]
    if len(types) != L:
        raise ValueError("layer_types does not list num_hidden_layers kinds")
    period = next(p for p in range(1, L + 1)
                  if L % p == 0 and types == types[:p] * (L // p))
    if conf["linear_num_key_heads"] != conf["linear_num_value_heads"]:
        raise ValueError("key and value heads of the linear layer differ: "
                         "not this file's equations")
    whole = conf.get("published", {}).get(
        "num_attention_heads", conf["num_attention_heads"])
    return {"L": L, "pattern": "".join(types[:period]),
            "d": conf["hidden_size"], "f": conf["intermediate_size"],
            "H": conf["num_attention_heads"],
            "G": conf["num_key_value_heads"],
            "D": conf["hidden_size"] // whole,
            "Hl": conf["linear_num_key_heads"],
            "dk": conf["linear_key_head_dim"],
            "dv": conf["linear_value_head_dim"],
            "K": conf["linear_conv_kernel_dim"],
            "neg": bool(conf["linear_allow_neg_eigval"]),
            "V": conf["vocab_size"], "eps": conf["rms_norm_eps"],
            "std": conf["init"]["std"]}


def leaf_shapes(z: dict) -> dict:
    """``{path: (shape, kind)}``. A layer leaf is stacked over (periods,
    the layers of its kind in a period) first: the full layer's leaves
    over the period's full layers, the linear mixer's (``la_``) over its
    linear layers, the MLP's over all of them."""
    d, f, H, G, D = z["d"], z["f"], z["H"], z["G"], z["D"]
    Hl, dk, dv, K = z["Hl"], z["dk"], z["dv"], z["K"]
    full = {"ln1_g": ((d,), "ones"), "wq": ((d, H, D), "normal"),
            "wk": ((d, G, D), "normal"), "wv": ((d, G, D), "normal"),
            "wo": ((H, D, d), "normal"), "q_norm": ((H, D), "ones"),
            "k_norm": ((G, D), "ones")}
    linear = {"la_ln_g": ((d,), "ones"), "la_wq": ((d, Hl, dk), "normal"),
              "la_wk": ((d, Hl, dk), "normal"),
              "la_wv": ((d, Hl, dv), "normal"),
              "la_wz": ((d, Hl, dv), "normal"), "la_wb": ((d, Hl), "normal"),
              "la_wa": ((d, Hl), "normal"), "la_cq": ((K, Hl, dk), "normal"),
              "la_ck": ((K, Hl, dk), "normal"),
              "la_cv": ((K, Hl, dv), "normal"), "la_a_log": ((Hl,), "a_log"),
              "la_dt_bias": ((Hl,), "dt_bias"), "la_gn_g": ((dv,), "ones"),
              "la_wo": ((Hl, dv, d), "normal")}
    every = {"ln2_g": ((d,), "ones"), "w_gate": ((d, f), "normal"),
             "w_up": ((d, f), "normal"), "w_down": ((f, d), "normal")}
    pat = z["pattern"]
    periods = z["L"] // len(pat)
    out = {"embed": ((z["V"], d), "normal"), "lnf_g": ((d,), "ones"),
           "head_w": ((d, z["V"]), "normal")}
    for leaves, count in ((full, pat.count("F")), (linear, pat.count("L")),
                          (every, len(pat))):
        if count:
            out.update({f"layers.{k}": ((periods, count) + shape, kind)
                        for k, (shape, kind) in leaves.items()})
    return out


def init_weights(conf: dict, seed: int) -> dict:
    """The configuration's init recipe: top-level leaves in sorted order,
    then the layer leaves in sorted order, numbered from 0; with ``k_i =
    fold_in(PRNGKey(seed), i)`` a matrix or conv leaf is ``normal(k_i) *
    std``, ``la_a_log`` is ``log(uniform(k_i, 1, 16))``, ``la_dt_bias``
    the inverse softplus of ``exp(uniform(k_i, log 0.001, log 0.1))``,
    norm weights ones. ``{path: array}``."""
    z = sizes(conf)
    shapes = leaf_shapes(z)
    top = sorted(p for p in shapes if not p.startswith("layers."))
    order = top + sorted(p for p in shapes if p.startswith("layers."))
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, path in enumerate(order):
        shape, kind = shapes[path]
        k = jax.random.fold_in(key, i)
        if kind == "normal":
            out[path] = jax.random.normal(k, shape, jnp.float32) * z["std"]
        elif kind == "a_log":
            out[path] = jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0))
        elif kind == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
            out[path] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            out[path] = jnp.ones(shape, jnp.float32)
    return out


# --- the equations ----------------------------------------------------------

def _down(x, dtype: str):
    """``x`` rounded to ``dtype`` and back: what computing that operand
    in the lower type loses. The cotangent passes unrounded (a float8
    cotangent over 448 would be NaN)."""
    if dtype == "float32":
        return x
    return x + jax.lax.stop_gradient(
        x.astype(jnp.dtype(dtype)).astype(jnp.float32) - x)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rms_all(x, g, eps):
    """One RMSNorm over the whole projection x (T, heads, D), g (heads, D)."""
    return x / jnp.sqrt(jnp.mean(x * x, (-2, -1), keepdims=True) + eps) * g


def _blocks(n: int, cap: int) -> int:
    return next(c for c in range(min(cap, n), 0, -1) if n % c == 0)


def full_attention(x, W, z, prec):
    """One row's full-attention mixer: ``o Wo`` (T, d)."""
    T = x.shape[0]
    op, att = prec["operands"], prec["attention_operands"]
    h = _down(x, op)
    q = _rms_all(jnp.einsum("td,dhe->the", h, W["wq"]), W["q_norm"], z["eps"])
    k = _rms_all(jnp.einsum("td,dge->tge", h, W["wk"]), W["k_norm"], z["eps"])
    v = jnp.einsum("td,dge->tge", h, W["wv"])
    q, k, v = (_down(a, att) for a in (q, k, v))
    rep = z["H"] // z["G"]
    kk, vv = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    block = _blocks(T, Q_BLOCK)

    def queries(start):
        t = start + jnp.arange(block)
        allowed = jnp.arange(T)[None, :] <= t[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        logits = jnp.einsum("the,she->hts", qb, kk) / np.sqrt(z["D"])
        p = jax.nn.softmax(jnp.where(allowed[None], logits, -jnp.inf), -1)
        return jnp.einsum("hts,she->the", _down(p, att), vv)

    o = jax.lax.map(jax.checkpoint(queries), jnp.arange(0, T, block))
    o = o.reshape(T, z["H"], z["D"])
    return jnp.einsum("the,hed->td", _down(o, op), W["wo"])


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, token by token. q, k (T, H, dk); v (T, H,
    dv); g, beta (T, H). ``(o (T, H, dv), largest |S| reached)``."""
    T, H, dk = q.shape
    dv = v.shape[-1]

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[:, None, None] * S
        write = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * write[:, None, :]
        return S, (jnp.einsum("hkv,hk->hv", S, q_t),
                   jnp.abs(jax.lax.stop_gradient(S)).max())

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    n = _blocks(T, SCAN_BLOCK)
    xs = tuple(a.reshape((T // n, n) + a.shape[1:])
               for a in (q, k, v, g, beta))
    _, (o, peak) = jax.lax.scan(block, jnp.zeros((H, dk, dv), jnp.float32),
                                xs)
    return o.reshape(T, H, dv), peak.max()


def linear_attention(x, W, z, prec, with_peak: bool = False):
    """One row's linear mixer: ``concat_heads(y) Wo`` (T, d)."""
    T = x.shape[0]
    op, att = prec["operands"], prec["attention_operands"]
    dk, K = z["dk"], z["K"]
    h = _down(x, op)
    u = jnp.concatenate([jnp.einsum("td,dhe->the", h, W[w])
                         for w in ("la_wq", "la_wk", "la_wv")], -1)
    zg = jnp.einsum("td,dhe->the", h, W["la_wz"])
    b = jnp.einsum("td,dh->th", h, W["la_wb"])
    a = jnp.einsum("td,dh->th", h, W["la_wa"])
    w = jnp.concatenate([W["la_cq"], W["la_ck"], W["la_cv"]], -1)
    u = jnp.concatenate([jnp.zeros((K - 1,) + u.shape[1:], u.dtype), u], 0)
    c = jax.nn.silu(sum(w[j] * u[j:j + T] for j in range(K)))

    def l2norm(y):
        return y / jnp.sqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    q = l2norm(c[..., :dk]) * dk ** -0.5
    k, v = l2norm(c[..., dk:2 * dk]), c[..., 2 * dk:]
    beta = jax.nn.sigmoid(b) * (2.0 if z["neg"] else 1.0)
    g = -jnp.exp(W["la_a_log"]) * jax.nn.softplus(a + W["la_dt_bias"])
    o, peak = delta_rule(*(_down(t, att) for t in (q, k, v)), g, beta)
    y = _rms(o, W["la_gn_g"], z["eps"]) * jax.nn.silu(zg)
    out = jnp.einsum("the,hed->td", _down(y, op), W["la_wo"])
    return (out, peak) if with_peak else out


def mlp(h, W, prec):
    """``(silu(h Wg) * (h Wu)) Wd`` on one row, a block of positions at a
    time."""
    T, d = h.shape
    block = _blocks(T, POS_BLOCK)

    @jax.checkpoint
    def part(hb):
        hb = _down(hb, prec["operands"])
        y = jax.nn.silu(hb @ W["w_gate"]) * (hb @ W["w_up"])
        return _down(y, prec["operands"]) @ W["w_down"]

    return jax.lax.map(part, h.reshape(T // block, block, d)).reshape(T, d)


def layer(x, W, kind: str, z, prec):
    """One block on one row: ``x + RMSNorm(Mixer(x))``, then ``+ RMSNorm(
    MLP(.))``."""
    if kind == "F":
        x = x + _rms(full_attention(x, W, z, prec), W["ln1_g"], z["eps"])
    else:
        x = x + _rms(linear_attention(x, W, z, prec), W["la_ln_g"], z["eps"])
    return x + _rms(mlp(x, W, prec), W["ln2_g"], z["eps"])


def layer_of(path: str, z: dict) -> list:
    """The model layers a stacked layer leaf's slices belong to, in the
    order of its two stacking axes flattened."""
    name = path[len("layers."):]
    mine = "L" if name.startswith("la_") else (
        "*" if GROUPS[name] == "mlp" else "F")
    pat = z["pattern"]
    within = [j for j, kind in enumerate(pat) if mine in (kind, "*")]
    return [p * len(pat) + j for p in range(z["L"] // len(pat))
            for j in within]


def unstack(w: dict, z: dict) -> dict:
    """``{path: array}`` with every stacked layer leaf split into one
    leaf a layer, ``layers.<i>.<name>``: the reference differentiates
    with respect to a layer's own arrays, so no gradient is a slice
    padded back into a stack."""
    out = {}
    for path, a in w.items():
        if not path.startswith("layers."):
            out[path] = a
            continue
        flat = a.reshape((-1,) + a.shape[2:])
        for n, i in enumerate(layer_of(path, z)):
            out[f"layers.{i}.{path[len('layers.'):]}"] = flat[n]
    return out


def _trunk(w, tokens, z, prec):
    """(B, T) tokens -> ``(x (B, T, d) before the final norm, the
    top-level weights as rounded)``; ``w`` unstacked."""
    W = {p: _down(a, prec["weights"]) for p, a in w.items()
         if not p.startswith("layers.")}
    x = W["embed"][tokens]
    pat = z["pattern"]
    for i in range(z["L"]):
        lead = f"layers.{i}."
        lw = {p[len(lead):]: _down(a, prec["weights"])
              for p, a in w.items() if p.startswith(lead)}
        one = jax.checkpoint(partial(layer, kind=pat[i % len(pat)], z=z,
                                     prec=prec))
        x = jax.lax.map(lambda xr: one(xr, lw), x)
    return x, W


def loss_parts(w, tokens, labels, z, prec):
    """``(L_LM, 0)`` of a batch: tokens (B, T) int32, labels (B,)."""
    x, W = _trunk(w, tokens, z, prec)
    B, T = tokens.shape
    targets = jnp.concatenate([tokens[:, 1:], labels[:, None]], 1)
    h = _down(_rms(x, W["lnf_g"], z["eps"]), prec["operands"])
    n = B * T
    block = _blocks(n, POS_BLOCK)

    @jax.checkpoint
    def positions(args):
        hb, tb = args
        logp = jax.nn.log_softmax(hb @ W["head_w"], -1)
        return -jnp.take_along_axis(logp, tb[:, None], 1).sum()

    ce = jax.lax.map(positions, (h.reshape(n // block, block, -1),
                                 targets.reshape(n // block, block)))
    return ce.sum() / n, jnp.zeros((), jnp.float32)


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
        w = {p: jnp.array(a, jnp.float32) for p, a in unstack(w, z).items()}
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
        x, W = _trunk(w, row[None], z, prec)
        last = _down(_rms(x[0, -1], W["lnf_g"], z["eps"]), prec["operands"])
        return jax.nn.softmax(last @ W["head_w"][:, :n_classes])

    with jax.default_matmul_precision("highest"):
        w = {p: jnp.asarray(a, jnp.float32) for p, a in unstack(w, z).items()}
        return np.stack([np.asarray(one(w, jnp.asarray(r, jnp.int32)))
                         for r in np.asarray(tokens)])


def mixer(conf: dict, W: dict, kind: str, x, prec: dict | None = None):
    """ONE mixer (``kind`` F or L) on one row ``x`` (T, d) with the
    leaves ``W`` of however many heads they carry: the uncut sublayer
    the tests add the shares up to. For ``L``: ``(out, largest |S|)``."""
    z, prec = sizes(conf), _precision(prec)
    with jax.default_matmul_precision("highest"):
        if kind == "L":
            return linear_attention(jnp.asarray(x), W, z, prec, True)
        z = dict(z, H=W["wq"].shape[1], G=W["wk"].shape[1])
        return full_attention(jnp.asarray(x), W, z, prec)


def head_logits(conf: dict, w: dict, tokens, prec: dict | None = None):
    """Every position's logits over the rows of the head given: (B, T, V)."""
    z, prec = sizes(conf), _precision(prec)
    with jax.default_matmul_precision("highest"):
        x, W = _trunk(unstack(w, z), jnp.asarray(tokens, jnp.int32), z, prec)
        return _rms(x, W["lnf_g"], z["eps"]) @ W["head_w"]
