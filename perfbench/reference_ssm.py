"""The plain reference for the ``tx`` family's Mamba-2 / expert block
(Nemotron-H's tower as Nemotron-Labs-TwoTower-30B-A3B configures it: the
configuration file holds its ``config.json`` keys and this file reads
the sizes from them).

Independent of ``learningorchestra_tpu``: nothing is imported from it.
From ``reference_tx.py`` come only the parts that are no model's: the
batch recipe and the reader of a saved model's files. Every layer is ONE
sublayer, ``x += Sublayer(RMSNorm(x))`` (eps ``layer_norm_epsilon``),
its kind the layer's letter of ``hybrid_override_pattern``; a final
RMSNorm before the head. On a row of ``T`` positions, ``h`` (T, d) the
normed input:

- ``M``, Mamba-2 (arXiv:2405.21060; nemotron_h's mixer): ``z = h Wz``,
  ``x' = h Wx``, ``[B', C'] = h W_bc``, ``dt' = h W_dt``; ``[x, B, C] =
  silu(conv(x', B', C') + b)``, a depthwise causal conv of width
  ``conv_kernel`` with a bias (zeros before the row's first token); ``x``
  is ``mamba_num_heads`` heads of ``mamba_head_dim``, ``B`` and ``C``
  ``n_groups`` groups of ``ssm_state_size``, a head's group its index
  over the heads a group has; ``dt = softplus(dt' + dt_bias)``, ``A =
  -exp(A_log)`` a head; per head a (head dim, state) state, ``S_0 = 0``:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D
  x_t``, run as written, TOKEN BY TOKEN (``lax.scan`` over ``T``); then
  ``RMSNorm_groups(y * silu(z)) * w`` (the gate first, the norm over
  groups of ``heads x head dim / n_groups`` channels); ``out = y Wo``;
- ``E``, experts: ``s = sigmoid(h W_r)`` over all ``n_routed_experts``;
  the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``
  are chosen (``n_group`` 1: no group limit); ``g = routed_scaling_factor
  * s_chosen / sum(s_chosen)``; each routed expert is ``relu(h U)^2 D``;
  the layer computes, PER HELD EXPERT over the tokens routed to it
  (gathered, then added back), the part the held experts give
  (``num_local_experts`` from ``experts_first``; what the absent experts
  would add is left out, as on the deployment's one chip), plus the
  shared expert ``relu(h U_s)^2 D_s`` on every token, once;
- ``*``, attention: grouped-query, causal softmax per head with scale
  ``head_dim^-0.5``, no bias, no QK-norm, no positions; ``out = o Wo``;
- loss: mean next-token cross-entropy over every position (the target
  of a row's last position is its label token, id = class), logits over
  the ``vocab_size`` rows held; ``loss_index`` is 0.

Departures from the published description, each because ``config.json``
gives sizes and not equations (the configuration file lists them under
``assumed``): the mixer's inner width is ``mamba_num_heads x
mamba_head_dim`` (the module's, not ``expand x hidden_size``); no rotary
(the module applies none though the config carries ``rope_theta``); the
correction bias is 0 and trains on no gradient.

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

#: Leaf -> group of the per-group gradient norms; a Mamba-2 leaf is also
#: reported on its own, under its name.
GROUPS = {
    "embed": "embedding", "head_w": "head", "lnf_g": "head",
    "ln1_g": "attention", "wq": "attention", "wk": "attention",
    "wv": "attention", "wo": "attention",
    "ln2_g": "experts", "we_up": "experts", "we_down": "experts",
    "router": "router", "router_bias": "router",
    "sh_up": "shared_expert", "sh_down": "shared_expert",
    **{"ssm_" + k: "ssm" for k in (
        "ln_g", "wz", "wx", "wbc", "wdt", "cx", "cbc", "cx_b", "cbc_b",
        "a_log", "dt_bias", "d", "gn_g", "wo")},
}
KIND = {"M": "M", "E": "E", "*": "F"}
Q_BLOCK = 256        # queries per block of the attention
POS_BLOCK = 2048     # positions per block of the head's logits
SCAN_BLOCK = 64      # tokens of the recurrence per checkpointed block


def sizes(conf: dict) -> dict:
    """The sizes this file needs, from the configuration's own keys."""
    kinds = [KIND[c] for c in conf["hybrid_override_pattern"]]
    L = conf["num_hidden_layers"]
    if len(kinds) != L:
        raise ValueError("hybrid_override_pattern does not list "
                         "num_hidden_layers kinds")
    if (conf["mlp_hidden_act"], conf["mamba_hidden_act"],
            conf["use_conv_bias"], conf["n_group"]) != ("relu2", "silu",
                                                       True, 1):
        raise ValueError("not this file's equations: relu2 experts, a "
                         "silu conv with a bias, one routing group")
    period = next(p for p in range(1, L + 1)
                  if L % p == 0 and kinds == kinds[:p] * (L // p))
    return {"L": L, "pattern": "".join(kinds[:period]),
            "d": conf["hidden_size"], "H": conf["num_attention_heads"],
            "G": conf["num_key_value_heads"], "D": conf["head_dim"],
            "Hs": conf["mamba_num_heads"], "P": conf["mamba_head_dim"],
            "N": conf["ssm_state_size"], "Gs": conf["n_groups"],
            "K": conf["conv_kernel"], "E": conf["n_routed_experts"],
            "held": conf["num_local_experts"],
            "first": conf["experts_first"],
            "topk": conf["num_experts_per_tok"],
            "f": conf["moe_intermediate_size"],
            "fs": conf["moe_shared_expert_intermediate_size"]
            * conf["n_shared_experts"],
            "scale": conf["routed_scaling_factor"],
            "renorm": conf["norm_topk_prob"],
            "V": conf["vocab_size"], "eps": conf["layer_norm_epsilon"],
            "std": conf["init"]["std"]}


def _leaves(z: dict) -> dict:
    """One layer's leaves by kind, ``{kind: {name: (shape, init)}}``."""
    d, Hs, P, N, Gs, K = z["d"], z["Hs"], z["P"], z["N"], z["Gs"], z["K"]
    return {
        "F": {"ln1_g": ((d,), "ones"), "wq": ((d, z["H"], z["D"]), "normal"),
              "wk": ((d, z["G"], z["D"]), "normal"),
              "wv": ((d, z["G"], z["D"]), "normal"),
              "wo": ((z["H"], z["D"], d), "normal")},
        "M": {"ssm_ln_g": ((d,), "ones"), "ssm_wz": ((d, Hs, P), "normal"),
              "ssm_wx": ((d, Hs, P), "normal"),
              "ssm_wbc": ((d, 2, Gs, N), "normal"),
              "ssm_wdt": ((d, Hs), "normal"),
              "ssm_cx": ((K, Hs, P), "normal"),
              "ssm_cbc": ((K, 2, Gs, N), "normal"),
              "ssm_cx_b": ((Hs, P), "zeros"),
              "ssm_cbc_b": ((2, Gs, N), "zeros"),
              "ssm_a_log": ((Hs,), "a_log"),
              "ssm_dt_bias": ((Hs,), "dt_bias"), "ssm_d": ((Hs,), "ones"),
              "ssm_gn_g": ((Hs, P), "ones"),
              "ssm_wo": ((Hs, P, d), "normal")},
        "E": {"ln2_g": ((d,), "ones"), "router": ((d, z["E"]), "normal"),
              "router_bias": ((z["E"],), "zeros"),
              "we_up": ((z["held"], d, z["f"]), "normal"),
              "we_down": ((z["held"], z["f"], d), "normal"),
              "sh_up": ((d, z["fs"]), "normal"),
              "sh_down": ((z["fs"], d), "normal")}}


def leaf_shapes(z: dict) -> dict:
    """``{path: (shape, kind)}``. A layer leaf is stacked over (periods,
    the layers of its kind in a period) first."""
    pat = z["pattern"]
    periods = z["L"] // len(pat)
    out = {"embed": ((z["V"], z["d"]), "normal"), "lnf_g": ((z["d"],), "ones"),
           "head_w": ((z["d"], z["V"]), "normal")}
    for kind, leaves in _leaves(z).items():
        if pat.count(kind):
            out.update({f"layers.{k}": ((periods, pat.count(kind)) + shape, i)
                        for k, (shape, i) in leaves.items()})
    return out


def init_weights(conf: dict, seed: int) -> dict:
    """The configuration's init recipe: top-level leaves in sorted order,
    then the layer leaves in sorted order, numbered from 0; with ``k_i =
    fold_in(PRNGKey(seed), i)`` a matrix or conv leaf is ``normal(k_i) *
    std``, ``ssm_a_log`` is ``log(uniform(k_i, 1, 16))``, ``ssm_dt_bias``
    the inverse softplus of ``exp(uniform(k_i, log 0.001, log 0.1))``,
    ``ssm_d`` and norm weights ones, biases zeros. ``{path: array}``."""
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
            out[path] = (jnp.ones if kind == "ones" else jnp.zeros)(
                shape, jnp.float32)
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


def _blocks(n: int, cap: int) -> int:
    return next(c for c in range(min(cap, n), 0, -1) if n % c == 0)


def attention(h, W, z, prec):
    """One row's attention on its normed input: ``o Wo`` (T, d)."""
    T = h.shape[0]
    op, att = prec["operands"], prec["attention_operands"]
    h = _down(h, op)
    q = jnp.einsum("td,dhe->the", h, W["wq"])
    k = jnp.einsum("td,dge->tge", h, W["wk"])
    v = jnp.einsum("td,dge->tge", h, W["wv"])
    q, k, v = (_down(a, att) for a in (q, k, v))
    H, D = q.shape[1:]
    rep = H // k.shape[1]
    kk, vv = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    block = _blocks(T, Q_BLOCK)

    def queries(start):
        t = start + jnp.arange(block)
        allowed = jnp.arange(T)[None, :] <= t[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        logits = jnp.einsum("the,she->hts", qb, kk) / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(allowed[None], logits, -jnp.inf), -1)
        return jnp.einsum("hts,she->the", _down(p, att), vv)

    o = jax.lax.map(jax.checkpoint(queries), jnp.arange(0, T, block))
    return jnp.einsum("the,hed->td", _down(o.reshape(T, H, D), op), W["wo"])


def ssm_scan(x, dt, A, B, C):
    """The recurrence, token by token. x (T, H, P); dt (T, H); A (H,);
    B, C (T, groups, N). ``(y (T, H, P) without the D skip, largest |S|
    reached)``."""
    T, H, P = x.shape
    N = B.shape[-1]
    r = H // B.shape[1]

    def token(S, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h, c_h = jnp.repeat(b_t, r, axis=0), jnp.repeat(c_t, r, axis=0)
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return S, (jnp.einsum("hpn,hn->hp", S, c_h),
                   jnp.abs(jax.lax.stop_gradient(S)).max())

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    n = _blocks(T, SCAN_BLOCK)
    xs = tuple(a.reshape((T // n, n) + a.shape[1:]) for a in (x, dt, B, C))
    _, (y, peak) = jax.lax.scan(block, jnp.zeros((H, P, N), jnp.float32), xs)
    return y.reshape(T, H, P), peak.max()


def ssm_mixer(h, W, z, prec, with_peak: bool = False):
    """One row's Mamba-2 mixer on its normed input: ``y Wo`` (T, d)."""
    T = h.shape[0]
    op, sc = prec["operands"], prec["scan_operands"]
    Hs, P, Gs, N, K = z["Hs"], z["P"], z["Gs"], z["N"], z["K"]
    h = _down(h, op)
    zg = jnp.einsum("td,dhp->thp", h, W["ssm_wz"])
    u = jnp.concatenate([
        jnp.einsum("td,dhp->thp", h, W["ssm_wx"]).reshape(T, Hs * P),
        jnp.einsum("td,dkgn->tkgn", h, W["ssm_wbc"]).reshape(T, 2 * Gs * N)],
        -1)
    dt = jax.nn.softplus(jnp.einsum("td,dh->th", h, W["ssm_wdt"])
                         + W["ssm_dt_bias"])
    w = jnp.concatenate([W["ssm_cx"].reshape(K, -1),
                         W["ssm_cbc"].reshape(K, -1)], -1)
    bias = jnp.concatenate([W["ssm_cx_b"].reshape(-1),
                            W["ssm_cbc_b"].reshape(-1)])
    u = jnp.concatenate([jnp.zeros((K - 1,) + u.shape[1:], u.dtype), u], 0)
    c = jax.nn.silu(sum(w[j] * u[j:j + T] for j in range(K)) + bias)
    x = c[:, :Hs * P].reshape(T, Hs, P)
    bc = c[:, Hs * P:].reshape(T, 2, Gs, N)
    y, peak = ssm_scan(_down(x, sc), dt, -jnp.exp(W["ssm_a_log"]),
                       _down(bc[:, 0], sc), _down(bc[:, 1], sc))
    y = (y + W["ssm_d"][:, None] * x) * jax.nn.silu(zg)
    y = (_rms(y.reshape(T, Gs, -1), 1.0, z["eps"]).reshape(T, Hs, P)
         * W["ssm_gn_g"])
    out = jnp.einsum("thp,hpd->td", _down(y, op), W["ssm_wo"])
    return (out, peak) if with_peak else out


def experts(h, W, z, prec, whole: bool = False):
    """One row's expert layer on its normed input: the held experts'
    part, each expert over the tokens routed to it, plus the shared
    expert (with ``whole`` every expert is held: ``W`` carries all)."""
    T, d = h.shape
    op = prec["operands"]
    s = jax.nn.sigmoid(h @ W["router"])
    _, top_e = jax.lax.top_k(s + W["router_bias"], z["topk"])
    top_s = jnp.take_along_axis(s, top_e, -1)
    if z["renorm"]:
        top_s = top_s / top_s.sum(-1, keepdims=True)
    g = z["scale"] * top_s
    hd = _down(h, op)
    first = 0 if whole else z["first"]

    def relu2(a):
        return _down(jnp.square(jax.nn.relu(a)), op)

    def one(acc, args):
        e, wu, wd = args
        mine = top_e == first + e
        gate = jnp.sum(jnp.where(mine, g, 0.0), -1)
        rows = jnp.nonzero(mine.any(-1), size=T, fill_value=T)[0]
        xs = hd.at[rows].get(mode="fill", fill_value=0.0)
        y = gate.at[rows].get(mode="fill", fill_value=0.0)[:, None] * (
            relu2(xs @ wu) @ wd)
        return acc.at[rows].add(y, mode="drop"), None

    part, _ = jax.lax.scan(one, jnp.zeros((T, d), jnp.float32), (
        jnp.arange(W["we_up"].shape[0]), W["we_up"], W["we_down"]))
    return part + relu2(hd @ W["sh_up"]) @ W["sh_down"]


_NORM = {"F": "ln1_g", "M": "ssm_ln_g", "E": "ln2_g"}


def layer(x, W, kind: str, z, prec):
    """One layer on one row: ``x + Sublayer(RMSNorm(x))``."""
    h = _rms(x, W[_NORM[kind]], z["eps"])
    if kind == "F":
        return x + attention(h, W, z, prec)
    if kind == "M":
        return x + ssm_mixer(h, W, z, prec)
    return x + experts(h, W, z, prec)


def layer_of(path: str, z: dict) -> list:
    """The model layers a stacked layer leaf's slices belong to, in the
    order of its two stacking axes flattened."""
    name = path[len("layers."):]
    mine = next(k for k, leaves in _leaves(z).items() if name in leaves)
    pat = z["pattern"]
    within = [j for j, kind in enumerate(pat) if kind == mine]
    return [p * len(pat) + j for p in range(z["L"] // len(pat))
            for j in within]


def unstack(w: dict, z: dict) -> dict:
    """``{path: array}`` with every stacked layer leaf split into one
    leaf a layer, ``layers.<i>.<name>``: the reference differentiates
    with respect to a layer's own arrays."""
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
        name = path.split(".")[-1]
        part = jnp.sum(g * g)
        sq[GROUPS[name]] = sq.get(GROUPS[name], 0.0) + part
        if GROUPS[name] == "ssm":
            sq[name] = sq.get(name, 0.0) + part
    return {k: jnp.sqrt(v) for k, v in sq.items()}


def _precision(prec: dict | None) -> dict:
    return dict({"weights": "float32", "operands": "float32",
                 "attention_operands": "float32",
                 "scan_operands": "float32"}, **(prec or {}))


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


def sublayer(conf: dict, W: dict, kind: str, h, prec: dict | None = None,
             whole: bool = False):
    """ONE sublayer (``kind`` M or E) on one row's normed input ``h`` (T,
    d): what the tests hold the program's to. For ``M``: ``(out, largest
    |S|)``; for ``E`` with ``whole``: every expert ``W`` carries held."""
    z, prec = sizes(conf), _precision(prec)
    with jax.default_matmul_precision("highest"):
        if kind == "M":
            return ssm_mixer(jnp.asarray(h), W, z, prec, True)
        return experts(jnp.asarray(h), W, z, prec, whole)
