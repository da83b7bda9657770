"""The plain reference for the five classifier families.

Independent of ``learningorchestra_tpu``: nothing is imported from it and
nothing it has made (weights, bin edges, trees) is read. Given the same
two tables and the configuration's hyperparameters it fits lr, nb, dt,
rf and gb itself, in straightforward ``jax.numpy`` at float32 with every
matrix product at ``Precision.HIGHEST``, and returns each family's
probability of class 1 for every row of the test table. The benchmark
compares what a client read back from the timed sweeps with these.

What the configuration states, and this file follows (the numbers are in
``perfbench/configs/<name>.json`` under ``families``):

- lr: L2-penalised logistic regression on standardised features
  (population mean / sd of the train table). Up to ``newton_max_cd``
  coefficients it is solved by ``newton_steps`` damped Newton steps from
  zero; above, by ``adam_steps`` full-batch Adam steps from
  ``0.01 * normal(PRNGKey(seed))``.
- nb: Gaussian naive Bayes, per-class variance floored and smoothed.
- dt / rf / gb: level-wise histogram trees over ``n_bins`` quantile bins
  (edges: ``numpy.quantile`` over the ``edge_sample`` rows drawn by
  ``numpy.random.default_rng(0).choice``), split by gini (dt, rf) or by
  the Newton gain on gradient / hessian sums (gb). rf draws Poisson(1)
  row weights and ``mtry`` features per tree from the key chain
  ``split(PRNGKey(seed), n_trees)[t] -> split -> (fold_in(., 0), .)``;
  those draws are inputs in the sense the seed is, so they are made
  with the same library calls.

Arrays keep features in rows and table rows in the last axis: an
``(n, 28)`` float32 array is laid out 128 wide on a TPU.

Where the configuration states a type below float32 for a part (gb sums
bfloat16-rounded gradients), the reference rounds that part so; the
control (``fit_predict(precision=<the configuration's control block>)``)
rounds every such part, and the tables, one type further down.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30
#: Two candidate splits of a node whose gini gains (as shares of the node's
#: weight) lie closer than this are a tie in float32: the three impurity
#: terms of a gain each carry an error near 6e-8 of the weight.
TIE = 2e-6
#: Rows per block of the blocked passes (a multiple of 128).
BLOCK = 1 << 16


def _pad_rows(a: np.ndarray, n_pad: int) -> np.ndarray:
    pad = n_pad - a.shape[-1]
    if pad == 0:
        return a
    width = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
    return np.pad(a, width)


def _blocks(n: int):
    block = min(BLOCK, -(-n // 128) * 128)
    nb = -(-n // block)
    return block, nb, nb * block


def _rounded(x, dtype):
    """``x`` through ``dtype`` and back to float32 (None: untouched)."""
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def _round_to(XT: np.ndarray, data_dtype) -> jax.Array:
    """The table on the device, through ``data_dtype`` and back to f32."""
    return _rounded(jnp.asarray(XT), data_dtype)


def dtype_of(name):
    """The stated type by name; float32 is None (nothing is rounded)."""
    return None if name in (None, "float32") else jnp.dtype(name)


# -- quantile bins ----------------------------------------------------------

def quantile_edges(XT: np.ndarray, n_bins: int, sample: int) -> np.ndarray:
    """(d, n_bins - 1) float32 edges from the stated row sample."""
    n = XT.shape[1]
    if n > sample:
        idx = np.random.default_rng(0).choice(n, sample, replace=False)
        Xs = XT[:, idx]
    else:
        Xs = XT
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    return np.ascontiguousarray(
        np.quantile(Xs, qs, axis=1).T.astype(np.float32))


@jax.jit
def _bin_block(xt, edges):
    # code = number of edges strictly below the value
    return (xt[:, None, :] > edges[:, :, None]).sum(axis=1).astype(jnp.uint8)


def bin_codes(XT: jax.Array, edges: jax.Array) -> jax.Array:
    """(d, n) uint8 codes, made block by block."""
    block, nb, n_pad = _blocks(XT.shape[1])
    assert n_pad == XT.shape[1]
    return jnp.concatenate(
        [_bin_block(XT[:, i * block:(i + 1) * block], edges)
         for i in range(nb)], axis=1)


# -- one histogram tree -----------------------------------------------------

def _gini_gain(left, total):
    right = total - left
    lw, rw, tw = left.sum(-1), right.sum(-1), total.sum(-1)

    def impurity(c, w):
        return w - (c ** 2).sum(-1) / jnp.maximum(w, 1e-12)

    return (impurity(total, tw) - impurity(left, lw)
            - impurity(right, rw)) / jnp.maximum(tw, 1e-12)


def _newton_gain(lam):
    def gain(left, total):
        right = total - left
        return (left[..., 0] ** 2 / (left[..., 1] + lam)
                + right[..., 0] ** 2 / (right[..., 1] + lam)
                - total[..., 0] ** 2 / (total[..., 1] + lam))

    return gain


def _level_hist(codes, stats, rel, active, nl, n_bins, block):
    """(nl, d, n_bins, S): per node of this level, feature and bin, the
    sum of each statistic over the rows there. A dense product of two
    one-hot operands per row block; float32 all through."""
    d, n_pad = codes.shape
    S = stats.shape[0]
    bins = jnp.arange(n_bins, dtype=jnp.uint8)[None, :, None]
    nodes = jnp.arange(nl, dtype=jnp.int32)[:, None]

    def body(hist, i):
        c = jax.lax.dynamic_slice_in_dim(codes, i * block, block, axis=1)
        s = jax.lax.dynamic_slice_in_dim(stats, i * block, block, axis=1)
        r = jax.lax.dynamic_slice_in_dim(rel, i * block, block)
        a = jax.lax.dynamic_slice_in_dim(active, i * block, block)
        at_node = ((r[None, :] == nodes) & a[None, :]).astype(jnp.float32)
        A = (at_node[:, None, :] * s[None, :, :]).reshape(nl * S, block)
        O = (c[:, None, :] == bins).astype(jnp.float32).reshape(
            d * n_bins, block)
        return hist + jax.lax.dot_general(
            A, O, (((1,), (1,)), ((), ())), precision=HI), None

    hist, _ = jax.lax.scan(body, jnp.zeros((nl * S, d * n_bins), jnp.float32),
                           jnp.arange(n_pad // block))
    return hist.reshape(nl, S, d, n_bins).transpose(0, 2, 3, 1)


def _lookup(table, idx):
    """``table[idx]`` for a small table and a block of indices, as a
    compare and a sum (a gather of this shape is slow on the chip)."""
    hit = idx[None, :] == jnp.arange(table.shape[0], dtype=idx.dtype)[:, None]
    return jnp.where(hit, table[:, None], 0).sum(axis=0)


def _by_block(fn, block, *arrays):
    """``fn`` over row blocks of arrays whose last axis is the rows; the
    results, one row-vector a block, joined again."""
    nb = arrays[0].shape[-1] // block
    parts = tuple(jnp.moveaxis(
        a.reshape(a.shape[:-1] + (nb, block)), -2, 0) for a in arrays)
    return jax.lax.map(lambda xs: fn(*xs), parts).reshape(nb * block)


def _leaf_stats(node, stats, M, block):
    """(M, S) sums of ``stats`` over the rows that ended in each node."""
    ids = jnp.arange(M, dtype=jnp.int32)[:, None]

    def body(acc, i):
        nd = jax.lax.dynamic_slice_in_dim(node, i * block, block)
        s = jax.lax.dynamic_slice_in_dim(stats, i * block, block, axis=1)
        at = (nd[None, :] == ids).astype(jnp.float32)
        return acc + jax.lax.dot_general(
            at, s, (((1,), (1,)), ((), ())), precision=HI), None

    acc, _ = jax.lax.scan(body, jnp.zeros((M, stats.shape[0]), jnp.float32),
                          jnp.arange(node.shape[0] // block))
    return acc


def build_tree(codes, stats, feat_mask, *, depth, n_bins, gain_fn, weight_fn,
               min_child_weight, min_gain, block):
    """One tree, level by level. Nodes are numbered as a binary heap.
    Returns (feat, thr, internal, leaf_stats (M, S), node of each row,
    tied (M,): internal nodes whose best two candidates lie within
    ``TIE``, so that float32 cannot say which is the split)."""
    d, n_pad = codes.shape
    M = 2 ** (depth + 1) - 1
    feat = jnp.zeros((M,), jnp.int32)
    thr = jnp.zeros((M,), jnp.int32)
    internal = jnp.zeros((M,), bool)
    tied = jnp.zeros((M,), bool)
    node = jnp.zeros((n_pad,), jnp.int32)
    features = jnp.arange(d, dtype=jnp.int32)[:, None]
    for level in range(depth):
        nl, off = 2 ** level, 2 ** level - 1
        rel = node - off
        active = (rel >= 0) & (rel < nl)
        hist = _level_hist(codes, stats, rel, active, nl, n_bins, block)
        left = jnp.cumsum(hist, axis=2)
        total = left[:, :, -1:, :]
        gain = gain_fn(left, total).at[:, :, -1].set(NEG)
        lw = weight_fn(left)
        rw = weight_fn(total) - lw
        ok = (lw >= min_child_weight) & (rw >= min_child_weight)
        gain = jnp.where(ok, gain, NEG) + feat_mask[None, :, None]
        flat = gain.reshape(nl, d * n_bins)
        best = jnp.argmax(flat, axis=1)
        top = jnp.max(flat, axis=1)
        split = top > min_gain
        second = jnp.max(jnp.where(
            jnp.arange(d * n_bins)[None, :] == best[:, None], NEG, flat),
            axis=1)
        tied = tied.at[off:off + nl].set(split & (top - second <= TIE))
        best_f = jnp.where(split, best // n_bins, 0).astype(jnp.int32)
        best_t = jnp.where(split, best % n_bins, 0).astype(jnp.int32)
        feat = feat.at[off:off + nl].set(best_f)
        thr = thr.at[off:off + nl].set(best_t)
        internal = internal.at[off:off + nl].set(split)

        def route(c, nd, best_f=best_f, best_t=best_t, split=split,
                  off=off, nl=nl):
            r = jnp.clip(nd - off, -1, nl)
            here = (r >= 0) & (r < nl) & (_lookup(split.astype(jnp.int32),
                                                  r) > 0)
            code = jnp.where(_lookup(best_f, r)[None, :] == features,
                             c.astype(jnp.int32), 0).sum(axis=0)
            right = (code > _lookup(best_t, r)).astype(jnp.int32)
            return jnp.where(here, 2 * nd + 1 + right, nd)

        node = _by_block(route, block, codes, node)
    return (feat, thr, internal, _leaf_stats(node, stats, M, block), node,
            tied)


def descend(codes, feat, thr, internal, depth):
    """The heap id of the leaf every row of ``codes`` (d, n) ends in."""
    node = jnp.zeros((codes.shape[1],), jnp.int32)
    rows = jnp.arange(codes.shape[1])
    for _ in range(depth):
        right = codes[feat[node], rows].astype(jnp.int32) > thr[node]
        node = jnp.where(internal[node], 2 * node + 1 + right, node)
    return node


# -- dt and rf ----------------------------------------------------------------

@partial(jax.jit, static_argnames=("depth", "n_bins", "block"))
def _fit_class_trees(codes, y, weights, feat_masks, *, depth, n_bins, block):
    """Gini trees, one per row of ``weights`` (T, n) / ``feat_masks``."""
    classes = jnp.arange(2, dtype=y.dtype)[:, None]
    base = (y[None, :] == classes).astype(jnp.float32)       # (2, n)

    def one(w, fmask):
        f, t, it, leaf, _, tied = build_tree(
            codes, base * w[None, :], fmask, depth=depth, n_bins=n_bins,
            gain_fn=_gini_gain, weight_fn=lambda s: s.sum(-1),
            min_child_weight=1.0, min_gain=1e-9, block=block)
        return f, t, it, leaf, tied

    # A few trees side by side, the batches one after another: the row
    # state of all the trees at once would not fit at 11M rows.
    T = weights.shape[0]
    tb = 5 if T % 5 == 0 else 1
    out = jax.lax.map(
        lambda xs: jax.vmap(one)(*xs),
        (weights.reshape(T // tb, tb, -1), feat_masks.reshape(T // tb, tb, -1)))
    return jax.tree.map(lambda a: a.reshape((T,) + a.shape[2:]), out)


@partial(jax.jit, static_argnames=("depth",))
def _below_a_tie(codes, feat, thr, internal, tied, *, depth):
    """Rows of ``codes`` (d, n) whose path through the tree passes a
    tied node: below it the tree is one of two that are equally right."""
    node = jnp.zeros((codes.shape[1],), jnp.int32)
    rows = jnp.arange(codes.shape[1])
    unsure = jnp.zeros((codes.shape[1],), bool)
    for _ in range(depth):
        unsure |= tied[node]
        right = codes[feat[node], rows].astype(jnp.int32) > thr[node]
        node = jnp.where(internal[node], 2 * node + 1 + right, node)
    return unsure


@partial(jax.jit, static_argnames=("depth",))
def _class_trees_p1(codes_test, feat, thr, internal, leaf, *, depth):
    def one(f, t, it, lf):
        counts = lf[descend(codes_test, f, t, it, depth)]     # (n, 2)
        return counts / jnp.maximum(counts.sum(-1, keepdims=True), 1e-12)

    return jax.vmap(one)(feat, thr, internal, leaf).mean(axis=0)[:, 1]


def _forest_draws(seed, n_trees, n, n_pad, d, mtry):
    """Row weights (T, n_pad) and feature masks (T, d) of the forest."""
    keys = jax.random.split(jax.random.PRNGKey(seed), n_trees)

    def one(key):
        kb, kf = jax.random.split(key)
        kb = jax.random.fold_in(kb, 0)
        w = jax.random.poisson(kb, 1.0, (n,)).astype(jnp.float32)
        perm = jax.random.permutation(kf, d)
        allowed = jnp.zeros((d,), bool).at[perm[:mtry]].set(True)
        return jnp.pad(w, (0, n_pad - n)), jnp.where(allowed, 0.0, NEG)

    return jax.lax.map(one, keys)


# -- gb -------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("depth", "n_bins", "rounds", "block",
                                   "stat_dtype"))
def _fit_gb(codes, y, valid, *, depth, n_bins, rounds, step, lam, block,
            stat_dtype=None):
    yf = y.astype(jnp.float32)
    no_mask = jnp.zeros((codes.shape[0],), jnp.float32)

    def boost(margin, _):
        p = jax.nn.sigmoid(margin)
        g = (p - yf) * valid
        h = jnp.maximum(p * (1 - p), 1e-6) * valid
        # the gradient statistics enter every sum in the stated type
        stats = _rounded(jnp.stack([g, h]), stat_dtype)
        f, t, it, leaf, node, _ = build_tree(
            codes, stats, no_mask, depth=depth, n_bins=n_bins,
            gain_fn=_newton_gain(lam), weight_fn=lambda s: s[..., 1],
            min_child_weight=1e-3, min_gain=1e-9, block=block)
        value = -leaf[:, 0] / (leaf[:, 1] + lam)
        return margin + step * _by_block(
            lambda nd: _lookup(value, nd), block, node), (f, t, it, value)

    _, trees = jax.lax.scan(boost, jnp.zeros_like(yf), None, length=rounds)
    return trees


@partial(jax.jit, static_argnames=("depth",))
def _gb_p1(codes_test, feat, thr, internal, value, step, *, depth):
    def one(f, t, it, v):
        return v[descend(codes_test, f, t, it, depth)]

    margin = step * jax.vmap(one)(feat, thr, internal, value).sum(axis=0)
    return jax.nn.sigmoid(margin)


# -- lr -------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("steps", "block", "operand_dtype",
                                   "result_dtype"))
def _fit_lr_newton(ZT, y, valid, n, *, steps, l2, block, operand_dtype=None,
                   result_dtype=None):
    """Two-class softmax regression, (d+1, 2) weights, damped Newton.
    ``operand_dtype`` (the control's) rounds what enters the matrix
    products: the design, the probabilities, the residual;
    ``result_dtype`` what comes out of each (a product of two arrays of
    one type comes back in that type unless asked otherwise)."""
    q = partial(_rounded, dtype=operand_dtype)
    r = partial(_rounded, dtype=result_dtype)
    d1, n_pad = ZT.shape
    ridge = jnp.tile(jnp.concatenate(
        [jnp.full((d1 - 1,), 2.0 * l2), jnp.zeros((1,))]), 2) + 1e-4
    onehot = (y[None, :] == jnp.arange(2)[:, None]).astype(jnp.float32)

    def step(W, _):
        def body(carry, i):
            g, H = carry
            z = jax.lax.dynamic_slice_in_dim(ZT, i * block, block, axis=1)
            yb = jax.lax.dynamic_slice_in_dim(onehot, i * block, block, 1)
            v = jax.lax.dynamic_slice_in_dim(valid, i * block, block)
            logits = r(jnp.einsum("dc,dn->cn", q(W), z, precision=HI))
            p = jax.nn.softmax(logits, axis=0) * v[None, :]
            g = g + r(jnp.einsum("cn,dn->cd", q(p - yb * v[None, :]), z,
                                 precision=HI))
            p = q(p)
            # H[(c,i),(c',j)] = sum_n z_i z_j p_c (delta_cc' - p_c')
            zz = [r(jnp.einsum("in,jn->ij", q(z * p[c][None, :]), z,
                               precision=HI)) for c in range(2)]
            zpp = [[r(jnp.einsum("in,jn->ij", q(z * p[c][None, :]),
                                 q(z * p[e][None, :]), precision=HI))
                    for e in range(2)] for c in range(2)]
            Hb = jnp.block([[zz[0] - zpp[0][0], -zpp[0][1]],
                            [-zpp[1][0], zz[1] - zpp[1][1]]])
            return (g, H + Hb), None

        (g, H), _ = jax.lax.scan(
            body, (jnp.zeros((2, d1), jnp.float32),
                   jnp.zeros((2 * d1, 2 * d1), jnp.float32)),
            jnp.arange(n_pad // block))
        w = W.T.reshape(2 * d1)
        grad = g.reshape(2 * d1) / n + ridge * w
        delta = jnp.linalg.solve(H / n + jnp.diag(ridge), grad)
        delta = delta * jnp.minimum(
            1.0, 5.0 / jnp.maximum(jnp.linalg.norm(delta), 1e-12))
        return W - delta.reshape(2, d1).T, None

    W, _ = jax.lax.scan(step, jnp.zeros((d1, 2), jnp.float32), None,
                        length=steps)
    return W


@partial(jax.jit, static_argnames=("steps", "operand_dtype", "result_dtype"))
def _fit_lr_adam(XsT, y, valid, n, W0, *, steps, lr, l2, operand_dtype=None,
                 result_dtype=None):
    """Full-batch Adam (b1 .9, b2 .999, eps 1e-8) on the mean log loss
    plus ``l2 * |W|^2``; the bias is not penalised. The two types round
    the weights entering the product and the logits leaving it."""
    q = partial(_rounded, dtype=operand_dtype)
    r = partial(_rounded, dtype=result_dtype)

    def loss(params):
        W, b = params
        logits = r(jnp.einsum("dc,dn->cn", q(W), XsT, precision=HI)) \
            + b[:, None]
        logp = jax.nn.log_softmax(logits, axis=0)
        nll = -jnp.where(y == 1, logp[1], logp[0])
        return (nll * valid).sum() / n + l2 * (W ** 2).sum()

    def step(carry, i):
        params, m, v = carry
        grads = jax.grad(loss)(params)
        m = jax.tree.map(lambda a, g: 0.9 * a + 0.1 * g, m, grads)
        v = jax.tree.map(lambda a, g: 0.999 * a + 0.001 * g * g, v, grads)
        t = (i + 1).astype(jnp.float32)
        params = jax.tree.map(
            lambda p, a, b: p - lr * (a / (1 - 0.9 ** t))
            / (jnp.sqrt(b / (1 - 0.999 ** t)) + 1e-8), params, m, v)
        return (params, m, v), None

    params = (W0, jnp.zeros((2,), jnp.float32))
    zeros = jax.tree.map(jnp.zeros_like, params)
    (params, _, _), _ = jax.lax.scan(step, (params, zeros, zeros),
                                     jnp.arange(steps))
    return params


def _fit_predict_lr(XT, y, valid, n, XT_test, hp, data_dtype,
                    result_dtype=None):
    """``data_dtype``: what the standardised design and the operands of
    the solver's and the predict pass's products are rounded to;
    ``result_dtype``: what their results are rounded to (None: float32)."""
    q = partial(_rounded, dtype=data_dtype)
    r = partial(_rounded, dtype=result_dtype)
    d, n_pad = XT.shape
    block = _blocks(n_pad)[0]
    mu = (XT * valid[None, :]).sum(axis=1) / n
    var = (((XT - mu[:, None]) * valid[None, :]) ** 2).sum(axis=1) / n
    sigma = jnp.sqrt(var)
    sigma = jnp.where(sigma < 1e-7, 1.0, sigma)

    def standardise(x):
        z = (x - mu[:, None]) / sigma[:, None]
        if data_dtype is not None:
            z = z.astype(data_dtype).astype(jnp.float32)
        return z

    Zs, Zt = standardise(XT), standardise(XT_test)
    if 2 * (d + 1) <= hp["newton_max_cd"]:
        ones = jnp.ones((1, n_pad), jnp.float32)
        W = _fit_lr_newton(jnp.concatenate([Zs, ones]), y, valid,
                           jnp.float32(n), steps=hp["newton_steps"],
                           l2=hp["l2"], block=block,
                           operand_dtype=data_dtype,
                           result_dtype=result_dtype)
        logits = r(jnp.einsum("dc,dn->cn", q(W[:d]), Zt, precision=HI)) \
            + W[d][:, None]
    else:
        W0 = 0.01 * jax.random.normal(jax.random.PRNGKey(hp["seed"]),
                                      (d, 2), jnp.float32)
        W, b = _fit_lr_adam(Zs, y, valid, jnp.float32(n), W0,
                            steps=hp["adam_steps"], lr=hp["adam_lr"],
                            l2=hp["l2"], operand_dtype=data_dtype,
                            result_dtype=result_dtype)
        logits = r(jnp.einsum("dc,dn->cn", q(W), Zt, precision=HI)) \
            + b[:, None]
    return jax.nn.softmax(logits, axis=0)[1]


# -- nb -------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("operand_dtype",))
def _fit_predict_nb(XT, y, valid, XT_test, smoothing, var_floor,
                    operand_dtype=None):
    """Gaussian naive Bayes through its four matrix products: the two
    per-class moment sums, and the log-likelihood in expanded form,
    ``sum_d (x - m)^2 / v = x^2 . (1/v) - 2 x . (m/v) + sum m^2/v`` on
    features centred by the across-class mean. ``operand_dtype`` (the
    control's) rounds what enters those products."""
    q = partial(_rounded, dtype=operand_dtype)
    onehot = ((y[None, :] == jnp.arange(2)[:, None]).astype(jnp.float32)
              * valid[None, :])                               # (2, n)
    counts = onehot.sum(axis=1)
    centre = (XT * valid[None, :]).sum(axis=1) / counts.sum()
    Xc = XT - centre[:, None]
    mean = jnp.einsum("cn,dn->cd", onehot, q(Xc), precision=HI) \
        / counts[:, None]
    sq = jnp.einsum("cn,dn->cd", onehot, q(Xc * Xc), precision=HI) \
        / counts[:, None]
    var = jnp.maximum(sq - mean ** 2, var_floor) + smoothing
    prior = jnp.log(counts / counts.sum())
    shift = mean.mean(axis=0)
    xt = XT_test - (centre + shift)[:, None]
    mu = mean - shift[None, :]
    quad = (jnp.einsum("cd,dn->cn", q(1.0 / var), q(xt * xt), precision=HI)
            - 2.0 * jnp.einsum("cd,dn->cn", q(mu / var), q(xt),
                               precision=HI))
    const = (mu ** 2 / var + jnp.log(2.0 * jnp.pi * var)).sum(axis=1)
    loglik = -0.5 * (quad + const[:, None])
    return jax.nn.softmax(loglik + prior[:, None], axis=0)[1]


# -- entry ----------------------------------------------------------------------

def fit_predict(XT: np.ndarray, y: np.ndarray, XT_test: np.ndarray,
                families: dict, kinds=None, precision=None) -> dict:
    """``{family: p1 float32 (n_test,)}``, and under ``dt.unsure`` the
    test rows that lie below a tied split of dt's tree (``TIE``): they are
    not compared. ``XT`` (d, n) and ``XT_test``
    (d, n_test) are host float32 arrays; ``families`` is the
    configuration's block of hyperparameters. ``precision`` names the
    type of each part the configuration states one for (its
    ``precision.reference`` block here, ``precision.control`` in the
    control): ``tree_features`` (the tables the tree families bin),
    ``gb_statistics`` (gradient and hessian as they are summed),
    ``lr_operands`` (lr's design and the operands of its solver's
    products), ``lr_results`` (what those products return), ``nb_operands`` (the operands of nb's four products).
    Unnamed: float32."""
    precision = precision or {}
    tree_dtype = dtype_of(precision.get("tree_features"))
    stat_dtype = dtype_of(precision.get("gb_statistics"))
    lr_dtype = dtype_of(precision.get("lr_operands"))
    lr_result_dtype = dtype_of(precision.get("lr_results"))
    nb_dtype = dtype_of(precision.get("nb_operands"))
    kinds = list(kinds or families)
    d, n = XT.shape
    block, _, n_pad = _blocks(n)
    _, _, nt_pad = _blocks(XT_test.shape[1])
    nt = XT_test.shape[1]
    y_dev = jnp.asarray(_pad_rows(np.asarray(y, np.int32), n_pad))
    valid = jnp.asarray((np.arange(n_pad) < n).astype(np.float32))
    out = {}

    dense = [k for k in kinds if k in ("lr", "nb")]
    if dense:
        X = jnp.asarray(_pad_rows(XT, n_pad))
        Xt = jnp.asarray(XT_test)
        if "lr" in dense:
            out["lr"] = _fit_predict_lr(X, y_dev, valid, n, Xt,
                                        families["lr"], lr_dtype,
                                        lr_result_dtype)
        if "nb" in dense:
            hp = families["nb"]
            out["nb"] = _fit_predict_nb(X, y_dev, valid, Xt, hp["smoothing"],
                                        hp["var_floor"],
                                        operand_dtype=nb_dtype)
        out = {k: np.asarray(v) for k, v in out.items()}
        del X, Xt

    trees = [k for k in kinds if k in ("dt", "rf", "gb")]
    if trees:
        hp0 = families[trees[0]]
        n_bins, depth = hp0["n_bins"], hp0["max_depth"]
        for k in trees:
            assert (families[k]["n_bins"], families[k]["max_depth"],
                    families[k]["edge_sample"]) == (
                n_bins, depth, hp0["edge_sample"]), "one binning per sweep"
        if tree_dtype is None:
            XT_r, XT_test_r = XT, XT_test
        else:
            XT_r = np.asarray(_round_to(XT, tree_dtype))
            XT_test_r = np.asarray(_round_to(XT_test, tree_dtype))
        edges = jnp.asarray(quantile_edges(XT_r, n_bins, hp0["edge_sample"]))
        if tree_dtype is not None:
            edges = edges.astype(tree_dtype).astype(jnp.float32)
        codes = bin_codes(jnp.asarray(_pad_rows(XT_r, n_pad)), edges)
        codes_test = bin_codes(jnp.asarray(_pad_rows(XT_test_r, nt_pad)),
                               edges)
        for k in trees:
            hp = families[k]
            if k == "gb":
                f, t, it, value = _fit_gb(
                    codes, y_dev, valid, depth=depth, n_bins=n_bins,
                    rounds=hp["n_rounds"], step=hp["step_size"],
                    lam=hp["lam"], block=block, stat_dtype=stat_dtype)
                p1 = _gb_p1(codes_test, f, t, it, value, hp["step_size"],
                            depth=depth)
            else:
                if k == "dt":
                    w = valid[None, :]
                    fmask = jnp.zeros((1, d), jnp.float32)
                else:
                    mtry = max(1, int(np.sqrt(d)))
                    w, fmask = _forest_draws(hp["seed"], hp["n_trees"], n,
                                             n_pad, d, mtry)
                f, t, it, leaf, tied = _fit_class_trees(
                    codes, y_dev, w, fmask, depth=depth, n_bins=n_bins,
                    block=block)
                p1 = _class_trees_p1(codes_test, f, t, it, leaf, depth=depth)
                if k == "dt":
                    # one tree: a tie moves whole rows to other leaves,
                    # so the rows below it have no single right answer
                    out["dt.unsure"] = np.asarray(_below_a_tie(
                        codes_test, f[0], t[0], it[0], tied[0],
                        depth=depth))[:nt]
            out[k] = np.asarray(p1)[:nt]
    return out
