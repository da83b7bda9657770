"""The attention kernels (ops/pallas_kernels.py ``chosen_attention``)
held to the plain body of ``models/transformer.py:_chosen_attention``,
in interpret mode at small aligned shapes: heads 128 wide, blocks of 128
queries, rows of 384 tokens (three key blocks of 128: the first query
block stops at the causal edge after one, the last walks all three) and
of 256 (one key block of 256)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learningorchestra_tpu.config import Settings
from learningorchestra_tpu.models import transformer as tx
from learningorchestra_tpu.ops import pallas_kernels as pk
from learningorchestra_tpu.parallel.mesh import local_mesh

H, G, D, C, TOPK = 4, 2, 128, 128, 64


def _plain(q_c, k, v, chosen):
    """The plain body's three lines and the head-summed probabilities."""
    C = q_c.shape[0]
    s = jnp.einsum("qgrd,kgd->grqk", q_c.reshape(C, G, H // G, D),
                   k) * D ** -0.5
    p = jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), axis=-1)
    return (jnp.einsum("grqk,kgd->qgrd", p, v).reshape(C, H, D),
            p.sum((0, 1)))


def _both(fn):
    """``fn``'s outputs and its gradients (of the first output against
    fixed weights) with respect to q, k, v, jitted once."""
    def run(q_c, k, v, chosen, i, w):
        def loss(q_c, k, v):
            o, probs = fn(q_c, k, v, chosen, i)
            return (o * w).sum(), (o, probs)
        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
            q_c, k, v)
        return out, grads
    return jax.jit(run)


@pytest.fixture(scope="module")
def block_fns():
    def fused(q_c, k, v, chosen, i):       # key-value heads flattened
        T = k.shape[0]
        return pk.chosen_attention(q_c, k.reshape(T, G * D),
                                   v.reshape(T, G * D), chosen, i)

    return (_both(fused),
            _both(lambda q_c, k, v, chosen, i: _plain(q_c, k, v, chosen)))


def _mask(kind: str, i: int, T: int, C: int = C):
    """(C, T) bool, causal, every query keeping at least one key."""
    t = (i * C + np.arange(C))[:, None]
    s = np.arange(T)[None, :]
    allowed = s <= t
    if kind == "dense":
        return allowed
    if kind == "random":
        rng = np.random.default_rng(7 + i)
        return allowed & ((rng.random((C, T)) < 0.3) | (s == t))
    if kind == "window":         # the newest 64: early key blocks empty
        return allowed & (s > t - TOPK)
    assert kind == "oldest"      # the oldest 64: later key blocks empty
    return allowed & (s < TOPK)


def _check_block(block_fns, kind, i, T, C):
    ks = jax.random.split(jax.random.PRNGKey(i), 4)
    q = jax.random.normal(ks[0], (C, H, D))
    k = jax.random.normal(ks[1], (T, G, D))
    v = jax.random.normal(ks[2], (T, G, D))
    w = jax.random.normal(ks[3], (C, H, D))
    chosen = jnp.asarray(_mask(kind, i, T, C))
    got, want = (f(q, k, v, chosen, jnp.int32(i), w) for f in block_fns)
    for name, a, b in zip(("o", "probs"), got[0], want[0]):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6, err_msg=name)
    np.testing.assert_allclose(got[0][1].sum(-1), H, rtol=1e-5)
    assert float(jnp.abs(jnp.where(chosen, 0.0, got[0][1])).max()) == 0.0
    for name, a, b in zip(("dq", "dk", "dv"), got[1], want[1]):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)
    past = (i + 1) * C           # keys past the block's last query
    assert float(jnp.abs(got[1][1][past:]).max(initial=0.0)) == 0.0
    assert float(jnp.abs(got[1][2][past:]).max(initial=0.0)) == 0.0


@pytest.mark.parametrize("kind", ["dense", "random", "window", "oldest"])
@pytest.mark.parametrize("i", [0, 2])
def test_block_matches_the_plain_body(block_fns, kind, i):
    """One query block: the first (the walk stops after one key block)
    and the last (all three); masks that leave whole key blocks empty
    for a query, so its running maximum is still -inf when they pass."""
    assert pk.chosen_attn_key_block(384, C, D, H // G) == 128
    _check_block(block_fns, kind, i, 384, C)


@pytest.mark.parametrize("i", [0, 1])
def test_key_block_smaller_than_the_query_block(block_fns, monkeypatch, i):
    """Where VMEM holds no larger one, the key block is smaller than the
    query block (many heads a group at the default ``q_chunk``): a block
    of 256 queries walks keys 128 at a time, two key blocks to the edge
    of the first query block, four of the last."""
    T, C2 = 512, 256
    monkeypatch.setattr(pk, "_ATTN_VMEM_BUDGET",
                        pk._attn_vmem_bytes(H // G * C2, C2, D, 128))
    assert pk.chosen_attn_key_block(T, C2, D, H // G) == 128
    _check_block(block_fns, "random", i, T, C2)


# --- through _chosen_attention: selection, the alignment loss, stats --------

def _cfg(**over):
    base = dict(vocab=32, d_model=64, n_heads=H, n_layers=1, d_ff=64,
                n_classes=3, max_len=256, causal=True, remat=True,
                rms_norm=True, n_kv_heads=G, head_dim=D, rope_theta=1e4,
                qk_norm=True, indexer_heads=2, indexer_head_dim=16,
                indexer_topk=TOPK, q_chunk=C, lm_head=True, init_std=0.2,
                token_chunk=128)
    return tx.TxConfig(**dict(base, **over))


def _row_fn(cfg, with_indexer: bool):
    """``_chosen_attention``'s outputs and the gradients of (o against
    fixed weights + the alignment loss) with respect to every input."""
    def run(q, k, v, ix, w):
        def loss(q, k, v, ix):
            o, stats = tx._chosen_attention(
                cfg, tx.NO_AXES, tx._query_blocks(cfg, tx.NO_AXES, 256),
                q, k, v, ix if with_indexer else None)
            return (o * w).sum() + stats[0], (o, stats)
        (_, out), grads = jax.value_and_grad(
            loss, (0, 1, 2, 3), has_aux=True)(q, k, v, ix)
        return out, grads
    return jax.jit(run)


def _inputs(T, ties: bool):
    ks = jax.random.split(jax.random.PRNGKey(11), 7)
    ix_k = jax.random.normal(ks[4], (T, 16))
    if ties:      # keys in equal pairs: scores tie, at the k-th value too
        ix_k = jnp.repeat(ix_k[::2], 2, axis=0)
    return (jax.random.normal(ks[0], (T, H, D)),
            jax.random.normal(ks[1], (T, G, D)),
            jax.random.normal(ks[2], (T, G, D)),
            (jax.random.normal(ks[3], (T, 2, 16)), ix_k,
             jax.random.normal(ks[5], (T, 2))),
            jax.random.normal(ks[6], (T, H, D)))


ROW_CASES = {
    "indexer": ({}, True, False),
    "ties-at-the-kth-value": ({}, True, True),
    "topk-covers-the-row": ({"indexer_topk": 256}, True, False),
    "no-indexer": ({"indexer_heads": 0}, False, False),
}


@pytest.fixture(scope="module")
def row_fns():
    """Per configuration, the jitted row function on the kernels and on
    the plain body (traced with the shape rule answering 0)."""
    made = {}

    def get(over, with_indexer):
        key = (tuple(sorted(over.items())), with_indexer)
        if key not in made:
            fused = _row_fn(_cfg(**over), with_indexer)
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(pk, "chosen_attn_key_block", lambda *a: 0)
                plain = _row_fn(_cfg(**over), with_indexer).lower(
                    *_inputs(256, False)).compile()
            made[key] = (fused, plain)
        return made[key]

    return get


@pytest.mark.parametrize("case", sorted(ROW_CASES))
def test_row_matches_the_plain_body(row_fns, case):
    over, with_indexer, ties = ROW_CASES[case]
    fused, plain = row_fns(over, with_indexer)
    args = _inputs(256, ties)
    (o, stats), grads = fused(*args)
    (o_p, stats_p), grads_p = plain(*args)
    np.testing.assert_allclose(o, o_p, rtol=2e-5, atol=2e-6)
    # [alignment loss, keys kept, queries short of top-k]
    assert stats[1] == stats_p[1] and stats[2] == stats_p[2]
    np.testing.assert_allclose(stats[0], stats_p[0], rtol=2e-5)
    if with_indexer:
        assert float(stats[0]) > 0
    if case == "topk-covers-the-row" or not with_indexer:
        assert float(stats[1]) == 256 * 257 / 2          # dense causal
    if ties:      # more than top-k kept where the k-th value ties
        assert float(stats[1]) > float(
            np.minimum(np.arange(256) + 1, TOPK).sum())
    names = ("q", "k", "v", "indexer")
    for name, a, b in zip(names, grads, grads_p):
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(x, y, rtol=2e-4, atol=2e-5,
                                       err_msg=name)
    if with_indexer:      # the alignment loss trains all three inputs
        assert all(float(jnp.abs(g).max()) > 0 for g in grads[3])


# --- through the loss, one device and a model axis of 2 ---------------------

def _mesh(shape: str):
    s = Settings()
    s.mesh_shape = shape
    n = int(np.prod([int(a) for a in shape.split(",")]))
    return local_mesh(s, devices=jax.devices()[:n])


def _loss_and_grads(cfg, mesh, params, batch):
    fn = jax.jit(jax.value_and_grad(
        tx.make_loss_fn(cfg, mesh, with_aux=True), has_aux=True))
    (loss, aux), grads = fn(tx.shard_params(params, cfg, mesh), *batch)
    return (float(loss), jax.device_get(aux),
            {k: float(v) for k, v in tx.group_norms(grads).items()})


@pytest.mark.parametrize("shape", ["1,1,1", "1,2,1"])
def test_loss_and_gradients_match_the_plain_body(monkeypatch, shape):
    """The whole step's loss, alignment loss, counters and gradient
    norms: under a model axis of 2 the kernels see a shard's heads and
    the head-sum is ``psum``med outside them."""
    cfg, mesh = _cfg(), _mesh(shape)
    assert tx.attention_path(cfg, tx.MESH_AXES, 256)["attn_kernel"] == 1.0
    params = tx.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(0)
    batch = (jnp.asarray(rng.integers(3, 32, (1, 256)), jnp.int32),
             jnp.asarray(rng.integers(0, 3, 1), jnp.int32))
    fused = _loss_and_grads(cfg, mesh, params, batch)
    monkeypatch.setattr(pk, "chosen_attn_key_block", lambda *a: 0)
    plain = _loss_and_grads(cfg, mesh, params, batch)
    assert fused[0] == pytest.approx(plain[0], rel=1e-5)
    for key in ("loss_main", "loss_index", "keys_kept", "queries_short"):
        assert float(fused[1][key]) == pytest.approx(
            float(plain[1][key]), rel=2e-5), key
    assert float(fused[1]["loss_index"]) > 0
    for group, norm in plain[2].items():
        assert fused[2][group] == pytest.approx(norm, rel=2e-4, abs=1e-7), \
            group


# --- the shape rule ----------------------------------------------------------

@pytest.mark.parametrize("T,C,D_,R,block", [
    (8192, 128, 128, 8, 512),      # the benchmark's cell
    (384, 128, 128, 2, 128), (256, 128, 128, 2, 256), (256, 64, 128, 2, 256),
    (64, 16, 16, 2, 0),            # tests/test_tx_arch.py's widths: plain
    (8192, 128, 64, 8, 0),         # a head is not a lane tile
    (8192, 100, 128, 8, 0),        # a chunk is not whole mask tiles
    (8200, 200, 128, 8, 0),        # no key block divides the row
    # The default q_chunk, 512: a group's stacked rows against VMEM.
    (8192, 512, 128, 8, 512), (8192, 512, 128, 12, 256),
    (8192, 512, 128, 16, 128), (8192, 512, 128, 32, 0),
    (8192, 512, 256, 8, 256),
])
def test_shape_rule(T, C, D_, R, block):
    assert pk.chosen_attn_key_block(T, C, D_, R) == block
    if block:
        assert pk._attn_vmem_bytes(R * C, C, D_, block) <= pk._ATTN_VMEM_BUDGET


@pytest.mark.parametrize("key_block,skipped,share", [
    (128, 2016, 0.4921875), (512, 480, 0.46875)])
def test_skipped_share_at_the_cells_size(monkeypatch, key_block, skipped,
                                         share):
    cell = _cfg(n_heads=32, n_kv_heads=4, max_len=8192)
    monkeypatch.setattr(pk, "chosen_attn_key_block", lambda *a: key_block)
    assert tx.attention_path(cell, tx.MESH_AXES, 8192) == {
        "attn_kernel": 1.0, "key_blocks_skipped_share": share}
    assert share == skipped / (64 * (8192 // key_block))
