"""The expert layer's grouped path against its dense oracle.

Outside a mesh program ``_experts`` computes the routed pairs only: the
layer's assignments to held experts, sorted by expert and walked in
windows by ``_expert_loop``, through ``pk.grouped_matmul`` /
``pk.grouped_matmul_t`` (interpret mode here).
The dense form (every held expert over every token, gate-scaled) is
what runs inside a mesh program off the TPU, and is the oracle: both
take float32 operands on the CPU, so they agree to rounding in the
order of summation. Output and gradients (input, router, every expert
leaf) are compared under both routing forms and under routings that
stress the schedule: every assignment to one expert, an expert with
none, assignments to experts held elsewhere, counts that are no
multiple of the tile.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from learningorchestra_tpu.models import sequence  # noqa: E402
from learningorchestra_tpu.models import transformer as tx  # noqa: E402
from learningorchestra_tpu.ops import pallas_kernels as pk  # noqa: E402

D, F, B, T = 64, 32, 2, 64


def _layer(cfg, seed, router_scale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    e = cfg.held
    lyr = {"router": jax.random.normal(ks[0], (D, cfg.n_experts))
           * router_scale,
           "we_up": jax.random.normal(ks[1], (e, D, F)) * 0.2,
           "we_down": jax.random.normal(ks[2], (e, F, D)) * 0.2}
    if cfg.router_sigmoid:
        lyr["router_bias"] = jnp.linspace(-0.2, 0.2, cfg.n_experts)
    if not cfg.relu2_experts:
        lyr["we_gate"] = jax.random.normal(ks[3], (e, D, F)) * 0.2
    if cfg.shared_width:
        lyr["sh_up"] = jax.random.normal(ks[4], (D, cfg.shared_width)) * 0.2
        lyr["sh_down"] = jax.random.normal(ks[5], (cfg.shared_width, D)) * 0.2
    return lyr


def _run(cfg, lyr, grouped, monkeypatch):
    """``_experts`` on one fixed input, forced onto one path: the output,
    the counters and the gradients of a fixed projection of the output
    for the input and every leaf."""
    monkeypatch.setattr(pk, "grouped_fits", lambda on_mesh=False: grouped)
    h = jax.random.normal(jax.random.PRNGKey(99), (B, T, D))
    cot = jnp.cos(jnp.arange(B * T * D, dtype=jnp.float32)).reshape(B, T, D)

    def f(h, lyr):
        out, counts, moe, tiles = tx._experts(cfg, tx.NO_AXES, h, lyr)
        return (out * cot).sum(), (out, counts, moe, tiles)

    (_, aux), grads = jax.jit(jax.value_and_grad(
        f, (0, 1), has_aux=True))(h, lyr)
    return aux, grads


def _close(a, b, rel=1e-5):
    a, b = np.asarray(a), np.asarray(b)
    return np.abs(a - b).max() <= rel * max(np.abs(b).max(), 1e-6)


BASE = dict(d_model=D, n_heads=4, n_layers=1, causal=True, n_experts=8,
            experts_per_token=3, expert_width=F, experts_held=4,
            experts_first=0, token_chunk=32)
SIGMOID = dict(router_sigmoid=True, routed_scale=2.5, relu2_experts=True,
               shared_width=48)

#: name: (config overrides, router scale): a zero router routes every
#: token to the first experts (ties go to the lower index).
ROUTINGS = {
    "softmax_swiglu": ({}, 1.0),
    "sigmoid_relu2_shared": (SIGMOID, 1.0),
    "absent_experts": (dict(experts_first=2), 1.0),
    "sigmoid_absent": (dict(SIGMOID, experts_first=4), 1.0),
    "all_to_one_expert": (dict(experts_per_token=1), 0.0),
    "one_expert_idle": ({}, 0.0),
    "more_picks_than_held": (dict(experts_per_token=6, experts_held=2,
                                  experts_first=6), 1.0),
    "one_block": (dict(token_chunk=B * T), 1.0),
}


@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_grouped_layer_matches_dense(routing, monkeypatch):
    over, scale = ROUTINGS[routing]
    cfg = tx.TxConfig(**dict(BASE, **over))
    lyr = _layer(cfg, 3, scale)
    (o_g, c_g, m_g, t_g), g_g = _run(cfg, lyr, True, monkeypatch)
    (o_d, c_d, m_d, t_d), g_d = _run(cfg, lyr, False, monkeypatch)
    assert _close(o_g, o_d)
    assert _close(g_g[0], g_d[0])                          # the input
    for name in lyr:
        if name == "router_bias":      # a buffer: no gradient trains it
            assert float(jnp.abs(g_g[1][name]).max()) == 0.0
            continue
        assert _close(g_g[1][name], g_d[1][name]), name
    np.testing.assert_array_equal(np.asarray(c_g), np.asarray(c_d))
    np.testing.assert_array_equal(np.asarray(m_g), np.asarray(m_d))
    assert float(m_g[2]) == 0.0                            # nothing dropped
    # the grouped products computed every assignment to a held expert,
    # and visited whole tiles covering them
    count = float(c_g.sum())
    assert float(t_g[1]) == count
    assert float(t_g[0]) >= count and float(t_g[0]) % pk.GROUP_TILE == 0
    assert np.asarray(t_d).tolist() == [0.0, 0.0]          # dense: none
    if routing == "one_expert_idle":
        assert np.asarray(c_g).tolist() == [B * T] * 3 + [0]
    if routing == "all_to_one_expert":
        assert np.asarray(c_g).tolist() == [B * T, 0, 0, 0]


@pytest.mark.parametrize("sizes", [[70, 0, 100, 30], [256, 0, 0, 0],
                                   [0, 0, 0, 1], [0, 0, 0, 0],
                                   [128, 128, 0, 0]])
def test_grouped_products_match_numpy(sizes):
    """The three products of the grouped path on rows sorted by group:
    each row through its group's weights (and through their transpose),
    and each group's rows' outer products summed; rows past the groups
    are left alone, an empty group's weight gradient is zero."""
    rng = np.random.default_rng(len(sizes) + sum(sizes))
    m, k, n, G = 256, 64, 96, len(sizes)
    count = sum(sizes)
    s = jnp.asarray(sizes, jnp.int32)
    x = rng.normal(size=(m, k)).astype(np.float32)
    w = rng.normal(size=(G, k, n)).astype(np.float32)
    g = rng.normal(size=(m, n)).astype(np.float32)
    gid = np.repeat(np.arange(G), sizes)
    out = np.asarray(jax.jit(pk.grouped_matmul)(x, w, s))[:count]
    ref = np.einsum("rk,rkn->rn", x[:count], w[gid])
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-4)
    out_t = np.asarray(jax.jit(lambda a, b, c: pk.grouped_matmul(
        a, b, c, transpose=True))(g, w, s))[:count]
    np.testing.assert_allclose(
        out_t, np.einsum("rn,rkn->rk", g[:count], w[gid]),
        rtol=1e-5, atol=1e-4)
    dw = np.asarray(jax.jit(pk.grouped_matmul_t)(x, g, s))
    ref_w = np.zeros((G, k, n), np.float32)
    np.add.at(ref_w, gid, np.einsum("rk,rn->rkn", x[:count], g[:count]))
    np.testing.assert_allclose(dw, ref_w, rtol=1e-5, atol=1e-4)
    tiles = int(pk.grouped_tile_rows(s))
    assert tiles >= count and tiles % pk.GROUP_TILE == 0


def test_tile_rows_count_straddles():
    """A tile two groups share is visited by each: 70 + 100 + 30 rows
    at 128 a tile are 1 + 2 + 1 tiles."""
    s = jnp.asarray([70, 0, 100, 30], jnp.int32)
    assert int(pk.grouped_tile_rows(s, 128)) == 4 * 128
    assert int(pk.grouped_tile_rows(s, 64)) == (2 + 3 + 1) * 64


def test_cpu_mesh_runs_dense_and_drops_nothing():
    """Inside a mesh program off the TPU the dense form runs (the
    oracle), and says so; outside one the grouped path runs. Neither
    drops a token."""
    from jax.sharding import Mesh

    from learningorchestra_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, SEQ_AXIS)

    cfg = tx.TxConfig(vocab=32, max_len=T, lm_head=True, rms_norm=True,
                      n_kv_heads=2, **BASE)
    assert tx.moe_path(cfg, tx.MESH_AXES) == {"moe_path": "dense"}
    assert tx.moe_path(cfg, tx.NO_AXES) == {"moe_path": "grouped"}
    assert tx.moe_path(tx.TxConfig(), tx.NO_AXES) == {}
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
                (DATA_AXIS, MODEL_AXIS, SEQ_AXIS))
    params = tx.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, 32)
    _, aux = jax.jit(tx.make_loss_fn(cfg, mesh, with_aux=True))(
        params, tokens, jnp.zeros(B, jnp.int32))
    assert float(aux["moe"][2]) == 0.0
    assert np.asarray(aux["moe_tiles"]).tolist() == [0.0, 0.0]


def test_step_report_reads_the_tile_overhead():
    """``moe_tile_rows_share``: tile rows visited over assignments
    computed, summed over the steps; absent where nothing ran grouped."""
    cfg = tx.TxConfig(**BASE)

    def report(tiles):
        return {"loss_main": 1.0, "loss_index": 0.0, "grad_norm": {"x": 1.0},
                "moe": [8.0, 2.0, 0.0], "experts": [3.0, 3.0, 0.0, 0.0],
                "moe_tiles": tiles}

    got = sequence._fit_metrics([report([256.0, 6.0]),
                                 report([128.0, 10.0])], cfg, 4)
    assert got["moe_tile_rows_share"] == pytest.approx(384.0 / 16.0)
    assert got["dropped_tokens"] == 0
    dense = sequence._fit_metrics([report([0.0, 0.0])], cfg, 4)
    assert "moe_tile_rows_share" not in dense


def _unchecked(kernel):
    """``kernel`` traced with ``shard_map``'s typing of mesh axes off, its
    output marked varying over every axis its inputs vary over (what
    the kernel states on the TPU): interpret mode's reads of a block
    mix varying refs with invariant indices, which the typing refuses."""
    from jax._src import config as jax_config

    def call(*args, **kw):
        vma = pk._varying(*args)
        with jax_config._check_vma(False):
            out = kernel(*args, **kw)
        return jax.lax.pcast(out, tuple(sorted(vma)), to="varying") \
            if vma else out

    return call


@pytest.mark.parametrize("routing", ["softmax_swiglu",
                                     "sigmoid_relu2_shared"])
def test_grouped_layer_matches_dense_on_a_mesh(routing, monkeypatch):
    """The grouped path inside a mesh program whose model axis splits
    the held experts (and whose data axis splits the rows), forced on
    (off the TPU the dense form runs there) with the kernels in
    interpret mode, against the dense form:
    the loss, and the gradient of every leaf. Each shard sorts its own
    assignments, so its gates are its own; their cotangents, and the
    input's, are summed over the shards (the router's gradient reads
    it)."""
    from jax.sharding import Mesh

    from learningorchestra_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, SEQ_AXIS)

    cfg = tx.TxConfig(vocab=32, max_len=T, lm_head=True, rms_norm=True,
                      n_kv_heads=2, **dict(BASE, **ROUTINGS[routing][0]))
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2, 1),
                (DATA_AXIS, MODEL_AXIS, SEQ_AXIS))
    params = tx.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0, 32)
    labels = jnp.zeros(B, jnp.int32)
    for name in ("grouped_matmul", "grouped_matmul_t"):
        monkeypatch.setattr(pk, name, _unchecked(getattr(pk, name)))
    got = {}
    for grouped in (False, True):
        monkeypatch.setattr(pk, "grouped_fits",
                            lambda on_mesh=False, g=grouped: g)
        loss_fn = tx.make_loss_fn(cfg, mesh, with_aux=True)
        got[grouped] = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
            params, tokens, labels)
    ((l_d, a_d), g_d), ((l_g, a_g), g_g) = got[False], got[True]
    assert _close(l_g, l_d)
    for path, leaf in jax.tree_util.tree_flatten_with_path(g_d)[0]:
        name = jax.tree_util.keystr(path)
        other = dict(jax.tree_util.tree_flatten_with_path(g_g)[0])[path]
        if "router_bias" in name:
            continue
        assert _close(other, leaf), name
    np.testing.assert_array_equal(np.asarray(a_g["moe"]),
                                  np.asarray(a_d["moe"]))
    assert float(a_g["moe"][2]) == 0.0
    assert float(a_g["moe_tiles"][1]) == float(a_g["moe"][0] - a_g["moe"][1])


@pytest.mark.parametrize("visit_empty", [False, True])
def test_schedule_is_megablox(visit_empty):
    """``pk._group_schedule`` visits what megablox's
    ``make_group_metadata`` visits: the same number of grid steps, each
    for the same group and, for a group with rows, the same row tile."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata)

    rng = np.random.default_rng(int(visit_empty))
    for _ in range(25):
        G = int(rng.integers(1, 10))
        tm = int(rng.choice([8, 16, 128]))
        m = tm * int(rng.integers(1, 12))
        cuts = np.sort(rng.integers(0, m + 1, G))
        sizes = np.diff(np.concatenate(
            [[0], np.minimum(cuts, int(rng.integers(0, m + 1)))]))
        if rng.random() < 0.3:
            sizes[rng.integers(0, G)] = 0
        s = jnp.asarray(sizes, jnp.int32)
        (off, grp, tile), steps = make_group_metadata(
            group_sizes=s, m=m, tm=tm, start_group=jnp.int32(0),
            num_nonzero_groups=G, visit_empty_groups=visit_empty)
        (off2, grp2, tile2), steps2 = pk._group_schedule(s, m, tm,
                                                         visit_empty)
        n = int(steps)
        assert int(steps2) == n
        np.testing.assert_array_equal(np.asarray(off2), np.asarray(off))
        np.testing.assert_array_equal(np.asarray(grp2)[:n],
                                      np.asarray(grp)[:n])
        rows = sizes[np.asarray(grp)[:n]] > 0
        np.testing.assert_array_equal(np.asarray(tile2)[:n][rows],
                                      np.asarray(tile)[:n][rows])


def _count(jaxpr, name: str) -> int:
    """Equations of primitive ``name`` in a jaxpr and the jaxprs its
    equations hold (a kernel's body left out)."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _count(sub, name)
    return n


def test_row_mapped_forward_skips_empty_windows():
    """The predict pass maps blocks of rows as batches
    (``sequence._in_batches``), not a ``vmap`` of single rows: the
    expert layer's window turn stays a conditional, once in the blocks'
    program and once in the remainder's (under ``lax.map``'s ``vmap`` it
    becomes a select that runs every window), and the mapped program
    reads what the rows give one by one, a remainder block included."""
    cfg = tx.TxConfig(**BASE)
    lyr = _layer(cfg, 5)
    h = jax.random.normal(jax.random.PRNGKey(9), (5, T, D))

    def block(rows):
        return tx._experts(cfg, tx.NO_AXES, rows, lyr)[0]

    def mapped(h):
        return sequence._in_batches(block, h, 2)

    assert _count(jax.make_jaxpr(block)(h[:1]).jaxpr, "cond") == 1
    assert _count(jax.make_jaxpr(mapped)(h).jaxpr, "cond") == 2
    vmapped = jax.make_jaxpr(lambda h: jax.lax.map(
        lambda r: block(r[None])[0], h, batch_size=2))(h)
    assert _count(vmapped.jaxpr, "cond") == 0
    rows = jnp.concatenate([jax.jit(block)(h[i:i + 1]) for i in range(5)])
    assert _close(jax.jit(mapped)(h), rows)
