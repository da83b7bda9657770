"""The tx family's architecture options (models/transformer.py) held to
the benchmark's plain reference (perfbench/reference_tx.py) at a small
size on the CPU: RMSNorm, RoPE, grouped-query attention with QK-norm,
the sparse-attention indexer with live selection (top-k 16 at T 64),
routed experts of which a holder was told its share, the next-token
loss with the label-token readout, and the indexer's alignment loss."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from learningorchestra_tpu.config import Settings
from learningorchestra_tpu.models import transformer as tx
from learningorchestra_tpu.models.registry import validate_hparams
from learningorchestra_tpu.parallel.mesh import local_mesh
from perfbench import reference_tx as R

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, B, CLASSES, SEED = 64, 2, 3, 5
CONF = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,
        "sa_config": {"indexer_num_heads": 4, "indexer_head_dim": 8,
                      "topk": 16},
        "num_experts": 8, "num_experts_per_tok": 2, "num_local_experts": 4,
        "experts_first": 2, "moe_intermediate_size": 32, "vocab_size": 48,
        "rms_norm_eps": 1e-6, "rope_theta": 1e4, "norm_topk_prob": True,
        "init": {"std": 0.2}}
GROUPS = ("attention", "indexer", "router", "experts", "embedding", "head")


def config(**over):
    base = dict(vocab=48, d_model=64, n_heads=4, n_layers=2,
                n_classes=CLASSES, max_len=T, causal=True, remat=True,
                rms_norm=True, norm_eps=1e-6, n_kv_heads=2, head_dim=16,
                rope_theta=1e4, qk_norm=True, indexer_heads=4,
                indexer_head_dim=8, indexer_topk=16, q_chunk=16,
                n_experts=8, experts_per_token=2, expert_width=32,
                experts_first=2, experts_held=4, lm_head=True, init_std=0.2,
                token_chunk=32)
    return tx.TxConfig(**dict(base, **over))


def mesh_of(shape: str):
    s = Settings()
    s.mesh_shape = shape
    n = int(np.prod([int(a) for a in shape.split(",")]))
    return local_mesh(s, devices=jax.devices()[:n])


def flat(params):
    out = {k: v for k, v in params.items() if k != "layers"}
    out.update({f"layers.{k}": v for k, v in params["layers"].items()})
    return out


def nest(w):
    out = {"layers": {}}
    for k, v in w.items():
        if k.startswith("layers."):
            out["layers"][k[len("layers."):]] = v
        else:
            out[k] = v
    return out


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    return (rng.integers(CLASSES, 48, (B, T)).astype(np.int32),
            rng.integers(0, CLASSES, B).astype(np.int32))


@pytest.fixture(scope="module")
def both(batch):
    """Three Adam steps of the program and of the reference from the
    same seeded weights on the same batch."""
    cfg, mesh = config(), mesh_of("1,1,1")
    opt = optax.adam(1e-2)
    init, step = tx.make_fit_programs(cfg, mesh, opt, B)
    state = init(jax.random.PRNGKey(SEED))
    w = R.init_weights(CONF, SEED)
    for k, v in flat(state[0]).items():       # the recipe, followed twice
        np.testing.assert_allclose(np.asarray(v), np.asarray(w[k]),
                                   rtol=1e-6, atol=1e-7)
    loss_fn = jax.jit(jax.value_and_grad(
        tx.make_loss_fn(cfg, mesh, with_aux=True), has_aux=True))
    train = tx.make_train_step(cfg, mesh, opt)
    params, opt_state = state[0], opt.init(state[0])
    prog = []
    for _ in range(3):
        (_, aux), grads = loss_fn(params, *map(jnp.asarray, batch))
        prog.append({"loss_main": float(aux["loss_main"]),
                     "loss_index": float(aux["loss_index"]),
                     "grad_norm": {k: float(v) for k, v in
                                   tx.group_norms(grads).items()},
                     "aux": jax.device_get(aux)})
        params, opt_state, _ = train(params, opt_state,
                                     *map(jnp.asarray, batch))
    return prog, R.adam_steps(CONF, w, [batch] * 3, 1e-2)


@pytest.mark.parametrize("what", ["loss_main", "loss_index", "adam.1",
                                  "adam.2"] + [f"grad.{g}" for g in GROUPS])
def test_program_matches_reference(both, what):
    prog, ref = both
    if what.startswith("grad."):
        g = what[5:]
        assert prog[0]["grad_norm"][g] == pytest.approx(
            ref[0]["grad_norm"][g], rel=2e-5)
        assert ref[0]["grad_norm"][g] > 1e-3        # the part is trained
    elif what.startswith("adam."):
        i = int(what[5:])
        for part in ("loss_main", "loss_index"):
            assert prog[i][part] == pytest.approx(ref[i][part], rel=2e-5)
        assert prog[i]["loss_main"] < prog[0]["loss_main"]
    else:
        assert prog[0][what] == pytest.approx(ref[0][what], rel=2e-5)


def test_selection_is_live_and_counted(both):
    aux = both[0][0]["aux"]
    queries = B * T * 2
    assert 0 < aux["queries_short"] < queries        # some rows exceed top-k
    assert aux["keys_kept"] < B * 2 * T * (T + 1) / 2     # keys were left out
    moe = aux["moe"]
    assert moe[0] == B * T * 2 * 2 and moe[2] == 0        # routed, dropped
    assert aux["experts"].sum() == moe[0] - moe[1]


def test_topk_covering_the_row_is_dense_causal(batch):
    """T <= top-k: the indexer keeps every earlier key, and the main loss
    is the dense causal model's."""
    mesh = mesh_of("1,1,1")
    sparse, dense = config(indexer_topk=T), config(indexer_heads=0)
    params = tx.init_params(jax.random.PRNGKey(1), sparse)
    plain = dict(params, layers={k: v for k, v in params["layers"].items()
                                 if not k.startswith("ix_")})
    args = tuple(map(jnp.asarray, batch))
    _, a = tx.make_loss_fn(sparse, mesh, with_aux=True)(params, *args)
    _, b = tx.make_loss_fn(dense, mesh, with_aux=True)(plain, *args)
    assert float(a["loss_main"]) == pytest.approx(float(b["loss_main"]),
                                                  rel=1e-6)
    assert float(a["keys_kept"]) == B * 2 * T * (T + 1) / 2


@pytest.mark.parametrize("shares", [8, 4, 2])
def test_expert_shares_add_up_to_the_uncut_layer(shares):
    """What every share's experts add, summed, is the reference's layer
    with all experts held."""
    whole = dict(CONF, num_local_experts=8, experts_first=0)
    w = R.init_weights(whole, 3)
    lw = {k[len("layers."):]: v[0] for k, v in w.items()
          if k.startswith("layers.")}
    x = jax.random.normal(jax.random.PRNGKey(9), (T, 64))
    mid, after = R.layer_whole(whole, lw, x)
    h = tx._rms(mid, lw["ln2_g"], 1e-6)[None]
    per = 8 // shares
    total = jnp.zeros_like(mid)
    for i in range(shares):
        cfg = config(experts_first=i * per, experts_held=per)
        lyr = dict(lw, **{k: lw[k][i * per:(i + 1) * per]
                          for k in ("we_gate", "we_up", "we_down")})
        out, counts, moe, _ = tx._experts(cfg, tx.NO_AXES, h, lyr)
        total = total + out[0]
        assert moe[2] == 0 and counts.sum() == moe[0] - moe[1]
    np.testing.assert_allclose(np.asarray(total), np.asarray(after - mid),
                               rtol=2e-4, atol=2e-5)


def test_vocabulary_slices_concatenate_to_the_whole_head(batch):
    cfg = config()
    w = R.init_weights(CONF, 4)
    whole = np.asarray(R.head_logits(CONF, w, batch[0]))
    params = nest(w)
    x, _ = tx._trunk(params, jnp.asarray(batch[0]), cfg, tx.NO_AXES)
    h = tx._rms(x, params["lnf_g"], 1e-6)
    parts = [h @ params["head_w"][:, i * 6:(i + 1) * 6] for i in range(8)]
    np.testing.assert_allclose(np.concatenate(parts, -1), whole,
                               rtol=2e-4, atol=2e-5)


def test_every_token_on_one_expert_drops_none(batch):
    """A router of zeros ties every expert: top-k then takes experts 0
    and 1 for every token. Both are held here; each sees every token."""
    cfg = config(experts_first=0)
    conf = dict(CONF, experts_first=0)
    w = R.init_weights(conf, 6)
    w["layers.router"] = jnp.zeros_like(w["layers.router"])
    mesh = mesh_of("1,1,1")
    _, aux = tx.make_loss_fn(cfg, mesh, with_aux=True)(
        nest(w), *map(jnp.asarray, batch))
    n = B * T * 2                                    # tokens x layers
    assert list(np.asarray(aux["experts"])) == [n, n, 0, 0]
    assert list(np.asarray(aux["moe"])) == [2 * n, 0, 0]
    ref = R.adam_steps(conf, w, [batch], 1e-2)[0]
    assert float(aux["loss_main"]) == pytest.approx(ref["loss_main"],
                                                    rel=2e-5)


def test_seq_axis_with_the_indexer_raises(batch):
    cfg, mesh = config(), mesh_of("1,1,2")
    params = tx.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="sequence axis of 2"):
        tx.make_loss_fn(cfg, mesh)(params, *map(jnp.asarray, batch))


@pytest.mark.parametrize("shape,over", [
    ("2,2,1", {}),                          # dp x tp with the indexer
    ("1,2,2", {"indexer_heads": 0}),        # dense GQA + next-token on the ring
])
def test_mesh_matches_one_device(batch, shape, over):
    cfg = config(**over)
    params = tx.init_params(jax.random.PRNGKey(2), cfg)
    args = tuple(map(jnp.asarray, batch))

    def read(mesh):
        fn = jax.jit(jax.value_and_grad(
            tx.make_loss_fn(cfg, mesh, with_aux=True), has_aux=True))
        (loss, aux), grads = fn(tx.shard_params(params, cfg, mesh), *args)
        return float(loss), {k: float(v) for k, v in
                             tx.group_norms(grads).items()}

    one, many = read(mesh_of("1,1,1")), read(mesh_of(shape))
    assert many[0] == pytest.approx(one[0], rel=1e-5)
    for g, v in one[1].items():
        assert many[1][g] == pytest.approx(v, rel=1e-4, abs=1e-7)


@pytest.mark.parametrize("bad,names", [
    ({"arch": {"n_kv_headz": 2}}, "arch.n_kv_headz"),
    ({"arch": {"indexer_topk": 0}}, "arch.indexer_topk"),
    ({"arch": {"lm_head": 1}}, "arch.lm_head"),
    ({"arch": 7}, "'arch'"),
])
def test_validate_hparams_names_the_bad_key(bad, names):
    with pytest.raises(ValueError, match=names):
        validate_hparams("tx", bad)
    validate_hparams("tx", {"arch": {"n_kv_heads": 2, "lm_head": True}})


def test_config_options_that_need_each_other():
    with pytest.raises(ValueError, match="n_kv_heads"):
        tx.TxConfig(rope_theta=1e4)
    with pytest.raises(ValueError, match="causal"):
        tx.TxConfig(n_kv_heads=2, n_heads=4, indexer_heads=2)
    with pytest.raises(ValueError, match="not among"):
        config(experts_first=6, experts_held=4)


def test_the_cell_posts_the_published_sizes():
    """``families.tx`` of the benchmark's configuration (what the cell
    POSTs) says what the file's own config.json keys say, and every
    number of the catalog's entry is there unchanged but the three the
    file lists as reduced."""
    with open(os.path.join(REPO, "perfbench", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as fh:
        conf = json.load(fh)
    hp, sa = conf["families"]["tx"], conf["sa_config"]
    arch = hp["arch"]
    assert (hp["d_model"], hp["n_heads"], hp["n_layers"], hp["vocab"]) == (
        conf["hidden_size"], conf["num_attention_heads"],
        conf["num_hidden_layers"], conf["vocab_size"])
    assert (arch["n_kv_heads"], arch["head_dim"], arch["rope_theta"],
            arch["norm_eps"]) == (
        conf["num_key_value_heads"], conf["head_dim"], conf["rope_theta"],
        conf["rms_norm_eps"])
    assert (arch["indexer_heads"], arch["indexer_head_dim"],
            arch["indexer_topk"]) == (
        sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"])
    assert (arch["n_experts"], arch["experts_per_token"],
            arch["expert_width"], arch["experts_held"],
            arch["experts_first"], arch["init_std"]) == (
        conf["num_experts"], conf["num_experts_per_tok"],
        conf["moe_intermediate_size"], conf["num_local_experts"],
        conf["experts_first"], conf["init"]["std"])
    published = {"hidden_size": 2048, "num_attention_heads": 32,
                 "num_key_value_heads": 4, "head_dim": 128,
                 "moe_intermediate_size": 768, "num_experts": 128,
                 "num_experts_per_tok": 8, "intermediate_size": 6144}
    assert {k: conf[k] for k in published} == published
    assert conf["published"]["num_hidden_layers"] == 48
    validate_hparams("tx", hp)


# --- through REST, and back from the disk ------------------------------------

ARCH_HP = {"d_model": 32, "n_heads": 4, "n_layers": 2, "vocab": 24,
           "train_steps": 6, "batch": 8, "lr": 1e-2, "causal": True,
           "remat": True,
           "arch": {"rms_norm": True, "norm_eps": 1e-6, "n_kv_heads": 2,
                    "head_dim": 8, "rope_theta": 1e4, "qk_norm": True,
                    "indexer_heads": 2, "indexer_head_dim": 8,
                    "indexer_topk": 8, "q_chunk": 8, "n_experts": 4,
                    "experts_per_token": 2, "expert_width": 16,
                    "experts_first": 0, "experts_held": 2,
                    "norm_topk_prob": True, "lm_head": True,
                    "init_std": 0.1}}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from learningorchestra_tpu.client import Context, DatabaseApi, Model
    from learningorchestra_tpu.models import persistence
    from learningorchestra_tpu.serving.app import App

    tmp = tmp_path_factory.mktemp("txarch")
    cfg = Settings()
    cfg.store_root, cfg.image_root = str(tmp / "store"), str(tmp / "img")
    cfg.port, cfg.persist = 0, True
    cfg.mesh_shape = "4,2,1"            # dp x tp; the indexer needs seq 1
    flat_bytes, persistence.FLAT_BYTES = persistence.FLAT_BYTES, 1
    app = App(cfg, recover=False)
    server = app.serve(background=True)
    ctx = Context(f"http://127.0.0.1:{server.port}", poll_seconds=0.1,
                  timeout=600)
    rng = np.random.default_rng(3)
    for name, n in (("ax_train", 64), ("ax_test", 12)):
        labels = rng.integers(0, 3, n)
        toks = rng.integers(3, 24, (n, 16))
        toks[:, ::2] = 3 + labels[:, None]          # the topic shows
        cols = {f"t{j:02d}": toks[:, j].astype(np.int64) for j in range(16)}
        cols["label"] = labels.astype(np.int64)
        app.store.create(name, columns=cols, finished=True)
    yield app, DatabaseApi(ctx), Model(ctx), tmp
    server.stop()
    persistence.FLAT_BYTES = flat_bytes


def test_rest_fit_with_the_architecture_block(served):
    app, db, model, _ = served
    out = model.create_model("ax_train", "ax_test", "axp", ["tx"], "label",
                             hparams={"tx": ARCH_HP})
    rep = out["result"][0]
    assert rep["classifier"] == "tx" and "error" not in rep, rep
    meta = db.read_file("axp_tx", limit=1)[0]
    assert meta["finished"] is True and not meta.get("error")
    # The fit's own course is stored with its metrics.
    assert len(meta["loss"]) == 6 and len(meta["loss_index"]) == 6
    assert set(meta["grad_norm"]) == set(GROUPS)
    assert meta["dropped_tokens"] == 0 and 0 < meta["absent_share"] < 1
    assert meta["loss"][-1] < meta["loss"][0]
    rows = db.read_file("axp_tx", skip=1, limit=12)
    assert len(rows) == 12
    for r in rows:
        assert len(r["probability"]) == 3
        assert r["prediction"] == int(np.argmax(r["probability"]))
    counters = app._metrics_doc()["tx"]
    assert counters["fits"] >= 1 and counters["dropped_tokens"] == 0
    assert counters["steps"] >= 6 and "moe_imbalance" in counters


def test_attention_path_is_chosen_from_the_shapes_and_reported(served):
    """The shape rule: the plain body at this file's widths (heads of 8
    and 16), the Pallas kernels at aligned ones (the benchmark cell's);
    and the fit's ``fit.tx.steps`` span says which ran."""
    from learningorchestra_tpu.utils import tracing

    assert tx.attention_path(config(), tx.MESH_AXES, T) == {
        "attn_kernel": 0.0, "key_blocks_skipped_share": 0.0}
    cell = config(n_heads=32, n_kv_heads=4, head_dim=128, q_chunk=128,
                  max_len=8192)
    assert tx.attention_path(cell, tx.MESH_AXES, 8192) == {
        "attn_kernel": 1.0, "key_blocks_skipped_share": 0.46875}
    # Without the indexer a fit's attention is the ring's, not the query
    # blocks'; the unsharded forward of predict goes through them.
    dense = config(indexer_heads=0)
    assert tx.attention_path(dense, tx.MESH_AXES, T) == {}
    assert tx.attention_path(dense, tx.NO_AXES, T)["attn_kernel"] == 0.0
    assert tx.attention_path(tx.TxConfig(), tx.NO_AXES, T) == {}
    _, _, model, _ = served
    model.create_model("ax_train", "ax_test", "axk", ["tx"], "label",
                       hparams={"tx": dict(ARCH_HP, train_steps=1)})
    spans = {d["name"]: d for d in tracing.recent_span_docs()
             if d["name"] in ("fit.tx.steps", "fit.tx.predict")}
    for name in ("fit.tx.steps", "fit.tx.predict"):
        attrs = spans[name]["attrs"]
        assert attrs["attn_kernel"] == 0.0, name
        assert attrs["key_blocks_skipped_share"] == 0.0, name
    assert "keys_kept_mean" in spans["fit.tx.steps"]["attrs"]


def test_saved_model_reloads_flat_and_predicts_the_same(served):
    app, db, model, tmp = served
    files = set(os.listdir(tmp / "store" / "_models" / "axp_tx"))
    assert {"params.bin", "params.json", "manifest.json"} <= files
    weights = R.load_saved(str(tmp / "store" / "_models" / "axp_tx"))
    man, loaded = app.builder.registry.load("axp_tx")
    assert man["hparams"]["arch"] == ARCH_HP["arch"]
    for path, arr in weights.items():          # any reader sees the same
        node = loaded.params
        for key in path.split("."):
            node = node[key]
        assert np.array_equal(node, arr) and arr.dtype == np.float32
    model.predict("axp_tx", "ax_test", "axp_again", wait=True)
    first = db.read_file("axp_tx", skip=1, limit=12)
    again = db.read_file("axp_again", skip=1, limit=12)
    for a, b in zip(first, again):
        np.testing.assert_allclose(a["probability"], b["probability"],
                                   rtol=1e-5, atol=1e-6)


def test_rest_names_a_bad_key_of_the_block(served):
    _, _, model, _ = served
    bad = dict(ARCH_HP, arch=dict(ARCH_HP["arch"], experts_hold=2))
    with pytest.raises(Exception, match="arch.experts_hold"):
        model.create_model("ax_train", "ax_test", "axbad", ["tx"], "label",
                           hparams={"tx": bad})
