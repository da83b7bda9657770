"""The tx family's hybrid options (models/transformer.py: a layer pattern,
the gated delta-rule linear mixer, the gated MLP, the norm on a
sublayer's output, the whole-projection QK-norm, no positions, heads
held) against the benchmark's plain reference
(perfbench/reference_hybrid.py) at a small size on the CPU. The program
computes the delta rule a chunk at a time; the reference runs the
recurrence token by token."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from learningorchestra_tpu.config import Settings
from learningorchestra_tpu.models import transformer as tx
from learningorchestra_tpu.models.registry import validate_hparams
from learningorchestra_tpu.ops import pallas_kernels as pk
from learningorchestra_tpu.parallel.mesh import local_mesh
from perfbench import reference_hybrid as R

T, B, CLASSES, SEED = 64, 2, 3, 5
KINDS = {"L": "linear_attention", "F": "full_attention"}
GROUPS = ("linear_attention", "attention", "mlp", "embedding", "head")


def conf_of(pattern="LLLF", periods=2, **over):
    base = {"num_hidden_layers": len(pattern) * periods,
            "layer_types": [KINDS[c] for c in pattern] * periods,
            "hidden_size": 64, "intermediate_size": 96,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "linear_num_key_heads": 4, "linear_num_value_heads": 4,
            "linear_key_head_dim": 8, "linear_value_head_dim": 16,
            "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
            "vocab_size": 48, "rms_norm_eps": 1e-6, "init": {"std": 0.05}}
    return dict(base, **over)


def config(pattern="LLLF", periods=2, **over):
    base = dict(vocab=48, d_model=64, n_heads=4, n_layers=len(pattern)
                * periods, n_classes=CLASSES, max_len=T, causal=True,
                remat=True, rms_norm=True, norm_eps=1e-6, n_kv_heads=4,
                layer_pattern=pattern, linear_heads=4, linear_key_dim=8,
                linear_value_dim=16, linear_conv=4, linear_neg_eigval=True,
                linear_chunk=16, gated_width=96,
                post_norm=True, qk_norm_whole=True, no_positions=True,
                lm_head=True, init_std=0.05, q_chunk=16, token_chunk=32)
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


def mixer_leaves(seed=SEED, gates=8.0):
    """One linear mixer's leaves by the recipe, the write gate's
    projection widened so that beta ranges over all of (0, 2)."""
    w = R.init_weights(conf_of("L", 1, init={"std": 0.2}), seed)
    W = {k[len("layers."):]: v[0, 0] for k, v in w.items()
         if k.startswith("layers.la_")}
    W["la_wb"] = W["la_wb"] * gates
    return W


def close(a, b, rel):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.abs(a - b).max()) <= rel * float(np.abs(b).max()) + 1e-12


@pytest.fixture(autouse=True)
def two_blocks_a_row(monkeypatch):
    """The mixer's loop holds 256 tokens a pass; at these sizes 32, so
    that a row of ``T`` tokens hands its state from block to block."""
    monkeypatch.setattr(tx, "_LINEAR_BLOCK", 32)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    return (rng.integers(CLASSES, 48, (B, T)).astype(np.int32),
            rng.integers(0, CLASSES, B).astype(np.int32))


# --- the chunked delta rule against the recurrence --------------------------

@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("rows", [64, 50])        # whole chunks, and not
@pytest.mark.parametrize("neg", [True, False])
def test_chunked_mixer_is_the_token_by_token_recurrence(neg, rows, chunk):
    """The whole mixer, conv to output projection: outputs and the
    gradient of every input (the layer's input and every leaf)."""
    cfg = config("L", 1, linear_neg_eigval=neg, linear_chunk=chunk,
                 max_len=rows)
    conf = conf_of("L", 1, linear_allow_neg_eigval=neg)
    W = mixer_leaves()
    rng = np.random.default_rng(rows + chunk)
    x = jnp.asarray(rng.normal(size=(rows, 64)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(rows, 64)), jnp.float32)
    z, prec = R.sizes(conf), R._precision(None)

    def program(W, x):
        o, peak = tx._linear_attention(cfg, tx.NO_AXES, x[None], W)
        return (o[0] * cot).sum(), (o[0], peak)

    def reference(W, x):
        o, peak = R.linear_attention(x, W, z, prec, True)
        return (o * cot).sum(), (o, peak)

    with jax.default_matmul_precision("highest"):
        (_, (o_p, peak_p)), g_p = jax.jit(jax.value_and_grad(
            program, (0, 1), has_aux=True))(W, x)
        (_, (o_r, peak_r)), g_r = jax.jit(jax.value_and_grad(
            reference, (0, 1), has_aux=True))(W, x)
    assert close(o_p, o_r, 1e-4)
    assert close(g_p[1], g_r[1], 1e-4)
    for name in W:      # float32 sums of a row's terms, in another order
        if name != "la_ln_g":                   # the block's norm, not here
            assert close(g_p[0][name], g_r[0][name], 5e-4), name
            assert float(jnp.abs(g_r[0][name]).max()) > 0, name
    # The program reads |S| where chunks end, the reference at every token.
    assert 0 < float(peak_p) <= float(peak_r) * (1 + 1e-5)


@pytest.mark.parametrize("neg", [True, False])
def test_delta_block_carries_its_state(neg):
    """Two blocks, the second starting from the first's state, are one
    row's recurrence; beta reaches 2 with negative eigenvalues."""
    rng = np.random.default_rng(7)
    H, dk, dv = 3, 8, 16

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(T, H, dk))).astype(np.float32) * dk ** -0.5
    k = unit(rng.normal(size=(T, H, dk))).astype(np.float32)
    v = rng.normal(size=(T, H, dv)).astype(np.float32)
    g = -rng.uniform(0.001, 1.5, (T, H)).astype(np.float32)
    beta = rng.uniform(0, 2 if neg else 1, (T, H)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want, peak = R.delta_rule(*map(jnp.asarray, (q, k, v, g, beta)))
        state = jnp.zeros((1, H, dk, dv))
        got, tops = [], []
        for half in (slice(0, 32), slice(32, 64)):
            o, state, top = tx._delta_block(
                *(jnp.asarray(a[half])[None] for a in (q, k, v, g, beta)),
                state, 16)
            got.append(o[0])
            tops.append(float(top))
    assert close(jnp.concatenate(got), want, 1e-5)
    assert 0 < max(tops) <= float(peak) * (1 + 1e-5)


def oracle_transform(A):
    """``(I + A)^-1`` by XLA's triangular solve: what the program called
    until PR 37, and the block-built transform's oracle since."""
    eye = jnp.eye(A.shape[-1], dtype=A.dtype)
    return jax.lax.linalg.triangular_solve(
        A + eye, jnp.broadcast_to(eye, A.shape), left_side=True,
        lower=True, unit_diagonal=True)


@pytest.mark.parametrize("form", ["as-run", "plain"])
@pytest.mark.parametrize("keys", ["random", "nearly-equal"])
@pytest.mark.parametrize("chunk", [16, 24, 32, 64, 128])
def test_chunk_transform_is_the_triangular_solve(chunk, keys, form):
    """The in-chunk transform alone, ``T = (I + A)^-1``, and its
    gradient with respect to what ``A`` is made of (k, g, beta; beta up
    to 2), against the triangular solve and autodiff through it. As the
    program runs it (``_chunk_transform``: the kernel's blocked
    substitution up to chunks of 64, here in interpret mode, the plain
    recursion at 128; the backward two products), and the plain block
    recursion itself under autodiff (24 is padded to 32 there). The hard
    case: a chunk of nearly equal keys that hardly decays, so that ``A``
    is nearly ``beta_i`` everywhere under the diagonal and a column of
    ``T`` does not die out below it."""
    assert pk.delta_transform_fits(chunk) == (chunk <= 64)
    transform = tx._chunk_transform if form == "as-run" else tx._block_inverse
    rng = np.random.default_rng(chunk)
    H, n, dk = 3, 2, 8
    if keys == "random":
        k = rng.normal(size=(H, n, chunk, dk))
        g = -rng.uniform(0.001, 1.5, (H, n, chunk))
        beta = rng.uniform(0, 2, (H, n, chunk))
    else:
        k = rng.normal(size=(H, n, 1, dk)) + 0.01 * rng.normal(
            size=(H, n, chunk, dk))
        g = -rng.uniform(1e-4, 1e-3, (H, n, chunk))
        beta = rng.uniform(1.0, 2.0, (H, n, chunk))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    k, g, beta = (jnp.asarray(a, jnp.float32) for a in (k, g, beta))
    cot = jnp.asarray(rng.normal(size=(H, n, chunk, chunk)), jnp.float32)
    under = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    def through(transform):
        def f(k, g, beta):
            G = jnp.cumsum(g, axis=-1)
            A = jnp.where(under, beta[..., None] * jnp.einsum(
                "hnik,hnjk->hnij", k, k) * jnp.exp(jnp.where(
                    under, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
            T = transform(A)
            return (T * cot).sum(), T
        return jax.jit(jax.value_and_grad(f, (0, 1, 2), has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, T_p), g_p = through(transform)(k, g, beta)
        (_, T_o), g_o = through(oracle_transform)(k, g, beta)
    assert T_p.shape == T_o.shape == (H, n, chunk, chunk)
    assert close(T_p, T_o, 1e-5)
    assert float(jnp.abs(jnp.triu(T_p, 1)).max()) == 0.0
    np.testing.assert_array_equal(
        np.asarray(jnp.diagonal(T_p, axis1=-2, axis2=-1)), 1.0)
    if keys == "nearly-equal":      # the last row still feels the first
        assert float(jnp.abs(T_o[..., -1, 0]).min()) > 0.0
    for got, want in zip(g_p, g_o):
        assert close(got, want, 1e-4)
        assert float(jnp.abs(want).max()) > 0


@pytest.mark.parametrize("chunk,fits", [
    (64, True), (8, True), (24, True), (128, False), (12, False), (4, False)])
def test_which_chunk_lengths_ride_the_transform_kernel(monkeypatch, chunk,
                                                       fits):
    """Whole groups of 8 rows up to 64; off the TPU (interpret mode) not
    where the operand varies over a mesh. The span attribute says what
    ``_inverse`` chose."""
    cfg = config("L", 1, linear_chunk=chunk)
    name = {True: "block_inverse_kernel", False: "block_inverse"}
    assert pk.delta_transform_fits(chunk) == fits
    assert not pk.delta_transform_fits(chunk, on_mesh=True)
    assert tx.delta_path(cfg, tx.NO_AXES) == {"delta_transform": name[fits]}
    assert tx.delta_path(cfg, tx.MESH_AXES) == {
        "delta_transform": "block_inverse"}
    monkeypatch.setattr(pk, "_interpret", lambda: False)     # as on the TPU
    assert pk.delta_transform_fits(chunk, on_mesh=True) == fits
    assert tx.delta_path(cfg, tx.MESH_AXES) == {"delta_transform": name[fits]}
    assert tx.delta_path(config("F", 1), tx.MESH_AXES) == {}


# --- the whole model against the reference ----------------------------------

@pytest.fixture(scope="module")
def both(batch):
    """Three Adam steps of the program and of the reference from the
    same seeded weights on the same batch, pattern LLLF x 2."""
    cfg, mesh, conf = config(), mesh_of("1,1,1"), conf_of()
    opt = optax.adam(1e-3)
    init, step = tx.make_fit_programs(cfg, mesh, opt, B)
    state = init(jax.random.PRNGKey(SEED))
    w = R.init_weights(conf, SEED)
    assert sorted(flat(state[0])) == sorted(w)
    for k, v in flat(state[0]).items():       # the recipe, followed twice
        np.testing.assert_allclose(np.asarray(v), np.asarray(w[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)
    loss_fn = jax.jit(jax.value_and_grad(
        tx.make_loss_fn(cfg, mesh, with_aux=True), has_aux=True))
    train = tx.make_train_step(cfg, mesh, opt)
    params, opt_state = state[0], opt.init(state[0])
    prog = []
    with jax.default_matmul_precision("highest"):
        for _ in range(3):
            (_, aux), grads = loss_fn(params, *map(jnp.asarray, batch))
            prog.append({"loss_main": float(aux["loss_main"]),
                         "loss_index": float(aux["loss_index"]),
                         "grad_norm": {k: float(v) for k, v in
                                       tx.group_norms(grads).items()},
                         "aux": jax.device_get(aux)})
            params, opt_state, _ = train(params, opt_state,
                                         *map(jnp.asarray, batch))
        probs = jax.nn.softmax(tx.forward_reference(
            params, jnp.asarray(batch[0]), cfg=cfg), -1)
        ref_probs = R.class_probs(conf, flat(params), batch[0], CLASSES)
    return (prog, R.adam_steps(conf, w, [batch] * 3, 1e-3),
            np.asarray(probs), ref_probs)


@pytest.mark.parametrize("what", ["loss_main", "loss_index", "adam.1",
                                  "adam.2", "probabilities", "state"]
                         + [f"grad.{g}" for g in GROUPS])
def test_program_matches_reference(both, what):
    prog, ref, probs, ref_probs = both
    if what.startswith("grad."):
        g = what[5:]
        assert set(prog[0]["grad_norm"]) == set(GROUPS)
        assert prog[0]["grad_norm"][g] == pytest.approx(
            ref[0]["grad_norm"][g], rel=1e-3)
        assert ref[0]["grad_norm"][g] > 1e-3        # the part is trained
    elif what.startswith("adam."):
        i = int(what[5:])
        assert prog[i]["loss_main"] == pytest.approx(ref[i]["loss_main"],
                                                     rel=1e-3)
        assert prog[i]["loss_main"] < prog[0]["loss_main"]
    elif what == "loss_index":           # no indexer: stored, and zero
        assert prog[0]["loss_index"] == ref[0]["loss_index"] == 0.0
    elif what == "probabilities":
        np.testing.assert_allclose(probs, ref_probs, atol=1e-3)
    elif what == "state":
        assert 0 < float(prog[0]["aux"]["state_absmax"]) < 1e3
    else:
        assert prog[0][what] == pytest.approx(ref[0][what], rel=2e-5)


# --- the share tied to the model --------------------------------------------

def test_model_axis_of_two_gives_the_uncut_reference(batch):
    """On a 2-device ``model`` axis (heads, MLP width and the QK-norm's
    statistic divided, parts reduced) the model is the reference's with
    every head: loss and every group's gradient norm."""
    cfg, conf = config(), conf_of()
    w = R.init_weights(conf, 2)
    mesh = mesh_of("1,2,1")
    with jax.default_matmul_precision("highest"):
        (_, aux), grads = jax.jit(jax.value_and_grad(
            tx.make_loss_fn(cfg, mesh, with_aux=True), has_aux=True))(
            tx.shard_params(nest(w), cfg, mesh), *map(jnp.asarray, batch))
    ref = R.adam_steps(conf, w, [batch], 1e-2)[0]
    assert float(aux["loss_main"]) == pytest.approx(ref["loss_main"],
                                                    rel=2e-5)
    for g, v in tx.group_norms(grads).items():
        assert float(v) == pytest.approx(ref["grad_norm"][g], rel=1e-3), g


@pytest.mark.parametrize("shares", [2, 4])
def test_linear_mixer_shares_add_up_to_the_uncut_mixer(shares):
    """Each holder is told how many heads it holds (``heads_held``) and
    given their leaves, computes their part of ``y Wo`` and adds nothing
    for the others: the
    parts of all holders add up to the reference's uncut mixer."""
    W = mixer_leaves(9)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(T, 64)),
                    jnp.float32)
    whole, _ = R.mixer(conf_of("L", 1), W, "L", x)
    held = 4 // shares
    by_head = {"la_wo": 0, "la_a_log": 0, "la_dt_bias": 0}
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for i in range(shares):
            cfg = config("L", 1, heads_held=held)
            assert cfg.lin_heads == held
            mine = slice(i * held, (i + 1) * held)
            part = {k: v if v.ndim == 1 and k not in by_head else jnp.take(
                v, jnp.arange(4)[mine], axis=by_head.get(k, 1))
                for k, v in W.items()}
            out, _ = tx._linear_attention(cfg, tx.NO_AXES, x[None], part)
            total = total + out[0]
    assert close(total, whole, 1e-4)


def test_full_layer_held_heads_are_the_reference_with_the_same_share():
    """The full layer told it holds 2 heads of 4: their part of ``o
    Wo``, with the QK-norm's statistic over the channels held, in program
    and reference alike."""
    cfg = config("F", 1, heads_held=2)
    assert (cfg.heads, cfg.kv_heads) == (2, 2)
    conf = conf_of("F", 1, num_attention_heads=2, num_key_value_heads=2,
                   published={"num_attention_heads": 4},
                   init={"std": 0.2})
    w = R.init_weights(conf, 3)
    W = {k[len("layers."):]: v[0, 0] for k, v in w.items()
         if k.startswith("layers.")}
    assert W["wq"].shape == (64, 2, 16)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(T, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, _ = tx._attention(cfg, tx.NO_AXES, x[None], W, jnp.arange(T))
        want = R.mixer(conf, W, "F", x)
    assert close(out[0], want, 1e-4)


def test_vocabulary_slices_concatenate_to_the_whole_head(batch):
    cfg, conf = config(), conf_of()
    w = R.init_weights(conf, 4)
    whole = np.asarray(R.head_logits(conf, w, batch[0]))
    params = nest(w)
    with jax.default_matmul_precision("highest"):
        x, _ = tx._trunk(params, jnp.asarray(batch[0]), cfg, tx.NO_AXES)
        h = tx._rms(x, params["lnf_g"], 1e-6)
        parts = [h @ params["head_w"][:, i * 6:(i + 1) * 6]
                 for i in range(8)]
    np.testing.assert_allclose(np.concatenate(parts, -1), whole,
                               rtol=2e-3, atol=2e-4)


# --- what the options refuse, and what the layout is -------------------------

def test_seq_axis_with_a_linear_layer_raises(batch):
    cfg, mesh = config(), mesh_of("1,1,2")
    params = tx.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="state does not run across a "
                                         "sequence axis of 2"):
        tx.make_loss_fn(cfg, mesh)(params, *map(jnp.asarray, batch))


def test_each_kind_is_stacked_on_its_own_and_one_period_is_compiled():
    cfg = config()
    shapes = tx._leaf_shapes(cfg)["layers"]
    assert shapes["la_wq"][0] == (2, 3, 64, 4, 8)       # periods, L's
    assert shapes["wq"][0] == (2, 1, 64, 4, 16)         # periods, F's
    assert shapes["w_gate"][0] == (2, 4, 64, 96)        # periods, all
    assert shapes["q_norm"][0] == (2, 1, 4, 16)
    assert "pos" not in tx._leaf_shapes(cfg)
    assert cfg.n_full == 2 and tx.has_options(cfg)
    # A model shallower than the period is the period cut to it.
    cut = config("LLLF", 1, n_layers=2)
    assert cut.pattern == "LL" and cut.n_full == 0
    assert "wq" not in tx._leaf_shapes(cut)["layers"]
    # The depth changes a leading axis and nothing of the traced body.
    def lowered(periods):
        c = config("LLLF", periods)
        params = jax.eval_shape(lambda k: tx.init_params(k, c),
                                jax.random.PRNGKey(0))
        return jax.jit(lambda p, t: tx._trunk(p, t, c, tx.NO_AXES)[0]).lower(
            params, jax.ShapeDtypeStruct((1, T), jnp.int32)).as_text()
    assert lowered(2).count("stablehlo.while") == \
        lowered(4).count("stablehlo.while")


@pytest.mark.parametrize("over,names", [
    ({"layer_pattern": "LXF"}, "made of F"),
    ({"n_layers": 6}, "neither a multiple"),
    ({"linear_heads": 0}, "needs linear_heads"),
    ({"heads_held": 5}, "more than the 4 heads"),
    ({"n_kv_heads": 2, "heads_held": 1}, "no whole share"),
    ({"qk_norm": True}, "two kinds of one norm"),
    ({"rope_theta": 1e4}, "exclude each other"),
])
def test_config_options_that_exclude_or_need_each_other(over, names):
    with pytest.raises(ValueError, match=names):
        config(**over)


@pytest.mark.parametrize("bad,names", [
    ({"arch": {"layer_pattern": "LLXF"}}, "arch.layer_pattern"),
    ({"arch": {"linear_chunk": 0}}, "arch.linear_chunk"),
    ({"arch": {"heads_held": 0}}, "arch.heads_held"),
    ({"arch": {"linear_head": 4}}, "arch.linear_head"),
    ({"arch": {"post_norm": "yes"}}, "arch.post_norm"),
])
def test_validate_hparams_names_the_bad_key(bad, names):
    with pytest.raises(ValueError, match=names):
        validate_hparams("tx", bad)
    validate_hparams("tx", {"arch": {"layer_pattern": "LLLF",
                                     "linear_heads": 4, "heads_held": 2}})


# --- through REST -----------------------------------------------------------

ARCH_HP = {"d_model": 32, "n_heads": 4, "n_layers": 4, "vocab": 24,
           "train_steps": 6, "batch": 8, "lr": 1e-2, "causal": True,
           "remat": True,
           "arch": {"rms_norm": True, "norm_eps": 1e-6, "n_kv_heads": 4,
                    "layer_pattern": "LLLF", "linear_heads": 4,
                    "linear_key_dim": 8, "linear_value_dim": 16,
                    "linear_conv": 4, "linear_neg_eigval": True,
                    "linear_chunk": 8, "gated_width": 48,
                    "post_norm": True, "qk_norm_whole": True,
                    "no_positions": True,
                    "heads_held": 2, "q_chunk": 8, "lm_head": True,
                    "init_std": 0.1}}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from learningorchestra_tpu.client import Context, DatabaseApi, Model
    from learningorchestra_tpu.models import persistence
    from learningorchestra_tpu.serving.app import App

    tmp = tmp_path_factory.mktemp("txhybrid")
    cfg = Settings()
    cfg.store_root, cfg.image_root = str(tmp / "store"), str(tmp / "img")
    cfg.port, cfg.persist = 0, True
    cfg.mesh_shape = "4,2,1"            # dp x tp; the state needs seq 1
    flat_bytes, persistence.FLAT_BYTES = persistence.FLAT_BYTES, 1
    app = App(cfg, recover=False)
    server = app.serve(background=True)
    ctx = Context(f"http://127.0.0.1:{server.port}", poll_seconds=0.1,
                  timeout=600)
    rng = np.random.default_rng(3)
    for name, n in (("hy_train", 64), ("hy_test", 12)):
        labels = rng.integers(0, 3, n)
        toks = rng.integers(3, 24, (n, 16))
        toks[:, ::2] = 3 + labels[:, None]          # the topic shows
        cols = {f"t{j:02d}": toks[:, j].astype(np.int64) for j in range(16)}
        cols["label"] = labels.astype(np.int64)
        app.store.create(name, columns=cols, finished=True)
    yield app, DatabaseApi(ctx), Model(ctx), tmp
    server.stop()
    persistence.FLAT_BYTES = flat_bytes


def test_rest_fit_with_the_hybrid_block(served):
    from learningorchestra_tpu.utils import tracing

    app, db, model, _ = served
    out = model.create_model("hy_train", "hy_test", "hyp", ["tx"], "label",
                             hparams={"tx": ARCH_HP})
    rep = out["result"][0]
    assert rep["classifier"] == "tx" and "error" not in rep, rep
    meta = db.read_file("hyp_tx", limit=1)[0]
    assert meta["finished"] is True and not meta.get("error")
    assert len(meta["loss"]) == 6 and meta["loss_index"] == [0.0] * 6
    assert set(meta["grad_norm"]) == set(GROUPS)
    assert 0 < meta["state_absmax"] < 1e3
    assert meta["loss"][-1] < meta["loss"][0]
    rows = db.read_file("hyp_tx", skip=1, limit=12)
    assert len(rows) == 12
    for r in rows:
        assert len(r["probability"]) == 3
        assert r["prediction"] == int(np.argmax(r["probability"]))
    steps = next(d for d in tracing.recent_span_docs()
                 if d["name"] == "fit.tx.steps")["attrs"]
    assert steps["layer_pattern"] == "LLLF" and steps["heads_held"] == 2
    assert steps["linear_chunk"] == 8 and steps["state_absmax"] > 0
    assert steps["attn_kernel"] == 0.0          # heads of 8: the plain body
    # Off the TPU a fit's transform (inside the mesh program) is the
    # plain recursion; the unsharded predict pass rides the kernel.
    assert steps["delta_transform"] == "block_inverse"
    predict = next(d for d in tracing.recent_span_docs()
                   if d["name"] == "fit.tx.predict")["attrs"]
    assert predict["delta_transform"] == "block_inverse_kernel"
    assert app._metrics_doc()["tx"]["state_absmax"] > 0


def test_saved_hybrid_model_reloads_and_predicts_the_same(served):
    app, db, model, tmp = served
    weights = R.load_saved(str(tmp / "store" / "_models" / "hyp_tx"))
    assert weights["layers.la_wq"].shape == (1, 3, 32, 2, 8)
    assert weights["layers.wq"].shape == (1, 1, 32, 2, 8)
    man, _ = app.builder.registry.load("hyp_tx")
    assert man["hparams"]["arch"] == ARCH_HP["arch"]
    model.predict("hyp_tx", "hy_test", "hyp_again", wait=True)
    first = db.read_file("hyp_tx", skip=1, limit=12)
    again = db.read_file("hyp_again", skip=1, limit=12)
    for a, b in zip(first, again):
        np.testing.assert_allclose(a["probability"], b["probability"],
                                   rtol=1e-5, atol=1e-6)


def test_rest_names_a_bad_key_of_the_hybrid_block(served):
    _, _, model, _ = served
    bad = dict(ARCH_HP, arch=dict(ARCH_HP["arch"], linear_headz=2))
    with pytest.raises(Exception, match="arch.linear_headz"):
        model.create_model("hy_train", "hy_test", "hybad", ["tx"], "label",
                           hparams={"tx": bad})
