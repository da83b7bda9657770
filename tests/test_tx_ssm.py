"""The tx family's Mamba-2 and expert-layer options (models/transformer.py:
``M`` and ``E`` layer kinds, each layer one sublayer, sigmoid routing
with the correction bias, the routed scaling factor, relu^2 experts, the
shared expert) against the benchmark's plain reference
(perfbench/reference_ssm.py) at a small size on the CPU. The program
runs the scan a chunk at a time; the reference runs the recurrence
token by token, and computes each expert over the tokens routed to it.
"""

import hashlib
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
from perfbench import reference_ssm as R

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, B, CLASSES, SEED = 64, 2, 3, 5
GROUPS = ("ssm", "experts", "shared_expert", "router", "attention",
          "embedding", "head")


def conf_of(pattern="MEM*E", periods=1, **over):
    base = {"num_hidden_layers": len(pattern) * periods,
            "hybrid_override_pattern": pattern * periods,
            "hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
            "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
            "n_groups": 2, "conv_kernel": 4, "n_routed_experts": 8,
            "num_local_experts": 8, "experts_first": 0,
            "num_experts_per_tok": 2, "moe_intermediate_size": 24,
            "moe_shared_expert_intermediate_size": 32, "n_shared_experts": 1,
            "routed_scaling_factor": 2.5, "norm_topk_prob": True,
            "vocab_size": 48, "layer_norm_epsilon": 1e-5,
            "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
            "use_conv_bias": True, "n_group": 1, "init": {"std": 0.05}}
    return dict(base, **over)


def config(pattern="MEM*E", periods=1, **over):
    base = dict(vocab=48, d_model=64, n_heads=4,
                n_layers=len(pattern) * periods, n_classes=CLASSES,
                max_len=T, causal=True, remat=True, rms_norm=True,
                norm_eps=1e-5, n_kv_heads=2, head_dim=16, no_positions=True,
                layer_pattern=pattern.replace("*", "F"), ssm_heads=4,
                ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_conv=4,
                ssm_chunk=8, n_experts=8, experts_per_token=2,
                expert_width=24, experts_held=8, router_sigmoid=True,
                routed_scale=2.5, relu2_experts=True, shared_width=32,
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


def leaves_of(kind, seed=SEED, std=0.2, **over):
    """One sublayer's leaves by the recipe, at a std at which the scan
    and the routing are far from trivial."""
    pattern = {"M": "M", "E": "E"}[kind]
    w = R.init_weights(conf_of(pattern, init={"std": std}, **over), seed)
    return {k[len("layers."):]: v[0, 0] for k, v in w.items()
            if k.startswith("layers.")}


def close(a, b, rel):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.abs(a - b).max()) <= rel * float(np.abs(b).max()) + 1e-12


@pytest.fixture(autouse=True)
def two_blocks_a_row(monkeypatch):
    """The mixer's loop holds 256 tokens a pass; at these sizes 16, so
    that a row hands its state and its conv's tail from block to block."""
    monkeypatch.setattr(tx, "_SSM_BLOCK", 16)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    return (rng.integers(CLASSES, 48, (B, T)).astype(np.int32),
            rng.integers(0, CLASSES, B).astype(np.int32))


# --- the chunked scan against the recurrence --------------------------------

@pytest.mark.parametrize("chunk", [8, 16, 12])
@pytest.mark.parametrize("rows", [64, 50])        # whole chunks, and not
def test_chunked_mixer_is_the_token_by_token_recurrence(rows, chunk):
    """The whole mixer, projections to output projection: outputs, the
    largest |state|, and the gradient of every input (the layer's input
    and every leaf), at chunk lengths that do and do not divide the row
    and the loop's block."""
    cfg = config("M", ssm_chunk=chunk, max_len=rows)
    W = leaves_of("M")
    W.pop("ssm_ln_g")                            # the layer's norm, not here
    rng = np.random.default_rng(rows + chunk)
    h = jnp.asarray(rng.normal(size=(rows, 64)), jnp.float32)
    cot = jnp.asarray(rng.normal(size=(rows, 64)), jnp.float32)
    z, prec = R.sizes(conf_of("M")), R._precision(None)

    def program(W, h):
        o, peak = tx._ssm_mixer(cfg, tx.NO_AXES, h[None], W)
        return (o[0] * cot).sum(), (o[0], peak)

    def reference(W, h):
        o, peak = R.ssm_mixer(h, W, z, prec, True)
        return (o * cot).sum(), (o, peak)

    with jax.default_matmul_precision("highest"):
        (_, (o_p, peak_p)), g_p = jax.jit(jax.value_and_grad(
            program, (0, 1), has_aux=True))(W, h)
        (_, (o_r, peak_r)), g_r = jax.jit(jax.value_and_grad(
            reference, (0, 1), has_aux=True))(W, h)
    assert close(o_p, o_r, 1e-4)
    assert close(g_p[1], g_r[1], 1e-4)
    for name in W:      # float32 sums of a row's terms, in another order
        assert close(g_p[0][name], g_r[0][name], 5e-4), name
        assert float(jnp.abs(g_r[0][name]).max()) > 0, name
    # The program reads |S| where chunks end, the reference at every token.
    assert 0 < float(peak_p) <= float(peak_r) * (1 + 1e-5)


def test_ssd_block_carries_its_state():
    """Two blocks, the second starting from the first's state, are one
    row's recurrence; the D skip is the mixer's, not the scan's."""
    rng = np.random.default_rng(7)
    H, P, G, N = 4, 8, 2, 16
    x = rng.normal(size=(T, H, P)).astype(np.float32)
    dt = rng.uniform(0.001, 0.5, (T, H)).astype(np.float32)
    A = -rng.uniform(1.0, 4.0, H).astype(np.float32)
    b, c = (rng.normal(size=(T, G, N)).astype(np.float32) for _ in range(2))
    with jax.default_matmul_precision("highest"):
        want, peak = R.ssm_scan(*map(jnp.asarray, (x, dt, A, b, c)))
        state, got, tops = jnp.zeros((1, H, P, N)), [], []
        for half in (slice(0, 32), slice(32, 64)):
            y, state, top = tx._ssd_block(
                jnp.asarray(x[half])[None], jnp.asarray(dt[half])[None],
                jnp.asarray(dt[half] * A)[None], jnp.asarray(b[half])[None],
                jnp.asarray(c[half])[None], state, 16)
            got.append(y[0])
            tops.append(float(top))
    assert close(jnp.concatenate(got), want, 1e-5)
    assert 0 < max(tops) <= float(peak) * (1 + 1e-5)


# --- the expert layer against the per-expert form ----------------------------

def test_expert_layer_is_the_per_expert_form():
    """Sigmoid scores, the top-k by score plus the correction bias (here
    not zero, so that it moves the choice), the chosen scores
    renormalised and scaled, relu^2 experts and the shared expert: the
    program's gate-scaled dense form against the reference's gather of
    each expert's tokens; outputs and every gradient."""
    cfg = config("E")
    W = leaves_of("E")
    W.pop("ln2_g")
    W["router_bias"] = jnp.linspace(-0.3, 0.3, 8)
    h = jnp.asarray(np.random.default_rng(3).normal(size=(T, 64)),
                    jnp.float32)
    cot = jnp.asarray(np.random.default_rng(4).normal(size=(T, 64)),
                      jnp.float32)
    z, prec = R.sizes(conf_of("E")), R._precision(None)

    def program(W, h):
        out, counts, moe, _ = tx._experts(cfg, tx.NO_AXES, h[None], W)
        return (out[0] * cot).sum(), (out[0], counts, moe)

    def reference(W, h):
        out = R.experts(h, W, z, prec)
        return (out * cot).sum(), out

    with jax.default_matmul_precision("highest"):
        (_, (o_p, counts, moe)), g_p = jax.jit(jax.value_and_grad(
            program, (0, 1), has_aux=True))(W, h)
        (_, o_r), g_r = jax.jit(jax.value_and_grad(
            reference, (0, 1), has_aux=True))(W, h)
        s = jax.nn.sigmoid(h @ W["router"])
    assert close(o_p, o_r, 1e-5)
    assert close(g_p[1], g_r[1], 1e-4)
    for name in W:
        if name == "router_bias":    # a buffer: no gradient trains it
            assert float(jnp.abs(g_p[0][name]).max()) == 0.0
            assert float(jnp.abs(g_r[0][name]).max()) == 0.0
        else:
            assert close(g_p[0][name], g_r[0][name], 1e-4), name
    # The bias moved the choice away from the plain top-k of the scores.
    biased = jax.lax.top_k(s + W["router_bias"], 2)[1]
    assert (jnp.sort(biased, -1) != jnp.sort(jax.lax.top_k(s, 2)[1], -1)).any()
    assert float(counts.sum()) == T * 2 and float(moe[2]) == 0.0


@pytest.mark.parametrize("shares", [2, 4, 8])
def test_expert_shares_add_up_to_the_uncut_layer(shares):
    """Each holder is told which experts it holds and computes their part
    plus the shared expert, which every holder computes alike: the
    holders' outputs, with the shared expert counted once, add up to the
    reference's layer with every expert held."""
    W = leaves_of("E", seed=11)
    W.pop("ln2_g")
    h = jnp.asarray(np.random.default_rng(5).normal(size=(T, 64)),
                    jnp.float32)
    whole = R.sublayer(conf_of("E"), W, "E", h, whole=True)
    shared = R.sublayer(conf_of("E", num_local_experts=0), dict(
        W, we_up=W["we_up"][:0], we_down=W["we_down"][:0]), "E", h)
    per = 8 // shares
    total = -(shares - 1) * shared
    with jax.default_matmul_precision("highest"):
        for i in range(shares):
            cfg = config("E", experts_first=i * per, experts_held=per)
            mine = dict(W, **{k: W[k][i * per:(i + 1) * per]
                              for k in ("we_up", "we_down")})
            out, counts, moe, _ = tx._experts(cfg, tx.NO_AXES, h[None], mine)
            total = total + out[0]
            assert moe[2] == 0 and counts.sum() == moe[0] - moe[1]
    assert close(total, whole, 1e-5)


# --- the whole model against the reference ----------------------------------

@pytest.fixture(scope="module")
def both(batch):
    """Three Adam steps of the program and of the reference from the
    same seeded weights on the same batch, pattern M E M * E x 2."""
    tx._SSM_BLOCK, block = 16, tx._SSM_BLOCK
    try:
        cfg, mesh, conf = config(periods=2), mesh_of("1,1,1"), conf_of(
            periods=2)
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
                             "grad_norm": {k: float(v) for k, v in
                                           tx.group_norms(grads).items()},
                             "aux": jax.device_get(aux)})
                params, opt_state, _ = train(params, opt_state,
                                             *map(jnp.asarray, batch))
            probs = jax.nn.softmax(tx.forward_reference(
                params, jnp.asarray(batch[0]), cfg=cfg), -1)
            ref_probs = R.class_probs(conf, flat(params), batch[0], CLASSES)
        ref = R.adam_steps(conf, w, [batch] * 3, 1e-3)
    finally:
        tx._SSM_BLOCK = block
    return prog, ref, np.asarray(probs), ref_probs


@pytest.mark.parametrize("what", ["adam.1", "adam.2", "probabilities",
                                  "counters", "loss_main"]
                         + [f"grad.{g}" for g in GROUPS]
                         + ["grad.ssm_wbc", "grad.ssm_a_log",
                            "grad.ssm_dt_bias"])
def test_program_matches_reference(both, what):
    prog, ref, probs, ref_probs = both
    if what.startswith("grad."):
        g = what[5:]
        assert set(prog[0]["grad_norm"]) == set(ref[0]["grad_norm"])
        assert set(GROUPS) < set(prog[0]["grad_norm"])
        assert prog[0]["grad_norm"][g] == pytest.approx(
            ref[0]["grad_norm"][g], rel=1e-3)
        assert ref[0]["grad_norm"][g] > 0            # the part is trained
    elif what.startswith("adam."):
        i = int(what[5:])
        assert prog[i]["loss_main"] == pytest.approx(ref[i]["loss_main"],
                                                     rel=1e-3)
        assert prog[i]["loss_main"] < prog[0]["loss_main"]
    elif what == "probabilities":
        np.testing.assert_allclose(probs, ref_probs, atol=1e-3)
    elif what == "counters":
        aux = prog[0]["aux"]
        assert 0 < float(aux["state_absmax"]) < 1e3
        assert float(aux["moe"][2]) == 0.0         # nothing dropped
        # 4 expert layers, each token 2 assignments, every expert held
        assert float(aux["experts"].sum()) == 4 * B * T * 2
    else:
        assert prog[0][what] == pytest.approx(ref[0][what], rel=2e-5)


def test_model_axis_of_two_gives_the_uncut_reference(batch):
    """On a 2-device ``model`` axis (Mamba-2 heads with their B / C groups,
    the held experts and the shared expert's width divided, parts
    reduced) the model is the reference's: loss and every group's and
    every Mamba-2 leaf's gradient norm."""
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
    norms = tx.group_norms(grads)
    assert set(norms) == set(ref["grad_norm"])
    for g, v in norms.items():
        assert float(v) == pytest.approx(ref["grad_norm"][g], rel=1e-3), g


# --- what the options refuse, and what the layout is -------------------------

def test_seq_axis_with_a_mamba_layer_raises(batch):
    cfg, mesh = config(), mesh_of("1,1,2")
    params = tx.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="Mamba-2 layer's state does not "
                                         "run across a sequence axis of 2"):
        tx.make_loss_fn(cfg, mesh)(params, *map(jnp.asarray, batch))


def test_each_kind_is_stacked_on_its_own_and_every_layer_is_one_sublayer():
    cfg = config(periods=2)
    shapes = tx._leaf_shapes(cfg)["layers"]
    assert shapes["ssm_wx"][0] == (2, 2, 64, 4, 8)      # periods, M's
    assert shapes["we_up"][0] == (2, 2, 8, 64, 24)      # periods, E's
    assert shapes["wq"][0] == (2, 1, 64, 4, 16)         # periods, F's
    assert shapes["sh_up"][0] == (2, 2, 64, 32)
    assert "we_gate" not in shapes and "ln1_b" not in shapes
    assert cfg.one_sublayer and cfg.has_state and cfg.n_full == 2
    assert tx.ssm_path(cfg) == {"ssm_path": "chunked", "ssm_chunk": 8}
    assert tx.ssm_path(config("F", layer_pattern="")) == {}


@pytest.mark.parametrize("over,names", [
    ({"layer_pattern": "MXE"}, "made of F"),
    ({"ssm_heads": 0}, "needs ssm_heads"),
    ({"ssm_heads": 3}, "a multiple of ssm_groups"),
    ({"n_experts": 0, "router_sigmoid": False, "relu2_experts": False,
      "routed_scale": 1.0, "shared_width": 0}, "E layer needs n_experts"),
    ({"layer_pattern": "MF", "n_layers": 2, "n_experts": 0,
      "router_sigmoid": False, "relu2_experts": False, "routed_scale": 1.0},
     "options of the expert layer"),
    ({"causal": False, "lm_head": False}, "needs causal attention"),
    ({"relu2_experts": False}, "shared expert has the relu"),
])
def test_config_options_that_exclude_or_need_each_other(over, names):
    with pytest.raises(ValueError, match=names):
        config(**over)


@pytest.mark.parametrize("bad,names", [
    ({"arch": {"layer_pattern": "MEXF"}}, "arch.layer_pattern"),
    ({"arch": {"ssm_chunk": 0}}, "arch.ssm_chunk"),
    ({"arch": {"routed_scale": -1.0}}, "arch.routed_scale"),
    ({"arch": {"relu2_experts": 1}}, "arch.relu2_experts"),
    ({"arch": {"ssm_headz": 4}}, "arch.ssm_headz"),
])
def test_validate_hparams_names_the_bad_key(bad, names):
    with pytest.raises(ValueError, match=names):
        validate_hparams("tx", bad)
    validate_hparams("tx", {"arch": {"layer_pattern": "MEMEMFEME",
                                     "ssm_heads": 64, "shared_width": 3712,
                                     "router_sigmoid": True}})


# --- the cells that came before keep their leaves and draws ------------------

#: What the parent of the Mamba-2 / expert-layer options made of the two
#: earlier tx configurations: the leaf order and a digest of (leaf
#: order, every shape) at the cells' sizes, and each leaf's sum of the
#: init draws of the tiny twins at PRNGKey(7).
EARLIER = {
    "perfbench/configs/keye-vl-2.0-30b-a3b.json": ("77da4247e90900f6", [
        "embed", "head_w", "lnf_g", "layers.ix_kn_b", "layers.ix_kn_g",
        "layers.ix_wk", "layers.ix_wq", "layers.ix_ww", "layers.k_norm",
        "layers.ln1_g", "layers.ln2_g", "layers.q_norm", "layers.router",
        "layers.we_down", "layers.we_gate", "layers.we_up", "layers.wk",
        "layers.wo", "layers.wq", "layers.wv"]),
    "perfbench/configs/olmo-hybrid-7b.json": ("5b50c907fc67ab52", [
        "embed", "head_w", "lnf_g", "layers.k_norm", "layers.la_a_log",
        "layers.la_ck", "layers.la_cq", "layers.la_cv", "layers.la_dt_bias",
        "layers.la_gn_g", "layers.la_ln_g", "layers.la_wa", "layers.la_wb",
        "layers.la_wk", "layers.la_wo", "layers.la_wq", "layers.la_wv",
        "layers.la_wz", "layers.ln1_g", "layers.ln2_g", "layers.q_norm",
        "layers.w_down", "layers.w_gate", "layers.w_up", "layers.wk",
        "layers.wo", "layers.wq", "layers.wv"]),
}
EARLIER_DRAWS = {
    "tests/perfbench/tiny/tx/bench/configs/tiny-tx.json": {
        "embed": -9.033387, "head_w": 0.8556848, "layers.ix_kn_b": 0.0,
        "layers.ix_kn_g": 16.0, "layers.ix_wk": 4.84847,
        "layers.ix_wq": -20.69821, "layers.ix_ww": 1.94455,
        "layers.k_norm": 32.0, "layers.ln1_g": 128.0, "layers.ln2_g": 128.0,
        "layers.q_norm": 32.0, "layers.router": -8.767022,
        "layers.we_down": -30.33754, "layers.we_gate": 24.58639,
        "layers.we_up": -6.946921, "layers.wk": -15.48369,
        "layers.wo": 14.70642, "layers.wq": -4.346189, "layers.wv": 19.91758,
        "lnf_g": 64.0},
    "tests/perfbench/tiny/hybrid/bench/configs/tiny-hybrid.json": {
        "embed": -1.071082, "head_w": -1.673879, "layers.k_norm": 32.0,
        "layers.la_a_log": 9.787992, "layers.la_ck": -1.780242,
        "layers.la_cq": -0.7049159, "layers.la_cv": 0.01611718,
        "layers.la_dt_bias": -24.14913, "layers.la_gn_g": 48.0,
        "layers.la_ln_g": 192.0, "layers.la_wa": -0.4972678,
        "layers.la_wb": -0.6783898, "layers.la_wk": 0.6372306,
        "layers.la_wo": 6.911092, "layers.la_wq": -2.266887,
        "layers.la_wv": -0.4853508, "layers.la_wz": -0.5697386,
        "layers.ln1_g": 64.0, "layers.ln2_g": 256.0, "layers.q_norm": 32.0,
        "layers.w_down": -8.972662, "layers.w_gate": 2.671797,
        "layers.w_up": 5.99419, "layers.wk": 3.55456, "layers.wo": 0.3711063,
        "layers.wq": 0.4846778, "layers.wv": 2.615376, "lnf_g": 64.0},
}


def _posted(path):
    """The TxConfig a cell's configuration file makes, as the fit does."""
    with open(os.path.join(REPO, path), encoding="utf-8") as fh:
        conf = json.load(fh)
    hp = conf["families"]["tx"]
    return tx.TxConfig(vocab=hp["vocab"], d_model=hp["d_model"],
                       n_heads=hp["n_heads"], n_layers=hp["n_layers"],
                       d_ff=128, n_classes=conf["data"]["num_classes"],
                       max_len=conf["data"]["seq_len"], causal=hp["causal"],
                       remat=hp["remat"], **hp["arch"])


@pytest.mark.parametrize("path", sorted(EARLIER) + sorted(EARLIER_DRAWS))
def test_earlier_cells_keep_their_leaves_shapes_and_draws(path):
    cfg = _posted(path)
    shapes = jax.eval_shape(lambda k: tx.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    order = tx.leaf_order(cfg)
    if path in EARLIER:
        digest, want = EARLIER[path]
        assert order == want
        flat_shapes = {jax.tree_util.keystr(p): list(v.shape) for p, v in
                       jax.tree_util.tree_flatten_with_path(shapes)[0]}
        got = hashlib.sha256(json.dumps([order, flat_shapes], sort_keys=True)
                             .encode()).hexdigest()[:16]
        assert got == digest
        return
    params = flat(tx.init_params(jax.random.PRNGKey(7), cfg))
    assert sorted(order) == sorted(EARLIER_DRAWS[path])
    for name, want in EARLIER_DRAWS[path].items():
        got = float(np.asarray(params[name], np.float64).sum())
        assert got == pytest.approx(want, rel=1e-5, abs=1e-6), name


# --- through REST -----------------------------------------------------------

ARCH_HP = {"d_model": 32, "n_heads": 4, "n_layers": 4, "vocab": 24,
           "train_steps": 6, "batch": 8, "lr": 1e-2, "causal": True,
           "remat": True,
           "arch": {"rms_norm": True, "norm_eps": 1e-5, "n_kv_heads": 2,
                    "head_dim": 8, "no_positions": True,
                    "layer_pattern": "MEFE", "ssm_heads": 4,
                    "ssm_head_dim": 8, "ssm_state": 8, "ssm_groups": 2,
                    "ssm_conv": 4, "ssm_chunk": 8, "n_experts": 8,
                    "experts_per_token": 2, "expert_width": 16,
                    "experts_first": 2, "experts_held": 4,
                    "router_sigmoid": True, "routed_scale": 2.5,
                    "relu2_experts": True, "shared_width": 16,
                    "q_chunk": 8, "lm_head": True, "init_std": 0.1}}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from learningorchestra_tpu.client import Context, DatabaseApi, Model
    from learningorchestra_tpu.models import persistence
    from learningorchestra_tpu.serving.app import App

    tmp = tmp_path_factory.mktemp("txssm")
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
    for name, n in (("ss_train", 64), ("ss_test", 12)):
        labels = rng.integers(0, 3, n)
        toks = rng.integers(3, 24, (n, 16))
        toks[:, ::2] = 3 + labels[:, None]          # the topic shows
        cols = {f"t{j:02d}": toks[:, j].astype(np.int64) for j in range(16)}
        cols["label"] = labels.astype(np.int64)
        app.store.create(name, columns=cols, finished=True)
    yield app, DatabaseApi(ctx), Model(ctx), tmp
    server.stop()
    persistence.FLAT_BYTES = flat_bytes


def test_rest_fit_with_mamba_and_expert_layers(served):
    from learningorchestra_tpu.utils import tracing

    app, db, model, _ = served
    out = model.create_model("ss_train", "ss_test", "ssm", ["tx"], "label",
                             hparams={"tx": ARCH_HP})
    rep = out["result"][0]
    assert rep["classifier"] == "tx" and "error" not in rep, rep
    meta = db.read_file("ssm_tx", limit=1)[0]
    assert meta["finished"] is True and not meta.get("error")
    assert len(meta["loss"]) == 6 and meta["loss_index"] == [0.0] * 6
    assert set(GROUPS) < set(meta["grad_norm"])
    assert "ssm_a_log" in meta["grad_norm"]
    assert 0 < meta["state_absmax"] < 1e3 and meta["dropped_tokens"] == 0
    assert len(meta["expert_tokens"]) == 4 and meta["moe_imbalance"] >= 1
    assert meta["loss"][-1] < meta["loss"][0]
    rows = db.read_file("ssm_tx", skip=1, limit=12)
    assert len(rows) == 12
    for r in rows:
        assert len(r["probability"]) == 3
        assert r["prediction"] == int(np.argmax(r["probability"]))
    steps = next(d for d in tracing.recent_span_docs()
                 if d["name"] == "fit.tx.steps")["attrs"]
    assert steps["layer_pattern"] == "MEFE" and steps["ssm_chunk"] == 8
    assert steps["ssm_path"] == "chunked" and steps["state_absmax"] > 0
    assert steps["moe_imbalance"] >= 1 and "linear_chunk" not in steps
    predict = next(d for d in tracing.recent_span_docs()
                   if d["name"] == "fit.tx.predict")["attrs"]
    assert predict["ssm_path"] == "chunked"


def test_saved_ssm_model_reloads_and_predicts_the_same(served):
    app, db, model, tmp = served
    weights = R.load_saved(str(tmp / "store" / "_models" / "ssm_tx"))
    assert weights["layers.ssm_wbc"].shape == (1, 1, 32, 2, 2, 8)
    assert weights["layers.we_up"].shape == (1, 2, 4, 32, 16)
    assert float(np.abs(weights["layers.router_bias"]).max()) == 0.0
    man, _ = app.builder.registry.load("ssm_tx")
    assert man["hparams"]["arch"] == ARCH_HP["arch"]
    model.predict("ssm_tx", "ss_test", "ssm_again", wait=True)
    first = db.read_file("ssm_tx", skip=1, limit=12)
    again = db.read_file("ssm_again", skip=1, limit=12)
    for a, b in zip(first, again):
        np.testing.assert_allclose(a["probability"], b["probability"],
                                   rtol=1e-5, atol=1e-6)
