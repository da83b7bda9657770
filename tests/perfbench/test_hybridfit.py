"""The ``hybridfit`` cell's yardstick: its costs, readers, configuration
file and comparison, and a whole run of its tiny twin on the CPU
(``tiny/hybrid``: the look for a chip skipped, everything else as on the
chip), sound and with the timed path broken underneath."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY = os.path.join(REPO, "tests", "perfbench", "tiny", "hybrid")
CELL = "olmo-hybrid-7b.hybridfit"
GROUPS = {"linear_attention", "attention", "mlp", "embedding", "head"}
METRICS = ("steps_s", "finish_model_s", "device_idle", "linear_attn_s",
           "linear_attn_roofline", "full_attn_s", "hybridfit_mfu", "init_s",
           "finish_store_s")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def real_cell():
    from perfbench import cells

    return cells.load_cell(CELL, REPO)


def _spec(name):
    with open(os.path.join(REPO, "perfbench", "layer_metrics",
                           name + ".json"), encoding="utf-8") as fh:
        return json.load(fh)


# --- costs, the cell's files ------------------------------------------------

def test_costs_count_the_equations_at_the_published_sizes(real_cell):
    from perfbench import costs_hybrid

    s = costs_hybrid.shapes(real_cell["config"])
    assert (s["n_linear"], s["n_full"], s["H"], s["Hl"], s["D"]) == (
        3, 1, 15, 15, 128)
    per = costs_hybrid.token_forward_ops(s)
    millions = {k: round(v / 1e6, 2) for k, v in per.items()}
    assert millions == {
        "linear_projections": 88.70,     # 2 x 3840 x 15 x (192 + 576 + 2)
        "linear_conv": 0.05, "linear_recurrence": 1.66,   # 6 x 15 x 96 x 192
        "full_projections": 58.98,       # 2 x 3840 x 128 x 60
        "full_attention": 31.46,         # the triangle: 4 x 1920 x 4096.5
        "mlp": 253.62, "head": 96.34}
    one_step = dict(s, steps=1, n_test=0)
    assert costs_hybrid.fit_ops(one_step) == pytest.approx(36.19e12, rel=1e-3)
    ops, nbytes = costs_hybrid.linear_attention_work(s)
    assert ops == 3 * 8192 * 3 * 15 * 6 * 96 * 192
    assert nbytes == 3 * 4 * 8192 * 3 * 15 * (96 + 96 + 3 * 192 + 2)
    least, bound = costs_hybrid.least_seconds((ops, nbytes), PEAKS)
    assert bound == "bytes" and 0.004 < least < 0.005
    fit = costs_hybrid.fit_linear_attention_work(s)
    assert fit[0] == pytest.approx((s["steps"] + 16 / 3) * ops)


def test_real_cell_resolves_and_limits_name_what_compare_reads(real_cell):
    from perfbench import cells

    assert real_cell["traffic"]["kind"] == "hybridfit"
    assert [m["name"] for m in real_cell["end_to_end"]] == ["sweep_s",
                                                            "setup_s"]
    assert [m["name"] for m in real_cell["per_layer"]] == [
        m + ".hybridfit" for m in METRICS]
    for m in real_cell["per_layer"]:
        assert m["workloads"] == [CELL] and m["moves"] == "sweep_s"
        cells.reader_module(m["spec"]["reader"])          # importable
    assert set(real_cell["limits"]) == {
        "unfinished", "rows_wrong", "loss0_gap", "loss_gap.1", "loss_gap.2",
        "off.tx"} | {f"grad_gap.{g}" for g in GROUPS}
    assert real_cell["traffic"]["steps_compared"] == 3     # none after step 2
    bench = cells.load_benchmark(REPO)
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    with open(os.path.join(REPO, "perfbench", "workloads", CELL + ".json"),
              encoding="utf-8") as fh:
        assert json.load(fh)["why"] == entry["why"]


def test_model_configuration_file_is_under_paths_and_used():
    """What ``test_perfbench.py`` asks of a table's configuration, for
    this model's (``conftest.py`` deselects that case for a cut
    configuration): under ``paths``, the source stated, used by a cell,
    shapes a cost model can read; and what a cut brings: every key of
    the catalog's entry unchanged but those listed as reduced, the
    published counts and the deployment stated beside the held ones, no
    width among the cuts, the floors kept."""
    from perfbench import cells, costs_hybrid

    bench = cells.load_benchmark(REPO)
    conf = next(c for c in bench["configs"] if c["name"] == "olmo-hybrid-7b")
    assert any(conf["file"].startswith(p + "/") for p in bench["paths"])
    with open(os.path.join(REPO, conf["file"]), encoding="utf-8") as fh:
        doc = json.load(fh)
    assert doc["source"] == conf["source"] and len(conf["source"]) <= 200
    assert len(conf["why"]) <= 200
    assert any(w["config"] == conf["name"] for w in bench["workloads"])
    s = costs_hybrid.shapes(doc)
    # 28 steps of 0.613 s, a 2.1 s predict pass and a 4.4 s save: a fit
    # of 24 s, two a 40 s window (PERF.md section 4).
    assert s["T"] == 8192 and s["steps"] == 28 and s["n_test"] > 0
    assert conf["reduced"] == [
        "num_hidden_layers", "layer_types", "num_attention_heads",
        "num_key_value_heads", "linear_num_key_heads",
        "linear_num_value_heads", "vocab_size"]
    period = ["linear_attention"] * 3 + ["full_attention"]
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "layer_types": period * 8, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None}}
    for key, value in published.items():
        if key in conf["reduced"]:
            assert doc[key] != value and doc["published"][key] == value, key
        else:
            assert doc[key] == value, key
    # No width among the cuts, and the guide's floors: a whole period,
    # at least four layers, at least an eighth of the vocabulary.
    assert not [k for k in conf["reduced"] if k.endswith(("_dim", "_size"))
                and k != "vocab_size"]
    assert doc["layer_types"] == period and doc["num_hidden_layers"] == 4
    assert doc["vocab_size"] * 8 == published["vocab_size"]
    for key in ("num_attention_heads", "num_key_value_heads",
                "linear_num_key_heads", "linear_num_value_heads"):
        assert doc[key] * 2 == published[key], key
    assert "2 chips share each layer's heads" in doc["deployment"]
    assert "MLP" in doc["deployment"] and "12,543" in doc["deployment"]
    assert len(doc["assumed"]) >= 8 and doc["guarantees"]
    assert "a_log" in doc["init"]["recipe"] and doc["init"]["std"] == 0.02
    # The state, by the file's own sizes: 766M parameters, 12.26 GB.
    d, f, V = doc["hidden_size"], doc["intermediate_size"], doc["vocab_size"]
    H, D, Hl = doc["num_attention_heads"], 128, doc["linear_num_key_heads"]
    dk, dv, K = (doc["linear_key_head_dim"], doc["linear_value_head_dim"],
                 doc["linear_conv_kernel_dim"])
    mlp = 3 * d * f + d
    linear = (d * Hl * (2 * dk + 2 * dv + 2) + Hl * dv * d
              + K * Hl * (2 * dk + dv) + 2 * Hl + dv + d)
    full = 4 * d * H * D + 2 * H * D + d
    count = 3 * (linear + mlp) + full + mlp + 2 * V * d + d
    assert count == doc["state"]["parameters"] == 766241946
    assert doc["state"]["bytes"] == 16 * count
    # What the cell POSTs says what the file's config.json keys say.
    hp, arch = doc["families"]["tx"], doc["families"]["tx"]["arch"]
    assert (hp["d_model"], hp["n_layers"], hp["vocab"]) == (d, 4, V)
    assert hp["n_heads"] == 30 and arch["heads_held"] == H
    assert arch["layer_pattern"] == "LLLF" and arch["gated_width"] == f
    assert (arch["linear_heads"], arch["linear_key_dim"],
            arch["linear_value_dim"], arch["linear_conv"]) == (30, dk, dv, K)
    assert arch["linear_neg_eigval"] and arch["head_dim"] == D


# --- the readers ------------------------------------------------------------

# Events named as the TPU's compiler names them (the cell's step program
# compiled for the described v5e, PR 36): the mixer's block loop, forward
# and backward; the full layer's query-block loop; the period's loop.
CORE_FWD = ("%while.375 = (s32[]{:T(128)}, f32[1,15,96,192]{3,2,1,0:T(8,128)"
            "S(1)}, f32[1,3,15,384]{3,1,2,0:T(4,128)S(1)}, f32[]{:T(128)}, "
            "bf16[32,1,256,15,192]{2,0,4,3,1:T(8,128)(2,1)}, f32[32,1,256,15,"
            "384]{2,4,3,0,1:T(8,128)}) while(%tuple.1), condition=%c, body=%b")
CORE_BWD = ("%while.378 = (s32[]{:T(128)}, f32[4,15,384]{2,1,0:T(8,128)}, "
            "f32[15]{0:T(128)}, f32[192]{0:T(256)S(1)}, f32[1,15,96,192]{3,2,"
            "1,0:T(8,128)}, f32[1,3,15,384]{3,1,2,0:T(4,128)S(1)}, f32[32,1,"
            "15,96,192]{4,3,2,1,0:T(8,128)}) while(%tuple.2), condition=%c, "
            "body=%b")
CORE_PREDICT = ("%while.41 = (s32[]{:T(128)}, f32[1,1,15,96,192]{4,3,2,1,0:T(8,"
                "128)S(1)}, f32[1,1,3,15,384]{4,2,3,1,0:T(4,128)S(1)}, f32[32,"
                "1,1,256,15,192]{3,5,4,2,1,0:T(8,128)}) while(%tuple.4), "
                "condition=%c, body=%b")
FULL_LOOP = ("%while.365 = (s32[]{:T(128)}, bf16[8,1024,15,128]{3,1,0,2:T(8,"
             "128)(2,1)}, s32[16]{0:T(128)S(1)}, bf16[8192,1920]{1,0:T(8,128)"
             "(2,1)S(1)}) while(%tuple.1247), condition=%c, body=%b")
KERNEL_LOOP = ("%while.372 = (s32[]{:T(128)}, bf16[15,1024,128]{2,1,0:T(8,128)"
               "(2,1)S(1)}, bf16[8192,1920]{1,0:T(8,128)(2,1)}, s8[1024,8192]"
               "{0,1:T(8,128)(4,1)S(1)}) while(%tuple.9), condition=%c, "
               "body=%b")
PERIOD_LOOP = ("%while.349 = (s32[]{:T(128)}, f32[1,8192,3840]{1,2,0:T(8,128)}"
               ", f32[1,3,15]{2,1,0:T(4,128)}, f32[1,3,4,15,96]{4,3,2,1,0:"
               "T(8,128)}, f32[1,3,3840,15,192]{4,3,2,1,0:T(8,128)}) "
               "while(%tuple.3), condition=%c, body=%b")


def _trace_ctx(real_cell):
    # the period's loop holds everything; a solve's fusion lies inside
    # the mixer's loop; the kernels' loop inside the query-block loop
    ops = [(PERIOD_LOOP, 0.0, 20e9), (CORE_FWD, 1e9, 2e9),
           ("%fusion.7 = f32[1,15,8,64,288]{4,3,2,1,0} fusion(%a), kind=kLoop",
            1.5e9, 0.5e9),
           (CORE_BWD, 4e9, 4e9), (CORE_PREDICT, 8.2e9, 0.5e9),
           (FULL_LOOP, 9e9, 1.5e9),
           (KERNEL_LOOP, 9.2e9, 1e9),
           ("%fusion.40 = f32[8192,11008]{1,0} fusion(%a, %b), kind=kOutput",
            12e9, 5e9)]
    return {"cell": real_cell, "ops": ops, "n_sweeps": 1, "window_ns": 26e9,
            "n_chips": 1, "peaks": PEAKS}


def test_linear_and_full_attention_time_are_their_loops_counted_once(
        real_cell):
    from perfbench import cells, costs_hybrid

    ctx = _trace_ctx(real_cell)
    read = {n: cells.reader_module(_spec(n)["reader"]).read(_spec(n), ctx)
            for n in ("linear_attn_s.hybridfit", "full_attn_s.hybridfit",
                      "linear_attn_roofline.hybridfit")}
    # 2 + 4 + the predict pass's 0.5 (a row axis of 1), not the fusion
    assert read["linear_attn_s.hybridfit"] == 6.5
    assert read["full_attn_s.hybridfit"] == 1.5      # not the kernels' loop
    least, bound = costs_hybrid.least_seconds(
        costs_hybrid.fit_linear_attention_work(
            costs_hybrid.shapes(real_cell["config"])), PEAKS)
    assert bound == "bytes"
    assert read["linear_attn_roofline.hybridfit"] == pytest.approx(
        100 * least / 6.5)
    assert 0 < read["linear_attn_roofline.hybridfit"] < 100
    # a later kernel that carries the work's name is found too
    ctx["ops"] = [("%linear_attn_chunk.3 = f32[8192,15,192]{2,1,0} custom-"
                   "call(%q), custom_call_target=\"tpu_custom_call\"", 0.0,
                   3e9)]
    spec = _spec("linear_attn_s.hybridfit")
    assert cells.reader_module(spec["reader"]).read(spec, ctx) == 3.0
    # the parent's program has neither loop: nothing is read, nothing raises
    ctx["ops"] = [("%fusion.1 = f32[8]{0} fusion(%a), kind=kLoop", 0.0, 1e9)]
    for n in read:
        assert cells.reader_module(_spec(n)["reader"]).read(_spec(n), ctx) \
            is None
    # nor does Keye's cell's pattern find this cell's loops, or this
    # cell's patterns Keye's
    import re

    keye = _spec("sparse_attn_s.txfit")["ops"] + _spec("moe_s.txfit")["ops"]
    for name in (CORE_FWD, CORE_BWD, CORE_PREDICT, FULL_LOOP, KERNEL_LOOP,
                 PERIOD_LOOP):
        assert not any(re.search(p, name) for p in keye)


def test_mfu_and_span_readers(real_cell):
    from perfbench import costs_hybrid
    from perfbench.readers import hybridfit_mfu, span_sum

    ctx = {"cell": real_cell, "ops": [("x", 0.0, 1.0)], "n_sweeps": 1,
           "window_ns": 26e9, "n_chips": 1, "peaks": PEAKS}
    want = 100 * costs_hybrid.fit_ops(
        costs_hybrid.shapes(real_cell["config"])) / 26 / 197e12
    assert hybridfit_mfu.read({}, ctx) == pytest.approx(want)
    assert 0 < want < 100
    assert hybridfit_mfu.read({}, dict(ctx, ops=[])) is None
    spans = {"spans": [[{"name": "fit.tx.steps", "duration_ms": 18000.0},
                        {"name": "fit.tx.finish.model",
                         "duration_ms": 4700.0},
                        {"name": "fit.tx.init", "duration_ms": 39.0},
                        {"name": "fit.tx.finish.store",
                         "duration_ms": 240.0}]]}
    for name, want in (("steps_s", 18.0), ("finish_model_s", 4.7),
                       ("init_s", 0.039), ("finish_store_s", 0.24)):
        assert span_sum.read(_spec(name + ".hybridfit"),
                             spans) == pytest.approx(want), name


# --- the tiny twin, end to end on the CPU ------------------------------------

@pytest.fixture()
def one_chip(monkeypatch):
    """One CPU device for the server's mesh, as the chip's machine gives
    one chip; and the flat model file at the twin's size too (the
    reference reads the persisted weights from it)."""
    import jax

    from learningorchestra_tpu.models import persistence
    from learningorchestra_tpu.parallel import mesh

    real = mesh.local_mesh
    monkeypatch.setattr(
        mesh, "local_mesh",
        lambda cfg=None, devices=None: real(cfg, devices=jax.devices()[:1]))
    monkeypatch.setattr(persistence, "FLAT_BYTES", 1)


def _run_tiny(capsys, seed):
    from perfbench import cells, run

    device = ({"platform": "cpu", "kind": "cpu", "count": 1},
              cells.load_peaks()["TPU v5 lite"])
    rc = run.main(["--workload", "tiny-hybrid.hybridfit", "--seed", str(seed),
                   "--seconds", "0.3", "--trace", "0"], root=TINY,
                  device=device)
    out, _ = capsys.readouterr()
    assert rc == 0
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "observed", "checks"]
    return last


def test_tiny_cell_is_correct_on_the_cpu(one_chip, capsys):
    last = _run_tiny(capsys, 3000000019)             # over 2**31
    assert last["correct"] is True, last["checks"]
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert set(last["metrics"]) == {"sweep_s", "setup_s"}
    assert last["checks"]["compiles_in_window"]["value"] == 0
    assert set(last["checks"]) == {
        "unfinished", "rows_wrong", "compiles_in_window", "loss0_gap",
        "loss_gap.1", "loss_gap.2", "off.tx"} | {
        f"grad_gap.{g}" for g in GROUPS}
    assert last["observed"]["gap_max.tx"] < 1e-3


@pytest.mark.parametrize("broken", ["half_rows", "answers", "no_decay"])
def test_tiny_cell_is_not_correct_when_the_timed_path_is_broken(
        one_chip, capsys, monkeypatch, broken):
    from learningorchestra_tpu.models import registry, sequence, transformer

    real_fit = sequence.fit
    fit = real_fit
    if broken == "half_rows":
        def fit(runtime, X, y, num_classes, *a, **kw):
            half = len(X) // 2
            return real_fit(runtime, X[:half], y[:half], num_classes, *a, **kw)
    elif broken == "answers":
        def fit(*a, **kw):
            model = real_fit(*a, **kw)
            proba = model.predict_proba_fn
            model.predict_proba_fn = lambda p, X: proba(p, X)[:, ::-1]
            return model
    else:       # the delta rule without its decay: a state never forgets
        real_block = transformer._delta_block
        monkeypatch.setattr(
            transformer, "_delta_block",
            lambda q, k, v, g, beta, state, C: real_block(
                q, k, v, g * 0.0, beta, state, C))
        sequence._fit_programs.cache_clear()
        sequence._proba_program.cache_clear()
    monkeypatch.setitem(registry.CLASSIFIERS, "tx", fit)
    try:
        last = _run_tiny(capsys, 11)
    finally:
        sequence._fit_programs.cache_clear()
        sequence._proba_program.cache_clear()
    assert last["correct"] is False
    failing = [k for k, c in last["checks"].items()
               if not c["value"] <= c["limit"]]
    assert failing and (("off.tx" in failing) if broken == "answers"
                        else any(k.startswith(("loss", "grad"))
                                 for k in failing))
    if broken == "no_decay":
        assert "grad_gap.linear_attention" in failing


def test_lower_precision_control_reads_over_every_limit():
    """The reference one precision down, in the program's place, reads
    over the twin's limits by at least three times."""
    from perfbench import cells, compare_tx, reference_hybrid
    from perfbench.traffic import txfit

    cell = cells.load_cell("tiny-hybrid.hybridfit", TINY)
    conf, hp = cell["config"], cell["config"]["families"]["tx"]
    train, y, test, _ = txfit.make_tables(conf, 21)
    batches = [(train[r], y[r]) for r in (
        reference_hybrid.batch_rows(21, s, hp["batch"], len(train))
        for s in range(3))]
    w = reference_hybrid.init_weights(conf, 21)
    ref = reference_hybrid.adam_steps(conf, w, batches, hp["lr"],
                                      conf["precision"]["reference"])
    ctl = reference_hybrid.adam_steps(conf, w, batches, hp["lr"],
                                      conf["precision"]["control"])
    reads = compare_tx.step_gaps(ctl, ref)
    p_ref = reference_hybrid.class_probs(conf, w, test, 4)
    p_ctl = reference_hybrid.class_probs(conf, w, test, 4,
                                         conf["precision"]["control"])
    reads["off.tx"] = float(np.mean(
        np.abs(p_ctl - p_ref).max(-1) > cell["tolerance"]["tx"]))
    held = [k for k in reads if k in cell["limits"]]
    assert len(held) == 9
    low = {k: reads[k] for k in held if reads[k] <= 3 * cell["limits"][k]}
    assert not low, (low, reads)
