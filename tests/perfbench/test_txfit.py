"""The ``txfit`` cell's yardstick: its tables, costs and comparison, and
a whole run of its tiny twin on the CPU (``tiny/tx``: the look for a
chip skipped, everything else as on the chip), sound and with the timed
path broken underneath."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY = os.path.join(REPO, "tests", "perfbench", "tiny", "tx")
CELL = "keye-vl-2.0-30b-a3b.txfit"


@pytest.fixture(scope="module")
def real_cell():
    from perfbench import cells

    return cells.load_cell(CELL, REPO)


# --- tables, costs, comparison ---------------------------------------------

def test_tables_come_from_the_seed_and_keep_label_tokens_out():
    from perfbench import cells
    from perfbench.traffic import txfit

    conf = cells.load_cell("tiny-tx.txfit", TINY)["config"]
    a = txfit.make_tables(conf, 3000000019)          # over 2**31
    b = txfit.make_tables(conf, 3000000019)
    c = txfit.make_tables(conf, 7)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    train, y, test, y_test = a
    d = conf["data"]
    assert train.shape == (d["n_train"], d["seq_len"])
    assert test.shape == (d["n_test"], d["seq_len"])
    assert train.min() >= d["num_classes"] and train.max() < conf["vocab_size"]
    assert set(np.unique(y)) <= set(range(d["num_classes"]))
    cols = txfit.as_columns(test, y_test)
    assert list(cols)[:2] == ["t00000", "t00001"] and "label" in cols


def test_costs_count_the_equations(real_cell):
    from perfbench import costs_tx

    s = costs_tx.shapes(real_cell["config"])
    assert costs_tx.keys_kept(8192, 2048) == pytest.approx(1792.1, abs=0.1)
    assert costs_tx.keys_kept(64, 2048) == 32.5          # every earlier key
    per = costs_tx.token_forward_ops(s)
    millions = {k: round(v / 1e6, 1) for k, v in per.items()}
    assert millions == {"projections": 37.7, "indexer_projections": 4.5,
                        "indexer_scores": 8.4, "attention": 29.4,
                        "router": 0.5, "experts": 9.4}
    one_step = dict(s, steps=1, n_test=0)
    assert costs_tx.fit_ops(one_step) == pytest.approx(15.2e12, rel=0.02)
    ops, nbytes = costs_tx.sparse_attention_work(s)
    assert ops == pytest.approx(3 * 8192 * 6 * (8.4e6 + 29.4e6), rel=0.01)
    least, bound = costs_tx.least_seconds((ops, nbytes), {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "operations" and 0.02 < least < 0.04


def _steps(scale=1.0):
    return [{"loss_main": 9.0 * scale, "loss_index": 0.5,
             "grad_norm": {"attention": 2.0 * scale, "head": 1.0}}
            for _ in range(3)]


def _meta(steps):
    return {"loss_main": [s["loss_main"] for s in steps],
            "loss_index": [s["loss_index"] for s in steps],
            "grad_norm": {g: [s["grad_norm"][g] for s in steps]
                          for g in steps[0]["grad_norm"]},
            "dropped_tokens": 0}


LIMITS = {"unfinished": 0, "rows_wrong": 0, "dropped_tokens": 0,
          "loss0_gap": 0.01, "grad_gap.attention": 0.01,
          "grad_gap.head": 0.01, "loss_gap.1": 0.01, "loss_gap.2": 0.01,
          "off.tx": 0.1}


@pytest.mark.parametrize("case,failing", [
    ("sound", None), ("loss", "loss0_gap"), ("no_steps", "rows_wrong"),
    ("row", "rows_wrong"), ("probs", "off.tx"), ("dropped", "dropped_tokens"),
    ("unfinished", "unfinished"), ("nothing_read", "off.tx"),
])
def test_compare_holds_each_number(case, failing):
    from perfbench import compare_tx

    tokens = np.arange(12).reshape(3, 4)
    labels = np.array([0, 1, 2])
    fields = ["t0", "t1", "t2", "t3"]
    ref_probs = np.array([[0.7, 0.2, 0.1], [0.1, 0.8, 0.1], [0.2, 0.2, 0.6]])

    def doc(r, probs):
        d = {f: int(v) for f, v in zip(fields, tokens[r])}
        return dict(d, label=int(labels[r]), probability=list(probs),
                    prediction=int(np.argmax(probs)))

    rows = [(r, doc(r, ref_probs[r])) for r in range(3)]
    meta = _meta(_steps(1.5 if case == "loss" else 1.0))
    if case == "no_steps":
        meta = {"dropped_tokens": 0}
    if case == "row":
        rows[1][1]["t2"] = 99
    if case == "probs":
        rows[2] = (2, doc(2, [0.3, 0.2, 0.5]))
    if case == "dropped":
        meta["dropped_tokens"] = 3
    if case == "nothing_read":
        rows = []
    fits = [{"meta": meta, "rows": rows, "probs_of": True}]
    correct, checks, observed = compare_tx.compare(
        fits, 1 if case == "unfinished" else 0, _steps(), ref_probs, tokens,
        labels, fields, LIMITS, {"tx": 0.05})
    bad = [k for k, c in checks.items()
           if not c["value"] <= c["limit"]]
    assert correct == (failing is None)
    if failing:
        assert failing in bad
    assert set(checks) == set(LIMITS) and "gap.tx" in observed


def test_real_cell_resolves_and_limits_name_what_compare_reads(real_cell):
    from perfbench import cells, compare_tx

    assert real_cell["traffic"]["kind"] == "txfit"
    assert [m["name"] for m in real_cell["end_to_end"]] == ["sweep_s",
                                                            "setup_s"]
    for m in real_cell["per_layer"]:
        assert m["workloads"] == [CELL] and m["moves"] == "sweep_s"
        cells.reader_module(m["spec"]["reader"])          # importable
    groups = {"attention", "indexer", "router", "experts", "embedding",
              "head"}
    held = set(real_cell["limits"])
    assert held == {"unfinished", "rows_wrong", "dropped_tokens",
                    "loss0_gap", "loss_gap.1", "loss_gap.2", "off.tx"} | {
        f"grad_gap.{g}" for g in groups}
    assert real_cell["traffic"]["steps_compared"] == 3     # none after step 2
    assert compare_tx.PARTS == ("loss_main", "loss_index")


# --- the span readers --------------------------------------------------------

def test_span_readers_read_the_fit_spans():
    from perfbench.readers import span_attr, span_sum

    spans = [[{"name": "fit.tx.steps", "duration_ms": 40000.0,
               "attrs": {"moe_imbalance": 1.25, "steps": 20}},
              {"name": "fit.tx.init", "duration_ms": 900.0},
              {"name": "fit.tx.finish.model", "duration_ms": 1500.0}]]
    ctx = {"spans": spans}
    assert span_sum.read({"spans": ["fit\\.tx\\.steps"]}, ctx) == 40.0
    assert span_attr.read({"spans": ["fit\\.tx\\.steps"],
                           "attr": "moe_imbalance"}, ctx) == 1.25
    # A program without the span or the attribute: nothing, not zero.
    assert span_attr.read({"spans": ["fit\\.tx\\.steps"],
                           "attr": "absent"}, ctx) is None
    assert span_attr.read({"spans": ["fit\\.nb\\.steps"],
                           "attr": "moe_imbalance"}, ctx) is None
    assert span_attr.read({"spans": ["x"], "attr": "y"}, {"spans": []}) is None


def test_mfu_reader_divides_the_fits_operations_by_the_window(real_cell):
    from perfbench import costs_tx
    from perfbench.readers import txfit_mfu

    ctx = {"cell": real_cell, "ops": [("x", 0.0, 1.0)], "n_sweeps": 1,
           "window_ns": 46e9, "n_chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12}}
    want = 100 * costs_tx.fit_ops(costs_tx.shapes(real_cell["config"])) \
        / 46 / 197e12
    assert txfit_mfu.read({}, ctx) == pytest.approx(want)
    assert 0 < want < 100
    assert txfit_mfu.read({}, dict(ctx, ops=[])) is None


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
    rc = run.main(["--workload", "tiny-tx.txfit", "--seed", str(seed),
                   "--seconds", "0.3", "--trace", "0"], root=TINY,
                  device=device)
    out, _ = capsys.readouterr()
    assert rc == 0
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "observed", "checks"]
    return last


def test_tiny_cell_is_correct_on_the_cpu(one_chip, capsys):
    last = _run_tiny(capsys, 3000000019)
    assert last["correct"] is True, last["checks"]
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert set(last["metrics"]) == {"sweep_s", "setup_s"}
    assert last["checks"]["compiles_in_window"]["value"] == 0
    assert last["observed"]["gap_max.tx"] < 1e-4


@pytest.mark.parametrize("broken", ["half_rows", "answers"])
def test_tiny_cell_is_not_correct_when_the_timed_path_is_broken(
        one_chip, capsys, monkeypatch, broken):
    from learningorchestra_tpu.models import registry, sequence

    real_fit = sequence.fit
    if broken == "half_rows":
        def fit(runtime, X, y, num_classes, *a, **kw):
            half = len(X) // 2
            return real_fit(runtime, X[:half], y[:half], num_classes, *a, **kw)
    else:
        def fit(*a, **kw):
            model = real_fit(*a, **kw)
            proba = model.predict_proba_fn
            model.predict_proba_fn = lambda p, X: proba(p, X)[:, ::-1]
            return model
    monkeypatch.setitem(registry.CLASSIFIERS, "tx", fit)
    last = _run_tiny(capsys, 11)
    assert last["correct"] is False
    failing = [k for k, c in last["checks"].items()
               if not c["value"] <= c["limit"]]
    assert failing and (("off.tx" in failing) if broken == "answers"
                        else any(k.startswith(("loss", "grad"))
                                 for k in failing))


def test_lower_precision_control_reads_over_every_limit():
    """The reference one precision down, in the program's place, reads
    over the twin's limits by at least three times."""
    from perfbench import cells, compare_tx, reference_tx
    from perfbench.traffic import txfit

    cell = cells.load_cell("tiny-tx.txfit", TINY)
    conf, hp = cell["config"], cell["config"]["families"]["tx"]
    train, y, test, _ = txfit.make_tables(conf, 21)
    batches = [(train[r], y[r]) for r in (
        reference_tx.batch_rows(21, s, hp["batch"], len(train))
        for s in range(3))]
    w = reference_tx.init_weights(conf, 21)
    ref = reference_tx.adam_steps(conf, w, batches, hp["lr"],
                                  conf["precision"]["reference"])
    ctl = reference_tx.adam_steps(conf, w, batches, hp["lr"],
                                  conf["precision"]["control"])
    reads = compare_tx.step_gaps(ctl, ref)
    p_ref = reference_tx.class_probs(conf, w, test, 4)
    p_ctl = reference_tx.class_probs(conf, w, test, 4,
                                     conf["precision"]["control"])
    reads["off.tx"] = float(np.mean(
        np.abs(p_ctl - p_ref).max(-1) > cell["tolerance"]["tx"]))
    held = [k for k in reads if k in cell["limits"]]
    assert len(held) == 10
    low = {k: reads[k] for k in held if reads[k] <= 3 * cell["limits"][k]}
    assert not low, (low, reads)


def test_model_configuration_file_is_under_paths_and_used():
    """What ``test_perfbench.py`` asks of a table's configuration, for a
    model's: under ``paths``, the source stated, used by a cell, shapes a
    cost model can read; and what a cut brings: every key of the
    catalog's entry unchanged but those listed as reduced, the published
    counts and the deployment stated beside the held ones, no width
    among the cuts."""
    from perfbench import cells, costs_tx

    bench = cells.load_benchmark(REPO)
    conf = next(c for c in bench["configs"]
                if c["name"] == "keye-vl-2.0-30b-a3b")
    assert any(conf["file"].startswith(p + "/") for p in bench["paths"])
    with open(os.path.join(REPO, conf["file"]), encoding="utf-8") as fh:
        doc = json.load(fh)
    assert doc["source"] == conf["source"] and len(conf["source"]) <= 200
    assert any(w["config"] == conf["name"] for w in bench["workloads"])
    s = costs_tx.shapes(doc)
    assert s["T"] == 8192 and s["steps"] >= 1 and s["n_test"] > 0
    assert conf["reduced"] == ["num_hidden_layers", "num_local_experts",
                               "vocab_size"]
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    for key, value in published.items():
        if key in conf["reduced"]:
            assert doc[key] < value and doc["published"][key] == value
        else:
            assert doc[key] == value, key
    assert doc["num_hidden_layers"] >= 4 and doc["num_local_experts"] >= 8
    assert doc["vocab_size"] * 8 >= published["vocab_size"]
    assert "8 chips share each layer" in doc["deployment"]
    assert len(doc["assumed"]) >= 8 and doc["guarantees"]


# --- the device-trace readers on events named as the TPU's compiler names them

ATTN_LOOP = ("%while.258 = (s32[]{:T(128)}, bf16[64,128,32,128]{3,1,0,2:T(8,128)"
             "(2,1)}, f32[64,3]{1,0:T(8,128)}, s32[64]{0:T(128)S(1)}) "
             "while(%tuple.845), condition=%wide.region_29.51, "
             "body=%wide.region_28.50.sunk")
SELECT_LOOP = ("%while.261 = (s32[]{:T(128)}, u32[128]{0:T(128)S(1)}, "
               "u32[128,8192]{1,0:T(8,128)S(1)}, s32[]{:T(128)}) "
               "while(%tuple.785), condition=%c, body=%b")
MOE_LOOP = ("%while.259 = (s32[]{:T(128)}, f32[8,1024,2048]{2,1,0:T(8,128)}, "
            "bf16[16,2048,768]{2,1,0:T(8,128)(2,1)S(1)}, s32[]{:T(128)}) "
            "while(%tuple.844), condition=%wide.region_33.57, "
            "body=%wide.region_32.56.sunk")
LAYER_LOOP = ("%while.249 = (s32[]{:T(128)}, f32[1,8192,2048]{2,1,0:T(8,128)"
              "S(1)}, f32[6,16,2048,768]{3,2,1,0:T(8,128)}) while(%tuple.9), "
              "condition=%c, body=%b")


def _trace_ctx(real_cell):
    # the layers' loop holds everything; the selection's bisection loop
    # lies inside the attention's loop; a fusion of the attention's body
    # and one outside every loop
    ops = [(LAYER_LOOP, 0.0, 40e9), (ATTN_LOOP, 1e9, 20e9),
           (SELECT_LOOP, 2e9, 3e9),
           ("%fusion.12 = f32[4,8,128]{2,1,0} fusion(f32[4,8,128,8192]{3,2,1,0}"
            " %p), kind=kLoop", 6e9, 4e9),
           (MOE_LOOP, 22e9, 6e9),
           ("%fusion.40 = f32[8192,2048]{1,0} fusion(%a, %b), kind=kOutput",
            30e9, 2e9)]
    return {"cell": real_cell, "ops": ops, "n_sweeps": 1, "window_ns": 46e9,
            "n_chips": 1, "peaks": {"bf16_flops_per_s": 197e12,
                                    "hbm_bytes_per_s": 819e9}}


def _spec(name):
    with open(os.path.join(REPO, "perfbench", "layer_metrics",
                           name + ".json"), encoding="utf-8") as fh:
        return json.load(fh)


def test_attention_and_expert_time_are_their_loops_counted_once(real_cell):
    from perfbench import cells, costs_tx

    ctx = _trace_ctx(real_cell)
    read = {n: cells.reader_module(_spec(n)["reader"]).read(_spec(n), ctx)
            for n in ("sparse_attn_s.txfit", "moe_s.txfit",
                      "sparse_attn_roofline.txfit")}
    assert read["sparse_attn_s.txfit"] == 20.0       # not 20 + 3 + 4
    assert read["moe_s.txfit"] == 6.0                # nor the layers' loop
    least, bound = costs_tx.least_seconds(
        costs_tx.fit_sparse_attention_work(
            costs_tx.shapes(real_cell["config"])), ctx["peaks"])
    assert bound == "operations"
    assert read["sparse_attn_roofline.txfit"] == pytest.approx(
        100 * least / 20.0)
    assert 0 < read["sparse_attn_roofline.txfit"] < 100
    # a later kernel that carries the work's name is found too
    ctx["ops"] = [("%sparse_attn_fwd.3 = bf16[8192,32,128]{2,1,0} custom-call("
                   "%q), custom_call_target=\"tpu_custom_call\"", 0.0, 5e9),
                  ("%moe_grouped.1 = f32[8192,2048]{1,0} custom-call(%x), "
                   "custom_call_target=\"tpu_custom_call\"", 6e9, 1e9)]
    spec = _spec("sparse_attn_s.txfit")
    assert cells.reader_module(spec["reader"]).read(spec, ctx) == 5.0
    spec = _spec("moe_s.txfit")
    assert cells.reader_module(spec["reader"]).read(spec, ctx) == 1.0
    # the parent's program has neither: nothing is read, nothing raises
    ctx["ops"] = [("%fusion.1 = f32[8]{0} fusion(%a), kind=kLoop", 0.0, 1e9)]
    for n in read:
        assert cells.reader_module(_spec(n)["reader"]).read(_spec(n), ctx) \
            is None


def test_readers_on_a_recorded_step_of_the_cell(real_cell):
    """``fixtures/tx_step.xplane.pb``: one training step and one predicted
    row of the cell on a TPU v5e (PR 34), cut down to every loop's own
    event, the first 2,000 other device events and 400 from inside an
    expert-layer loop. The loops' events are there under the names the
    patterns expect, each counted once."""
    from perfbench import cells, trace_reduce

    profile = trace_reduce.load(os.path.join(
        REPO, "perfbench", "fixtures", "tx_step.xplane.pb"))
    (ops,) = trace_reduce.device_ops(profile).values()
    assert len(ops) > 2500
    notes = trace_reduce.host_annotations(profile, "perfbench.sweep.")
    assert [n[0] for n in notes] == ["perfbench.sweep.0"]
    ctx = {"cell": real_cell, "ops": ops, "n_sweeps": 1,
           "window_ns": notes[0][2], "n_chips": 1,
           "peaks": cells.load_peaks()["TPU v5 lite"]}
    read = {n: cells.reader_module(_spec(n)["reader"]).read(_spec(n), ctx)
            for n in ("sparse_attn_s.txfit", "moe_s.txfit",
                      "sparse_attn_roofline.txfit")}
    busy = trace_reduce.busy_ns(ops) / 1e9
    assert 0 < read["moe_s.txfit"] < read["sparse_attn_s.txfit"] < busy
    spec = _spec("sparse_attn_s.txfit")
    hits = trace_reduce.matching(ops, spec["ops"])
    # the loops do not nest in one another: their time is their union
    assert sum(d for _, _, d in hits) == pytest.approx(
        trace_reduce.busy_ns(hits))
    assert all(" while(" in name for name, _, _ in hits)
    kinds = {name.split(" = ")[1][:40] for name, _, _ in hits}
    assert len(kinds) >= 2               # forward and backward loops
