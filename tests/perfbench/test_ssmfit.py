"""The ``ssmfit`` cell's yardstick: its costs, readers, configuration file
and comparison, and a whole run of its tiny twin on the CPU
(``tiny/ssm``: the look for a chip skipped, everything else as on the
chip), sound and with the timed path broken underneath."""

import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY = os.path.join(REPO, "tests", "perfbench", "tiny", "ssm")
CELL = "nemotron-labs-twotower-30b-a3b.ssmfit"
GROUPS = {"ssm", "experts", "shared_expert", "router", "attention",
          "embedding", "head"}
LEAVES = {"ssm_wbc"}
HELD = GROUPS - {"embedding", "router", "experts"}
METRICS = ("steps_s", "init_s", "finish_model_s", "finish_store_s",
           "device_idle", "ssm_s", "ssm_roofline", "moe_s", "moe_imbalance",
           "ssmfit_mfu", "save_fetch_s", "save_write_s", "save_sync_s",
           "save_ahead_s", "full_attn_s")
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
    from perfbench import costs_ssm

    s = costs_ssm.shapes(real_cell["config"])
    assert (s["n_ssm"], s["n_experts"], s["n_full"]) == (4, 4, 1)
    assert (s["Hs"], s["P"], s["N"], s["Gs"], s["held"], s["E"]) == (
        64, 64, 128, 8, 8, 128)
    per = costs_ssm.token_forward_ops(s)
    millions = {k: round(v / 1e6, 2) for k, v in per.items()}
    assert millions == {
        "ssm_projections": 77.41,   # 2 x 2688 x 10,304 + 2 x 4,096 x 2688
        "ssm_conv": 0.05, "ssm_scan": 2.62,          # 5 x 64 x 128 x 64
        "router": 0.69, "experts": 7.48,   # 4 x 2688 x 1856 x 6 x 8 / 128
        "shared_expert": 39.91, "attn_projections": 46.79,
        "attention": 67.12,                # the triangle: 4 x 4096 x 4096.5
        "head": 88.08}
    # a token's forward operations: 72% to the M and E layers, 16% to the
    # attention layer, 12% to the head
    layers = costs_ssm.layers_forward_ops(s)
    whole = layers + per["head"]
    ssm_e = 4 * (per["ssm_projections"] + per["ssm_conv"] + per["ssm_scan"]
                 + per["router"] + per["experts"] + per["shared_expert"])
    assert round(ssm_e / whole, 2) == 0.72
    assert round(per["head"] / whole, 2) == 0.12
    one_step = dict(s, steps=1, n_test=0)
    assert costs_ssm.fit_ops(one_step) == pytest.approx(
        3 * 8192 * whole * s["batch"])
    ops, nbytes = costs_ssm.ssm_work(s)
    assert ops == 3 * 8192 * s["batch"] * 4 * 5 * 64 * 128 * 64
    assert nbytes == 3 * 4 * 8192 * s["batch"] * 4 * (
        3 * 4096 + 2 * 8 * 128 + 64)
    least, bound = costs_ssm.least_seconds((ops, nbytes), PEAKS)
    assert bound == "bytes"
    fit = costs_ssm.fit_ssm_work(s)
    assert fit[0] == pytest.approx((s["steps"] + 16 / s["batch"] / 3) * ops)


def test_real_cell_resolves_and_limits_name_what_compare_reads(real_cell):
    from perfbench import cells

    assert real_cell["traffic"]["kind"] == "ssmfit"
    assert real_cell["traffic"]["reference"] == "reference_ssm"
    cells.traffic_module("ssmfit")
    assert [m["name"] for m in real_cell["end_to_end"]] == ["sweep_s",
                                                            "setup_s"]
    assert [m["name"] for m in real_cell["per_layer"]] == [
        m + ".ssmfit" for m in METRICS]
    for m in real_cell["per_layer"]:
        assert m["workloads"] == [CELL] and m["moves"] == "sweep_s"
        cells.reader_module(m["spec"]["reader"])          # importable
    # The numbers the limits hold: those whose sound and control readings
    # on the chip leave room for a limit between them (PERF.md section 2);
    # loss0_gap and the embedding, router and expert norms do not, and
    # are read and reported only.
    assert set(real_cell["limits"]) == {
        "unfinished", "rows_wrong", "dropped_tokens", "loss_gap.1",
        "loss_gap.2", "off.tx"} | {
        f"grad_gap.{g}" for g in HELD | LEAVES}
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
    configuration), and what a cut brings: every key of the catalog's
    entry unchanged but those listed as reduced, the published counts and
    the deployment stated beside the held ones, no width among the cuts,
    the floors kept."""
    from perfbench import cells, costs_ssm, reference_ssm

    bench = cells.load_benchmark(REPO)
    conf = next(c for c in bench["configs"]
                if c["name"] == "nemotron-labs-twotower-30b-a3b")
    assert any(conf["file"].startswith(p + "/") for p in bench["paths"])
    with open(os.path.join(REPO, conf["file"]), encoding="utf-8") as fh:
        doc = json.load(fh)
    assert doc["source"] == conf["source"] and len(conf["source"]) <= 200
    assert len(conf["why"]) <= 200
    assert any(w["config"] == conf["name"] for w in bench["workloads"])
    assert conf["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                               "num_local_experts", "vocab_size"]
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern": pattern, "intermediate_size": 1856,
        "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 64,
        "mamba_proj_bias": False, "max_position_embeddings": 262144,
        "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 52,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None,
        "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_limit": [0, None],
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    for key, value in published.items():
        if key in conf["reduced"]:
            assert doc[key] != value and doc["published"][key] == value, key
        else:
            assert doc[key] == value, key
    # No width among the cuts, and the guide's floors: a whole period of
    # the published 4 : 4 : 1, at least 8 routed experts held, at least
    # an eighth of the vocabulary; the router keeps its 128 and top-6.
    assert not [k for k in conf["reduced"] if k.endswith(("_dim", "_size"))
                and k != "vocab_size"]
    assert doc["hybrid_override_pattern"] == pattern[:9] == "MEMEM*EME"
    assert doc["num_hidden_layers"] == 9
    assert doc["vocab_size"] * 8 == published["vocab_size"]
    assert doc["num_local_experts"] == 8 and doc["experts_first"] == 0
    assert doc["published"]["n_routed_experts"] == doc["n_routed_experts"]
    assert "16 chips share each expert layer" in doc["deployment"]
    assert "16,383" in doc["deployment"] and "denoiser" in doc["deployment"]
    assert len(doc["assumed"]) >= 8 and doc["guarantees"]
    assert "ssm_a_log" in doc["init"]["recipe"] and doc["init"]["std"] == 0.02
    assert set(doc["precision"]["reference"]) == set(
        doc["precision"]["control"]) >= {"scan_operands"}
    # The state, by the file's own sizes: 667.0M parameters, 10.67 GB.
    d, V, E, f, fs = (doc["hidden_size"], doc["vocab_size"],
                      doc["n_routed_experts"], doc["moe_intermediate_size"],
                      doc["moe_shared_expert_intermediate_size"])
    Hs, P, N, Gs, K = (doc["mamba_num_heads"], doc["mamba_head_dim"],
                       doc["ssm_state_size"], doc["n_groups"],
                       doc["conv_kernel"])
    H, G, D = (doc["num_attention_heads"], doc["num_key_value_heads"],
               doc["head_dim"])
    mamba = (d * (2 * Hs * P + 2 * Gs * N + Hs) + Hs * P * d
             + (K + 1) * (Hs * P + 2 * Gs * N) + 3 * Hs + Hs * P + d)
    experts = d * E + 2 * d * fs + 8 * 2 * d * f + d
    attention = d * D * (2 * H + 2 * G) + d
    count = 4 * mamba + 4 * experts + attention + 2 * V * d + d
    assert count == doc["state"]["parameters"] == 666962944
    assert doc["state"]["bytes"] == 16 * count
    z = reference_ssm.sizes(doc)
    assert z["pattern"] == "MEMEMFEME" and z["fs"] == 3712
    assert sum(int(np.prod(shape)) for shape, _ in
               reference_ssm.leaf_shapes(z).values()) == count + 4 * E
    # What the cell POSTs says what the file's config.json keys say.
    hp, arch = doc["families"]["tx"], doc["families"]["tx"]["arch"]
    assert (hp["d_model"], hp["n_layers"], hp["vocab"], hp["n_heads"]) == (
        d, 9, V, H)
    assert arch["layer_pattern"] == doc["hybrid_override_pattern"].replace(
        "*", "F")
    assert (arch["ssm_heads"], arch["ssm_head_dim"], arch["ssm_state"],
            arch["ssm_groups"], arch["ssm_conv"], arch["ssm_chunk"]) == (
        Hs, P, N, Gs, K, doc["chunk_size"])
    assert (arch["n_experts"], arch["experts_per_token"],
            arch["expert_width"], arch["experts_held"],
            arch["shared_width"], arch["routed_scale"]) == (
        E, doc["num_experts_per_tok"], f, 8, fs,
        doc["routed_scaling_factor"])
    assert arch["router_sigmoid"] and arch["relu2_experts"]
    assert (arch["n_kv_heads"], arch["head_dim"], arch["norm_eps"]) == (
        G, D, doc["layer_norm_epsilon"])
    assert arch["no_positions"] and "rope_theta" not in arch
    s = costs_ssm.shapes(doc)
    assert s["T"] == 8192 and 10 <= s["steps"] <= 32 and s["n_test"] > 0


# --- the readers ------------------------------------------------------------

# Events named as the TPU's compiler names them (the cell's step and
# predict programs compiled for the described v5e; layouts left out): a
# Mamba-2 mixer's block loop forward, rematerialised and backward, and in
# the predict pass; an expert layer's token-block loop; the attention's
# query-block loop; the head's token-block loop.
SSM_FWD = ("%while.416 = (s32[], f32[1,64,64,128], f32[1,3,6144], f32[], "
           "bf16[32,1,256,64,64], f32[32,1,256,6144], f32[32,1,256,64,64], "
           "f32[32,1,256,64], f32[4,6144], f32[6144], f32[64], f32[64], "
           "f32[64], f32[64,64], s32[], f32[], f32[1,1,1,1,1], s32[]) "
           "while(%t), condition=%c, body=%b")
SSM_REMAT = ("%while.425 = (s32[], f32[1,64,64,128], f32[1,3,6144], "
             "bf16[32,1,256,64,64], f32[32,1,64,64,128], f32[32,1,3,6144], "
             "f32[32,1,256,6144], f32[32,1,256,64,64], f32[32,1,256,64], "
             "f32[1,1,64], pred[128,128], s32[], f32[]) while(%t), "
             "condition=%c, body=%b")
SSM_BWD = ("%while.433 = (s32[], f32[4,6144], f32[6144], f32[64], f32[64], "
           "f32[64], f32[64,64], f32[1,64,64,128], f32[1,3,6144], "
           "f32[32,1,256,6144], bf16[32,1,256,64,64], f32[32,1,64,64,128], "
           "s32[], f32[], pred[2]) while(%t), condition=%c, body=%b")
SSM_PREDICT = ("%while.266 = (s32[], f32[1,1,64,64,128], f32[1,1,3,6144], "
               "bf16[32,1,1,256,64,64], f32[32,1,1,256,6144], f32[4,6144], "
               "f32[6144], f32[64], s32[], f32[], s32[]) while(%t), "
               "condition=%c, body=%b")
MOE_LOOP = ("%while.417 = (s32[], f32[8,1024,2688], bf16[8,1024,2688], "
            "f32[8,1024,8], bf16[8,2688,1856], bf16[8,1856,2688], "
            "bf16[2688,3712], bf16[3712,2688], s32[]) while(%t), "
            "condition=%c, body=%b")
ATTN_LOOP = ("%while.415 = (s32[], bf16[16,512,32,128], f32[16,2,8192,128], "
             "f32[16,2,8192], s32[16,1], s32[16], s8[16,512,8192], "
             "f32[8192,32,128], f32[8192,256], f32[8192,256], s32[], f32[]) "
             "while(%t), condition=%c, body=%b")
ATTN_PREDICT = ("%while.276 = (s32[], bf16[16,1,512,32,128], s32[16], "
                "f32[1,8192,32,128], f32[1,8192,256], f32[1,8192,256], s32[], "
                "s32[1], s32[1], s32[1]) while(%t), condition=%c, body=%b")
HEAD_LOOP = ("%while.431 = (s32[], f32[2688,16384], f32[8,1024,2688], "
             "bf16[8,1024,2688], s32[8,1024], bf16[2688,16384], s32[], s32[], "
             "f32[], s32[]) while(%t), condition=%c, body=%b")
# The hybrid cell's delta-rule block loop and full-attention query-block
# loop (``test_hybridfit.py``'s, layouts left out).
OLMO_CORE = ("%while.375 = (s32[], f32[1,15,96,192], f32[1,3,15,384], f32[], "
             "bf16[32,1,256,15,192], f32[32,1,256,15,384]) while(%tuple.1), "
             "condition=%c, body=%b")
OLMO_FULL = ("%while.365 = (s32[], bf16[8,1024,15,128], s32[16], "
             "bf16[8192,1920]) while(%tuple.1247), condition=%c, body=%b")


def _trace_ctx(real_cell):
    ops = [(SSM_FWD, 0.0, 1e9), ("%fusion.7 = f32[1,2,8,8,128,128]{5,4,3,2,"
                                 "1,0} fusion(%a), kind=kLoop", 0.2e9, 0.3e9),
           (SSM_REMAT, 2e9, 1e9), (SSM_BWD, 3e9, 2e9),
           (SSM_PREDICT, 6e9, 0.5e9), (MOE_LOOP, 7e9, 3e9),
           (ATTN_LOOP, 11e9, 1e9), (HEAD_LOOP, 12e9, 1e9),
           (ATTN_PREDICT, 12.5e9, 0.25e9),
           ("%fusion.40 = f32[8192,10304]{1,0} fusion(%a, %b), kind=kOutput",
            13e9, 5e9)]
    return {"cell": real_cell, "ops": ops, "n_sweeps": 1, "window_ns": 20e9,
            "n_chips": 1, "peaks": PEAKS}


def test_scan_and_expert_time_are_their_loops_counted_once(real_cell):
    from perfbench import cells, costs_ssm

    ctx = _trace_ctx(real_cell)
    read = {n: cells.reader_module(_spec(n)["reader"]).read(_spec(n), ctx)
            for n in ("ssm_s.ssmfit", "moe_s.ssmfit", "ssm_roofline.ssmfit",
                      "full_attn_s.ssmfit")}
    # 1 + 1 + 2 + the predict pass's 0.5, not the fusion inside
    assert read["ssm_s.ssmfit"] == 4.5
    assert read["moe_s.ssmfit"] == 3.0
    # the query-block loops of a step and of the predict pass, not the head's
    assert read["full_attn_s.ssmfit"] == 1.25
    least, bound = costs_ssm.least_seconds(
        costs_ssm.fit_ssm_work(costs_ssm.shapes(real_cell["config"])), PEAKS)
    assert bound == "bytes"
    assert read["ssm_roofline.ssmfit"] == pytest.approx(100 * least / 4.5)
    assert 0 < read["ssm_roofline.ssmfit"] < 100
    assert ctx["notes"]["fit_ssm_work_bound"] == "bytes"
    # a later kernel that carries the work's name is found too
    ctx["ops"] = [("%ssm_scan_chunk.3 = f32[8192,64,64]{2,1,0} custom-call("
                   "%q), custom_call_target=\"tpu_custom_call\"", 0.0, 3e9)]
    spec = _spec("ssm_s.ssmfit")
    assert cells.reader_module(spec["reader"]).read(spec, ctx) == 3.0
    # the parent's program has neither loop: nothing is read, nothing raises
    ctx["ops"] = [("%fusion.1 = f32[8]{0} fusion(%a), kind=kLoop", 0.0, 1e9)]
    for n in read:
        assert cells.reader_module(_spec(n)["reader"]).read(_spec(n), ctx) \
            is None
    # nor do the other tx cells' patterns find this cell's loops, or this
    # cell's patterns theirs
    mine = (_spec("ssm_s.ssmfit")["ops"] + _spec("moe_s.ssmfit")["ops"]
            + _spec("full_attn_s.ssmfit")["ops"])
    theirs = [p for n in ("sparse_attn_s.txfit", "moe_s.txfit",
                          "linear_attn_s.hybridfit", "full_attn_s.hybridfit")
              for p in _spec(n)["ops"]]
    for name in (SSM_FWD, SSM_REMAT, SSM_BWD, SSM_PREDICT, MOE_LOOP,
                 ATTN_LOOP, ATTN_PREDICT, HEAD_LOOP):
        assert not any(re.search(p, name) for p in theirs), name
    # and within the cell each loop is one metric's
    for name in (SSM_FWD, SSM_REMAT, SSM_BWD, SSM_PREDICT, MOE_LOOP,
                 ATTN_LOOP, ATTN_PREDICT, HEAD_LOOP):
        assert sum(any(re.search(p, name) for p in _spec(n)["ops"]) for n in (
            "ssm_s.ssmfit", "moe_s.ssmfit", "full_attn_s.ssmfit")) == (
            name != HEAD_LOOP), name
    for name in (OLMO_CORE, OLMO_FULL):
        assert not any(re.search(p, name) for p in mine), name


def test_mfu_and_span_readers(real_cell):
    from perfbench import costs_ssm
    from perfbench.readers import cost_mfu, span_attr, span_sum

    ctx = {"cell": real_cell, "ops": [("x", 0.0, 1.0)], "n_sweeps": 2,
           "window_ns": 40e9, "n_chips": 1, "peaks": PEAKS}
    spec = _spec("ssmfit_mfu.ssmfit")
    want = 100 * 2 * costs_ssm.fit_ops(
        costs_ssm.shapes(real_cell["config"])) / 40 / 197e12
    assert cost_mfu.read(spec, ctx) == pytest.approx(want)
    assert 0 < want < 100
    assert cost_mfu.read(spec, dict(ctx, ops=[])) is None
    spans = {"spans": [[{"name": "fit.tx.steps", "duration_ms": 15000.0,
                         "attrs": {"moe_imbalance": 2.5, "ssm_path":
                                   "chunked"}},
                        {"name": "fit.tx.finish.model",
                         "duration_ms": 1800.0,
                         "attrs": {"save_staged": True, "save_ahead_s": 2.5}},
                        {"name": "fit.tx.finish.model.stage",
                         "duration_ms": 4300.0},
                        {"name": "fit.tx.finish.model.fetch",
                         "duration_ms": 600.0},
                        {"name": "fit.tx.finish.model.write",
                         "duration_ms": 1000.0},
                        {"name": "fit.tx.finish.model.write",
                         "duration_ms": 1200.0},
                        {"name": "fit.tx.finish.model.sync",
                         "duration_ms": 900.0},
                        {"name": "fit.tx.init", "duration_ms": 35.0},
                        {"name": "fit.tx.finish.store",
                         "duration_ms": 240.0}]]}
    for name, want in (("steps_s", 15.0), ("finish_model_s", 1.8),
                       ("init_s", 0.035), ("finish_store_s", 0.24),
                       ("save_fetch_s", 0.6), ("save_write_s", 2.2),
                       ("save_sync_s", 0.9)):
        assert span_sum.read(_spec(name + ".ssmfit"),
                             spans) == pytest.approx(want), name
    assert span_attr.read(_spec("moe_imbalance.ssmfit"), spans) == 2.5
    assert span_attr.read(_spec("save_ahead_s.ssmfit"), spans) == 2.5


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
    rc = run.main(["--workload", "tiny-ssm.ssmfit", "--seed", str(seed),
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
        "unfinished", "rows_wrong", "dropped_tokens", "compiles_in_window",
        "loss0_gap", "loss_gap.1", "loss_gap.2", "off.tx"} | {
        f"grad_gap.{g}" for g in GROUPS | LEAVES}
    # every Mamba-2 leaf is read, held or not
    assert {"grad_gap.ssm_wx", "grad_gap.ssm_d"} <= set(last["observed"])
    assert last["observed"]["gap_max.tx"] < 1e-3


@pytest.mark.parametrize("broken", ["half_rows", "answers", "no_decay",
                                    "no_shared_expert", "no_update"])
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
    elif broken == "no_decay":      # the scan's state never forgets
        real_block = transformer._ssd_block
        monkeypatch.setattr(
            transformer, "_ssd_block",
            lambda x, dt, a, b, c, state, C: real_block(
                x, dt, a * 0.0, b, c, state, C))
    elif broken == "no_update":     # Adam's steps leave the state as it was
        real_adam = sequence.optax.adam
        monkeypatch.setattr(sequence.optax, "adam",
                            lambda lr, *a, **kw: real_adam(0.0, *a, **kw))
    else:           # the shared expert adds nothing
        real_experts = transformer._experts
        monkeypatch.setattr(
            transformer, "_experts", lambda cfg, ax, h, lyr: real_experts(
                cfg, ax, h, dict(lyr, sh_down=lyr["sh_down"] * 0.0)))
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
        assert "grad_gap.ssm_wbc" in failing
        assert last["observed"]["grad_gap.ssm_a_log"] > 0.5   # no gradient
    if broken == "no_shared_expert":
        assert "grad_gap.shared_expert" in failing
    if broken == "no_update":
        assert "loss_gap.1" in failing


def test_lower_precision_control_reads_over_every_limit():
    """The reference one precision down, in the program's place, reads
    over the twin's limits by at least three times."""
    from perfbench import cells, compare_tx, reference_ssm
    from perfbench.traffic import txfit

    cell = cells.load_cell("tiny-ssm.ssmfit", TINY)
    conf, hp = cell["config"], cell["config"]["families"]["tx"]
    train, y, test, _ = txfit.make_tables(conf, 21)
    batches = [(train[r], y[r]) for r in (
        reference_ssm.batch_rows(21, s, hp["batch"], len(train))
        for s in range(3))]
    w = reference_ssm.init_weights(conf, 21)
    ref = reference_ssm.adam_steps(conf, w, batches, hp["lr"],
                                   conf["precision"]["reference"])
    ctl = reference_ssm.adam_steps(conf, w, batches, hp["lr"],
                                   conf["precision"]["control"])
    reads = compare_tx.step_gaps(ctl, ref)
    p_ref = reference_ssm.class_probs(conf, w, test, 4)
    p_ctl = reference_ssm.class_probs(conf, w, test, 4,
                                      conf["precision"]["control"])
    reads["off.tx"] = float(np.mean(
        np.abs(p_ctl - p_ref).max(-1) > cell["tolerance"]["tx"]))
    held = [k for k in reads if k in cell["limits"]]
    assert len(held) == len(GROUPS | LEAVES) + 4
    low = {k: reads[k] for k in held if reads[k] <= 3 * cell["limits"][k]}
    assert not low, (low, reads)


@pytest.mark.parametrize("scan", ["as_stated", "step_down"])
def test_precision_probe_reads_the_control_and_the_step_down(
        one_chip, capsys, monkeypatch, scan):
    """``tools/ssm_precision.py`` on the twin, two seeds in one process:
    the control reads over every limit it holds on each seed; a fit whose
    state never changes fails ``loss_gap.1`` on the first; and the
    program's scan products on float8 operands fail the run, through
    ``grad_gap.ssm_wbc``, where the products as stated pass."""
    from learningorchestra_tpu.models import sequence, transformer
    from perfbench import cells
    from tools import ssm_precision

    monkeypatch.setattr(transformer, "_ssm_dot", transformer._ssm_dot)
    cell = cells.load_cell("tiny-ssm.ssmfit", TINY)
    device = ({"platform": "cpu", "kind": "cpu", "count": 1},
              cells.load_peaks()["TPU v5 lite"])
    argv = ["--workload", "tiny-ssm.ssmfit", "--seeds", "11,3000000019",
            "--seconds", "0.3", "--no-update"]
    try:
        rc = ssm_precision.main(
            argv + (["--scan-step-down"] if scan == "step_down" else []),
            root=TINY, device=device)
    finally:
        sequence._fit_programs.cache_clear()
        sequence._proba_program.cache_clear()
    out, _ = capsys.readouterr()
    assert rc == 0
    lines = [json.loads(ln) for ln in out.splitlines()
             if ln.startswith('{"seed"')]
    assert [ln["seed"] for ln in lines] == [11, 3000000019]
    for ln in lines:
        held = {k: v for k, v in ln["control"].items() if k in cell["limits"]}
        assert len(held) == len(GROUPS | LEAVES) + 4
        assert all(v > cell["limits"][k] for k, v in held.items()), held
        if scan == "as_stated":
            assert ln["correct"] is True and ln["failing"] == []
        else:
            assert ln["correct"] is False
            assert "grad_gap.ssm_wbc" in ln["failing"]
    never = lines[0]["no_update"]
    assert never["loss0_gap"] == 0 and never["grad_gap.ssm_wbc"] == 0
    assert never["loss_gap.1"] > cell["limits"]["loss_gap.1"]
    assert "no_update" not in lines[1]
