"""Operations and bytes of the ``tx`` Mamba-2 / expert block, counted from
the equations (``reference_ssm.py``'s docstring) and the configuration's
sizes, whatever implements them: routed work only (the held experts'
share of each token's ``num_experts_per_tok``), causal attention as the
triangle, the Mamba-2 scan as the RECURRENCE (not as the chunked
algorithm the program runs, so that a later kernel is read against the
same count), no rematerialised pass. A multiply-add is two operations;
the backward pass is twice the forward's products.
"""

from __future__ import annotations

from perfbench.costs_tx import least_seconds  # noqa: F401  (the readers')

FLOAT_BYTES = 4     # the configuration states float32 activations
KIND = {"M": "M", "E": "E", "*": "F"}


def shapes(conf: dict) -> dict:
    data, fam = conf["data"], conf["families"]["tx"]
    kinds = [KIND[c] for c in conf["hybrid_override_pattern"]]
    return {"n_ssm": kinds.count("M"), "n_experts": kinds.count("E"),
            "n_full": kinds.count("F"), "d": conf["hidden_size"],
            "H": conf["num_attention_heads"],
            "G": conf["num_key_value_heads"], "D": conf["head_dim"],
            "Hs": conf["mamba_num_heads"], "P": conf["mamba_head_dim"],
            "N": conf["ssm_state_size"], "Gs": conf["n_groups"],
            "K": conf["conv_kernel"], "E": conf["n_routed_experts"],
            "held": conf["num_local_experts"],
            "topk": conf["num_experts_per_tok"],
            "f": conf["moe_intermediate_size"],
            "fs": conf["moe_shared_expert_intermediate_size"]
            * conf["n_shared_experts"], "V": conf["vocab_size"],
            "T": data["seq_len"], "n_test": data["n_test"],
            "steps": fam["train_steps"], "batch": fam["batch"]}


def token_forward_ops(s: dict) -> dict:
    """Forward operations per token, by part; a layer's parts are per
    layer of that kind."""
    d, Hs, P, N, Gs = s["d"], s["Hs"], s["P"], s["N"], s["Gs"]
    inner = Hs * P
    return {
        # z, x, B, C, dt in; the output projection
        "ssm_projections": 2 * d * (2 * inner + 2 * Gs * N + Hs)
        + 2 * inner * d,
        "ssm_conv": 2 * s["K"] * (inner + 2 * Gs * N),
        # per head and token: the decay of S, the write dt x B^T, S C:
        # 5 P N
        "ssm_scan": 5 * P * N * Hs,
        "router": 2 * d * s["E"],
        # relu(h U)^2 D: two products, the held experts' routed share
        "experts": 2 * 2 * d * s["f"] * s["topk"] * s["held"] / s["E"],
        "shared_expert": 2 * 2 * d * s["fs"],
        "attn_projections": 2 * d * s["D"] * (2 * s["H"] + 2 * s["G"]),
        "attention": 2 * 2 * s["H"] * s["D"] * (s["T"] + 1) / 2,
        "head": 2 * d * s["V"],
    }


def layers_forward_ops(s: dict) -> float:
    """Forward operations per token of every held layer."""
    per = token_forward_ops(s)
    ssm = per["ssm_projections"] + per["ssm_conv"] + per["ssm_scan"]
    moe = per["router"] + per["experts"] + per["shared_expert"]
    full = per["attn_projections"] + per["attention"]
    return s["n_ssm"] * ssm + s["n_experts"] * moe + s["n_full"] * full


def fit_ops(s: dict) -> float:
    """Model operations of one whole fit: ``steps`` training steps
    (forward + backward = 3 x forward) and the forward pass of the test
    rows."""
    layers = layers_forward_ops(s)
    step = 3 * s["batch"] * s["T"] * (layers + token_forward_ops(s)["head"])
    return s["steps"] * step + s["n_test"] * s["T"] * layers


def ssm_work(s: dict) -> tuple:
    """``(operations, bytes)`` of the Mamba-2 scans in ONE training step
    (forward and backward): what ``ssm_s`` times. Operations: the
    recurrence, forward and twice that backward. Bytes: x, B, C, dt, z
    read and y written once a pass (three passes), float32."""
    tokens = s["batch"] * s["T"] * s["n_ssm"]
    ops = 3 * tokens * token_forward_ops(s)["ssm_scan"]
    elems = tokens * (3 * s["Hs"] * s["P"] + 2 * s["Gs"] * s["N"] + s["Hs"])
    return ops, 3 * FLOAT_BYTES * elems


def fit_ssm_work(s: dict) -> tuple:
    """``(operations, bytes)`` of that work in one whole fit: every
    training step, and the forward pass of the test rows."""
    ops, nbytes = ssm_work(s)
    rows = s["n_test"] / s["batch"] / 3          # forward only, per row
    return (s["steps"] + rows) * ops, (s["steps"] + rows) * nbytes
