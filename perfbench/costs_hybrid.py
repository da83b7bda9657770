"""Operations and bytes of the ``tx`` hybrid block, counted from the
equations (``reference_hybrid.py``'s docstring) and the configuration's
sizes, whatever implements them: held heads only, causal attention as
the triangle, the linear layer as the RECURRENCE (not as the chunked
algorithm the program runs, so that a later kernel is read against the
same count), no rematerialised pass. A multiply-add is two operations;
the backward pass is twice the forward's products.
"""

from __future__ import annotations

from perfbench.costs_tx import least_seconds  # noqa: F401  (the readers')

FLOAT_BYTES = 4     # the configuration states float32 activations


def shapes(conf: dict) -> dict:
    data, fam = conf["data"], conf["families"]["tx"]
    kinds = conf["layer_types"]
    whole = conf["published"]["num_attention_heads"]
    return {"n_linear": kinds.count("linear_attention"),
            "n_full": kinds.count("full_attention"),
            "d": conf["hidden_size"], "f": conf["intermediate_size"],
            "H": conf["num_attention_heads"],
            "G": conf["num_key_value_heads"],
            "D": conf["hidden_size"] // whole,
            "Hl": conf["linear_num_key_heads"],
            "dk": conf["linear_key_head_dim"],
            "dv": conf["linear_value_head_dim"],
            "K": conf["linear_conv_kernel_dim"], "V": conf["vocab_size"],
            "T": data["seq_len"], "n_test": data["n_test"],
            "steps": fam["train_steps"], "batch": fam["batch"]}


def token_forward_ops(s: dict) -> dict:
    """Forward operations per token, by part; a layer's parts are per
    layer of that kind."""
    d, H, G, D = s["d"], s["H"], s["G"], s["D"]
    Hl, dk, dv = s["Hl"], s["dk"], s["dv"]
    return {
        # q', k', v', z, b, a and the output projection
        "linear_projections": 2 * d * Hl * (2 * dk + 3 * dv + 2),
        "linear_conv": 2 * s["K"] * Hl * (2 * dk + dv),
        # per head: the decay of S, S^T k, the rank-one write, S^T q,
        # as ISSUE 36 counts them: 6 dk dv
        "linear_recurrence": 6 * Hl * dk * dv,
        "full_projections": 2 * d * D * (2 * H + 2 * G),
        "full_attention": 2 * 2 * H * D * (s["T"] + 1) / 2,
        "mlp": 2 * 3 * d * s["f"],
        "head": 2 * d * s["V"],
    }


def linear_attention_work(s: dict) -> tuple:
    """``(operations, bytes)`` of the linear mixers' core in ONE
    training step (forward and backward): what ``linear_attn_s`` times.
    Operations: the recurrence, forward and twice that backward. Bytes:
    q, k, v, z, g, beta read and o written once a pass (three passes),
    float32."""
    per = token_forward_ops(s)
    tokens = s["batch"] * s["T"] * s["n_linear"]
    ops = 3 * tokens * per["linear_recurrence"]
    elems = tokens * s["Hl"] * (2 * s["dk"] + 3 * s["dv"] + 2)
    return ops, 3 * FLOAT_BYTES * elems


def fit_linear_attention_work(s: dict) -> tuple:
    """``(operations, bytes)`` of that work in one whole fit: every
    training step, and the forward pass of the test rows."""
    ops, nbytes = linear_attention_work(s)
    rows = s["n_test"] / s["batch"] / 3          # forward only, per row
    return (s["steps"] + rows) * ops, (s["steps"] + rows) * nbytes


def layers_forward_ops(s: dict) -> float:
    """Forward operations per token of every held layer."""
    per = token_forward_ops(s)
    linear = (per["linear_projections"] + per["linear_conv"]
              + per["linear_recurrence"] + per["mlp"])
    full = per["full_projections"] + per["full_attention"] + per["mlp"]
    return s["n_linear"] * linear + s["n_full"] * full


def fit_ops(s: dict) -> float:
    """Model operations of one whole fit: ``steps`` training steps
    (forward + backward = 3 x forward) and the forward pass of the test
    rows."""
    layers = layers_forward_ops(s)
    step = 3 * s["batch"] * s["T"] * (layers + token_forward_ops(s)["head"])
    return s["steps"] * step + s["n_test"] * s["T"] * layers
