"""Operations and bytes of the ``tx`` language-model block, counted from
the equations (``reference_tx.py``'s docstring) and the configuration's
sizes, whatever implements them: chosen keys only, held experts only, no
rematerialised pass. A multiply-add is two operations; the backward pass
is twice the forward's products.
"""

from __future__ import annotations


def shapes(conf: dict) -> dict:
    sa, data, fam = conf["sa_config"], conf["data"], conf["families"]["tx"]
    return {"L": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "H": conf["num_attention_heads"],
            "G": conf["num_key_value_heads"], "D": conf["head_dim"],
            "Hi": sa["indexer_num_heads"], "Di": sa["indexer_head_dim"],
            "topk": sa["topk"], "E": conf["num_experts"],
            "K": conf["num_experts_per_tok"],
            "held": conf["num_local_experts"],
            "f": conf["moe_intermediate_size"], "V": conf["vocab_size"],
            "T": data["seq_len"], "n_test": data["n_test"],
            "steps": fam["train_steps"], "batch": fam["batch"]}


def keys_kept(T: int, topk: int) -> float:
    """Mean keys a query attends to: ``min(t + 1, topk)`` over a row."""
    full = max(T - topk, 0)
    short = min(T, topk)
    return (short * (short + 1) / 2 + full * topk) / T


def token_forward_ops(s: dict) -> dict:
    """Forward operations per token and layer, by part."""
    d, H, G, D = s["d"], s["H"], s["G"], s["D"]
    kept = keys_kept(s["T"], s["topk"])
    causal = (s["T"] + 1) / 2                      # keys s <= t, mean
    return {
        "projections": 2 * d * (2 * H * D + 2 * G * D),
        "indexer_projections": 2 * d * (s["Hi"] * s["Di"] + s["Di"]
                                        + s["Hi"]),
        "indexer_scores": 2 * s["Hi"] * s["Di"] * causal,
        "attention": 2 * 2 * H * D * kept,
        "router": 2 * d * s["E"],
        "experts": 2 * 3 * d * s["f"] * s["K"] * s["held"] / s["E"],
    }


def sparse_attention_work(s: dict) -> tuple:
    """``(operations, bytes)`` of the indexer's scores, the selection
    and the attention over the chosen keys in ONE training step (forward
    and backward): what ``sparse_attn_s`` times. Bytes: q, k, v, o and
    the indexer's q, k, w read and written once a pass in bfloat16."""
    per = token_forward_ops(s)
    tokens = s["batch"] * s["T"] * s["L"]
    ops = 3 * tokens * (per["indexer_scores"] + per["attention"])
    elems = tokens * (2 * s["H"] * s["D"] + 2 * s["G"] * s["D"]
                      + s["Hi"] * s["Di"] + s["Di"] + s["Hi"])
    return ops, 3 * 2 * elems


def fit_sparse_attention_work(s: dict) -> tuple:
    """``(operations, bytes)`` of that work in one whole fit: every
    training step, and the forward pass of the test rows."""
    ops, nbytes = sparse_attention_work(s)
    rows = s["n_test"] / s["batch"] / 3          # forward only, per row
    return (s["steps"] + rows) * ops, (s["steps"] + rows) * nbytes


def fit_ops(s: dict) -> float:
    """Model operations of one whole fit: ``steps`` training steps
    (forward + backward = 3 x forward) and the forward pass of the test
    rows."""
    per = token_forward_ops(s)
    layer = sum(per.values())
    head = 2 * s["d"] * s["V"]
    step = 3 * s["batch"] * s["T"] * (s["L"] * layer + head)
    predict = s["n_test"] * s["T"] * s["L"] * layer
    return s["steps"] * step + predict


def least_seconds(work: tuple, peaks: dict) -> tuple:
    ops, nbytes = work
    by_ops = ops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_ops, "operations") if by_ops >= by_bytes \
        else (by_bytes, "bytes")
