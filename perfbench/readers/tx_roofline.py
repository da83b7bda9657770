"""Share of the roofline the sparse attention reaches, in percent: the
least time the chip needs for the indexer's scores and the attention
over the chosen keys of a fit's training steps and predict pass (``costs_tx.py``:
operations or bytes over the peaks table, whichever is larger) over the
device time the matched operations took per traced fit."""

from perfbench import costs_tx
from perfbench.readers import trace_ops_sum


def read(params, ctx):
    took = trace_ops_sum.read(params, ctx)
    if not took:
        return None
    least, bound = costs_tx.least_seconds(
        costs_tx.fit_sparse_attention_work(
            costs_tx.shapes(ctx["cell"]["config"])), ctx["peaks"])
    ctx.setdefault("notes", {})["sparse_attn_roofline_bound"] = bound
    return 100.0 * least / took
