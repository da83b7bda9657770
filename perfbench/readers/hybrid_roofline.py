"""Share of the roofline the linear mixers' core reaches, in percent: the
least time the chip needs for the gated delta rule of a fit's training
steps and predict pass, counted from the recurrence (``costs_hybrid.py``:
operations or bytes over the peaks table, whichever is larger), over the
device time the matched operations took per traced fit."""

from perfbench import costs_hybrid
from perfbench.readers import trace_ops_sum


def read(params, ctx):
    took = trace_ops_sum.read(params, ctx)
    if not took:
        return None
    least, bound = costs_hybrid.least_seconds(
        costs_hybrid.fit_linear_attention_work(
            costs_hybrid.shapes(ctx["cell"]["config"])), ctx["peaks"])
    ctx.setdefault("notes", {})["linear_attn_roofline_bound"] = bound
    return 100.0 * least / took
