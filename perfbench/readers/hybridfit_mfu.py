"""The whole fit's share of the chip's peak, in percent: the model
operations of the traced fits (``costs_hybrid.fit_ops``) over the traced
window and the peaks table's bf16 rate, per chip."""

from perfbench import costs_hybrid


def read(params, ctx):
    if not ctx["window_ns"] or not ctx["ops"]:
        return None
    ops = costs_hybrid.fit_ops(costs_hybrid.shapes(ctx["cell"]["config"])) \
        * ctx["n_sweeps"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["n_chips"]
    return 100.0 * ops / (ctx["window_ns"] / 1e9) / peak
