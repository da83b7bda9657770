"""The whole step's share of the chip's peak, in percent: the analytic
operations of the traced sweeps (``costs.sweep_ops``) over the traced
window and the peaks table's bf16 rate, per chip."""

from perfbench import costs


def read(params, ctx):
    if not ctx["window_ns"] or not ctx["ops"]:
        return None
    cell = ctx["cell"]
    ops = costs.sweep_ops(costs.shapes(cell["config"]),
                          cell["traffic"]["classifiers"]) * ctx["n_sweeps"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["n_chips"]
    return 100.0 * ops / (ctx["window_ns"] / 1e9) / peak
