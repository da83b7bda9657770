"""The whole fit's share of the chip's peak, in percent: the model
operations of the traced fits, counted by a cost module's ``fit_ops``
(``params["costs"]``, a module of ``perfbench``), over the traced window
and the peaks table's bf16 rate, per chip."""

import importlib


def read(params, ctx):
    if not ctx["window_ns"] or not ctx["ops"]:
        return None
    costs = importlib.import_module("perfbench." + params["costs"])
    ops = costs.fit_ops(costs.shapes(ctx["cell"]["config"])) \
        * ctx["n_sweeps"]
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["n_chips"]
    return 100.0 * ops / (ctx["window_ns"] / 1e9) / peak
