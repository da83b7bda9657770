"""Share of the roofline a layer's matched device time reaches, in
percent: the least time the chip needs for that layer's work in a fit,
counted by a cost module (``params["costs"]``, a module of
``perfbench``; ``params["work"]``, its function of the configuration's
shapes that returns ``(operations, bytes)``: operations or bytes over
the peaks table, whichever is larger), over the device time of the ops
``params["ops"]`` matches, per traced fit."""

import importlib

from perfbench.readers import trace_ops_sum


def read(params, ctx):
    took = trace_ops_sum.read(params, ctx)
    if not took:
        return None
    costs = importlib.import_module("perfbench." + params["costs"])
    least, bound = costs.least_seconds(
        getattr(costs, params["work"])(costs.shapes(ctx["cell"]["config"])),
        ctx["peaks"])
    ctx.setdefault("notes", {})[params["work"] + "_bound"] = bound
    return 100.0 * least / took
