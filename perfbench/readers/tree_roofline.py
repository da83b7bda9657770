"""Share of the roofline the tree kernels reach, in percent: the least
time the chip needs for one sweep's tree building (``costs.py``, bytes
or operations over the peaks table, whichever is larger) over the device
time the matched kernels took per traced sweep."""

from perfbench import costs
from perfbench.readers import trace_ops_sum


def read(params, ctx):
    took = trace_ops_sum.read(params, ctx)
    if not took:
        return None
    cell = ctx["cell"]
    work = costs.sweep_tree_work(costs.shapes(cell["config"]),
                                 cell["traffic"]["classifiers"])
    least, bound = costs.least_seconds(work, ctx["peaks"])
    ctx.setdefault("notes", {})["tree_roofline_bound"] = bound
    return 100.0 * least / took
