"""Idle share of the busiest chip over the traced window, in percent:
1 - (union of its op intervals / window). Nothing traced: nothing read."""

from perfbench import trace_reduce


def read(params, ctx):
    if not ctx["window_ns"] or not ctx["ops"]:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_ns(ctx["ops"]) / ctx["window_ns"])
