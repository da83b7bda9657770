"""Seconds per sweep of device time in the ops whose names match
``params["ops"]`` (regular expressions, searched in the op name), on the
busiest chip's ``XLA Ops`` line, over the traced sweeps."""

from perfbench import trace_reduce


def read(params, ctx):
    hits = trace_reduce.matching(ctx["ops"], params["ops"])
    if not hits:
        return None
    return sum(d for _, _, d in hits) / 1e9 / ctx["n_sweeps"]
