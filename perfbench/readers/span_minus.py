"""Seconds per sweep in the spans matching ``params["spans"]`` less
those matching ``params["minus"]``: a layer's own time, where its span
holds the span of the layer below (a request's ``http.handle`` less the
``build`` inside it). Both are summed as ``span_sum`` sums them; either
one missing, nothing is read.
"""

from perfbench.readers import span_sum


def read(params, ctx):
    whole = span_sum.read({"spans": params["spans"]}, ctx)
    inner = span_sum.read({"spans": params["minus"]}, ctx)
    if whole is None or inner is None:
        return None
    return whole - inner
