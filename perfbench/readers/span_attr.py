"""A number the program counted, read from an attribute of its spans.

``params["spans"]``: regular expressions matched against the whole span
name, as ``span_sum`` does; ``params["attr"]``: the attribute. The mean
over the matching spans that carry it, over the traced requests; none
does: nothing is read.
"""

import re


def read(params, ctx):
    regs = [re.compile(p) for p in params["spans"]]
    values = [sp["attrs"][params["attr"]]
              for spans in ctx["spans"] for sp in spans
              if any(r.fullmatch(sp["name"]) for r in regs)
              and params["attr"] in (sp.get("attrs") or {})]
    if not values:
        return None
    return sum(float(v) for v in values) / len(values)
