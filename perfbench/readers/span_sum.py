"""Seconds per sweep in the program's spans whose names match.

``params["spans"]``: regular expressions matched against the whole span
name (``GET /trace/<id>`` of each traced request). Sums the matching
spans' durations over the traced requests, divided by their number.
"""

import re


def read(params, ctx):
    regs = [re.compile(p) for p in params["spans"]]
    total, found = 0.0, False
    for spans in ctx["spans"]:
        for sp in spans:
            if any(r.fullmatch(sp["name"]) for r in regs):
                total += sp["duration_ms"] / 1e3
                found = True
    if not found or not ctx["spans"]:
        return None
    return total / len(ctx["spans"])
