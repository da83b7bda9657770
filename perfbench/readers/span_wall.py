"""Seconds per sweep that the matching spans cover: the union of their
intervals, per traced request.

``params["spans"]``: regular expressions matched against the whole span
name, as ``span_sum`` does. Where ``span_sum`` adds durations, and so
counts the same second once for every family that was in the phase,
this counts it once: what the phase holds of the wall clock. The unions
of the traced requests are added and divided by their number.
"""

import re

from perfbench import trace_reduce


def intervals(spans: list, patterns: list) -> list:
    """``[(name, start_s, duration_s)]`` of one request's spans whose
    whole name matches any of the regular expressions."""
    regs = [re.compile(p) for p in patterns]
    return [(sp["name"], sp["start"], sp["duration_ms"] / 1e3)
            for sp in spans if any(r.fullmatch(sp["name"]) for r in regs)]


def read(params, ctx):
    per_request = [intervals(spans, params["spans"])
                   for spans in ctx["spans"]]
    if not any(per_request):
        return None
    covered = sum(end - start for evs in per_request
                  for start, end in trace_reduce.merged(evs))
    return covered / len(per_request)
