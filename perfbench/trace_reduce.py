"""From a profiler trace to busy time, kernel time, gaps and top ops.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` and nothing else. The arithmetic works on
plain ``(name, start_ns, duration_ns)`` tuples, so the tests drive it
with intervals made by hand and with the cut-down recorded trace under
``perfbench/fixtures/``.

What a TPU v5e trace looks like (looked at by hand, PR 26): one plane
per chip named ``/device:TPU:<i>``. Its line ``XLA Ops`` holds one event
per executed HLO instruction, named by the instruction's whole text
(``%closed_call.65 = f32[5,1,32,896]{...} custom-call(...),
custom_call_target="tpu_custom_call", ...``): a Pallas kernel is such a
custom call and carries no name of its own; a ``while``, ``call`` or
``conditional`` is an event too and spans its body's events, so a union
counts the time once and a sum over names would count it twice. ``XLA
Modules`` holds one event per executed program; ``Async XLA Ops`` the
copies in flight. Host threads are lines of the ``/host:CPU`` plane;
``jax.profiler.TraceAnnotation`` spans appear on the ``python`` line
under their names, on the device events' clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def line_events(plane, line_name: str) -> list:
    """``[(name, start_ns, duration_ns)]`` of the plane's line."""
    out = []
    for line in plane.lines:
        if line.name == line_name:
            out.extend((ev.name, float(ev.start_ns), float(ev.duration_ns))
                       for ev in line.events)
    return out


def device_ops(profile) -> dict:
    """``{plane name: [(name, start_ns, dur_ns)]}`` of every chip's ops."""
    return {p.name: line_events(p, OPS_LINE) for p in profile.planes
            if DEVICE_PLANE.match(p.name)}


def host_annotations(profile, prefix: str) -> list:
    """Host events whose name starts with ``prefix``, from every thread."""
    out = []
    for p in profile.planes:
        if p.name == HOST_PLANE:
            for line in p.lines:
                out.extend((ev.name, float(ev.start_ns),
                            float(ev.duration_ns))
                           for ev in line.events
                           if ev.name.startswith(prefix))
    return sorted(out, key=lambda e: e[1])


def clip(events: list, lo: float, hi: float) -> list:
    """The parts of the events that lie inside ``[lo, hi]``."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def merged(events: list) -> list:
    """The union of the events' intervals as sorted ``[start, end]``."""
    out = []
    for s, e in sorted((s, s + d) for _, s, d in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events: list) -> float:
    return sum(e - s for s, e in merged(events))


def gaps(events: list, lo: float, hi: float) -> list:
    """``[(start_ns, dur_ns)]`` inside ``[lo, hi]`` in which no event
    ran, longest first."""
    out, at = [], lo
    for s, e in merged(clip(events, lo, hi)):
        if s > at:
            out.append((at, s - at))
        at = max(at, e)
    if hi > at:
        out.append((at, hi - at))
    return sorted(out, key=lambda g: -g[1])


def matching(events: list, patterns: list) -> list:
    """Events whose name matches any of the regular expressions."""
    regs = [re.compile(p) for p in patterns]
    return [ev for ev in events if any(r.search(ev[0]) for r in regs)]


CONTAINERS = ("while", "call", "conditional")
_KIND = re.compile(r"\s([a-z][a-z\-]*)\(")


def short_name(name: str) -> tuple:
    """``(short, kind)`` of an ops-line event: the instruction's own name
    without its number, its kind and its result's shape (layouts off)."""
    var, eq, rest = name.partition(" = ")
    m = _KIND.search(rest) if eq else None
    if not m:
        return re.sub(r"[.\d]+$", "", name)[:120] or name[:120], ""
    shape = "(tuple)" if rest.startswith("(") else re.sub(
        r"\{[^}]*\}", "", rest[:m.start()])
    return f"{re.sub(r'[.0-9]+$', '', var)} {m.group(1)} {shape}"[:120], \
        m.group(1)


def top_ops(events: list, k: int = 10) -> list:
    """``[[name, seconds]]`` of the k instructions with the most time.
    Loops and calls are left out: their bodies' instructions are listed."""
    total: dict = {}
    for name, _, dur in events:
        short, kind = short_name(name)
        if kind not in CONTAINERS:
            total[short] = total.get(short, 0.0) + dur
    best = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in best]


def label_gaps(gap_list: list, annotations: list, k: int = 10) -> list:
    """``[[label, seconds]]`` of the k longest gaps. A gap is labelled
    with the host spans that cover its middle, the request's own
    annotation (``perfbench.*``) only where no span of the program does;
    nothing covers it: ``host:unattributed``."""
    out = []
    for start, dur in gap_list[:k]:
        mid = start + dur / 2
        names = sorted({n for n, s, d in annotations if s <= mid <= s + d})
        inner = [n for n in names if not n.startswith("perfbench.")]
        label = "+".join(inner or names) or "host:unattributed"
        out.append([label[:120], dur / 1e9])
    return out
