"""Traffic kind ``sweep``: a closed loop of ``POST /models`` requests.

One client; it sends the next five-family sweep when the last returned,
a sweep in flight when the window's seconds run out is finished, and the
window is the time from the first send to the last return. Parameters
(the workload file's ``traffic`` block):

    classifiers      the families of one request
    label            the label column
    pages_per_sweep  20-row pages read back per family and sweep for
                     the comparison, drawn from the seed
    trace_sweeps     sweeps a ``--trace 1`` run profiles

Set-up ends when one whole sweep has returned (it compiles or loads every
program of the cell). After the window the prediction datasets are read
back through the client, then deleted; then the server is stopped, the
device freed, and the plain reference fits the same tables.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from perfbench import cells, compare, datagen, trace_reduce

PAGE = 20          # the server's read cap (reference parity)
READERS = 16       # client threads of the read-back after the window


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def closed_loop(send, seconds: float, clock=time.time, max_sends=None):
    """Call ``send(i)`` back to back until ``seconds`` have passed since
    the first send (the one in flight is finished), or ``max_sends``.
    Returns ``[(t_send, t_return)]``."""
    out = []
    t_first = clock()
    while True:
        t0 = clock()
        send(len(out))
        out.append((t0, clock()))
        if out[-1][1] - t_first >= seconds:
            break
        if max_sends is not None and len(out) >= max_sends:
            break
    return out


def outcomes(docs: dict) -> tuple:
    """``(attempted, failed)`` from ``{dataset: metadata doc or None}``: a
    dataset that is missing, unfinished or carries an error has failed."""
    failed = sum(1 for doc in docs.values()
                 if not doc or not doc.get("finished") or doc.get("error"))
    return len(docs), failed


def sample_pages(seed: int, n_rows: int, n_sweeps: int, families: list,
                 per_sweep: int) -> dict:
    """``{(sweep, family): [first row of a page]}``, drawn from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 77]))
    pages = max(1, n_rows // PAGE)
    return {(s, c): sorted(int(p) * PAGE for p in rng.choice(
        pages, min(per_sweep, pages), replace=False))
        for s in range(n_sweeps) for c in families}


def make_tables(conf: dict, seed: int):
    data = conf["data"]
    s_train, s_test = np.random.SeedSequence(int(seed)).spawn(2)
    recipe = {"tile_scale": data.get("tile_scale", 1.0),
              "floor": data.get("signal_floor", 0.0)}
    train = datagen.make_table(data["n_train"], data["n_features"], s_train,
                               **recipe)
    test = datagen.make_table(data["n_test"], data["n_features"], s_test,
                              **recipe)
    return train, test


def run(cell: dict, seed: int, seconds: float, trace: bool, env: dict) -> dict:
    import jax

    from perfbench import reference
    from perfbench.server import Server, free_device, require_program

    require_program()
    conf, tr = cell["config"], cell["traffic"]
    families = list(tr["classifiers"])
    t = time.time()
    (XT, y), (XT_test, y_test) = make_tables(conf, seed)
    say(phase="tables", seconds=round(time.time() - t, 3),
        train=list(XT.shape), test=list(XT_test.shape))

    trace_dir = trace and (env.get("trace_dir") or tempfile.mkdtemp(
        prefix="perfbench_trace_"))
    with Server() as srv:
        srv.place("train", datagen.as_columns(XT, y))
        srv.place("test", datagen.as_columns(XT_test, y_test))

        def send(prefix):
            srv.model.create_model("train", "test", prefix, families,
                                   tr["label"])

        t = time.time()
        c0 = srv.compile_count()
        send("warm")
        setup_s = time.time() - env["t0"]
        say(phase="warm_up", seconds=round(time.time() - t, 3),
            compiles=srv.compile_count() - c0, setup_s=round(setup_s, 3))

        c0 = srv.compile_count()
        if trace:
            # Device ops and the annotations below, nothing else: the
            # Python tracer and the runtime's own host events slow the
            # program's host code inside the traced window.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)

        def timed_send(i):
            with jax.profiler.TraceAnnotation(f"perfbench.sweep.{i}"):
                send(f"s{i}")

        times = closed_loop(timed_send, seconds,
                            max_sends=tr["trace_sweeps"] if trace else None)
        if trace:
            jax.profiler.stop_trace()
        compiled = srv.compile_count() - c0
        window_s = times[-1][1] - times[0][0]
        n_sweeps = len(times)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())
        say(phase="window", sweeps=n_sweeps, window_s=round(window_s, 4),
            each_s=[round(b - a, 3) for a, b in times],
            compiles_in_window=compiled, memory_peak_bytes=peak)

        # What a client reads back: every dataset's metadata, and the
        # sampled pages of every sweep.
        t = time.time()
        plan = sample_pages(seed, XT_test.shape[1], n_sweeps, families,
                            tr["pages_per_sweep"])

        def read_back(key):
            s, c = key
            name, rows = f"s{s}_{c}", []
            try:
                doc = srv.db.read_file(name, limit=1)[0]
                for first in plan[key]:
                    page = srv.db.read_file(name, skip=1 + first, limit=PAGE)
                    rows += [(first + j, r) for j, r in enumerate(page)]
            except Exception as exc:  # noqa: BLE001 — a failed read is a failed answer
                print(f"read of {name} failed: {exc!r}", file=sys.stderr)
                doc = None
            return name, c, doc, rows

        # A few reader threads: each GET waits ~40 ms on the server's
        # socket (see PERF.md), and the waits overlap.
        docs, samples = {}, {c: [] for c in families}
        with ThreadPoolExecutor(max_workers=READERS) as pool:
            for name, c, doc, rows in pool.map(read_back, sorted(plan)):
                docs[name] = doc
                samples[c] += rows
        attempted, failed = outcomes(docs)
        spans = []
        if trace:
            # Newest first: the window's requests, not the warm-up's.
            for tdoc in srv.obs.traces(route="/models", limit=n_sweeps):
                spans.append(srv.obs.trace(tdoc["trace_id"])["spans"])
        accuracy = {c: docs[f"s0_{c}"].get("accuracy") for c in families
                    if docs.get(f"s0_{c}")}
        for prefix in ["warm"] + [f"s{i}" for i in range(n_sweeps)]:
            for c in families:
                try:
                    srv.db.delete_file(f"{prefix}_{c}")
                except Exception:  # noqa: BLE001 — already counted as failed
                    pass
        say(phase="read_back", seconds=round(time.time() - t, 3),
            rows={c: len(v) for c, v in samples.items()}, accuracy=accuracy)

    left = free_device()
    t = time.time()
    ref = reference.fit_predict(XT, y, XT_test, conf["families"], families,
                                conf["precision"]["reference"])
    say(phase="reference", seconds=round(time.time() - t, 3),
        bytes_left_by_program=left)
    correct, checks, observed = compare.compare(
        samples, failed, ref, XT_test, y_test, cell["limits"],
        cell["tolerance"])
    if compiled:
        print(f"{compiled} compilations inside the measured window",
              file=sys.stderr)
        correct = False
    checks["compiles_in_window"] = {"value": float(compiled), "limit": 0.0}

    device = dict(env["device"], memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "checks": checks, "observed": observed, "device": device,
              "breakdown": None}
    e2e = {"sweep_s": window_s / n_sweeps, "setup_s": setup_s}
    if not trace:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell["end_to_end"]}
        return result

    result["metrics"], result["breakdown"] = traced(
        cell, env, trace_dir, times, spans, device)
    if not env.get("trace_dir"):
        shutil.rmtree(trace_dir, ignore_errors=True)
    return result


def traced(cell: dict, env: dict, trace_dir: str, times: list, spans: list,
           device: dict) -> tuple:
    """``(per-layer metrics, breakdown)`` of the traced sweeps, and
    ``busy_s`` / ``window_s`` into ``device``. The window runs from the
    first request's annotation to the end of the last one's."""
    profile = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    ops = trace_reduce.device_ops(profile)
    notes = trace_reduce.host_annotations(profile, "perfbench.sweep.")
    lo = notes[0][1] if notes else min(
        (e[1] for evs in ops.values() for e in evs), default=0.0)
    hi = (notes[-1][1] + notes[-1][2]) if notes else max(
        (e[1] + e[2] for evs in ops.values() for e in evs), default=0.0)
    per_chip = {k: trace_reduce.clip(v, lo, hi) for k, v in ops.items()}
    busiest = max(per_chip.values(), key=trace_reduce.busy_ns, default=[])
    ctx = {"cell": cell, "spans": spans, "ops": busiest,
           "n_sweeps": len(times), "window_ns": hi - lo,
           "peaks": env["peaks"], "n_chips": max(len(per_chip), 1)}
    device["busy_s"] = float(np.mean(
        [trace_reduce.busy_ns(v) for v in per_chip.values()] or [0.0])) / 1e9
    device["window_s"] = (hi - lo) / 1e9
    # The program's spans on the trace's clock: request i was sent at
    # times[i][0] by the host's clock and at notes[i] by the trace's.
    # The phases only: a family's whole ``fit.<c>`` span and its
    # ``.device`` part say nothing about what the host was doing.
    whole = {f"fit.{c}" for c in cell["traffic"]["classifiers"]}
    host_spans = list(notes)
    for (t_send, _), note, tree in zip(times, notes, reversed(spans)):
        shift = note[1] - t_send * 1e9
        host_spans += [(sp["name"], sp["start"] * 1e9 + shift,
                        sp["duration_ms"] * 1e6) for sp in tree
                       if sp["name"].startswith(("fit.", "design."))
                       and not sp["name"].endswith(".device")
                       and sp["name"] not in whole]
    breakdown = {
        "device_ops": trace_reduce.top_ops(busiest),
        "idle_gaps": trace_reduce.label_gaps(
            trace_reduce.gaps(busiest, lo, hi), host_spans)}
    return cells.layer_metrics(cell, ctx), breakdown
