"""Traffic kind ``ssmfit``: ``txfit``'s closed loop of ``POST /models``
requests, each a fit of the ``tx`` family on token rows, held to the
reference module the workload file's ``traffic`` block names
(``reference``: a module of ``perfbench`` with ``hybridfit``'s
reference's functions: ``batch_rows``, ``init_weights``,
``adam_steps``, ``class_probs``, ``load_saved``).

``hybridfit``'s loop with the reference named and not imported: a cell
of another block adds its reference module and names it, and no fifth
copy of the loop is made. Everything that is no model's is ``txfit``'s
and ``sweep``'s, by import: the tables from the seed (``make_tables``),
the closed loop, the traced run, the comparison (``compare_tx.compare``:
it holds the numbers the workload's ``limits`` name). As ``hybridfit``
does, the warm-up model is not deleted before the window. The other
parameters of the ``traffic`` block are ``txfit``'s.
"""

from __future__ import annotations

import importlib
import os
import shutil
import sys
import tempfile
import time

from perfbench import cells, compare_tx
from perfbench.traffic.sweep import (
    PAGE, closed_loop, outcomes, say, traced)
from perfbench.traffic.txfit import as_columns, fields_of, make_tables


def run(cell: dict, seed: int, seconds: float, trace: bool, env: dict) -> dict:
    import jax

    from perfbench.server import Server, free_device, require_program

    require_program()
    conf, tr = cell["config"], cell["traffic"]
    reference = importlib.import_module("perfbench." + tr["reference"])
    hp = dict(conf["families"]["tx"], seed=int(seed) % (2 ** 31 - 1))
    n_cmp = int(tr["steps_compared"])
    t = time.time()
    train, y, test, y_test = make_tables(conf, seed)
    fields = fields_of(train.shape[1])
    say(phase="tables", seconds=round(time.time() - t, 3),
        train=list(train.shape), test=list(test.shape))

    trace_dir = trace and (env.get("trace_dir") or tempfile.mkdtemp(
        prefix="perfbench_trace_"))
    with Server() as srv:
        srv.place("train", as_columns(train, y))
        srv.place("test", as_columns(test, y_test))

        def send(prefix, hparams=hp):
            srv.model.create_model("train", "test", prefix,
                                   tr["classifiers"], tr["label"],
                                   hparams={"tx": hparams})

        t = time.time()
        c0 = srv.compile_count()
        try:
            # The warm-up model stays on disk until the server's scratch
            # goes: unlinking its gigabytes here is no part of the
            # traffic, and the file system freeing them slows the
            # window's first save.
            send("warm", dict(hp, train_steps=int(tr["warm_steps"])))
        except Exception as exc:  # noqa: BLE001 — e.g. a program without the architecture block
            raise cells.BenchError(
                f"the program cannot fit this configuration: {exc}") from exc
        # What set-up wrote and did not sync (compile-cache entries, the
        # tables' and the warm-up's files) is flushed here, inside
        # set-up: its write-back would otherwise land on a window
        # save's syncs at a moment that differs run to run.
        os.sync()
        setup_s = time.time() - env["t0"]
        say(phase="warm_up", seconds=round(time.time() - t, 3),
            compiles=srv.compile_count() - c0, setup_s=round(setup_s, 3))

        c0 = srv.compile_count()
        if trace:
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
        n_fits = len(times)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.local_devices())
        say(phase="window", fits=n_fits, window_s=round(window_s, 4),
            each_s=[round(b - a, 3) for a, b in times],
            compiles_in_window=compiled, memory_peak_bytes=peak)

        # What a client reads back: every fit's metadata and test rows.
        t = time.time()
        docs, fits = {}, []
        for i in range(n_fits):
            name, rows = f"s{i}_tx", []
            try:
                doc = srv.db.read_file(name, limit=1)[0]
                for first in range(0, min(test.shape[0],
                                          tr["pages_per_sweep"] * PAGE), PAGE):
                    page = srv.db.read_file(name, skip=1 + first, limit=PAGE)
                    rows += [(first + j, r) for j, r in enumerate(page)]
            except Exception as exc:  # noqa: BLE001 — a failed read is a failed answer
                print(f"read of {name} failed: {exc!r}", file=sys.stderr)
                doc = None
            docs[name] = doc
            fits.append({"meta": doc, "rows": rows,
                         "probs_of": i == n_fits - 1})
        attempted, failed = outcomes(docs)
        # The last fit's weights as it persisted them, from its files.
        saved = None
        try:
            saved = reference.load_saved(os.path.join(
                srv.scratch, "store", "_models", f"s{n_fits - 1}_tx"))
        except Exception as exc:  # noqa: BLE001 — no saved model: off.tx reads nan
            print(f"saved model unreadable: {exc!r}", file=sys.stderr)
        # Newest first: the window's requests, not the warm-up's. Read in
        # every run: what varies run to run is known by span name.
        spans = [srv.obs.trace(tdoc["trace_id"])["spans"] for tdoc in
                 srv.obs.traces(route="/models", limit=n_fits)]
        say(phase="spans", fits=[
            {sp["name"]: round(sp["duration_ms"] / 1e3, 3) for sp in tree
             if sp["name"].startswith("fit.tx.")} for tree in reversed(spans)])
        last = docs.get(f"s{n_fits - 1}_tx") or {}
        for prefix in ["warm"] + [f"s{i}" for i in range(n_fits)]:
            try:
                srv.db.delete_file(f"{prefix}_tx")
            except Exception:  # noqa: BLE001 — already counted as failed
                pass
        say(phase="read_back", seconds=round(time.time() - t, 3),
            rows=sum(len(f["rows"]) for f in fits),
            accuracy=last.get("accuracy"), loss=last.get("loss"),
            state_absmax=last.get("state_absmax"))

    left = free_device()
    t = time.time()
    prec = conf["precision"]["reference"]
    batches = [(train[rows], y[rows]) for rows in (
        reference.batch_rows(hp["seed"], s, hp["batch"], train.shape[0])
        for s in range(n_cmp))]
    ref_steps = reference.adam_steps(
        conf, reference.init_weights(conf, hp["seed"]), batches,
        hp["lr"], prec)
    t_steps = time.time() - t
    free_device()
    ref_probs = None
    if saved is not None:
        ref_probs = reference.class_probs(
            conf, saved, test, conf["data"]["num_classes"], prec)
    else:
        fits[-1]["rows"] = []                # nothing to compare: nan
    say(phase="reference", seconds=round(time.time() - t, 3),
        steps_s=round(t_steps, 3), bytes_left_by_program=left)
    correct, checks, observed = compare_tx.compare(
        fits, failed, ref_steps, ref_probs, test, y_test, fields,
        cell["limits"], cell["tolerance"])
    if compiled:
        print(f"{compiled} compilations inside the measured window",
              file=sys.stderr)
        correct = False
    checks["compiles_in_window"] = {"value": float(compiled), "limit": 0.0}

    device = dict(env["device"], memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "checks": checks, "observed": observed, "device": device,
              "breakdown": None}
    e2e = {"sweep_s": window_s / n_fits, "setup_s": setup_s}
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
