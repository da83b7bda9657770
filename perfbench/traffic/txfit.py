"""Traffic kind ``txfit``: a closed loop of ``POST /models`` requests that
each fit the ``tx`` family's language-model block on token rows.

One client; it sends the next fit when the last returned, a fit in
flight when the window's seconds run out is finished, and ``sweep_s`` is
the window over the fits completed, as ``sweep`` defines it. Parameters
(the workload file's ``traffic`` block):

    classifiers      ``["tx"]``: the one family of a request
    label            the label column
    warm_steps       ``train_steps`` of the warm-up request (it compiles
                     every program of the cell; the step program does
                     not depend on the number of steps)
    steps_compared   leading steps held against the reference's own
    pages_per_sweep  pages of test rows read back per fit (one page
                     holds all ``n_test`` rows here)
    trace_sweeps     fits a ``--trace 1`` run profiles

Tables from the seed (the configuration's ``data`` block): each row is a
document of one of ``num_classes`` topics, a topic its own seeded
permutation of a Zipf law over the ids above the label tokens; the label
is the topic. Set-up ends when the warm-up request has returned. After
the window a client reads back every fit's metadata (the fit's own step
reports are stored there) and its test rows; the last fit's persisted
weights are read from the saved model's files; then the server is
stopped, the device freed, and the plain reference takes the same first
steps from the same seeded weights and runs its own forward pass on the
persisted ones.
"""

from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time

import numpy as np

from perfbench import cells, compare_tx
from perfbench.traffic.sweep import (
    PAGE, closed_loop, outcomes, say, traced)


def make_tables(conf: dict, seed: int) -> tuple:
    """``(train tokens (n, T) int32, train labels, test tokens, test
    labels)`` by the configuration's ``data`` recipe."""
    data = conf["data"]
    C, V, T = data["num_classes"], conf["vocab_size"], data["seq_len"]
    s_topics, s_train, s_test = np.random.SeedSequence(int(seed)).spawn(3)
    ranks = np.arange(1, V - C + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(data["zipf_exponent"]))
    cdf /= cdf[-1]
    rng = np.random.default_rng(s_topics)
    topics = np.stack([C + rng.permutation(V - C) for _ in range(C)])

    def table(n, seq):
        rng = np.random.default_rng(seq)
        labels = rng.integers(0, C, n).astype(np.int32)
        draws = np.searchsorted(cdf, rng.random((n, T)), side="right")
        draws = np.minimum(draws, V - C - 1)
        return topics[labels[:, None], draws].astype(np.int32), labels

    return table(data["n_train"], s_train) + table(data["n_test"], s_test)


def fields_of(T: int) -> list:
    return [f"t{i:05d}" for i in range(T)]


def as_columns(tokens: np.ndarray, labels: np.ndarray) -> dict:
    cols = {f: np.ascontiguousarray(tokens[:, i]).astype(np.int64)
            for i, f in enumerate(fields_of(tokens.shape[1]))}
    cols["label"] = labels.astype(np.int64)
    return cols


def run(cell: dict, seed: int, seconds: float, trace: bool, env: dict) -> dict:
    import jax

    from perfbench import reference_tx
    from perfbench.server import Server, free_device, require_program

    require_program()
    conf, tr = cell["config"], cell["traffic"]
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
            send("warm", dict(hp, train_steps=int(tr["warm_steps"])))
            srv.model.delete_trained_model("warm_tx")
        except Exception as exc:  # noqa: BLE001 — e.g. a program without the architecture block
            raise cells.BenchError(
                f"the program cannot fit this configuration: {exc}") from exc
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
            saved = reference_tx.load_saved(os.path.join(
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
            keys_kept_mean=last.get("keys_kept_mean"),
            absent_share=last.get("absent_share"),
            moe_imbalance=last.get("moe_imbalance"))

    left = free_device()
    t = time.time()
    prec = conf["precision"]["reference"]
    batches = [(train[rows], y[rows]) for rows in (
        reference_tx.batch_rows(hp["seed"], s, hp["batch"], train.shape[0])
        for s in range(n_cmp))]
    ref_steps = reference_tx.adam_steps(
        conf, reference_tx.init_weights(conf, hp["seed"]), batches,
        hp["lr"], prec)
    t_steps = time.time() - t
    free_device()
    ref_probs = None
    if saved is not None:
        ref_probs = reference_tx.class_probs(
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
