"""End-to-end tracing plane (ISSUE 9): span ring buffer, parent links
through the serving batcher, HTTP trace roots + X-Request-Id contract,
job traces whose device spans reconcile with the job profile, Prometheus
exposition (live-scraped and line-regex validated), histogram-aware
OpTimer, and the structured logger's trace-id stamping."""

import io
import json
import re
import threading

import numpy as np
import pytest
import requests

from learningorchestra_tpu.client import Context, DatabaseApi, Observability
from learningorchestra_tpu.config import Settings
from learningorchestra_tpu.serving.app import App
from learningorchestra_tpu.utils import structlog, tracing
from learningorchestra_tpu.utils.profiling import (
    BUCKETS_S, OpTimer, op_timer, quantile_from_buckets, timed)


@pytest.fixture(autouse=True)
def _tracing_isolation():
    tracing.reset()
    tracing.set_sample(None)
    tracing.set_capacity(None)
    yield
    tracing.reset()
    tracing.set_sample(None)
    tracing.set_capacity(None)


# -- core span mechanics ------------------------------------------------------

def test_span_nesting_and_parent_links():
    with tracing.trace("root", attrs={"route": "/x"}) as root:
        with tracing.span("mid") as mid:
            with tracing.span("leaf", rows=3):
                pass
    tree = tracing.trace_tree(root.trace_id)
    assert tree["span_count"] == 3
    by_name = {s["name"]: s for s in tree["spans"]}
    assert by_name["root"]["parent_id"] is None
    assert by_name["mid"]["parent_id"] == root.span_id
    assert by_name["leaf"]["parent_id"] == mid.span_id
    assert by_name["leaf"]["attrs"] == {"rows": 3}
    # Nested view mirrors the links.
    assert tree["roots"][0]["name"] == "root"
    assert tree["roots"][0]["children"][0]["name"] == "mid"
    assert tree["roots"][0]["children"][0]["children"][0]["name"] == "leaf"


def test_error_status_records_and_reraises():
    with pytest.raises(ValueError):
        with tracing.trace("boom") as ctx:
            raise ValueError("nope")
    (span,) = tracing.spans_for(ctx.trace_id)
    assert span["status"] == "error"
    assert "nope" in span["error"]


def test_ring_buffer_eviction_is_bounded():
    tracing.set_capacity(8)
    ids = []
    for i in range(20):
        with tracing.trace(f"t{i}") as ctx:
            pass
        ids.append(ctx.trace_id)
    counters = tracing.counters_snapshot()
    assert counters["buffer_spans"] == 8
    assert counters["spans_recorded"] == 20
    assert counters["spans_dropped"] == 12
    # Oldest evicted, newest retained.
    assert tracing.spans_for(ids[0]) == []
    assert len(tracing.spans_for(ids[-1])) == 1


def test_sampling_zero_mints_ids_but_records_nothing():
    tracing.set_sample(0.0)
    with tracing.trace("unsampled") as ctx:
        assert ctx.trace_id                     # id still propagates
        with tracing.span("child") as c:
            assert c is ctx or c is None        # no child bookkeeping
        assert tracing.record_span("manual", 0.01) is None
    assert tracing.spans_for(ctx.trace_id) == []
    assert tracing.counters_snapshot()["traces_unsampled"] == 1


def test_ingest_merges_and_tree_dedupes():
    with tracing.trace("local") as ctx:
        pass
    worker_doc = {"trace_id": ctx.trace_id, "span_id": "w1",
                  "parent_id": ctx.span_id, "name": "dispatch.device",
                  "start": 1.0, "duration_ms": 5.0, "process": 1}
    assert tracing.ingest([worker_doc, worker_doc, {"junk": True}]) == 2
    tree = tracing.trace_tree(ctx.trace_id)
    assert tree["processes"] == [0, 1]
    # Duplicate shipment collapses to one node.
    assert tree["span_count"] == 2
    assert [c["name"] for c in tree["roots"][0]["children"]] == [
        "dispatch.device"]


def test_pop_spans_removes_from_buffer():
    with tracing.trace("job") as ctx:
        with tracing.span("inner"):
            pass
    popped = tracing.pop_spans(ctx.trace_id)
    assert len(popped) == 2
    assert tracing.spans_for(ctx.trace_id) == []


def test_recent_traces_filters():
    with tracing.trace("http.handle",
                       attrs={"route": "/files", "status": 200}):
        pass
    # The async-job shape: the job span is a CHILD of the submitting
    # request's trace — the kind filter must still find the sweep.
    with tracing.trace("http.handle", attrs={"route": "/models"}) as req:
        with tracing.span("job.model_builder", kind="model_builder"):
            pass
    assert [t["trace_id"] for t in tracing.recent_traces(
        route="/files")] != [req.trace_id]
    (got,) = tracing.recent_traces(kind="model_builder")
    assert got["trace_id"] == req.trace_id
    assert got["kinds"] == ["model_builder"]
    assert got["spans"] == 2
    assert tracing.recent_traces(min_ms=1e7) == []
    # One summary per trace, newest first.
    assert len(tracing.recent_traces()) == 2


# -- OpTimer histograms (satellite: the max(count,1) guard is gone) ----------

def test_op_timer_histogram_aware_and_never_empty():
    t = OpTimer()
    t.record("op.a", 0.004)
    t.record("op.a", 0.006)
    snap = t.snapshot()
    assert set(snap) == {"op.a"}            # no empty entries, ever
    s = snap["op.a"]
    assert s["count"] == 2
    assert s["mean_s"] == pytest.approx(0.005)
    assert sum(s["buckets"]) == s["count"]
    assert len(s["buckets"]) == len(BUCKETS_S) + 1
    assert s["p50_s"] is not None and s["p99_s"] >= s["p50_s"]


def test_quantile_from_buckets_interpolates():
    buckets = [0] * (len(BUCKETS_S) + 1)
    buckets[3] = 100                        # all mass in (0.005, 0.01]
    est = quantile_from_buckets(buckets, 0.5)
    assert 0.005 <= est <= 0.01
    assert quantile_from_buckets([0] * (len(BUCKETS_S) + 1), 0.5) is None
    # +Inf bucket clamps to the last finite bound.
    top = [0] * (len(BUCKETS_S) + 1)
    top[-1] = 5
    assert quantile_from_buckets(top, 0.99) == BUCKETS_S[-1]


def test_timed_emits_matching_span():
    with tracing.trace("op-ctx") as ctx:
        with timed("tracing_test.timed_op"):
            pass
    spans = [s for s in tracing.spans_for(ctx.trace_id)
             if s["name"] == "tracing_test.timed_op"]
    assert len(spans) == 1
    assert op_timer.snapshot()["tracing_test.timed_op"]["count"] >= 1


# -- parent linking under the batcher ----------------------------------------

def test_batcher_parent_links():
    from learningorchestra_tpu.serving.batcher import ModelBatcher, _Stats

    class _Entry:
        def predict(self, X):
            return np.tile(np.asarray([[0.25, 0.75]], np.float32),
                           (len(X), 1))

    cfg = Settings()
    b = ModelBatcher("tm", cfg, _Stats())
    entry = _Entry()
    roots = {}

    def one_request(i):
        with tracing.trace("http.handle") as ctx:
            roots[i] = ctx
            b.submit(np.zeros((2, 3), np.float32), entry)

    try:
        threads = [threading.Thread(target=one_request, args=(i,),
                                    name=f"req-{i}") for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        b.stop()

    for ctx in roots.values():
        spans = tracing.spans_for(ctx.trace_id)
        by_name = {s["name"]: s for s in spans}
        # queue.wait hangs off the request's root span.
        assert by_name["queue.wait"]["parent_id"] == ctx.span_id
        # dispatch.device's parent is the coalesced batch.coalesce span
        # (recorded into the first co-batched request's trace).
        dispatch = by_name["dispatch.device"]
        assert dispatch["attrs"]["co_batched"] >= 1
        coalesce_ids = set()
        for other in roots.values():
            for s in tracing.spans_for(other.trace_id):
                if s["name"] == "batch.coalesce":
                    coalesce_ids.add(s["span_id"])
        assert dispatch["parent_id"] in coalesce_ids


def test_serving_percentiles_track_recent_window():
    """Review finding: a long-lived server's JSON-view p50/p99 must
    follow the RECENT latency regime, not drown a regression in
    millions of historical observations — while the lifetime histogram
    (the Prometheus series) keeps every observation."""
    from learningorchestra_tpu.serving.batcher import _Stats

    s = _Stats()
    for _ in range(5000):
        s.observe(0.005)                     # days of fast traffic
    for _ in range(2):                       # regression: two epochs of
        s._rotated_at -= 1e3                 # slow traffic (forced
        for _ in range(50):                  # rotation)
            s.observe(0.5)
    snap = s.snapshot(0)
    # The window now holds only slow epochs: p50 reflects the regression
    # even though 98% of lifetime observations were fast.
    assert snap["p50_ms"] > 100, snap["p50_ms"]
    # The lifetime series kept everything for scrapers.
    assert sum(snap["latency"]["buckets"]) == 5100
    # An idle gap longer than both epochs clears the window instead of
    # promoting a stale epoch into "recent": percentiles fall back to
    # the lifetime shape (dominated by the fast regime here).
    s._rotated_at -= 1e4
    snap = s.snapshot(0)
    assert snap["p50_ms"] < 100, snap["p50_ms"]


# -- live server: HTTP roots, /traces, /trace/{id}, prometheus ---------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("trace_serve")
    cfg = Settings()
    cfg.store_root = str(tmp / "store")
    cfg.image_root = str(tmp / "images")
    cfg.port = 0
    cfg.persist = True
    app = App(cfg, recover=False)
    rng = np.random.default_rng(0)
    n = 400
    y = rng.integers(0, 2, n)
    centers = rng.normal(size=(2, 4)) * 2.0
    X = centers[y] + rng.normal(size=(n, 4))
    cols = {f"x{j}": X[:, j] for j in range(4)}
    cols["label"] = y.astype(np.int64)
    for name in ("tr_train", "tr_test"):
        app.store.create(name, columns={k: v.copy()
                                        for k, v in cols.items()})
        app.store.finish(name)
    server = app.serve(background=True)
    ctx = Context(f"http://127.0.0.1:{server.port}", poll_seconds=0.05,
                  timeout=120)
    yield ctx, app
    server.stop()


def test_http_root_span_and_request_id_contract(served):
    ctx, app = served
    rid = "req-abc.123"
    resp = requests.get(ctx.url("/files"), headers={"X-Request-Id": rid})
    assert resp.status_code == 200
    # The response echoes the inbound id; the trace is queryable by it.
    assert resp.headers["X-Request-Id"] == rid
    tree = requests.get(ctx.url(f"/trace/{rid}")).json()
    root = tree["roots"][0]
    assert root["name"] == "http.handle"
    assert root["attrs"]["route"] == "/files"
    assert root["attrs"]["status"] == 200
    # A garbage inbound id is replaced, not propagated.
    bad = requests.get(ctx.url("/files"),
                       headers={"X-Request-Id": "x" * 200})
    assert bad.headers["X-Request-Id"] != "x" * 200
    # Errors carry an id too, and /traces can filter the route.
    miss = requests.get(ctx.url("/files/definitely_missing"))
    assert miss.status_code == 404 and miss.headers["X-Request-Id"]
    listed = requests.get(
        ctx.url("/traces"), params={"route": "/files/definitely_missing"}
    ).json()
    assert listed and listed[0]["attrs"]["status"] == 404


def test_unknown_trace_404s(served):
    ctx, _app = served
    assert requests.get(ctx.url("/trace/feedfacefeedface")).status_code == 404


def test_client_wrappers_and_error_request_id(served):
    ctx, _app = served
    obs = Observability(ctx)
    assert isinstance(obs.traces(limit=5), list)
    with pytest.raises(RuntimeError) as exc:
        DatabaseApi(ctx).read_file("definitely_missing")
    m = re.search(r"\[request-id ([0-9a-f]{16})\]", str(exc.value))
    assert m, f"no request id in client error: {exc.value}"
    tree = ctx.trace(m.group(1))
    assert tree["roots"][0]["attrs"]["status"] == 404


def test_sweep_job_trace_reconciles_with_profile(served):
    """Acceptance: a classifier-sweep job's trace shows the PR-3
    structure — per-family host_prep/device/finish spans, correctly
    parented — and the device spans sum to within 5% of the job
    profile's fit_device_s."""
    ctx, app = served
    resp = requests.post(ctx.url("/models"), json={
        "training_filename": "tr_train", "test_filename": "tr_test",
        "prediction_filename": "tr_pred",
        "classificators_list": ["lr", "nb"], "label": "label",
        "sync": False})
    assert resp.status_code == 201, resp.text
    app.jobs.wait_all(timeout=120)
    (job,) = [j for j in requests.get(ctx.url("/jobs")).json()
              if j["kind"] == "model_builder"]
    assert job["status"] == "done"
    assert job["trace_id"]
    profile = job["profile"]["fit_device_s"]

    tree = requests.get(ctx.url(f"/trace/{job['trace_id']}")).json()
    by_name = {}
    for s in tree["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    # The async job joins the submitting POST's trace.
    assert by_name["http.handle"][0]["attrs"]["route"] == "/models"
    (job_span,) = by_name["job.model_builder"]
    # One ``build`` span under the job holds the whole of the build.
    (build,) = by_name["build"]
    assert build["parent_id"] == job_span["span_id"]
    (design,) = by_name["design.build"]
    assert design["parent_id"] == build["span_id"]
    for fam in ("lr", "nb"):
        (fit,) = by_name[f"fit.{fam}"]
        assert fit["parent_id"] == build["span_id"]
        for phase in ("host_prep", "device", "finish"):
            (ps,) = by_name[f"fit.{fam}.{phase}"]
            assert ps["parent_id"] == fit["span_id"], (fam, phase)
        (dev,) = by_name[f"fit.{fam}.device"]
        # The trace's device span and the profile's fit_device_s are the
        # same measurement — they must agree (5% covers rounding).
        assert dev["duration_ms"] / 1e3 == pytest.approx(
            profile[fam], rel=0.05, abs=5e-4), (fam, profile)


def test_failed_family_fit_span_records_error(served):
    """A failing family's fit.<c> span must carry status=error — the
    trace view and the job report may never disagree about whether a
    family succeeded (review finding: the except used to sit inside the
    span, so failures recorded as ok)."""
    from learningorchestra_tpu.models.builder import ModelBuilder

    _ctx, app = served
    mb = ModelBuilder(app.store, app.runtime, app.cfg)
    with tracing.trace("job.model_builder") as ctx:
        reports = mb.build("tr_train", "tr_test", "tr_failspan", ["lr"],
                           "label", hparams={"lr": {"bogus_knob": 1}})
    assert "error" in reports[0].metrics
    spans = {s["name"]: s for s in tracing.spans_for(ctx.trace_id)}
    assert spans["fit.lr"]["status"] == "error"
    assert "bogus_knob" in spans["fit.lr"]["error"]


SWEEP = ["dt", "rf", "gb", "nb"]
PHASES = ("host_prep", "gate_wait", "dispatch", "device", "finish")
FINISH_PARTS = ("score", "model", "rows", "store")


def _sync_sweep(ctx, prefix):
    resp = requests.post(ctx.url("/models"), json={
        "training_filename": "tr_train", "test_filename": "tr_test",
        "prediction_filename": prefix, "classificators_list": SWEEP,
        "label": "label"})
    assert resp.status_code == 201, resp.text
    return resp


def test_sync_sweep_span_tree(served):
    """The span tree of a four-family sync ``POST /models`` (ISSUE 27):
    one ``build`` under the request, every family's five phases under
    its ``fit.<c>`` and covering it, the finish sub-phases inside their
    ``finish``, the report's ``device_s`` the device span's duration,
    and the sub-phases in the attribution table."""
    ctx, _app = served
    resp = _sync_sweep(ctx, "tr_tree")
    device_s = {r["classifier"]: r["device_s"] for r in resp.json()["result"]}
    spans = requests.get(ctx.url(
        f"/trace/{resp.headers['X-Request-Id']}")).json()["spans"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)

    def end(s):
        return s["start"] + s["duration_ms"] / 1e3

    (root,) = by_name["http.handle"]
    (build,) = by_name["build"]
    assert build["parent_id"] == root["span_id"]
    assert build["attrs"] == {"train": "tr_train", "test": "tr_test",
                              "classifiers": SWEEP, "rows": 400}
    assert by_name["design.build"][0]["parent_id"] == build["span_id"]
    for c in SWEEP:
        (fit,) = by_name[f"fit.{c}"]
        assert fit["parent_id"] == build["span_id"]
        covered = 0.0
        for phase in PHASES:
            (ps,) = by_name[f"fit.{c}.{phase}"]
            assert ps["parent_id"] == fit["span_id"], (c, phase)
            assert ps["status"] == "ok"
            covered += ps["duration_ms"]
        # Nothing of a family's time is outside a phase (the gate wait
        # and the dispatch used to be fit.<c> minus its children). The
        # device phases' exit samples of device bytes lie inside
        # dispatch and device: on this rig each walks every live array
        # of the process, milliseconds in a worker that ran other files
        # first, over 5% of a warm nb fit when they lay between phases.
        assert covered == pytest.approx(fit["duration_ms"], rel=0.05), c
        (finish,) = by_name[f"fit.{c}.finish"]
        for part in FINISH_PARTS:
            (ps,) = by_name[f"fit.{c}.finish.{part}"]
            assert ps["parent_id"] == finish["span_id"], (c, part)
            assert finish["start"] - 1e-3 <= ps["start"]
            assert end(ps) <= end(finish) + 1e-3
        # The save's waits and writes nest inside its span (ISSUE 38;
        # a tree family's params take the checkpoint layer's path: one
        # of each).
        (save,) = by_name[f"fit.{c}.finish.model"]
        for part in ("fetch", "write"):
            (ps,) = by_name[f"fit.{c}.finish.model.{part}"]
            assert ps["parent_id"] == save["span_id"], (c, part)
            assert save["start"] - 1e-3 <= ps["start"]
            assert end(ps) <= end(save) + 1e-3
        # journal.commit nests under the store phase.
        (store,) = by_name[f"fit.{c}.finish.store"]
        assert any(s["parent_id"] == store["span_id"]
                   for s in by_name["journal.commit"])
        # One measurement: the report's device_s IS the span's duration.
        (dev,) = by_name[f"fit.{c}.device"]
        assert dev["duration_ms"] / 1e3 == pytest.approx(device_s[c],
                                                         abs=1.5e-6)
        # What each step compiled (None where the windows overlapped).
        for phase in ("dispatch", "device"):
            attrs = by_name[f"fit.{c}.{phase}"][0]["attrs"]
            assert set(attrs) == {"compiles", "compile_s"}
            assert attrs["compiles"] is None or attrs["compiles"] >= 0

    table = requests.get(ctx.url("/metrics")).json()["latency_attribution"]
    for phase in PHASES + tuple(f"finish.{p}" for p in FINISH_PARTS):
        assert set(table[f"fit.{phase}"]) >= set(SWEEP), phase
    # Ten phases a family: the 512-entry cap is nowhere near.
    assert sum(len(v) for k, v in table.items()
               if k.startswith("fit")) == 10 * len(SWEEP)
    assert "attribution_dropped" not in tracing.counters_snapshot()


def test_build_tail_is_the_finishing_after_the_last_device_phase(served):
    """``build.tail`` (ISSUE 31): once a build, under ``build``, from
    the end of the last family's device phase to the end of the round —
    the finishing nothing on the device overlaps."""
    ctx, _app = served
    resp = _sync_sweep(ctx, "tr_tail")
    spans = requests.get(ctx.url(
        f"/trace/{resp.headers['X-Request-Id']}")).json()["spans"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)

    def end(s):
        return s["start"] + s["duration_ms"] / 1e3

    (build,) = by_name["build"]
    (tail,) = by_name["build.tail"]
    assert tail["parent_id"] == build["span_id"]
    assert tail["status"] == "ok"
    last_device = max(end(by_name[f"fit.{c}.device"][0]) for c in SWEEP)
    last_finish = max(end(by_name[f"fit.{c}.finish"][0]) for c in SWEEP)
    # Spans round to the millisecond; the clocks are read a few
    # statements apart.
    assert tail["start"] == pytest.approx(last_device, abs=0.02)
    assert last_finish - 0.02 <= end(tail) <= end(build) + 0.002


def test_unsampled_sweep_records_no_span(served):
    ctx, _app = served
    tracing.set_sample(0.0)
    resp = _sync_sweep(ctx, "tr_unsampled")
    assert re.fullmatch(r"[0-9a-f]{16}", resp.headers["X-Request-Id"])
    counters = tracing.counters_snapshot()
    assert counters["spans_recorded"] == 0 and counters["buffer_spans"] == 0
    assert counters["traces_unsampled"] >= 1
    assert tracing.attribution_snapshot() == {}


def test_span_raised_through_keeps_error_and_pinned_duration():
    with tracing.trace("root") as root:
        with pytest.raises(KeyError):
            with tracing.span("fit.gb.finish.rows") as sp:
                raise KeyError("gone")
        assert tracing.current() is root       # the context was restored
        with tracing.span("fit.gb.device") as sp:
            sp.duration_s = 1.25               # what device_span measured
    spans = {s["name"]: s for s in tracing.spans_for(root.trace_id)}
    assert spans["fit.gb.finish.rows"]["status"] == "error"
    assert "gone" in spans["fit.gb.finish.rows"]["error"]
    assert spans["fit.gb.device"]["duration_ms"] == 1250.0
    assert spans["root"]["status"] == "ok"


@pytest.mark.parametrize("name,key", [
    ("fit.gb", ("fit", "gb")),
    ("fit.gb.finish", ("fit.finish", "gb")),
    ("fit.gb.finish.rows", ("fit.finish.rows", "gb")),
    ("fit.nb.gate_wait", ("fit.gate_wait", "nb")),
    ("fit.gb.finish.rows.more", None),
    ("fit.tx.finish.model.sync", None),
    ("build", None),
])
def test_attribution_key_folds_fit_sub_phases_by_family(name, key):
    assert tracing._attrib_key(name, None) == key


def test_spans_lie_on_a_running_profile_under_their_names(tmp_path):
    """A span opened as a context is also a profiler annotation: a
    capture taken meanwhile (``POST /debug/profile``) holds it on the
    host plane; ``record_span`` durations, known only afterwards, are
    not there."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tracing.trace("http.handle"):
            with tracing.span("build"), tracing.span("fit.gb.finish.rows"):
                pass
            tracing.record_span("queue.wait", 0.001)
        with tracing.trace("unsampled.root", sampled=False):
            with tracing.span("unsampled.child"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    events = {ev.name: (ev.start_ns, ev.duration_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events}
    assert {"http.handle", "build", "fit.gb.finish.rows"} <= set(events)
    assert not {"queue.wait", "unsampled.root", "unsampled.child"} \
        & set(events)
    outer, inner = events["build"], events["fit.gb.finish.rows"]
    assert outer[0] <= inner[0] and \
        inner[0] + inner[1] <= outer[0] + outer[1]


#: Exposition-format line shapes (version 0.0.4): comments, and samples
#: with optional labels and a float/+Inf/NaN value.
_PROM_LINE = re.compile(
    r"^(?:# (?:HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(?:\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""
    r"(?:,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*\})?"
    r" (?:[-+]?[0-9]*\.?[0-9]+(?:[eE][-+]?[0-9]+)?|\+Inf|NaN))$")


def test_prometheus_exposition_live_scrape(served):
    """Tier-1 smoke (CI satellite): scrape ?format=prometheus from a
    live server and validate it parses — every line matches the
    exposition grammar, histogram buckets are cumulative, and +Inf
    equals _count."""
    ctx, _app = served
    op_timer.record("tracing_test.prom_op", 0.003)
    resp = requests.get(ctx.url("/metrics"),
                        params={"format": "prometheus"})
    assert resp.status_code == 200
    assert resp.headers["Content-Type"].startswith("text/plain")
    text = resp.text
    assert text.endswith("\n")
    for line in text.splitlines():
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"

    # Histogram invariants for the op we just recorded.
    bucket_re = re.compile(
        r'^lo_op_seconds_bucket\{op="tracing_test\.prom_op",le="([^"]+)"\}'
        r" (\d+)$", re.M)
    buckets = bucket_re.findall(text)
    assert buckets and buckets[-1][0] == "+Inf"
    counts = [int(c) for _le, c in buckets]
    assert counts == sorted(counts), "buckets must be cumulative"
    count_re = re.search(
        r'^lo_op_seconds_count\{op="tracing_test\.prom_op"\} (\d+)$',
        text, re.M)
    assert int(count_re.group(1)) == counts[-1]
    # The JSON view comes from the same registry snapshot.
    doc = requests.get(ctx.url("/metrics")).json()
    assert doc["ops"]["tracing_test.prom_op"]["count"] == counts[-1]
    assert "tracing" in doc

    # Resource & capacity plane series (ISSUE 10): the new lo_resource_*
    # / lo_compile_* / lo_alert_* gauges render from the same snapshot
    # and pass the same grammar sweep above.
    for needle in ("lo_resource_host_rss_bytes",
                   "lo_resource_host_open_fds",
                   "lo_resource_disk_free_bytes",
                   "lo_resource_device_total_bytes_in_use",
                   "lo_compile_compiles", "lo_compile_compile_s",
                   "lo_compile_cache_hits",
                   "lo_alert_firing", "lo_alert_threshold",
                   "lo_pod_degraded"):
        assert re.search(rf"^{needle}(?:\{{| )", text, re.M), \
            f"missing exposition series: {needle}"
    # Every rule on /alerts has a firing gauge, and the JSON sections
    # exist in the same document.
    alert_names = set(doc["alerts"]["rules"])
    exposed = set(re.findall(r'^lo_alert_firing\{alert="([^"]+)"\}',
                             text, re.M))
    assert exposed == alert_names
    assert doc["resources"]["host"]["rss_bytes"] > 0
    assert doc["compile"]["compiles"] >= 0


# -- structured logs ----------------------------------------------------------

def _restore_logger_tree():
    import logging

    root = logging.getLogger(structlog.ROOT)
    for h in list(root.handlers):
        root.removeHandler(h)
    root.propagate = True
    root.setLevel(logging.NOTSET)


def test_structlog_json_carries_trace_ids():
    cfg = Settings()
    cfg.log_format = "json"
    buf = io.StringIO()
    structlog.configure(cfg, stream=buf)
    try:
        log = structlog.get_logger("tracing_test")
        with tracing.trace("logged-op") as ctx:
            log.info("inside %s", "trace")
        log.info("outside")
        lines = [json.loads(ln) for ln in
                 buf.getvalue().strip().splitlines()]
        assert lines[0]["msg"] == "inside trace"
        assert lines[0]["trace_id"] == ctx.trace_id
        assert lines[0]["logger"] == "lo_tpu.tracing_test"
        assert "trace_id" not in lines[1]
    finally:
        _restore_logger_tree()


def test_structlog_text_appends_trace_ids():
    cfg = Settings()
    cfg.log_format = "text"
    buf = io.StringIO()
    structlog.configure(cfg, stream=buf)
    try:
        log = structlog.get_logger("tracing_test")
        with tracing.trace("logged-op") as ctx:
            log.warning("slow thing")
        line = buf.getvalue().strip()
        assert f"trace={ctx.trace_id}" in line
        assert "slow thing" in line
    finally:
        _restore_logger_tree()
