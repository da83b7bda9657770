"""Flight recorder + telemetry history end-to-end (ISSUE 13).

The load-bearing guarantees under test:

- CHAOS ACCEPTANCE: arming a serving failpoint until the quarantine
  alert fires leaves a flight-recorder bundle on disk containing the
  failing request's trace spans, the alert transition, and the
  surrounding history window — and after a process "restart" (new App
  over the same store root) ``GET /metrics/history`` still serves the
  pre-restart window;
- recorder mechanics: bounded retention, automatic-dump rate limiting,
  staged (all-or-nothing) bundle writes, best-effort gather;
- the /healthz 503 flip dumps a bundle and the client's degraded-
  healthz error quotes the freshest bundle id;
- client passthroughs: ``Observability.history()`` /
  ``.flight_recordings()`` / ``.record_flight()``;
- latency attribution rides /metrics (JSON + ``lo_phase_seconds``
  exposition) and the status page shows phase columns + history
  sparklines.
"""

import json
import os
import time

import numpy as np
import pytest
import requests

from learningorchestra_tpu.client import Context, Observability
from learningorchestra_tpu.config import Settings
from learningorchestra_tpu.utils import failpoints, flightrec

ROW = {"Sex": "male", "Age": 30, "Pclass": 3, "Fare": 7.5}


@pytest.fixture(autouse=True)
def _clean_failpoints():
    failpoints.reset()
    yield
    failpoints.reset()


def _mk_cfg(tmp):
    cfg = Settings()
    cfg.store_root = str(tmp / "store")
    cfg.image_root = str(tmp / "images")
    cfg.port = 0
    cfg.persist = False
    cfg.serve_max_batch = 64
    cfg.serve_restart_backoff_s = 0.01
    cfg.serve_quarantine_crashes = 2
    cfg.alert_window_s = 0.0
    cfg.telemetry_sample_s = 0.0          # one history sample per read
    cfg.flightrec_min_interval_s = 0.0
    return cfg


def _mk_app(cfg, with_model=True):
    from learningorchestra_tpu.serving.app import App

    app = App(cfg, recover=False)
    if with_model:
        rng = np.random.default_rng(0)
        n = 120
        sex = rng.choice(["male", "female"], n)
        surv = (rng.random(n) < np.where(sex == "female", 0.8, 0.2)
                ).astype(np.int64)
        ds = app.store.create("frtrain")
        ds.append_columns({
            "Sex": sex.astype(object),
            "Age": rng.integers(1, 70, n).astype(np.float64),
            "Pclass": rng.integers(1, 4, n).astype(np.int64),
            "Fare": rng.lognormal(2.5, 1.0, n), "Survived": surv})
        app.store.finish("frtrain")
        app.builder.build("frtrain", "frtrain", "frm", ["lr"],
                          "Survived")
    return app


@pytest.fixture(scope="module")
def flight(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("flightrec")
    cfg = _mk_cfg(tmp)
    app = _mk_app(cfg)
    server = app.serve(background=True)
    ctx = Context(f"http://127.0.0.1:{server.port}", poll_seconds=0.1,
                  timeout=60)
    app.predictor.predict("frm_lr", [ROW])      # warm the AOT ladder
    yield ctx, app, server, cfg
    server.stop()


# -- the chaos acceptance -----------------------------------------------------

def test_quarantine_dumps_bundle_and_history_survives_restart(
        tmp_path_factory):
    """The ISSUE 13 acceptance path, end to end, with its own App so
    the quarantine/restart cannot disturb the shared fixture."""
    tmp = tmp_path_factory.mktemp("chaos")
    cfg = _mk_cfg(tmp)
    app = _mk_app(cfg)
    server = app.serve(background=True)
    base = f"http://127.0.0.1:{server.port}"
    try:
        # Seed traffic + history samples.
        r = requests.post(f"{base}/trained-models/frm_lr/predict",
                          json={"rows": [ROW]}, timeout=30)
        assert r.status_code == 200
        for _ in range(3):
            requests.get(f"{base}/metrics", timeout=10)

        # Arm the failpoint persistently: every dispatch crashes, so
        # the 2-crash quarantine threshold trips on one request.
        failpoints.configure("serving.batcher.pre_dispatch=raise:0")
        r = requests.post(f"{base}/trained-models/frm_lr/predict",
                          json={"rows": [ROW]}, timeout=30)
        assert r.status_code == 503
        assert "quarantined" in r.json()["result"]
        failing_trace = r.headers["X-Request-Id"]
        failpoints.reset()

        # The alert engine sees the quarantine on the next read; its
        # firing transition dumps a bundle (the batcher's own
        # quarantine incident dumped one too — min interval is 0).
        requests.get(f"{base}/metrics", timeout=10)
        alerts_doc = requests.get(f"{base}/alerts", timeout=10).json()
        assert "serving_quarantined" in alerts_doc["firing"]
        assert alerts_doc["flightrec_latest"]

        # The batcher fails its waiters BEFORE it dumps (a blocked
        # caller gets its 503 at once), so its bundle may still be on
        # the dispatcher thread's way to disk when the 503 is back and
        # the alert's is already written: wait for both.
        want = {"serving.quarantine", "alert:serving_quarantined"}
        deadline = time.monotonic() + 30.0
        while True:
            bundles = requests.get(f"{base}/debug/flightrec",
                                   timeout=10).json()
            reasons = {b["reason"] for b in bundles}
            if want <= reasons or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        assert want <= reasons, reasons
        bdir = next(b["path"] for b in bundles
                    if b["reason"] == "alert:serving_quarantined")

        # Bundle contents: the alert transition...
        with open(os.path.join(bdir, "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["detail"]["alert"] == "serving_quarantined"
        assert manifest["detail"]["to"] == "firing"
        assert manifest["config"]["serve_quarantine_crashes"] == 2
        # ...the failing request's trace spans...
        with open(os.path.join(bdir, "spans.json")) as f:
            spans = json.load(f)
        failing = [s for s in spans if s["trace_id"] == failing_trace]
        assert failing, "failing request's trace missing from bundle"
        # The request's root span carries the 503 + quarantine message
        # (mapped HttpErrors are handled inside the trace block, so
        # the error lands in attrs, not span status).
        assert any(s["name"] == "http.handle"
                   and (s.get("attrs") or {}).get("status") == 503
                   for s in failing)
        # ...and the surrounding history window.
        with open(os.path.join(bdir, "history.json")) as f:
            hist = json.load(f)
        assert hist["samples"] >= 3
        assert "serving.requests" in hist["series"]

        pre_restart = time.time()
    finally:
        server.stop()                      # flushes the history segment

    # "Restart": a fresh App over the same store root serves the
    # pre-restart window from the flushed segments.
    app2 = _mk_app(cfg, with_model=False)
    server2 = app2.serve(background=True)
    try:
        q = requests.get(
            f"http://127.0.0.1:{server2.port}/metrics/history",
            params={"series": "serving.requests"}, timeout=10).json()
        pts = q["series"]["serving.requests"]
        assert any(t < pre_restart for t, _v in pts), \
            "pre-restart history window lost across restart"
        # The bundles survive too, listable from the new incarnation.
        reasons = [b["reason"] for b in requests.get(
            f"http://127.0.0.1:{server2.port}/debug/flightrec",
            timeout=10).json()]
        assert any(r_.startswith("alert:serving_quarantined")
                   for r_ in reasons)
    finally:
        server2.stop()


# -- recorder mechanics -------------------------------------------------------

def test_retention_rate_limit_and_staged_writes(tmp_path):
    cfg = Settings()
    cfg.store_root = str(tmp_path / "store")
    cfg.flightrec_keep = 2
    cfg.flightrec_min_interval_s = 3600.0
    rec = flightrec.FlightRecorder(cfg, gather={
        "spans": lambda: [{"name": "x"}],
        "boom": lambda: (_ for _ in ()).throw(RuntimeError("gather")),
    })
    first = rec.dump("alert:a", force=True)
    assert first is not None
    # Automatic dumps rate-limit; forced ones do not.
    assert rec.dump("alert:b") is not None      # first auto claims slot
    assert rec.dump("alert:c") is None          # suppressed
    assert rec.dump("alert:d", force=True) is not None
    snap = rec.snapshot()
    assert snap["suppressed"] == 1
    # Retention pruned to the 2 newest; no .tmp- staging left behind.
    entries = os.listdir(rec.root)
    assert len(entries) == 2
    assert not any(e.startswith(".tmp-") for e in entries)
    # A failing gather thunk degrades to an error artifact, never a
    # failed dump.
    latest = os.path.join(rec.root, rec.latest())
    with open(os.path.join(latest, "boom.json")) as f:
        assert "gather" in json.load(f)["error"]
    # keep=0 disables.
    cfg.flightrec_keep = 0
    assert rec.dump("alert:e", force=True) is None


def test_dump_minimal_and_incident_hook(tmp_path):
    # dump_minimal: what the supervisor writes on a child death.
    bundle = flightrec.dump_minimal(str(tmp_path / "s"),
                                    "supervisor:incident",
                                    detail={"exit_codes": [1]})
    assert bundle is not None
    with open(os.path.join(flightrec.bundle_root(str(tmp_path / "s")),
                           bundle, "manifest.json")) as f:
        man = json.load(f)
    assert man["detail"]["exit_codes"] == [1]
    assert man["versions"]["python"]

    # incident(): no recorder -> None; with one -> dumps through it.
    flightrec.set_recorder(None)
    assert flightrec.incident("serving.quarantine") is None
    cfg = Settings()
    cfg.store_root = str(tmp_path / "s2")
    cfg.flightrec_min_interval_s = 0.0
    rec = flightrec.FlightRecorder(cfg)
    flightrec.set_recorder(rec)
    try:
        assert flightrec.incident("serving.quarantine",
                                  detail={"model": "m"}) is not None
    finally:
        flightrec.set_recorder(None)


# -- healthz flip + client quoting --------------------------------------------

def test_healthz_flip_dumps_and_client_quotes_bundle(flight):
    ctx, app, server, cfg = flight
    obs = Observability(ctx)
    assert obs.healthz()["healthy"]
    before = {b["bundle"] for b in app.flightrec.list()}
    app.begin_drain()
    try:
        with pytest.raises(RuntimeError) as exc:
            obs.healthz()
        msg = str(exc.value)
        assert "lifecycle" in msg
        # The freshest bundle id is quoted in the degraded error.
        latest = app.flightrec.latest()
        assert latest is not None
        assert f"[flight recording {latest}]" in msg
        # The flip itself dumped a bundle naming the failing check.
        new = [b for b in app.flightrec.list()
               if b["bundle"] not in before]
        assert any(b["reason"] == "healthz:503" for b in new)
    finally:
        app._draining.clear()              # un-drain for later tests
        app._was_healthy = None


# -- client passthroughs ------------------------------------------------------

def test_client_history_and_flight_recordings(flight):
    ctx, app, server, cfg = flight
    obs = Observability(ctx)
    requests.get(ctx.url("/metrics"), timeout=10)
    doc = obs.history(series=["serving"], window_s=3600)
    assert doc["samples"] >= 1
    assert all(name.startswith("serving") for name in doc["series"])

    out = obs.record_flight("operator-test")
    assert out["bundle"]
    recs = obs.flight_recordings()
    assert recs[0]["bundle"] == out["bundle"]
    assert recs[0]["reason"] == "manual:operator-test"
    assert "manifest.json" in recs[0]["files"]


def test_manual_dump_disabled_is_406(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("nofr")
    cfg = _mk_cfg(tmp)
    cfg.flightrec_keep = 0
    app = _mk_app(cfg, with_model=False)
    server = app.serve(background=True)
    try:
        r = requests.post(
            f"http://127.0.0.1:{server.port}/debug/flightrec",
            json={}, timeout=10)
        assert r.status_code == 406
        assert "disabled" in r.json()["result"]
    finally:
        server.stop()


# -- attribution + status page ------------------------------------------------

def test_latency_attribution_on_metrics_and_exposition(flight):
    ctx, app, server, cfg = flight
    r = requests.post(ctx.url("/trained-models/frm_lr/predict"),
                      json={"rows": [ROW]}, timeout=30)
    assert r.status_code == 200
    doc = requests.get(ctx.url("/metrics"), timeout=10).json()
    attrib = doc["latency_attribution"]
    for phase in ("queue.wait", "dispatch.device", "design.build"):
        assert "frm_lr" in attrib[phase], phase
        ent = attrib[phase]["frm_lr"]
        assert ent["count"] >= 1 and ent["p99_ms"] is not None
    # fit sub-phases attribute per family (recorded here under a
    # traced scope — direct builder calls outside a job/request trace
    # record no spans, like every other instrumentation point)...
    from learningorchestra_tpu.utils import tracing
    with tracing.trace("job.attrib_probe"):
        tracing.record_span("fit.lr.device", 0.05)
        tracing.record_span("fit.lr.host_prep", 0.01)
    attrib = requests.get(ctx.url("/metrics"),
                          timeout=10).json()["latency_attribution"]
    assert attrib["fit.device"]["lr"]["count"] >= 1
    assert attrib["fit.host_prep"]["lr"]["count"] >= 1
    # ...and http.handle attributes per route.
    assert any(route.startswith("/") for route in attrib["http.handle"])
    text = requests.get(ctx.url("/metrics"),
                        params={"format": "prometheus"}, timeout=10).text
    assert 'lo_phase_seconds_bucket{phase="queue.wait",label="frm_lr"' \
        in text
    assert "lo_telemetry_samples" in text
    assert "lo_flightrec_bundles" in text


def test_unmatched_routes_cannot_poison_attribution(flight):
    """404 scanner traffic collapses into the single '-' http.handle
    label (unmatched requests carry no route attr) instead of minting
    one attribution entry per bogus URL and exhausting the bounded
    table (review finding)."""
    ctx, app, server, cfg = flight
    for i in range(5):
        r = requests.get(ctx.url(f"/no/such/route/{i}"), timeout=10)
        assert r.status_code == 404
    attrib = requests.get(ctx.url("/metrics"),
                          timeout=10).json()["latency_attribution"]
    labels = set(attrib["http.handle"])
    assert not any("/no/such/route" in lbl for lbl in labels)
    assert "-" in labels
    # Matched requests still attribute by route PATTERN, one label
    # regardless of the concrete model name in the URL.
    assert "/trained-models/{name}/predict" in labels


def test_status_page_phase_column_and_sparklines(flight):
    ctx, app, server, cfg = flight
    for _ in range(3):                     # a few history samples
        requests.get(ctx.url("/metrics"), timeout=10)
    html = requests.get(ctx.url("/status"), timeout=10).text
    assert "phase p99s (ms)" in html
    assert "device" in html                # the breakdown cell content
    assert "<svg" in html and "polyline" in html
    assert "/metrics/history" in html


def test_telemetry_section_and_history_route_filters(flight):
    ctx, app, server, cfg = flight
    doc = requests.get(ctx.url("/metrics"), timeout=10).json()
    tele = doc["telemetry"]
    assert tele["samples"] >= 1 and tele["series"] > 10
    assert doc["flightrec"]["bundles"] >= 0
    q = requests.get(ctx.url("/metrics/history"),
                     params={"series": "serving.qps,serving.requests",
                             "window": 3600}, timeout=10).json()
    assert set(q["series"]) <= {"serving.qps", "serving.requests"}
    assert q["window_s"] == 3600
