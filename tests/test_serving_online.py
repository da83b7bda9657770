"""Online inference tier tests: AOT predict programs + the continuous
micro-batching queue (models/aot.py, serving/batcher.py, the
``POST /trained-models/{name}/predict`` route).

The load-bearing guarantees under test:

- batched-vs-serial parity: any interleaving / padding bucket through the
  micro-batcher is BIT-identical to one-row-at-a-time predictions through
  the batch predict path, for every online-servable family;
- the endpoint is exempt from idempotency replay (read-like: identical
  retried POSTs must both hit the model);
- queue-full → 503 + Retry-After, which the stock client retries to
  completion.
"""

import threading

import numpy as np
import pytest
import requests

from learningorchestra_tpu.client import Context, Model, micro_batches
from learningorchestra_tpu.models.registry import ONLINE_KINDS

FAMILIES = list(ONLINE_KINDS)


@pytest.fixture(scope="module")
def online(tmp_path_factory):
    """Live in-process server with one persisted model per online
    family, fitted on a Titanic-shaped task (string column for the
    vocab path, NaNs for the fillna path)."""
    from learningorchestra_tpu.config import Settings
    from learningorchestra_tpu.serving.app import App

    tmp = tmp_path_factory.mktemp("online")
    cfg = Settings()
    cfg.store_root = str(tmp / "store")
    cfg.image_root = str(tmp / "images")
    cfg.port = 0
    cfg.persist = False
    cfg.serve_max_batch = 64            # bucket ladder 1/8/64
    app = App(cfg, recover=False)
    rng = np.random.default_rng(0)
    n = 400
    sex = rng.choice(["male", "female"], n)
    age = rng.integers(1, 70, n).astype(np.float64)
    age[rng.random(n) < 0.1] = np.nan   # exercise fitted fillna stats
    surv = (rng.random(n) < np.where(sex == "female", 0.8, 0.2)).astype(
        np.int64)
    ds = app.store.create("otrain")
    ds.append_columns({
        "Sex": sex.astype(object), "Age": age,
        # Integer column on purpose: fillna fits statistics only for
        # float columns, so a serve-time null here is unfillable — the
        # explicit-406 path under test in test_predict_errors.
        "Pclass": rng.integers(1, 4, n).astype(np.int64),
        "Fare": rng.lognormal(2.5, 1.0, n), "Survived": surv})
    app.store.finish("otrain")
    app.builder.build("otrain", "otrain", "om", FAMILIES, "Survived")
    server = app.serve(background=True)
    ctx = Context(f"http://127.0.0.1:{server.port}", poll_seconds=0.1,
                  timeout=60)
    yield ctx, app, server
    server.stop()


def _sample_rows(n, seed=1):
    """Dict rows covering the preprocessing surface: categories (one the
    vocab never saw), None ages (fitted mean-fill), float fares."""
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        rows.append({
            "Sex": rng.choice(["male", "female", "other"]).item(),
            "Age": None if rng.random() < 0.15 else int(rng.integers(1, 70)),
            "Pclass": int(rng.integers(1, 4)),
            "Fare": round(float(rng.lognormal(2.5, 1.0)), 4),
        })
    return rows


def _oracle(app, name, rows):
    """One-row-at-a-time predictions through the batch predict path
    (registry.load + TrainedModel.predict_proba over the mesh) — the
    builder.predict serving oracle."""
    from learningorchestra_tpu.models.aot import design_from_rows

    man, model = app.builder.registry.load(name)
    X = design_from_rows(rows, man["preprocess"])
    return np.concatenate(
        [np.asarray(model.predict_proba(app.runtime, X[i:i + 1]),
                    np.float32) for i in range(len(X))], axis=0)


@pytest.mark.parametrize("kind", FAMILIES)
def test_batched_vs_serial_parity(online, kind):
    """Micro-batched probabilities — any coalescing interleaving, any
    padding bucket — must be bit-identical to the one-row-at-a-time
    batch-path oracle."""
    ctx, app, server = online
    name = f"om_{kind}"
    rows = _sample_rows(40)
    oracle = _oracle(app, name, rows)

    # One request spanning the top bucket (40 rows → bucket 64).
    out = Model(ctx).predict_online(name, rows, max_batch=64)
    got = np.asarray(out["probabilities"], np.float32)
    np.testing.assert_array_equal(got, oracle)
    assert out["predictions"] == np.argmax(oracle, axis=1).tolist()

    # Concurrent mixed-size requests: the dispatcher coalesces them in
    # whatever interleaving the scheduler produces; every slice must
    # still scatter back bit-identical.
    sizes = [1, 3, 7, 12, 17]
    offsets = np.cumsum([0] + sizes)
    results = [None] * len(sizes)

    def submit(j):
        lo, hi = offsets[j], offsets[j + 1]
        results[j] = app.predictor.predict(name, rows[lo:hi])

    threads = [threading.Thread(target=submit, args=(j,))
               for j in range(len(sizes))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for j in range(len(sizes)):
        lo, hi = offsets[j], offsets[j + 1]
        np.testing.assert_array_equal(
            np.asarray(results[j]["probabilities"], np.float32),
            oracle[lo:hi])


def test_predict_errors(online):
    ctx, app, server = online
    # unknown model → 404
    r = requests.post(ctx.url("/trained-models/nope/predict"),
                      json={"rows": [{"Age": 1}]})
    assert r.status_code == 404
    # missing feature fields → 406
    r = requests.post(ctx.url("/trained-models/om_lr/predict"),
                      json={"rows": [{"NotAField": 1}]})
    assert r.status_code == 406
    # empty / malformed rows → 406
    r = requests.post(ctx.url("/trained-models/om_lr/predict"),
                      json={"rows": []})
    assert r.status_code == 406
    # list rows of the wrong width → 406
    r = requests.post(ctx.url("/trained-models/om_lr/predict"),
                      json={"rows": [[1.0]]})
    assert r.status_code == 406
    # null for a field with NO fitted fill statistic (Pclass was an
    # integer column at train time, so fillna never fitted a mean for
    # it): must 406 naming the field, not serve NaN probabilities
    # (live-verification finding)
    r = requests.post(ctx.url("/trained-models/om_lr/predict"),
                      json={"rows": [{"Sex": "male", "Age": 30,
                                      "Pclass": None, "Fare": 7.5}]})
    assert r.status_code == 406 and "Pclass" in r.json()["result"]
    # over the per-request cap → 406 (the client splits client-side)
    too_many = [[1.0, 2.0, 3.0]] * 65
    r = requests.post(ctx.url("/trained-models/om_lr/predict"),
                      json={"rows": too_many})
    assert r.status_code == 406
    # missing body field → 400
    r = requests.post(ctx.url("/trained-models/om_lr/predict"), json={})
    assert r.status_code == 400
    # rows present but not an array (null / scalar) → 406, not a
    # TypeError 500 (review finding)
    for bad in (None, 5, "x"):
        r = requests.post(ctx.url("/trained-models/om_lr/predict"),
                          json={"rows": bad})
        assert r.status_code == 406, (bad, r.status_code)
    # list rows holding non-numeric elements → 406, not numpy's
    # TypeError as a 500 (review finding)
    for bad_rows in ([[1.0, {"a": 1}, 3.0, 4.0]],
                     [[1.0, 2.0, 3.0, 4.0], {"Sex": "male"}]):
        r = requests.post(ctx.url("/trained-models/om_lr/predict"),
                          json={"rows": bad_rows})
        assert r.status_code == 406, (bad_rows, r.status_code)
    # extra non-feature fields (full raw records) are tolerated, and
    # strings for an actual numeric FEATURE are rejected naming it
    ok = {"Sex": "male", "Age": 30, "Pclass": 2, "Fare": 7.5,
          "Name": "Smith, John", "Ticket": "A/5 21171"}
    r = requests.post(ctx.url("/trained-models/om_lr/predict"),
                      json={"rows": [ok]})
    assert r.status_code == 200
    r = requests.post(ctx.url("/trained-models/om_lr/predict"),
                      json={"rows": [dict(ok, Pclass="first")]})
    assert r.status_code == 406 and "Pclass" in r.json()["result"]


def test_stopped_dispatcher_maps_to_503(online):
    """A request racing the model's dispatcher teardown (DELETE or
    shutdown) gets 503 + Retry-After — transient, retryable — never a
    500 (review finding: the bare RuntimeError used to fall through the
    exception mapping)."""
    ctx, app, server = online
    b = app.predictor._batcher("om_nb")
    # Simulate the race window: stopped but still registered (DELETE's
    # invalidate() pops it only after stop() completes).
    b.stop()
    try:
        r = requests.post(ctx.url("/trained-models/om_nb/predict"),
                          json={"rows": [{"Sex": "male", "Age": 30,
                                          "Pclass": 3, "Fare": 7.5}]})
        assert r.status_code == 503 and r.headers.get("Retry-After")
    finally:
        app.predictor.invalidate("om_nb")   # fresh dispatcher for later tests
    r = requests.post(ctx.url("/trained-models/om_nb/predict"),
                      json={"rows": [{"Sex": "male", "Age": 30,
                                      "Pclass": 3, "Fare": 7.5}]})
    assert r.status_code == 200


def test_predict_online_empty_rows_not_silent_success(online):
    """predict_online([]) must surface the server's 406 for empty rows
    (review finding: the SDK used to fabricate an empty success without
    any HTTP call, masking e.g. a typo'd model name)."""
    ctx, app, server = online
    with pytest.raises(RuntimeError):
        Model(ctx).predict_online("om_lr", [])
    with pytest.raises(RuntimeError):
        Model(ctx).predict_online("no_such_model", [])


def test_predict_online_learns_server_cap(online):
    """The cap parsed from an oversized call's 406 sticks on the Model,
    so later oversized calls split correctly up front instead of paying
    a guaranteed-406 round trip each time."""
    ctx, app, server = online
    m = Model(ctx)
    rejected = app.predictor.snapshot()["models"]["om_lr"]["rejected"]
    out = m.predict_online("om_lr", _sample_rows(80, seed=7))
    assert len(out["predictions"]) == 80 and m._server_max_batch == 64
    out = m.predict_online("om_lr", _sample_rows(80, seed=8))
    assert len(out["predictions"]) == 80
    # No new queue-level rejections, and only the FIRST call's probe
    # 406 — the second call split to the learned cap straight away.
    assert (app.predictor.snapshot()["models"]["om_lr"]["rejected"]
            == rejected)


def test_predict_exempt_from_idempotency(online):
    """Two identical predict POSTs sharing an Idempotency-Key must BOTH
    hit the model — /predict is read-like and exempt from the POST
    replay cache (a replayed prediction would pin a client to a stale
    model version and hide re-execution)."""
    ctx, app, server = online
    before = app.predictor.snapshot()["models"].get(
        "om_nb", {}).get("requests", 0)
    body = {"rows": [{"Sex": "male", "Age": 30, "Pclass": 2,
                      "Fare": 7.5}]}
    key = "same-key-on-purpose"
    r1 = requests.post(ctx.url("/trained-models/om_nb/predict"),
                       json=body, headers={"Idempotency-Key": key})
    r2 = requests.post(ctx.url("/trained-models/om_nb/predict"),
                       json=body, headers={"Idempotency-Key": key})
    assert r1.status_code == 200 and r2.status_code == 200
    assert r1.json()["probabilities"] == r2.json()["probabilities"]
    after = app.predictor.snapshot()["models"]["om_nb"]["requests"]
    assert after - before == 2          # executed twice, not replayed


def test_client_micro_batch_split(online):
    """Inputs above the server's per-request cap split client-side and
    concatenate in row order."""
    ctx, app, server = online
    assert [len(c) for c in micro_batches(list(range(10)), 4)] == [4, 4, 2]
    with pytest.raises(ValueError):
        micro_batches([1], 0)

    rows = _sample_rows(150, seed=3)    # > serve_max_batch=64
    # Default client cap (256) exceeds this server's (64): the first
    # attempt 406s with the server's cap in the message and the client
    # re-splits to it — the default call must work against any server.
    out = Model(ctx).predict_online("om_lr", rows)
    assert len(out["predictions"]) == 150
    oracle = _oracle(app, "om_lr", rows)
    np.testing.assert_array_equal(
        np.asarray(out["probabilities"], np.float32), oracle)


def test_request_bigger_than_queue_is_terminal_406(online):
    """A request with more rows than the whole queue can NEVER be
    accepted — it must 406 with the effective cap (which the client
    re-splits to) instead of 503ing retryably forever (review
    finding)."""
    ctx, app, server = online
    old = app.cfg.serve_queue_depth
    app.cfg.serve_queue_depth = 4
    try:
        rows = _sample_rows(8, seed=11)
        r = requests.post(ctx.url("/trained-models/om_lr/predict"),
                          json={"rows": rows})
        assert r.status_code == 406
        assert "serve_max_batch=4" in r.json()["result"]
        out = Model(ctx).predict_online("om_lr", rows)  # re-splits to 4
        assert len(out["predictions"]) == 8
    finally:
        app.cfg.serve_queue_depth = old


def test_queue_full_503_and_stock_client_retries(online):
    """Backpressure end-to-end: with the dispatcher wedged and the queue
    at capacity, raw requests get 503 + Retry-After; the stock client's
    backoff machinery retries the same call to completion once the
    queue drains."""
    ctx, app, server = online
    entry = app.predictor.aot.entry("om_lr")
    orig_predict = entry.predict
    started = threading.Event()
    gate = threading.Event()

    def wedged(X):
        started.set()
        assert gate.wait(20), "test gate never released"
        return orig_predict(X)

    entry.predict = wedged
    old_depth = app.cfg.serve_queue_depth
    app.cfg.serve_queue_depth = 2
    url = ctx.url("/trained-models/om_lr/predict")
    row = {"Sex": "male", "Age": 30, "Pclass": 3, "Fare": 7.5}
    first = {}

    def post_first():
        first["resp"] = requests.post(url, json={"rows": [row]},
                                      timeout=30)

    t_first = threading.Thread(target=post_first)
    try:
        # r1 enters the dispatcher and wedges; r2 fills the queue (2
        # rows = depth); r3 must bounce with 503 + Retry-After.
        t_first.start()
        assert started.wait(10), "dispatcher never picked up r1"
        r2 = [None]
        t_second = threading.Thread(target=lambda: r2.__setitem__(
            0, requests.post(url, json={"rows": [row, row]}, timeout=30)))
        t_second.start()
        deadline = 50
        while app.predictor._batcher("om_lr").queue_rows() < 2:
            deadline -= 1
            assert deadline > 0, "r2 never queued"
            threading.Event().wait(0.1)
        r3 = requests.post(url, json={"rows": [row]}, timeout=30)
        assert r3.status_code == 503
        assert "Retry-After" in r3.headers
        assert float(r3.headers["Retry-After"]) >= 1

        # Stock client against the still-full queue: first attempt(s)
        # eat 503s, the Retry-After-paced retries land after release.
        fast_ctx = Context(ctx.base_url, retries=8, backoff_seconds=0.05,
                           retry_after_cap=0.3)
        client_out = {}
        t_client = threading.Thread(target=lambda: client_out.update(
            Model(fast_ctx).predict_online("om_lr", [row])))
        t_client.start()
        threading.Event().wait(0.3)     # let it collect at least one 503
        gate.set()
        t_client.join(timeout=30)
        assert not t_client.is_alive(), "client never completed"
        assert len(client_out["predictions"]) == 1
        t_first.join(timeout=30)
        t_second.join(timeout=30)
        assert first["resp"].status_code == 200
        assert r2[0].status_code == 200
        assert app.predictor.snapshot()["models"]["om_lr"]["rejected"] >= 1
    finally:
        gate.set()
        entry.predict = orig_predict
        app.cfg.serve_queue_depth = old_depth


def test_hot_swap_and_delete(online):
    """A re-saved model serves its new version without a restart (the
    AOT cache keys on the manifest version token); a deleted model 404s
    and its compiled programs drop."""
    ctx, app, server = online
    reg = app.builder.registry
    row = [{"Sex": "female", "Age": 20, "Pclass": 1, "Fare": 30.0}]
    app.predictor.predict("om_dt", row)
    ev0 = app.predictor.snapshot()["aot"]["evictions"]
    man, model = reg.load("om_dt")
    v0 = reg.version("om_dt")
    reg.save("om_dt", model, metrics=man.get("metrics"),
             preprocess=man.get("preprocess"))
    assert reg.version("om_dt") != v0
    app.predictor.predict("om_dt", row)     # reloads + recompiles
    assert app.predictor.snapshot()["aot"]["evictions"] == ev0 + 1

    # delete through the route: programs invalidated, predicts 404
    r = requests.delete(ctx.url("/trained-models/om_dt"))
    assert r.status_code == 200
    r = requests.post(ctx.url("/trained-models/om_dt/predict"),
                      json={"rows": row})
    assert r.status_code == 404


def test_dispatcher_survives_timeout_withdrawal():
    """A timeout withdrawal that empties the queue during the linger
    wait must not kill the dispatcher thread (review finding: _loop
    read the empty batch as 'stopped and drained' and returned, leaving
    a dead dispatcher that black-holed the model until restart)."""
    import time as _time

    from learningorchestra_tpu.config import Settings
    from learningorchestra_tpu.serving.batcher import (
        ModelBatcher, PredictTimeout, _Stats)

    class _StubEntry:
        preprocess = None
        kind = "stub"

        def predict(self, X):
            return np.tile(np.array([[0.3, 0.7]]), (len(X), 1))

    entry = _StubEntry()
    cfg = Settings()
    cfg.serve_max_wait_ms = 150         # linger: waits for a fuller batch
    cfg.serve_timeout_s = 0.05          # handler gives up mid-linger
    b = ModelBatcher("m", cfg, _Stats())
    try:
        with pytest.raises(PredictTimeout):
            b.submit(np.zeros((1, 2)), entry)
        _time.sleep(0.4)                # linger deadline passes, loop spins
        assert b._thread.is_alive(), "dispatcher died after withdrawal"
        cfg.serve_timeout_s = 10.0
        assert b.submit(np.zeros((2, 2)), entry).shape == (2, 2)
    finally:
        b.stop()


def test_mixed_entry_batch_groups_by_entry():
    """Requests that straddle a hot-swap carry the AOT entry their
    design was built against; a coalesced batch holding two entry
    versions dispatches per-group so old-state rows never run through
    new params (review finding)."""
    from learningorchestra_tpu.config import Settings
    from learningorchestra_tpu.serving.batcher import ModelBatcher, _Stats

    class _Entry:
        def __init__(self, v):
            self.v = v

        def predict(self, X):
            return np.full((len(X), 2), self.v)

    e1, e2 = _Entry(1.0), _Entry(2.0)
    cfg = Settings()
    cfg.serve_max_wait_ms = 50          # encourage coalescing both
    cfg.serve_timeout_s = 10.0
    b = ModelBatcher("m", cfg, _Stats())
    res = {}
    try:
        ts = [threading.Thread(
            target=lambda e=e, k=k: res.__setitem__(
                k, b.submit(np.zeros((2, 2)), e)))
            for k, e in (("a", e1), ("b", e2))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        assert np.all(res["a"] == 1.0), res["a"]
        assert np.all(res["b"] == 2.0), res["b"]
    finally:
        b.stop()


def test_hot_swap_never_404s_live_traffic(online):
    """Re-saves are atomic against concurrent /predict: a request must
    never see a transient ModelNotFound (→ terminal 404 at the client)
    because save() is mid-rewrite (review finding: the old rmtree→
    checkpoint→manifest sequence left a long missing-model window)."""
    ctx, app, server = online
    reg = app.builder.registry
    man, model = reg.load("om_gb")
    url = ctx.url("/trained-models/om_gb/predict")
    row = {"Sex": "male", "Age": 40, "Pclass": 2, "Fare": 12.0}
    stop = threading.Event()
    statuses = []

    def hammer():
        while not stop.is_set():
            r = requests.post(url, json={"rows": [row]}, timeout=30)
            statuses.append(r.status_code)

    t = threading.Thread(target=hammer)
    t.start()
    try:
        for _ in range(3):
            reg.save("om_gb", model, metrics=man.get("metrics"),
                     preprocess=man.get("preprocess"))
    finally:
        stop.set()
        t.join(timeout=60)
    assert not t.is_alive()
    assert statuses and 404 not in statuses, statuses
    assert set(statuses) <= {200, 503}, statuses


def test_serving_metrics_and_status_page(online):
    ctx, app, server = online
    m = requests.get(ctx.url("/metrics")).json()
    srv = m["serving"]
    for key in ("requests", "rows", "batches", "mean_batch_rows",
                "rejected", "timeouts", "errors", "queue_rows", "qps",
                "aot", "models"):
        assert key in srv
    assert srv["requests"] >= 1
    per = srv["models"]["om_lr"]
    for key in ("p50_ms", "p99_ms", "qps", "mean_batch_rows",
                "queue_rows", "rejected"):
        assert key in per
    assert per["p50_ms"] is not None and per["p50_ms"] >= 0

    html = requests.get(ctx.url("/status")).text
    assert "Online predict" in html
    assert "om_lr" in html
    assert "rows/batch" in html
