"""Resumable-ingest tests (VERDICT r3 §4).

Every ingest chunk commit journals the source byte offset past its last
row; an ingest killed mid-flight resumes from the last committed byte on
restart instead of failing — upgraded behavior over the reference, whose
mid-flight crash left ``finished: false`` forever (SURVEY.md §5).
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import learningorchestra_tpu.catalog.ingest as ing
from learningorchestra_tpu.catalog.ingest import ingest_csv_url, resume_ingest
from learningorchestra_tpu.catalog.store import DatasetStore


def _write_csv(path, n):
    lines = ["a,b,s"]
    for i in range(n):
        lines.append(f"{i},{i * 1.5},tag{i % 5}")
    path.write_text("\n".join(lines) + "\n")
    return str(path)


def _expected(n):
    return (list(range(n)), [i * 1.5 for i in range(n)],
            [f"tag{i % 5}" for i in range(n)])


def _dying_stream(real_open, die_after):
    """``_open_url_stream`` whose source dies once it has served
    ``die_after`` bytes, in 4 KB pieces.

    The pipeline parses up to ``min(8, max(4, cores)) + 2`` blocks ahead
    of the one it appends and commits (ingest.py ``max_inflight``), and a
    dead stream abandons them as a dead process would. At 200 rows a
    block (6,400 bytes of this CSV) that is up to 64,000 bytes parsed and
    not yet committed, so ``die_after`` has to lie well beyond it for any
    core count to leave committed rows behind."""
    def dying(url, timeout, offset=0):
        served = 0
        for chunk in real_open(url, timeout, offset=offset):
            for i in range(0, len(chunk), 4 << 10):
                piece = chunk[i:i + (4 << 10)]
                served += len(piece)
                yield piece
                if served > die_after:
                    raise ConnectionError("stream died")
    return dying


def _assert_rows_identical(ds, n):
    ea, eb, es = _expected(n)
    assert ds.num_rows == n
    assert ds.column("a").tolist() == ea
    assert ds.column("b").tolist() == eb
    assert ds.column("s").tolist() == es


def test_src_offsets_journaled(cfg, tmp_path):
    cfg.persist = True
    cfg.ingest_chunk_rows = 100
    cfg.ingest_commit_bytes = 0
    p = _write_csv(tmp_path / "d.csv", 1000)
    store = DatasetStore(cfg)
    store.create("d", url=p)
    ingest_csv_url(store, "d", p, cfg)
    journal = os.path.join(cfg.store_root, "d", "journal.jsonl")
    with open(journal) as f:
        recs = [json.loads(line) for line in f]
    assert len(recs) >= 2
    offs = [r["src_off"] for r in recs]
    assert offs == sorted(offs)
    # Last committed offset is exactly the file size (all bytes consumed).
    assert offs[-1] == os.path.getsize(p)
    assert store.get("d").resume_offset == os.path.getsize(p)


def test_interrupted_ingest_resumes_byte_identical(cfg, tmp_path):
    """Simulated process death: the source stream dies mid-ingest, the
    process 'restarts' (fresh store over the same root), and resume
    completes the dataset with byte-identical rows."""
    cfg.persist = True
    cfg.ingest_chunk_rows = 200
    cfg.ingest_commit_bytes = 0
    n = 20_000
    p = _write_csv(tmp_path / "d.csv", n)

    real_open = ing._open_url_stream
    store = DatasetStore(cfg)
    store.create("d", url=p)
    ing._open_url_stream = _dying_stream(real_open, 200_000)
    try:
        with pytest.raises(ConnectionError):
            ingest_csv_url(store, "d", p, cfg)
    finally:
        ing._open_url_stream = real_open

    committed = store.get("d").num_rows
    assert 0 < committed < n            # genuinely mid-flight

    # "Restart": fresh catalog from disk. The interrupted ingest is
    # resumable, not failed.
    store2 = DatasetStore(cfg)
    store2.load_all(resume_ingests=True)
    assert store2.resumable_ingests == ["d"]
    ds = store2.get("d")
    assert ds.metadata.finished is False and ds.metadata.error is None
    assert ds.num_rows == committed

    resume_ingest(store2, "d", cfg)
    _assert_rows_identical(store2.get("d"), n)
    assert store2.get("d").metadata.finished is True

    # And the resumed dataset survives another reload (journal coherent).
    store3 = DatasetStore(cfg)
    store3.load_all()
    _assert_rows_identical(store3.get("d"), n)


def test_load_all_without_resume_flag_still_fails_interrupted(cfg, tmp_path):
    """CLI/default recovery keeps the terminal-state guarantee: without
    resume_ingests, an interrupted ingest is marked failed (pollers
    terminate), exactly as before."""
    cfg.persist = True
    cfg.ingest_chunk_rows = 100
    cfg.ingest_commit_bytes = 0
    p = _write_csv(tmp_path / "d.csv", 1000)
    store = DatasetStore(cfg)
    store.create("d", url=p)
    real_open = ing._open_url_stream

    def dying(url, timeout, offset=0):
        it = real_open(url, timeout, offset=offset)
        yield next(it)[:8 << 10]
        raise ConnectionError("died")

    ing._open_url_stream = dying
    try:
        with pytest.raises(ConnectionError):
            ingest_csv_url(store, "d", p, cfg)
    finally:
        ing._open_url_stream = real_open
    store2 = DatasetStore(cfg)
    store2.load_all()
    doc = store2.get("d").metadata.to_doc()
    assert doc["finished"] is True and "interrupted" in doc["error"]


def test_resume_noop_when_source_fully_committed(cfg, tmp_path):
    """Resuming a dataset whose offset is already EOF appends nothing."""
    cfg.persist = True
    cfg.ingest_chunk_rows = 100
    cfg.ingest_commit_bytes = 0
    n = 500
    p = _write_csv(tmp_path / "d.csv", n)
    store = DatasetStore(cfg)
    store.create("d", url=p)
    ingest_csv_url(store, "d", p, cfg)
    ds = store.get("d")
    ds.metadata.finished = False        # pretend the finish flip was lost
    resume_ingest(store, "d", cfg)
    _assert_rows_identical(store.get("d"), n)


def test_resume_refuses_changed_source(cfg, tmp_path):
    """A source rewritten between crash and restart must NOT be spliced
    onto the committed prefix: resume validates the identity captured at
    ingest start and refuses."""
    from learningorchestra_tpu.catalog.ingest import SourceChanged

    cfg.persist = True
    cfg.ingest_chunk_rows = 200
    cfg.ingest_commit_bytes = 0
    p = _write_csv(tmp_path / "d.csv", 20_000)

    real_open = ing._open_url_stream
    store = DatasetStore(cfg)
    store.create("d", url=p)
    ing._open_url_stream = _dying_stream(real_open, 150_000)
    try:
        with pytest.raises(ConnectionError):
            ingest_csv_url(store, "d", p, cfg)
    finally:
        ing._open_url_stream = real_open

    # Rewrite the source with different content (and length).
    _write_csv(tmp_path / "d.csv", 1000)

    store2 = DatasetStore(cfg)
    store2.load_all(resume_ingests=True)
    with pytest.raises(SourceChanged):
        resume_ingest(store2, "d", cfg)


def test_kill9_mid_ingest_then_resume(cfg, tmp_path):
    """The full drill: SIGKILL a real ingesting process mid-flight, then a
    fresh process resumes from the journal and the dataset matches a
    clean one-shot parse byte for byte."""
    cfg.persist = True
    n = 20000
    p = _write_csv(tmp_path / "big.csv", n)
    child = os.path.join(os.path.dirname(__file__), "resume_child.py")
    proc = subprocess.Popen(
        [sys.executable, child, cfg.store_root, p],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    journal = os.path.join(cfg.store_root, "victim", "journal.jsonl")
    deadline = time.time() + 60
    # Wait for >=2 committed chunks, then kill -9.
    while time.time() < deadline:
        if proc.poll() is not None:
            out, err = proc.communicate()
            pytest.fail(f"child exited early: {out!r} {err!r}")
        try:
            with open(journal) as f:
                if sum(1 for _ in f) >= 2:
                    break
        except FileNotFoundError:
            pass
        time.sleep(0.05)
    else:
        pytest.fail("child never committed two chunks")
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait()
    proc.stdout.close()   # SIGKILL path never communicate()s; close the
    proc.stderr.close()   # pipes or their GC trips the warning gate

    cfg.ingest_chunk_rows = 500
    cfg.ingest_commit_bytes = 0
    store = DatasetStore(cfg)
    store.load_all(resume_ingests=True)
    assert store.resumable_ingests == ["victim"]
    committed = store.get("victim").num_rows
    assert committed < n
    resume_ingest(store, "victim", cfg)
    ds = store.get("victim")
    _assert_rows_identical(ds, n)
    assert ds.metadata.finished is True


def test_app_auto_resumes_interrupted_ingest(cfg, tmp_path):
    """Server startup resubmits interrupted ingests as jobs (App wiring)."""
    from learningorchestra_tpu.serving.app import App

    cfg.persist = True
    cfg.ingest_chunk_rows = 100
    cfg.ingest_commit_bytes = 0
    n = 3000
    p = _write_csv(tmp_path / "d.csv", n)
    store = DatasetStore(cfg)
    store.create("d", url=p)
    real_open = ing._open_url_stream

    def dying(url, timeout, offset=0):
        served = 0
        for chunk in real_open(url, timeout, offset=offset):
            for i in range(0, len(chunk), 4 << 10):
                piece = chunk[i:i + (4 << 10)]
                served += len(piece)
                yield piece
                if served > 20_000:
                    raise ConnectionError("died")

    ing._open_url_stream = dying
    try:
        with pytest.raises(ConnectionError):
            ingest_csv_url(store, "d", p, cfg)
    finally:
        ing._open_url_stream = real_open
    del store

    app = App(cfg, recover=True)
    app.jobs.wait_all(timeout=60)
    ds = app.store.get("d")
    _assert_rows_identical(ds, n)
    assert ds.metadata.finished is True
    kinds = [j["kind"] for j in app.jobs.records()]
    assert "ingest_resume" in kinds
