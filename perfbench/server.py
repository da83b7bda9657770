"""The system under test, started the way a user's server starts.

``App`` + the stdlib HTTP server on a free localhost port, in THIS
process (one process holds the chip), on the server's defaults: no
``LO_TPU_*`` knob is set here. The client SDK then talks to it over
HTTP. This is the only file of the benchmark that imports the program.
"""

from __future__ import annotations

import gc
import importlib.util
import shutil
import tempfile
import threading


def require_program() -> None:
    """``BenchError`` (no result line) where the checkout holds the
    benchmark but not the program."""
    if importlib.util.find_spec("learningorchestra_tpu") is None:
        from perfbench.cells import BenchError

        raise BenchError("learningorchestra_tpu is not in this checkout")


class Server:
    """``with Server() as s``: ``s.app``, and the SDK's clients ``s.db``,
    ``s.model``, ``s.obs`` bound to its port."""

    def __enter__(self):
        from learningorchestra_tpu.client import (
            Context, DatabaseApi, Model, Observability)
        from learningorchestra_tpu.config import Settings
        from learningorchestra_tpu.parallel import distributed
        from learningorchestra_tpu.serving.app import App
        from learningorchestra_tpu.utils import structlog

        structlog.configure()
        distributed.place_compile_cache()
        self.scratch = tempfile.mkdtemp(prefix="perfbench_")   # under TMPDIR
        cfg = Settings()
        cfg.host, cfg.port = "127.0.0.1", 0
        cfg.store_root = f"{self.scratch}/store"
        cfg.image_root = f"{self.scratch}/images"
        self.app = App(cfg, recover=False)
        self.http = self.app.serve(background=True)
        ctx = Context(f"http://127.0.0.1:{self.http.port}", poll_seconds=0.05,
                      timeout=900.0, request_timeout=900.0)
        self.db, self.model = DatabaseApi(ctx), Model(ctx)
        self.obs = Observability(ctx)
        return self

    def place(self, name: str, columns: dict) -> None:
        """A finished table straight into the store's RAM tier, as
        ``chip_smoke.py`` places its headline table: there is no 1.3 GB
        CSV. Not persisted (set-up would write the table to disk in every
        run); the flag goes back before any request is sent, so the timed
        path persists its prediction datasets as a server does."""
        cfg = self.app.store.cfg
        persist, cfg.persist = cfg.persist, False
        try:
            self.app.store.create(name, columns=columns, finished=True)
        finally:
            cfg.persist = persist

    def compile_count(self) -> int:
        """Backend compiles so far, as ``GET /metrics`` counts them."""
        return int(self.obs.metrics()["compile"]["compiles"])

    def __exit__(self, *exc):
        clean = self.app.drain(timeout_s=60.0)
        self.http.stop()
        left = [t.name for t in threading.enumerate() if t.name == "lo-http"]
        self.app = self.http = self.db = self.model = self.obs = None
        gc.collect()
        shutil.rmtree(self.scratch, ignore_errors=True)
        if exc[0] is None and (not clean or left):
            raise RuntimeError(f"the server did not shut down cleanly: "
                               f"drained={clean}, threads={left}")
        return False


def free_device() -> int:
    """Drop every array the stopped program left on the device, so the
    reference starts from an empty chip. Returns bytes still in use."""
    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_in_use", 0))
