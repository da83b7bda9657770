"""Test configuration.

Forces JAX onto a simulated 8-device CPU mesh — the TPU-native analogue of
"multi-node without a real cluster" (SURVEY.md §4): every sharding/collective
test runs against real XLA partitioning semantics with no TPU attached. Must
run before the first ``import jax`` anywhere in the test session.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take the chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# The environment decides for a fresh process; the config update holds
# even where jax was imported (and read the environment) before this.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# -- runtime thread sanitizer -------------------------------------------------
# Dynamic backstop for lolint's static thread-lifecycle rule
# (docs/static_analysis.md): PR 6's dispatcher thread died silently and
# black-holed its model until restart — nothing in the test suite could
# notice a background thread evaporating. Here every uncaught exception
# that kills a thread is recorded via threading.excepthook and FAILS the
# test it happened under; faulthandler dumps all thread stacks if the
# suite hard-hangs or crashes instead.

import faulthandler  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402

faulthandler.enable()


class ThreadDeath:
    """One background thread killed by an uncaught exception."""

    def __init__(self, args):
        self.name = getattr(args.thread, "name", "<unknown>") \
            if args.thread is not None else "<unknown>"
        self.exc_type = args.exc_type
        self.traceback = "".join(traceback.format_exception(
            args.exc_type, args.exc_value, args.exc_traceback))

    def __repr__(self):
        return f"<ThreadDeath {self.name}: {self.exc_type.__name__}>"


class ThreadSanitizer:
    """Collects :class:`ThreadDeath` records; the autouse fixture below
    drains them per test and fails the test that owned the thread."""

    def __init__(self):
        self._lock = threading.Lock()
        self._deaths = []

    def record(self, args):
        with self._lock:
            self._deaths.append(ThreadDeath(args))

    def drain(self):
        with self._lock:
            out, self._deaths = self._deaths, []
        return out

    def fail_if_deaths(self, where: str) -> None:
        deaths = self.drain()
        if deaths:
            details = "\n".join(d.traceback for d in deaths)
            pytest.fail(
                f"{len(deaths)} background thread(s) died with an "
                f"uncaught exception during {where}: "
                f"{[d.name for d in deaths]} — a silently dead thread "
                "black-holes whatever it owned (the PR 6 dispatcher "
                "class). Handle the exception in the thread or mark the "
                "test @pytest.mark.allow_thread_death.\n" + details,
                pytrace=False)


thread_sanitizer_state = ThreadSanitizer()


def _sanitizing_excepthook(args):
    if args.exc_type is SystemExit:
        return  # matches the stdlib hook: SystemExit in a thread is benign
    thread_sanitizer_state.record(args)


threading.excepthook = _sanitizing_excepthook


@pytest.fixture()
def thread_sanitizer():
    """Direct access to the death records — for tests that deliberately
    kill a background thread and assert the harness caught it."""
    return thread_sanitizer_state


#: Deaths recorded OUTSIDE any test's gate window — a leaked thread
#: dying between one test's gate teardown and the next test's setup.
#: Misattributing them to the next test would flake it, so they are
#: stashed here and reported at session end instead of dropped.
_orphaned_deaths = []


@pytest.fixture(autouse=True)
def _thread_sanitizer_gate(request):
    # Deaths from a previous test's leaked threads must not bleed into
    # this one: start from a clean slate (but keep them for the
    # session-end report — silence would defeat the whole tier).
    _orphaned_deaths.extend(thread_sanitizer_state.drain())
    yield
    if request.node.get_closest_marker("allow_thread_death"):
        thread_sanitizer_state.drain()
        return
    thread_sanitizer_state.fail_if_deaths(request.node.nodeid)


def pytest_sessionfinish(session, exitstatus):
    """Backstop for deaths no per-test gate covers: after the final
    test's gate, a pending death fails the whole session; between-gate
    orphans are reported loudly (not failed — blaming an arbitrary test
    would flake it, and the thread's true owner is unknowable here)."""
    late = thread_sanitizer_state.drain()
    if late:
        sys.stderr.write(
            f"\n[thread-sanitizer] {len(late)} background thread(s) died "
            f"with an uncaught exception after the final test's gate: "
            f"{[d.name for d in late]}\n"
            + "\n".join(d.traceback for d in late) + "\n")
        session.exitstatus = 1
    if _orphaned_deaths:
        sys.stderr.write(
            f"\n[thread-sanitizer] {len(_orphaned_deaths)} thread "
            f"death(s) occurred between test gate windows "
            f"(unattributable): {[d.name for d in _orphaned_deaths]}\n"
            + "\n".join(d.traceback for d in _orphaned_deaths) + "\n")


@pytest.fixture()
def cfg(tmp_path):
    from learningorchestra_tpu.config import Settings

    s = Settings()
    s.store_root = str(tmp_path / "store")
    s.image_root = str(tmp_path / "images")
    s.persist = False
    return s


@pytest.fixture()
def store(cfg):
    from learningorchestra_tpu.catalog.store import DatasetStore

    return DatasetStore(cfg)
