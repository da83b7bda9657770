"""Resource accounting — the capacity leg of the observability plane.

The tracing plane (PR 9) answers *where time went*; nothing answered
*what resources a job consumed* or *how much headroom the host has* —
yet cost-based packing (ROADMAP 5) needs per-job device-memory and
compile-time profiles as its cost inputs, and multi-host shard placement
(ROADMAP 3) needs disk/host capacity signals. This module is the one
sampling seam every surface reads from:

- **Device HBM**: per-device ``Device.memory_stats()`` where the backend
  provides it (TPU/GPU: ``bytes_in_use`` / ``peak_bytes_in_use``), with
  a live-buffer fallback (sum of ``jax.live_arrays()`` byte sizes) on
  backends that return nothing (the CPU test rig) — so ``source`` in the
  snapshot says which number you are reading.
- **Host**: RSS/VMS from ``/proc/self/statm``, open-fd and thread counts
  from ``/proc/self`` — the signals that catch fd leaks and host-RAM
  creep before the OOM killer does.
- **Disk**: filesystem totals via ``shutil.disk_usage(store_root)`` plus
  a per-dataset byte breakdown of the chunk store (TTL-cached — walking
  a terabyte store per scrape would be its own regression).
- **XLA compile time**: a ``jax.monitoring`` duration listener
  accumulates every real backend compile in this process
  (``backend_compile_duration`` fires only on actual compiles — a warm
  program fires nothing), so ``compile_s`` / ``compiles`` are exact
  without wrapping every jit call site. Cache *hits* are counted at the
  seams that know them: the AOT predict-program cache
  (models/aot.py) and device phases that complete without a single new
  compile (a warm fit program).

Job watermarks: :class:`job_phase` (wrapped around every managed job's
body by jobs.JobManager) and :class:`family_phase` / the ``device_span``
hook (models/builder.py, utils/profiling.py) sample compile-seconds,
RSS, and device bytes around compute phases and merge them into the
current job's profile — ``peak_hbm_bytes`` (max), ``compile_s`` (the
job window's compile total), ``host_rss_delta``, and per-family
``fit_resources`` on sweeps. SPMD workers sample the same way around
their dispatched device ops and ship the watermarks back over the job
channel with their spans (parallel/spmd.py), so the coordinator's job
profile covers the pod and ``GET /cluster`` can show every process's
last-known snapshot.

Counters are process-global (one server process = one metrics surface,
the OpTimer convention); concurrent jobs' compile windows overlap, so a
job's ``compile_s`` reads "compile seconds this process spent during the
job's window" — exact when jobs serialize (the SPMD dispatch
guard), an honest upper bound when they overlap.
"""

from __future__ import annotations

import os
import shutil
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, Optional

from learningorchestra_tpu.config import Settings, settings as global_settings
from learningorchestra_tpu.utils.structlog import get_logger

log = get_logger("resources")

_lock = threading.Lock()

# -- XLA compile accounting ---------------------------------------------------

#: Cumulative compile counters, fed by the jax.monitoring listener
#: (misses = real backend compiles) and by the cache seams that know
#: their hits (AotCache, warm device phases).
_compile = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
            "persistent_cache_hits": 0}
#: One registration attempt per process (claimed under _lock); _listener_ok
#: records whether it succeeded — a concurrent caller racing the attempt
#: reads False until the registering thread publishes the outcome.
_listener_installed = False
_listener_ok = False


def _on_duration(event: str, duration: float, **_kw: Any) -> None:
    if event.endswith("backend_compile_duration"):
        with _lock:
            _compile["compiles"] += 1
            _compile["compile_s"] += float(duration)


def _on_event(event: str, **_kw: Any) -> None:
    if "cache_hit" in event:
        with _lock:
            _compile["persistent_cache_hits"] += 1


def ensure_listener() -> bool:
    """Install the jax.monitoring compile listener once per process.
    Returns False (and accounts nothing) on jax builds without the
    monitoring API — every reader treats the counters as best-effort.

    Exactly ONE registration attempt per process, decided under the
    lock: jax.monitoring has no unregister, so two concurrent first
    callers must not both register (every compile would count twice
    forever), and a failed attempt must not be retried by a later
    caller (a partial registration would double the half that
    succeeded)."""
    global _listener_installed, _listener_ok
    with _lock:
        if _listener_installed:
            return _listener_ok
        _listener_installed = True     # claim the one attempt
    ok = True
    try:
        import jax.monitoring as monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
    except Exception as exc:  # noqa: BLE001 — degrade, don't break fits
        log.warning("compile accounting unavailable: %s", exc)
        ok = False
    with _lock:
        _listener_ok = ok
    return ok


def compile_seconds() -> float:
    ensure_listener()
    with _lock:
        return _compile["compile_s"]


def note_cache_hit(n: int = 1) -> None:
    """Count a compilation-cache hit observed at a seam that knows one:
    an AOT predict-program served from cache, or a device phase that
    completed without a single new backend compile (warm program)."""
    with _lock:
        _compile["cache_hits"] += int(n)


def compile_snapshot() -> Dict[str, Any]:
    """The ``compile`` section of ``/metrics``: real backend compiles
    (= cache misses), their cumulative seconds, and cache hits."""
    ensure_listener()
    with _lock:
        out = dict(_compile)
    out["compile_s"] = round(out["compile_s"], 6)
    out["cache_misses"] = out["compiles"]
    return out


# -- host (/proc/self) --------------------------------------------------------

def host_rss_bytes() -> int:
    try:
        with open("/proc/self/statm") as f:
            parts = f.read().split()
        return int(parts[1]) * (os.sysconf("SC_PAGE_SIZE")
                                if hasattr(os, "sysconf") else 4096)
    except (OSError, IndexError, ValueError):
        return 0


def host_snapshot() -> Dict[str, Any]:
    """RSS/VMS, open fds, thread count from ``/proc/self`` (zeros on
    platforms without procfs — keys stay present so dashboards never
    branch)."""
    rss = vms = 0
    try:
        page = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096
        with open("/proc/self/statm") as f:
            parts = f.read().split()
        vms, rss = int(parts[0]) * page, int(parts[1]) * page
    except (OSError, IndexError, ValueError):
        pass
    try:
        open_fds = len(os.listdir("/proc/self/fd"))
    except OSError:
        open_fds = 0
    return {"rss_bytes": rss, "vms_bytes": vms, "open_fds": open_fds,
            "threads": threading.active_count()}


# -- device HBM ---------------------------------------------------------------

def device_snapshot() -> Dict[str, Any]:
    """Per-local-device memory accounting. ``source`` says where the
    numbers came from: ``memory_stats`` (backend-reported, with true
    peaks — TPU/GPU) or ``live_buffers`` (sum of live jax array bytes —
    the CPU rig's fallback, attributed to the process, not per device)."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception as exc:  # noqa: BLE001 — pre-init callers
        return {"devices": [], "source": "unavailable", "error": str(exc),
                "total_bytes_in_use": 0, "peak_bytes_in_use": None}
    docs, total, peak_total, have_stats = [], 0, 0, False
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — backend without the API
            stats = None
        doc: Dict[str, Any] = {"id": str(d), "platform": d.platform}
        if stats:
            have_stats = True
            doc["bytes_in_use"] = int(stats.get("bytes_in_use", 0))
            doc["peak_bytes_in_use"] = int(
                stats.get("peak_bytes_in_use", doc["bytes_in_use"]))
            if "bytes_limit" in stats:
                doc["bytes_limit"] = int(stats["bytes_limit"])
            total += doc["bytes_in_use"]
            peak_total += doc["peak_bytes_in_use"]
        docs.append(doc)
    if not have_stats:
        # Live-buffer fallback: exact for what jax holds, attributed
        # per device via each array's committed placement so the
        # per-device rows (and lo_resource_device_bytes_in_use_by_device)
        # show every replica's params residency even on the CPU rig —
        # the old process-wide sum left every device but 0 reading as
        # empty once the serve plane replicated params across devices.
        per_dev: Dict[str, int] = {}
        total = 0
        try:
            for a in jax.live_arrays():
                nbytes = int(a.nbytes)
                total += nbytes
                try:
                    devs = list(a.devices())
                except Exception:  # noqa: BLE001 — deleted/donated array
                    continue
                if not devs:
                    continue
                share = nbytes // len(devs)
                for d in devs:
                    per_dev[str(d)] = per_dev.get(str(d), 0) + share
        except Exception:  # noqa: BLE001 — best-effort
            total = 0
        for doc in docs:
            if doc["id"] in per_dev:
                doc["bytes_in_use"] = per_dev[doc["id"]]
        return {"devices": docs, "source": "live_buffers",
                "total_bytes_in_use": total, "peak_bytes_in_use": None}
    return {"devices": docs, "source": "memory_stats",
            "total_bytes_in_use": total, "peak_bytes_in_use": peak_total}


def hbm_bytes_in_use() -> int:
    """One number for watermark sampling: CURRENT device bytes in use
    (summed across local devices; live-buffer bytes on backends without
    memory_stats). Deliberately not the backend's ``peak_bytes_in_use``
    — that peak is process-lifetime and never resets, so sampling it
    would stamp every job after the hungriest one with the hungriest
    one's footprint. Per-job peaks come from max-merging this current
    reading at each device phase end, when the phase's arrays are still
    live."""
    snap = device_snapshot()
    return int(snap.get("total_bytes_in_use") or 0)


# -- disk (chunk store) -------------------------------------------------------

#: Disk-walk TTL cache: (root) -> (expires_monotonic, doc). Walking the
#: store per scrape is O(store size); 5 s staleness is invisible to a
#: 15 s alert window.
_DISK_TTL_S = 5.0
_disk_cache: Dict[str, tuple] = {}


def disk_snapshot(cfg: Optional[Settings] = None,
                  ttl_s: float = _DISK_TTL_S) -> Dict[str, Any]:
    """Filesystem totals for the chunk-store root plus per-dataset byte
    usage (top-level directories under ``store_root``, including
    ``_models``). ``free_bytes`` is what the disk-headroom alert and
    ``/healthz`` judge against."""
    cfg = cfg or global_settings
    root = cfg.store_root
    now = time.monotonic()
    with _lock:
        hit = _disk_cache.get(root)
        if hit is not None and hit[0] > now:
            return dict(hit[1])
    doc: Dict[str, Any] = {"root": root}
    try:
        usage = shutil.disk_usage(root if os.path.isdir(root) else
                                  os.path.dirname(root) or "/")
        doc.update(total_bytes=usage.total, free_bytes=usage.free,
                   used_bytes=usage.used)
    except OSError as exc:
        doc.update(total_bytes=0, free_bytes=0, used_bytes=0,
                   error=str(exc))
    datasets: Dict[str, int] = {}
    store_bytes = 0
    if os.path.isdir(root):
        for entry in sorted(os.listdir(root)):
            path = os.path.join(root, entry)
            if not os.path.isdir(path):
                try:
                    store_bytes += os.path.getsize(path)
                except OSError:
                    pass
                continue
            size = 0
            for dirpath, _dirs, files in os.walk(path):
                for fname in files:
                    try:
                        size += os.path.getsize(
                            os.path.join(dirpath, fname))
                    except OSError:
                        pass
            datasets[entry] = size
            store_bytes += size
    doc["store_bytes"] = store_bytes
    doc["datasets"] = datasets
    with _lock:
        _disk_cache[root] = (now + max(0.0, ttl_s), dict(doc))
    return doc


# -- full snapshots -----------------------------------------------------------

def process_snapshot(cfg: Optional[Settings] = None,
                     lite: bool = False) -> Dict[str, Any]:
    """Everything ``GET /resources`` serves for this process. ``lite``
    drops the per-dataset disk walk — the form workers ship over the
    SPMD job channel and ``/cluster`` displays per process."""
    from learningorchestra_tpu import config

    doc: Dict[str, Any] = {
        "process": config.process_id() or 0,
        "host": host_snapshot(),
        "devices": device_snapshot(),
        "compile": compile_snapshot(),
    }
    if not lite:
        doc["disk"] = disk_snapshot(cfg)
    return doc


#: Last-known snapshots of OTHER pod processes, keyed by pod rank —
#: shipped over the SPMD job channel (hello handshake + per-job span
#: shipments) so ``GET /cluster`` compares the whole pod at a glance.
_remote: Dict[int, Dict[str, Any]] = {}


def note_remote(process: Any, doc: Any) -> None:
    """Record a worker process's shipped resource snapshot (coordinator
    side of the job channel). Malformed shipments are dropped — the
    channel peer is trusted code, but a half-dead worker must never
    corrupt the pod view."""
    if not isinstance(doc, dict):
        return
    try:
        idx = int(process)
    except (TypeError, ValueError):
        return
    with _lock:
        _remote[idx] = {"at": time.time(), **doc}


def remote_snapshots() -> Dict[int, Dict[str, Any]]:
    with _lock:
        return {k: dict(v) for k, v in _remote.items()}


# -- phase sampling (the seam jobs/builder/spmd/profiling hook into) ----------

#: Per-family watermark table accumulated across sweeps since the last
#: reset — what models/tune.py ``plan_waves`` sizes a population wave by
#: (a fit outside a managed job has no job profile to carry them).
_families: Dict[str, Dict[str, Any]] = {}


def family_watermarks() -> Dict[str, Dict[str, Any]]:
    with _lock:
        return {k: dict(v) for k, v in _families.items()}


def _merge_family(family: str, compile_s: float, peak_hbm: int) -> None:
    with _lock:
        ent = _families.setdefault(
            family, {"compile_s": 0.0, "peak_hbm_bytes": 0, "phases": 0})
        ent["compile_s"] = round(ent["compile_s"] + compile_s, 6)
        ent["peak_hbm_bytes"] = max(ent["peak_hbm_bytes"], int(peak_hbm))
        ent["phases"] += 1


def observe_device_phase(name: Optional[str],
                         compile_delta_s: Optional[float],
                         peak_hbm: int) -> None:
    """Merge one device phase's watermarks into the module table and the
    current job's profile. ``name`` follows the span taxonomy —
    ``fit.<family>.device`` attributes the phase to its family.
    ``compile_delta_s`` None means the phase's compile window OVERLAPPED
    another phase's (the process-global counter can't attribute the
    seconds to one family) — the peak still merges, compile attribution
    is skipped rather than double-counted."""
    from learningorchestra_tpu import jobs

    family = None
    if name:
        parts = name.split(".")
        if len(parts) >= 2 and parts[0] == "fit":
            family = parts[1]
    if compile_delta_s is not None and compile_delta_s <= 0.0:
        note_cache_hit()        # warm program: the phase compiled nothing
    if family is not None:
        _merge_family(family, compile_delta_s or 0.0, peak_hbm)
        stats = {"peak_hbm_bytes": int(peak_hbm)}
        if compile_delta_s is not None:
            stats["compile_s"] = round(compile_delta_s, 6)
        jobs.record_job_watermarks(family=family, family_stats=stats)
    jobs.record_job_watermarks(peak_hbm_bytes=peak_hbm)


#: Currently-open device-phase tokens and the subset that overlapped
#: another phase at any point of their window. Compile seconds are a
#: process-global counter, so only a phase that was the SOLE open window
#: for its whole duration can attribute its delta to one family — the
#: serialized instrumented sweep and dispatched pod rounds qualify; a
#: pipelined sweep's concurrent phases record peaks only.
_open_phases: set = set()
_overlapped_phases: set = set()


@contextmanager
def device_phase(name: Optional[str],
                 phase: Optional[Dict[str, Any]] = None):
    """The one device-phase sampling window, shared by ``family_phase``
    and ``profiling.device_span``: compile-seconds delta (None when the
    window overlapped another phase — attribution would double-count)
    and a current-device-bytes sample at exit, merged via
    :func:`observe_device_phase`. Exception-transparent — a failing
    phase still records what it consumed before dying.

    Yields ``phase`` (a new dict when None), which gains ``compiles``
    and ``compile_s`` at exit (both None for an overlapped window): the
    caller opens the phase's span around the window with that dict as
    its attributes, recorded by reference, so a trace of a warm-up sweep
    says which step compiled, and the exit's sample is the span's own
    time (on the CPU rig it walks every live array: milliseconds in a
    process that holds thousands) and not a gap between a family's
    phases."""
    ensure_listener()
    token = object()
    with _lock:
        if _open_phases:
            _overlapped_phases.update(_open_phases)
            _overlapped_phases.add(token)
        _open_phases.add(token)
        n0, c0 = _compile["compiles"], _compile["compile_s"]
    phase = {} if phase is None else phase
    try:
        yield phase
    finally:
        with _lock:
            count = _compile["compiles"] - n0
            delta = _compile["compile_s"] - c0
            _open_phases.discard(token)
            overlapped = token in _overlapped_phases
            _overlapped_phases.discard(token)
        phase["compiles"] = None if overlapped else count
        phase["compile_s"] = None if overlapped else round(delta, 6)
        try:
            observe_device_phase(name, None if overlapped else delta,
                                 hbm_bytes_in_use())
        except Exception:  # noqa: BLE001 — sampling must never fail a fit
            pass


def family_phase(family: str, phase: Optional[Dict[str, Any]] = None):
    """Wrap one classifier family's dispatch region (models/builder.py);
    see :func:`device_phase` for the attribution rules."""
    return device_phase(f"fit.{family}.device", phase)


@contextmanager
def job_phase():
    """Wrap a managed job's whole body (jobs.JobManager): at exit, the
    job's profile carries ``peak_hbm_bytes`` (max of the end sample and
    whatever device phases recorded mid-job), ``compile_s`` (the job
    window's process compile total), and ``host_rss_delta``."""
    from learningorchestra_tpu import jobs

    ensure_listener()
    c0 = compile_seconds()
    rss0 = host_rss_bytes()
    jobs.record_job_watermarks(peak_hbm_bytes=hbm_bytes_in_use())
    try:
        yield
    finally:
        jobs.record_job_watermarks(
            peak_hbm_bytes=hbm_bytes_in_use(),
            compile_s=compile_seconds() - c0,
            host_rss_delta=host_rss_bytes() - rss0)


# -- on-demand device profile (POST /debug/profile) ---------------------------

#: Hard cap on one capture — /debug/profile is an operator tool, not a
#: way to leave the profiler running forever.
PROFILE_MAX_SECONDS = 60.0


#: JAX allows one active profiler trace per process; concurrent
#: captures serialize here instead of crashing.
_profile_lock = threading.Lock()


def capture_profile(out_dir: str, seconds: float) -> str:
    """Capture a ``jax.profiler`` trace of this process for ``seconds``
    into ``out_dir`` (TensorBoard-loadable): the device's ops and, on
    the ``/host:CPU`` plane and the same clock, every span the program
    opened meanwhile (``tracing.span`` enters a ``TraceAnnotation``)."""
    import jax

    seconds = min(max(0.0, float(seconds)), PROFILE_MAX_SECONDS)
    os.makedirs(out_dir, exist_ok=True)
    with _profile_lock:
        jax.profiler.start_trace(out_dir)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
    log.info("device profile captured: %s (%.1fs)", out_dir, seconds)
    return out_dir


def reset() -> None:
    """Test isolation: clear remote snapshots, family watermarks, and
    the disk cache (compile counters are monotonic by design — tests
    read deltas)."""
    with _lock:
        _remote.clear()
        _families.clear()
        _disk_cache.clear()
