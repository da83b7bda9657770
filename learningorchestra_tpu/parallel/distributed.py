"""Multi-host bootstrap — the communication backend over ICI/DCN.

The reference's distributed backend is Spark standalone RPC: driver-in-
service ↔ master:7077 ↔ workers:41352 over Docker overlay networks, with
py4j bridging Python↔JVM and all bulk data routed through MongoDB
(SURVEY.md §2 "Distributed communication backend"). Here the backend is
``jax.distributed`` + XLA collectives: one controller process per TPU host
joins a coordination service, after which ``jax.devices()`` is the *global*
device list and every collective (psum/all_gather/reduce_scatter/ppermute
emitted by pjit/shard_map) rides ICI within a slice and DCN across slices —
no first-party RPC layer to maintain.

Single-host (and CPU-simulated) runs skip initialization entirely; the same
mesh code paths work unchanged, which is what lets tests run on an 8-device
CPU mesh (tests/conftest.py) and the driver dry-run multi-chip shardings
without TPU hardware.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from learningorchestra_tpu import config

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join (or start) the multi-host coordination service.

    Arguments default from the standard env vars so a TPU pod launcher can
    start identical processes on every host:

    - ``LO_TPU_COORDINATOR`` (host:port of process 0),
    - ``LO_TPU_NUM_PROCESSES``, ``LO_TPU_PROCESS_ID``.

    The coordinator address is required to form a pod: besides seeding
    ``jax.distributed``, its host also locates the SPMD job channel
    (parallel/spmd.py — coordinator host, port + 1). No-op when unset
    (single-host dev/test).
    """
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or config.coordinator_address()
    if num_processes is None:
        num_processes = config.num_processes()
    if process_id is None:
        process_id = config.process_id()
    if coordinator_address is None and num_processes is None:
        return  # single-host
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)
    _initialized = True


def process_info() -> dict:
    """Topology snapshot for the /cluster observability route."""
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_device_count": jax.local_device_count(),
        "global_device_count": jax.device_count(),
        "devices": [str(d) for d in jax.devices()],
        "platform": jax.default_backend(),
    }


def device_info() -> dict:
    """The device every benchmark and smoke result names, as JAX reports
    it: ``platform``, ``kind`` and ``count`` of ``jax.devices()``."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


#: The checkout this package runs from — the fixed place its compile
#: cache lives (the path is part of the cache key, so it never moves).
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at a place that survives
    the process, before the first compile: where
    ``JAX_COMPILATION_CACHE_DIR`` says if it is set (JAX reads it
    itself — nothing is set in code), else ``<checkout>/.jax_cache``.
    Returns the directory set in code, None when the environment
    decides. Called by every long-lived entry point (the server,
    perfbench/server.py, chip_smoke.py), so a restart re-reads its fit
    programs instead of recompiling them."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
