"""Model persistence — the capability upgrade SURVEY.md §5 calls for.

The reference *discards* every fitted model: only predictions and metrics
survive (reference model_builder.py:227-248); there is no way to re-use a
classifier on new data. Here every successful fit checkpoints its
parameter pytree with orbax (the TPU-native checkpoint layer: async-safe
array serialization, sharding-aware restore) plus a JSON manifest carrying
everything needed to serve it again: classifier kind, hparams (the static
args of its predictor), the fitted preprocessing state (vocabularies, fill
values, standardization stats), and the training metrics.

A parameter tree of ``FLAT_BYTES`` or more (a language model's
gigabytes, not a forest's kilobytes) is written as ONE file of raw leaf
bytes, ``params.bin``, beside an index ``params.json`` (each leaf's path,
dtype, shape and offset): every byte is written once, uncompressed, and
synced, where the checkpoint layer's chunked and compressed store took
seconds that varied run to run. Any reader with numpy can read it back.
Such a tree can be written ahead of its save: ``ModelRegistry.stage``
writes the leaves to a staging directory of the version's own on a
writer thread while the caller goes on (the builder: while the chip
runs the probability pass), and ``save(..., staged=)`` waits for it,
then writes the manifest and swaps the version in.

``ModelRegistry.load`` rebuilds a ``TrainedModel`` whose predictor comes
from ``registry.predictor_for`` — so a persisted model predicts on any
stored dataset through POST /trained-models/<name>/predictions with the
exact train-time preprocessing applied.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from learningorchestra_tpu.catalog.store import validate_name
from learningorchestra_tpu.config import Settings
from learningorchestra_tpu.models.base import TrainedModel
from learningorchestra_tpu.models.registry import predictor_for
from learningorchestra_tpu.utils import tracing


#: Parameter trees at least this large are written flat (module doc).
FLAT_BYTES = 64 << 20
#: A leaf this large is synced to the disk as soon as it is written.
_SYNC_BYTES = 32 << 20


class ModelNotFound(KeyError):
    pass


def _flat_leaves(tree: Any, prefix: str = "") -> Optional[List[Tuple[str, Any]]]:
    """``[(dotted path, leaf)]`` of a tree of nested str-keyed dicts, or
    None where the tree has any other container (then orbax writes it)."""
    out: List[Tuple[str, Any]] = []
    for key in sorted(tree):
        value = tree[key]
        if not isinstance(key, str) or "." in key:
            return None
        if isinstance(value, dict):
            sub = _flat_leaves(value, f"{prefix}{key}.")
            if sub is None:
                return None
            out += sub
        elif isinstance(value, (list, tuple)):
            return None
        else:
            out.append((prefix + key, value))
    return out


def _flat_of(params: Any) -> Optional[List[Tuple[str, Any]]]:
    """The leaves of a tree the save writes flat (module doc): a dict
    tree of ``FLAT_BYTES`` or more; None for any other."""
    import jax

    if not isinstance(params, dict):
        return None
    total = sum(int(getattr(leaf, "nbytes", 0))
                for leaf in jax.tree.leaves(params))
    return _flat_leaves(params) if total >= FLAT_BYTES else None


def _start_copies(leaves: List[Tuple[str, Any]]) -> None:
    """Every leaf's device-to-host copy is started before the first is
    waited for: gigabytes overlap instead of queueing behind one
    np.asarray after another. A small tree keeps its leaf-by-leaf
    copies in ``save``: kilobytes, for which the chip showed no
    difference either way."""
    import jax

    for _, leaf in leaves:
        if isinstance(leaf, jax.Array):
            leaf.copy_to_host_async()


def _write_flat(d: str, leaves: List[Tuple[str, Any]], phase: str) -> None:
    """Each leaf's wait, write and sync is a span under ``phase``:
    ``.fetch`` the part of its device-to-host copy not yet done,
    ``.write`` its copy into the page cache, ``.sync`` each
    ``fdatasync`` and the last ``fsync``."""
    fetch, write, sync = (f"{phase}.{p}" for p in ("fetch", "write", "sync"))
    index, offset = [], 0
    with open(os.path.join(d, "params.bin"), "wb") as f:
        for path, leaf in leaves:
            with tracing.span(fetch):
                arr = np.ascontiguousarray(np.asarray(leaf))
            with tracing.span(write):
                f.write(arr.reshape(-1).view(np.uint8).data)
            index.append({"path": path, "dtype": arr.dtype.name,
                          "shape": list(arr.shape), "offset": offset})
            offset += arr.nbytes
            if arr.nbytes >= _SYNC_BYTES:
                # The disk takes this leaf while the next ones are
                # still on their way from the device: the save costs
                # the slower of the two, not their sum at the end.
                with tracing.span(sync):
                    f.flush()
                    os.fdatasync(f.fileno())
        with tracing.span(sync):
            f.flush()
            os.fsync(f.fileno())
    with open(os.path.join(d, "params.json"), "w") as f:
        json.dump({"leaves": index}, f)


def _read_flat(d: str) -> Dict[str, Any]:
    with open(os.path.join(d, "params.json")) as f:
        index = json.load(f)["leaves"]
    tree: Dict[str, Any] = {}
    with open(os.path.join(d, "params.bin"), "rb") as f:
        for leaf in index:
            f.seek(leaf["offset"])
            count = int(np.prod(leaf["shape"], dtype=np.int64))
            arr = np.fromfile(f, dtype=leaf["dtype"], count=count)
            node = tree
            *parents, last = leaf["path"].split(".")
            for key in parents:
                node = node.setdefault(key, {})
            node[last] = arr.reshape(leaf["shape"])
    return tree


class StagedSave:
    """One version of a model's leaves on its way to disk
    (``ModelRegistry.stage``): every device-to-host copy started, then
    ``_write_flat`` on a writer thread into ``.tmp.<name>.<unique>``,
    a staging directory no other save shares, so the writer takes no
    lock. Under the caller's trace its work is one
    ``<phase>.stage`` span holding the ``.fetch`` / ``.write`` /
    ``.sync`` spans. ``ModelRegistry.save(..., staged=)`` commits it;
    ``discard`` drops it."""

    def __init__(self, registry: "ModelRegistry", name: str,
                 leaves: List[Tuple[str, Any]], phase: str):
        self.dir: Optional[str] = None
        self._error: Optional[Exception] = None
        self._t0 = time.monotonic()
        self._t1: Optional[float] = None
        _start_copies(leaves)
        ctx = tracing.current()

        def write() -> None:
            try:
                with tracing.attach(ctx), tracing.span(f"{phase}.stage"):
                    self.dir = registry._staging_dir(name)
                    _write_flat(self.dir, leaves, phase)
            except Exception as exc:  # noqa: BLE001 — join() re-raises it
                self._error = exc
            finally:
                self._t1 = time.monotonic()

        # thread-lifecycle: owner=StagedSave; exits when the leaves are
        # written or the write failed (caught; join() re-raises it);
        # joined by ModelRegistry.save(staged=) or discard().
        self._writer = threading.Thread(target=write, daemon=True,
                                        name=f"lo-model-stage-{name}")
        self._writer.start()

    def ahead_s(self) -> float:
        """Seconds the staging has run so far, all of it once done."""
        end = self._t1 if self._t1 is not None else time.monotonic()
        return end - self._t0

    def join(self) -> None:
        """Wait for the writer; raise what it raised."""
        self._writer.join()
        if self._error is not None:
            raise self._error

    def discard(self) -> None:
        """Wait for the writer and remove what it wrote. Nothing is left
        to remove once ``save`` has swapped the version in."""
        self._writer.join()
        if self.dir is not None and os.path.isdir(self.dir):
            shutil.rmtree(self.dir)


class ModelRegistry:
    """Disk-backed registry of fitted models under ``store_root/_models``."""

    def __init__(self, cfg: Settings):
        self.cfg = cfg
        # abspath: orbax refuses relative checkpoint paths, and store_root
        # may arrive relative via LO_TPU_STORE_ROOT.
        self.root = os.path.abspath(os.path.join(cfg.store_root, "_models"))
        # One lock per model name: the swap and the torn-read argument
        # below are about one name's directory, so save/load/delete of
        # the same name exclude each other and different names never
        # wait (a sweep's families save side by side; the online tier
        # reads one model while another is re-saved).
        self._name_locks: Dict[str, threading.Lock] = {}
        self._locks_guard = threading.Lock()
        self._recover_interrupted_saves()

    def _recover_interrupted_saves(self) -> None:
        """A crash between save()'s two swap renames leaves the live dir
        missing with the previous version parked at ``.old.<name>`` —
        promote it back, so a durably-saved model never 404s after a
        restart (the crash-recovery discipline the chunk store already
        follows). Leftover ``.tmp.<name>`` staging (crash mid-write, or
        mid-swap once its ``.old.`` source is promoted) is garbage."""
        if not os.path.isdir(self.root):
            return
        for entry in os.listdir(self.root):
            if not entry.startswith(".old."):
                continue
            live = os.path.join(self.root, entry[len(".old."):])
            parked = os.path.join(self.root, entry)
            if os.path.isdir(live):
                shutil.rmtree(parked)       # swap completed; stray aside
            else:
                os.rename(parked, live)
        for entry in os.listdir(self.root):
            if entry.startswith(".tmp."):
                shutil.rmtree(os.path.join(self.root, entry))

    def _dir(self, name: str) -> str:
        validate_name(name)
        return os.path.join(self.root, name)

    def _staging_dir(self, name: str) -> str:
        """A new empty ``.tmp.<name>.<unique>`` beside the live
        directory: no two saves of a name share one. The leading dot
        keeps it out of ``list()``, and a restart removes it."""
        self._dir(name)
        d = os.path.join(self.root, f".tmp.{name}.{tracing.new_id()}")
        os.makedirs(d)
        return d

    def _lock_of(self, name: str) -> threading.Lock:
        """Bound to a local called ``name_lock`` at every use: lolint's
        lock-blocking rule knows a held lock by its name."""
        with self._locks_guard:
            return self._name_locks.setdefault(name, threading.Lock())

    # -- write ---------------------------------------------------------------

    def stage(self, name: str, model: TrainedModel,
              phase: str = "model.save") -> Optional[StagedSave]:
        """Start writing ``model``'s leaves for a later
        ``save(name, model, ..., staged=)``, which then only waits for
        them, writes the manifest and swaps the version in. Only a tree
        that is written flat is staged; for any other this returns None
        and the caller saves as usual."""
        leaves = _flat_of(model.params)
        if leaves is None:
            return None
        return StagedSave(self, name, leaves, phase)

    def save(self, name: str, model: TrainedModel,
             metrics: Optional[Dict[str, float]] = None,
             preprocess: Optional[Dict[str, Any]] = None,
             phase: str = "model.save",
             staged: Optional[StagedSave] = None) -> None:
        """Persist ``model`` under ``name``. ``phase`` is the caller's
        span around the save: the waits for the device, the writes and
        the syncs are its ``.fetch`` / ``.write`` / ``.sync`` children
        (``_write_flat``; on the checkpoint layer's path one ``.fetch``
        and one ``.write``). With ``staged`` (``stage``'s handle for
        this name) the leaves are its, and a failed staging is raised
        here: the previous version stays live."""
        import orbax.checkpoint as ocp

        d = self._dir(name)
        flat = params = None
        if staged is None:
            flat = _flat_of(model.params)
            if flat is None:
                # Replicated params → host numpy before checkpointing:
                # keeps the save a process-local write under
                # multi-process operation (orbax would otherwise
                # coordinate a distributed save that only process 0
                # participates in).
                import jax

                with tracing.span(f"{phase}.fetch"):
                    params = jax.tree.map(np.asarray, model.params)
            else:
                _start_copies(flat)
        # The whole new version is staged in a sibling temp dir, then
        # swapped in by rename: a re-save (hot-swap) must never leave a
        # window where the model is missing — the online tier's
        # version()/load() run concurrently with live /predict traffic,
        # and a transient ModelNotFound maps to a terminal 404 at the
        # client.
        tmp: Optional[str] = None
        old = os.path.join(self.root, f".old.{name}")
        try:
            if staged is not None:
                staged.join()
                tmp = staged.dir
            name_lock = self._lock_of(name)
            with name_lock:
                if tmp is None:
                    tmp = self._staging_dir(name)
                    if flat is not None:
                        _write_flat(tmp, flat, phase)
                    else:
                        with tracing.span(f"{phase}.write"):
                            ocp.PyTreeCheckpointer().save(
                                os.path.join(tmp, "params"), params)
                if os.path.isdir(old):
                    shutil.rmtree(old)
                manifest = {
                    "name": name,
                    "kind": model.kind,
                    "num_classes": model.num_classes,
                    "hparams": model.hparams,
                    "metrics": metrics or {},
                    "preprocess": preprocess,
                    "time_created": time.strftime("%Y-%m-%d %H:%M:%S"),
                }
                with open(os.path.join(tmp, "manifest.json"), "w") as f:
                    json.dump(manifest, f, indent=1)
                # The swap itself: readers take the same name's lock, so
                # the brief old→aside / tmp→live two-step is invisible
                # to them.
                man_path = os.path.join(d, "manifest.json")
                prev = None
                if os.path.isdir(d):
                    try:
                        pst = os.stat(man_path)
                        prev = (pst.st_mtime_ns, pst.st_size)
                    except OSError:
                        pass
                    os.rename(d, old)
                os.rename(tmp, d)
                if os.path.isdir(old):
                    shutil.rmtree(old)
                # version() tokens on (mtime_ns, size); on filesystems
                # with coarse timestamps a fast re-save can land the
                # same token and the online tier would silently keep
                # serving the OLD params. Enforce strictly-INCREASING
                # mtime across saves (not mere inequality with the
                # previous token — that allows an ABA collision where
                # save3 lands save1's token while the cache still holds
                # save1's params).
                try:
                    st = os.stat(man_path)
                    if prev is not None and st.st_mtime_ns <= prev[0]:
                        os.utime(man_path,
                                 ns=(st.st_atime_ns, prev[0] + 1))
                except OSError:
                    pass
        finally:
            # A failed save leaves no staging behind; a saved one has
            # none left.
            if staged is not None:
                staged.discard()
            elif tmp is not None and os.path.isdir(tmp):
                shutil.rmtree(tmp)

    # -- read ----------------------------------------------------------------

    def version(self, name: str) -> Tuple[int, int]:
        """Cheap staleness token for the persisted model: the manifest
        file's (mtime_ns, size). ``save`` rewrites the manifest, so any
        re-fit under the same name changes the token — what the online
        tier's AOT program cache keys on (models/aot.py) to hot-swap a
        re-saved model without a restart. Raises ModelNotFound when the
        model is gone."""
        path = os.path.join(self._dir(name), "manifest.json")
        # Lock-free stat on the hot path (one call per /predict): taking
        # the model's lock here would head-of-line-block its online
        # requests behind an in-flight re-save's orbax write. The stat can
        # only miss an existing model while a save holds the lock
        # mid-swap — so on miss, wait the swap out and re-check before
        # concluding ModelNotFound.
        try:
            st = os.stat(path)
        except OSError:
            name_lock = self._lock_of(name)
            with name_lock:
                try:
                    st = os.stat(path)
                except OSError:
                    raise ModelNotFound(name) from None
        return (st.st_mtime_ns, st.st_size)

    def manifest(self, name: str) -> Dict[str, Any]:
        # Same lock-free-read / locked-recheck shape as version():
        # manifests are only ever swapped in whole by rename, so a
        # plain open() sees the old or the new file, never a torn one —
        # only the mid-swap missing-file window needs to wait out the
        # save (taking the lock unconditionally would stall listing and
        # batch predicts behind a seconds-long orbax write).
        try:
            return self._read_manifest(name)
        except ModelNotFound:
            name_lock = self._lock_of(name)
            with name_lock:
                return self._read_manifest(name)

    def _read_manifest(self, name: str) -> Dict[str, Any]:
        path = os.path.join(self._dir(name), "manifest.json")
        if not os.path.exists(path):
            raise ModelNotFound(name)
        with open(path) as f:
            return json.load(f)

    def load(self, name: str) -> Tuple[Dict[str, Any], TrainedModel]:
        import jax
        import numpy as np
        import orbax.checkpoint as ocp

        # Whole restore under the lock: a save() swapping the dir while
        # orbax walks the checkpoint files would hand back a torn mix of
        # versions (or crash on vanished files). Loads happen per model
        # (re)load, not per request, so the exclusion is cheap.
        d = self._dir(name)
        name_lock = self._lock_of(name)
        with name_lock:
            man = self._read_manifest(name)
            if os.path.exists(os.path.join(d, "params.json")):
                params = _read_flat(d)
            else:
                params = ocp.PyTreeCheckpointer().restore(
                    os.path.join(d, "params"))
        # Restore to host arrays: orbax would otherwise pin each leaf to
        # the sharding it was saved with, which may mix device placements
        # (and may not exist on the restoring topology at all). Predict
        # jits re-place them wherever the serving mesh lives.
        params = jax.tree.map(np.asarray, params)
        model = TrainedModel(
            kind=man["kind"], params=params,
            predict_proba_fn=predictor_for(man["kind"], man["hparams"]),
            num_classes=man["num_classes"], hparams=man["hparams"])
        return man, model

    def list(self) -> List[Dict[str, Any]]:
        if not os.path.isdir(self.root):
            return []
        out = []
        for name in sorted(os.listdir(self.root)):
            try:
                out.append(self.manifest(name))
            except (ModelNotFound, json.JSONDecodeError, ValueError):
                # Stray entries (temp files, invalid names) are not models.
                continue
        return out

    def exists(self, name: str) -> bool:
        return os.path.exists(os.path.join(self._dir(name), "manifest.json"))

    def delete(self, name: str) -> None:
        d = self._dir(name)
        name_lock = self._lock_of(name)
        with name_lock:
            if not os.path.isdir(d):
                raise ModelNotFound(name)
            shutil.rmtree(d)
