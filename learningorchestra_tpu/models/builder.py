"""ModelBuilder — the trainer service core (reference call stack §3.2).

The reference's ``SparkModelBuilder.build_model``: load train/test
collections, ``exec()`` user preprocessing, fit up to 5 classifiers
*concurrently* (ThreadPoolExecutor submitting into one FAIR-scheduled
SparkSession, model_builder.py:95,160-176), time each fit, evaluate F1 +
accuracy, and write one prediction collection per classifier whose metadata
carries the metrics and whose rows are the test set plus ``prediction`` and
``probability`` columns (with vector internals dropped,
model_builder.py:179-248).

TPU-native design: preprocessing is declarative (ops/preprocess; exec only
behind the opt-in flag); each classifier family is one jit-compiled program
(models/*), so "concurrent fits" become overlapped dispatch of XLA
executables. The sweep is PIPELINED on both execution paths:

- Single-process: every family runs on its own thread, but only
  ``max_concurrent_fits`` of them may sit in their *device phase* at a
  time (a semaphore, not the pool size, is the concurrency knob) — so
  host-side prep of one family (tree quantile edges, streamed chunk
  reads) and host-side finishing of another (metrics, prediction
  datasets, persistence) overlap device compute of a third, while the
  device working set stays bounded (five concurrently dispatched
  11M-row fits thrash HBM — measured 363 s vs 106 s sequential). On a
  multi-device mesh the device phase serializes outright: concurrent
  collective programs from different threads can interleave on the
  per-device streams and wedge (see ``_build_pipelined``).
- Multi-process pod: one dispatched round covers the whole build; the
  fit programs of every family are enqueued back-to-back with no host
  barrier between them (JAX dispatch is async), the probability passes
  follow in the same deterministic order, and all host-side finishing
  happens after the collective program completes — every process runs
  the identical device-op sequence (parallel/spmd.prep_build_job).

Each fit records ``device_s`` — dispatch through blocked completion of
its device programs — next to wall-clock, the split that separates
host jitter from device compute. Under a sampled trace the build is one
span tree, each span opened around its work (so it also lies on a
running device profile's timeline, utils/tracing.py): ``build`` >
``design.build``, ``fit.<c>`` > ``host_prep`` / ``gate_wait`` /
``dispatch`` / ``device`` / ``finish`` > ``score`` / ``model`` /
``rows`` / ``store`` (a flat-written tree's leaves are staged under
``fit.<c>.finish.model.stage`` while its ``device`` phase runs), and
``build.tail``: the finishing left when the last family's device phase
has ended, which the chip has to wait for (docs/observability.md has
the table). Output
contract is preserved: dataset ``<name>_<classifier>`` per classifier,
metrics in its metadata.
"""

from __future__ import annotations

import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from learningorchestra_tpu.catalog.store import DatasetStore
from learningorchestra_tpu.config import Settings, settings as global_settings
from learningorchestra_tpu.models.base import FitReport, Timer
from learningorchestra_tpu.models.metrics import classification_metrics
from learningorchestra_tpu.models.persistence import ModelRegistry
from learningorchestra_tpu.models.registry import get_trainer
from learningorchestra_tpu.ops import preprocess
from learningorchestra_tpu.parallel import spmd
from learningorchestra_tpu.parallel.mesh import MeshRuntime
from learningorchestra_tpu.utils import fitckpt, resources, tracing
from learningorchestra_tpu.utils.profiling import (
    device_span, op_timer, timed)


class ModelBuilder:
    def __init__(self, store: DatasetStore, runtime: MeshRuntime,
                 cfg: Optional[Settings] = None):
        self.store = store
        self.runtime = runtime
        self.cfg = cfg or global_settings
        self.registry = ModelRegistry(self.cfg)

    # -- validation (reference model_builder.py:272-292) ---------------------

    def validate(self, train: str, test: str, classifiers: Sequence[str],
                 prediction_name: str) -> None:
        for ds_name in (train, test):
            if not self.store.exists(ds_name):
                raise KeyError(f"dataset not found: {ds_name}")
        for c in classifiers:
            get_trainer(c)  # raises ValueError on unknown name
        for c in classifiers:
            if self.store.exists(f"{prediction_name}_{c}"):
                raise ValueError(
                    f"prediction dataset already exists: {prediction_name}_{c}")

    def validate_tune(self, train: str, out_name: str, classifier: str,
                      configs: Sequence[Dict[str, Any]]) -> None:
        """Synchronous admission checks for a tune sweep — everything that
        must 4xx at the route instead of stranding an async job: missing
        dataset (404), duplicate output (ValueError → 406), and the full
        per-config hyperparameter validation (unknown names / out-of-range
        values name the offending key, models/registry.HPARAM_SPECS)."""
        from learningorchestra_tpu.models import tune as tune_mod

        if not self.store.exists(train):
            raise KeyError(f"dataset not found: {train}")
        if self.store.exists(out_name):
            raise ValueError(f"tune dataset already exists: {out_name}")
        tune_mod.validate_population(classifier, configs)

    # -- the main path -------------------------------------------------------

    def build(self, train: str, test: str, prediction_name: str,
              classifiers: Sequence[str], label: str,
              steps: Sequence[Dict[str, Any]] = (),
              preprocessor_code: Optional[str] = None,
              hparams: Optional[Dict[str, Dict[str, Any]]] = None,
              existing: bool = False) -> List[FitReport]:
        """Fit all requested classifiers; returns per-classifier reports.

        Synchronous core (the reference's POST /models also blocks until all
        fits finish, SURVEY.md §3.2); the serving layer may wrap it in a job.
        ``existing=True`` means the caller already created the prediction
        datasets (the async route does, metadata-first, so pollers can see
        them — and their failure flags — from the moment of submission).

        One ``build`` span covers the whole of it, on the sync route and
        in a job alike: what a request spends outside it is the REST
        layer's own (``rest_overhead_s.sweep``).
        """
        span_attrs = {"train": train, "test": test,
                      "classifiers": list(classifiers)}
        with tracing.span("build", span_attrs):
            return self._build(span_attrs, train, test, prediction_name,
                               classifiers, label, steps, preprocessor_code,
                               hparams, existing)

    def _build(self, span_attrs: Dict[str, Any], train: str, test: str,
               prediction_name: str, classifiers: Sequence[str], label: str,
               steps: Sequence[Dict[str, Any]],
               preprocessor_code: Optional[str],
               hparams: Optional[Dict[str, Dict[str, Any]]],
               existing: bool) -> List[FitReport]:
        train_ds = self.store.get(train)
        test_ds = self.store.get(test)
        hparams = hparams or {}
        multi = spmd.is_multiprocess()
        ck_on = int(self.cfg.fit_ckpt_rounds) > 0
        # Read-pipeline traffic of this whole build (streamed-fit scans,
        # ChunkedDesign shard reads, double-buffered device feeding) —
        # recorded on the job profile so a cache/prefetch regression
        # shows up per-job before it shows up as wall-clock.
        from learningorchestra_tpu.catalog import readpipe

        rp0 = readpipe.snapshot()

        pp_meta = None
        streamed = False
        design_t0 = time.monotonic()
        if preprocessor_code is not None:
            if multi:
                raise PermissionError(
                    "exec preprocessing cannot run SPMD (workers rebuild "
                    "inputs deterministically); use declarative steps")
            if not self.cfg.allow_exec_preprocessing:
                raise PermissionError(
                    "exec preprocessing is disabled; enable "
                    "LO_TPU_ALLOW_EXEC or use declarative steps")
            X_train, y_train, X_test, y_test = preprocess.exec_preprocess(
                preprocessor_code, train_ds, test_ds, label, cfg=self.cfg)
            feature_fields = [f"f{i}" for i in range(X_train.shape[1])]
        elif (self.cfg.stream_design or train_ds.over_budget
                or test_ds.over_budget):
            # Shard-local streamed path: the design matrix never exists
            # fully on any host — state is fitted with streaming passes
            # and each device shard materializes only its own row range
            # (preprocess.ChunkedDesign → mesh.shard_chunked). This is
            # how fits scale past one host's RAM, the reference's
            # executor residency model (model_builder.py:200). No memo:
            # memoization consolidates, which is exactly what this path
            # must never do.
            streamed = True
            fit_prof: Dict[str, Any] = {}
            # Pass-boundary checkpoints for the streamed state fit: a
            # retried build resumes the fitting scans instead of
            # re-reading the dataset from pass zero. Safe under SPMD
            # too — only process 0 ever FITS state (workers receive it
            # pinned in the dispatched spec).
            design_ckpt = fitckpt.context(
                self.cfg, dataset=train, family="design",
                config={"label": label, "steps": list(steps)},
                snapshot="") if ck_on else None
            X_train, y_train, feature_fields, state = \
                preprocess.design_matrix_streamed(train_ds, label, steps,
                                                  profile=fit_prof,
                                                  ckpt=design_ckpt)
            X_test, y_test, _, _ = preprocess.design_matrix_streamed(
                test_ds, label, steps, state=state,
                feature_fields=feature_fields)
            if fit_prof:
                # Surface the streamed-fit scan count on the job record:
                # the fused fitting passes (ops/preprocess) exist to keep
                # this at ~2 for the default pipeline, and a regression
                # shows up here before it shows up as Criteo-scale IO.
                from learningorchestra_tpu.jobs import record_job_profile

                record_job_profile(**fit_prof)
            pp_meta = {"steps": list(steps), "state": state,
                       "feature_fields": feature_fields, "label": label}
        else:
            # Memoized per dataset-snapshot: repeat builds on the same data
            # reuse the identical X arrays, so the runtime's transfer cache
            # keeps the on-device copies (re-transferring an 11M-row matrix
            # over PCIe per build would dwarf the fits themselves).
            steps_key = json.dumps(list(steps), sort_keys=True, default=str)
            X_train, y_train, feature_fields, state = train_ds.memo(
                ("design", label, steps_key),
                lambda: preprocess.design_matrix(train_ds, label, steps))
            X_test, y_test, _, _ = test_ds.memo(
                ("design_t", label, steps_key, tuple(feature_fields)),
                lambda: preprocess.design_matrix(
                    test_ds, label, steps, state=state,
                    feature_fields=feature_fields),
                token=state)
            # Everything needed to apply the identical pipeline to future
            # datasets when the fitted model is re-served (persistence.py).
            pp_meta = {"steps": list(steps), "state": state,
                       "feature_fields": feature_fields, "label": label}
        # One span covers whichever design-matrix path ran (exec /
        # streamed / memoized-resident): explicit duration, no reindent
        # of the three-way branch above.
        tracing.record_span(
            "design.build", time.monotonic() - design_t0,
            attrs={"train": train, "test": test, "streamed": streamed,
                   "rows": int(len(X_train))})
        span_attrs["rows"] = int(len(X_train))
        if y_train is None:
            raise ValueError(f"label field {label!r} not in {train!r}")
        num_classes = int(max(int(y_train.max()) + 1,
                              2 if y_test is None else int(y_test.max()) + 1))

        # Create all output datasets first (metadata-first protocol), so
        # pollers see them immediately with finished=false.
        if not existing:
            for c in classifiers:
                self.store.create(f"{prediction_name}_{c}", parent=test,
                                  extra={"classifier": c, "label": label})

        # Mid-fit checkpoint contexts (utils/fitckpt.py), one per family
        # with natural segment boundaries. Keyed on everything that
        # could change the fit's arithmetic — hparams, label/steps,
        # row snapshot, mesh shape (psum summation grouping) — so a
        # resume under ANY changed configuration starts fresh. The
        # single-process paths only: the dispatched SPMD round must run
        # one identical program on every process, and a mid-fit resume
        # decision made from local disk state could diverge between
        # processes (job-level retry + the design-state checkpoint
        # above still cover the pod path).
        ckpt_ctxs: Dict[str, Any] = {}
        if ck_on and not multi:
            for c in classifiers:
                if c not in fitckpt.SEGMENTED_FAMILIES:
                    continue
                ckpt_ctxs[c] = fitckpt.context(
                    self.cfg, dataset=train, family=c,
                    config={"family": c, "hparams": hparams.get(c, {}),
                            "num_classes": num_classes, "label": label,
                            "steps": list(steps), "streamed": streamed,
                            "mesh": dict(self.runtime.mesh.shape)},
                    snapshot=f"rows={int(len(X_train))}")

        def prep_fit(c: str):
            """One family's host-side prep (the trainer's ``host_prep``
            hook — e.g. tree quantile edges from host/chunk-store reads).
            Pure host work, runs OUTSIDE the device gate so it overlaps
            other families' device compute. Returns (extra_kwargs,
            prep_s)."""
            trainer = get_trainer(c)
            hp = hparams.get(c, {})
            with tracing.span(f"fit.{c}.host_prep"), Timer() as tp:
                prep = getattr(trainer, "host_prep", None)
                extra = prep(X_train, **hp) if prep is not None else {}
            return extra, tp.elapsed

        def dispatch_fit(c: str, extra: Dict[str, Any]):
            """The family's fit-program dispatch. JAX dispatch is
            asynchronous, so this returns as soon as the fit's device
            programs are enqueued — the device may still be computing.
            (The checkpointed families' segmented drivers block per
            segment — pulling params to host at each boundary IS the
            checkpoint.)"""
            kw = dict(hparams.get(c, {}), **extra)
            if c in ckpt_ctxs:
                kw["ckpt"] = ckpt_ctxs[c]
            return get_trainer(c)(self.runtime, X_train, y_train,
                                  num_classes, **kw)

        def collect_fit(c: str, model, pre_s: float):
            """The family's probability pass, blocked to completion (the
            host gather inside ``predict_proba`` consumes the fitted
            params, so its completion bounds the fit's device programs
            too). ``pre_s`` is everything before this span — host prep
            plus the trainer's dispatch wall time (which includes e.g.
            the design matrix's host→device transfer, a real per-family
            cost a serialized sweep would pay). Returns (probs,
            device_s)."""
            probs, device_s = device_span(
                lambda: model.predict_proba(self.runtime, X_test),
                name=f"fit.{c}.device")
            op_timer.record(f"fit.{c}", pre_s + device_s)
            op_timer.record(f"fit.{c}.device", device_s)
            # Progress mark for the job watchdog: a family's device
            # programs ran to completion — the build is alive.
            from learningorchestra_tpu import jobs

            jobs.heartbeat()
            return probs, device_s

        def stage_model(c: str, model):
            """Start writing the fitted weights to disk while the chip
            runs the probability pass (``ModelRegistry.stage``: a tree
            written flat, None for any other); ``finish_host`` commits
            them."""
            if not self.cfg.persist_models:
                return None
            return self.registry.stage(f"{prediction_name}_{c}", model,
                                       phase=f"fit.{c}.finish.model")

        def finish_host(c: str, model, probs, fit_time: float,
                        device_s: float, staged=None) -> FitReport:
            """Metrics, model persistence, prediction dataset — everything
            host-side after the device programs complete. ``fit_time`` is
            the family's per-fit time: on the single-process pipeline,
            prep + dispatch + device spans (excluding scheduler waits,
            so the sum estimates the serialized sweep); on the pod
            batched round, the family's prep-to-probabilities wall span
            (spans overlap across families, so build wall-clock below
            their sum is the overlap evidence). ``staged``:
            ``stage_model``'s handle, whose leaves the save commits."""
            with tracing.span(f"fit.{c}.finish"):
                with tracing.span(f"fit.{c}.finish.score"):
                    preds = np.argmax(probs, axis=1)
                    report = FitReport(kind=c, fit_time=fit_time)
                    if y_test is not None and (y_test >= 0).all():
                        report.metrics = classification_metrics(
                            y_test, preds, num_classes)
                report.metrics["device_s"] = round(device_s, 6)
                report.metrics.update(model.fit_metrics)
                if self.cfg.persist_models:
                    # Best-effort: a persistence failure must not discard
                    # an otherwise successful fit's predictions; surface it
                    # in the persisted metrics instead. The span holds
                    # what the staging left: the wait for its writer, the
                    # manifest and the swap.
                    try:
                        phase = f"fit.{c}.finish.model"
                        ahead = 0.0 if staged is None else staged.ahead_s()
                        with tracing.span(
                                phase, save_staged=staged is not None,
                                save_ahead_s=round(ahead, 6)):
                            self.registry.save(
                                f"{prediction_name}_{c}", model,
                                metrics=report.metrics, preprocess=pp_meta,
                                phase=phase, staged=staged)
                    except Exception as exc:  # noqa: BLE001 — isolation
                        report.metrics["persist_error"] = (
                            f"{type(exc).__name__}: {exc}")
                self._save_predictions(f"{prediction_name}_{c}", test_ds,
                                       preds, probs, report,
                                       phase=f"fit.{c}.finish")
                # The family reached its terminal outputs: its mid-fit
                # checkpoint stream is superseded (a retry of THIS family
                # can no longer happen — the retry machinery refits only
                # families whose datasets failed), so reclaim the disk.
                if c in ckpt_ctxs:
                    ckpt_ctxs[c].clear()
                from learningorchestra_tpu import jobs

                jobs.heartbeat()
                return report

        def fail_report(c: str, exc: Exception) -> FitReport:
            self.store.fail(f"{prediction_name}_{c}",
                            f"{type(exc).__name__}: {exc}")
            return FitReport(kind=c, fit_time=0.0,
                             metrics={"error": str(exc)})

        stages = (prep_fit, dispatch_fit, collect_fit, finish_host,
                  fail_report)
        if multi:
            reports = self._build_dispatched(
                train, test, prediction_name, classifiers, label, steps,
                hparams, X_train, X_test, state, feature_fields, streamed,
                *stages)
        else:
            reports = self._build_pipelined(classifiers, *stages,
                                            stage_model)
        device_s = {r.kind: r.metrics["device_s"] for r in reports
                    if "device_s" in r.metrics}
        rp1 = readpipe.snapshot()
        rp_delta = {k: rp1[k] - rp0[k]
                    for k in ("cache_hits", "cache_misses",
                              "prefetch_stalls", "prefetched_chunks")}
        if device_s or any(rp_delta.values()):
            from learningorchestra_tpu.jobs import record_job_profile

            prof: Dict[str, Any] = {}
            if device_s:
                prof["fit_device_s"] = device_s
            if any(rp_delta.values()):
                prof["read_pipeline"] = rp_delta
            record_job_profile(**prof)
        if streamed and ck_on and all("error" not in r.metrics
                                      for r in reports):
            # Every family completed: the design-state checkpoint has no
            # retry left to serve — reclaim it (a failed family keeps it
            # so the retry skips the fitted passes).
            design_ckpt.clear()
        return reports

    def _build_pipelined(self, classifiers, prep_fit, dispatch_fit,
                         collect_fit, finish_host, fail_report,
                         stage_model) -> List[FitReport]:
        """Single-process pipelined sweep (reference: 5-way
        ThreadPoolExecutor + FAIR pool, model_builder.py:95,160-176).

        Every family gets a thread; a semaphore — not the pool size —
        caps how many sit in their device phase, so host prep and host
        finishing of other families overlap device compute while the
        device working set stays bounded.

        On a MULTI-DEVICE mesh the device phase serializes outright
        (gate of 1) regardless of ``max_concurrent_fits``: every fit and
        probability program carries collectives (psum/all-gather over
        the data axis), and two such programs dispatched from different
        threads can enqueue onto the per-device execution streams in
        different orders — the same cross-program interleaving deadlock
        ``dispatch_guard`` exists to prevent across processes, observed
        as a real rendezvous wedge on the simulated 8-device CPU mesh.
        Host-side prep and finishing still pipeline against device
        compute, which is where the overlap win lives; on a single
        device (the production single-chip path) programs carry no
        cross-device rendezvous and up to ``max_concurrent_fits`` may
        dispatch concurrently to keep the device queue fed.

        A fit's weights start on their way to disk (``stage_model``) as
        soon as its trainer returns, while the probability pass runs;
        a family that fails after that drops what was staged."""
        n_dev = int(np.prod(list(self.runtime.mesh.shape.values())))
        gate = threading.BoundedSemaphore(
            max(1, int(self.cfg.max_concurrent_fits)) if n_dev == 1 else 1)
        # Pool threads carry no ambient trace OR job record — re-attach
        # both so each family's spans nest under the job/request span
        # (the Gantt view of the PR-3 overlap: fit.<c> spans overlap in
        # wall time; their host_prep/device/finish children show which
        # phase overlapped which) and its resource watermarks
        # (family_phase, device_span) land on the right job's profile.
        from learningorchestra_tpu import jobs

        parent_ctx = tracing.current()
        job_rec = jobs.current_job_record()
        # When the last family left its device phase (each writes the
        # clock as it leaves; the last write stands): build.tail runs
        # from there to the end of the round.
        device_done: Optional[float] = None

        def fit_guarded(c: str) -> FitReport:
            nonlocal device_done
            staged = None
            with tracing.attach(parent_ctx), \
                    jobs.attach_job_record(job_rec):
                try:
                    # The except sits OUTSIDE the span: a failing family
                    # must escape it so the fit.<c> span records
                    # status=error — the trace view and the report may
                    # never disagree about whether a family succeeded.
                    with tracing.span(f"fit.{c}", family=c):
                        extra, prep_s = prep_fit(c)   # outside the gate
                        with tracing.span(f"fit.{c}.gate_wait"):
                            gate.acquire()            # device phase
                        try:
                            # family_phase attributes the fit program's
                            # compile seconds to this family; the
                            # probability pass's compiles land via
                            # collect_fit's device_span. The compile
                            # counter is process-global, so resources.
                            # device_phase attributes a window's delta
                            # only when no other phase overlapped it
                            # (a gate >1 admits concurrent families) —
                            # overlapped windows record peaks only,
                            # never a double-counted compile_s.
                            phase: Dict[str, Any] = {}
                            with Timer() as td, \
                                    tracing.span(f"fit.{c}.dispatch", phase), \
                                    resources.family_phase(c, phase):
                                model = dispatch_fit(c, extra)
                            staged = stage_model(c, model)
                            pre_s = prep_s + td.elapsed
                            probs, device_s = collect_fit(c, model, pre_s)
                        finally:
                            gate.release()
                            device_done = time.monotonic()
                        # fit_time = prep + dispatch + device spans, no
                        # scheduler waits: the per-family sum estimates
                        # the serialized sweep, and the gap to build
                        # wall-clock IS the overlap won.
                        return finish_host(c, model, probs,
                                           pre_s + device_s, device_s,
                                           staged)
                except Exception as exc:  # noqa: BLE001 — per-model bound
                    if staged is not None:
                        staged.discard()
                    return fail_report(c, exc)

        with ThreadPoolExecutor(
                max_workers=max(len(classifiers), 1)) as pool:
            futures = {c: pool.submit(fit_guarded, c) for c in classifiers}
            reports = [fut.result() for fut in futures.values()]
        if device_done is not None:
            tracing.record_span("build.tail",
                                time.monotonic() - device_done)
        return reports

    def _build_dispatched(self, train, test, prediction_name, classifiers,
                          label, steps, hparams, X_train, X_test, state,
                          feature_fields, streamed, prep_fit, dispatch_fit,
                          collect_fit, finish_host,
                          fail_report) -> List[FitReport]:
        """Multi-process SPMD: broadcast ONE build spec covering every
        classifier, then run the whole sweep as a single batched dispatch
        round. The fit programs of every family are enqueued back-to-back
        with no host barrier between them (JAX dispatch is async — family
        k+1's host prep runs while family k computes), the probability
        passes follow in the same deterministic order, and all host-side
        finishing (metrics, prediction datasets, persistence) runs after
        the collective program — exactly the worker-side device-op
        sequence (parallel/spmd.prep_build_job), so collective-program
        order is identical on every process. Per-family failures are
        caught and the family's remaining device ops skipped identically
        everywhere (deterministic inputs ⇒ deterministic failures),
        preserving alignment.

        Row counts pin the snapshot: a concurrent ingest commit between
        the save and a worker's load must not change the collective
        program's shapes (workers truncate to these counts). State +
        feature fields pin the preprocessing snapshot too: a worker
        refitting stats over a longer dataset would otherwise build
        numerically different (or wider) matrices than process 0's."""
        fitted: Dict[str, Any] = {}
        results: Dict[str, Any] = {}
        with spmd.dispatch_job(
                self.store, (train, test), {
                    "op": "build", "train": train, "test": test,
                    "label": label, "steps": list(steps),
                    "classifiers": list(classifiers),
                    "hparams": hparams,
                    "n_train": int(len(X_train)),
                    "n_test": int(len(X_test)),
                    "state": spmd.jsonable_state(state),
                    "feature_fields": list(feature_fields),
                    "streamed": streamed,
                },
                outputs=[f"{prediction_name}_{c}" for c in classifiers]):
            for c in classifiers:           # phase 1: enqueue every fit
                t0 = time.time()
                try:
                    extra, prep_s = prep_fit(c)
                    # Same compile-attribution split as the pipelined
                    # path: fit-program compiles here, the probability
                    # pass's via collect_fit's device_span. This loop is
                    # sequential, so these windows never overlap and
                    # always attribute.
                    phase: Dict[str, Any] = {}
                    with tracing.span(f"fit.{c}.dispatch", phase), \
                            resources.family_phase(c, phase):
                        model = dispatch_fit(c, extra)
                        # No-op on TPU (stream order keeps back-to-back
                        # programs aligned); fences the CPU test rig,
                        # whose in-flight programs execute concurrently.
                        spmd.serialize_collectives(model.params)
                    fitted[c] = (model, time.time() - t0, t0)
                except Exception as exc:  # noqa: BLE001 — per-model boundary
                    fitted[c] = exc
            for c in classifiers:           # phase 2: probability passes
                if isinstance(fitted[c], Exception):
                    results[c] = fitted[c]
                    continue
                model, pre_s, t0 = fitted[c]
                try:
                    probs, device_s = collect_fit(c, model, pre_s)
                    # Per-fit time = dispatch-to-probabilities wall span.
                    # Families' spans overlap (fits enqueue back-to-back;
                    # every span covers the shared device region), so the
                    # build wall-clock landing BELOW their sum is the
                    # direct evidence the round pipelines — under the old
                    # serialized fit-per-guard-hold loop the spans were
                    # disjoint and summed to wall minus overhead.
                    results[c] = (model, probs, time.time() - t0,
                                  device_s)
                except Exception as exc:  # noqa: BLE001 — per-model boundary
                    results[c] = exc
            device_done = time.monotonic()
        reports = []
        for c in classifiers:               # phase 3: host finishing
            res = results[c]
            if isinstance(res, Exception):
                reports.append(fail_report(c, res))
                continue
            try:
                reports.append(finish_host(c, *res))
            except Exception as exc:  # noqa: BLE001 — per-model boundary
                reports.append(fail_report(c, exc))
        tracing.record_span("build.tail", time.monotonic() - device_done)
        return reports

    def predict(self, model_name: str, dataset: str, out_name: str,
                existing: bool = False) -> None:
        """Serve a persisted model on a stored dataset: apply its train-time
        preprocessing state, predict, and write a prediction dataset — the
        re-use path the reference lacks entirely (models were discarded,
        reference model_builder.py:227-248).

        ``existing=True``: the caller (the async route) already created the
        output dataset metadata-first, so a crash mid-predict is pollable.
        """
        man, model = self.registry.load(model_name)
        pp = man.get("preprocess")
        if pp is None:
            raise ValueError(
                f"model {model_name} was exec-preprocessed; it carries no "
                "reproducible preprocessing state to apply to new datasets")
        ds = self.store.get(dataset)
        if not existing:
            self.store.create(out_name, parent=dataset,
                              extra={"model": model_name, "kind": man["kind"]})
        streamed = ds.over_budget or self.cfg.stream_design
        with timed("model_predict"):
            if streamed:
                X, _, _, _ = preprocess.design_matrix_streamed(
                    ds, pp["label"], pp["steps"], state=pp["state"],
                    feature_fields=pp["feature_fields"], need_y=False)
            else:
                X, _, _, _ = preprocess.design_matrix(
                    ds, pp["label"], pp["steps"], state=pp["state"],
                    feature_fields=pp["feature_fields"])
            with spmd.dispatch_job(
                    self.store, (dataset,),
                    {"op": "predict", "model": model_name,
                     "dataset": dataset, "n_rows": int(len(X)),
                     "streamed": streamed},
                    outputs=(out_name,)):
                probs = model.predict_proba(self.runtime, X)
        preds = np.argmax(probs, axis=1)
        self._save_predictions(out_name, ds, preds, probs,
                               FitReport(kind=man["kind"], fit_time=0.0),
                               phase="predict.save")

    # -- device-resident hyperparameter search (models/tune.py) --------------

    def tune(self, train: str, out_name: str, classifier: str,
             configs: Sequence[Dict[str, Any]], label: str,
             steps: Sequence[Dict[str, Any]] = (),
             folds: Optional[int] = None, rungs: Optional[int] = None,
             promote: bool = False,
             existing: bool = False) -> Dict[str, Any]:
        """Run one vmapped hyperparameter sweep over ``configs`` of a
        single family against the resident design of ``train``; the
        leaderboard (per-config fold scores, fit seconds, rung survival,
        winner) lands in ``out_name``'s metadata and is returned.

        ``promote=True`` refits the winning config on ALL rows (CV fold
        masking off) and persists it under ``out_name`` in the trained-
        model registry, so the sweep's product is directly servable.
        ``existing=True`` means the async route already created the
        marker dataset metadata-first.
        """
        from learningorchestra_tpu.models import tune as tune_mod

        train_ds = self.store.get(train)
        if self.cfg.stream_design or train_ds.over_budget:
            # The member-axis fold masks multiply against ONE resident
            # (n, d) design; a streamed design never materializes, so
            # there is nothing to mask.
            raise ValueError(
                "tune sweeps need a resident design matrix; streamed "
                "designs are fit-only")
        steps_key = json.dumps(list(steps), sort_keys=True, default=str)
        with tracing.span("design.build", train=train):
            X_train, y_train, feature_fields, state = train_ds.memo(
                ("design", label, steps_key),
                lambda: preprocess.design_matrix(train_ds, label, steps))
        if y_train is None:
            raise ValueError(f"label field {label!r} not in {train!r}")
        num_classes = max(2, int(y_train.max()) + 1)
        pp_meta = {"steps": list(steps), "state": state,
                   "feature_fields": feature_fields, "label": label}

        if not existing:
            self.store.create(out_name, parent=train,
                              extra={"classifier": classifier,
                                     "label": label, "tune": True})
        ck_on = int(self.cfg.fit_ckpt_rounds) > 0
        ckpt = None
        if ck_on and not spmd.is_multiprocess():
            # Rung-boundary checkpoints: keyed on everything that changes
            # the sweep's arithmetic or orchestration (configs, folds,
            # rungs, mesh shape), so a resume under ANY changed setup
            # starts fresh instead of splicing incompatible state.
            ckpt = fitckpt.context(
                self.cfg, dataset=train, family=f"tune_{classifier}",
                config={"family": classifier, "configs": list(configs),
                        "folds": folds, "rungs": rungs, "label": label,
                        "steps": list(steps), "num_classes": num_classes,
                        "mesh": dict(self.runtime.mesh.shape)},
                snapshot=f"rows={int(len(X_train))}")
        try:
            with timed("tune"), \
                    tracing.span("tune.sweep", family=classifier,
                                 configs=len(configs)):
                board = tune_mod.sweep(
                    self.runtime, X_train, y_train, num_classes,
                    classifier, configs, cfg=self.cfg,
                    folds=folds, rungs=rungs, ckpt=ckpt)
        except Exception as exc:
            self.store.fail(out_name, f"{type(exc).__name__}: {exc}")
            raise

        if promote:
            # Winner promotion: one full-data fit of the best config —
            # the same trainer entry point as build, so host_prep hooks
            # (tree quantile edges) and registry manifests match.
            hp = dict(board["winner"]["config"])
            trainer = get_trainer(classifier)
            prep = getattr(trainer, "host_prep", None)
            extra = prep(X_train, **hp) if prep is not None else {}
            with timed("tune.promote"), resources.family_phase(classifier):
                model = trainer(self.runtime, X_train, y_train,
                                num_classes, **dict(hp, **extra))
            if self.cfg.persist_models:
                try:
                    with tracing.span("tune.promote.model"):
                        self.registry.save(
                            out_name, model,
                            metrics={"mean_score":
                                     board["winner"]["mean_score"],
                                     "tuned": True},
                            preprocess=pp_meta, phase="tune.promote.model")
                    board["promoted"] = out_name
                except Exception as exc:  # noqa: BLE001 — best-effort
                    board["promote_error"] = (
                        f"{type(exc).__name__}: {exc}")

        self.store.finish(out_name, tune=board)
        from learningorchestra_tpu import jobs

        jobs.heartbeat()
        return board

    def _save_predictions(self, name: str, test_ds, preds: np.ndarray,
                          probs: np.ndarray, report: FitReport,
                          phase: str) -> None:
        """Write the prediction dataset: original test rows + prediction +
        probability list; metrics into metadata (reference
        model_builder.py:191-248 drops 'features'/'rawPrediction' and
        converts the probability vector to a plain list). Two spans
        under the caller's ``phase``: ``<phase>.rows`` gathers the
        columns, ``<phase>.store`` appends and commits them."""
        ds = self.store.get(name)
        n = len(preds)

        def prob_objcol(block_probs: np.ndarray) -> np.ndarray:
            # Object array of Python lists of Python floats, filled by
            # one tolist() (np.array(list-of-lists, dtype=object) would
            # build a 2-D array instead; a loop over the rows holds the
            # GIL against the other families' finishing).
            out = np.empty(len(block_probs), dtype=object)
            out[:] = block_probs.astype(np.float64).tolist()
            return out

        def commit() -> None:
            self.store.finish(name, fit_time=report.fit_time,
                              **report.metrics)

        if test_ds.over_budget or self.cfg.stream_design:
            # Out-of-core test set (or forced streaming): write the
            # prediction dataset in row blocks instead of consolidating
            # the parent — the same predicate as every other
            # streamed/resident decision, so LO_TPU_STREAM_DESIGN never
            # re-introduces the O(dataset) host spike it exists to avoid.
            block = 1 << 18
            for off in range(0, n, block):
                stop = min(off + block, n)
                with tracing.span(f"{phase}.rows"):
                    cols = test_ds.read_rows(None, off, stop)
                    cols["prediction"] = preds[off:stop].astype(np.int64)
                    cols["probability"] = prob_objcol(probs[off:stop])
                with tracing.span(f"{phase}.store"):
                    ds.append_columns(cols)
            with tracing.span(f"{phase}.store"):
                commit()
        else:
            with tracing.span(f"{phase}.rows"):
                cols = {f: test_ds.columns[f]
                        for f in test_ds.metadata.fields}
                cols["prediction"] = preds.astype(np.int64)
                cols["probability"] = prob_objcol(probs)
            with tracing.span(f"{phase}.store"):
                ds.append_columns(cols)
                commit()
