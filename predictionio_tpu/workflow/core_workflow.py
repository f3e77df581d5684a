"""CoreWorkflow — train/eval runs with metadata + model persistence.

Reference parity: ``core/.../workflow/CoreWorkflow.scala`` — ``runTrain``
(:45-102): insert EngineInstance, engine.train, serialize models into the
Models repo, mark COMPLETED; ``runEvaluation`` (:104-164): insert
EvaluationInstance, run evaluator, persist one-liner/HTML/JSON results.
Train wall-clock is recorded explicitly (the reference only kept
startTime/endTime implicitly — SURVEY.md section 6 calls this out as a gap).
"""

from __future__ import annotations

import contextlib
import datetime as _dt
import json
import logging
import os
import sys
import time
from typing import Any

from predictionio_tpu.controller.engine import Engine, EngineParams, TrainOptions
from predictionio_tpu.data.storage.base import (
    EngineInstance,
    EngineInstanceStatus,
    EvaluationInstance,
    EvaluationInstanceStatus,
    Model,
)
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.obs import xray
from predictionio_tpu.obs.profiler import maybe_profile_train
from predictionio_tpu.workflow import model_io
from predictionio_tpu.workflow.cleanup import CleanupFunctions
from predictionio_tpu.workflow.context import WorkflowContext
from predictionio_tpu.workflow.engine_loader import EngineManifest

logger = logging.getLogger(__name__)
UTC = _dt.timezone.utc


def run_train(
    engine: Engine,
    manifest: EngineManifest,
    engine_params: EngineParams,
    ctx: WorkflowContext | None = None,
    options: TrainOptions | None = None,
    storage: Storage | None = None,
    batch: str = "",
    env: dict[str, str] | None = None,
    registry_dir: str | None = None,
    keep_versions: int = 5,
) -> str:
    """Run training end-to-end; returns the engine-instance id.

    With a registry configured (``registry_dir`` argument or the
    ``PIO_REGISTRY_DIR`` env var) the serialized blob is ALSO published as
    a content-addressed, sha256-checksummed artifact with a lineage
    manifest — the unit ``pio models`` and the progressive-rollout router
    operate on. Publish failures never fail the train: the metadata/model
    stores above are written first and remain authoritative for recovery.

    Multi-host: every process runs the same compute (SPMD — non-coordinator
    hosts must participate in the collectives inside ``engine.train``), but
    only process 0 touches the metadata/model stores; the others return ""
    (ref: the Spark driver was the single metadata writer,
    CoreWorkflow.scala:45-102).
    """
    storage = storage or Storage.instance()
    ctx = ctx or WorkflowContext(mode="training", _storage=storage, batch=batch)
    # multi-host detection via the launcher's env contract, NOT an
    # unconditional jax.process_count(): calling into jax here would
    # initialize the XLA backend for every train — including pure-host
    # LocalAlgorithm engines that never touch jax — contending for the
    # accelerator with any already-deployed server on the same machine.
    # A deployment that initializes jax.distributed programmatically
    # (without the launcher env contract) is still covered: when jax is
    # ALREADY imported AND its distributed runtime is initialized,
    # consulting it is safe — ``is_initialized`` only reads client state,
    # and ``process_count`` can no longer trigger a *fresh* backend init
    # fight because distributed init implies the deployment owns the
    # device. Without the check every such process would take the
    # coordinator path and concurrently write metadata/models.
    multi_host = bool(
        os.environ.get("PIO_COORDINATOR")
        or os.environ.get("JAX_COORDINATOR_ADDRESS")
    )
    if not multi_host and "jax" in sys.modules:
        import jax

        if jax.distributed.is_initialized():
            multi_host = jax.process_count() > 1
    if multi_host:
        import jax

        if jax.process_count() > 1 and jax.process_index() != 0:
            try:
                # non-coordinator workers profile too (PIO_PROFILE_DIR gate):
                # their bundle context names the process index so a per-host
                # straggler is attributable
                with maybe_profile_train(
                    context={
                        "engine": manifest.engine_id,
                        "engineVersion": manifest.version,
                        "processIndex": jax.process_index(),
                    }
                ):
                    models = engine.train(ctx, engine_params, options)
                if not (
                    options
                    and (options.stop_after_read or options.stop_after_prepare)
                ):
                    # serialization includes the cross-host gather of sharded
                    # model arrays (model_to_host), which is itself a
                    # collective — every process must run it even though only
                    # process 0 persists
                    engine.make_serializable_models(ctx, engine_params, models)
            finally:
                # same contract as the coordinator path's finally: cleanup
                # hooks run even when a worker's collective aborts
                CleanupFunctions.run()
            logger.info(
                "process %d finished (coordinator persists)", jax.process_index()
            )
            return ""
    instances = storage.get_meta_data_engine_instances()
    params_json = Engine.engine_params_to_json(engine_params)
    instance = EngineInstance(
        id="",
        status=EngineInstanceStatus.INIT,
        start_time=_dt.datetime.now(tz=UTC),
        end_time=_dt.datetime.now(tz=UTC),
        engine_id=manifest.engine_id,
        engine_version=manifest.version,
        engine_variant=manifest.variant,
        engine_factory=manifest.engine_factory,
        batch=batch,
        env=env or {},
        **params_json,
    )
    instance_id = instances.insert(instance)
    logger.info("engine instance %s created", instance_id)
    # the step profiler (obs/xray): phases tile the train wall clock,
    # every iteration becomes a train.step span, and the finished profile
    # rides the registry manifest as this version's training evidence.
    # PIO_XRAY=0 opts out (restores the fully-async unprofiled dispatch).
    profile: xray.TrainProfile | None = None
    if os.environ.get("PIO_XRAY", "1").lower() not in ("0", "false", "off"):
        profile = xray.TrainProfile(trainer=f"{manifest.engine_id}:batch")
    t0 = time.perf_counter()
    try:
        instance.status = EngineInstanceStatus.TRAINING
        instances.update(instance)
        with contextlib.ExitStack() as scope:
            if profile is not None:
                scope.enter_context(xray.use_profile(profile))
                scope.enter_context(profile.measure())
            # device-trace gate (PIO_PROFILE_DIR): the trace now lands as a
            # content-addressed profile bundle whose manifest cross-links
            # the xray TrainProfile running in this same scope
            with maybe_profile_train(
                context={
                    "engine": manifest.engine_id,
                    "engineVersion": manifest.version,
                    "batch": batch,
                    "instanceId": instance_id,
                },
                parts_fn=lambda: (
                    {"xray": profile.to_json_dict()}
                    if profile is not None
                    else {}
                ),
            ):
                models = engine.train(ctx, engine_params, options)
            if options and (
                options.stop_after_read or options.stop_after_prepare
            ):
                instance.status = EngineInstanceStatus.COMPLETED
                instance.end_time = _dt.datetime.now(tz=UTC)
                instances.update(instance)
                return instance_id
            with xray.phase(xray.PHASE_HOST_ETL):
                persistable = engine.make_serializable_models(
                    ctx, engine_params, models
                )
                blob = model_io.serialize_models(persistable)
        if profile is not None:
            profile.finish()
        storage.get_model_data_models().insert(Model(instance_id, blob))
        wall = time.perf_counter() - t0
        instance.status = EngineInstanceStatus.COMPLETED
        instance.end_time = _dt.datetime.now(tz=UTC)
        # where the train's arrays sat, as the profile sampled it from the
        # arrays themselves; None for a pure-host engine or under PIO_XRAY=0
        devices = profile.devices if profile is not None else None
        instance.spark_conf = {
            "train_wall_clock_sec": f"{wall:.3f}",
            "train_device": json.dumps(devices),
        }
        instances.update(instance)
        _publish_to_registry(
            manifest,
            instance_id,
            blob,
            params_json,
            wall,
            batch,
            registry_dir,
            keep_versions,
            train_profile=profile.to_json_dict() if profile is not None else {},
            models=persistable,
        )
        logger.info(
            "training completed: instance %s, %.2fs, %d model(s), %d byte "
            "blob, device %s",
            instance_id,
            wall,
            len(models),
            len(blob),
            json.dumps(devices),
        )
        return instance_id
    except Exception:
        instance.status = EngineInstanceStatus.FAILED
        instance.end_time = _dt.datetime.now(tz=UTC)
        instances.update(instance)
        raise
    finally:
        CleanupFunctions.run()


def _publish_to_registry(
    manifest: EngineManifest,
    instance_id: str,
    blob: bytes,
    params_json: dict[str, str],
    wall_s: float,
    batch: str,
    registry_dir: str | None,
    keep_versions: int,
    train_profile: dict | None = None,
    models: list[Any] | None = None,
) -> None:
    """Write the trained blob into the artifact registry with its lineage
    manifest — including the train profile, so every version carries its
    training evidence (`pio models show` answers "how was this trained,
    how long, how big"). Atomic (tmp+rename inside the store);
    best-effort by contract — a broken registry disk must not fail a
    completed train.

    When the trained models expose an item-vector table and the corpus
    clears the ANN threshold (predictionio_tpu/ann, docs/ann.md), the
    version also gets its retrieval index built and pinned here — the
    end-of-train half of the index lifecycle."""
    registry_dir = registry_dir or os.environ.get("PIO_REGISTRY_DIR")
    if not registry_dir:
        return
    try:
        from predictionio_tpu.registry import (
            ArtifactStore,
            ModelManifest,
            params_hash_of,
        )

        store = ArtifactStore(registry_dir)
        published = store.publish(
            ModelManifest(
                version="",
                engine_id=manifest.engine_id,
                engine_version=manifest.version,
                engine_variant=manifest.variant,
                engine_factory=manifest.engine_factory,
                instance_id=instance_id,
                params_hash=params_hash_of(params_json),
                data_span={
                    "trainedAt": ModelManifest.now_iso(),
                    "batch": batch,
                    "trainWallClockSec": round(wall_s, 3),
                },
                train_profile=train_profile or {},
            ),
            blob,
            keep_last=keep_versions,
        )
        logger.info(
            "registry: published %s (instance %s)", published.version, instance_id
        )
        if models:
            from predictionio_tpu.ann import lifecycle as ann_lifecycle

            ann_lifecycle.build_for_version(
                store, manifest.engine_id, published.version, models
            )
    except Exception:
        logger.exception(
            "registry publish failed (metadata store remains authoritative)"
        )


def load_models_for_instance(
    engine: Engine,
    engine_params: EngineParams,
    instance_id: str,
    ctx: WorkflowContext | None = None,
    storage: Storage | None = None,
) -> list[Any]:
    """Model-repo blob -> deployable models (ref CreateServer.scala:196-220)."""
    storage = storage or Storage.instance()
    ctx = ctx or WorkflowContext(mode="serving", _storage=storage)
    record = storage.get_model_data_models().get(instance_id)
    if record is None:
        raise RuntimeError(f"no model blob for engine instance {instance_id}")
    persisted = model_io.deserialize_models(record.models)
    return engine.prepare_deploy(ctx, engine_params, persisted)


def run_grid_evaluation(
    evaluation_source: "Any",
    ctx: WorkflowContext | None = None,
    storage: Storage | None = None,
    batch: str = "",
    **grid_kwargs: Any,
) -> tuple[str, Any]:
    """Run an Evaluation through the parallel, resumable evaluation grid
    (predictionio_tpu/tuning, docs/evaluation.md) with the same
    EvaluationInstance bookkeeping as :func:`run_evaluation`: the
    metadata store keeps its one-liner/JSON/HTML results row, the grid
    keeps its durable cell ledger, and (when publishing) the winner
    rides the registry as a candidate. Returns (instance_id, GridReport).
    """
    from predictionio_tpu.tuning import run_grid
    from predictionio_tpu.tuning.cells import resolve_evaluation

    storage = storage or Storage.instance()
    ctx = ctx or WorkflowContext(mode="evaluation", _storage=storage, batch=batch)
    evaluation = grid_kwargs.pop("evaluation", None) or resolve_evaluation(
        evaluation_source
    )
    instances = storage.get_meta_data_evaluation_instances()
    instance = EvaluationInstance(
        id="",
        status=EvaluationInstanceStatus.INIT,
        start_time=_dt.datetime.now(tz=UTC),
        end_time=_dt.datetime.now(tz=UTC),
        evaluation_class=type(evaluation).__module__
        + "."
        + type(evaluation).__qualname__,
        batch=batch,
    )
    instance_id = ""

    def record_start() -> None:
        # inserted only AFTER run_grid's argument/ledger validation: a
        # flag typo (ledger-exists-without-resume, missing registry for
        # --publish, ...) must not leave a forever-EVALUATING zombie row
        # in the metadata store on every retry
        nonlocal instance_id
        instance_id = instances.insert(instance)
        instance.status = EvaluationInstanceStatus.EVALUATING
        instances.update(instance)

    try:
        # workers>0 rebuild the evaluation by name in each process — hand
        # the original source through; the resolved instance serves the
        # in-process path
        source = (
            evaluation_source
            if isinstance(evaluation_source, str)
            or not hasattr(evaluation_source, "run")
            else evaluation
        )
        report = run_grid(
            source,
            ctx=ctx,
            storage=storage,
            evaluation=evaluation,
            on_validated=record_start,
            **grid_kwargs,
        )
    except BaseException:
        # stays EVALUATING — never EVALCOMPLETED; the ledger holds the
        # finished cells for a --resume
        if instance_id:
            instance.end_time = _dt.datetime.now(tz=UTC)
            instances.update(instance)
        CleanupFunctions.run()
        raise
    result = report.evaluator_result
    instance.status = EvaluationInstanceStatus.EVALCOMPLETED
    instance.end_time = _dt.datetime.now(tz=UTC)
    instance.evaluator_results = report.one_liner()
    if result is not None:
        instance.evaluator_results_json = json.dumps(result.to_json_dict())
        instance.evaluator_results_html = result.to_html()
    instances.update(instance)
    CleanupFunctions.run()
    return instance_id, report


def run_evaluation(
    evaluation: "Any",
    ctx: WorkflowContext | None = None,
    storage: Storage | None = None,
    batch: str = "",
) -> tuple[str, Any]:
    """Run an Evaluation (engine + metric + params list); persists an
    EvaluationInstance with one-liner/JSON/HTML results. Returns
    (instance_id, evaluator result)."""
    storage = storage or Storage.instance()
    ctx = ctx or WorkflowContext(mode="evaluation", _storage=storage, batch=batch)
    instances = storage.get_meta_data_evaluation_instances()
    instance = EvaluationInstance(
        id="",
        status=EvaluationInstanceStatus.INIT,
        start_time=_dt.datetime.now(tz=UTC),
        end_time=_dt.datetime.now(tz=UTC),
        evaluation_class=type(evaluation).__module__
        + "."
        + type(evaluation).__qualname__,
        batch=batch,
    )
    instance_id = instances.insert(instance)
    instance.status = EvaluationInstanceStatus.EVALUATING
    instances.update(instance)
    result = evaluation.run(ctx)
    if getattr(result, "no_save", False):
        # ref CoreWorkflow.scala:140-142 — FakeRun results are not persisted
        logger.info("evaluation result not inserted into database (no_save)")
    else:
        instance.status = EvaluationInstanceStatus.EVALCOMPLETED
        instance.end_time = _dt.datetime.now(tz=UTC)
        instance.evaluator_results = result.one_liner()
        instance.evaluator_results_json = json.dumps(result.to_json_dict())
        instance.evaluator_results_html = result.to_html()
        instances.update(instance)
    CleanupFunctions.run()
    return instance_id, result
