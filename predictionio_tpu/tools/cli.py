"""`pio`-style command-line console.

Reference parity: ``tools/.../console/Console.scala:134-630`` verb set —
  version, status, build, train, eval, deploy, undeploy, batchpredict,
  eventserver, adminserver, dashboard,
  app {new, list, show, delete, data-delete, channel-new, channel-delete},
  accesskey {new, list, delete}, template {list, get}, import, export, run.

Beyond the reference: ``lint`` (TPU-aware static analysis), ``top``
(live terminal summary of a running server's /metrics — qps, p95, shed
rate, breaker states, jit recompile count; see docs/observability.md),
and ``models`` (model registry: versioned artifacts, canary/shadow
rollout, promote/rollback/diff; see docs/model_registry.md).

Where the reference assembled a spark-submit command line around JVM mains
(``Runner.runOnSpark``, process boundary #1 in SURVEY.md section 3), this CLI
*is* the workflow process: train/eval/deploy run in-process on the local
devices; multi-host jobs launch this same CLI once per host with
``JAX_COORDINATOR`` env (jax.distributed) — no submission layer needed.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import shutil
import sys

import predictionio_tpu
# import-light by design (pure stdlib AST walking, no jax/numpy) — safe to
# pull in for every pio verb
from predictionio_tpu.analysis.cli import add_lint_arguments, run_lint
from predictionio_tpu.data.storage.base import AccessKey, App, Channel
from predictionio_tpu.data.storage.registry import Storage

logger = logging.getLogger(__name__)


def _storage() -> Storage:
    return Storage.instance()


def _die(msg: str, code: int = 1) -> int:
    print(f"[ERROR] {msg}", file=sys.stderr)
    return code


# ---------------------------------------------------------------------------
# app / accesskey / channel management (ref commands/App.scala)
# ---------------------------------------------------------------------------


def cmd_app_new(args) -> int:
    storage = _storage()
    apps = storage.get_meta_data_apps()
    if apps.get_by_name(args.name):
        return _die(f"App {args.name} already exists.")
    app_id = apps.insert(App(args.id or 0, args.name, args.description))
    if app_id is None:
        return _die(f"Unable to create app {args.name}.")
    storage.get_l_events().init(app_id)
    key = storage.get_meta_data_access_keys().insert(
        AccessKey(args.access_key or "", app_id, ())
    )
    if key is None:
        return _die(
            f"App {args.name} created (ID {app_id}) but access key "
            f"{args.access_key!r} already exists; create one with `accesskey new`."
        )
    print(f"Created a new app:")
    print(f"      Name: {args.name}")
    print(f"        ID: {app_id}")
    print(f"Access Key: {key}")
    return 0


def cmd_app_list(args) -> int:
    storage = _storage()
    keys = storage.get_meta_data_access_keys()
    print(f"{'Name':<20} | {'ID':>4} | Access Key")
    for app in storage.get_meta_data_apps().get_all():
        app_keys = keys.get_by_app_id(app.id)
        first = app_keys[0].key if app_keys else ""
        print(f"{app.name:<20} | {app.id:>4} | {first}")
    return 0


def cmd_app_show(args) -> int:
    storage = _storage()
    app = storage.get_meta_data_apps().get_by_name(args.name)
    if app is None:
        return _die(f"App {args.name} does not exist.")
    print(f"    App Name: {app.name}")
    print(f"      App ID: {app.id}")
    print(f" Description: {app.description or ''}")
    for k in storage.get_meta_data_access_keys().get_by_app_id(app.id):
        events = ",".join(k.events) if k.events else "(all)"
        print(f"  Access Key: {k.key} | {events}")
    for c in storage.get_meta_data_channels().get_by_app_id(app.id):
        print(f"     Channel: {c.name} (ID {c.id})")
    return 0


def cmd_app_delete(args) -> int:
    storage = _storage()
    apps = storage.get_meta_data_apps()
    app = apps.get_by_name(args.name)
    if app is None:
        return _die(f"App {args.name} does not exist.")
    if not args.force:
        return _die("Refusing to delete without --force (destructive).")
    for c in storage.get_meta_data_channels().get_by_app_id(app.id):
        storage.get_l_events().remove(app.id, c.id)
        storage.get_meta_data_channels().delete(c.id)
    storage.get_l_events().remove(app.id)
    for k in storage.get_meta_data_access_keys().get_by_app_id(app.id):
        storage.get_meta_data_access_keys().delete(k.key)
    apps.delete(app.id)
    print(f"Deleted app {args.name}.")
    return 0


def cmd_app_data_delete(args) -> int:
    storage = _storage()
    app = storage.get_meta_data_apps().get_by_name(args.name)
    if app is None:
        return _die(f"App {args.name} does not exist.")
    if not args.force:
        return _die("Refusing to delete data without --force (destructive).")
    if args.channel:
        channels = storage.get_meta_data_channels().get_by_app_id(app.id)
        ch = next((c for c in channels if c.name == args.channel), None)
        if ch is None:
            return _die(f"Channel {args.channel} does not exist.")
        storage.get_l_events().remove(app.id, ch.id)
        storage.get_l_events().init(app.id, ch.id)
    else:
        storage.get_l_events().remove(app.id)
        storage.get_l_events().init(app.id)
    print(f"Deleted data of app {args.name}.")
    return 0


def cmd_channel_new(args) -> int:
    storage = _storage()
    app = storage.get_meta_data_apps().get_by_name(args.app_name)
    if app is None:
        return _die(f"App {args.app_name} does not exist.")
    cid = storage.get_meta_data_channels().insert(Channel(0, args.channel, app.id))
    if cid is None:
        return _die(
            f"Unable to create channel {args.channel} "
            "(name must match ^[a-zA-Z0-9-]{1,16}$)."
        )
    storage.get_l_events().init(app.id, cid)
    print(f"Created channel {args.channel} (ID {cid}) for app {args.app_name}.")
    return 0


def cmd_channel_delete(args) -> int:
    storage = _storage()
    app = storage.get_meta_data_apps().get_by_name(args.app_name)
    if app is None:
        return _die(f"App {args.app_name} does not exist.")
    channels = storage.get_meta_data_channels().get_by_app_id(app.id)
    ch = next((c for c in channels if c.name == args.channel), None)
    if ch is None:
        return _die(f"Channel {args.channel} does not exist.")
    if not args.force:
        return _die("Refusing to delete without --force (destructive).")
    storage.get_l_events().remove(app.id, ch.id)
    storage.get_meta_data_channels().delete(ch.id)
    print(f"Deleted channel {args.channel}.")
    return 0


def cmd_accesskey_new(args) -> int:
    storage = _storage()
    app = storage.get_meta_data_apps().get_by_name(args.app_name)
    if app is None:
        return _die(f"App {args.app_name} does not exist.")
    key = storage.get_meta_data_access_keys().insert(
        AccessKey(args.key or "", app.id, tuple(args.event or ()))
    )
    if key is None:
        return _die(f"Access key {args.key!r} already exists.")
    print(f"Created new access key: {key}")
    return 0


def cmd_accesskey_list(args) -> int:
    storage = _storage()
    keys = storage.get_meta_data_access_keys()
    if args.app_name:
        app = storage.get_meta_data_apps().get_by_name(args.app_name)
        if app is None:
            return _die(f"App {args.app_name} does not exist.")
        listing = keys.get_by_app_id(app.id)
    else:
        listing = keys.get_all()
    print(f"{'Access Key':<66} | {'App ID':>6} | Allowed Events")
    for k in listing:
        events = ",".join(k.events) if k.events else "(all)"
        print(f"{k.key:<66} | {k.appid:>6} | {events}")
    return 0


def cmd_accesskey_delete(args) -> int:
    _storage().get_meta_data_access_keys().delete(args.key)
    print(f"Deleted access key {args.key}.")
    return 0


# ---------------------------------------------------------------------------
# engine lifecycle (ref commands/Engine.scala)
# ---------------------------------------------------------------------------


def cmd_build(args) -> int:
    """No compilation step exists (Python); build = validate the engine dir
    loads and its variant parses (ref `pio build` sbt packaging)."""
    from predictionio_tpu.workflow.engine_loader import load_engine

    manifest, engine = load_engine(args.engine_dir, args.variant)
    engine.engine_params_from_variant(manifest.variant_json)
    print(f"Engine {manifest.engine_id} is ready (factory {manifest.engine_factory}).")
    return 0


def cmd_unregister(args) -> int:
    """Compatibility verb (ref ``Console.scala:172-177``). In the reference
    0.12.x the parser still accepts ``unregister`` but the dispatch has no
    case for it (engine manifests were removed when ``pio build`` stopped
    registering engines), so it falls through to the help text. Here the
    verb is accepted explicitly: there is nothing to unregister — engines
    are plain directories, never registered anywhere — and saying so beats
    dumping help."""
    print(
        "Nothing to unregister: engines are not registered. An engine is "
        f"just its directory ({args.engine_dir}); remove the directory (or "
        "its trained instances via the metadata store) instead."
    )
    return 0


def _strip_launcher_flags(argv: list[str]) -> list[str]:
    """Drop --num-hosts/--hosts (and their values) so workers don't
    recursively launch fleets."""
    out: list[str] = []
    skip = False
    for a in argv:
        if skip:
            skip = False
            continue
        if a in ("--num-hosts", "--hosts"):
            skip = True
            continue
        if a.startswith("--num-hosts=") or a.startswith("--hosts="):
            continue
        out.append(a)
    return out


def cmd_train(args) -> int:
    from predictionio_tpu.controller.engine import TrainOptions
    from predictionio_tpu.parallel.distributed import maybe_initialize_distributed
    from predictionio_tpu.workflow.core_workflow import run_train
    from predictionio_tpu.workflow.engine_loader import load_engine

    if getattr(args, "follow", False) and not args.app_name:
        # fail BEFORE the (possibly hours-long) train, not after it
        return _die("pio train --follow requires --app-name")
    hosts = [h for h in (args.hosts or "").split(",") if h]
    if (args.num_hosts > 1 or hosts) and "PIO_PROCESS_ID" not in os.environ:
        # launcher role (ref Runner.runOnSpark, Runner.scala:185-334): spawn
        # one worker per host running this same train command; workers join
        # via the PIO_COORDINATOR contract and this process supervises
        from predictionio_tpu.parallel.launcher import launch_cli_multihost

        if not hosts and os.environ.get("JAX_PLATFORMS") != "cpu":
            # a chip belongs to one process at a time and every local
            # worker would claim every chip of this host
            return _die(
                f"--num-hosts {args.num_hosts} starts {args.num_hosts} "
                "processes on this host and each would claim every chip: "
                'one process drives all chips of a host ("distributed": '
                "true in the variant), --hosts h1,h2 places one process "
                "per host, and JAX_PLATFORMS=cpu runs the local rendezvous "
                "on the CPU"
            )
        # the argv main() actually PARSED, not the process's sys.argv: a
        # programmatic main(["train", ...]) call (test harness, wrapper)
        # must not spawn workers executing the wrapper's own command line
        invocation = getattr(args, "_invocation_argv", None)
        worker_args = _strip_launcher_flags(
            invocation if invocation is not None else sys.argv[1:]
        )
        return launch_cli_multihost(
            worker_args, num_hosts=args.num_hosts, hosts=hosts or None
        )

    maybe_initialize_distributed()

    manifest, engine = load_engine(args.engine_dir, args.variant)
    engine_params = engine.engine_params_from_variant(manifest.variant_json)
    options = TrainOptions(
        skip_sanity_check=args.skip_sanity_check,
        stop_after_read=args.stop_after_read,
        stop_after_prepare=args.stop_after_prepare,
    )
    instance_id = run_train(
        engine,
        manifest,
        engine_params,
        options=options,
        batch=args.batch or "",
        registry_dir=args.registry_dir,
        keep_versions=args.keep_versions,
    )
    print(f"Training completed. Engine instance ID: {instance_id}")
    if instance_id:  # "" on a non-coordinator host: process 0 keeps the record
        record = _storage().get_meta_data_engine_instances().get(instance_id)
        print(f"Trained on: {record.spark_conf.get('train_device', 'null')}")
    if getattr(args, "follow", False):
        # lambda-architecture handoff: the batch train just published the
        # stable; keep tailing the event store and publishing candidates
        print("Entering follow mode (speed layer)...")
        return _run_stream(args, manifest)
    return 0


def _run_stream(args, manifest) -> int:
    """Build and run the speed-layer pipeline (shared by ``pio stream``
    and ``pio train --follow``); see docs/streaming.md."""
    from predictionio_tpu.data.store.event_store import resolve_app
    from predictionio_tpu.registry import ArtifactStore
    from predictionio_tpu.stream import (
        CursorStore,
        EventTailer,
        StreamConfig,
        StreamInstruments,
        StreamPipeline,
        trainer_for_models,
    )
    from predictionio_tpu.workflow import model_io

    if not args.app_name:
        return _die("--app-name is required to tail an event store")
    storage = _storage()
    app_id, channel_id = resolve_app(storage, args.app_name, args.channel or None)
    registry_dir = args.registry_dir or os.environ.get("PIO_REGISTRY_DIR")
    store = ArtifactStore(registry_dir)
    state = store.get_state(manifest.engine_id)
    if not state.stable:
        return _die(
            f"no stable model in registry {store.base_dir} for engine "
            f"{manifest.engine_id}; run `pio train --registry-dir ...` first"
        )
    models = model_io.deserialize_models(
        store.load_blob(manifest.engine_id, state.stable)
    )
    trainer = trainer_for_models(models)
    tailer = EventTailer(
        storage.get_l_events(),
        app_id,
        channel_id,
        batch_limit=args.batch_limit,
        safety_lag_s=getattr(args, "safety_lag", 0.0),
    )
    cursors = CursorStore(getattr(args, "cursor_dir", None))
    cursor = cursors.load(app_id, channel_id)
    if cursor.position is None and not args.from_beginning:
        # fresh cursor: the stable already covers history — start at the
        # store head so only NEW events fold in (--from-beginning replays)
        head = tailer.head_position()
        if head is not None:
            cursor.seed(head)
            cursors.save(cursor)
    config = StreamConfig(
        engine_id=manifest.engine_id,
        engine_version=manifest.version,
        engine_variant=manifest.variant,
        engine_factory=manifest.engine_factory,
        mode=args.mode,
        fraction=args.fraction,
        publish_min_events=args.publish_min_events,
        interval_s=args.interval,
    )
    stage_hook = None
    if getattr(args, "notify_url", None):

        def stage_hook(version, mode, fraction, _url=args.notify_url):
            _http_json(
                f"{_url}/models/candidate",
                method="POST",
                payload={"version": version, "mode": mode, "fraction": fraction},
            )

    instruments = StreamInstruments()
    # --obs-dir: drift breaches become structured signals on the shared
    # telemetry ring (the lifecycle controller's retune sensor) plus
    # rate-limited incident bundles, instead of only a counter bump
    ring = incidents = None
    obs_dir = getattr(args, "obs_dir", None)
    if obs_dir:
        from predictionio_tpu.obs.incidents import IncidentRecorder
        from predictionio_tpu.obs.tsring import TelemetryRing

        ring = TelemetryRing(
            os.path.join(obs_dir, "telemetry"), writer_id="stream"
        )
        incidents = IncidentRecorder(
            os.path.join(obs_dir, "incidents"), metrics=instruments.registry
        )
        incidents.add_source("telemetry-ring", lambda: ring.tail(200))
    pipeline = StreamPipeline(
        tailer,
        trainer,
        cursors,
        store,
        config,
        instruments=instruments,
        stage_hook=stage_hook,
        ring=ring,
        incidents=incidents,
    )
    metrics_server = None
    if getattr(args, "metrics_port", 0):
        from predictionio_tpu.stream.pipeline import serve_metrics

        metrics_server = serve_metrics(instruments.registry, args.metrics_port)
        print(f"Metrics on http://0.0.0.0:{args.metrics_port}/metrics")
    print(
        f"Streaming app {args.app_name} (id {app_id}) -> registry "
        f"{store.base_dir} [{trainer.name}, {config.mode}@{config.fraction:g}]"
    )
    try:
        pipeline.run_forever(max_cycles=args.cycles)
    except KeyboardInterrupt:
        pass
    finally:
        if metrics_server is not None:
            metrics_server.shutdown()
    return 0


def cmd_stream(args) -> int:
    """Speed layer: tail the event store, fold events into the stable
    model incrementally, publish registry candidates continuously."""
    from predictionio_tpu.workflow.engine_loader import load_manifest

    manifest = load_manifest(args.engine_dir, args.variant)
    return _run_stream(args, manifest)


def cmd_eval(args) -> int:
    """Hyperparameter search as the evaluation grid (docs/evaluation.md):
    fold×params cells trained in parallel workers, scored through the
    offline mega-batch path, finished cells persisted to a durable ledger
    (``--resume`` retrains zero finished cells), and — with an engine
    identity and a registry — the winning refit published as a CANDIDATE
    carrying the full grid evidence, riding the same bake gates as every
    other model change."""
    import importlib
    import tempfile

    from predictionio_tpu.workflow.core_workflow import run_grid_evaluation

    # user evaluations live in the engine project's cwd (ref Console eval
    # runs from the engine dir); the installed `pio` script's sys.path[0]
    # is its bin dir, so put the cwd on the path like load_engine does for
    # engine dirs
    cwd = os.getcwd()
    if cwd not in sys.path:
        sys.path.insert(0, cwd)
    source: str = args.evaluation
    # FakeRun-style evaluations (run() but no engine/metric — the
    # `pio eval HelloWorld` dev flow, workflow/fake_workflow.py) have no
    # grid to search: keep them on the sequential parity path, which
    # also honors their no_save contract
    from predictionio_tpu.tuning.cells import resolve_evaluation

    probe = resolve_evaluation(args.evaluation)
    if (
        getattr(probe, "engine", None) is None
        or getattr(probe, "metric", None) is None
    ) and hasattr(probe, "run"):
        from predictionio_tpu.workflow.core_workflow import run_evaluation

        instance_id, result = run_evaluation(probe, batch=args.batch or "")
        print(result.one_liner())
        print(f"Evaluation instance ID: {instance_id}")
        return 0
    if args.engine_params_generator:
        # a separate generator overrides the evaluation's own params list;
        # resolve both here and hand the composed instance to the runner
        # (workers then require a self-contained evaluation path, which
        # the error below explains)
        if args.workers > 0:
            return _die(
                "an explicit engine_params_generator cannot ride to "
                "process workers (they rebuild the evaluation by its "
                "dotted path); set engine_params_generator on the "
                "Evaluation itself, or use --workers 0"
            )
        evaluation = probe
        module_name, _, attr = args.engine_params_generator.rpartition(".")
        generator = getattr(importlib.import_module(module_name), attr)
        if isinstance(generator, type):
            generator = generator()
        evaluation.engine_params_generator = generator
        source = evaluation  # type: ignore[assignment]

    engine_manifest = None
    if args.engine_dir:
        from predictionio_tpu.workflow.engine_loader import load_manifest

        engine_manifest = load_manifest(args.engine_dir, args.variant)
    registry_dir = args.registry_dir or os.environ.get("PIO_REGISTRY_DIR")
    if args.publish and args.no_publish:
        return _die("--publish and --no-publish are mutually exclusive")
    # default: publish when the pieces are in place (engine identity +
    # registry), stay quiet otherwise; --publish forces (and errors
    # loudly on missing pieces), --no-publish always wins
    publish = (
        False
        if args.no_publish
        else (args.publish or bool(engine_manifest and registry_dir))
    )
    if args.resume and not args.workdir:
        return _die(
            "--resume needs the --workdir of the run to resume "
            "(the trial ledger lives there)"
        )
    workdir = args.workdir or tempfile.mkdtemp(prefix="pio_eval_grid_")
    try:
        instance_id, report = run_grid_evaluation(
            source,
            evaluation=probe,  # already resolved above; don't rebuild
            batch=args.batch or "",
            workdir=workdir,
            workers=args.workers,
            folds=args.folds,
            resume=args.resume,
            batch_size=args.batch_size,
            publish=publish,
            registry_dir=registry_dir,
            engine_manifest=engine_manifest,
            stage_mode=args.stage_mode,
            stage_fraction=args.stage_fraction,
            status_path=args.status_file,
            cwd=cwd,
            nice=args.nice,
            worker_class=args.worker_class,
        )
    except ValueError as exc:
        return _die(str(exc))
    print(report.one_liner())
    if report.published_version:
        print(
            f"Winner published to registry as candidate "
            f"{report.published_version} (evidence: {report.cells_total} "
            f"cells, ledger sha {report.ledger_sha256[:12]})"
        )
    print(f"Trial ledger: {report.ledger_path}")
    print(f"Evaluation instance ID: {instance_id}")
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(report.to_json_dict(), fh, indent=1, sort_keys=True)
        print(f"Grid report written to {args.out}")
    return 0


def cmd_deploy(args) -> int:
    from predictionio_tpu.workflow.create_server import (
        ServerConfig,
        run_query_server,
    )

    if getattr(args, "autoscale", False) and not args.fleet:
        # silently ignoring elasticity flags would leave the operator
        # believing the fleet sizes itself when nothing is running
        return _die(
            "--autoscale requires --fleet N (the autoscaler drives the "
            "fleet supervisor; docs/fleet.md §Autoscaling)"
        )
    if getattr(args, "hosts", None) and not args.fleet:
        return _die(
            "--hosts requires --fleet N (host placement is the fleet "
            "supervisor's job; docs/fleet.md §Multi-host)"
        )
    if getattr(args, "lifecycle", None) and not args.fleet:
        return _die(
            "--lifecycle requires --fleet N (the controller rides the "
            "fleet parent's obs plane; for a single server run "
            "`pio lifecycle run` alongside it; docs/lifecycle.md)"
        )
    if getattr(args, "gateways", 1) != 1 and not args.fleet:
        return _die(
            "--gateways requires --fleet N (peer gateways front the "
            "fleet's replica set; docs/fleet.md §Gateway tier)"
        )
    if args.fleet:
        # N supervised worker processes behind a gateway (docs/fleet.md):
        # the gateway takes --port, workers take port+1..port+N and get a
        # registry sync interval so rollouts propagate fleet-wide
        from predictionio_tpu.fleet.launch import run_fleet

        try:
            return run_fleet(args, sys.argv[1:])
        except ValueError as exc:
            return _die(str(exc))

    from predictionio_tpu.parallel.distributed import maybe_initialize_distributed

    maybe_initialize_distributed()
    config = ServerConfig(
        ip=args.ip,
        port=args.port,
        accesskey=args.accesskey,
        feedback=args.feedback,
        event_server_url=args.event_server_url,
        feedback_access_key=args.feedback_access_key,
        ssl_certfile=args.ssl_certfile,
        ssl_keyfile=args.ssl_keyfile,
        log_url=args.log_url,
        log_prefix=args.log_prefix or "",
        request_timeout_s=args.request_timeout,
        queue_high_water=args.queue_high_water,
        breaker_threshold=args.breaker_threshold,
        breaker_recovery_s=args.breaker_recovery,
        registry_dir=args.registry_dir,
        sticky_key_field=args.sticky_key,
        candidate_breaker_threshold=args.candidate_breaker_threshold,
        bake_window_s=args.bake_window,
        bake_min_requests=args.bake_min_requests,
        auto_promote=not args.no_auto_promote,
        result_cache_size=args.result_cache_size,
        result_cache_ttl_s=args.result_cache_ttl,
        registry_sync_interval_s=args.registry_sync_interval or 0.0,
        drain_grace_s=args.drain_grace,
        bandit_policy=args.bandit,
        bandit_epsilon=args.bandit_epsilon,
        bandit_min_pulls=args.bandit_min_pulls,
        bandit_app_name=args.bandit_app_name,
        bandit_reward_events=tuple(
            s.strip() for s in args.bandit_reward_event.split(",") if s.strip()
        )
        if args.bandit_reward_event
        else ("reward",),
    )
    print(f"Engine server starting on {args.ip}:{args.port} ...")
    run_query_server(args.engine_dir, args.variant, config=config)
    return 0


def cmd_undeploy(args) -> int:
    """POST /stop to a running engine server (ref commands/Engine.scala:244-267)."""
    import ssl
    import urllib.request

    scheme = "https" if args.ssl else "http"
    url = f"{scheme}://{args.ip}:{args.port}/stop"
    context = ssl._create_unverified_context() if args.ssl else None
    try:
        with urllib.request.urlopen(
            urllib.request.Request(url, method="POST"), timeout=10, context=context
        ) as resp:
            print(resp.read().decode())
        return 0
    except Exception as exc:
        return _die(f"undeploy failed: {exc}")


def cmd_batchpredict(args) -> int:
    """Offline mega-batch prediction (docs/batch_predict.md): stream
    queries from a file or straight off the event store, dispatch
    device-sized batches through the fused kernels (double-buffered), and
    stream the scored top-k back to a file (atomic) and/or the event
    store. Nonzero exit only when setup fails or EVERY query line failed
    — a malformed line becomes a line-aligned error object, not an
    abort."""
    from predictionio_tpu.workflow.batch_predict import run_batch_predict

    if args.from_events and args.input is not None:
        return _die("--from-events and --input are mutually exclusive")
    input_path = (
        None
        if args.from_events
        else (args.input or "batchpredict-input.json")
    )
    try:
        report = run_batch_predict(
            args.engine_dir,
            input_path,
            args.output,
            variant_path=args.variant,
            from_events=args.from_events,
            app_name=args.app_name,
            channel=args.channel,
            query_num=args.query_num,
            to_events=args.to_events,
            batch_size=args.batch,
            limit=args.limit,
            status_path=args.status_file,
        )
    except (RuntimeError, OSError) as exc:
        return _die(f"batchpredict failed: {exc}")
    sinks = ([args.output] if args.output else []) + (
        ["event store"] if args.to_events else []
    )
    print(
        f"Batch predict completed: {report.queries} queries "
        f"({report.ok} ok, {report.errors} errors) in {report.wall_s:.2f}s "
        f"({report.qps:.0f} q/s) -> {', '.join(sinks)}"
    )
    if report.all_failed:
        return _die("batch predict: every query line failed")
    return 0


# ---------------------------------------------------------------------------
# servers / status / data
# ---------------------------------------------------------------------------


def cmd_eventserver(args) -> int:
    from predictionio_tpu.data.api.event_server import (
        EventServerConfig,
        run_event_server,
    )

    print(f"Event server starting on {args.ip}:{args.port} ...")
    run_event_server(
        EventServerConfig(
            ip=args.ip,
            port=args.port,
            stats=args.stats,
            ssl_certfile=args.ssl_certfile,
            ssl_keyfile=args.ssl_keyfile,
            storage_retries=args.storage_retries,
            breaker_threshold=args.breaker_threshold,
            breaker_recovery_s=args.breaker_recovery,
        )
    )
    return 0


def cmd_adminserver(args) -> int:
    from predictionio_tpu.tools.admin_api import run_admin_server

    print(f"Admin server starting on {args.ip}:{args.port} ...")
    run_admin_server(args.ip, args.port, registry_dir=args.registry_dir)
    return 0


def cmd_dashboard(args) -> int:
    from predictionio_tpu.tools.dashboard import run_dashboard

    print(f"Dashboard starting on {args.ip}:{args.port} ...")
    run_dashboard(args.ip, args.port, metrics_urls=args.metrics_url or ())
    return 0


_TOP_DEFAULT_URL = "http://127.0.0.1:8000"


def cmd_top(args) -> int:
    """Live one-screen summary of a running server's /metrics (qps, p95,
    waterfall, SLO burn, shed rate, breaker states, recompile count).
    ``--fleet`` points it at a fleet gateway's federated /metrics (the
    fleet line renders automatically when pio_fleet_* metrics exist);
    repeated ``--metrics-url`` polls several endpoints per refresh —
    with ``--json``, one object per endpoint per refresh. ``--history``
    renders the telemetry ring's queue-depth/burn series instead: from
    the gateway's ``/telemetry/window`` endpoint, or straight off the
    on-disk ring (``--obs-dir``) when the gateway is down."""
    from predictionio_tpu.tools.top import (
        run_batchpredict_top,
        run_evalgrid_top,
        run_history,
        run_lifecycle_top,
        run_top,
    )

    if getattr(args, "lifecycle", None):
        return run_lifecycle_top(
            args.lifecycle,
            interval_s=args.interval,
            iterations=1 if args.once else args.iterations,
            json_mode=args.json,
        )
    if args.eval:
        return run_evalgrid_top(
            args.eval,
            interval_s=args.interval,
            iterations=1 if args.once else args.iterations,
            json_mode=args.json,
        )
    if args.batchpredict:
        return run_batchpredict_top(
            args.batchpredict,
            interval_s=args.interval,
            iterations=1 if args.once else args.iterations,
            json_mode=args.json,
        )
    if args.history:
        url = args.url if (args.fleet or args.url != _TOP_DEFAULT_URL) else None
        if args.obs_dir is None and url is None:
            url = args.url  # default gateway address is still worth a try
        return run_history(
            url=url,
            obs_dir=args.obs_dir,
            window_s=args.history_window,
            json_mode=args.json,
        )
    iterations = 1 if args.once else args.iterations
    # --metrics-url endpoints poll IN ADDITION to a --url the operator
    # actually pointed somewhere (the flag's "too"): replicas scrape
    # directly alongside the gateway's federated view, which stays first
    # in the refresh. An untouched default --url is not silently polled.
    urls = list(args.metrics_url or [])
    url_given = args.fleet or args.url != _TOP_DEFAULT_URL
    if urls and url_given and args.url not in urls:
        urls.insert(0, args.url)
    elif args.fleet and not urls:
        urls = [args.url]  # the gateway IS the fleet view
    return run_top(
        args.url,
        interval_s=args.interval,
        iterations=iterations,
        clear_screen=False if args.once else None,
        json_mode=args.json,
        urls=urls or None,
        hotspots=args.hotspots,
    )


def _lifecycle_state_dir(args) -> str:
    return args.state_dir or os.path.join(args.obs_dir, "lifecycle")


def cmd_lifecycle_run(args) -> int:
    """The standalone lifecycle controller (docs/lifecycle.md): watch the
    obs dir's telemetry ring for drift signals (plus cadence/manual
    triggers), retune on background cpu-fallback grid workers, stage the
    winner, watch the bake, warm the cache on promote. `pio deploy
    --fleet N --lifecycle` embeds the same loop in the fleet parent; this
    command runs it against an already-running server."""
    import asyncio

    from predictionio_tpu.lifecycle import (
        LifecycleConfig,
        LifecycleController,
        LifecyclePolicy,
        build_grid_tuner,
        build_warmer,
    )
    from predictionio_tpu.lifecycle.warm import event_store_queries
    from predictionio_tpu.obs.incidents import IncidentRecorder
    from predictionio_tpu.obs.tsring import TelemetryRing
    from predictionio_tpu.registry import registry_rollout_probe
    from predictionio_tpu.workflow.engine_loader import load_manifest

    manifest = load_manifest(args.engine_dir, args.variant)
    registry_dir = args.registry_dir or os.environ.get("PIO_REGISTRY_DIR")
    if not registry_dir:
        return _die(
            "the lifecycle controller needs a registry "
            "(--registry-dir or $PIO_REGISTRY_DIR)"
        )
    state_dir = _lifecycle_state_dir(args)
    config = LifecycleConfig(
        cadence_s=args.cadence,
        drift_window_s=args.drift_window,
        min_drift_records=args.min_drift_records,
        cooldown_s=args.cooldown,
        tune_timeout_s=args.tune_timeout,
        bake_timeout_s=args.bake_timeout,
        tick_interval_s=args.tick_interval,
        warm_limit=args.warm_limit,
    )
    ring = TelemetryRing(
        os.path.join(args.obs_dir, "telemetry"), writer_id="lifecycle"
    )
    incidents = IncidentRecorder(os.path.join(args.obs_dir, "incidents"))
    incidents.add_source("telemetry-ring", lambda: ring.tail(200))
    cwd = os.getcwd()
    if cwd not in sys.path:
        sys.path.insert(0, cwd)
    tuner = build_grid_tuner(
        args.evaluation,
        workdir=args.workdir or os.path.join(state_dir, "grid"),
        engine_manifest=manifest,
        registry_dir=registry_dir,
        workers=args.workers,
        nice=args.nice,
        folds=args.folds,
        stage_mode=args.stage_mode,
        stage_fraction=args.stage_fraction,
        cwd=cwd,
        env={k: v for k, v in os.environ.items() if k.startswith("PIO_")},
    )
    warmer = None
    if args.serve_url and args.app_name:
        from predictionio_tpu.data.store.event_store import resolve_app

        storage = _storage()
        app_id, _ = resolve_app(storage, args.app_name, None)
        warmer = build_warmer(
            args.serve_url,
            lambda: event_store_queries(
                storage, app_id, limit=args.warm_limit
            ),
            limit=args.warm_limit,
        )
    controller = LifecycleController(
        LifecyclePolicy(config),
        state_dir=state_dir,
        engine_id=manifest.engine_id,
        registry_dir=registry_dir,
        tune=tuner,
        warm=warmer,
        rollout_probe=registry_rollout_probe(registry_dir),
        ring=ring,
        incidents=incidents,
    )
    print(
        f"Lifecycle controller for {manifest.engine_id}: state {state_dir}, "
        f"registry {registry_dir}, "
        f"triggers {'cadence %gs' % args.cadence if args.cadence else 'drift/manual'}"
    )
    try:
        asyncio.run(controller.run())
    except KeyboardInterrupt:
        pass
    return 0


def cmd_lifecycle_status(args) -> int:
    """One status line (or JSON) from the controller's durable state
    file; works whether or not the controller is alive — the file is the
    interface, exactly like `pio top --lifecycle`."""
    from predictionio_tpu.lifecycle import read_json_file
    from predictionio_tpu.lifecycle.controller import STATE_FILE
    from predictionio_tpu.tools.top import render_lifecycle

    path = os.path.join(_lifecycle_state_dir(args), STATE_FILE)
    status = read_json_file(path)
    if status is None:
        return _die(
            f"no lifecycle state at {path} (is a controller running with "
            "this --obs-dir/--state-dir?)"
        )
    if args.json:
        print(json.dumps(status, indent=2, sort_keys=True))
    else:
        print(render_lifecycle(status))
    return 0


def cmd_lifecycle_trigger(args) -> int:
    """Queue one manual retune: bumps the control file's trigger token;
    the controller consumes it on its next tick (bypassing cooldown —
    an operator asked — but never an in-flight episode or a live bake)."""
    from predictionio_tpu.lifecycle import write_control

    data = write_control(_lifecycle_state_dir(args), trigger=True)
    print(
        f"Retune queued (trigger token {data['trigger']}); the controller "
        "starts it on its next tick unless an episode is already running."
    )
    return 0


def cmd_lifecycle_pause(args) -> int:
    """Flip automatic triggers off/on. Pause stops NEW episodes only —
    an in-flight grid, bake, or warm always runs to its outcome (killing
    half-applied lifecycle work is how registries end up wedged)."""
    from predictionio_tpu.lifecycle import write_control

    paused = args.subcommand == "pause"
    write_control(_lifecycle_state_dir(args), paused=paused)
    print(
        "Lifecycle paused (automatic triggers off; `pio lifecycle resume` "
        "re-enables, manual `trigger` still works)."
        if paused
        else "Lifecycle resumed (automatic triggers back on)."
    )
    return 0


def _incidents_dir(args) -> str:
    return os.path.join(args.obs_dir, "incidents")


def cmd_incidents_list(args) -> int:
    """Incident bundles captured by the fleet flight recorder
    (docs/observability.md §Incident flight recorder)."""
    from predictionio_tpu.obs.incidents import list_bundles

    refs = list_bundles(_incidents_dir(args))
    if not refs:
        print(
            f"No incident bundles under {_incidents_dir(args)} "
            "(fleet deploys write them on worker crash / breaker trip / "
            "SLO alert; --obs-dir points elsewhere)"
        )
        return 0
    print(f"Incidents: {_incidents_dir(args)}")
    print(f"{'Bundle':<30} | {'Trigger':<14} | Captured")
    import time as _time

    for ref in refs:
        when = _time.strftime(
            "%Y-%m-%d %H:%M:%S", _time.localtime(ref.captured_at)
        )
        print(f"{ref.bundle_id:<30} | {ref.trigger:<14} | {when}")
    return 0


def cmd_incidents_show(args) -> int:
    from predictionio_tpu.obs.incidents import load_bundle

    try:
        bundle = load_bundle(_incidents_dir(args), args.bundle)
    except (FileNotFoundError, ValueError) as exc:
        return _die(str(exc))
    if args.json:
        print(json.dumps(bundle, indent=2, sort_keys=True, default=repr))
        return 0
    manifest = bundle["manifest"]
    print(f"trigger   {manifest.get('trigger')}")
    print(f"captured  {manifest.get('capturedAt')}")
    print(f"sha256    {manifest.get('sha256')}")
    context = manifest.get("context") or {}
    if context:
        print("context   " + json.dumps(context, sort_keys=True))
    for name, part in sorted(bundle["parts"].items()):
        size = len(json.dumps(part))
        print(f"part      {name}.json ({size} bytes)")
    for name, text in sorted(bundle["texts"].items()):
        print(f"text      {name}.txt ({len(text)} bytes)")
        n = max(0, args.tail_lines)
        tail = text.strip().splitlines()[-n:] if n else []
        for line in tail:
            print(f"  | {line}")
    return 0


def cmd_incidents_export(args) -> int:
    from predictionio_tpu.obs.incidents import export_bundle

    try:
        dest = export_bundle(_incidents_dir(args), args.bundle, args.dest)
    except (FileNotFoundError, ValueError, OSError) as exc:
        return _die(str(exc))
    print(f"Exported to {dest}")
    return 0


def _profile_dir(args) -> str:
    # CLI flag > PIO_PROFILE_DIR (the training compat alias) > the
    # serving default (ServerConfig.profile_dir)
    return (
        args.profile_dir
        or os.environ.get("PIO_PROFILE_DIR")
        or "pio_obs/profiles"
    )


def cmd_profile_serve(args) -> int:
    """Trigger an on-demand device capture on a RUNNING server (query,
    event, or fleet gateway — the gateway fans out to one replica):
    ``POST /profile/capture?ms=``. The bundle lands in the server's own
    profile store; inspect it with ``pio profile list/show`` against
    that directory."""
    import urllib.error
    import urllib.request

    target = args.url.rstrip("/") + f"/profile/capture?ms={args.ms}"
    req = urllib.request.Request(target, data=b"", method="POST")
    try:
        with urllib.request.urlopen(req, timeout=args.timeout) as resp:
            body = json.loads(resp.read().decode("utf-8", errors="replace"))
    except urllib.error.HTTPError as exc:
        detail = exc.read().decode("utf-8", errors="replace")[:400]
        if exc.code == 409:
            return _die(f"capture already in flight on {args.url}: {detail}")
        return _die(f"capture failed ({exc.code}): {detail}")
    except Exception as exc:  # noqa: BLE001 - network errors -> one line
        return _die(f"server unreachable at {args.url}: {exc}")
    print(json.dumps(body, indent=2, sort_keys=True))
    return 0


def cmd_profile_train(args) -> int:
    """Train under the device tracer: sets ``PIO_PROFILE_DIR`` (the
    compatibility gate `obs.profiler.maybe_profile_train` honors) and
    re-invokes ``pio train`` with the remaining arguments; the trace
    lands as a content-addressed bundle under the profile dir."""
    rest = list(args.train_args)
    if rest and rest[0] == "--":
        rest = rest[1:]
    os.environ["PIO_PROFILE_DIR"] = _profile_dir(args)
    return main(["train", *rest])


def cmd_profile_list(args) -> int:
    """Profile bundles (same content-addressed grammar as incident
    bundles; docs/observability.md §Profiling plane)."""
    from predictionio_tpu.obs.incidents import list_bundles

    directory = _profile_dir(args)
    refs = list_bundles(directory)
    if not refs:
        print(
            f"No profile bundles under {directory} "
            "(POST /profile/capture, `pio profile serve|train`, or "
            "profile-on-alert write them; --profile-dir points elsewhere)"
        )
        return 0
    print(f"Profiles: {directory}")
    print(f"{'Bundle':<30} | {'Trigger':<14} | Captured")
    import time as _time

    for ref in refs:
        when = _time.strftime(
            "%Y-%m-%d %H:%M:%S", _time.localtime(ref.captured_at)
        )
        print(f"{ref.bundle_id:<30} | {ref.trigger:<14} | {when}")
    return 0


def cmd_profile_show(args) -> int:
    from predictionio_tpu.obs.incidents import load_bundle

    directory = _profile_dir(args)
    try:
        bundle = load_bundle(directory, args.bundle)
    except (FileNotFoundError, ValueError) as exc:
        return _die(str(exc))
    if args.json:
        print(json.dumps(bundle, indent=2, sort_keys=True, default=repr))
        return 0
    manifest = bundle["manifest"]
    print(f"trigger   {manifest.get('trigger')}")
    print(f"captured  {manifest.get('capturedAt')}")
    print(f"sha256    {manifest.get('sha256')}")
    context = manifest.get("context") or {}
    if context:
        print("context   " + json.dumps(context, sort_keys=True))
    for name, part in sorted(bundle["parts"].items()):
        size = len(json.dumps(part))
        print(f"part      {name}.json ({size} bytes)")
    for name, text in sorted(bundle["texts"].items()):
        print(f"text      {name}.txt ({len(text)} bytes)")
    for entry in manifest.get("trace") or []:
        print(
            f"trace     {entry.get('name')} ({entry.get('bytes')} bytes, "
            f"sha256 {str(entry.get('sha256'))[:12]})"
        )
    return 0


def cmd_profile_export(args) -> int:
    from predictionio_tpu.obs.incidents import export_bundle

    try:
        dest = export_bundle(_profile_dir(args), args.bundle, args.dest)
    except (FileNotFoundError, ValueError, OSError) as exc:
        return _die(str(exc))
    print(f"Exported to {dest}")
    return 0


def cmd_status(args) -> int:
    """ref commands/Management.status + Storage.verifyAllDataObjects."""
    print(f"predictionio_tpu {predictionio_tpu.__version__}")
    try:
        storage = _storage()
    except Exception as exc:
        return _die(f"storage configuration invalid: {exc}")
    failures = storage.verify_all_data_objects()
    if failures:
        for f in failures:
            print(f"  [FAILED] {f}")
        return _die("storage verification failed")
    print("  storage: all data objects verified")
    # the device probe runs in a bounded child: a chip belongs to one
    # process at a time, so `pio status` must neither take it nor keep it,
    # and beside a live `pio deploy` on a one-chip host the child cannot
    # have it either — it fails or hangs, and the line below says so.
    import subprocess

    pkg_root = os.path.dirname(os.path.dirname(predictionio_tpu.__file__))
    probe_env = {
        **os.environ,
        "PYTHONPATH": pkg_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
    }
    try:
        probe = subprocess.run(
            [
                sys.executable,
                "-c",
                "import jax; d = jax.devices(); print('PIO-JAX', "
                "jax.__version__, len(d), d[0].platform, d[0].device_kind)",
            ],
            capture_output=True,
            timeout=60,
            text=True,
            env=probe_env,
        )
        # libtpu logs around the probe line: find OUR marker instead of
        # assuming clean stdout
        marker = next(
            (
                ln.split(None, 4)
                for ln in probe.stdout.splitlines()
                if ln.startswith("PIO-JAX ")
            ),
            None,
        )
        if probe.returncode == 0 and marker and len(marker) >= 3:
            where = f" ({', '.join(marker[3:])})" if len(marker) > 3 else ""
            print(f"  jax {marker[1]}; devices: {marker[2]}{where}")
        else:
            err = probe.stderr.strip().splitlines()
            print(f"  jax devices unavailable: {err[-1] if err else 'unknown'}")
    except subprocess.TimeoutExpired:
        print(
            "  jax devices unavailable: device init timed out after 60s "
            "(is another process holding the chip?)"
        )
    except Exception as exc:  # noqa: BLE001 - status must never crash here
        print(f"  jax devices unavailable: {exc}")
    print("(sleeping)   <- your engine is ready to train")
    return 0


def _parse_bytes(text: str) -> int:
    """'16e9', '16000000000', '16GB', '16GiB' -> bytes."""
    t = text.strip().lower()
    for suffix, mult in (
        ("gib", 1 << 30), ("mib", 1 << 20), ("kib", 1 << 10),
        ("gb", 10**9), ("mb", 10**6), ("kb", 10**3), ("b", 1),
    ):
        if t.endswith(suffix):
            return int(float(t[: -len(suffix)]) * mult)
    return int(float(t))


def _doctor_roofline(args) -> int:
    """``pio doctor --roofline``: the device-free roofline — lower and
    compile every registered jit bucket family, read XLA's own
    ``cost_analysis()`` flops/bytes into arithmetic intensity and a
    per-model device cost per 1k queries (obs/costmodel). Runs on the
    CPU backend; exits nonzero only when NO family produced numbers."""
    from predictionio_tpu.obs import costmodel

    families = (
        [f.strip() for f in args.families.split(",") if f.strip()]
        if getattr(args, "families", None)
        else None
    )
    try:
        report = costmodel.analyze(
            families=families,
            device=args.device or costmodel.DEFAULT_DEVICE,
        )
    except ValueError as exc:
        return _die(str(exc))
    print(json.dumps(report, indent=2, sort_keys=True))
    if not report["families"]:
        return _die("no bucket family produced cost numbers", code=1)
    return 0


def cmd_doctor(args) -> int:
    """Preflight diagnostics. ``--capacity USERS ITEMS K`` runs the HBM
    capacity planner (obs/xray.estimate_factors): will this ALS train fit
    per-device HBM? ``--ann "clusters,nprobe"`` prices a serving-side ANN
    index for the same corpus next to the factor tables (the budget check
    then gates the sum). Exits nonzero when the estimate exceeds
    ``--hbm-bytes`` — ROADMAP item 1's memory target as a gate instead of
    an OOM. Without ``--capacity``: device inventory + live memory + any
    ANN indexes pinned in the registry."""
    from predictionio_tpu.obs import xray

    if getattr(args, "roofline", False):
        return _doctor_roofline(args)
    if getattr(args, "ann", None) and not args.capacity:
        return _die("--ann needs --capacity USERS ITEMS K (ITEMS and K size the index)")
    if args.capacity:
        users, items, k = (int(v) for v in args.capacity)
        est = xray.estimate_factors(
            users,
            items,
            k,
            dtype=args.dtype,
            mesh=args.mesh or None,
            nnz=args.nnz,
            gather_dtype=args.gather_dtype,
        )
        budget = _parse_bytes(args.hbm_bytes) if args.hbm_bytes else None
        need = est.per_device_bytes
        ann_est = None
        if getattr(args, "ann", None):
            try:
                clusters_s, _, nprobe_s = args.ann.partition(",")
                clusters, nprobe = int(clusters_s or 0), int(nprobe_s or 0)
            except ValueError:
                return _die(
                    f"--ann expects 'clusters,nprobe' (0 = auto), got {args.ann!r}"
                )
            ann_est = xray.estimate_ann(
                items,
                k,
                clusters,
                nprobe,
                quantize_int8=bool(getattr(args, "ann_int8", False)),
            )
            need += ann_est["perDeviceBytes"]
        out = {
            "capacity": est.to_json_dict(),
            "ann": ann_est,
            "perDeviceBytesTotal": need,
            "hbmBudgetBytes": budget,
            "fits": (need <= budget) if budget is not None else None,
        }
        print(json.dumps(out, indent=2))
        if budget is not None:
            gb = need / 1e9
            if need > budget:
                print(
                    f"EXCEEDS BUDGET: {gb:.2f} GB/device needed vs "
                    f"{budget / 1e9:.2f} GB budget — shard wider (--mesh), "
                    f"lower k, bf16 the tables"
                    + (
                        ", or --ann-int8 / fewer clusters for the index"
                        if ann_est
                        else ""
                    ),
                    file=sys.stderr,
                )
                return 1
            print(
                f"fits: {gb:.2f} GB/device of {budget / 1e9:.2f} GB budget "
                f"({100.0 * need / budget:.1f}%)"
            )
        return 0
    # inventory mode: what does this host actually have
    try:
        import jax

        devices = jax.local_devices()
        print(f"backend: {jax.default_backend()}  devices: {len(devices)}")
        per = xray.live_bytes_per_device()
        for d in devices:
            stats = getattr(d, "memory_stats", lambda: None)() or {}
            live = per.get(str(d), 0)
            line = f"  {d}  live {live} B"
            if stats:
                line += (
                    f"  in_use {stats.get('bytes_in_use', 0)}"
                    f"  peak {stats.get('peak_bytes_in_use', 0)}"
                    f"  limit {stats.get('bytes_limit', 0)}"
                )
            print(line)
    except Exception as exc:  # noqa: BLE001 - doctor reports, never crashes
        print(f"devices unavailable: {exc}")
    _doctor_ann_inventory(getattr(args, "registry_dir", None))
    return 0


def _doctor_ann_inventory(registry_dir: str | None) -> None:
    """List every ANN index pinned on a registry-stable version — the
    'what retrieval indexes are live' half of the inventory."""
    import os as _os

    registry_dir = registry_dir or _os.environ.get("PIO_REGISTRY_DIR")
    if not registry_dir or not _os.path.isdir(registry_dir):
        return
    try:
        from predictionio_tpu.registry import ArtifactStore

        store = ArtifactStore(registry_dir)
        lines = []
        for key in store.engines():
            state = store.state_by_key(key)
            if not state.stable:
                continue
            versions = {m.version: m for m in store.versions_by_key(key)}
            manifest = versions.get(state.stable)
            if manifest is None or not manifest.ann_index:
                continue
            a = manifest.ann_index
            lines.append(
                f"  {key} {state.stable}: {a.get('items', '?')} items, "
                f"{a.get('clusters', '?')} clusters x cap "
                f"{a.get('bucketCap', '?')}, nprobe {a.get('nprobe', '?')}, "
                f"{a.get('hbmBytes', 0)} B"
                + (" (int8)" if a.get("quantized") else "")
            )
        if lines:
            print("ann indexes (registry-pinned stable):")
            for line in lines:
                print(line)
    except Exception as exc:  # noqa: BLE001 - doctor reports, never crashes
        print(f"ann inventory unavailable: {exc}")


def cmd_import(args) -> int:
    from predictionio_tpu.tools.import_export import import_events

    try:
        n = import_events(args.input, args.app_name, args.channel)
    except (OSError, ValueError) as exc:
        # surface the underlying parse/storage error (file:line: cause), not
        # a bare nonzero exit — operators need to know WHICH line was bad
        return _die(f"import failed: {exc}")
    print(f"Imported {n} events.")
    return 0


def cmd_export(args) -> int:
    from predictionio_tpu.tools.import_export import export_events

    n = export_events(args.output, args.app_name, args.channel, format=args.format)
    print(f"Exported {n} events.")
    return 0


# ---------------------------------------------------------------------------
# model registry (docs/model_registry.md)
# ---------------------------------------------------------------------------


def _models_store(args):
    from predictionio_tpu.registry import ArtifactStore

    return ArtifactStore(getattr(args, "registry_dir", None) or None)


def _models_engine_id(args) -> str:
    if getattr(args, "engine_id", None):
        return args.engine_id
    from predictionio_tpu.workflow.engine_loader import load_manifest

    return load_manifest(args.engine_dir, args.variant).engine_id


def _http_json(url: str, method: str = "GET", payload=None, timeout: float = 10.0):
    import urllib.error
    import urllib.request

    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data, method=method, headers={"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return json.loads(resp.read().decode())
    except urllib.error.HTTPError as exc:
        body = exc.read().decode(errors="replace")
        try:
            message = json.loads(body).get("message", body)
        except ValueError:
            message = body
        raise RuntimeError(f"{method} {url} -> {exc.code}: {message}") from exc


def cmd_models_list(args) -> int:
    store = _models_store(args)
    engine_id = _models_engine_id(args)
    state = store.get_state(engine_id)
    versions = store.list_versions(engine_id)
    if not versions:
        print(
            f"No versions in registry {store.base_dir} for engine "
            f"{engine_id} (key {store.engine_key(engine_id)}). "
            "Train with PIO_REGISTRY_DIR set (or pio train --registry-dir)."
        )
        return 0
    print(f"Registry: {store.base_dir} (engine key {store.engine_key(engine_id)})")
    print(f"{'Version':<10} | {'Role':<10} | {'Created':<26} | {'Bytes':>9} | Instance")
    for m in versions:
        role = ""
        if m.version == state.stable:
            role = "stable"
        elif m.version == state.candidate:
            role = f"candidate ({state.mode} {state.fraction:g})"
        created = (m.created_at or "")[:26]
        print(f"{m.version:<10} | {role:<10} | {created:<26} | {m.blob_size:>9} | {m.instance_id}")
    return 0


def cmd_models_show(args) -> int:
    if args.url:
        data = _http_json(f"{args.url}/models")
        if not args.version:
            print(json.dumps(data, indent=2))
            return 0
        # a positional version narrows to THAT version (and errors when
        # the server doesn't know it) instead of dumping unrelated state
        out = {"version": args.version}
        for role in ("stable", "candidate"):
            lane = data.get(role)
            if lane and lane.get("version") == args.version:
                out["role"] = role
                out["live"] = lane
        registry_row = next(
            (
                v
                for v in (data.get("registry") or {}).get("versions", ())
                if v.get("version") == args.version
            ),
            None,
        )
        if registry_row is not None:
            out["registry"] = registry_row
        if "live" not in out and registry_row is None:
            return _die(
                f"version {args.version} is not known to the server at "
                f"{args.url}"
            )
        print(json.dumps(out, indent=2))
        return 0
    store = _models_store(args)
    engine_id = _models_engine_id(args)
    state = store.get_state(engine_id)
    version = args.version or state.stable
    if not version:
        return _die("no version given and no stable recorded; see `pio models list`")
    manifest = store.get_manifest(engine_id, version)
    if manifest is None:
        return _die(f"unknown version {version}; see `pio models list`")
    print(
        json.dumps(
            {"manifest": manifest.to_json_dict(), "rollout": state.to_json_dict()},
            indent=2,
        )
    )
    return 0


def cmd_models_promote(args) -> int:
    if args.url:
        # an explicit version is sent as a guard: the server refuses (409)
        # if it isn't the staged candidate, instead of promoting whatever
        # happens to be staged
        payload = {"version": args.version} if args.version else {}
        out = _http_json(f"{args.url}/models/promote", method="POST", payload=payload)
        print(f"Promoted {out.get('version')} (instance {out.get('instanceId')}).")
        return 0
    store = _models_store(args)
    engine_id = _models_engine_id(args)
    state = store.promote(engine_id, args.version or None)
    print(f"Promoted {state.stable} to stable (previous: {state.previous_stable or '-'}).")
    return 0


def cmd_models_rollback(args) -> int:
    if args.url:
        out = _http_json(f"{args.url}/models/rollback", method="POST", payload={})
        print(f"Rolled back candidate {out.get('version')}.")
        return 0
    store = _models_store(args)
    engine_id = _models_engine_id(args)
    state = store.rollback(engine_id, reason="manual (cli)")
    print(f"Rolled back; stable is {state.stable or '-'}.")
    return 0


def cmd_models_stage(args) -> int:
    """Stage a candidate on a RUNNING server (sticky canary or shadow)."""
    out = _http_json(
        f"{args.url}/models/candidate",
        method="POST",
        payload={
            "version": args.version,
            "mode": args.mode,
            "fraction": args.fraction,
        },
    )
    print(
        f"Staged {out.get('version')} as {out.get('mode')} candidate "
        f"(fraction {out.get('fraction')})."
    )
    return 0


def _profile_delta_lines(label_a, label_b, pa: dict, pb: dict) -> list[str]:
    """Human train-profile comparison: wall clock, device share, memory —
    "did this version get slower or bigger to train" at a glance."""

    def fmt_delta(va, vb, unit=""):
        if not isinstance(va, (int, float)) or not isinstance(vb, (int, float)):
            return f"{va} -> {vb}"
        pct = f" ({(vb - va) / va * 100.0:+.1f}%)" if va else ""
        return f"{va:g}{unit} -> {vb:g}{unit}{pct}"

    lines = [f"train_profile ({label_a} -> {label_b}):"]
    rows = (
        ("wall clock", "wallClockS", "s"),
        ("device time", "deviceS", "s"),
        ("steps", "steps", ""),
        ("rows/s", "rowsPerS", ""),
    )
    for title, key, unit in rows:
        va, vb = pa.get(key), pb.get(key)
        if va is not None or vb is not None:
            lines.append(f"  {title}: {fmt_delta(va, vb, unit)}")
    ma = (pa.get("memory") or {}).get("peakBytesPerDevice")
    mb = (pb.get("memory") or {}).get("peakBytesPerDevice")
    if ma is not None or mb is not None:
        lines.append(f"  peak bytes/device: {fmt_delta(ma, mb, ' B')}")
    return lines


def cmd_models_diff(args) -> int:
    store = _models_store(args)
    engine_id = _models_engine_id(args)
    a = store.get_manifest(engine_id, args.version_a)
    b = store.get_manifest(engine_id, args.version_b)
    if a is None or b is None:
        missing = args.version_a if a is None else args.version_b
        return _die(f"unknown version {missing}; see `pio models list`")
    da, db = a.to_json_dict(), b.to_json_dict()
    # the train profiles are compared as a wall/memory delta, not dumped
    # raw (a step timeline in a field diff is unreadable); strip the copy
    # embedded under data_span.stream for the same reason
    pa, pb = da.pop("train_profile", None) or {}, db.pop("train_profile", None) or {}
    for d in (da, db):
        stream = d.get("data_span", {}).get("stream")
        if isinstance(stream, dict):
            stream.pop("profile", None)
    same = True
    for key in sorted(set(da) | set(db)):
        va, vb = da.get(key), db.get(key)
        if va != vb:
            same = False
            print(f"{key}:")
            print(f"  - {args.version_a}: {va}")
            print(f"  + {args.version_b}: {vb}")
    if pa or pb:
        for line in _profile_delta_lines(args.version_a, args.version_b, pa, pb):
            print(line)
        if pa != pb:
            same = False
    if same:
        print(f"{args.version_a} and {args.version_b} are identical.")
    elif a.params_hash == b.params_hash:
        print("(same engine params; differs only in data/lineage)")
    return 0


# ---------------------------------------------------------------------------
# templates (ref commands/Template.scala — gallery replaced by bundled dirs)
# ---------------------------------------------------------------------------

BUNDLED_TEMPLATES = (
    "recommendation",
    "similarproduct",
    "classification",
    "ecommerce",
    "twotower",
    "sequential",
)


def cmd_template_list(args) -> int:
    base = os.path.dirname(
        os.path.abspath(sys.modules["predictionio_tpu"].__file__)
    )
    for name in BUNDLED_TEMPLATES:
        path = os.path.join(base, "models", name)
        marker = "" if os.path.isdir(path) else " (planned)"
        print(f"  {name}{marker}")
    return 0


def cmd_template_get(args) -> int:
    """Copy a bundled template's engine.json (+ optional scaffold) into a new
    engine dir the user can customize."""
    base = os.path.dirname(os.path.abspath(sys.modules["predictionio_tpu"].__file__))
    src = os.path.join(base, "models", args.name)
    if not os.path.isdir(src):
        return _die(f"unknown template {args.name}; see `template list`")
    dst = args.directory or args.name
    if os.path.exists(dst) and os.listdir(dst):
        return _die(f"directory {dst} exists and is not empty")
    os.makedirs(dst, exist_ok=True)
    shutil.copy(os.path.join(src, "engine.json"), os.path.join(dst, "engine.json"))
    with open(os.path.join(dst, "template.json"), "w") as f:
        json.dump({"pio": {"version": {"min": "0.1.0"}}}, f)
    print(f"Engine template {args.name} created at {dst}/")
    print("Edit engine.json (appName, algorithm params) and run `pio train`.")
    return 0


def cmd_run(args) -> int:
    """Run an arbitrary python main with the framework importable
    (ref `pio run` spark-submit of a custom main)."""
    import runpy

    sys.argv = [args.main] + (args.args or [])
    runpy.run_path(args.main, run_name="__main__")
    return 0


def cmd_upgrade(args) -> int:
    """Storage-format migration check (ref Console.scala 'upgrade' — the
    reference migrates 0.8.x HBase layouts; here every backend is verified
    and its content stamp reported so operators can confirm compatibility
    after a framework update)."""
    storage = _storage()
    errors = storage.verify_all_data_objects()
    if errors:
        for e in errors:
            print(f"[ERROR] {e}")
        return 1
    print("All storage repositories verified; data formats are current.")
    try:
        stamp = storage.get_p_events().store_identity()
        if stamp:
            print(f"Event store identity: {stamp}")
    except Exception:
        pass
    print("No migration necessary.")
    return 0


def cmd_lint(args) -> int:
    return run_lint(args)


def cmd_version(args) -> int:
    print(predictionio_tpu.__version__)
    return 0


def cmd_shell(args) -> int:
    from predictionio_tpu.tools.shell import run_shell

    run_shell()
    return 0


def _pidfile_dir() -> str:
    base = os.environ.get(
        "PIO_FS_BASEDIR", os.path.join(os.path.expanduser("~"), ".pio_store")
    )
    os.makedirs(base, exist_ok=True)
    return base


def cmd_start_all(args) -> int:
    """Start event server + admin server + dashboard as background processes
    (ref bin/pio-start-all)."""
    import subprocess

    pidfile = os.path.join(_pidfile_dir(), "pio-services.pid")
    if os.path.exists(pidfile):
        return _die(f"{pidfile} exists; run stop-all first")
    specs = [
        ("eventserver", ["eventserver", "--port", str(args.eventserver_port)]),
        ("adminserver", ["adminserver", "--port", str(args.adminserver_port)]),
        ("dashboard", ["dashboard", "--port", str(args.dashboard_port)]),
    ]
    pids = []
    for name, argv in specs:
        proc = subprocess.Popen(
            [sys.executable, "-m", "predictionio_tpu.tools.cli", *argv],
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
            start_new_session=True,
        )
        pids.append(f"{name}:{proc.pid}")
        print(f"started {name} (pid {proc.pid})")
    with open(pidfile, "w") as f:
        f.write("\n".join(pids))
    return 0


def cmd_stop_all(args) -> int:
    """Stop services started by start-all (ref bin/pio-stop-all)."""
    import signal

    pidfile = os.path.join(_pidfile_dir(), "pio-services.pid")
    if not os.path.exists(pidfile):
        return _die("no pio-services.pid; nothing to stop")
    with open(pidfile) as f:
        entries = [l.strip() for l in f if l.strip()]
    for entry in entries:
        name, _, pid = entry.partition(":")
        try:
            os.kill(int(pid), signal.SIGTERM)
            print(f"stopped {name} (pid {pid})")
        except ProcessLookupError:
            print(f"{name} (pid {pid}) already gone")
    os.remove(pidfile)
    return 0


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pio",
        description="TPU-native PredictionIO-class ML framework console",
    )
    p.add_argument("-v", "--verbose", action="store_true")
    sub = p.add_subparsers(dest="command", required=True)

    sub.add_parser("version").set_defaults(fn=cmd_version)
    sub.add_parser(
        "upgrade", help="verify storage formats after a framework update"
    ).set_defaults(fn=cmd_upgrade)
    sub.add_parser("status").set_defaults(fn=cmd_status)
    sub.add_parser("shell").set_defaults(fn=cmd_shell)

    x = sub.add_parser("start-all")
    x.add_argument("--eventserver-port", type=int, default=7070)
    x.add_argument("--adminserver-port", type=int, default=7071)
    x.add_argument("--dashboard-port", type=int, default=9000)
    x.set_defaults(fn=cmd_start_all)
    sub.add_parser("stop-all").set_defaults(fn=cmd_stop_all)

    # app
    app = sub.add_parser("app").add_subparsers(dest="subcommand", required=True)
    x = app.add_parser("new")
    x.add_argument("name")
    x.add_argument("--id", type=int, default=0)
    x.add_argument("--description")
    x.add_argument("--access-key", default="")
    x.set_defaults(fn=cmd_app_new)
    app.add_parser("list").set_defaults(fn=cmd_app_list)
    x = app.add_parser("show")
    x.add_argument("name")
    x.set_defaults(fn=cmd_app_show)
    x = app.add_parser("delete")
    x.add_argument("name")
    x.add_argument("-f", "--force", action="store_true")
    x.set_defaults(fn=cmd_app_delete)
    x = app.add_parser("data-delete")
    x.add_argument("name")
    x.add_argument("--channel")
    x.add_argument("-f", "--force", action="store_true")
    x.set_defaults(fn=cmd_app_data_delete)
    x = app.add_parser("channel-new")
    x.add_argument("app_name")
    x.add_argument("channel")
    x.set_defaults(fn=cmd_channel_new)
    x = app.add_parser("channel-delete")
    x.add_argument("app_name")
    x.add_argument("channel")
    x.add_argument("-f", "--force", action="store_true")
    x.set_defaults(fn=cmd_channel_delete)

    # accesskey
    ak = sub.add_parser("accesskey").add_subparsers(dest="subcommand", required=True)
    x = ak.add_parser("new")
    x.add_argument("app_name")
    x.add_argument("--key", default="")
    x.add_argument("--event", action="append")
    x.set_defaults(fn=cmd_accesskey_new)
    x = ak.add_parser("list")
    x.add_argument("app_name", nargs="?")
    x.set_defaults(fn=cmd_accesskey_list)
    x = ak.add_parser("delete")
    x.add_argument("key")
    x.set_defaults(fn=cmd_accesskey_delete)

    # engine lifecycle
    def engine_args(x):
        x.add_argument("--engine-dir", default=".")
        x.add_argument("--variant")

    def stream_args(x, require_app: bool):
        """Speed-layer flags shared by `pio stream` and `pio train --follow`."""
        x.add_argument(
            "--app-name",
            required=require_app,
            default=None if require_app else "",
            help="app whose event store to tail",
        )
        x.add_argument("--channel", default="", help="channel name (optional)")
        x.add_argument(
            "--interval", type=float, default=5.0, help="seconds between cycles"
        )
        x.add_argument(
            "--batch-limit",
            type=int,
            default=500,
            help="events per drain micro-batch (the backpressure unit)",
        )
        x.add_argument(
            "--safety-lag",
            type=float,
            default=0.5,
            help="seconds the drain stays behind the wall clock, so a "
            "concurrently committing insert cannot land behind the "
            "cursor and be skipped (0 disables)",
        )
        x.add_argument(
            "--publish-min-events",
            type=int,
            default=1,
            help="publish a candidate once this many new events folded in",
        )
        x.add_argument(
            "--mode",
            choices=("canary", "shadow"),
            default="canary",
            help="rollout mode published candidates are staged with",
        )
        x.add_argument(
            "--fraction", type=float, default=0.1, help="canary fraction"
        )
        x.add_argument(
            "--from-beginning",
            action="store_true",
            help="a fresh cursor replays the whole store instead of "
            "starting at the head",
        )
        x.add_argument(
            "--cursor-dir", help="cursor state dir (default: $PIO_STREAM_DIR)"
        )
        x.add_argument(
            "--cycles",
            type=int,
            default=None,
            help="stop after N cycles (default: run until interrupted)",
        )
        x.add_argument(
            "--notify-url",
            help="POST staged candidates to this query server's "
            "/models/candidate instead of writing registry rollout state "
            "directly",
        )
        x.add_argument(
            "--metrics-port",
            type=int,
            default=0,
            help="serve the pipeline's pio_stream_* metrics at "
            "http://0.0.0.0:PORT/metrics (for `pio top`); 0 disables",
        )
        x.add_argument(
            "--obs-dir",
            help="observability plane dir: drift-guard breaches land on "
            "its telemetry ring (kind=drift — the lifecycle controller's "
            "retune sensor) and snapshot rate-limited incident bundles",
        )

    x = sub.add_parser("build")
    engine_args(x)
    x.set_defaults(fn=cmd_build)

    x = sub.add_parser("unregister")
    engine_args(x)
    x.set_defaults(fn=cmd_unregister)

    x = sub.add_parser("train")
    engine_args(x)
    x.add_argument("--batch", default="")
    x.add_argument("--skip-sanity-check", action="store_true")
    x.add_argument("--stop-after-read", action="store_true")
    x.add_argument("--stop-after-prepare", action="store_true")
    x.add_argument(
        "--num-hosts",
        type=int,
        default=1,
        help="launch N local worker processes joined via jax.distributed "
        "(ref Runner.runOnSpark)",
    )
    x.add_argument(
        "--hosts",
        default="",
        help="comma-separated remote hosts; one ssh-launched worker each",
    )
    x.add_argument(
        "--registry-dir",
        help="publish the trained model into this artifact registry "
        "(default: $PIO_REGISTRY_DIR when set, else no registry publish)",
    )
    x.add_argument(
        "--keep-versions",
        type=int,
        default=5,
        help="registry GC: keep this many versions (stable/candidate are "
        "always kept)",
    )
    x.add_argument(
        "--follow",
        action="store_true",
        help="after training, keep tailing the event store and publish "
        "registry candidates continuously (speed layer; requires "
        "--app-name — see docs/streaming.md)",
    )
    stream_args(x, require_app=False)
    x.set_defaults(fn=cmd_train)

    x = sub.add_parser(
        "stream",
        help="speed layer: tail the event store, fold events into the "
        "stable model, publish registry candidates (docs/streaming.md)",
    )
    engine_args(x)
    x.add_argument(
        "--registry-dir",
        help="artifact registry holding the stable model and receiving "
        "candidates (default: $PIO_REGISTRY_DIR)",
    )
    stream_args(x, require_app=True)
    x.set_defaults(fn=cmd_stream)

    x = sub.add_parser(
        "eval",
        help="hyperparameter search: parallel, resumable fold×params "
        "evaluation grid; winner publishes through the registry "
        "(docs/evaluation.md)",
    )
    x.add_argument("evaluation", help="dotted path to an Evaluation")
    x.add_argument("engine_params_generator", nargs="?")
    x.add_argument("--batch", default="")
    x.add_argument(
        "--workers",
        type=int,
        default=0,
        help="parallel cell worker processes (0 = score cells in-process; "
        "workers rebuild the evaluation from its dotted path)",
    )
    x.add_argument(
        "--folds",
        type=int,
        default=None,
        help="expected fold count (default: discovered from the data "
        "source's read_eval)",
    )
    x.add_argument(
        "--workdir",
        default=None,
        help="grid working directory holding the trial ledger; a stable "
        "--workdir is what makes --resume possible (default: a fresh "
        "temp dir per run)",
    )
    x.add_argument(
        "--resume",
        action="store_true",
        help="resume a killed run from --workdir's ledger: finished "
        "cells are never retrained",
    )
    x.add_argument(
        "--batch-size",
        type=int,
        default=512,
        help="mega-batch size for held-out scoring through "
        "Engine.dispatch_batch (default 512)",
    )
    x.add_argument(
        "--engine-dir",
        default=None,
        help="engine project directory — supplies the registry identity "
        "the winner publishes under (with --variant)",
    )
    x.add_argument("--variant", help="engine.json variant (with --engine-dir)")
    x.add_argument(
        "--registry-dir",
        help="artifact registry receiving the winning refit as a "
        "candidate (default: $PIO_REGISTRY_DIR)",
    )
    x.add_argument(
        "--publish",
        action="store_true",
        help="force winner publication (default: publish automatically "
        "when --engine-dir and a registry dir are both available)",
    )
    x.add_argument(
        "--no-publish",
        action="store_true",
        help="never publish the winner (scores and ledger only)",
    )
    x.add_argument(
        "--stage-mode",
        choices=["canary", "shadow"],
        default="canary",
        help="rollout mode the winner is staged under (default canary)",
    )
    x.add_argument(
        "--stage-fraction",
        type=float,
        default=0.1,
        help="canary fraction for the staged winner (default 0.1)",
    )
    x.add_argument(
        "--status-file",
        default=None,
        help="write throttled atomic progress snapshots here; "
        "`pio top --eval PATH` renders them live",
    )
    x.add_argument(
        "--nice",
        type=int,
        default=0,
        help="re-nice grid worker processes by this amount (background "
        "retunes yield the CPU to serving; 0 = inherit)",
    )
    x.add_argument(
        "--worker-class",
        choices=["", "cpu-fallback"],
        default="",
        help="fleet replica class the workers run as: cpu-fallback pins "
        "workers to JAX_PLATFORMS=cpu and bounds --workers so the grid "
        "never grabs the accelerator from serving",
    )
    x.add_argument(
        "--out", default=None, help="write the grid report JSON here"
    )
    x.set_defaults(fn=cmd_eval)

    x = sub.add_parser("deploy")
    engine_args(x)
    x.add_argument("--ip", default="0.0.0.0")
    x.add_argument("--port", type=int, default=8000)
    x.add_argument("--accesskey")
    x.add_argument("--feedback", action="store_true")
    x.add_argument("--event-server-url")
    x.add_argument("--feedback-access-key")
    x.add_argument("--ssl-certfile")
    x.add_argument("--ssl-keyfile")
    x.add_argument("--log-url", help="POST serving errors to this collector URL")
    x.add_argument("--log-prefix", help="prefix prepended to each remote log body")
    x.add_argument(
        "--request-timeout",
        type=float,
        default=10.0,
        help="per-request deadline in seconds for /queries.json "
        "(503 instead of hanging; <= 0 disables)",
    )
    x.add_argument(
        "--queue-high-water",
        type=int,
        default=256,
        help="shed load with 503 + Retry-After when this many queries are "
        "already queued (0 = unbounded)",
    )
    x.add_argument(
        "--breaker-threshold",
        type=int,
        default=3,
        help="consecutive deadline-blown device calls that open the "
        "dispatch circuit breaker",
    )
    x.add_argument(
        "--breaker-recovery",
        type=float,
        default=5.0,
        help="seconds an open dispatch breaker waits before probing again",
    )
    x.add_argument(
        "--registry-dir",
        help="serve the model registry's pinned stable version and expose "
        "the /models rollout surface (default: registry disabled)",
    )
    x.add_argument(
        "--sticky-key",
        default="user",
        help="query payload field whose hash pins a user to one model "
        "during a canary",
    )
    x.add_argument(
        "--candidate-breaker-threshold",
        type=int,
        default=3,
        help="consecutive candidate-lane failures that force an instant "
        "rollback",
    )
    x.add_argument(
        "--bake-window",
        type=float,
        default=60.0,
        help="seconds a candidate must bake before the promotion gates run",
    )
    x.add_argument(
        "--bake-min-requests",
        type=int,
        default=20,
        help="minimum canary queries (shadow: scored queries) before any "
        "promote/rollback verdict",
    )
    x.add_argument(
        "--no-auto-promote",
        action="store_true",
        help="gates report 'ready' instead of promoting; an operator "
        "promotes via `pio models promote --url ...`",
    )
    x.add_argument(
        "--bandit",
        choices=("epsilon", "thompson"),
        help="steer staged candidates with a contextual-bandit policy: "
        "arms are the stable/candidate lanes, reward is feedback events "
        "matched to served impressions by trace id, and the bake gate "
        "doubles as reward accounting (docs/bandit.md)",
    )
    x.add_argument(
        "--bandit-epsilon",
        type=float,
        default=0.1,
        help="explore share for the epsilon policy (doubles as the "
        "cold-start fraction for thompson)",
    )
    x.add_argument(
        "--bandit-min-pulls",
        type=int,
        default=20,
        help="per-arm impression floor before the reward posterior may "
        "promote or retire",
    )
    x.add_argument(
        "--bandit-app-name",
        help="app whose event stream carries the reward events (required "
        "with --bandit)",
    )
    x.add_argument(
        "--bandit-reward-event",
        help="comma-separated event names credited as rewards "
        "(default: reward)",
    )
    x.add_argument(
        "--result-cache-size",
        type=int,
        default=1024,
        help="version-keyed result cache entries (0 disables); hits "
        "answer before micro-batch admission",
    )
    x.add_argument(
        "--result-cache-ttl",
        type=float,
        default=10.0,
        help="result-cache entry TTL seconds — the staleness bound for "
        "serving components reading live state outside the model",
    )
    x.add_argument(
        "--fleet",
        type=int,
        default=0,
        metavar="N",
        help="deploy N supervised QueryServer worker processes (ports "
        "PORT+1..PORT+N) behind a gateway on PORT: least-loaded routing, "
        "/healthz ejection, crash restart, one-retry failover, federated "
        "/metrics (docs/fleet.md)",
    )
    x.add_argument(
        "--fleet-probe-interval",
        type=float,
        default=1.0,
        help="gateway /healthz probe cadence in seconds (bounds how fast "
        "a dead replica is ejected)",
    )
    x.add_argument(
        "--autoscale",
        action="store_true",
        help="size the fleet from the telemetry ring: scale out on "
        "fast-window SLO burn / sustained queue depth, scale in (graceful "
        "drain) on sustained idle; never resizes mid-bake; needs the "
        "flight recorder (--obs-dir) enabled (docs/fleet.md §Autoscaling)",
    )
    x.add_argument(
        "--fleet-min",
        type=int,
        default=None,
        metavar="N",
        help="autoscaler device-class floor (default 1)",
    )
    x.add_argument(
        "--fleet-max",
        type=int,
        default=None,
        metavar="N",
        help="autoscaler device-class ceiling (default 2x the --fleet "
        "boot size); wanting capacity past the whole envelope snapshots "
        "an autoscaler-saturated incident bundle",
    )
    x.add_argument(
        "--cpu-fallback-max",
        type=int,
        default=None,
        metavar="N",
        help="max cheap cpu-fallback replicas (JAX_PLATFORMS=cpu workers) "
        "added once the device envelope is exhausted; the gateway routes "
        "them overflow-first so spikes degrade to slower answers instead "
        "of sheds (default 0 = disabled)",
    )
    x.add_argument(
        "--autoscale-interval",
        type=float,
        default=None,
        help="autoscaler control-loop cadence in seconds (default 5)",
    )
    x.add_argument(
        "--hosts",
        default=None,
        metavar="SPEC",
        help="multi-host worker placement: comma list of "
        "[driver@]host:slots entries (drivers: local, ssh, container; "
        "e.g. 'local:4,ssh@gpu-2:8'); workers spread across the "
        "inventory and a dead host's capacity respawns on survivors "
        "(docs/fleet.md §Multi-host)",
    )
    x.add_argument(
        "--gateways",
        type=int,
        default=1,
        metavar="N",
        help="run N shared-nothing gateways on ports PORT..PORT+N-1 over "
        "the same replica set (put any TCP balancer in front); each peer "
        "serves its own /metrics, /traces/recent and /slo fan in across "
        "peers (default 1)",
    )
    x.add_argument(
        "--obs-dir",
        default="pio_obs",
        help="fleet flight-recorder directory: worker log tails, the "
        "durable telemetry ring (`pio top --history`), and incident "
        "bundles (`pio incidents list`); '' disables "
        "(docs/observability.md)",
    )
    x.add_argument(
        "--registry-sync-interval",
        type=float,
        default=None,
        help="poll the registry's state generation on this cadence and "
        "adopt stage/promote/rollback made by other processes (fleet "
        "workers default to 1.0; 0 disables; needs --registry-dir)",
    )
    x.add_argument(
        "--lifecycle",
        default=None,
        metavar="EVALUATION",
        help="run the self-driving lifecycle controller in the fleet "
        "parent: drift on the telemetry ring (or --lifecycle-cadence) "
        "triggers a background retune of this dotted Evaluation on "
        "nice'd cpu-fallback grid workers, the winner bakes through the "
        "rollout gates, promotes auto-warm the result cache; needs "
        "--registry-dir and --obs-dir (docs/lifecycle.md)",
    )
    x.add_argument(
        "--lifecycle-cadence",
        type=float,
        default=None,
        help="also retune every N seconds (default 0 = drift/manual only)",
    )
    x.add_argument(
        "--lifecycle-cooldown",
        type=float,
        default=None,
        help="seconds after an episode before auto triggers re-arm "
        "(default 600)",
    )
    x.add_argument(
        "--lifecycle-workers",
        type=int,
        default=None,
        help="grid worker processes for lifecycle retunes (default 2; "
        "always the cpu-fallback class)",
    )
    x.add_argument(
        "--lifecycle-nice",
        type=int,
        default=None,
        help="re-nice lifecycle grid workers (default 10)",
    )
    x.add_argument(
        "--lifecycle-warm-limit",
        type=int,
        default=None,
        help="max queries replayed per post-promote cache warm "
        "(default 256; 0 disables)",
    )
    x.add_argument(
        "--lifecycle-app",
        default=None,
        metavar="APP_NAME",
        help="app whose event store supplies warm-up queries (distinct "
        "users); unset disables cache warming",
    )
    x.add_argument(
        "--drain-grace",
        type=float,
        default=15.0,
        help="seconds a SIGTERM'd server waits for in-flight queries to "
        "answer after closing its listener (graceful drain)",
    )
    x.set_defaults(fn=cmd_deploy)

    x = sub.add_parser("undeploy")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=8000)
    x.add_argument("--ssl", action="store_true", help="server was deployed with TLS")
    x.set_defaults(fn=cmd_undeploy)

    x = sub.add_parser(
        "batchpredict",
        help="offline mega-batch prediction through the fused device "
        "kernels (docs/batch_predict.md)",
    )
    engine_args(x)
    x.add_argument(
        "--input",
        default=None,
        help="multi-line JSON query file, streamed (default "
        "batchpredict-input.json; mutually exclusive with --from-events)",
    )
    x.add_argument(
        "--output",
        default="batchpredict-output.json",
        help="line-aligned JSONL predictions, written atomically "
        "(tmp+rename); '' disables the file sink",
    )
    x.add_argument(
        "--from-events",
        action="store_true",
        help="stream DISTINCT users straight off the app's event store "
        "(find_after order, bounded pages) instead of a query file",
    )
    x.add_argument(
        "--app-name",
        default="",
        help="app for --from-events/--to-events (default: the engine "
        "variant's datasource appName)",
    )
    x.add_argument("--channel", default="", help="channel name (optional)")
    x.add_argument(
        "--query-num",
        type=int,
        default=10,
        help="top-k per synthesized --from-events query (default 10)",
    )
    x.add_argument(
        "--batch",
        type=int,
        default=512,
        help="mega-batch size; pow2 keeps the compiled-bucket universe "
        "at one program (default 512)",
    )
    x.add_argument(
        "--to-events",
        action="store_true",
        help="also write scored results back into the event store "
        "(batchpredict.result events, retry/breaker-protected)",
    )
    x.add_argument(
        "--limit",
        type=int,
        default=0,
        help="cap the number of queries processed (0 = all)",
    )
    x.add_argument(
        "--status-file",
        default=None,
        help="write throttled atomic progress snapshots here; "
        "`pio top --batchpredict PATH` renders them live",
    )
    x.set_defaults(fn=cmd_batchpredict)

    # servers
    x = sub.add_parser("eventserver")
    x.add_argument("--ip", default="0.0.0.0")
    x.add_argument("--port", type=int, default=7070)
    x.add_argument("--stats", action="store_true")
    x.add_argument("--ssl-certfile")
    x.add_argument("--ssl-keyfile")
    x.add_argument(
        "--storage-retries",
        type=int,
        default=3,
        help="attempts per storage call for transient failures (<= 1 disables)",
    )
    x.add_argument(
        "--breaker-threshold",
        type=int,
        default=5,
        help="consecutive storage failures that open the circuit breaker "
        "(requests then answer 503 'storage unavailable')",
    )
    x.add_argument(
        "--breaker-recovery",
        type=float,
        default=5.0,
        help="seconds an open storage breaker waits before probing again",
    )
    x.set_defaults(fn=cmd_eventserver)

    x = sub.add_parser("adminserver")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=7071)
    x.add_argument(
        "--registry-dir",
        help="model registry base dir served at /cmd/models "
        "(default: $PIO_REGISTRY_DIR, else $PIO_FS_BASEDIR/registry)",
    )
    x.set_defaults(fn=cmd_adminserver)

    x = sub.add_parser("dashboard")
    x.add_argument("--ip", default="127.0.0.1")
    x.add_argument("--port", type=int, default=9000)
    x.add_argument(
        "--metrics-url",
        action="append",
        help="a server base URL whose /metrics the dashboard shows as "
        "breaker/queue/latency panels (repeatable; e.g. "
        "http://localhost:8000)",
    )
    x.set_defaults(fn=cmd_dashboard)

    x = sub.add_parser(
        "top",
        help="live terminal summary of a running server's /metrics "
        "(qps, p95, shed rate, breaker states, recompile count)",
    )
    x.add_argument(
        "--url",
        default=_TOP_DEFAULT_URL,
        help="server base URL (QueryServer or EventServer)",
    )
    x.add_argument("--interval", type=float, default=2.0)
    x.add_argument(
        "-n",
        "--iterations",
        type=int,
        default=None,
        help="stop after N refreshes (default: run until Ctrl-C)",
    )
    x.add_argument(
        "--once",
        action="store_true",
        help="print one snapshot and exit (rates need two samples and "
        "show as '-')",
    )
    x.add_argument(
        "--json",
        action="store_true",
        help="machine-readable output: one JSON snapshot per line instead "
        "of the terminal screen (for CI and fleet tooling)",
    )
    x.add_argument(
        "--metrics-url",
        action="append",
        help="poll this endpoint (repeatable); an explicitly-set --url "
        "(or --fleet gateway) is polled too, first in each refresh — an "
        "untouched default --url is not. Fleet dashboards scrape replicas "
        "directly alongside the gateway's federated view; with --json, "
        "one object per endpoint per refresh",
    )
    x.add_argument(
        "--fleet",
        action="store_true",
        help="fleet mode: point --url at a `pio deploy --fleet` gateway; "
        "the per-replica fleet line renders from its federated /metrics",
    )
    x.add_argument(
        "--history",
        action="store_true",
        help="render the fleet telemetry ring's queue-depth/burn/health "
        "series (one shot): from the gateway's /telemetry/window, or "
        "straight off the on-disk ring via --obs-dir when the gateway "
        "is down (the ring survives the process)",
    )
    x.add_argument(
        "--history-window",
        type=float,
        default=600.0,
        metavar="S",
        help="trailing seconds of telemetry to render (default 600)",
    )
    x.add_argument(
        "--obs-dir",
        default=None,
        help="read the telemetry ring from this fleet obs directory "
        "instead of over HTTP (pairs with --history)",
    )
    x.add_argument(
        "--batchpredict",
        default=None,
        metavar="STATUS_FILE",
        help="render the progress line of an offline `pio batchpredict` "
        "run from its --status-file (live while the run is active, "
        "final totals after)",
    )
    x.add_argument(
        "--eval",
        default=None,
        metavar="STATUS_FILE",
        help="render the live grid line of a `pio eval` run from its "
        "--status-file: cells done/total, running workers, best score "
        "so far, ETA",
    )
    x.add_argument(
        "--lifecycle",
        default=None,
        metavar="STATE_FILE",
        help="render the lifecycle controller's episode line from its "
        "durable state file (<state-dir>/lifecycle.json): state, "
        "trigger, grid progress, candidate baking, last outcome",
    )
    x.add_argument(
        "--hotspots",
        action="store_true",
        help="append the host-sampler hotspots block (top-of-stack "
        "frames per thread role + sampler overhead %%) from the "
        "server's /profile/stacks; an endpoint without the profiling "
        "plane degrades to one 'unreachable' line",
    )
    x.set_defaults(fn=cmd_top)

    inc = sub.add_parser(
        "incidents",
        help="inspect incident bundles captured by the fleet flight "
        "recorder (worker crash, breaker trip, SLO alert; "
        "docs/observability.md)",
    ).add_subparsers(dest="subcommand", required=True)
    x = inc.add_parser("list", help="bundles oldest first")
    x.add_argument(
        "--obs-dir",
        default="pio_obs",
        help="fleet observability directory (`pio deploy --fleet --obs-dir`)",
    )
    x.set_defaults(fn=cmd_incidents_list)
    x = inc.add_parser(
        "show", help="manifest, parts, and the stderr tail of one bundle"
    )
    x.add_argument("bundle", help="bundle id (unique prefix accepted)")
    x.add_argument("--obs-dir", default="pio_obs")
    x.add_argument("--json", action="store_true", help="full bundle as JSON")
    x.add_argument(
        "--tail-lines",
        type=int,
        default=20,
        help="stderr-tail lines to print (default 20)",
    )
    x.set_defaults(fn=cmd_incidents_show)
    x = inc.add_parser("export", help="copy one bundle somewhere shippable")
    x.add_argument("bundle", help="bundle id (unique prefix accepted)")
    x.add_argument("dest", help="destination directory")
    x.add_argument("--obs-dir", default="pio_obs")
    x.set_defaults(fn=cmd_incidents_export)

    lc = sub.add_parser(
        "lifecycle",
        help="the self-driving model lifecycle: drift → retune → bake → "
        "promote → warm, zero human commands (docs/lifecycle.md)",
    ).add_subparsers(dest="subcommand", required=True)

    def lifecycle_dir_args(x):
        x.add_argument(
            "--obs-dir",
            default="pio_obs",
            help="fleet observability directory (the controller's state "
            "lives under <obs-dir>/lifecycle by default)",
        )
        x.add_argument(
            "--state-dir",
            default=None,
            help="controller state directory override (default "
            "<obs-dir>/lifecycle)",
        )

    x = lc.add_parser(
        "run",
        help="run the controller against an already-deployed server "
        "(`pio deploy --fleet N --lifecycle` embeds the same loop)",
    )
    x.add_argument("evaluation", help="dotted path to the retune Evaluation")
    x.add_argument("--engine-dir", default=".")
    x.add_argument("--variant")
    x.add_argument(
        "--registry-dir",
        help="artifact registry the loop stages/promotes through "
        "(default: $PIO_REGISTRY_DIR)",
    )
    lifecycle_dir_args(x)
    x.add_argument(
        "--cadence",
        type=float,
        default=0.0,
        help="scheduled retune every N seconds (0 = drift/manual only)",
    )
    x.add_argument(
        "--drift-window",
        type=float,
        default=600.0,
        help="trailing seconds of ring drift records that count as a "
        "live signal (default 600)",
    )
    x.add_argument(
        "--min-drift-records",
        type=int,
        default=1,
        help="drift records inside the window needed to trigger "
        "(default 1 — each breach already suppressed a publish)",
    )
    x.add_argument(
        "--cooldown",
        type=float,
        default=600.0,
        help="seconds after an episode before drift/cadence can "
        "retrigger (manual `pio lifecycle trigger` bypasses it)",
    )
    x.add_argument(
        "--tune-timeout",
        type=float,
        default=7200.0,
        help="abandon a grid run older than this (its ledger still "
        "speeds up the next episode)",
    )
    x.add_argument(
        "--bake-timeout",
        type=float,
        default=3600.0,
        help="unstage a candidate no server resolves within this",
    )
    x.add_argument(
        "--tick-interval", type=float, default=2.0, help="control-loop cadence"
    )
    x.add_argument(
        "--workers",
        type=int,
        default=2,
        help="grid worker processes (cpu-fallback class: JAX_PLATFORMS "
        "pinned to cpu, count bounded)",
    )
    x.add_argument(
        "--nice",
        type=int,
        default=10,
        help="re-nice grid workers (background retunes yield to serving)",
    )
    x.add_argument("--folds", type=int, default=None)
    x.add_argument(
        "--workdir",
        default=None,
        help="grid workdir root, one run-NNNN per episode (default "
        "<state-dir>/grid); stable across restarts = crash resume",
    )
    x.add_argument(
        "--stage-mode", choices=["canary", "shadow"], default="canary"
    )
    x.add_argument("--stage-fraction", type=float, default=0.1)
    x.add_argument(
        "--serve-url",
        default=None,
        help="server/gateway base URL; promoted models warm their result "
        "cache by replaying queries here (with --app-name)",
    )
    x.add_argument(
        "--app-name",
        default=None,
        help="app whose event store supplies warm-up queries "
        "(distinct users, the batchpredict --from-events source)",
    )
    x.add_argument(
        "--warm-limit",
        type=int,
        default=256,
        help="max queries replayed per post-promote cache warm "
        "(0 disables warming)",
    )
    x.set_defaults(fn=cmd_lifecycle_run)

    x = lc.add_parser(
        "status", help="episode state from the controller's durable file"
    )
    lifecycle_dir_args(x)
    x.add_argument("--json", action="store_true")
    x.set_defaults(fn=cmd_lifecycle_status)

    x = lc.add_parser(
        "trigger",
        help="queue one manual retune (bypasses cooldown, never an "
        "in-flight episode or a live bake)",
    )
    lifecycle_dir_args(x)
    x.set_defaults(fn=cmd_lifecycle_trigger)

    x = lc.add_parser(
        "pause",
        help="stop automatic triggers (in-flight episodes finish; "
        "manual trigger still works)",
    )
    lifecycle_dir_args(x)
    x.set_defaults(fn=cmd_lifecycle_pause)

    x = lc.add_parser("resume", help="re-enable automatic triggers")
    lifecycle_dir_args(x)
    x.set_defaults(fn=cmd_lifecycle_pause)

    prof = sub.add_parser(
        "profile",
        help="the profiling plane: on-demand device captures against a "
        "live server, device-traced training, and content-addressed "
        "profile bundle inspection (docs/observability.md §Profiling "
        "plane)",
    ).add_subparsers(dest="subcommand", required=True)

    def profile_dir_arg(x):
        x.add_argument(
            "--profile-dir",
            default=None,
            help="profile bundle directory (default $PIO_PROFILE_DIR, "
            "else pio_obs/profiles — the server default)",
        )

    x = prof.add_parser(
        "serve",
        help="POST /profile/capture?ms= on a running server (or a fleet "
        "gateway, which fans out to one replica)",
    )
    x.add_argument("--url", default=_TOP_DEFAULT_URL)
    x.add_argument(
        "--ms",
        type=int,
        default=500,
        help="device-trace duration (clamped server-side to its max; "
        "0 = host-only bundle, no device trace)",
    )
    x.add_argument("--timeout", type=float, default=30.0)
    x.set_defaults(fn=cmd_profile_serve)
    x = prof.add_parser(
        "train",
        help="run `pio train ...` under the device tracer; the trace "
        "lands as a content-addressed bundle under --profile-dir",
    )
    profile_dir_arg(x)
    x.add_argument(
        "train_args",
        nargs=argparse.REMAINDER,
        help="arguments forwarded to `pio train` (prefix with -- )",
    )
    x.set_defaults(fn=cmd_profile_train)
    x = prof.add_parser("list", help="bundles oldest first")
    profile_dir_arg(x)
    x.set_defaults(fn=cmd_profile_list)
    x = prof.add_parser(
        "show", help="manifest, parts, and trace inventory of one bundle"
    )
    x.add_argument("bundle", help="bundle id (unique prefix accepted)")
    profile_dir_arg(x)
    x.add_argument("--json", action="store_true", help="full bundle as JSON")
    x.set_defaults(fn=cmd_profile_show)
    x = prof.add_parser("export", help="copy one bundle somewhere shippable")
    x.add_argument("bundle", help="bundle id (unique prefix accepted)")
    x.add_argument("dest", help="destination directory")
    profile_dir_arg(x)
    x.set_defaults(fn=cmd_profile_export)

    x = sub.add_parser(
        "doctor",
        help="preflight diagnostics: HBM capacity planning "
        "(--capacity USERS ITEMS K) and device/memory inventory",
    )
    x.add_argument(
        "--capacity",
        nargs=3,
        metavar=("USERS", "ITEMS", "K"),
        help="predict per-device bytes for an ALS train of this shape",
    )
    x.add_argument("--dtype", choices=["f32", "bf16"], default="f32")
    x.add_argument(
        "--gather-dtype",
        choices=["f32", "bf16"],
        default="f32",
        help="solver gather dtype (bf16 adds half-size table copies)",
    )
    x.add_argument(
        "--mesh",
        help="mesh axis sizes, e.g. data=8,model=2 (explicit sizes only)",
    )
    x.add_argument("--nnz", type=int, help="rating count (adds wire bytes)")
    x.add_argument(
        "--ann",
        metavar="CLUSTERS,NPROBE",
        help="price an ANN retrieval index (ITEMS items, dim K) next to "
        "the factor tables: 'clusters,nprobe' (0,0 = auto sizing); the "
        "budget check then covers factors + index (docs/ann.md)",
    )
    x.add_argument(
        "--ann-int8",
        action="store_true",
        help="price the int8-quantized index layout",
    )
    x.add_argument(
        "--hbm-bytes",
        help="per-device HBM budget (accepts 16e9 / 16GB / 16GiB); "
        "exit 1 when the estimate exceeds it",
    )
    x.add_argument(
        "--registry-dir",
        help="registry to inventory pinned ANN indexes from "
        "(default $PIO_REGISTRY_DIR)",
    )
    x.add_argument(
        "--roofline",
        action="store_true",
        help="device-free roofline: compile the registered jit bucket "
        "families and report cost_analysis flops/bytes, arithmetic "
        "intensity, and device cost per 1k queries",
    )
    x.add_argument(
        "--families",
        help="comma list of bucket families for --roofline "
        "(default: all of topk,ann,als,twotower)",
    )
    x.add_argument(
        "--device",
        default=None,
        help="device spec the roofline prices against "
        "(tpu-v4/tpu-v5e/tpu-v5p/cpu-host; default tpu-v5e)",
    )
    x.set_defaults(fn=cmd_doctor)

    # data
    x = sub.add_parser("import")
    x.add_argument("--appname", dest="app_name", required=True)
    x.add_argument("--input", required=True)
    x.add_argument("--channel")
    x.set_defaults(fn=cmd_import)

    x = sub.add_parser("export")
    x.add_argument("--appname", dest="app_name", required=True)
    x.add_argument("--output", required=True)
    x.add_argument("--channel")
    x.add_argument("--format", default="json", choices=["json", "parquet", "npz"])
    x.set_defaults(fn=cmd_export)

    # model registry
    mdl = sub.add_parser(
        "models",
        help="model registry: versioned artifacts, canary/shadow rollout, "
        "promote/rollback (docs/model_registry.md)",
    ).add_subparsers(dest="subcommand", required=True)

    def models_args(x):
        x.add_argument("--engine-dir", default=".")
        x.add_argument("--variant")
        x.add_argument(
            "--engine-id",
            help="registry engine id (skips resolving it from --engine-dir)",
        )
        x.add_argument(
            "--registry-dir",
            help="artifact registry base dir (default: $PIO_REGISTRY_DIR, "
            "else $PIO_FS_BASEDIR/registry)",
        )

    x = mdl.add_parser("list")
    models_args(x)
    x.set_defaults(fn=cmd_models_list)
    x = mdl.add_parser("show")
    models_args(x)
    x.add_argument("version", nargs="?", help="default: the stable version")
    x.add_argument("--url", help="show a RUNNING server's /models instead")
    x.set_defaults(fn=cmd_models_show)
    x = mdl.add_parser("promote")
    models_args(x)
    x.add_argument("version", nargs="?", help="default: the staged candidate")
    x.add_argument("--url", help="promote on a RUNNING server (lanes swap live)")
    x.set_defaults(fn=cmd_models_promote)
    x = mdl.add_parser("rollback")
    models_args(x)
    x.add_argument("--url", help="roll back on a RUNNING server")
    x.set_defaults(fn=cmd_models_rollback)
    x = mdl.add_parser("stage")
    models_args(x)
    x.add_argument("version")
    x.add_argument("--url", required=True, help="running server base URL")
    x.add_argument("--mode", choices=["canary", "shadow"], default="canary")
    x.add_argument("--fraction", type=float, default=0.1)
    x.set_defaults(fn=cmd_models_stage)
    x = mdl.add_parser("diff")
    models_args(x)
    x.add_argument("version_a")
    x.add_argument("version_b")
    x.set_defaults(fn=cmd_models_diff)

    # templates
    tpl = sub.add_parser("template").add_subparsers(dest="subcommand", required=True)
    tpl.add_parser("list").set_defaults(fn=cmd_template_list)
    x = tpl.add_parser("get")
    x.add_argument("name")
    x.add_argument("directory", nargs="?")
    x.set_defaults(fn=cmd_template_get)

    # static analysis
    x = sub.add_parser(
        "lint",
        help="TPU-aware static analysis: tracer safety, recompile hazards, "
        "host-sync stalls, concurrency, storage contracts",
    )
    add_lint_arguments(x)
    x.set_defaults(fn=cmd_lint)

    # run
    x = sub.add_parser("run")
    x.add_argument("main")
    x.add_argument("args", nargs="*")
    x.set_defaults(fn=cmd_run)

    return p


def main(argv: list[str] | None = None) -> int:
    from predictionio_tpu.utils.platform import configure_jax

    configure_jax()
    args = build_parser().parse_args(argv)
    # remember the EXACT argv this invocation parsed (None = process argv);
    # the multi-host launcher re-execs it in the workers
    args._invocation_argv = list(argv) if argv is not None else None
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="[%(levelname)s] [%(name)s] %(message)s",
    )
    try:
        return args.fn(args)
    except KeyboardInterrupt:
        return 130
    except Exception as exc:
        if args.verbose:
            raise
        return _die(str(exc))


if __name__ == "__main__":
    sys.exit(main())
